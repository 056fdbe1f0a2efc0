"""Graph-level automatic pipeline splitting.

Capability parity with the reference's fx-based PipeParser
(legacy/vescale/pipe/pipe_parser.py:46, tracer.py:81,93): split an
*arbitrary* model — not just one already structured as a list of blocks —
into balanced pipeline stages.

TPU-native mechanism: where the reference traces ``nn.Module``s into a
torch.fx graph and partitions the node list, here the model function is
traced into a **jaxpr** (``jax.make_jaxpr``), its topologically-ordered
equation list is cut into contiguous ranges balanced by a FLOP cost model
(dot_general/conv dominate, matching the reference's param-count balancing
but measuring compute directly), and each range is replayed by a small
jaxpr interpreter.  Values produced before a cut and consumed after it
become the carried activation tuple — residual streams, tied embeddings and
multi-tensor carries all fall out of the dataflow instead of needing the
reference's send/recv shape handshake.

``GraphPipeModule`` exposes the same surface as ``PipeModule``
(``group_forward`` / ``group_index`` / ``sync_shared_params_grads``), so the
eager ``PipeEngine`` and every schedule (1F1B, interleaved, zero-bubble)
run unmodified on auto-split graphs.

The traced function must be deterministic (no rng argument): trace-time
splitting sees one static graph, same as the reference tracer.  Stages are
shape-specialized (XLA static shapes), so ``x_example`` must be shaped like
one *microbatch* when the module is driven by ``PipeEngine`` — where the
reference's fx modules stay shape-polymorphic, the TPU analog re-traces per
shape, and the engine always feeds microbatches of one shape.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal

from ..plan import PipelineParallelPlan
from .pipe_stage import _cuts_by_weight

__all__ = ["GraphPipeModule", "split_graph", "jaxpr_flops"]


# ------------------------------------------------------------- cost model
def _eqn_flops(eqn) -> float:
    """FLOP estimate for one equation.  dot_general gets exact MAC math;
    conv gets the dense im2col equivalent; everything else counts output
    elements (so long elementwise chains still carry a little weight)."""
    if eqn.primitive.name == "dot_general":
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        batch = 1
        for d in lb:
            batch *= lhs.shape[d]
        k = 1
        for d in lc:
            k *= lhs.shape[d]
        m = 1
        for i, s in enumerate(lhs.shape):
            if i not in lc and i not in lb:
                m *= s
        n = 1
        for i, s in enumerate(rhs.shape):
            if i not in rc and i not in rb:
                n *= s
        return 2.0 * batch * m * n * k
    if eqn.primitive.name.startswith("conv"):
        out = eqn.outvars[0].aval
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        k = 1
        for s in rhs.shape[1:]:
            k *= s
        return 2.0 * out.size * k
    total = 0.0
    for ov in eqn.outvars:
        total += getattr(ov.aval, "size", 0)
    return total


def jaxpr_flops(jaxpr) -> float:
    """Total FLOPs of a (closed) jaxpr under the same cost model, recursing
    into call/sub-jaxprs (pjit, remat, custom_vjp, scan, cond branches)."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in j.eqns:
        subs = []
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                subs.append(v)
            elif isinstance(v, (tuple, list)):
                subs.extend(x for x in v if hasattr(x, "eqns") or hasattr(x, "jaxpr"))
        if subs:
            mult = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
            total += mult * sum(jaxpr_flops(s) for s in subs)
        else:
            total += _eqn_flops(eqn)
    return total


def _eqn_invars(eqn):
    return [v for v in eqn.invars if not isinstance(v, Literal)]


def _run_eqns(eqns, env: Dict[Any, Any]) -> None:
    """Interpret a contiguous eqn range in-place over ``env`` (the standard
    eval_jaxpr loop, scoped to a sub-range)."""
    for eqn in eqns:
        invals = [v.val if isinstance(v, Literal) else env[v] for v in eqn.invars]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        if eqn.primitive.multiple_results:
            for ov, a in zip(eqn.outvars, ans):
                env[ov] = a
        else:
            env[eqn.outvars[0]] = ans


class GraphPipeModule:
    """Pipeline groups cut from a traced jaxpr (see module docstring).

    ``params_per_group = module.partition_params(params)`` gives each group
    the param leaves its equations consume (tied params are placed in every
    consuming group and registered as a shared group, mirroring
    ``PipeModule.shared_groups``); ``group_forward(g)`` returns the pure
    ``(group_params, carry) -> carry`` replay function.
    """

    def __init__(self, fn: Callable, params_example, x_example, plan: PipelineParallelPlan):
        self.plan = plan
        self.num_stages = plan.num_stages
        self.virtual_chunks = max(1, plan.virtual_chunks)
        n = self.num_stages * self.virtual_chunks

        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(params_example, x_example)
        jaxpr = closed.jaxpr
        self._consts = dict(zip(jaxpr.constvars, closed.consts))
        self._out_tree = jax.tree_util.tree_structure(out_shape)
        self._outvars = list(jaxpr.outvars)

        # invars = flattened (params, x); recover the param-leaf names
        p_paths = [
            ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(params_example)[0]
        ]
        self._params_treedef = jax.tree_util.tree_structure(params_example)
        n_p = len(p_paths)
        self._param_vars = list(jaxpr.invars[:n_p])
        self._param_names = p_paths
        self._x_vars = list(jaxpr.invars[n_p:])
        self._x_treedef = jax.tree_util.tree_structure(x_example)

        eqns = list(jaxpr.eqns)
        if n > max(1, len(eqns)):
            raise ValueError(f"{n} pipeline groups for a graph of {len(eqns)} equations")
        cuts = _cuts_by_weight([_eqn_flops(e) for e in eqns], n)
        self._bounds = [0] + list(cuts) + [len(eqns)]
        self._eqns = eqns

        # dataflow at each boundary: defs before the cut, uses at/after it
        var_of_param = dict(zip(self._param_vars, self._param_names))
        var_of_name = dict(zip(self._param_names, self._param_vars))
        self._carry_vars: List[List[Any]] = []  # carry INTO group g (g>=1)
        self._group_params: List[List[Tuple[str, Any]]] = []
        use_after: List[set] = [set() for _ in range(n + 1)]
        live = set(v for v in self._outvars if not isinstance(v, Literal))
        for g in range(n, 0, -1):
            lo, hi = self._bounds[g - 1], self._bounds[g]
            use_after[g - 1] = set(live)
            for eqn in eqns[lo:hi]:
                live |= set(_eqn_invars(eqn))
            live -= set(v for e in eqns[lo:hi] for v in e.outvars)
        for g in range(n):
            lo, hi = self._bounds[g], self._bounds[g + 1]
            used = set(v for e in eqns[lo:hi] for v in _eqn_invars(e))
            pnames = sorted({var_of_param[v] for v in used if v in var_of_param})
            self._group_params.append([(nm, var_of_name[nm]) for nm in pnames])
            if g > 0:
                # carry = non-param, non-const values defined earlier and
                # still needed by this group or any later one (incl. outputs)
                need = use_after[g] | used
                carry = [
                    v
                    for v in self._iter_defs_before(lo)
                    if v in need and v not in var_of_param and v not in self._consts
                ]
                self._carry_vars.append(carry)

        # param leaves no equation consumes (config-disabled branches, extra
        # checkpoint heads): park them in group 0 so partition/merge stays a
        # lossless round-trip; vjp gives them zero grads there
        assigned = {nm for plist in self._group_params for nm, _ in plist}
        for nm, var in zip(self._param_names, self._param_vars):
            if nm not in assigned:
                self._group_params[0].append((nm, var))

        # shared (tied) params: used by >1 group
        counts: Dict[str, List[int]] = {}
        for g, plist in enumerate(self._group_params):
            for nm, _ in plist:
                counts.setdefault(nm, []).append(g)
        self.shared_groups: Dict[str, List[Tuple[int, str]]] = {
            nm: [(g, nm) for g in gs] for nm, gs in counts.items() if len(gs) > 1
        }

    # ------------------------------------------------------------ helpers
    def _iter_defs_before(self, lo: int):
        for v in self._x_vars:
            yield v
        for eqn in self._eqns[:lo]:
            for v in eqn.outvars:
                yield v

    @property
    def num_groups(self) -> int:
        return len(self._group_params)

    def group_index(self, stage: int, chunk: int = 0) -> int:
        return chunk * self.num_stages + stage

    def stage_of_group(self, g: int) -> Tuple[int, int]:
        return g % self.num_stages, g // self.num_stages

    def group_param_names(self, g: int) -> List[str]:
        return [nm for nm, _ in self._group_params[g]]

    # ------------------------------------------------------------- params
    def partition_params(self, params) -> List[Dict[str, Any]]:
        """Split a full params tree into per-group {name: leaf} dicts (tied
        leaves are copied into every consuming group)."""
        leaves = jax.tree_util.tree_leaves(params)
        by_name = dict(zip(self._param_names, leaves))
        return [{nm: by_name[nm] for nm, _ in plist} for plist in self._group_params]

    def merge_params(self, params_per_group) -> Any:
        """Inverse of partition_params (shared leaves: first group wins)."""
        by_name: Dict[str, Any] = {}
        for d in reversed(params_per_group):
            by_name.update(d)
        return jax.tree_util.tree_unflatten(
            self._params_treedef, [by_name[nm] for nm in self._param_names]
        )

    # ------------------------------------------------------------ forward
    def group_forward(self, g: int) -> Callable:
        lo, hi = self._bounds[g], self._bounds[g + 1]
        eqns = self._eqns[lo:hi]
        plist = self._group_params[g]
        last = g == self.num_groups - 1
        carry_in = self._carry_vars[g - 1] if g > 0 else None
        carry_out = self._carry_vars[g] if not last else None

        def bind(env, var, val):
            if tuple(getattr(val, "shape", ())) != tuple(var.aval.shape):
                raise ValueError(
                    f"graph pipeline stages are shape-specialized (XLA static "
                    f"shapes): got {getattr(val, 'shape', None)} for traced "
                    f"{var.aval.shape}.  Trace split_graph with a "
                    f"microbatch-sized x_example."
                )
            env[var] = val

        def fwd(group_params, x):
            env = dict(self._consts)
            for nm, var in plist:
                env[var] = group_params[nm]
            if g == 0:
                for var, leaf in zip(self._x_vars, jax.tree_util.tree_leaves(x)):
                    bind(env, var, leaf)
            else:
                for var, val in zip(carry_in, x):
                    bind(env, var, val)
            _run_eqns(eqns, env)
            if last:
                outs = [v.val if isinstance(v, Literal) else env[v] for v in self._outvars]
                return jax.tree_util.tree_unflatten(self._out_tree, outs)
            return tuple(env[v] for v in carry_out)

        return fwd

    def stage_forward(self, stage: int, chunk: int = 0) -> Callable:
        return self.group_forward(self.group_index(stage, chunk))

    def full_forward(self, params, x):
        """Chain every group (debug / parity checking)."""
        pg = self.partition_params(params)
        y = x
        for g in range(self.num_groups):
            y = self.group_forward(g)(pg[g], y)
        return y

    # ------------------------------------------------------------- shared
    def sync_shared_params_grads(self, grads_per_group):
        """Sum tied-param grads across their groups (PipeModule parity)."""
        for nm, members in self.shared_groups.items():
            total = None
            for g, _ in members:
                gr = grads_per_group[g].get(nm)
                if gr is None:
                    continue
                total = gr if total is None else jax.tree_util.tree_map(jnp.add, total, gr)
            for g, _ in members:
                if nm in grads_per_group[g]:
                    grads_per_group[g][nm] = total
        return grads_per_group


def split_graph(
    fn: Callable,
    params_example,
    x_example,
    plan: PipelineParallelPlan,
) -> GraphPipeModule:
    """Trace ``fn(params, x)`` and cut it into ``num_stages * virtual_chunks``
    FLOP-balanced pipeline groups (reference pipe_parser.py:46 parse +
    construct_pipeline_stage flow, in one step)."""
    return GraphPipeModule(fn, params_example, x_example, plan)
