"""DModule — plan-driven TP/SP parallelization of flax modules.

Capability parity with the reference DModule (legacy/vescale/dmodule/):
  - ``parallelize_module(module, mesh, {"parameter": ..., "forward": ...})``
    <- dmodule/api.py:33
  - FQN-regex param plans -> param shardings  <- _dmodule.py:133,217
  - forward input/output resharding at module boundaries <- _hook.py:76-259
  - deferred init / materialize only the local shard <- initialize/deferred_init.py

TPU-native design: instead of per-module pre/post hooks issuing NCCL calls,
the plan lowers to

  * ``NamedSharding`` for every parameter (applied at init via jit
    ``out_shardings`` — parameters materialize *already sharded*, the
    deferred-init story, with no torchdistX patch), and
  * ``jax.lax.with_sharding_constraint`` at module boundaries via a flax
    method interceptor (the forward plan).  XLA inserts the collectives the
    reference's hooks performed (all-gather at TP boundaries, the SP
    Shard(seq) <-> Replicate transitions, grad psum in backward — the
    _grad_sync.py machinery is implicit in GSPMD's reverse-mode).

The sharding-plan *format* mirrors the reference examples
(e.g. legacy/examples/nanogpt_4D_finetune/sharding_plan.py): regex FQNs ->
placements.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

import flax.linen as nn

from ..mesh import DeviceMesh
from ..placements import Placement, Replicate, Shard, normalize_placements
from ..spec import DArraySpec, TensorMeta

__all__ = ["parallelize_module", "DModule", "PlacementsInterface", "pspec_of", "keypath_fqn"]


def keypath_fqn(keypath) -> str:
    """Dotted FQN for a jax tree keypath (DictKey/SequenceKey/etc.)."""
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


def pspec_of(placements, ndim: int, mesh: DeviceMesh) -> PartitionSpec:
    """Lower placements to a logical PartitionSpec (Partial -> no constraint
    on that mesh dim; XLA tracks partial sums itself)."""
    placements = normalize_placements(placements, mesh.ndim, ndim)
    names: List[List[str]] = [[] for _ in range(ndim)]
    for i, p in enumerate(placements):
        if type(p) is Shard:
            names[p.dim].append(mesh.dim_name(i))
    return PartitionSpec(*(None if not ns else (ns[0] if len(ns) == 1 else tuple(ns)) for ns in names))


@dataclasses.dataclass
class PlacementsInterface:
    """Input/output resharding hints for one module
    (reference dmodule/placements_interface.py)."""

    input: Optional[Sequence] = None   # per positional arg: placements | None
    output: Optional[Sequence] = None  # per output leaf: placements | None

    @classmethod
    def normalize(cls, v) -> "PlacementsInterface":
        if isinstance(v, PlacementsInterface):
            return v
        if isinstance(v, dict):
            return cls(input=v.get("input"), output=v.get("output"))
        # bare list == input placements
        return cls(input=v)


def _match(plan: Dict[str, Any], fqn: str) -> Tuple[Optional[str], Any]:
    """(pattern, value) of the first plan entry fullmatching ``fqn``."""
    for pattern, v in plan.items():
        if re.fullmatch(pattern, fqn):
            return pattern, v
    return None, None


def _constrain(x, placements, mesh: DeviceMesh):
    if placements is None or not isinstance(x, (jax.Array, jnp.ndarray)) or np.isscalar(x):
        return x
    spec = pspec_of(placements, x.ndim, mesh)
    # Inside a mesh context whose axis types differ from the plan's mesh
    # (e.g. the compiled pipeline's shard_map with a Manual pp axis), a
    # concrete NamedSharding would not match the context mesh — constrain
    # with the bare PartitionSpec so jax resolves it against the context,
    # dropping axes that are manual there (they're already local).
    ctx = jax.sharding.get_abstract_mesh()
    if ctx.shape_tuple:  # non-empty context mesh
        manual = {
            n
            for n, t in zip(ctx.axis_names, ctx.axis_types)
            if t == jax.sharding.AxisType.Manual
        }
        def drop_manual(entry):
            if entry is None:
                return None
            if isinstance(entry, tuple):
                kept = tuple(n for n in entry if n not in manual)
                return kept if kept else None
            return None if entry in manual else entry
        spec = PartitionSpec(*(drop_manual(e) for e in spec))
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh.jax_mesh, spec))


def _constrain_entry(entry, placements, mesh: DeviceMesh):
    """Constrain every array leaf of one top-level entry (an arg, kwarg or
    output element — possibly itself a pytree) with the same placements."""
    if placements is None:
        return entry
    return jax.tree_util.tree_map(lambda leaf: _constrain(leaf, placements, mesh), entry)


def _align_placements(placements_list, n: int):
    pl = list(placements_list)
    if len(pl) == 1 and n > 1:
        pl = pl * n
    return pl + [None] * (n - len(pl))


def _constrain_inputs(args, kwargs, placements_list, mesh: DeviceMesh):
    """Reshard the FULL input tree — positional and keyword args alike
    (reference _hook.py:76 PreHookInput).  Placement entries align with the
    top-level entries in order (args, then kwargs in call order); each entry
    constrains all array leaves of that argument's subtree; a single entry
    broadcasts to every argument."""
    if placements_list is None:
        return args, kwargs
    entries = list(args) + list(kwargs.values())
    pl = _align_placements(placements_list, len(entries))
    out = [_constrain_entry(e, p, mesh) for e, p in zip(entries, pl)]
    return tuple(out[: len(args)]), dict(zip(kwargs.keys(), out[len(args):]))


def _constrain_tree(tree, placements_list, mesh: DeviceMesh):
    """Output resharding: placements align with the top-level elements of a
    tuple/list output (a single non-sequence output is one entry)."""
    if placements_list is None:
        return tree
    entries = list(tree) if isinstance(tree, (tuple, list)) else [tree]
    pl = _align_placements(placements_list, len(entries))
    out = [_constrain_entry(e, p, mesh) for e, p in zip(entries, pl)]
    if isinstance(tree, tuple):
        return tuple(out)
    if isinstance(tree, list):
        return out
    return out[0]


class DModule:
    """A flax module bound to a mesh + sharding plan.

    Usage (mirrors reference dmodule/api.py:33):

        dmodel = parallelize_module(model, mesh, {"parameter": PARAM_PLAN,
                                                  "forward": FWD_PLAN})
        variables = dmodel.init(key, x)        # params born sharded
        out = dmodel.apply(variables, x)       # boundary resharding applied
    """

    def __init__(
        self,
        module: nn.Module,
        device_mesh: DeviceMesh,
        sharding_plan: Dict[str, Any],
        validate_plan: bool = True,
    ):
        self.module = module
        self.mesh = device_mesh
        self.validate_plan = validate_plan
        plan = sharding_plan or {}
        self.param_plan: Dict[str, Any] = dict(plan.get("parameter", {}))
        self.fwd_plan: Dict[str, PlacementsInterface] = {
            k: PlacementsInterface.normalize(v) for k, v in dict(plan.get("forward", {})).items()
        }
        self.default_input_placements = plan.get("default_input", None)
        self._fwd_matched: set = set()
        self._param_matched: set = set()
        self._warned_fwd = False
        # static plan validation (analysis/shardcheck.py VSC107): Partial
        # params, un-normalizable entries.  Mode-gated (VESCALE_SHARDCHECK):
        # warn surfaces one aggregated warning, strict raises before any
        # parameter is materialized wrong
        if validate_plan and self.param_plan:
            from .. import analysis as _analysis

            if _analysis.enabled():
                _analysis.dispatch_report(
                    _analysis.check_param_plan(
                        self.param_plan, device_mesh, name="dmodule parameter plan"
                    ),
                    stacklevel=3,
                )

    # --------------------------------------------------------- param plans
    def param_placements(self, path: str, ndim: int) -> Tuple[Placement, ...]:
        pattern, v = _match(self.param_plan, path)
        if pattern is not None:
            self._param_matched.add(pattern)
        return normalize_placements(v, self.mesh.ndim, ndim)

    def _warn_unmatched(self, plan: Dict[str, Any], matched: set, kind: str) -> None:
        import warnings

        unmatched = [p for p in plan if p not in matched and p != r".*"]
        if unmatched and self.validate_plan:
            warnings.warn(
                f"{kind} plan patterns matched nothing: {unmatched} — "
                "typo'd FQN regexes silently leave params/activations "
                "unconstrained (reference plans are validated the same way)",
                stacklevel=3,
            )

    def _warn_unmatched_fwd_once(self) -> None:
        if self._warned_fwd or not self.fwd_plan:
            return
        self._warned_fwd = True
        # method-scoped entries ("fqn:method") often bind paths the first
        # apply never takes (e.g. decode-only attend) — exclude them
        call_plan = {p: v for p, v in self.fwd_plan.items() if ":" not in p}
        self._warn_unmatched(call_plan, self._fwd_matched, "forward")

    def _path_str(self, keypath) -> str:
        # drop the leading collection name ("params")
        return keypath_fqn(keypath[1:] if len(keypath) > 1 else keypath)

    def variables_shardings(self, abstract_variables):
        """Tree of NamedSharding for a variables pytree (params sharded per
        plan; other collections replicated)."""

        def one(keypath, leaf):
            path = self._path_str(keypath)
            coll = str(keypath[0].key) if hasattr(keypath[0], "key") else ""
            if coll != "params":
                return NamedSharding(self.mesh.jax_mesh, PartitionSpec())
            pl = self.param_placements(path, len(leaf.shape))
            return NamedSharding(self.mesh.jax_mesh, pspec_of(pl, len(leaf.shape), self.mesh))

        return jax.tree_util.tree_map_with_path(one, abstract_variables)

    def param_specs(self, variables):
        """Tree of DArraySpec for the params (used by optimizer/checkpoint)."""

        def one(keypath, leaf):
            path = self._path_str(keypath)
            pl = self.param_placements(path, len(leaf.shape))
            return DArraySpec(self.mesh, pl, TensorMeta(tuple(leaf.shape), leaf.dtype))

        return jax.tree_util.tree_map_with_path(one, variables)

    # ------------------------------------------------------------ init
    def init(self, rngs, *args, **kwargs):
        """Deferred + sharded init: trace init abstractly (eval_shape — the
        torchdistX-free deferred init), compute param shardings from the
        plan, then materialize each shard on its own devices via jit
        out_shardings (reference materialize_dtensor semantics)."""
        abstract = jax.eval_shape(lambda r: self.module.init(r, *args, **kwargs), rngs)
        shardings = self.variables_shardings(abstract)
        if self.param_plan:
            self._warn_unmatched(self.param_plan, self._param_matched, "parameter")
        init_fn = jax.jit(
            lambda r: self.module.init(r, *args, **kwargs), out_shardings=shardings
        )
        return init_fn(rngs)

    # ------------------------------------------------------------ apply
    def _match_fwd(self, fqn: str, method_name: str):
        """Fwd-plan lookup: bare ``fqn`` keys bind ``__call__`` (the
        reference hooks wrap forward); ``fqn:method`` keys bind any other
        intercepted method (e.g. ``emb:attend`` for a tied head)."""
        for pattern, v in self.fwd_plan.items():
            pat_fqn, _, pat_method = pattern.rpartition(":")
            if not pat_fqn:
                pat_fqn, pat_method = pat_method, "__call__"
            if pat_method == method_name and re.fullmatch(pat_fqn, fqn):
                self._fwd_matched.add(pattern)
                return v
        return None

    def _interceptor(self, next_fun, args, kwargs, context):
        fqn = ".".join(context.module.path)
        pi = self._match_fwd(fqn, context.method_name)
        if pi is None:
            return next_fun(*args, **kwargs)
        if pi.input is not None:
            args, kwargs = _constrain_inputs(args, kwargs, pi.input, self.mesh)
        out = next_fun(*args, **kwargs)
        if pi.output is not None:
            out = _constrain_tree(out, pi.output, self.mesh)
        return out

    def apply(self, variables, *args, **kwargs):
        with nn.intercept_methods(self._interceptor):
            out = self.module.apply(variables, *args, **kwargs)
        self._warn_unmatched_fwd_once()
        return out

    def __call__(self, variables, *args, **kwargs):
        return self.apply(variables, *args, **kwargs)


def parallelize_module(
    module: nn.Module,
    device_mesh: DeviceMesh,
    sharding_plan: Optional[Dict[str, Any]] = None,
    validate_plan: bool = True,
) -> DModule:
    """Reference dmodule/api.py:33 — wrap a module with a sharding plan.

    ``validate_plan=False`` silences the matched-nothing warnings — for
    intentionally applying a whole-model plan to one sub-module (e.g. the
    compiled pipeline parallelizes embed/block/head separately)."""
    return DModule(module, device_mesh, sharding_plan or {}, validate_plan=validate_plan)
