"""CommDebugMode — count the collectives a computation performs.

Capability parity with the reference CommDebugMode
(vescale/dtensor/debug/_comm_mode.py:21), which intercepts dispatched
communication ops eagerly.  TPU-native: communication is decided by the XLA
compiler, so the ground truth is the compiled program — we lower the jitted
function and count collective ops in the (stable)HLO.  This catches comms
the eager interceptor can never see (GSPMD-inserted reshards), making it
strictly more faithful on TPU.

Quantized-collective attribution: the int8 gradient collectives
(collectives.all_reduce_q / q_psum and friends) move ONE packed byte
buffer per logical collective, with a fixed wire-dtype convention —
REDUCTION payloads are signed ``s8``, pure MOVEMENT payloads unsigned
``u8``.  ``count_collectives`` keys on that: an ``s8`` all-gather is the
wire form of a quantized logical all-reduce and counts under
``all_reduce`` with an ``all_reduce:int8`` tag (an ``s8`` all-to-all
likewise under ``reduce_scatter``); ``u8`` collectives keep their own
logical op with an ``:int8`` tag.  Step reports therefore stay comparable
across compression settings instead of quantized runs showing phantom
scatter/gather traffic.  (Within this framework only the quantized
collectives put s8/u8 payloads on the wire.)
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

import jax

__all__ = [
    "comm_counts",
    "count_collectives",
    "collective_wire_bytes",
    "CommDebugMode",
]

# HLO/stableHLO opcodes per logical collective.  Async collectives appear
# as op-start/op-done pairs — only the start (or sync form) is counted, so
# each real collective counts once.
_COLLECTIVE_OPCODES = {
    "all_reduce": {"all-reduce", "all-reduce-start", "stablehlo.all_reduce"},
    "all_gather": {"all-gather", "all-gather-start", "stablehlo.all_gather"},
    "reduce_scatter": {"reduce-scatter", "stablehlo.reduce_scatter"},
    "all_to_all": {"all-to-all", "stablehlo.all_to_all"},
    "collective_permute": {
        "collective-permute",
        "collective-permute-start",
        "stablehlo.collective_permute",
    },
}
# applied opcodes are bare lowercase tokens immediately before '(' — operand
# references carry a '%' prefix and never precede '(' directly.  stableHLO
# additionally quotes the opcode: `"stablehlo.all_gather"(...)`.
_OPCODE_RE = re.compile(r'(?<![%\w.])"?([a-z][a-z0-9\-\._]*)"?\(')
# the instruction's RESULT dtype: first type token after '=' (HLO spelling)
_RESULT_DTYPE_RE = re.compile(r"=\s*\(?\s*([a-z][a-z0-9]*)\[")

# wire-dtype convention -> logical-op remap (module docstring)
_S8_LOGICAL = {"all_gather": "all_reduce", "all_to_all": "reduce_scatter"}


def _line_wire_dtype(line: str) -> Optional[str]:
    """'int8' when the collective's payload rides the quantized wire
    convention (s8 = packed reduction, u8 = packed movement), else None."""
    m = _RESULT_DTYPE_RE.search(line)
    if m and m.group(1) in ("s8", "u8"):
        return m.group(1)
    if "stablehlo" in line:  # stablehlo spelling: tensor<...xi8> / xui8>
        if "xui8>" in line:
            return "u8"
        if "xi8>" in line:
            return "s8"
    return None


def count_collectives(text: str) -> Dict[str, int]:
    """Count collective ops in (stable)HLO text — the shared counter behind
    ``comm_counts`` and the telemetry step reports, so the two views agree
    by construction on the same program.

    Quantized collectives (s8/u8 payloads, module docstring) count under
    their LOGICAL op plus a ``<op>:int8`` tag key; tag keys are extra
    detail and excluded from ``total`` (their instructions are already
    counted once under the logical op)."""
    out = {name: 0 for name in _COLLECTIVE_OPCODES}
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("//") or "=" not in line:
            continue
        for opcode in _OPCODE_RE.findall(line):
            matched = False
            for name, ops in _COLLECTIVE_OPCODES.items():
                if opcode in ops:
                    wire = _line_wire_dtype(line)
                    if wire is not None:
                        logical = _S8_LOGICAL.get(name, name) if wire == "s8" else name
                        out[logical] = out.get(logical, 0) + 1
                        tag = f"{logical}:int8"
                        out[tag] = out.get(tag, 0) + 1
                    else:
                        out[name] += 1
                    matched = True
                    break
            if matched:
                break  # one collective application per instruction line
    out["total"] = sum(v for k, v in out.items() if k != "total" and ":" not in k)
    return out


# ------------------------------------------------------- wire-byte model
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# stableHLO spelling: tensor<4x128xf32> / tensor<i8> (scalar)
_STABLEHLO_SHAPE_RE = re.compile(r"tensor<((?:[0-9]+x)*)(u?[a-z][a-z0-9]*)>")
_STABLEHLO_DTYPES = {
    "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8, "f64": 8,
}
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[\s*\d+\s*,\s*(\d+)\s*\]<=")
# stableHLO: replica_groups = dense<[[0, 1]]> : tensor<1x2xi64>
_GROUPS_SHLO_RE = re.compile(r"replica_groups\s*=\s*dense<\[?\[([0-9, ]+)\]")


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return max(1, int(m.group(1)))
    m = _GROUPS_V1_RE.search(line) or _GROUPS_SHLO_RE.search(line)
    if m:
        ids = [t for t in m.group(1).replace(" ", "").split(",") if t]
        return max(1, len(ids))
    return default


def _result_bytes(line: str, op_pos: int) -> int:
    """Sum of the instruction's RESULT buffer bytes — HLO spelling
    (``f32[4,128]``, the segment between '=' and the opcode) or stableHLO
    (``tensor<4x128xf32>``, searched over the whole line since stableHLO
    puts result types at the end).  Tuples sum their element buffers."""
    seg = line[line.index("=") + 1 : op_pos]
    total = 0
    for dtype, dims in _SHAPE_RE.findall(seg):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _stablehlo_result_bytes(lines, i: int) -> int:
    """Result bytes of the stableHLO op starting at ``lines[i]``: the type
    signature's ``-> tensor<...>`` may sit lines below (region-bearing ops
    like ``stablehlo.all_reduce`` close with ``}) : (...) -> tensor<...>``);
    scanning for the arrow also skips attribute tensors (replica_groups'
    ``dense<...> : tensor<NxMxi64>``), which are not results."""
    for j in range(i, min(i + 200, len(lines))):
        if "->" not in lines[j]:
            continue
        seg = lines[j].rsplit("->", 1)[1]
        total = 0
        for dims, dtype in _STABLEHLO_SHAPE_RE.findall(seg):
            if dtype not in _STABLEHLO_DTYPES:
                continue
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            total += n * _STABLEHLO_DTYPES[dtype]
        return total
    return 0


def collective_wire_bytes(text: str, default_group: int = 1) -> Dict[str, float]:
    """Per-device bytes-on-the-wire estimate from compiled HLO, using the
    standard ring algorithmic volumes per collective (result buffer R,
    group size n): all-reduce ``2(n-1)/n * R``, all-gather ``(n-1)/n * R``,
    reduce-scatter ``(n-1) * R`` (its input is ``n*R``), all-to-all
    ``(n-1)/n * R``, collective-permute ``R``.  This is the measurement
    surface of scripts/quantcomm_smoke.py: the payload DTYPE comes from the
    program, so an int8-compressed reduction shows its real packed bytes.
    Keys: logical op (quantized ops remapped per the wire convention) plus
    ``<op>:int8`` tags; ``total`` sums the logical keys only."""
    out: Dict[str, float] = {name: 0.0 for name in _COLLECTIVE_OPCODES}
    lines = [l.strip() for l in text.splitlines()]
    for i, line in enumerate(lines):
        if line.startswith("//") or "=" not in line:
            continue
        for m in _OPCODE_RE.finditer(line):
            opcode = m.group(1)
            name = next(
                (nm for nm, ops in _COLLECTIVE_OPCODES.items() if opcode in ops), None
            )
            if name is None:
                continue
            n = _group_size(line, default_group)
            r = _result_bytes(line, m.start())
            if r == 0 and "stablehlo" in line:
                r = _stablehlo_result_bytes(lines, i)
            f = (n - 1) / max(1, n)
            if name == "all_reduce":
                b = 2.0 * f * r
            elif name == "reduce_scatter":
                b = (n - 1) * r
            elif name == "collective_permute":
                b = float(r)
            else:  # all_gather / all_to_all
                b = f * r
            wire = _line_wire_dtype(line)
            if wire is not None:
                logical = _S8_LOGICAL.get(name, name) if wire == "s8" else name
                out[logical] = out.get(logical, 0.0) + b
                tag = f"{logical}:int8"
                out[tag] = out.get(tag, 0.0) + b
            else:
                out[name] += b
            break  # one collective application per instruction line
    out["total"] = sum(v for k, v in out.items() if k != "total" and ":" not in k)
    return out


def comm_counts(fn: Callable, *args, static_argnums=(), **kwargs) -> Dict[str, int]:
    """Compile ``fn(*args, **kwargs)`` and count collectives in the
    optimized HLO (after GSPMD partitioning)."""
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args, **kwargs)
    try:
        text = lowered.compile().as_text()
    except Exception:
        text = lowered.as_text()
    return count_collectives(text)


class CommDebugMode:
    """Context-flavored API for migration parity:

        with CommDebugMode() as comm:
            out = comm.trace(fn, *args)
        comm.get_comm_counts()
    """

    def __init__(self):
        self.counts: Dict[str, int] = {}
        self.plan_attribution: Dict[str, Any] = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def trace(self, fn: Callable, *args, **kwargs):
        """Count collectives AND execute — compiling ONCE: the lowered
        program is compiled to an executable that serves both the optimized
        HLO text (counting) and the actual run (previously this compiled
        twice: ``comm_counts``' throwaway ``lowered.compile()`` plus a fresh
        ``jax.jit(fn)(*args)``)."""
        lowered = jax.jit(fn).lower(*args, **kwargs)
        try:
            compiled = lowered.compile()
        except Exception:
            # unpartitionable on this backend: count from the unoptimized
            # text and fall back to the normal jit path for execution
            self.counts = count_collectives(lowered.as_text())
            return jax.jit(fn)(*args, **kwargs)
        self.counts = count_collectives(compiled.as_text())
        return compiled(*args, **kwargs)

    def get_comm_counts(self) -> Dict[str, int]:
        return dict(self.counts)

    def get_total_counts(self) -> int:
        return self.counts.get("total", 0)

    def attribute_plan(self, plan, compiled: bool = False) -> Dict[str, Any]:
        """Attribute collectives to the hops of a multi-hop redistribution
        plan (redistribute_plan.RedistributePlan).

        The static view comes from ``plan_comm_summary`` — the SAME
        accounting that feeds the telemetry ``redistribute.bytes_moved``
        gauge, so the two surfaces agree by construction.  With
        ``compiled=True`` each kernel hop is additionally lowered and its
        optimized HLO counted through ``count_collectives`` (the shared
        counter), attached per hop as ``hlo_collectives`` — ground truth
        for what XLA actually emits on this backend."""
        from ..redistribute_plan import plan_comm_summary

        summary = plan_comm_summary(plan)
        if compiled:
            for hop, rec in zip(plan.hops, summary["hops"]):
                if hop.fn is None or not hasattr(hop.fn, "lower"):
                    continue  # reshard/device_put: runtime-chosen pattern
                arg = jax.ShapeDtypeStruct(
                    hop.src.layout().physical_shape, hop.src.dtype
                )
                lowered = hop.fn.lower(arg)
                try:
                    text = lowered.compile().as_text()
                except Exception:
                    text = lowered.as_text()
                rec["hlo_collectives"] = count_collectives(text)
        self.plan_attribution = summary
        return summary
