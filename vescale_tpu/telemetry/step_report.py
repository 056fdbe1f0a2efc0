"""Compile-time step reports.

One JSON artifact per compiled program combining the two static views the
stack already half-produces: ``debug/comm_mode`` collective counts and XLA's
cost/memory analysis (``compiled.cost_analysis()`` / ``memory_analysis()``).
Generated ONCE per program (compile-time, not per-step): the report answers
"what does a step cost" — FLOPs, peak HBM, argument/output/temp bytes, and
how many of each collective the partitioner inserted — before any step runs.

The collective counts here and ``debug.comm_mode.comm_counts`` are computed
by the same counter over the same optimized-HLO text, so they agree by
construction on the same program (the acceptance contract the smoke test
asserts).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Optional

import jax

from ..debug.comm_mode import count_collectives

__all__ = ["build_step_report", "write_step_report", "read_step_report"]


def _cost_dict(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()`` as a dict; empty where the backend
    reports none."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(cost) if cost else {}


def build_step_report(
    fn: Callable,
    *args,
    static_argnums=(),
    name: str = "step",
    aot_report=None,
    donate_argnums=None,
    **kwargs,
) -> Dict[str, Any]:
    """Lower+compile ``fn(*args, **kwargs)`` (or reuse ``fn.lower`` when fn
    is already jitted — e.g. the step from ``make_train_step``) and distill
    the compiled program into a JSON-serializable report.

    Keys: ``flops``, ``bytes_accessed``, ``peak_bytes`` (argument + output +
    temp - aliased: the program's HBM high-water mark as XLA accounts it),
    ``argument_bytes``/``output_bytes``/``temp_bytes``/``alias_bytes``/
    ``generated_code_bytes``, and ``collectives`` (the comm_mode counter over
    the optimized HLO).  Fields XLA cannot provide on a backend come back
    None rather than raising — the report must degrade, not fail a run.

    ``aot_report`` (path or loaded ahead-of-time compile report): attaches an
    ``aot_drift`` section diffing the measured memory footprint against the
    AOT budget (memory_report.compare_with_aot; None when either side lacks
    a usable byte count).

    ``donate_argnums``: the donation the jit of ``fn`` uses — forwarded to
    the shardcheck section so donated steps are not falsely flagged VSC105;
    None (default) skips the donation check."""
    if hasattr(fn, "lower"):
        lowered = fn.lower(*args, **kwargs)
    else:
        lowered = jax.jit(fn, static_argnums=static_argnums).lower(*args, **kwargs)
    report: Dict[str, Any] = {
        "name": name,
        "platform": jax.devices()[0].platform,
        "num_devices": len(jax.devices()),
    }
    try:
        compiled = lowered.compile()
    except Exception as e:  # unpartitionable/abstract program: static views only
        report.update(
            flops=None,
            bytes_accessed=None,
            peak_bytes=None,
            collectives=count_collectives(lowered.as_text()),
            compile_error=repr(e),
        )
        return report
    cost = _cost_dict(compiled)
    report["flops"] = float(cost["flops"]) if "flops" in cost else None
    report["bytes_accessed"] = (
        float(cost["bytes accessed"]) if "bytes accessed" in cost else None
    )
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("alias_bytes", "alias_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    ):
        report[key] = getattr(mem, attr, None) if mem is not None else None
    peak = getattr(mem, "peak_memory_in_bytes", None) if mem is not None else None
    if peak is None and mem is not None:
        parts = [report["argument_bytes"], report["output_bytes"], report["temp_bytes"]]
        if all(p is not None for p in parts):
            peak = sum(parts) - (report["alias_bytes"] or 0)
    report["peak_bytes"] = peak
    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    report["collectives"] = count_collectives(text)
    try:
        from .costaudit import layer_attribution

        # per-layer roofline attribution over the same optimized HLO the
        # collective counter reads: FLOPs/bytes per op_name scope,
        # compute- vs memory-bound against the device roofline
        report["layer_attribution"] = layer_attribution(text)
    except Exception as e:  # degrade, never fail a run for observability
        report["layer_attribution"] = {"error": repr(e)}
    if aot_report is not None:
        from .memory_report import compare_with_aot

        report["aot_drift"] = compare_with_aot(report, aot_report)
    _attach_shardcheck(report, fn, args, kwargs, name, donate_argnums, static_argnums)
    return report


def _attach_shardcheck(report, fn, args, kwargs, name, donate_argnums,
                       static_argnums=()) -> None:
    """Static placement findings for the SAME program the report describes
    (analysis/shardcheck.py), keyed ``shardcheck`` — input shardings read
    off the argument arrays' own NamedShardings.  Gated by
    ``VESCALE_SHARDCHECK`` (off -> no section); never fails the report.
    ``donate_argnums``: forwarded from the caller; ``None`` (the default —
    the report builder cannot see what the caller's jit donates) skips the
    VSC105 donation check rather than falsely flagging donated steps."""
    from .. import analysis

    if not analysis.enabled():
        return
    try:
        findings = analysis.shardcheck(
            fn, *args, name=name, check_source=False,
            donate_argnums=donate_argnums, static_argnums=static_argnums,
            **kwargs
        )
        report["shardcheck"] = findings.to_dict()
    except Exception as e:  # degrade, never fail a run for observability
        report["shardcheck"] = {"error": repr(e)}


def write_step_report(report: Dict[str, Any], path: str) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, default=str)
    return path


def read_step_report(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)
