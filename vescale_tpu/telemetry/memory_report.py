"""Memory measurement primitives — device stats, host fallback, live-array
census, AOT-budget drift.

Pure functions only: the stateful half (tag registry, per-step sampling,
leak detection, the OOM flight recorder) lives in memtrack.py.  Everything
here degrades instead of raising — memory observability must never be the
thing that kills a run.

Byte accounting convention: a sharded ``jax.Array``'s ``nbytes`` is the
LOGICAL global size, so census buckets report logical bytes (what the
training code owns), while ``device_memory_stats`` reports physical
per-device HBM (what the allocator sees).  The two agree only on a
single-device run; both are in the flight-recorder bundle on purpose.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax

__all__ = [
    "host_rss_bytes",
    "host_peak_rss_bytes",
    "device_memory_stats",
    "live_array_census",
    "aot_memory_budget",
    "compare_with_aot",
]


def host_rss_bytes() -> Optional[int]:
    """Current resident-set size of this process (Linux /proc; None where
    unavailable) — the degradation target when ``memory_stats()`` has
    nothing (CPU backend, old jax)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        return None


def host_peak_rss_bytes() -> Optional[int]:
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device allocator stats (bytes_in_use / peak / limit).  On
    backends where ``memory_stats()`` returns None or raises (CPU, older
    jax), degrades to ONE host-RSS entry (``source: "host_rss"``) rather
    than zero entries — the gauges must always have something to say."""
    out: List[Dict[str, Any]] = []
    for d in jax.devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append(
            {
                "device": str(d),
                "id": d.id,
                "platform": d.platform,
                "source": "device",
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
        )
    if not out:
        out.append(
            {
                "device": "host",
                "platform": jax.devices()[0].platform,
                "source": "host_rss",
                "bytes_in_use": host_rss_bytes(),
                "peak_bytes_in_use": host_peak_rss_bytes(),
                "bytes_limit": None,
            }
        )
    return out


def live_array_census(
    tag_of: Callable[[Any], Optional[str]], top_k: int = 10
) -> Dict[str, Any]:
    """Bucket ``jax.live_arrays()`` by owner tag.

    ``tag_of(arr)`` maps one live array to its registered tag or None
    (-> ``untagged``).  Returns per-tag ``{count, bytes}`` buckets plus the
    ``top_k`` largest arrays — the first thing to read in an OOM dump."""
    buckets: Dict[str, Dict[str, int]] = {}
    largest: List[Dict[str, Any]] = []
    n = 0
    for arr in jax.live_arrays():
        try:
            if arr.is_deleted():
                continue
            nbytes = int(arr.nbytes)
            shape, dtype = tuple(arr.shape), str(arr.dtype)
        except Exception:
            continue
        n += 1
        tag = tag_of(arr) or "untagged"
        b = buckets.setdefault(tag, {"count": 0, "bytes": 0})
        b["count"] += 1
        b["bytes"] += nbytes
        largest.append({"shape": shape, "dtype": dtype, "bytes": nbytes, "tag": tag})
    largest.sort(key=lambda e: e["bytes"], reverse=True)
    return {"live_arrays": n, "tags": buckets, "top_arrays": largest[:top_k]}


# ----------------------------------------------------------- AOT drift
def aot_memory_budget(aot: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Extract the per-device memory budget from an ahead-of-time compile
    report.  Prefers the measured fp32-compile bytes (same basis as a
    fresh CPU/AOT compile of the step); falls back to the bf16-basis total.
    None when the document carries neither."""
    measured = (aot.get("measured") or {}).get("per_device_bytes_fp32_compile")
    if measured:
        return {"bytes": float(measured), "source": "measured.per_device_bytes_fp32_compile"}
    bf16 = (aot.get("bf16_basis_memory") or {}).get("total_bytes")
    if bf16:
        return {"bytes": float(bf16), "source": "bf16_basis_memory.total_bytes"}
    return None


def compare_with_aot(
    report: Dict[str, Any],
    aot: Any,
    tolerance: float = 0.10,
) -> Optional[Dict[str, Any]]:
    """Diff a compiled step report's memory footprint against the matching
    AOT report's budget; ``exceeds_tolerance`` flags drift beyond
    ``tolerance`` (default 10%) in either direction — a regression OR a
    budget that is no longer honest.

    ``aot`` may be a loaded AOT document (dict) or a path to one.  Returns
    None (never raises) when either side lacks a usable byte count."""
    if isinstance(aot, str):
        try:
            with open(aot) as f:
                aot = json.load(f)
        except Exception:
            return None
    if not isinstance(aot, dict):
        return None
    budget = aot_memory_budget(aot)
    measured = report.get("peak_bytes")
    if budget is None or not measured:
        return None
    drift = (float(measured) - budget["bytes"]) / budget["bytes"]
    return {
        "aot_bytes": budget["bytes"],
        "aot_source": budget["source"],
        "measured_bytes": float(measured),
        "drift_frac": drift,
        "tolerance": tolerance,
        "exceeds_tolerance": abs(drift) > tolerance,
        "components": {
            k: report.get(k)
            for k in ("argument_bytes", "output_bytes", "temp_bytes",
                      "alias_bytes", "generated_code_bytes")
        },
    }
