"""vescale_tpu.kernels — the Pallas kernel layer behind ONE dispatch contract.

Every hand-written TPU kernel in the framework lives in this package and is
reached through the same knob (``VESCALE_KERNELS``, registered in
``analysis.envreg``).  Unset, each kernel takes its own default:
``paged_decode``, ``paged_decode_latent``, ``ssm_step``, ``selective_scan``, ``grouped_experts``, ``head_select``, ``kda_step`` and
``kda_chunk`` are the compiled
kernels on TPU and the XLA leg on every other backend (what the platform is,
the code can see; PERF.md, PR 27, PR 29, PR 46, PR 47, PR 61 and PR 63); the other three stay
``off``.  Set, it means the same for all eleven:

  ``off``        the kernels are never consulted — every caller takes
                 exactly the XLA path it took before this package
                 existed, byte-identical (asserted by tests/test_kernels.py).
  ``interpret``  the Pallas kernels run through the pallas INTERPRETER on
                 any backend — slow, but it executes the real kernel code
                 path, so CPU tier-1 exercises the same program a TPU would
                 compile and parity against the XLA reference is checkable
                 bit-for-bit (or to the documented ulp bound where fp32
                 accumulation order differs — docs/kernels.md).
  ``on``         compiled Pallas kernels on TPU; off-TPU this degrades to
                 the XLA path (counted as a fallback) rather than crawling
                 through the interpreter.

Kernels in this package:

  * ``flash_attention``  — online-softmax fused attention (forward +
    backward); dispatched by ``ops/flash_attention.py``.
  * ``paged_decode``     — PagedAttention-style serve decode: one kernel a
    layer over the whole 5-D ``PagedKVCache`` pool (left in HBM), which
    fetches only the pages each slot holds (per-page DMAs through the
    scalar-prefetched page table, double-buffered blocks of pages) and
    runs an online fp32 softmax over them — instead of the slice →
    gather → masked-softmax → matmul chain over all ``Tmax`` positions
    (its XLA leg); for ``serve/engine.py`` and every model of pages under
    ``serve/hybrid_engine.py``, the default decode path on TPU.
  * ``paged_decode_latent`` — its sibling over a LATENT pool (multi-head
    latent attention's absorbed decode): one row a position serves every
    head's score and, by its leading columns, the values, so a page is
    fetched once for both; for ``models/deepseek_v2.py`` under
    ``serve/hybrid_engine.py``, the default on TPU.
  * ``paged_decode_folded`` — its sibling over FOLDED pools (keys of one
    width beside values of another, neither a whole number of lane tiles a
    head: a position's row is every key head's entries side by side), with
    an optional sink logit a head in the softmax; for
    ``models/mimo_v2.py`` under ``serve/hybrid_engine.py``, dispatched
    under ``paged_decode``'s name, the default on TPU.
  * ``ssm_step``         — a state-space (Mamba-2) layer's decode step
    over every slot's recurrent state, in place: one read and one write of
    the state where XLA reads it twice (its XLA leg); for
    ``models/mamba2.py`` under ``serve/hybrid_engine.py``, the default on TPU;
    ``ssm_step_selective`` beside it is the Mamba-1 form (a decay that is one
    value a state row and lane, formed in VMEM from ``dt`` and ``A``), for
    ``models/phi4flash.py``, under the same name in the dispatch.
  * ``selective_scan``   — a Mamba-1 layer's recurrence over a prompt
    (``kernels/selective_scan.py``): the state stays in VMEM while the kernel
    walks the positions, where XLA's loop carries it through HBM (its XLA
    leg); for ``models/phi4flash.py``'s prefill, the default on TPU.
  * ``kda_step`` / ``kda_chunk`` — delta-rule linear attention
    (``kernels/kda.py``), whose state a head is a float32 MATRIX that a
    position decays a row at a time and corrects by a rank-1 term: the decode
    step over every slot's state in place (one read, one write), and a
    prompt's positions in chunks of 128 with the state in VMEM (the chunk's
    triangular system inverted by products the MXU runs, every decay taken
    forward in time or inside 16 positions: the gate's lower bound); for
    ``models/kda.py`` under ``serve/hybrid_engine.py``, the defaults on TPU.
  * ``grouped_experts``  — the sorted form of a dropless expert layer
    (``kernels/grouped_swiglu.py``): one grid over row tiles of the (token,
    expert) pairs in expert order; a tile's expert, a scalar-prefetch operand,
    picks the three SwiGLU weight blocks, which stay in VMEM across that
    expert's tiles, and the hidden never leaves VMEM — instead of three
    ``jax.lax.ragged_dot`` with the hidden between them in HBM; dispatched by
    ``moe/dropless.py``, the default on TPU.
  * ``head_select``      — from the head's product to what a block
    diffusion pass selects by (``kernels/head_select.py``): a row's largest
    logit, its id and the softmax denominator, reduced tile by tile of the
    vocabulary as the weights stream past once, so that no logits are
    written (its XLA leg writes them); for ``models/sdar_moe.py`` under
    ``serve/hybrid_engine.py``, the default on TPU.
  * ``fused_adamw``      — the adamw_lowmem moment/update elementwise
    chain as one kernel over (g, m, v); dispatched by
    ``parallel/optimizer.py``.
  * ``fused_xent``       — vocab-parallel cross entropy's per-shard
    sumexp + gold-logit pick + Σlogits in ONE pass over the vocab dim
    (full logits still never materialized); dispatched by ``loss.py``.

Contract points:

  * Dispatch decisions are HOST-side and live-read: each call site asks
    :func:`resolve` (or :func:`mode` + the counters) at trace/build time
    (a decode kernel's own ``leg(...)``; its op runs its XLA leg on None).
    A jitted program therefore latches the mode at compile time — flip the
    knob, rebuild/retrace, and the other path compiles.  The serve engine
    documents the same latch (mode read at ``ServeEngine`` build).
  * Telemetry: every dispatch decision increments
    ``kernel_dispatch_<name>_total`` (kernel path taken) or
    ``kernel_fallback_<name>_total`` (kernel requested but the XLA path
    ran: off-TPU ``on``, unsupported shape), plus the
    ``kernel_dispatch_total`` / ``kernel_fallback_total`` aggregates.
    They ride the telemetry registry gate — a run that never calls
    ``telemetry.init()`` pays one dormant-branch check, nothing else —
    and render as the dashboard's ``kernels:`` block.
  * ``vescale-lint`` VSC206 bans direct ``pallas_call`` outside this
    package, so every kernel stays behind this contract.
  * Every custom-partitioned op registers ONE partition rule covering its
    kernel and XLA implementations (one rule per op, not one per
    implementation).
"""

from __future__ import annotations

from typing import Callable, Optional

__all__ = [
    "MODES",
    "mode",
    "resolve",
    "record_dispatch",
    "record_fallback",
    "with_xla_leg",
    "on_tpu",
    "ulps_at_scale",
]

MODES = ("off", "interpret", "on")
# what an unset VESCALE_KERNELS means for these: compiled on TPU, the XLA leg
# elsewhere (every other kernel: off)
DEFAULT_ON_TPU = frozenset({"paged_decode", "paged_decode_latent", "ssm_step", "selective_scan", "grouped_experts",
                            "head_select", "kda_step", "kda_chunk"})


def mode() -> str:
    """The active ``VESCALE_KERNELS`` mode (live env read via envreg)."""
    from ..analysis import envreg

    m = (envreg.get_str("VESCALE_KERNELS") or "off").strip().lower()
    if m not in MODES:
        raise ValueError(
            f"VESCALE_KERNELS={m!r}: expected one of {'|'.join(MODES)} "
            "(see docs/kernels.md)"
        )
    return m


def on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def resolve(name: str, *, supported: Callable[[bool], bool] = lambda interpret: True) -> Optional[bool]:
    """One-stop dispatch decision for kernel ``name``.

    Returns ``None`` when the caller must take its XLA path (mode off,
    ``on`` off-TPU, or a shape the kernel does not take: ``supported``,
    asked with the ``interpret`` flag the kernel would get, says no), else
    the ``interpret=`` flag to pass to the kernel (True under ``interpret``
    mode, False for compiled-on-TPU).
    With ``VESCALE_KERNELS`` unset a kernel in :data:`DEFAULT_ON_TPU`
    resolves as under ``on`` where there is a TPU and as under ``off``
    where there is none (nothing was asked for, so nothing is counted as
    a fallback).  Counts the decision into the kernel telemetry (no-op
    while telemetry is dormant).
    """
    from ..analysis import envreg

    m = mode()
    if envreg.get_str("VESCALE_KERNELS") is None and name in DEFAULT_ON_TPU and on_tpu():
        m = "on"    # unset: this kernel's own default
    if m == "off":
        return None
    interpret = m == "interpret"
    if (m == "on" and not on_tpu()) or not supported(interpret):  # "on" wants compiled kernels; no TPU -> XLA path
        record_fallback(name)
        return None
    record_dispatch(name)
    return interpret


def with_xla_leg(xla: Callable, **jit_kwargs):
    """A decorator for an op's kernel leg ``fn(*args, interpret=<bool>, **kw)``:
    the op it makes also takes ``interpret=None`` and then runs ``xla(*args,
    **kw)``, its XLA leg, chosen in plain Python ABOVE the kernel's
    ``jax.jit(fn, **jit_kwargs)`` (a program's calls, one a layer, are traced
    and lowered once, under the op's name)."""
    import functools
    import jax

    def op(fn):
        kernel = jax.jit(fn, **jit_kwargs)

        @functools.wraps(fn)
        def either_leg(*args, interpret: Optional[bool], **kw):
            return xla(*args, **kw) if interpret is None else kernel(*args, interpret=interpret, **kw)

        return either_leg

    return op


def record_dispatch(name: str) -> None:
    """Count one kernel-path dispatch decision (per call site evaluation:
    once per eager call, once per trace for jitted programs)."""
    from ..telemetry import api as _telemetry

    _telemetry.count("kernel_dispatch_total")
    _telemetry.count(f"kernel_dispatch_{name}_total")


def record_fallback(name: str) -> None:
    """Count one requested-but-declined dispatch (the XLA path ran)."""
    from ..telemetry import api as _telemetry

    _telemetry.count("kernel_fallback_total")
    _telemetry.count(f"kernel_fallback_{name}_total")


def ulps_at_scale(a, b) -> float:
    """THE parity metric of the kernel layer (docs/kernels.md): max
    ``|a - b|`` over the fp32 spacing at the reference ``b``'s max
    magnitude — "off by N representable steps at the tensor's scale", so
    near-zero elements don't inflate the number.  NaN and signed-Inf
    patterns must agree exactly: a kernel that overflows to Inf (or
    drops/creates a NaN) where the reference doesn't returns ``inf``, a
    parity failure, never an excluded element.  One definition, imported
    by scripts/kernels_smoke.py and tests/test_kernels.py, so
    the asserted bound cannot drift between them."""
    import numpy as np

    a64 = np.asarray(a, np.float64).ravel()
    b64 = np.asarray(b, np.float64).ravel()
    if (
        not (np.isnan(a64) == np.isnan(b64)).all()
        or not (np.isposinf(a64) == np.isposinf(b64)).all()
        or not (np.isneginf(a64) == np.isneginf(b64)).all()
    ):
        return float("inf")
    fin = np.isfinite(a64) & np.isfinite(b64)
    if not fin.any():
        return 0.0
    step = float(np.spacing(np.float32(np.max(np.abs(b64[fin])) or 1.0)))
    return float(np.max(np.abs(a64[fin] - b64[fin])) / step)
