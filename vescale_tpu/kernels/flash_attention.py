"""Flash attention — the fused Pallas TPU kernels (forward + backward).

The kernel half of ``ops/flash_attention.py`` (which owns dispatch, the
custom_vjp and the GSPMD partition rule): forward streams K/V blocks
through the MXU with online-softmax accumulation in fp32 and saves the
per-row logsumexp; backward runs the standard flash decomposition as two
kernels (dq over q-blocks; dk/dv over kv-blocks) recomputing probabilities
from the saved LSE — the T x T score matrix never touches HBM in either
direction, so activation memory is O(T * D).

Lives under ``vescale_tpu.kernels`` so the dispatch contract (and lint
rule VSC206) covers it; the entry points here are implementation-only and
assume the caller already decided kernel-vs-XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "_NEG_INF",
    "BLOCK_MASK_NAME",
    "WINDOW_NAME",
    "CAUSAL_NAME",
    "_use_streaming",
    "_flash_fwd_pallas",
    "_flash_bwd_pallas",
    "_flash_fwd_serve",
]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp/where VPU-safe
BLOCK_MASK_NAME = "block_flash_fwd"     # the forward under a block mask, as the device trace names it
WINDOW_NAME = "window_flash_fwd"        # ... and under a sliding window
CAUSAL_NAME = "causal_flash_fwd"        # ... and the causal forward whose values are narrower than its keys, or that has a sink


# ------------------------------------------------------------------ forward
def _visible_to(q_pos, mask_block: int):
    """The last key position a query at ``q_pos`` sees under the causal mask:
    itself, or with ``mask_block`` > 1 (causal over BLOCKS of that many
    positions, full attention inside one: generation by diffusion over blocks)
    the last position of its block."""
    return q_pos if mask_block == 1 else (q_pos // mask_block + 1) * mask_block - 1


def _with_sink(m, l, acc, sink):
    """The softmax's last normalisation with a SINK: one more column of logit
    ``sink`` (a scalar a head) that takes mass and mixes no value, so it joins
    the running maximum and the denominator, once, after the last key block."""
    m_all = jnp.maximum(m, sink)
    shrink = jnp.exp(m - m_all)
    return m_all, l * shrink + jnp.exp(sink - m_all), acc * shrink[:, None]


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k, seq_len, mask_block=1, window=None, sink=False):
    sink_ref, (o_ref, lse_ref) = (rest[0], rest[1:]) if sink else (None, rest)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, D)
    D = v_ref.shape[-1]                       # the values' width (the keys', but for a caller that says otherwise)

    nk_total = seq_len // block_k
    if causal:
        last = (qi * block_q + block_q - 1) // block_k + 1
        nk = jnp.minimum(nk_total, last)
    else:
        nk = nk_total

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, D), jnp.float32)
    # the mask's blocks divide the tiles (the caller checks), so a tile's last row still bounds what its rows see
    q_pos = _visible_to(qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0), mask_block)
    # a sliding window (static, causal; a row sees the ``window`` newest positions, itself among them): the key
    # blocks before the one that holds the tile's first row's oldest visible position are not visited
    first = 0 if window is None else jnp.maximum(qi * block_q - (window - 1), 0) // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            keep = q_pos >= k_pos if window is None else (q_pos >= k_pos) & (q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(first, nk, body, (m0, l0, acc0))
    if sink:
        m, l, acc = _with_sink(m, l, acc, sink_ref[0, 0, 0])
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # (1, block_q, 1) block: trailing singleton satisfies TPU tiling rules
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


# The resident kernels keep whole-(T, D) K/V (or Q/dO) blocks in VMEM —
# fastest when they fit (one HBM fetch amortized over the whole inner loop).
# Where they do not fit the compiler's scoped-VMEM limit, the streaming
# kernels walk the inner loop as a grid dimension with fp32 scratch
# accumulators instead: VMEM O(block), HBM traffic O(T^2/block) on the
# streamed side — the standard large-T flash trade.  The choice is made per
# kernel (fwd, dq, dk/dv hold different blocks) from the bytes its resident
# form holds in VMEM as the compiler lays them out.
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024  # Mosaic's scoped-VMEM limit for one kernel


def _vmem_bytes(rows: int, cols: int, dtype) -> int:
    """VMEM footprint of one (rows, cols) block: the last dim is padded to
    128 lanes and the rows to the dtype's sublane tile (8 x 32 bits), so a
    (T, 1) fp32 block costs as much as a (T, 128) one."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    return -(-rows // sublanes) * sublanes * -(-cols // 128) * 128 * itemsize


# fp32 (block_q, block_k) tiles allowed for what one inner-loop step keeps
# besides its operand blocks (scores, probabilities, dp, ds).  Read off the
# compiler at 512 x 512 blocks: where it refused a kernel whose blocks alone
# fit, its count exceeded them by at most 2.62 MB (fwd), 3.04 MB (dq) and
# nothing (dk/dv, whose whole-T operands it does not all double-buffer).
_WORK_TILES = {"fwd": 3, "dq": 3, "dkv": 2}


def _resident_vmem_bytes(kernel: str, T: int, D: int, dtype, block_q: int, block_k: int,
                         rep: int = 1) -> int:
    """Scoped VMEM the resident form of ``kernel`` ("fwd", "dq" or "dkv")
    needs: every BlockSpec operand double-buffered by the pipeline, plus
    the score tiles of one inner-loop step.  An upper bound — for some
    shapes the compiler gets by with less."""
    row = lambda n, dt=dtype: _vmem_bytes(n, D, dt)
    stat = lambda n: _vmem_bytes(n, 1, jnp.float32)  # lse / delta column
    if kernel == "fwd":    # q, o blocks; k, v whole; lse out
        blocks = 2 * row(block_q) + 2 * row(T) + stat(block_q)
    elif kernel == "dq":   # q, do, dq blocks; k, v whole; lse, delta
        blocks = 3 * row(block_q) + 2 * row(T) + 2 * stat(block_q)
    else:                  # dkv: q, do, lse, delta whole; k, v, dk, dv blocks
        acc = dtype if rep == 1 else jnp.float32
        blocks = 2 * row(T) + 2 * stat(T) + 2 * row(block_k) + 2 * row(block_k, acc)
    return 2 * blocks + _WORK_TILES[kernel] * _vmem_bytes(block_q, block_k, jnp.float32)


def _use_streaming(kernel: str, T: int, D: int, dtype, block_q: int, block_k: int,
                   rep: int = 1) -> bool:
    return _resident_vmem_bytes(kernel, T, D, dtype, block_q, block_k, rep) > _VMEM_LIMIT_BYTES


def _fwd_kernel_stream(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k, seq_len, mask_block=1, window=None,
                       sink=False):
    """Streaming forward: grid (BH, nq, nk) — k/v arrive one block per grid
    step; online-softmax state lives in VMEM scratch across the nk steps."""
    sink_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = (rest[0], rest[1:]) if sink else (None, rest)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = seq_len // block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = _visible_to(qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0), mask_block)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            keep = q_pos >= k_pos if window is None else (q_pos >= k_pos) & (q_pos - k_pos < window)
            s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[:, 0] = m_new

    if causal:
        # blocks fully above the diagonal contribute nothing; skip compute
        # (the DMA for the block still happens — data-independent grid); under a window so do the
        # blocks wholly older than the tile's first row's oldest visible position
        live = j * block_k <= qi * block_q + block_q - 1
        if window is not None:
            live = jnp.logical_and(live, j * block_k + block_k - 1 >= qi * block_q - (window - 1))
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _final():
        if sink:
            m, l, acc = _with_sink(m_scr[:, 0], l_scr[:, 0], acc_scr[...], sink_ref[0, 0, 0])
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[0] = (m + jnp.log(l_safe))[:, None]
            return
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, 0] + jnp.log(l_safe))[:, None]


def _flash_fwd_pallas(q3, k3, v3, scale, causal, block_q, block_k, interpret, H, KV,
                      streaming=None, mask_block=1, window=None, sink=None):
    """q3: (B*H, T, D); k3/v3: (B*KV, T, D) — GQA never materializes the
    repeated K/V heads; the BlockSpec index map routes each q head to its
    kv group (rows are consecutive per group, llama repeat convention).
    ``mask_block`` > 1 (with ``causal``) is the mask of generation by diffusion
    over blocks: a row sees the keys up to the end of its own block of that
    many positions.  ``window`` (with ``causal``, and no block mask) is a sliding
    window: a row sees the ``window`` newest positions, itself among them, and
    the key loop starts at the block that holds the oldest of them.  Both are
    static parameters: at their defaults the kernels are traced as they were
    before either existed.  FORWARD ONLY (no backward kernel knows them): ``v3``
    may be (B*KV, T, Dv) with ``Dv`` another width than ``D`` (the output is
    then (B*H, T, Dv)), and ``sink`` (H,) float32 is one logit a query head that
    joins the softmax's maximum and denominator and mixes no value
    (:func:`_with_sink`; the logsumexp counts it); without a window such a
    forward is named ``CAUSAL_NAME``.  With ``Dv == D`` and no sink the kernels
    are traced as they were."""
    BH, T, D = q3.shape
    Dv = v3.shape[-1]
    rep = H // KV
    if streaming is None:
        streaming = _use_streaming("fwd", T, D, k3.dtype, block_q, block_k)
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k, seq_len=T)
    name = None
    if mask_block != 1:
        if not causal or block_q % mask_block or block_k % mask_block:
            raise ValueError(f"a block mask of {mask_block} positions is causal over blocks that divide the tiles "
                             f"({block_q} x {block_k})")
        kw["mask_block"], name = mask_block, BLOCK_MASK_NAME
    if window is not None:
        if not causal or mask_block != 1 or window < 1:
            raise ValueError(f"a window of {window} positions is causal, of 1 or more positions, and takes no block mask")
        kw["window"], name = int(window), WINDOW_NAME
    if (sink is not None or Dv != D) and name is None:
        name = CAUSAL_NAME
    sinks, sink_specs = (), []
    if sink is not None:
        # a head's logit as a (1, 1, 1) block, by the query row's head
        kw["sink"] = True
        sinks = (jnp.tile(sink.astype(jnp.float32), BH // H).reshape(BH, 1, 1),)
        sink_specs = [pl.BlockSpec((1, 1, 1), lambda b, *_: (b, 0, 0))]
    out_shape = (
        jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype),
        jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
    )
    if streaming:
        kv_row_s = lambda b, i, j: ((b // H) * KV + (b % H) // rep, j, 0)
        return pl.pallas_call(
            functools.partial(_fwd_kernel_stream, **kw),
            out_shape=out_shape,
            grid=(BH, T // block_q, T // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_row_s),
                pl.BlockSpec((1, block_k, Dv), kv_row_s),
                *sink_specs,
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
            interpret=interpret,
            name=name,
        )(q3, k3, v3, *sinks)
    kv_row = lambda b, i: ((b // H) * KV + (b % H) // rep, 0, 0)
    grid = (BH, T // block_q)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kw),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), kv_row),
            pl.BlockSpec((1, T, Dv), kv_row),
            *sink_specs,
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ),
        interpret=interpret,
        name=name,
    )(q3, k3, v3, *sinks)


# ------------------------------------------------- forward for a serve prefill
# The kernels above widen their blocks to float32 before the products (the
# backward recomputes the same probabilities, and training compares losses to
# 1e-3), which on the chip is several MXU passes a product: the device trace of
# a long prefill read 29% of the MXU's peak from them (PERF.md, PR 34).  A serve
# prefill never differentiates its attention, so this forward multiplies the
# operands AS THEY ARE (bfloat16 blocks, float32 accumulation; the probabilities
# rounded to the values' type for the second product, as the decode kernels do),
# keeps the softmax in float32, writes no logsumexp, masks only the blocks the
# diagonal crosses, and takes values of another width than the scores.
_SERVE_VMEM_LIMIT_BYTES = 48 * 1024 * 1024     # of the chip's 128 MiB: a head's whole K and V at 8192 positions


def _fwd_kernel_serve(q_ref, k_ref, v_ref, o_ref, *, scale, block_q, block_k):
    """Grid (H, T / block_q), causal.  The head's whole K (T, D) and V (T, Dv)
    stay in VMEM across its query blocks; the loop runs over the key blocks up
    to the diagonal, unmasked below it."""
    qi = pl.program_id(1)
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)          # (block_q, D), scaled once a block
    first_row = qi * block_q
    unmasked = (first_row + 1) // block_k                                    # key blocks wholly at or below the first row
    last = (first_row + block_q - 1) // block_k + 1
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def step(j, carry, masked):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if masked:
            col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    carry = (jnp.full((block_q, 1), _NEG_INF, jnp.float32), jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32))
    carry = jax.lax.fori_loop(0, unmasked, lambda j, c: step(j, c, False), carry)
    _m, l, acc = jax.lax.fori_loop(unmasked, last, lambda j, c: step(j, c, True), carry)
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _serve_vmem_bytes(T: int, D: int, Dv: int, dtype, block_q: int, block_k: int) -> int:
    """Scoped VMEM of :func:`_fwd_kernel_serve`: every operand double-buffered, and the step's score tiles."""
    blocks = _vmem_bytes(block_q, D, dtype) + _vmem_bytes(block_q, Dv, dtype) + _vmem_bytes(T, D, dtype) + _vmem_bytes(T, Dv, dtype)
    return 2 * blocks + _WORK_TILES["fwd"] * _vmem_bytes(block_q, block_k, jnp.float32)


def _flash_fwd_serve(q3, k3, v3, scale, block_q, block_k, interpret, name=None):
    """Causal forward alone over q3, k3 (H, T, D) and v3 (H, T, Dv), one
    sequence, no grouped heads; returns (H, T, Dv) in q3's type.  A head's K
    and V must fit ``_SERVE_VMEM_LIMIT_BYTES`` (16k positions at these widths)."""
    H, T, D = q3.shape
    Dv = v3.shape[-1]
    need = _serve_vmem_bytes(T, D, Dv, k3.dtype, block_q, block_k)
    if need > _SERVE_VMEM_LIMIT_BYTES:
        raise ValueError(f"the serve flash forward keeps a head's K and V in VMEM: {T} positions of {D} / {Dv} need "
                         f"{need} bytes of {_SERVE_VMEM_LIMIT_BYTES} (a streaming form is not written)")
    return pl.pallas_call(
        functools.partial(_fwd_kernel_serve, scale=scale, block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((H, T, Dv), q3.dtype),
        grid=(H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, T, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, T, Dv), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda h, i: (h, i, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_SERVE_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(q3, k3, v3)


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]    # (block_q,)
    delta = delta_ref[0, :, 0]  # (block_q,)
    D = q.shape[-1]
    nk_total = seq_len // block_k
    if causal:
        last = (qi * block_q + block_q - 1) // block_k + 1
        nk = jnp.minimum(nk_total, last)
    else:
        nk = nk_total
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, causal, block_q, block_k, seq_len, rep):
    """Grid (B*KV, T//block_k, rep): the last (fastest) grid dim walks the
    ``rep`` q heads of this kv group, accumulating into the same dk/dv
    block (TPU grids run sequentially, so output revisiting is the
    accumulation pattern) — GQA head reduction without materializing
    repeated K/V or an (rep, T, D) VMEM slab."""
    ki = pl.program_id(1)
    r = pl.program_id(2)
    k = k_ref[0].astype(jnp.float32)  # (block_k, D)
    v = v_ref[0].astype(jnp.float32)
    D = k.shape[-1]
    nq_total = seq_len // block_q
    if causal:
        first = (ki * block_k) // block_q  # earliest q block on/after diagonal
    else:
        first = 0
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), 0]
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])  # (block_q, block_k)
        dv_new = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        first, nq_total, body, (jnp.zeros((block_k, D), jnp.float32), jnp.zeros((block_k, D), jnp.float32))
    )
    if rep == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
    else:

        # rep > 1 outputs are fp32 (cast happens outside the kernel): the
        # cross-head accumulation must not round through bf16 each step
        @pl.when(r == 0)
        def _init():
            dk_ref[0] = dk
            dv_ref[0] = dv

        @pl.when(r > 0)
        def _acc():
            dk_ref[0] = dk_ref[0] + dk
            dv_ref[0] = dv_ref[0] + dv


def _dq_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                      *, scale, causal, block_q, block_k, seq_len):
    """Streaming dq: grid (BH, nq, nk), dq accumulates in fp32 scratch."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = seq_len // block_k

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(j * block_k <= qi * block_q + block_q - 1)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel_stream(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                       dk_scr, dv_scr, *, scale, causal, block_q, block_k, seq_len, rep):
    """Streaming dk/dv: grid (B*KV, nk, rep, nq) — k/v blocks stay resident
    while q/do stream; the GQA head-group reduction accumulates in the same
    fp32 scratch as the q loop (no fp32 output-revisit pass needed)."""
    ki = pl.program_id(1)
    r = pl.program_id(2)
    i = pl.program_id(3)
    nq = seq_len // block_q

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def compute():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(i * block_q + block_q - 1 >= ki * block_k)(compute)
    else:
        compute()

    @pl.when((r == rep - 1) & (i == nq - 1))
    def _final():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q3, k3, v3, o3, do3, lse, scale, causal, block_q, block_k, interpret, H, KV,
                      streaming=None):
    """dq and dk/dv each run resident or streaming on their own VMEM count
    (``streaming`` forces both, for tests)."""
    BH, T, D = q3.shape
    rep = H // KV
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1, keepdims=True)  # (BH, T, 1)
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k, seq_len=T)
    args = (q3, k3, v3, do3, lse, delta)

    def stream(kernel):
        if streaming is not None:
            return streaming
        return _use_streaming(kernel, T, D, k3.dtype, block_q, block_k, rep)

    dq = (_dq_stream if stream("dq") else _dq_resident)(args, kw, interpret, H, KV)
    dk, dv = (_dkv_stream if stream("dkv") else _dkv_resident)(args, kw, interpret, H, KV)
    return dq, dk, dv


def _dq_resident(args, kw, interpret, H, KV):
    q3 = args[0]
    BH, T, D = q3.shape
    rep = H // KV
    block_q = kw["block_q"]
    kv_row = lambda b, i: ((b // H) * KV + (b % H) // rep, 0, 0)
    return pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        grid=(BH, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), kv_row),
            pl.BlockSpec((1, T, D), kv_row),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(*args)


def _dkv_resident(args, kw, interpret, H, KV):
    """kv-centric grid; q rows of group g are the consecutive
    [g*rep, (g+1)*rep) band, walked by the last grid dim."""
    q3, k3, v3 = args[:3]
    BH, T, D = q3.shape
    rep = H // KV
    block_k = kw["block_k"]
    q_row = lambda b, i, r: ((b // KV) * H + (b % KV) * rep + r, 0, 0)
    kv_blk = lambda b, i, r: (b, i, 0)
    acc_dtype = k3.dtype if rep == 1 else jnp.float32
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, rep=rep, **kw),
        out_shape=(
            jax.ShapeDtypeStruct(k3.shape, acc_dtype),
            jax.ShapeDtypeStruct(v3.shape, acc_dtype),
        ),
        grid=(k3.shape[0], T // block_k, rep),
        in_specs=[
            pl.BlockSpec((1, T, D), q_row),
            pl.BlockSpec((1, block_k, D), kv_blk),
            pl.BlockSpec((1, block_k, D), kv_blk),
            pl.BlockSpec((1, T, D), q_row),
            pl.BlockSpec((1, T, 1), q_row),
            pl.BlockSpec((1, T, 1), q_row),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), kv_blk),
            pl.BlockSpec((1, block_k, D), kv_blk),
        ),
        interpret=interpret,
    )(*args)
    return dk.astype(k3.dtype), dv.astype(v3.dtype)


def _dq_stream(args, kw, interpret, H, KV):
    q3 = args[0]
    BH, T, D = q3.shape
    rep = H // KV
    block_q, block_k = kw["block_q"], kw["block_k"]
    kv_row_s = lambda b, i, j: ((b // H) * KV + (b % H) // rep, j, 0)
    q_blk_s = lambda b, i, j: (b, i, 0)
    return pl.pallas_call(
        functools.partial(_dq_kernel_stream, **kw),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        grid=(BH, T // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_blk_s),
            pl.BlockSpec((1, block_k, D), kv_row_s),
            pl.BlockSpec((1, block_k, D), kv_row_s),
            pl.BlockSpec((1, block_q, D), q_blk_s),
            pl.BlockSpec((1, block_q, 1), q_blk_s),
            pl.BlockSpec((1, block_q, 1), q_blk_s),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_blk_s),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(*args)


def _dkv_stream(args, kw, interpret, H, KV):
    """dk/dv accumulate the GQA group reduction in scratch, so outputs are
    native dtype directly."""
    q3, k3, v3 = args[:3]
    BH, T, D = q3.shape
    rep = H // KV
    block_q, block_k = kw["block_q"], kw["block_k"]
    # q rows of kv group g are the consecutive [g*rep, (g+1)*rep) band
    q_row_s = lambda b, ki, r, i: ((b // KV) * H + (b % KV) * rep + r, i, 0)
    kv_blk_s = lambda b, ki, r, i: (b, ki, 0)
    return pl.pallas_call(
        functools.partial(_dkv_kernel_stream, rep=rep, **kw),
        out_shape=(
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ),
        grid=(k3.shape[0], T // block_k, rep, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_row_s),
            pl.BlockSpec((1, block_k, D), kv_blk_s),
            pl.BlockSpec((1, block_k, D), kv_blk_s),
            pl.BlockSpec((1, block_q, D), q_row_s),
            pl.BlockSpec((1, block_q, 1), q_row_s),
            pl.BlockSpec((1, block_q, 1), q_row_s),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), kv_blk_s),
            pl.BlockSpec((1, block_k, D), kv_blk_s),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
