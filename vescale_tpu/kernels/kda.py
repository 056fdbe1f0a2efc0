"""Delta-rule linear attention kernels (Kimi Delta Attention, arXiv:2510.26692)
— a head's state is a MATRIX, and a position corrects it by a rank-1 term.

A head keeps ``S`` (d_k, d_v) float32.  A position first decays every ROW of it
by its own gate (``g`` (d_k,) <= 0, a log-decay a channel), then corrects it
towards the new key's value, then reads it with the query:

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        = (I - beta k k^T) Diag(exp g) S + beta k v^T
    o_t = S_t^T q_t

Every other recurrence of this package is diagonal (``h <- a h + b x``:
``kernels/ssm_step.py``, ``kernels/selective_scan.py``); this one is not: the
correction reads the state it writes, so a chunk of positions is a triangular
SYSTEM, not a prefix sum.  Two kernels:

**:func:`kda_step`** (decode): one position for every slot of one layer, the
state read once, written once, in place.  The state of all delta-rule layers is
one array ``(layers, slots, H, d_k, d_v)``, aliased to the output; the layer
index rides in as a scalar-prefetch operand (every layer of a decode program is
the same Mosaic kernel), the grid visits ``(slot, block of heads)`` and a block
is ``_STEP_HEADS`` heads' states (1 MiB at 128 x 128; in and out double-buffered,
4 MiB of VMEM).  ``d_k`` lies on sublanes and ``d_v`` on lanes, so ``v``, ``beta``
and the output are ROWS and ``q``, ``k`` and ``exp(g)`` COLUMNS over the
sublanes: the wrapper lays a block's ``k | q | g`` rows out as one ``(128, d_k)``
tile a slot and block, the kernel turns it round once (one square transpose),
and a head's three columns are static lanes of the result; no operand is a
lane-padded column in HBM.  A head is 16 vector registers: a decay, a product
and a sublane reduction (``S'^T k``), a rank-1 update, a second product and
reduction (the output).  A slot whose ``g`` and ``beta`` are 0 keeps its state
bit for bit (``exp(0) S + k * 0``): that is how the caller names a slot idle.
Must-move bytes: twice the state it touches.

**:func:`kda_chunk`** (prefill): one sequence from a zero state, in chunks of
``C`` = 128 positions, a head's state float32 in VMEM scratch from the first
chunk to the last (grid ``(heads, chunks)``, heads parallel, chunks in turn).
With ``G_i`` the gates' running sum inside a chunk and ``S_0`` the state at its
start, the WY / UT form is

    A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])  (j < i)      L = Diag(beta) A
    B_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])  (j <= i)
    U = (I + L)^-1 Diag(beta) (V - (K * exp G) S_0)                the pseudo-values
    O = (Q * exp G) S_0 + B U
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

**The gate's lower bound is what makes this computable in float32.**  A gate
may reach -5 a position (``kda_lower_bound``), so a chunk's running sum reaches
-640 and ``exp(-G_j)``, which a factored ``(K exp G)(K exp -G)^T`` would need,
overflows after 18 positions.  Here a decay is only ever ``exp`` of a sum that
runs FORWARD in time (``<= 0``), but inside a sub-chunk of ``_SUB`` = 16
positions, whose 16 x 5 = 80 of log-decay a factored form can carry: the two
factors meet at the sub-chunk's MIDDLE row, so each is within ``e^+-40`` (met at
its first row, ``exp(-80)`` times a small entry of ``q`` is a denormal, and a
thousandth of the last row's output goes with it).  Row block ``a`` of ``A`` and
``B`` is one product: its 16 rows' ``k beta | q`` brought to that middle row,
against every earlier position's ``k`` decayed up to it (``<= 1``) and its own
sub-chunk's (``e^+-40``); later positions are zeroed before the product.  The
wrapper hands the kernel the running sums INSIDE sub-chunks (a ``cumsum`` over
16 rows in XLA); the kernel adds the sub-chunks' totals.

**The inverse** of the unit lower-triangular ``I + L`` is taken in a form the
MXU runs: ``L = D + E``, ``D`` the 16 x 16 blocks on the diagonal (nilpotent:
``D^16 = 0``), so ``(I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8)`` exactly;
then ``N = (I + D)^-1 E`` is strictly lower by BLOCKS (``N^8 = 0`` at 8 blocks)
and ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)``; ``(I + L)^-1 = (I + N)^-1 (I +
D)^-1``.  Powers stay low (15 inside a block, 7 across), which keeps the
cancellation of correlated keys within float32 (the one-level product over 128
would take ``L^64``).  Twelve 128-cube products a chunk and head, all float32
(``_PRECISION``).

A pad row has ``g = 0`` and ``beta = 0`` (the caller zeroes ``k beta`` and ``v
beta``): its pseudo-value is 0 and it decays nothing, so the state stands where
the last real row left it, bit for bit in a chunk that is all pad.

Numerics: float32 throughout; :func:`kda_step` does the XLA leg's operations in
its order but for the order of the two sums over ``d_k``; :func:`kda_chunk`'s XLA
leg is the recurrence itself (a ``lax.scan`` over positions), which the chunked
form equals to rounding (``tests/test_kda.py``: 1e-5 of the tensor's scale, the
gates pinned at -5 over a whole rung included).  Each op takes the kernel's
``interpret`` flag or None for its XLA leg; :func:`leg_step` / :func:`leg_chunk`
resolve that for a shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

__all__ = ["kda_step", "kda_step_xla", "kda_chunk", "kda_chunk_xla", "supports_step", "supports_chunk", "leg_step",
           "leg_chunk", "STEP_NAME", "CHUNK_NAME"]

STEP_NAME, CHUNK_NAME = "kda_step", "kda_chunk"      # as the dispatch and the device trace name them
_STEP_HEADS = 16        # heads' states in one block of the step: 1 MiB at 128 x 128 float32
_TILE = 128             # rows of the step's column tile (k | q | g of a block's heads, padded), and a chunk's positions
_SUB = 16               # positions whose decays may be factored: 16 x 5 = 80, e^+-40 about the middle row
_GROW_MOST = 87.0       # no exponent is larger (e^88 is the first that is no float32); 40 at the published bound
_PRECISION = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _step_heads(H: int) -> int:
    """Heads in one block of the step: at most ``_STEP_HEADS``, dividing ``H``."""
    block = min(_STEP_HEADS, H)
    while H % block:
        block -= 1
    return block


def supports_step(state_dtype, heads: int, d_k: int, d_v: int, *, interpret: bool) -> bool:
    """Whether :func:`kda_step` takes such a state: float32, and, compiled,
    whole (8, 128) tiles a head with a block's three columns a head in one tile."""
    if jnp.dtype(state_dtype) != jnp.float32:
        return False
    return interpret or (d_k % _TILE == 0 and d_v % _TILE == 0 and 3 * _step_heads(heads) <= _TILE
                         and _step_heads(heads) % 8 == 0)


def _chunk(T: int) -> int:
    """Positions of one chunk: ``_TILE``, or the largest halving of it that divides ``T``."""
    chunk = _TILE
    while T % chunk:
        chunk //= 2
    return chunk


def supports_chunk(heads: int, d_k: int, d_v: int, positions: int, *, interpret: bool) -> bool:
    """Whether :func:`kda_chunk` takes such a sequence: whole sub-chunks, and,
    compiled, chunks of 128 and heads of whole lane tiles (every matrix of a
    chunk is then a 128-square)."""
    if positions % min(_SUB, positions):
        return False
    return interpret or (positions % _TILE == 0 and d_k % _TILE == 0 and d_v % _TILE == 0)


def leg_step(state_dtype, heads: int, d_k: int, d_v: int) -> Optional[bool]:
    """The leg :func:`kda_step` takes over such a state: the kernel's ``interpret`` flag, or None for the XLA leg."""
    return kernels.resolve(STEP_NAME, supported=lambda interpret: supports_step(state_dtype, heads, d_k, d_v, interpret=interpret))


def leg_chunk(heads: int, d_k: int, d_v: int, positions: int) -> Optional[bool]:
    """The leg :func:`kda_chunk` takes over such a sequence."""
    return kernels.resolve(CHUNK_NAME, supported=lambda interpret: supports_chunk(heads, d_k, d_v, positions, interpret=interpret))


# --------------------------------------------------------------------- the step
def kda_step_xla(state, q, k, v, g, beta, *, layer):
    """:func:`kda_step` without the kernel: the decayed state is written out
    and read again for each of the two sums."""
    decayed = jnp.exp(g.astype(F32))[..., None] * state[layer]
    u = beta.astype(F32)[..., None] * (v.astype(F32) - jnp.sum(decayed * k.astype(F32)[..., None], axis=2))
    new = decayed + k.astype(F32)[..., None] * u[:, :, None, :]
    return state.at[layer].set(new), jnp.sum(new * q.astype(F32)[..., None], axis=2)


def _step_kernel(layer_ref, cols_ref, rows_ref, s_ref, s_out_ref, o_ref, *, heads: int):
    del layer_ref                                   # it placed the blocks
    cols = cols_ref[0, 0].T                         # (d_k, tile): column j is row j of k | q | g
    for j in range(heads):
        k, q = cols[:, j: j + 1], cols[:, heads + j: heads + j + 1]
        decayed = jnp.exp(cols[:, 2 * heads + j: 2 * heads + j + 1]) * s_ref[0, 0, j]             # (d_k, 1) * (d_k, d_v)
        v, beta = rows_ref[0, 0, j: j + 1, :], rows_ref[0, 1, j: j + 1, :]                          # (1, d_v) rows
        u = beta * (v - jnp.sum(decayed * k, axis=0, keepdims=True))
        new = decayed + k * u
        s_out_ref[0, 0, j] = new
        o_ref[0, j: j + 1, :] = jnp.sum(new * q, axis=0, keepdims=True)


@kernels.with_xla_leg(kda_step_xla, static_argnames=("interpret",), donate_argnames=("state",))
def kda_step(state, q, k, v, g, beta, *, layer, interpret):
    """One position of one layer for every slot, on the ``layer``-th state (an
    int32 scalar or array of one) of ``state`` (layers, S, H, d_k, d_v) float32,
    updated in place: ``q``, ``k`` and the log-decays ``g`` (S, H, d_k), ``v``
    (S, H, d_v), ``beta`` (S, H).  A slot whose ``g`` and ``beta`` are 0 keeps
    its state bit for bit.  ``interpret`` the kernel's flag
    (:func:`supports_step`), or None for the XLA leg (what :func:`leg_step`
    resolved).  Returns the state array and the outputs (S, H, d_v) float32."""
    _layers, S, H, dk, dv = state.shape
    if q.shape != (S, H, dk) or k.shape != q.shape or g.shape != q.shape or v.shape != (S, H, dv) or beta.shape != (S, H):
        raise ValueError(f"kda_step: q {q.shape}, k {k.shape}, g {g.shape}, v {v.shape}, beta {beta.shape} against a "
                         f"state of {(H, dk, dv)} a slot")
    Hb = _step_heads(H)
    tile = -(-3 * Hb // _TILE) * _TILE
    by_block = lambda a: a.astype(F32).reshape(S, H // Hb, Hb, dk)
    cols = jnp.concatenate([by_block(k), by_block(q), by_block(g), jnp.zeros((S, H // Hb, tile - 3 * Hb, dk), F32)], axis=2)
    rows = jnp.stack([v.astype(F32), jnp.broadcast_to(beta.astype(F32)[..., None], (S, H, dv))], axis=1)       # (S, 2, H, d_v)
    block = pl.BlockSpec((1, 1, Hb, dk, dv), lambda s, b, layer: (layer[0], s, b, 0, 0))
    new_state, out = pl.pallas_call(
        functools.partial(_step_kernel, heads=Hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // Hb),
            in_specs=[pl.BlockSpec((1, 1, tile, dk), lambda s, b, layer: (s, b, 0, 0)),
                      pl.BlockSpec((1, 2, Hb, dv), lambda s, b, layer: (s, 0, b, 0)), block],
            out_specs=[block, pl.BlockSpec((1, Hb, dv), lambda s, b, layer: (s, b, 0))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype), jax.ShapeDtypeStruct((S, H, dv), F32)],
        input_output_aliases={3: 0},          # the state (operand 3, the scalar first) is the first output
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=STEP_NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), cols, rows, state)
    return new_state, out


# -------------------------------------------------------------------- the chunks
def kda_chunk_xla(q, k, v, g, beta):
    """:func:`kda_chunk` without the kernel: the recurrence itself, a
    ``lax.scan`` over the positions with every head's state its carry."""
    T, H, dk = q.shape

    def position(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        decayed = jnp.exp(g_t)[..., None] * S
        u = b_t[:, None] * (v_t - jnp.sum(decayed * k_t[..., None], axis=1))
        S = decayed + k_t[..., None] * u[:, None, :]
        return S, jnp.sum(S * q_t[..., None], axis=1)

    last, out = jax.lax.scan(position, jnp.zeros((H, dk, v.shape[-1]), F32), tuple(a.astype(F32) for a in (q, k, v, g, beta)))
    return out, last


def _dot(a, b):
    return jnp.dot(a, b, precision=_PRECISION, preferred_element_type=F32)


def _dot_nt(a, b):
    """``a @ b.T``: both contract their last axis."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_PRECISION, preferred_element_type=F32)


def _chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, gl_ref, o_ref, last_ref, s_scr, *, chunk: int, sub: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    n = chunk // sub
    S0 = s_scr[...]
    q, k, kb, vb, gl = q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], gl_ref[...]
    dk = k.shape[1]
    # the gates' running sum from the chunk's start: inside a sub-chunk it came in; the sub-chunks' totals are added here
    starts = [jnp.zeros((1, dk), F32)]
    for a in range(n):
        starts.append(starts[-1] + gl[(a + 1) * sub - 1: (a + 1) * sub, :])
    G = jnp.concatenate([gl[a * sub: (a + 1) * sub, :] + starts[a] for a in range(n)], axis=0) if n > 1 else gl
    position = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    rows_a, rows_b = [], []
    for a in range(n):
        own = slice(a * sub, (a + 1) * sub)
        # the sub-chunk's two factors meet at its MIDDLE row's running sum: its own rows lie within e^+-40 of there
        middle = gl[a * sub + sub // 2 - 1: a * sub + sub // 2, :] if sub > 1 else gl[own, :]
        since = jnp.exp(gl[own, :] - middle)
        # every position up to this sub-chunk's end, brought to that row: an earlier one decayed (<= 1), its own within e^+-40
        until = jnp.where(position < (a + 1) * sub, k * jnp.exp(jnp.minimum(middle + starts[a] - G, _GROW_MOST)), 0.0)
        both = _dot_nt(jnp.concatenate([kb[own, :] * since, q[own, :] * since], axis=0), until)      # (2 sub, chunk)
        rows_a.append(both[:sub])
        rows_b.append(both[sub:])
    A = jnp.concatenate(rows_a, axis=0) if n > 1 else rows_a[0]
    B = jnp.concatenate(rows_b, axis=0) if n > 1 else rows_b[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (i == j).astype(F32)
    same_block = (i // sub) == (j // sub)
    D = jnp.where((i > j) & same_block, A, 0.0)
    E = jnp.where((i > j) & ~same_block, A, 0.0)
    # (I + D)^-1 = (I - D)(I + D^2)(I + D^4) ... up to the power that is 0
    inverse, power, p = eye - D, D, 2
    while p < sub:
        power = _dot(power, power)
        inverse = _dot(inverse, eye + power)
        p *= 2
    if n > 1:       # ... and (I + N)^-1 of N = (I + D)^-1 E, strictly lower by blocks
        N = _dot(inverse, E)
        outer, power, p = eye - N, N, 2
        while p < n:
            power = _dot(power, power)
            outer = _dot(outer, eye + power)
            p *= 2
        inverse = _dot(outer, inverse)
    grown = jnp.exp(G)
    U = _dot(inverse, vb - _dot(kb * grown, S0))                                            # the pseudo-values (chunk, d_v)
    o_ref[...] = _dot(q * grown, S0) + _dot(jnp.where(i >= j, B, 0.0), U)
    # the state at the chunk's end: every row decayed by the chunk's whole gate (a column over d_k: a row turned round)
    whole = jnp.broadcast_to(jnp.exp(starts[n]), (S0.shape[1], dk)).T                      # (d_k, d_v)
    new = whole * S0 + _dot((k * jnp.exp(starts[n] - G)).T, U)
    s_scr[...] = new

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        last_ref[0] = new


@kernels.with_xla_leg(kda_chunk_xla, static_argnames=("interpret",))
def kda_chunk(q, k, v, g, beta, *, interpret):
    """The recurrence over one sequence from a zero state: ``q``, ``k`` and the
    log-decays ``g`` (T, H, d_k) (``g`` <= 0, and no lower than about -5 a
    position: the module's text says why), ``v`` (T, H, d_v), ``beta`` (T, H); a
    row with ``g`` = 0 and ``beta`` = 0 leaves the state as it was.
    ``interpret`` the kernel's flag (:func:`supports_chunk`), or None for the
    XLA leg (what :func:`leg_chunk` resolved).  Returns the outputs (T, H, d_v)
    and every head's state after the last position (H, d_k, d_v), float32."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or g.shape != q.shape or v.shape != (T, H, dv) or beta.shape != (T, H):
        raise ValueError(f"kda_chunk: q {q.shape}, k {k.shape}, g {g.shape}, v {v.shape}, beta {beta.shape}")
    if not supports_chunk(H, dk, dv, T, interpret=bool(interpret)):
        raise ValueError(f"kda_chunk takes no sequence of {T} positions over heads of {(dk, dv)} (see supports_chunk())")
    chunk = _chunk(T)
    sub = min(_SUB, chunk)
    q, k, v, g = (a.astype(F32) for a in (q, k, v, g))
    b = beta.astype(F32)[..., None]
    inside = jnp.cumsum(g.reshape(T // sub, sub, H, dk), axis=1).reshape(T, H * dk)       # the running sum inside a sub-chunk
    flat = lambda a: a.reshape(T, -1)
    wide_k = pl.BlockSpec((chunk, dk), lambda h, t: (t, h))
    wide_v = pl.BlockSpec((chunk, dv), lambda h, t: (t, h))
    out, last = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, sub=sub),
        grid=(H, T // chunk),
        in_specs=[wide_k, wide_k, wide_k, wide_v, wide_k],
        out_specs=[wide_v, pl.BlockSpec((1, dk, dv), lambda h, t: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, H * dv), F32), jax.ShapeDtypeStruct((H, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=CHUNK_NAME,
    )(flat(q), flat(k), flat(k * b), flat(v * b), inside)
    return out.reshape(T, H, dv), last
