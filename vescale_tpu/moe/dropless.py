"""A dropless expert layer that is told which experts it holds.

The router scores every expert the model has; this chip (or rank) holds the
contiguous ids ``first_held .. first_held + held``, and computes the part of
the result that its own experts give.  The (token, expert) pairs that fall on
held experts are sorted by expert and go through grouped matrix products
(``jax.lax.ragged_dot``: on TPU one Mosaic kernel a product, which reads each
expert's weights once a row tile it spans), and come back to their tokens
weighted by the gates.  No capacity, no ``(N, E, C)`` mask, nothing dropped:
work and memory are linear in tokens x experts-per-token.  A pair whose expert
lives elsewhere adds nothing here (the exchange that would carry it there is
not this module's; on one chip the layer runs without it), and tokens masked
out (slots that are not active, pad positions) route nowhere.

Two shapes, one result (the choice is by the token count, never a knob).  At
most ``DENSE_MAX_TOKENS`` tokens (a decode step): every held expert runs on
every token as one batched product and the gates, zero where a token did not
keep the expert, weigh the sum.  A grouped product works in row tiles of 128,
so with so few tokens each touched expert costs it a whole tile anyway, and
the chip's trace (PERF.md, PR 29) showed the grouped kernel streaming the
expert weights at half the memory's rate where the batched product streams
them at nine tenths: a decode step is those weights' read.  More tokens (a
prefill) are sorted, as above; there the batched product's work would grow
with tokens x held experts.

``moe.layer.MoEMLP`` / ``TokenDispatcher`` (capacity, one-hot masks, expert
biases) stay as they are for training; ROADMAP D4 moves them here.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route_topk", "route_group_limited", "dropless_experts", "DENSE_MAX_TOKENS"]

# one row tile of a grouped product: up to here each touched expert costs it a tile, sorted or not
DENSE_MAX_TOKENS = 128


def route_topk(scores, k: int) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` largest of each token's router scores (N, E) and their
    gates: a softmax over those ``k`` alone.  Returns ids (N, k) int32 and
    gates (N, k) float32."""
    top, idx = jax.lax.top_k(scores.astype(jnp.float32), k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_group_limited(scores, k: int, *, n_group: int, topk_group: int, scale: float = 1.0
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Group-limited greedy routing: a softmax over ALL of a token's router
    scores (N, E); the experts lie in ``n_group`` contiguous groups of ``E /
    n_group``, a group scores as its best expert does, the ``topk_group`` best
    groups are kept and the rest zeroed; then the ``k`` largest of what is
    left.  The gates are those probabilities AS THEY ARE (not renormalised
    over the kept), times ``scale``.  Returns ids (N, k) int32 and gates
    (N, k) float32, as :func:`route_topk` does, and which groups each token
    kept (N, n_group) bool."""
    N, E = scores.shape
    if E % n_group or not 0 < topk_group <= n_group or k > topk_group * (E // n_group):
        raise ValueError(f"{E} experts in {n_group} groups, {topk_group} kept, cannot give {k} a token")
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    _, groups = jax.lax.top_k(jnp.max(probs.reshape(N, n_group, E // n_group), axis=-1), topk_group)
    kept = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], groups].set(True)
    top, idx = jax.lax.top_k(jnp.where(jnp.repeat(kept, E // n_group, axis=1), probs, 0.0), k)
    return idx.astype(jnp.int32), top * scale, kept


def dropless_experts(x, idx, gates, w_gate, w_up, w_down, *, first_held: int = 0,
                     token_mask: Optional[jax.Array] = None, dtype=None):
    """``sum over kept and held e of g_e * W_down,e (silu(W_gate,e x) * W_up,e x)``.

    ``x`` (N, d) tokens; ``idx`` / ``gates`` (N, k) from :func:`route_topk`,
    ids over ALL experts; ``w_gate`` / ``w_up`` (held, d, f) and ``w_down``
    (held, f, d) the held experts' SwiGLU weights, no biases; ``token_mask``
    (N,) bool, False for tokens that route nowhere; ``dtype`` the products'
    operand type (default: the weights').  Returns the result (N, d) float32
    and the tokens each held expert got (held,) int32.
    """
    held = w_gate.shape[0]
    N, k = idx.shape
    dtype = w_gate.dtype if dtype is None else dtype
    local = idx - first_held
    here = (local >= 0) & (local < held)
    if token_mask is not None:
        here = here & token_mask[:, None]
    # pairs by expert, the held ones first; everything else in one trailing group that no product touches
    group = jnp.where(here, local, held).reshape(N * k)
    counts = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    kept = jnp.where(here, gates, 0.0)                               # (N, k): a pair that adds nothing here weighs 0
    if N <= DENSE_MAX_TOKENS:
        # every held expert on every token; a token's gate for an expert it did not keep is 0
        weight = jnp.einsum("nk,nke->ne", kept, jax.nn.one_hot(local, held, dtype=jnp.float32))
        xb = jnp.broadcast_to(x.astype(dtype)[None], (held, N, x.shape[-1]))
        batched = lambda w: jnp.einsum("end,edf->enf", xb, w.astype(dtype), preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(batched(w_gate)) * batched(w_up)).astype(dtype)                           # (held, N, f)
        out = jnp.einsum("enf,efd->end", hidden, w_down.astype(dtype), preferred_element_type=jnp.float32)
        return jnp.einsum("end,ne->nd", out, weight), counts
    order = jnp.argsort(group, stable=True)
    xs = jnp.take(x, order // k, axis=0).astype(dtype)
    product = lambda a, w: jax.lax.ragged_dot(a, w.astype(dtype), counts, preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)).astype(dtype)
    ys = product(hidden, w_down)                                    # (N * k, d); rows past the groups are undefined
    # back to the tokens, a choice at a time, under the gates; a pair that is not here lies past the groups, and
    # its row is dropped where it is read.  (N, d) at a time: zeroing the rows first and un-sorting them whole
    # passed four times over (N * k, d) in float32, 66 ms of a 396 ms prefill of 8192 tokens (PERF.md, PR 34)
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(jnp.arange(N * k, dtype=jnp.int32)).reshape(N, k)
    out = jnp.zeros((N, ys.shape[-1]), jnp.float32)
    for j in range(k):
        out = out + jnp.where(here[:, j, None], jnp.take(ys, back[:, j], axis=0), 0.0) * kept[:, j, None]
    return out, counts
