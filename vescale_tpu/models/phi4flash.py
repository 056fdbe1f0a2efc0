"""Phi-4-mini-flash (``phi4flash``, the "SambaY" decoder-hybrid-decoder of
arXiv:2507.06607): a stack in TWO halves, whose second half keeps no cache of
its own.

With ``L`` layers and ``s = 2 (L // 4)`` (published: 32 and 16):

  * layers ``0 .. s - 1``, the SELF-DECODER: ``s / 2`` periods of (Mamba-1,
    window attention);
  * layer ``s``: Mamba-1, which also hands on its scan output ``m`` (with the
    ``D`` term, before the output gate);
  * layer ``s + 1``: full attention, whose K and V of every position are THE
    cache of the whole model;
  * layers ``s + 2 .. L - 1``, the CROSS-DECODER: periods of (gated memory unit,
    cross-attention).  A gated memory unit reads ``m`` of its own position and
    keeps nothing; a cross-attention layer projects only a query and reads
    layer ``s + 1``'s K and V.

Every layer: ``x = x + mixer(LN1(x))``, then ``x = x + W_2 (silu(g) * u)`` with
``[g, u] = W_1 LN2(x)`` (one matrix of twice the intermediate width); LayerNorm with weight and bias; no rotary term
and no position embedding anywhere; a final LayerNorm and the tied embedding as
the head.  The mixers (``u`` the normed input):

    Mamba-1   [x, z] = W_in u;  x = silu(conv1d_causal(x) + b);  [d, B, C] = W_x x;  dt = softplus(W_dt d + b_dt)
              h_t = exp(dt_t (x) A) * h_{t-1} + B_t (x) (dt_t x_t);  y_t = h_t C_t + D x_t;  out = W_out (y * silu(z))
    attention heads come in adjacent PAIRS (differential attention, arXiv:2410.05258): query pair i = heads (2i,
              2i + 1), key pair g = i // (query pairs / key pairs); S_s = softmax(q_{i,s} k_{g,s}^T / sqrt(hd) + mask);
              V = [v_{g,1}, v_{g,2}];  o_i = S_1 V - lam S_2 V;  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l);
              lam0(l) = 0.8 - 0.6 exp(-0.3 l);  o_i = rmsnorm(o_i; w) (1 - lam0(l));  out = W_o [o_0 ... ] + b_o
              window (odd l < s): position t sees t - window + 1 .. t;  full (l = s + 1): causal;
              cross (odd l > s + 1): W_q and W_o alone, keys and values layer s + 1's, causal
    GMU       out = W_b (m * silu(W_a u))

**Differential attention on the kernels that are there.**  A position's row is
read as ``KV / 2`` key heads of ``2 hd`` = ``[k_{g,1}, k_{g,2}]`` and as many
value heads ``V`` (the projections' own order: nothing moves), and the decode
kernel and the flash forward are given ``H`` query heads of ``2 hd`` on them:
``q_{i,1}`` as ``[q, 0]``, ``q_{i,2}`` as ``[0, q]`` (:func:`pair_queries`).  Scores, masks and values are then the formula's, but
for a sum that now adds zeros, so no kernel knows of pairs; the combination
(:func:`differential`, under ``vs.diff-attn``) is a few elementwise operations on
``(rows, H, 2 hd)``.  The bytes a decode step reads are the formula's; a
prefill's score products double.  Published: 40 query and 20 key heads of 64 are
40 rows of 128 on 10.

**The cache** (``serve/kv_cache.py``).  ONE layer of pages (layer ``s + 1``'s K
and V), read by that layer and by every cross-attention layer: eight readings a
decode step at the published depth.  The pools are FOLDED
(``KVCacheConfig.folded``: a position's row is every key head's entries side by
side, ``(1, KV hd)``; ten heads are no whole number of the chip's sublane
tiles, which ``paged_decode``'s page copies want) and read by
``kernels.paged_decode_folded``.  A window layer keeps a folded RING a slot
(``ring_k`` / ``ring_v``, ``(window layers, slots, window, 1, KV hd)``),
position ``p`` at row ``p mod window``, read through the same kernel as pages
under an arithmetic table (``models/laguna.py`` says how).  A Mamba-1 layer
keeps its state ``ssm`` ``(Mamba layers, slots, N, d_inner)`` float32, ``N`` on
sublanes as ``kernels/ssm_step.py`` lays it out, and the last ``d_conv - 1``
inputs of its convolution, ``conv``.  A decode step leaves an idle slot's rings,
states and tails bit for bit.  Rings and states keep no history:
``cache.refuse_slot_state`` refuses prefix sharing, speculation and rollback.

**A prefill stops half way.**  The prompt's rows go through layers ``0 .. s``
and through layer ``s + 1``'s norm and K/V projection only; that layer's
attention and MLP, the cross-decoder, the final norm and the head run on the
LAST REAL ROW alone (nothing later reads another row: every later layer reads
``m`` and layer ``s + 1``'s K and V, which the first half made).  Pad positions
follow the real ones: ``dt`` is 0 there, the convolution tail and the rings are
taken from the last real positions, and a query sees no position past its own.

**The stack is scanned.**  The self-decoder's periods and the cross-decoder's
are each one ``lax.scan`` over parameters stacked on a leading axis, the cache's
arrays in the carry, the layer's index and ``lam0`` as scanned operands
(``paged_decode_folded`` and ``ssm_step_selective`` take the layer as a scalar-prefetch
operand): a program holds six layer bodies whatever the depth.

Precision: weights and matmul operands ``config.dtype`` (bfloat16) with float32
accumulation; the residual stream, norms, gates, ``dt``, the recurrence, lambda
and the softmax float32; K and V are rounded to the cache's type once, the
convolution's inputs to the weights' type (prefill and decode then convolve the
same values).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, _mm, layernorm, ring_row, ring_source, rmsnorm, write_position

__all__ = [
    "Phi4FlashConfig", "init_params", "embed", "head", "lambda_init", "pair_queries", "differential", "attend_row",
    "mamba_prefill", "mamba_step", "window_prefill", "attention_step", "cross_step", "cross_row", "gmu", "mlp", "cache_config",
    "prefill_chunk", "decode_kernels", "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters",
    "prefill_counters", "SCORE_DEVIATION",
]

# ``models/laguna.py`` says why: scores of unit deviation make a softmax over some hundred positions flat, and a
# window of 512 and no window then read the same to rounding.  ``init_params`` draws ``W_q`` and ``W_k`` wider, by
# as much as gives a layer's scores ``q . k / sqrt(hd)`` this deviation on a LayerNorm's unit rows.
SCORE_DEVIATION = 2.0


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064            # rows of the tied embedding
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512           # a window layer sees this many positions, its own among them
    mb_per_layer: int = 2               # a Mamba-1 mixer every second layer of the self-decoder
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160            # ceil(hidden_size / 16)
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole tile
    dtype: Any = jnp.bfloat16           # weights, matmul operands, K and V, the convolution's inputs
    state_dtype: Any = jnp.float32      # the recurrent states

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("the self-decoder alternates a Mamba-1 mixer and window attention: mb_per_layer is 2")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 or self.hidden_size % self.num_attention_heads:
            raise ValueError("differential attention pairs adjacent heads: query and key heads come in twos")
        if (self.num_attention_heads // 2) % (self.num_key_value_heads // 2):
            raise ValueError("query pairs come in whole groups a key pair")
        if self.num_hidden_layers < 6 or self.num_hidden_layers % 2:
            raise ValueError("the stack is periods of two layers round the two middle ones: an even depth of 6 or more")
        if self.sliding_window < 1:
            raise ValueError("a window holds at least the position itself")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def self_periods(self) -> int:
        return self.num_hidden_layers // 4

    @property
    def split(self) -> int:
        """The layer whose Mamba-1 mixer hands on ``m``; the next one's K and V are the cache."""
        return 2 * self.self_periods

    @property
    def cross_periods(self) -> int:
        return (self.num_hidden_layers - self.split - 2) // 2

    @property
    def pair_heads(self) -> int:
        """Key (and value) heads of ``2 head_dim`` a position's row holds."""
        return self.num_key_value_heads // 2

    @property
    def pool_readers(self) -> int:
        """The layers that read the one pool layer in a decode step."""
        return 1 + self.cross_periods


def lambda_init(layer) -> Any:
    """``lam0(l) = 0.8 - 0.6 exp(-0.3 l)`` of the 0-based layer ``l`` (arXiv:2410.05258)."""
    return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(layer, np.float64))


# ------------------------------------------------------------------ parameters
def init_params(config: Phi4FlashConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance 1 / fan-in, but for: the embedding (unit
    variance: the stream starts at the size the branches add to it); ``W_q`` and
    ``W_k`` (``SCORE_DEVIATION`` says why); ``W_dt`` uniform in ``+- R^-1/2`` and
    ``A_log = log(1 .. N)``, ``D = 1``, ``b_dt`` the inverse softplus of a step
    size log-uniform in [1e-3, 1e-1], as Mamba draws them; the lambda vectors
    normal of deviation 0.1.  Norm weights 1; every bias normal of deviation 0.02.
    The periods' parameters are stacked on a leading axis (``self``: ``L // 4``
    of them, ``cross``: the rest), the two middle layers stand alone."""
    c, dt = config, config.dtype
    E, F, Di, N, R, K = c.hidden_size, c.intermediate_size, c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    hd, KVw = c.head_dim, c.num_key_value_heads * c.head_dim

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    bias = lambda k, n: (0.02 * jax.random.normal(k, (n,), F32)).astype(dt)

    def norm(k):
        return {"weight": jnp.ones((E,), dt), "bias": bias(k, E)}

    def around(k, mixer):
        """A layer: its two norms and its MLP round ``mixer``'s parameters."""
        ks = jax.random.split(k, 4)
        return {"input_layernorm": norm(ks[0]), "post_attention_layernorm": norm(ks[1]), **mixer,
                "mlp": {"gate_up": normal(ks[2], (E, 2 * F), E), "down": normal(ks[3], (F, E), F)}}

    def mamba(k):
        ks = jax.random.split(k, 7)
        step = jnp.exp(jax.random.uniform(ks[4], (Di,), F32) * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return around(ks[6], {"mamba": {
            "in_proj": normal(ks[0], (E, 2 * Di), E), "conv_weight": normal(ks[1], (K, Di), K), "conv_bias": bias(ks[5], Di),
            "x_proj": normal(ks[2], (Di, R + 2 * N), Di),
            "dt_proj": jax.random.uniform(ks[3], (R, Di), F32, -R ** -0.5, R ** -0.5).astype(dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, Di)),   # (N, d_inner): N on sublanes
            "D": jnp.ones((Di,), F32), "out_proj": normal(jax.random.fold_in(k, 7), (Di, E), Di)}})

    def attention(k, cross: bool):
        ks = jax.random.split(k, 10)
        gain = SCORE_DEVIATION ** 0.5
        ap = {"q_proj": normal(ks[0], (E, E), E, gain=gain), "q_bias": bias(ks[1], E),
              "o_proj": normal(ks[2], (E, E), E), "o_bias": bias(ks[3], E),
              "lambda": 0.1 * jax.random.normal(ks[4], (4, hd), F32),        # lq1, lk1, lq2, lk2
              "subln": jnp.ones((2 * hd,), dt)}
        if not cross:
            ap.update(k_proj=normal(ks[5], (E, KVw), E, gain=gain), v_proj=normal(ks[6], (E, KVw), E),
                      kv_bias=bias(ks[7], 2 * KVw))
        return around(ks[8], {"attn": ap})

    def gmu_layer(k):
        ks = jax.random.split(k, 3)
        return around(ks[2], {"gmu": {"in_proj": normal(ks[0], (E, Di), E), "out_proj": normal(ks[1], (Di, E), Di)}})

    def periods(k, n, first, second):
        return jax.vmap(lambda kk: {"first": first(jax.random.fold_in(kk, 0)), "second": second(jax.random.fold_in(kk, 1))})(
            jax.random.split(k, n))

    ks = jax.random.split(key, 6)
    return {
        "embed_tokens": {"embedding": normal(ks[0], (c.vocab_size, E), 1.0)},
        "final_layernorm": norm(ks[1]),
        "self": periods(ks[2], c.self_periods, mamba, lambda k: attention(k, False)),
        "mid_mamba": mamba(ks[3]),
        "mid_full": attention(ks[4], False),
        "cross": periods(ks[5], c.cross_periods, gmu_layer, lambda k: attention(k, True)),
    }


# ------------------------------------------------------------ embedding, head
def embed(config: Phi4FlashConfig, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: Phi4FlashConfig, params, x):
    """Next-token logits (float32): the final LayerNorm, then the tied embedding."""
    norm = params["final_layernorm"]
    h = layernorm(x, norm["weight"], norm["bias"], config.layer_norm_eps)
    return jax.lax.dot_general(h.astype(config.dtype), params["embed_tokens"]["embedding"], (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _norm(c: Phi4FlashConfig, np_, x):
    return layernorm(x, np_["weight"], np_["bias"], c.layer_norm_eps)


def mlp(c: Phi4FlashConfig, lp, x):
    """The layer's second half on the stream ``x``: ``x + W_2 (silu(g) * u)``, ``[g, u] = W_1 LN2(x)``."""
    with jax.named_scope("vs.mlp"):
        gu = _mm(_norm(c, lp["post_attention_layernorm"], x), lp["mlp"]["gate_up"], c.dtype)
        F = c.intermediate_size
        return x + _mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], lp["mlp"]["down"], c.dtype)


# --------------------------------------------------------------------- Mamba-1
def _mamba_in(c: Phi4FlashConfig, mp, u):
    """``[x, z] = W_in u``: ``x`` in the weights' type, as the convolution's tail is kept; ``z`` float32."""
    xz = _mm(u, mp["in_proj"], c.dtype)
    return xz[..., : c.d_inner].astype(c.dtype), xz[..., c.d_inner:]


def _selective(c: Phi4FlashConfig, mp, conv):
    """From the convolution's output (rows, d_inner) float32: the activation
    ``x``, the step sizes ``dt`` (rows, d_inner) and ``B``, ``C`` (rows, N)."""
    R, N = c.mamba_dt_rank, c.mamba_d_state
    x = jax.nn.silu(conv)
    dbc = _mm(x, mp["x_proj"], c.dtype)
    dt = jax.nn.softplus(_mm(dbc[..., :R], mp["dt_proj"], c.dtype) + mp["dt_bias"].astype(F32))
    return x, dt, dbc[..., R: R + N], dbc[..., R + N:]


def _mamba_out(c: Phi4FlashConfig, mp, y, x, z):
    """``m = y + D x`` and the mixer's output ``W_out (m * silu(z))``."""
    m = y + mp["D"].astype(F32) * x
    return _mm(m * jax.nn.silu(z), mp["out_proj"], c.dtype), m


def mamba_prefill(c: Phi4FlashConfig, mp, u, length, *, interpret: Optional[bool]):
    """One sequence ``u`` (T, E) of which the first ``length`` positions are
    real: the convolution from zeros before the start, the selective scan from a
    zero state with ``dt`` forced to 0 in the pad (``kernels.selective_scan`` on
    the leg ``interpret`` names).  Returns the mixer's output (T, E), ``m`` (T,
    d_inner), the state (N, d_inner) in the cache's type and the tail (d_conv -
    1, d_inner) of the last real inputs."""
    from ..kernels.selective_scan import selective_scan     # (Pallas comes with it: imported late)

    x, z = _mamba_in(c, mp, u)
    T, K = x.shape[0], c.mamba_d_conv
    padded = jnp.concatenate([jnp.zeros((K - 1, c.d_inner), x.dtype), x], axis=0)
    tail = jax.lax.dynamic_slice_in_dim(padded, length, K - 1, axis=0)
    w = mp["conv_weight"].astype(F32)
    conv = mp["conv_bias"].astype(F32) + sum(w[k] * padded[k: k + T].astype(F32) for k in range(K))
    x, dt, B, C = _selective(c, mp, conv)
    dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    with jax.named_scope("vs.s6-scan"):
        y, state = selective_scan(x, dt, -jnp.exp(mp["A_log"].astype(F32)), B, C, interpret=interpret)
    out, m = _mamba_out(c, mp, y, x, z)
    return out, m, state.astype(c.state_dtype), tail


def mamba_step(c: Phi4FlashConfig, mp, u, ssm, conv, *, layer, active, interpret: Optional[bool]):
    """The recurrence's one step for every slot: ``u`` (S, E); ``ssm`` (Mamba
    layers, S, N, d_inner) and ``conv`` (Mamba layers, S, d_conv - 1, d_inner),
    of which this mixer's are the ``layer``-th (an int or a traced int32).  A
    slot ``active`` (S,) does not name takes ``dt = 0`` and ``dt x = 0`` and
    keeps its tail: its state stands bit for bit though the kernel reads and
    writes it.  Returns the output (S, E), ``m`` (S, d_inner), ``ssm`` and
    ``conv`` advanced."""
    from ..kernels.ssm_step import ssm_step_selective

    x, z = _mamba_in(c, mp, u)
    tail = jax.lax.dynamic_index_in_dim(conv, layer, axis=0, keepdims=False)
    window = jnp.concatenate([tail, x[:, None, :]], axis=1)                                      # (S, K, d_inner)
    conved = mp["conv_bias"].astype(F32) + jnp.sum(mp["conv_weight"].astype(F32)[None] * window.astype(F32), axis=1)
    x, dt, B, C = _selective(c, mp, conved)
    dt = jnp.where(active[:, None], dt, 0.0)
    with jax.named_scope("vs.s6-step"):
        ssm, y = ssm_step_selective(ssm, dt, -jnp.exp(mp["A_log"].astype(F32)), dt * x, B, C, layer=layer,
                                    interpret=interpret)
    conv = jax.lax.dynamic_update_index_in_dim(conv, jnp.where(active[:, None, None], window[:, 1:], tail), layer, axis=0)
    out, m = _mamba_out(c, mp, y, x, z)
    return out, m, ssm, conv


# ------------------------------------------------------ differential attention
def pair_queries(q):
    """``q`` (rows, H, hd) as the rows of ``2 hd`` that meet a position's paired
    key heads ``[k_1, k_2]``: an even head ``[q, 0]``, an odd one ``[0, q]``."""
    rows, H, hd = q.shape
    placed = q.reshape(rows, H // 2, 2, 1, hd) * jnp.eye(2, dtype=q.dtype)[:, :, None]        # (rows, pairs, s, half, hd)
    return placed.reshape(rows, H, 2 * hd)


def _queries(c: Phi4FlashConfig, ap, u):
    q = (_mm(u, ap["q_proj"], c.dtype) + ap["q_bias"].astype(F32)).reshape(u.shape[0], c.num_attention_heads, c.head_dim)
    return pair_queries(q.astype(c.dtype))


def _keys_values(c: Phi4FlashConfig, ap, u):
    """K and V of the rows ``u`` as a folded store holds them, (rows, 1, KV hd) each, in ``c.dtype``."""
    KVw = c.num_key_value_heads * c.head_dim
    b = ap["kv_bias"].astype(F32)
    return ((_mm(u, ap["k_proj"], c.dtype) + b[:KVw])[:, None, :].astype(c.dtype),
            (_mm(u, ap["v_proj"], c.dtype) + b[KVw:])[:, None, :].astype(c.dtype))


def _pair_heads(c: Phi4FlashConfig, a):
    """Folded rows (rows, 1, KV hd) as the ``KV / 2`` heads of ``2 hd`` they hold: (rows, KV / 2, 2 hd)."""
    return a.reshape(a.shape[0], c.pair_heads, 2 * c.head_dim)


def differential(c: Phi4FlashConfig, ap, y, lam0):
    """From the two softmaxes' results ``y`` (rows, H, 2 hd), head ``2 i`` the
    first of pair ``i`` and ``2 i + 1`` the second: ``o_i = y_{i,1} - lam
    y_{i,2}``, sub-normed, times ``1 - lam0``, through ``W_o``.  ``lam0`` the
    layer's :func:`lambda_init` (a float or a traced scalar).  Returns (rows, E)."""
    with jax.named_scope("vs.diff-attn"):
        lq1, lk1, lq2, lk2 = ap["lambda"].astype(F32)
        lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
        y = y.astype(F32).reshape(y.shape[0], y.shape[1] // 2, 2, y.shape[2])
        o = rmsnorm(y[:, :, 0] - lam * y[:, :, 1], ap["subln"], c.layer_norm_eps) * (1.0 - lam0)
        o = o.reshape(o.shape[0], -1)
    return _mm(o, ap["o_proj"], c.dtype) + ap["o_bias"].astype(F32)


def window_prefill(c: Phi4FlashConfig, ap, u, lam0, *, interpret: Optional[bool] = None):
    """A window layer over one sequence ``u`` (T, E) from position 0, through
    the windowed flash forward.  Returns the output (T, E) and this layer's K
    and V, folded (T, 1, KV hd).  Pad positions follow the real ones, so causality
    keeps them out."""
    from ..ops.flash_attention import flash_attention

    k, v = _keys_values(c, ap, u)
    y = flash_attention(_queries(c, ap, u)[None], _pair_heads(c, k)[None], _pair_heads(c, v)[None], causal=True,
                        scale=c.head_dim ** -0.5, interpret=interpret, window=c.sliding_window)[0]
    return differential(c, ap, y, lam0), k, v


def attend_row(c: Phi4FlashConfig, q, k, v, length):
    """One query row a head, ``q`` (H, 2 hd), over the first ``length`` of the
    positions ``k``, ``v`` (T, 1, KV hd), folded: a float32 softmax in plain XLA
    (a prefill's last real row is all of it that the second half runs).  (H, 2 hd)."""
    k, v = _pair_heads(c, k), _pair_heads(c, v)
    KV = k.shape[1]
    qg = (q.astype(F32) * c.head_dim ** -0.5).reshape(KV, q.shape[0] // KV, q.shape[1])
    s = jnp.einsum("kgd,tkd->kgt", qg, k.astype(F32))
    p = jax.nn.softmax(jnp.where(jnp.arange(k.shape[0]) < length, s, -1e30), axis=-1)
    return jnp.einsum("kgt,tkd->kgd", p, v.astype(F32)).reshape(q.shape)


def attention_step(c: Phi4FlashConfig, ap, u, k_store, v_store, lam0, *, layer, table, write, valid_len, active=None,
                   interpret: Optional[bool]):
    """One new position a slot through a layer that keeps K and V: they go to
    ``write`` = ``(page, offset)`` of the stores' ``layer`` (the pools; the
    rings, seen as pages), then ``kernels.paged_decode_folded`` reads through
    ``table`` up to ``valid_len``.  Where ``active`` (S,) is given (the rings:
    every slot writes its own), a slot it does not name writes back what the
    row held.  Returns the output (S, E) and both stores."""
    from ..kernels.paged_attention import paged_decode_folded

    k, v = _keys_values(c, ap, u)
    where = (layer,) + write
    if active is not None:
        k = jnp.where(active[:, None, None], k, k_store[where])
        v = jnp.where(active[:, None, None], v, v_store[where])
    k_store, v_store = write_position(k_store, v_store, k, v, where)
    y = paged_decode_folded(_queries(c, ap, u), k_store, v_store, table, valid_len, layer=layer, scale=c.head_dim ** -0.5,
                            interpret=interpret)
    return differential(c, ap, y, lam0), k_store, v_store


def cross_step(c: Phi4FlashConfig, ap, u, k_pool, v_pool, lam0, *, table, valid_len, interpret: Optional[bool]):
    """A cross-attention layer's step: a query alone, over the pool's one layer."""
    from ..kernels.paged_attention import paged_decode_folded

    with jax.named_scope("vs.cross-attn"):
        y = paged_decode_folded(_queries(c, ap, u), k_pool, v_pool, table, valid_len, layer=0, scale=c.head_dim ** -0.5,
                                interpret=interpret)
    return differential(c, ap, y, lam0)


def cross_row(c: Phi4FlashConfig, ap, u, k, v, length, lam0):
    """A layer's attention for ONE row ``u`` (1, E) over the rung's ``k``, ``v``
    (layer ``s + 1``'s, in the cache's type) up to ``length``: layer ``s + 1``'s
    own and every cross-attention layer's, in a prefill."""
    with jax.named_scope("vs.cross-attn"):
        y = attend_row(c, _queries(c, ap, u)[0], k, v, length)[None]
    return differential(c, ap, y, lam0)


def gmu(c: Phi4FlashConfig, gp, u, m):
    """The gated memory unit: ``W_b (m * silu(W_a u))``, ``m`` layer ``s``'s of the same position."""
    with jax.named_scope("vs.gmu"):
        return _mm(m * jax.nn.silu(_mm(u, gp["in_proj"], c.dtype)), gp["out_proj"], c.dtype)


# ------------------------------------------- what the serve engine asks of a model
# (``serve/hybrid_engine.py``, "The seam")
def cache_config(config: Phi4FlashConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """ONE layer of folded pages (layer ``s + 1``'s, which every cross-attention
    layer reads too); a folded ring of ``sliding_window`` positions a slot for
    each window layer (whole pages of the pool's size, so that the ring reads as
    pages); a float32 state and a convolution tail for each Mamba-1 layer."""
    from ..serve.kv_cache import KVCacheConfig

    c = config
    if c.sliding_window % page_size:
        raise ValueError(f"a ring of {c.sliding_window} positions is read as whole pages of {page_size}")
    ring = (c.sliding_window, 1, c.num_key_value_heads * c.head_dim)
    mambas = c.self_periods + 1
    return KVCacheConfig(
        layers=1, kv_heads=c.pair_heads, head_dim=2 * c.head_dim, folded=True, num_slots=num_slots, page_size=page_size,
        pages_per_slot=pages_per_slot, num_pages=num_pages, dtype=c.dtype,
        slot_state=(("ring_k", c.self_periods, ring, c.dtype), ("ring_v", c.self_periods, ring, c.dtype),
                    ("ssm", mambas, (c.mamba_d_state, c.d_inner), c.state_dtype),
                    ("conv", mambas, (c.mamba_d_conv - 1, c.d_inner), c.dtype)))


def prefill_chunk(config: Phi4FlashConfig) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: Phi4FlashConfig, cache) -> Dict[str, Any]:
    """``{"decode": paged_decode_folded's flag over pool and rings (one row, one
    type), "ssm": ssm_step_selective's over the states}``, or None for an XLA leg."""
    from ..kernels import paged_attention, ssm_step

    c = config
    return {"decode": paged_attention.leg_folded(cache.k.data.dtype, c.pair_heads, 2 * c.head_dim, 2 * c.head_dim,
                                                 cache.config.page_size),
            "ssm": ssm_step.leg(cache.state["ssm"].dtype, c.mamba_d_state, c.d_inner)}


def _cross_decoder(c: Phi4FlashConfig, params, x, m, attend):
    """The cross-decoder over the rows ``x``: periods of a gated memory unit
    that reads ``m`` and a cross-attention layer, whose attention over layer ``s
    + 1``'s K and V is ``attend(ap, u, lam0)`` (a step's, or a prefill's one row's)."""
    def period(x, xs):
        lp, lam0 = xs
        gp, cp = lp["first"], lp["second"]
        x = mlp(c, gp, x + gmu(c, gp["gmu"], _norm(c, gp["input_layernorm"], x), m))
        with jax.named_scope("vs.attn"):
            x = x + attend(cp["attn"], _norm(c, cp["input_layernorm"], x), lam0)
        return mlp(c, cp, x), None

    return jax.lax.scan(period, x, (params["cross"], _lam0(c.split + 3, c.cross_periods)))[0]


def _lam0(first: int, periods: int):
    """``lam0`` of the attention layers ``first, first + 2, ...`` (periods,) float32."""
    return jnp.asarray(lambda_init(first + 2 * np.arange(periods)), F32)


def serve_prefill(c: Phi4FlashConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body.  ``tokens`` (rung,) go through the
    self-decoder, layer ``s`` and layer ``s + 1``'s K/V projection; everything
    after runs on the last real row.  Layer ``s + 1``'s K and V of the rung go
    to the slot's pages, the slot's rows of the rings are rewritten from the
    last ``min(length, window)`` real positions, its states and tails from
    where the prompt ends.  Returns the last real position's logits row and the
    cache's arrays."""
    from ..kernels import selective_scan
    from ..serve.kv_cache import write_pages

    T, W = tokens.shape[0], c.sliding_window
    scan_leg = selective_scan.leg(c.mamba_d_state, c.d_inner, T) if interpret is None else interpret
    source = ring_source(length, T, W)

    def self_layer(lp, x):
        out, m, state, tail = mamba_prefill(c, lp["mamba"], _norm(c, lp["input_layernorm"], x), length, interpret=scan_leg)
        return mlp(c, lp, x + out), m, state, tail

    def period(x, xs):
        lp, lam0 = xs
        x, _m, state, tail = self_layer(lp["first"], x)
        wp = lp["second"]
        with jax.named_scope("vs.attn"):
            y, k, v = window_prefill(c, wp["attn"], _norm(c, wp["input_layernorm"], x), lam0, interpret=interpret)
        return mlp(c, wp, x + y), (state, tail, jnp.take(k, source, axis=0), jnp.take(v, source, axis=0))

    x, (states, tails, ring_k, ring_v) = jax.lax.scan(period, embed(c, params, tokens), (params["self"], _lam0(1, c.self_periods)))
    x, m, state, tail = self_layer(params["mid_mamba"], x)
    fp = params["mid_full"]
    u = _norm(c, fp["input_layernorm"], x)
    k, v = _keys_values(c, fp["attn"], u)                                   # of every row: the cache
    # ... and the rest of the stack on the last real row alone
    row = lambda a: jax.lax.dynamic_slice_in_dim(a, length - 1, 1, axis=0)
    x, m = row(x), row(m)
    with jax.named_scope("vs.attn"):
        x = x + cross_row(c, fp["attn"], row(u), k, v, length, float(lambda_init(c.split + 1)))
    x = mlp(c, fp, x)

    x = _cross_decoder(c, params, x, m, lambda ap, u, lam0: cross_row(c, ap, u, k, v, length, lam0))
    logits = head(c, params, x)[0]

    out = dict(arrays)
    out["k"] = write_pages(arrays["k"], k[None], page_row, page)
    out["v"] = write_pages(arrays["v"], v[None], page_row, page)
    own = {"ring_k": ring_k, "ring_v": ring_v, "ssm": jnp.concatenate([states, state[None]]),
           "conv": jnp.concatenate([tails, tail[None]])}
    for name, rows in own.items():          # (layers, ...) -> the slot's rows of (layers, slots, ...)
        out[name] = jax.lax.dynamic_update_slice_in_dim(arrays[name], rows[:, None].astype(arrays[name].dtype), slot, axis=1)
    return logits, out


def serve_decode(c: Phi4FlashConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 kernels: Dict[str, Any]):
    """The decode program's body, one token a slot.  A Mamba-1 layer moves its
    state one step (``ssm_step_selective``); a window layer writes ring row
    ``lengths mod window`` and reads the ring, viewed as pages under an
    arithmetic table, up to ``min(lengths + 1, window)``; layer ``s + 1`` writes
    the new position to the slot's page and reads its pages up to it; every
    cross-attention layer reads those same pages with its own query: all
    through the same ``paged_decode_folded``.  An idle slot's rings, states and tails
    stand bit for bit.  Returns the logits (S, vocab), ``{"pool_kernel": whether
    the pool was read by the kernel}`` and the cache's arrays."""
    S, W, page = lengths.shape[0], c.sliding_window, arrays["k"].shape[2]
    # the rings as pools of ``window / page`` pages a slot (a split of a major axis: no bytes move)
    as_pages = lambda ring: ring.reshape(ring.shape[0], S * (W // page), page, *ring.shape[3:])
    row = ring_row(lengths, W)
    ring_table = (jnp.arange(S, dtype=jnp.int32) * (W // page))[:, None] + jnp.arange(W // page, dtype=jnp.int32)[None, :]
    ring = dict(table=ring_table, write=(ring_table[:, 0] + row // page, row % page), valid_len=jnp.minimum(lengths + 1, W),
                active=active, interpret=kernels["decode"])

    def self_layer(lp, x, ssm, conv, layer):
        out, m, ssm, conv = mamba_step(c, lp["mamba"], _norm(c, lp["input_layernorm"], x), ssm, conv, layer=layer,
                                       active=active, interpret=kernels["ssm"])
        return mlp(c, lp, x + out), m, ssm, conv

    def period(carry, xs):
        x, ssm, conv, ring_k, ring_v = carry
        lp, i, lam0 = xs
        x, _m, ssm, conv = self_layer(lp["first"], x, ssm, conv, i)
        wp = lp["second"]
        with jax.named_scope("vs.attn"):
            y, ring_k, ring_v = attention_step(c, wp["attn"], _norm(c, wp["input_layernorm"], x), ring_k, ring_v, lam0,
                                               layer=i, **ring)
        return (mlp(c, wp, x + y), ssm, conv, ring_k, ring_v), None

    carry = (embed(c, params, tokens), arrays["ssm"], arrays["conv"], as_pages(arrays["ring_k"]), as_pages(arrays["ring_v"]))
    (x, ssm, conv, ring_k, ring_v), _ = jax.lax.scan(
        period, carry, (params["self"], jnp.arange(c.self_periods, dtype=jnp.int32), _lam0(1, c.self_periods)))
    x, m, ssm, conv = self_layer(params["mid_mamba"], x, ssm, conv, c.self_periods)
    fp = params["mid_full"]
    with jax.named_scope("vs.attn"):
        y, k_pool, v_pool = attention_step(
            c, fp["attn"], _norm(c, fp["input_layernorm"], x), arrays["k"], arrays["v"], float(lambda_init(c.split + 1)),
            layer=0, table=table, write=(write_page, write_offset), valid_len=lengths + 1, interpret=kernels["decode"])
    x = mlp(c, fp, x + y)

    x = _cross_decoder(c, params, x, m, lambda ap, u, lam0: cross_step(
        c, ap, u, k_pool, v_pool, lam0, table=table, valid_len=lengths + 1, interpret=kernels["decode"]))
    out = dict(arrays, k=k_pool, v=v_pool, ssm=ssm, conv=conv, ring_k=ring_k.reshape(arrays["ring_k"].shape),
               ring_v=ring_v.reshape(arrays["ring_v"].shape))
    return head(c, params, x), {"pool_kernel": jnp.int32(kernels["decode"] is not None)}, out


# this model's own counters beside those every model's engine keeps (``HybridServeEngine.trace_counters``).  Of the
# decode steps read: the pool's bytes read by ALL its readers (every slot's ``length + 1`` positions, an idle slot's
# one, K and V, times the readers); the ring positions the window layers read (every slot's
# ``min(length + 1, window)``, times the window layers), what they would have read without a window, and the rings'
# bytes read and written; the states' and tails' bytes read and written (every slot's, idle or not: the kernel moves
# them all).  ``decode_pages_*``, which the engine counts for ONE reading of the pool where the kernel reads it, are
# counted here for the other readings.  Of the prefills: the rows the second half ran, one a prompt (the first half's
# real rows are the engine's ``prefill_tokens_real``).
STEP_COUNTERS = ("shared_pool_bytes_read", "ring_positions_read", "ring_positions_unwindowed", "ring_bytes_rw",
                 "ssm_state_bytes_rw", "prefill_rows_cross")


def step_counters(config: Phi4FlashConfig, cache, lengths: np.ndarray, counts) -> Dict[str, int]:
    c = config
    S, windows, mambas, readers = len(lengths), c.self_periods, c.self_periods + 1, c.pool_readers
    reach = lengths.astype(np.int64) + 1
    position_bytes = 2 * c.num_key_value_heads * c.head_dim * jnp.dtype(c.dtype).itemsize
    ring_read = int(np.minimum(reach, c.sliding_window).sum()) * windows
    state = c.mamba_d_state * c.d_inner * jnp.dtype(c.state_dtype).itemsize
    tail = (c.mamba_d_conv - 1) * c.d_inner * jnp.dtype(c.dtype).itemsize
    out = {"shared_pool_bytes_read": int(reach.sum()) * readers * position_bytes, "ring_positions_read": ring_read,
           "ring_positions_unwindowed": int(reach.sum()) * windows, "ring_bytes_rw": (ring_read + S * windows) * position_bytes,
           "ssm_state_bytes_rw": 2 * S * mambas * (state + tail)}
    if int(counts.get("pool_kernel", 0)):
        page, per_slot = cache.config.page_size, cache.config.pages_per_slot
        out["decode_pages_read"] = int(np.minimum(-(-reach // page), per_slot).sum()) * (readers - 1)
        out["decode_pages_capacity"] = S * per_slot * (readers - 1)
    return out


def prefill_counters(config: Phi4FlashConfig, bucket: int) -> Dict[str, int]:
    return {"prefill_rows_cross": 1}
