"""SDAR (``sdar_moe``): a Qwen3-MoE-shaped decoder that GENERATES BY DIFFUSION
OVER BLOCKS.  The stack is plain (pre-norm residual layers of grouped-query
attention with a per-head RMSNorm on queries and keys before the rotary term,
and a routed expert layer in every layer: 128 experts, 8 a token, gates
renormalised over the kept, no shared expert; a final norm, an untied head);
what is new is what a step is.

Attention is causal over BLOCKS of ``block_length`` (B) positions, not over
positions: position ``i`` sees ``j`` iff ``j // B <= i // B`` (full inside a
block, causal between blocks), in the prompt as in what is generated.  And the
logits are NOT SHIFTED: the row at a position predicts that position.  A block
is generated as the family's published ``block_diffusion_generate`` does under
its static low-confidence schedule, greedy:

  * a prompt of ``n`` tokens: its ``floor(n / B)`` whole blocks are prefilled
    under the block mask into the cache; its last ``n mod B`` tokens open the
    first generated block as positions already revealed; every other position
    of a block starts as the ``mask_token_id``;
  * a DENOISING pass runs the block's B positions through the stack against the
    cached blocks before it and the block itself; of the still-masked positions
    the ``B / T`` (``denoising_steps`` T) with the largest confidence (the
    softmax probability of their argmax, over the whole vocabulary) take their
    argmax;
  * when no position is masked, the final tokens go through the stack once more
    (the COMMIT), which leaves the block's K and V in the cache, and the block
    is out.  The commit rides in the call that runs the first denoising pass of
    the block after it, as B more rows of that call (``serve_decode``,
    ``FUSED``); only a request's last block commits in a call of its own.

So a whole block costs T calls for B tokens (T + 1 units of B rows through the
stack, as the published loop has passes), a request of n blocks n T + 1, and
slots are at different passes of their blocks in one program call.  Both
programs are written once, as pure functions over a plain parameter tree (the
norm, the product and the rotary term are ``models/blocks.py``'s); the last
section is what ``serve.HybridServeEngine`` asks of a model's
module, with ``block_schedule`` for the engine's host-side mirror
(``serve/hybrid_engine.py``, "A block engine", says what ``serve_decode`` is
given and gives, and the teacher-forced use of the same program).

Two departures from the published loop, neither of which a run can meet but by
an exact tie or a one-in-151,936 draw: a tie of confidences goes to the lower
position (``torch.topk`` leaves it open), and a position that takes the mask id
ITSELF as its argmax counts as revealed (the published loop, which finds the
masked positions by comparing ids, would mask it again and leave its block
without a commit).

Precision: weights and matmul operands ``config.dtype`` (bfloat16) with
float32 accumulation; residual stream, norms, rotary, router, softmax and
confidence float32; K and V are rounded to the cache's type once.  A chip's
share: as in ``models/granite_hybrid.py`` (``num_experts`` is what the router
scores, ``experts_held`` / ``first_expert_held`` which of them this tree holds).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..moe.dropless import route_topk, routed_experts
from .blocks import F32, ROUTED_DOWN_GAIN, _mm, rmsnorm, rotary

__all__ = [
    "SdarMoeConfig", "init_params", "embed", "head", "attention_prefill", "attention_pass", "expert_layer",
    "layer_prefill", "layer_pass", "head_stats", "unmask", "cache_config", "prefill_chunk", "decode_kernels",
    "block_schedule", "serve_prefill", "serve_decode", "STEP_COUNTERS", "step_counters", "prefill_counters",
]



@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936            # rows of the embedding and of the head
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768    # width of one routed expert
    num_experts: int = 128              # the router's outputs: every expert the model has
    num_experts_per_tok: int = 8
    experts_held: int = 128             # ... and the contiguous ids this tree holds
    first_expert_held: int = 0
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    block_length: int = 4               # B: positions a block, and the mask's granularity
    denoising_steps: int = 4            # T: denoising passes a whole block
    mask_token_id: int = 151669
    prefill_chunk: int = 128            # the prefill ladder's first rung: the flash forward's smallest whole tile
    dtype: Any = jnp.bfloat16           # weights, matmul operands, K and V

    def __post_init__(self):
        if not (0 <= self.first_expert_held and self.first_expert_held + self.experts_held <= self.num_experts
                and self.experts_held > 0):
            raise ValueError(f"experts {self.first_expert_held}..{self.first_expert_held + self.experts_held} "
                             f"are not among the router's {self.num_experts}")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("query heads come in whole groups a key head, and a head is made of rotary pairs")
        if not 0 < self.denoising_steps <= self.block_length or self.prefill_chunk % self.block_length:
            raise ValueError(f"a block of {self.block_length} takes 1 to {self.block_length} denoising steps and "
                             f"divides the prefill's chunk ({self.prefill_chunk})")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("the mask token is a row of the embedding")


# ------------------------------------------------------------------ parameters
def init_params(config: SdarMoeConfig, key) -> Dict[str, Any]:
    """Seeded random weights in the types they are served in (jit the call).
    Matrices are normal with variance 1 / fan-in, but for two: the router
    (float32) twice as wide, so that its softmax is not flat, and the routed
    experts' down projections ``ROUTED_DOWN_GAIN`` times as wide (the constant
    says why; here a routed expert's output would be three times the stream:
    random attention over some hundred positions averages its values away)."""
    c, dt = config, config.dtype
    E, H, KV, hd, F, held = (c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                             c.moe_intermediate_size, c.experts_held)

    def normal(k, shape, fan_in, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape, F32) * (gain / math.sqrt(fan_in))).astype(dtype)

    def attention(k):
        ks = jax.random.split(k, 4)
        return {"q_proj": normal(ks[0], (E, H * hd), E), "k_proj": normal(ks[1], (E, KV * hd), E),
                "v_proj": normal(ks[2], (E, KV * hd), E), "o_proj": normal(ks[3], (H * hd, E), H * hd),
                "q_norm": jnp.ones((hd,), dt), "k_norm": jnp.ones((hd,), dt)}

    def moe(k):
        ks = jax.random.split(k, 4)
        return {"router": normal(ks[0], (E, c.num_experts), E, F32, gain=2.0),
                "w_gate": normal(ks[1], (held, E, F), E), "w_up": normal(ks[2], (held, E, F), E),
                "w_down": normal(ks[3], (held, F, E), F, gain=ROUTED_DOWN_GAIN)}

    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": normal(jax.random.fold_in(key, 1 << 20), (c.vocab_size, E), E)},
        "lm_head": {"kernel": normal(jax.random.fold_in(key, 1 << 21), (E, c.vocab_size), E)},
        "norm": {"weight": jnp.ones((E,), dt)},
    }
    for l in range(c.num_hidden_layers):
        k_attn, k_moe = jax.random.split(jax.random.fold_in(key, l))
        params[f"layers_{l}"] = {"input_layernorm": {"weight": jnp.ones((E,), dt)},
                                 "post_attention_layernorm": {"weight": jnp.ones((E,), dt)},
                                 "self_attn": attention(k_attn), "mlp": moe(k_moe)}
    return params


# ------------------------------------------------------------ embedding, head
def embed(config: SdarMoeConfig, params, tokens):
    return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(F32)


def head(config: SdarMoeConfig, params, x):
    """Logits (float32), a row a position, not shifted."""
    return _mm(rmsnorm(x, params["norm"]["weight"], config.rms_norm_eps), params["lm_head"]["kernel"], config.dtype)


# ------------------------------------------------------------------ attention
def _qkv(c: SdarMoeConfig, ap, u, positions):
    """Queries (N, H, hd) and keys (N, KV, hd), each head normed and then
    rotated, and values (N, KV, hd), all in ``c.dtype``."""
    N, H, KV, hd = u.shape[0], c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = rmsnorm(_mm(u, ap["q_proj"], c.dtype).reshape(N, H, hd), ap["q_norm"], c.rms_norm_eps)
    k = rmsnorm(_mm(u, ap["k_proj"], c.dtype).reshape(N, KV, hd), ap["k_norm"], c.rms_norm_eps)
    v = _mm(u, ap["v_proj"], c.dtype).reshape(N, KV, hd)
    return rotary(q, positions, c.rope_theta).astype(c.dtype), rotary(k, positions, c.rope_theta).astype(c.dtype), \
        v.astype(c.dtype)


def attention_prefill(c: SdarMoeConfig, ap, u, *, interpret: Optional[bool] = None):
    """Attention over one sequence ``u`` (T, E) from position 0 under the block
    mask, through the flash forward (its GQA kernel on TPU, the dense product
    elsewhere); returns the output (T, E) and this layer's K and V (T, KV, hd).
    Pad positions lie in later blocks than the real ones, so the mask keeps
    them out."""
    from ..ops.flash_attention import flash_attention

    q, k, v = _qkv(c, ap, u, jnp.arange(u.shape[0], dtype=jnp.int32))
    y = flash_attention(q[None], k[None], v[None], causal=True, scale=c.head_dim ** -0.5, interpret=interpret,
                        mask_block=c.block_length)[0]
    return _mm(y.reshape(u.shape[0], -1), ap["o_proj"], c.dtype), k, v


def attention_pass(c: SdarMoeConfig, ap, u, k_pool, v_pool, *, layer: int, table, page, offset, positions, valid_len,
                   interpret: Optional[bool]):
    """One pass over every slot's open block: ``u`` (S x B, E), slot-major.  The
    block's K and V go to ``(page, offset .. offset + B)`` of the pool's
    ``layer`` (a block never straddles a page; the null page for a slot that
    may not write), FIRST: every query of the block then sees exactly
    ``valid_len`` = block start + B positions, which is the block mask, and
    ``kernels.paged_decode`` (``interpret``: the kernel's flag, or None for its
    XLA leg) is the decode attention any model's step calls.  Its queries are a
    slot's ``B x H`` rows laid out so that the ``B x H / KV`` rows of one key
    head lie together, which the kernel takes as a group: no change to the
    kernel.  Returns the output (S x B, E) and both pools."""
    from ..kernels.paged_attention import paged_decode

    S, B = page.shape[0], c.block_length
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q, k, v = _qkv(c, ap, u, positions.reshape(S * B))
    where = (layer, page[:, None], offset[:, None] + jnp.arange(B, dtype=offset.dtype)[None, :])
    k_pool = k_pool.at[where].set(k.reshape(S, B, KV, hd).astype(k_pool.dtype))
    v_pool = v_pool.at[where].set(v.reshape(S, B, KV, hd).astype(v_pool.dtype))
    grouped = q.reshape(S, B, KV, H // KV, hd).transpose(0, 2, 1, 3, 4).reshape(S, B * H, hd)
    y = paged_decode(grouped, k_pool, v_pool, table, valid_len, layer=layer, scale=hd ** -0.5, interpret=interpret)
    y = y.reshape(S, KV, B, H // KV, hd).transpose(0, 2, 1, 3, 4).reshape(S * B, H * hd)
    return _mm(y, ap["o_proj"], c.dtype), k_pool, v_pool


# -------------------------------------------------------------- expert layer
def expert_layer(c: SdarMoeConfig, ep, h, token_mask=None):
    """``sum over kept and held e of g_e E_e(h)`` for tokens ``h`` (N, E): the
    router scores all ``num_experts`` in float32, the eight largest are kept
    and their gates are a softmax over those eight (the source's softmax over
    all, top-k, renormalised: the same numbers); no shared expert.  Returns the
    sum (N, E) float32 and how many tokens each held expert got (held,)."""
    return routed_experts(h, ep["router"], lambda scores: route_topk(scores, c.num_experts_per_tok), ep["w_gate"],
                          ep["w_up"], ep["w_down"], first_held=c.first_expert_held, token_mask=token_mask, dtype=c.dtype)


def _after_attention(c: SdarMoeConfig, lp, x, y, token_mask):
    x = x + y
    with jax.named_scope("vs.moe"):
        y, counts = expert_layer(c, lp["mlp"], rmsnorm(x, lp["post_attention_layernorm"]["weight"], c.rms_norm_eps),
                                 token_mask=token_mask)
    return x + y, counts


def layer_prefill(c: SdarMoeConfig, lp, x, live, *, interpret: Optional[bool] = None):
    """One layer over one padded sequence ``x`` (T, E) float32; ``live`` (T,)
    the positions that route to experts (the prompt's blocks).  Returns the
    residual stream and the layer's K and V."""
    with jax.named_scope("vs.attn"):
        y, k, v = attention_prefill(c, lp["self_attn"], rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps),
                                    interpret=interpret)
    x, _ = _after_attention(c, lp, x, y, live)
    return x, k, v


def layer_pass(c: SdarMoeConfig, lp, x, live, attention_step):
    """One layer over one pass, ``x`` (S x B, E) float32; ``attention_step(u)``
    is :func:`attention_pass` over this layer of the pools.  Returns the
    residual stream, both pools and the held experts' counts."""
    with jax.named_scope("vs.attn"):
        y, k_pool, v_pool = attention_step(rmsnorm(x, lp["input_layernorm"]["weight"], c.rms_norm_eps))
    x, counts = _after_attention(c, lp, x, y, live)
    return x, k_pool, v_pool, counts


# ------------------------------------------------------- from logits to a block
def head_stats(c: SdarMoeConfig, params, x, interpret: Optional[bool]):
    """Of the rows of logits :func:`head` makes of ``x`` (N, E), what a
    selection asks of each (``kernels.head_select``: the largest logit, its id,
    the softmax's denominator): ``interpret`` None the XLA leg (the logits are
    made, and read three times), else the kernel's flag (no logits)."""
    from ..kernels.head_select import head_select

    return head_select(rmsnorm(x, params["norm"]["weight"], c.rms_norm_eps).astype(c.dtype),
                       params["lm_head"]["kernel"].astype(c.dtype), interpret=interpret)


def unmask(c: SdarMoeConfig, best, denominator, ids, masked, passes, may_reveal):
    """The static low-confidence schedule's one step over blocks ``ids`` (S, B)
    of which ``masked`` (S, B) are still to decide, at their ``passes``
    (S,)-th denoising pass: ``best`` (S, B) every position's greedy token and
    ``denominator`` (S, B) its softmax's (:func:`head_stats`: the position's
    confidence, the softmax probability of that token over the whole
    vocabulary, is its inverse); of a slot's masked positions the ``B / T`` most
    confident (the first ``B mod T`` passes one more; never more than are
    masked; none where ``may_reveal`` (S,) is False) take their token.  Returns
    the new ids and the new mask."""
    B, T = c.block_length, c.denoising_steps
    confidence = jnp.where(masked, 1.0 / denominator, -jnp.inf)                      # exp(top - logsumexp)
    per_pass = jnp.asarray([B // T + (k < B % T) for k in range(T)], jnp.int32)
    count = jnp.minimum(per_pass[jnp.minimum(passes, T - 1)], jnp.sum(masked, axis=-1).astype(jnp.int32))
    count = jnp.where(may_reveal, count, 0)
    # a position's rank among its block's: how many are more confident, ties to the lower position
    j = jnp.arange(B)
    ahead = (confidence[:, None, :] > confidence[:, :, None]) | (
        (confidence[:, None, :] == confidence[:, :, None]) & (j[None, None, :] < j[None, :, None]))
    take = masked & (jnp.sum(ahead, axis=-1) < count[:, None])
    return jnp.where(take, best.astype(ids.dtype), ids), masked & ~take


# ------------------------------------------- what the serve engine asks of a model
def cache_config(config: SdarMoeConfig, *, num_slots: int, page_size: int, pages_per_slot: int,
                 num_pages: Optional[int] = None):
    """The cache's geometry: K and V pages of every layer, and a slot's open
    block beside them (its ids, which are masked, the pass it is at)."""
    from ..serve.kv_cache import KVCacheConfig

    B = config.block_length
    return KVCacheConfig(
        layers=config.num_hidden_layers, kv_heads=config.num_key_value_heads, head_dim=config.head_dim,
        num_slots=num_slots, page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages, dtype=config.dtype,
        slot_state=(("block_ids", 1, (B,), jnp.int32), ("block_masked", 1, (B,), jnp.bool_),
                    ("block_pass", 1, (), jnp.int32)))


def prefill_chunk(config: SdarMoeConfig) -> int:
    """The prefill ladder's first rung."""
    return config.prefill_chunk


def decode_kernels(config: SdarMoeConfig, cache) -> Dict[str, Any]:
    """The pass's kernels, latched at build: ``{"decode": the ``interpret`` flag
    of ``paged_decode``, "head_select": that of ``head_select`` over the open
    rows of every slot, or None for the XLA leg}``."""
    from ..kernels import head_select, paged_attention

    return {"decode": paged_attention.leg(cache.k.data.dtype, config.num_key_value_heads, config.head_dim),
            "head_select": head_select.leg(config.dtype, cache.num_slots * config.block_length, config.hidden_size)}


def block_schedule(config: SdarMoeConfig):
    """The host's mirror of what a pass does to a block (``serve.engine.BlockSchedule``)."""
    from ..serve.engine import BlockSchedule

    return BlockSchedule(config.block_length, config.denoising_steps)


def serve_prefill(c: SdarMoeConfig, params, arrays, tokens, length, page_row, slot, *, page: int,
                  interpret: Optional[bool] = None):
    """The prefill program's body: ``tokens`` (bucket,) through the stack under
    the block mask, every position past the prompt holding the mask id (so the
    prompt's last, partial block is run with its open positions masked, as the
    first denoising pass will run it).  K and V of the bucket's positions go to
    the slot's pages (those of the partial block are provisional: every pass
    rewrites them); the slot's open block is the prompt's last ``length mod B``
    tokens, revealed, and masks.  Returns position ``length - 1``'s logits row
    (not shifted) and the cache's arrays."""
    from ..serve.kv_cache import write_pages

    B, T = c.block_length, tokens.shape[0]
    position = jnp.arange(T, dtype=jnp.int32)
    ids = jnp.where(position < length, tokens, c.mask_token_id).astype(jnp.int32)
    live = position < -(-length // B) * B                   # the prompt's blocks, the open one whole
    x = embed(c, params, ids)
    ks, vs = [], []
    for l in range(c.num_hidden_layers):
        x, k, v = layer_prefill(c, params[f"layers_{l}"], x, live, interpret=interpret)
        ks.append(k)
        vs.append(v)
    logits = head(c, params, jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True))[0]
    kd = write_pages(arrays["k"], jnp.stack(ks), page_row, page)
    vd = write_pages(arrays["v"], jnp.stack(vs), page_row, page)
    revealed = length % B
    opened = jax.lax.dynamic_slice_in_dim(jnp.pad(ids, (0, B)), length - revealed, B)
    j = jnp.arange(B)
    opened = jnp.where(j < revealed, opened, c.mask_token_id)
    put = lambda array, row: jax.lax.dynamic_update_slice_in_dim(array, row[None, None].astype(array.dtype), slot, axis=1)
    return logits, {"k": kd, "v": vd, "block_ids": put(arrays["block_ids"], opened),
                    "block_masked": put(arrays["block_masked"], j >= revealed),
                    "block_pass": put(arrays["block_pass"], jnp.zeros((), jnp.int32))}


def serve_decode(c: SdarMoeConfig, params, arrays, table, lengths, tokens, *, active, write_page, write_offset,
                 next_page, next_offset, kernels: Dict[str, Any]):
    """The decode program's body: ONE CALL over every slot's open block (B
    positions a slot, its OPEN ROWS) and over ``C = commit_places(S)`` places of
    B COMMIT ROWS each, ``(S + C) x B`` rows through the stack.  ``tokens`` (S,)
    says what the call is for a slot: ``BlockSchedule.OWN_PASS`` the one pass its
    state asks for (denoise, or commit alone where nothing is masked: the
    block's final ids go through as its open rows and its state is a fresh
    block), ``HOLD`` none (its block and the cache stay as they are), an id the
    teacher-forced use (the id is revealed at position ``lengths mod B`` of the
    block and nothing else is), and ``FUSED``, of a slot whose block has nothing
    masked: the block's final ids go through as the COMMIT ROWS of the next free
    place (a running count over the slots that fuse; their K and V land at the
    block's positions and they see ``block start + B`` positions, as a commit
    alone has it) and the slot's open rows are the block AFTER it, all masked, at
    its first denoising pass: its K and V land at ``next_page`` /
    ``next_offset``, it sees ``block start + 2 B`` positions, the committed
    block's final K and V among them (a layer writes before it attends), and
    head, confidence and selection run on the open rows alone (:func:`head_stats`
    on the leg ``kernels["head_select"]`` names: on the kernel's NO LOGITS ARE
    MADE, on the XLA leg's they are the program's temporary).  To the attention
    the places are C more slots of the same call (the slot's own table row; an
    unused place has length 0), and their rows route to no expert.  A ``FUSED``
    that finds something masked, or every place taken, is an ``OWN_PASS``.
    Returns the open rows' final hidden state (S x B, E) float32, slot-major (the
    rows :func:`head` makes logits of: the engine makes those a caller reads,
    when it reads them), a block a slot (S, B): the one
    the call committed where it fused, else the open block as the pass leaves
    it; ``{"experts": (layers, held) tokens an expert got, "block": (units of B
    rows that went through for a request, those that were commits, masked query
    rows, commits that rode in a place) of the slots moved}`` and the cache's
    arrays; a slot whose pass found nothing masked has committed, and its state
    is a fresh block; a slot that fused has the block after it, one pass on."""
    from ..serve.engine import BlockSchedule

    B, S = c.block_length, lengths.shape[0]
    C = block_schedule(c).commit_places(S)
    kd, vd = arrays["k"], arrays["v"]
    held_ids, held_masked, held_pass = arrays["block_ids"][0], arrays["block_masked"][0], arrays["block_pass"][0]
    j = jnp.arange(B, dtype=lengths.dtype)
    start = lengths // B * B
    forced = tokens >= 0
    moving = active & (tokens != BlockSchedule.HOLD)
    here = forced[:, None] & (j[None, :] == (lengths - start)[:, None])
    # the slots that fuse, each to the place its rank among them names
    fuse = moving & (tokens == BlockSchedule.FUSED) & ~jnp.any(held_masked, axis=-1)
    rank = jnp.cumsum(fuse) - 1
    fuse = fuse & (rank < C)
    source = jnp.zeros((C,), jnp.int32).at[jnp.where(fuse, rank, C)].set(jnp.arange(S, dtype=jnp.int32), mode="drop")
    used = jnp.arange(C) < jnp.sum(fuse)
    # open rows: the slot's block as its state has it, or the block after the one it commits
    ids = jnp.where(fuse[:, None], c.mask_token_id, jnp.where(here, tokens[:, None], held_ids))
    masked = fuse[:, None] | (held_masked & ~here)
    passes = jnp.where(fuse, 0, held_pass)
    first = jnp.concatenate([start + B * fuse, start[source]])                      # (S + C,) of every B rows
    page = jnp.concatenate([jnp.where(moving, jnp.where(fuse, next_page, write_page), 0),
                            jnp.where(used, write_page[source], 0)])
    offset = jnp.concatenate([jnp.where(fuse, next_offset, write_offset), write_offset[source]])
    valid_len = jnp.concatenate([start + B * fuse + B, jnp.where(used, start[source] + B, 0)])
    tables = jnp.concatenate([table, table[source]])
    positions = first[:, None] + j[None, :]
    live = jnp.repeat(jnp.concatenate([moving, used]), B)
    x = embed(c, params, jnp.concatenate([ids, held_ids[source]]).reshape((S + C) * B))
    experts = []
    for l in range(c.num_hidden_layers):
        lp = params[f"layers_{l}"]
        step = lambda u, lp=lp, l=l: attention_pass(c, lp["self_attn"], u, kd, vd, layer=l, table=tables, page=page,
                                                    offset=offset, positions=positions, valid_len=valid_len,
                                                    interpret=kernels["decode"])
        x, kd, vd, n = layer_pass(c, lp, x, live, step)
        experts.append(n)
    hidden = x[: S * B]
    with jax.named_scope("vs.unmask"):
        _top, best, denominator = head_stats(c, params, hidden, kernels["head_select"])
        new_ids, new_masked = unmask(c, best.reshape(S, B), denominator.reshape(S, B), ids, masked, passes, moving & ~forced)
    commit = moving & ~jnp.any(masked, axis=-1)             # nothing was masked: the K and V just written are final
    counts = {"experts": jnp.stack(experts),
              "block": jnp.stack([jnp.sum(moving) + jnp.sum(fuse), jnp.sum(commit) + jnp.sum(fuse),
                                  jnp.sum(masked & moving[:, None]), jnp.sum(fuse)]).astype(jnp.int32)}
    keep, fresh = ~moving[:, None], commit[:, None]
    state = {"block_ids": jnp.where(keep, held_ids, jnp.where(fresh, c.mask_token_id, new_ids)),
             "block_masked": jnp.where(keep, held_masked, fresh | new_masked),
             "block_pass": jnp.where(moving, jnp.where(commit, 0, passes + 1), held_pass)}
    return hidden, jnp.where(fuse[:, None], held_ids, new_ids), counts, {
        "k": kd, "v": vd, **{name: value[None].astype(arrays[name].dtype) for name, value in state.items()}}


# counters of this model beside those every model's engine keeps (``HybridServeEngine.trace_counters``): the
# useful operations of the prefills' attention under the block mask (``prefill_counters``)
STEP_COUNTERS = ("prefill_attn_flops",)


def step_counters(config: SdarMoeConfig, cache, lengths: np.ndarray, counts: Dict[str, np.ndarray]) -> Dict[str, int]:
    return {}


def prefill_counters(config: SdarMoeConfig, bucket: int) -> Dict[str, int]:
    """What one prefill of ``bucket`` positions adds: attention's useful
    operations under the block mask (scores and values over the (query, key)
    pairs the mask keeps: half the square and half a block's width more)."""
    c = config
    pairs = bucket * (bucket + c.block_length) // 2
    return {"prefill_attn_flops": c.num_attention_heads * 4 * c.head_dim * pairs * c.num_hidden_layers}
