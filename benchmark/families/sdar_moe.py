"""The SDAR family (``"model": "sdar_moe"``, HF ``model_type`` ``sdar_moe``): a
Qwen3-MoE-shaped decoder (grouped-query attention with a per-head RMSNorm on
queries and keys, 128 routed experts top-8 renormalised, no shared expert, an
untied head) that GENERATES BY DIFFUSION OVER BLOCKS: attention is causal over
blocks of ``B`` positions and full inside one, the logits are not shifted, and a
block of ``B`` masked positions is denoised in ``T`` passes and committed by one
more; ``vescale_tpu/models/sdar_moe.py`` under
``vescale_tpu/serve/hybrid_engine.py`` in the program.  A family that only
serves.  The names are those ``benchmark/README.md`` ("Adding a family") fixes.

What a later family of this kind (a BLOCK ENGINE) needs to know beyond the README:

- **What the runner's surface means here.**  ``engine.prefill(prompt, slot)``
  returns the logits row of the prompt's LAST position (position ``n - 1``
  predicts itself: not the next token's row); ``engine.decode(tokens)`` in the
  host-token form is the decode program TEACHER-FORCED: the fed token is
  revealed at the slot's length ``L``, the rest of ``L``'s block stays masked,
  and ``step[slot]`` is the row of position ``L``; ``cache.advance(slot)`` then
  settles that position.  So ``logits(params, config, tokens, rows)`` of this
  file mirrors it: **row r is the reference's logits at position r of the
  sequence in which positions <= r hold their tokens and the rest of r's block
  holds the mask id, under the block mask**.  The runner's check (320 prompt
  tokens = 80 whole blocks, then four forced tokens) therefore reads the
  prefill's last row, three passes over a partly masked block and one commit
  pass; it never reads a committed GENERATED block back.  ``check_blocks``
  below does (the builder's scratch run and ``tests/test_sdar_moe.py`` call
  it): it generates whole blocks through the engine as the serve loop does and
  holds every pass's ``B`` rows, and every selection, to the reference.
- **The serve loop** learns from ``engine.block`` (a ``BlockSchedule``) that a
  step yields a count of tokens; the traffic's ``max_new_tokens`` are met
  exactly, the last block cut.  ``vocab`` is the mask id, 151669: prompts draw
  below it, so no prompt token is a mask; the head keeps its 151,936 rows.
- **The counters** (``HybridServeEngine.trace_counters``): those Granite's cell
  has (``moe_*`` count (position, expert) pairs of the slots a pass moved:
  ``B`` positions a slot; ``decode_pages_*`` the pages up to the end of the open
  block), ``block_passes``, ``block_commit_passes``, ``block_tokens_emitted``,
  ``block_positions_masked`` and ``prefill_attn_flops``.
  ``layer_metrics/blockdiff_serve_batch.py`` reads them with the counts at the
  end of this file.

The reference is straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the block mask as a dense ``(T, T)`` comparison of block indices, a
softmax over it (a block of heads at a time), the source's router (a softmax
over all 128, the 8 largest, renormalised), a loop over the experts; no
kernels, cache, buckets or batching, and nothing imported from the program.
It follows HF ``modeling_sdar_moe.py`` (Qwen3-MoE's layer with the mask handed
in) and ``generate`` follows the release's ``block_diffusion_generate`` under
``remasking_strategy="low_confidence_static"``, temperature 0, to the letter,
but for: full forwards in place of its cache (a block's passes see the settled
tokens before it through the block mask, which is what its cache holds); a tie
of confidences goes to the lower position (``torch.topk`` leaves it open); a
revealed position that took the mask id as its argmax stays revealed (the
source would mask it again).  The program's tree is read a layer, and inside a
layer an expert, at a time and cast inside each jitted call: a float32 copy of
the weights (17 GB) never exists.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.families import ServeSystem
from benchmark.spec import SpecError

# ----------------------------------------------------------------- tolerances
# All three as ``reference.rel_at_scale`` reads them: the largest difference as
# a share of the largest reference logit.  The program multiplies in bf16 with
# float32 accumulation (2^-9 = 2e-3 a rounded operand, some five products deep
# a sub-layer, six layers), keeps the residual stream, norms, rotary, router,
# softmax and confidence in float32, and rounds K and V to bf16 once; the
# reference reads the same bf16 weights.  The readings are PERF.md's (section
# 6, PR 36, my chip runs).
#
# 1. The runner's check (the prefill's last row, three teacher-forced passes
#    over a partly masked block, one commit pass).  Readings on the chip: the
#    program over thirteen seeds 3.4e-3 to 4.4e-3; the reference with its weights
#    in fp8 (e4m3), the nearest type below the one the configuration states,
#    3.2e-2 against the reference itself on the same rows, which this limit
#    fails 2.7-fold; a causal mask in place of the block mask 0.20, no q/k norm
#    5.1e-2; one kept expert fewer 1.2e-3, which it CANNOT tell from rounding
#    (the routed part is a few per cent of the stream by construction:
#    ``ROUTED_DOWN_GAIN`` in the model's file, PR 34's rule).  The limit lies a
#    factor 2.7 over the largest reading and 2.7 under fp8's.
SERVE_LOGITS_TOLERANCE = 1.2e-2
# 2. ``check_blocks``: every row of every pass of whole generated blocks, the
#    later ones against committed generated blocks read back from the cache.
#    The same arithmetic over the same depth, so the same limit.  Readings on
#    the chip (three seeds, 40-42 passes each over prompts of 18 to 320 tokens):
#    4.5e-3, 4.8e-3, 5.1e-3; fp8 weights 3.4e-2 to 4.6e-2; a causal mask 0.27; a
#    block settled WITHOUT its commit pass (its K and V are then those of a pass
#    in which its last position was still the mask) 8.2e-2 and 9.0e-2 after a
#    prompt of 18, 4.0e-2 and 5.1e-2 after one of 45, and 9.1e-3 after one of
#    202: one wrong position in two hundred reaches a row by a two-hundredth
#    (random attention is diffuse), so the check vouches for the commit pass on
#    short prompts, which is where it is run.
PASS_LOGITS_TOLERANCE = 1.2e-2
# 3. A selection (which masked position is revealed, and as which token) is
#    held to the reference's only where the reference's own margins are wider
#    than rounding can close: the chosen position's confidence over the
#    runner-up's, and the token's logit over the second's, each as a share of
#    the largest logit, against the pass's logits tolerance.  With random
#    weights the head's rows are nearly flat, so few selections qualify (a
#    dozen of a hundred at the published widths); every one that did agreed.
SELECTION_MARGIN = PASS_LOGITS_TOLERANCE


# --------------------------------------------------------------- the program
def program_config(config: Dict[str, Any], *, max_positions: int = 0, prefill_chunk: int = 128):
    """The program's ``SdarMoeConfig`` from a configuration file's object; the
    published keys go through unchanged, the sizes the source's config has no
    key for come from ``assumed`` (``block_length``, ``denoising_steps``,
    ``mask_token_id``).  ``max_positions`` sizes nothing."""
    from vescale_tpu.models.sdar_moe import SdarMoeConfig

    if config.get("rope_scaling") or config.get("use_sliding_window") or config.get("sliding_window"):
        raise SpecError("this family's rotary is plain and its attention has no window")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise SpecError("this family has no attention bias and an untied head")
    if config.get("decoder_sparse_step") != 1 or config.get("mlp_only_layers") or not config.get("norm_topk_prob"):
        raise SpecError("every layer of this family is an expert layer, and its gates are renormalised over the kept")
    if config.get("hidden_act") != "silu":
        raise SpecError("this family's experts are SwiGLU")
    assumed = config.get("assumed") or {}
    if assumed.get("remasking") != "low_confidence_static" or not assumed.get("greedy") or not assumed.get("qk_norm"):
        raise SpecError("the program generates by the static low-confidence schedule, greedy, with normed queries and "
                        "keys: the file states them under assumed")
    return SdarMoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"], num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"], num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"], experts_held=config["num_experts"], first_expert_held=0,
        rope_theta=float(config["rope_theta"]), rms_norm_eps=float(config["rms_norm_eps"]),
        block_length=int(assumed["block_length"]), denoising_steps=int(assumed["denoising_steps"]),
        mask_token_id=int(assumed["mask_token_id"]), prefill_chunk=int(prefill_chunk), dtype=jnp.bfloat16)


def _cache_config(cfg, serve: Dict[str, Any]):
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

    return hybrid_cache_config(cfg, num_slots=int(serve["slots"]), page_size=int(serve["page_size"]),
                               pages_per_slot=int(serve["positions_per_slot"]) // int(serve["page_size"]))


def _serve_config(config: Dict[str, Any], serve: Dict[str, Any]):
    if serve["weight_dtype"] != "bfloat16":
        raise ValueError("serve cells hold their weights in bfloat16")
    try:
        return program_config(config, prefill_chunk=int(serve.get("prefill_chunk", 128)))
    except ImportError as e:
        raise RuntimeError(f"this checkout's program cannot run the sdar_moe family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a paged K/V cache with a slot's open block beside
    it; ``HybridServeEngine`` over the model's module with every rung and the
    pass compiled.  ``vocab`` is the mask id: ids are drawn below it."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.sdar_moe import init_params
    from vescale_tpu.serve import HybridServeEngine, PagedKVCache

    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))
    cache = PagedKVCache(_cache_config(cfg, serve), mesh)
    return ServeSystem(params, cache, HybridServeEngine(cfg, mesh, params, cache).warm(), cfg.mask_token_id)


def rehearse_serve(name: str, config: Dict[str, Any], serve: Dict[str, Any], devices):
    """Every prefill rung and the pass, lowered for described devices: shapes
    where the cache would allocate (two functions patched for the duration,
    here, not in the program)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.sdar_moe import init_params
    from vescale_tpu.serve import HybridServeEngine, PagedKVCache
    from vescale_tpu.serve import kv_cache as kv_cache_module

    cfg = _serve_config(config, serve)
    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    replicated = NamedSharding(mesh.jax_mesh, P())
    shaped = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated)
    params = jax.tree_util.tree_map(shaped, jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0)))

    def pool_shapes(cache_spec):
        return jax.ShapeDtypeStruct(cache_spec.layout().physical_shape, cache_spec.dtype,
                                    sharding=cache_spec.named_sharding())

    def state_shapes(shape, dtype, _mesh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)

    with mock.patch.object(kv_cache_module, "_zeros_global", pool_shapes), \
            mock.patch.object(kv_cache_module, "_zeros_replicated", state_shapes):
        cache = PagedKVCache(_cache_config(cfg, serve), mesh)
        engine = HybridServeEngine(cfg, mesh, params, cache)
    S, page = cache.num_slots, cache.config.page_size
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)
    nbytes = lambda a: int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
    sizes = {"weights_bytes": sum(nbytes(a) for a in jax.tree_util.tree_leaves(params)),
             "kv_pool_bytes": nbytes(cache.k.data) + nbytes(cache.v.data),
             "slot_state_bytes": sum(nbytes(a) for a in cache.state.values())}
    held = tuple(cache.arrays().values())
    programs = [(f"{name}: prefill, rung of {b} positions, depth {cfg.num_hidden_layers}",
                 engine._prefill_fn.lower(params, *held, i32(b), i32(), i32(b // page), i32()))
                for b in engine.buckets]
    programs.append((f"{name}: one pass, {S} slots x {cfg.block_length} positions over {cache.max_seq_len} a slot",
                     engine._decode_fn.lower(params, *held, i32(S, cache.config.pages_per_slot), i32(S), i32(S))))
    return sizes, programs


# ------------------------------------------------------------- the reference
F32 = jnp.float32
HEAD_BLOCK = 8          # heads whose (T, T) scores exist at once
# what a wrong computation reads (``wrong=``: the tolerances' reasons, the tests, the builder's chip readings)
FAULTS = ("causal_mask", "fp8_weights", "top7", "no_qk_norm")


def _weights(wrong: str):
    """How a weight is read: as float32, or (the fault ``fp8_weights``: the
    nearest type below the one the configuration states) rounded to e4m3 first."""
    if wrong == "fp8_weights":
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(F32) if a.ndim >= 2 else a.astype(F32)
    return lambda a: a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "eps", "block", "wrong"))
def attention(ap: Dict[str, Any], u, *, heads: int, kv_heads: int, head_dim: int, theta: float, eps: float,
              block: int, wrong: str = ""):
    """``SDARMoeAttention`` over one sequence ``u`` (T, E) from position 0,
    float32, under the block mask as a dense (T, T) comparison, ``HEAD_BLOCK``
    heads at a time."""
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        q = (u @ f(ap["q_proj"])).reshape(T, heads, head_dim)
        k = (u @ f(ap["k_proj"])).reshape(T, kv_heads, head_dim)
        v = (u @ f(ap["v_proj"])).reshape(T, kv_heads, head_dim)
        if wrong != "no_qk_norm":
            q, k = _rmsnorm(q, ap["q_norm"], eps), _rmsnorm(k, ap["k_norm"], eps)
        position = jnp.arange(T, dtype=F32)
        inv_freq = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim)
        emb = jnp.concatenate([position[:, None] * inv_freq[None, :]] * 2, axis=-1)[:, None, :]     # (T, 1, hd)
        q = q * jnp.cos(emb) + _rotate_half(q) * jnp.sin(emb)
        k = k * jnp.cos(emb) + _rotate_half(k) * jnp.sin(emb)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))                           # repeat_kv
        of = jnp.arange(T) // (1 if wrong == "causal_mask" else block)
        sees = (of[None, :] <= of[:, None])[None]                                                    # (1, T, T)

        def some_heads(args):
            qb, kb, vb = args                                                                        # (hb, T, hd)
            s = jnp.einsum("hqd,hkd->hqk", qb, kb) * head_dim ** -0.5
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(sees, s, -jnp.inf), axis=-1), vb)

        hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else 1
        split = lambda a: a.transpose(1, 0, 2).reshape(heads // hb, hb, T, head_dim)
        o = jax.lax.map(some_heads, (split(q), split(k), split(v)))
        return o.reshape(heads, T, head_dim).transpose(1, 0, 2).reshape(T, heads * head_dim) @ f(ap["o_proj"])


@functools.partial(jax.jit, static_argnames=("k",))
def _route(router, h, *, k: int):
    """``SDARMoeSparseMoeBlock``'s gate: a float32 softmax over ALL experts, the
    ``k`` largest, renormalised to sum 1 (``norm_topk_prob``)."""
    with jax.default_matmul_precision("highest"):
        weights, idx = jax.lax.top_k(jax.nn.softmax(h @ router.astype(F32), axis=-1), k)
        return idx, weights / jnp.sum(weights, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("wrong",))
def _swiglu(h, w_gate, w_up, w_down, wrong: str = ""):
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def expert_layer(ep: Dict[str, Any], h, *, k: int, wrong: str = ""):
    """``sum over the kept e of g_e E_e(h)``: every expert on every token,
    weighted by the gate it has there (0 where it is not among the token's
    ``k``); ``top7`` (a fault) keeps one fewer."""
    idx, gates = _route(ep["router"], h, k=k - 1 if wrong == "top7" else k)
    out = jnp.zeros_like(h)
    for e in range(ep["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(h, ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e], wrong=wrong)
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, *, eps: float):
    return _rmsnorm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps", "wrong"))
def _head(norm_w, kernel, x, *, eps: float, wrong: str = ""):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_w, eps) @ _weights(wrong)(kernel)


def _sizes(config: Dict[str, Any]) -> Tuple[int, int]:
    assumed = config["assumed"]
    return int(assumed["block_length"]), int(assumed["mask_token_id"])


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], ids: Sequence[int], wrong: str = ""):
    """The residual stream after the last layer for the sequence ``ids`` AS IT
    IS (mask ids included), (T, E) float32, under the block mask.  ``wrong``
    (one of ``FAULTS``) computes a wrong model on the same weights."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong is one of {FAULTS}")
    eps = float(config["rms_norm_eps"])
    attn = dict(heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
                theta=float(config["rope_theta"]), eps=eps, block=_sizes(config)[0], wrong=wrong)
    x = _weights(wrong)(jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(ids, np.int32)), axis=0))
    for l in range(config["num_hidden_layers"]):
        lp = params[f"layers_{l}"]
        x = x + attention(lp["self_attn"], _norm(lp["input_layernorm"]["weight"], x, eps=eps), **attn)
        x = x + expert_layer(lp["mlp"], _norm(lp["post_attention_layernorm"]["weight"], x, eps=eps),
                             k=config["num_experts_per_tok"], wrong=wrong)
    return x


def sequence_logits(params: Dict[str, Any], config: Dict[str, Any], ids: Sequence[int], rows: Sequence[int],
                    wrong: str = ""):
    """Logits (float32, not shifted) of the sequence ``ids`` as it is, at the positions ``rows``."""
    x = hidden_states(params, config, ids, wrong)[jnp.asarray(np.asarray(rows, np.int32))]
    return _head(params["norm"]["weight"], params["lm_head"]["kernel"], x, eps=float(config["rms_norm_eps"]), wrong=wrong)


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int], wrong: str = ""):
    """The runner's rows (the module docstring's contract): row ``r`` is
    position ``r``'s logits in the sequence ``tokens[: r + 1]`` followed by mask
    ids to the end of ``r``'s block.  One forward a row that is not its block's
    last; the rows that are (the block is whole) share one."""
    B, mask_id = _sizes(config)
    tokens, out = [int(t) for t in tokens], {}
    whole = [r for r in rows if (r + 1) % B == 0]
    if whole:
        got = sequence_logits(params, config, tokens[: max(whole) + 1], whole, wrong)
        out.update(zip(whole, got))
    for r in rows:
        if r not in out:
            ids = tokens[: r + 1] + [mask_id] * (B - 1 - r % B)
            out[r] = sequence_logits(params, config, ids, [r], wrong)[0]
    return jnp.stack([out[r] for r in rows])


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# ---------------------------------------------- the reference's generation
def block_logits(params, config, settled: Sequence[int], block: Sequence[int], wrong: str = ""):
    """The ``B`` rows of one pass: the block's ids as they stand (mask ids
    where masked) after the ``settled`` tokens (whole blocks), by a full forward."""
    n = len(settled)
    return sequence_logits(params, config, list(settled) + list(block), range(n, n + len(block)), wrong)


def transfers(B: int, T: int, k: int) -> int:
    """``get_num_transfer_tokens(B, T)[k]``."""
    return B // T + (k < B % T)


def decide(lg: np.ndarray, masked: np.ndarray, count: int):
    """One step of the static low-confidence schedule on a pass's logits (B,
    vocab): every position's greedy token and its confidence, the ``count``
    most confident masked positions (ties to the lower), and the margins that
    say how safe that reading is against rounding, each as a share of the
    largest logit: the last chosen position's confidence over the first
    unchosen's, and over the chosen positions the least gap between a token's
    logit and the second's."""
    lg = np.asarray(lg, np.float64)
    scale = np.max(np.abs(lg)) or 1.0
    best = np.argmax(lg, axis=-1)
    top = np.max(lg, axis=-1)
    confidence = 1.0 / np.sum(np.exp(lg - top[:, None]), axis=-1)
    order = sorted(np.flatnonzero(masked), key=lambda j: (-confidence[j], j))
    chosen, rest = order[:count], order[count:]
    second = np.sort(lg, axis=-1)[:, -2]
    token_margin = min(((top[j] - second[j]) / scale for j in chosen), default=np.inf)
    # confidences are probabilities: compare them through the logits' gap that would close them
    position_margin = np.inf
    if chosen and rest:
        position_margin = abs(np.log(confidence[chosen[-1]]) - np.log(confidence[rest[0]])) / scale
    return best, confidence, chosen, float(position_margin), float(token_margin)


def generate(params, config, prompt: Sequence[int], max_new_tokens: int, wrong: str = ""):
    """``block_diffusion_generate`` with ``remasking_strategy =
    "low_confidence_static"``, greedy, by full forwards.  Returns the
    ``max_new_tokens`` generated tokens and the passes in order: ``(first
    position of the block, the block's ids going in, logits (B, vocab), commit?)``."""
    B, mask_id = _sizes(config)
    T = int(config["assumed"]["denoising_steps"])
    x = [int(t) for t in prompt]
    n, total = len(x), -(-(len(x) + max_new_tokens) // B) * B
    x += [mask_id] * (total - n)
    passes = []
    for start in range(n // B * B, total, B):
        cur = x[start: start + B]
        masked = np.asarray([start + j >= n for j in range(B)]) if start < n else np.ones((B,), bool)
        for k in range(T + 1):
            lg = np.asarray(block_logits(params, config, x[:start], cur, wrong))
            if not masked.any():
                passes.append((start, list(cur), lg, True))       # the pass that stores the block's K and V
                break
            passes.append((start, list(cur), lg, False))
            best, _conf, chosen, _pm, _tm = decide(lg, masked, min(transfers(B, T, k), int(masked.sum())))
            for j in chosen:
                cur[j], masked[j] = int(best[j]), False
        x[start: start + B] = cur
    return x[n: n + max_new_tokens], passes


def check_blocks(engine, config: Dict[str, Any], prompts: Dict[int, Sequence[int]], blocks: int, wrong: str = "",
                 skip_commit_of: Optional[int] = None) -> Dict[str, Any]:
    """Generate ``blocks`` whole blocks for the prompts ``{slot: prompt}``
    through ``engine`` as the serve loop does (one program call a pass, the
    slots at whatever pass their block is at), and hold EVERY pass to the
    reference: the pass's ``B`` rows of logits against ``block_logits`` of the
    same ids (the program's own trajectory is followed, so one flipped argmax
    does not unhinge what comes after), and its selection against ``decide`` on
    the reference's logits wherever both of the reference's margins exceed
    ``SELECTION_MARGIN``.  The slots must be free in ``engine.cache``; it is
    reset at the end.  ``skip_commit_of`` (a fault, for the tests and the
    builder's readings: a slot) settles that slot's FIRST block without its
    commit pass.  Returns the worst logits error, the passes compared and what
    disagreed."""
    from vescale_tpu.serve.engine import DecodeFeed

    cache, schedule = engine.cache, engine.block
    B, mask_id = _sizes(config)
    state, settled, done = {}, {}, {}
    for slot, prompt in prompts.items():
        got = cache.alloc(len(prompt), blocks * B, slot=slot)
        assert got == slot
        engine.prefill(list(prompt), slot)
        cache.commit_prefill(slot, len(prompt))
        state[slot], settled[slot], done[slot] = schedule.open(len(prompt)), [int(t) for t in prompt], 0
    worst, compared, selections, disagreed, skipped = 0.0, 0, 0, [], False
    while any(done[s] < blocks for s in prompts):
        moving = [s for s in prompts if done[s] < blocks]
        before = {s: np.asarray(cache.state["block_ids"][0, s]) for s in moving}
        masked = {s: np.asarray(cache.state["block_masked"][0, s]) for s in moving}
        if skip_commit_of in moving and not skipped and not masked[skip_commit_of].any():
            # the fault: the block is taken as settled with the K and V its last denoising pass left
            slot, skipped = skip_commit_of, True
            _skip, _count, positions = schedule.plan(state[slot], B)
            fresh = {"block_ids": cache.state["block_ids"].at[0, slot].set(mask_id),
                     "block_masked": cache.state["block_masked"].at[0, slot].set(True),
                     "block_pass": cache.state["block_pass"].at[0, slot].set(0)}
            cache.update_state(**fresh)
            cache.advance(slot, positions)
            first = len(settled[slot]) // B * B
            settled[slot] = settled[slot][:first] + [int(t) for t in before[slot]]
            done[slot] += 1
            continue
        plans = {s: schedule.plan(state[s], B) for s in moving}
        passes_done = {s: int(cache.state["block_pass"][0, s]) for s in moving}
        step = engine.decode(DecodeFeed(None, slots={s: plans[s][1] for s in moving}))
        after = step.tokens
        for s in moving:
            first = len(settled[s]) // B * B
            want = np.asarray(block_logits(engine.params, config, settled[s][:first], before[s], wrong))
            got = step.block(s)
            worst = max(worst, reference.rel_at_scale(got, want))
            compared += 1
            if masked[s].any():
                count = min(transfers(B, schedule.T, passes_done[s]), int(masked[s].sum()))
                best, _conf, chosen, position_margin, token_margin = decide(want, masked[s], count)
                if min(position_margin, token_margin) > SELECTION_MARGIN:
                    selections += 1
                    expect = before[s].copy()
                    expect[chosen] = best[chosen]
                    if not np.array_equal(expect, after[s]):
                        disagreed.append((s, first, expect.tolist(), after[s].tolist()))
            else:
                if not np.array_equal(before[s], after[s]):
                    disagreed.append((s, first, before[s].tolist(), after[s].tolist()))
                settled[s] = settled[s][:first] + [int(t) for t in after[s]]
                done[s] += 1
            cache.advance(s, plans[s][2])
    cache.reset()
    return {"logits_max_abs_diff_over_max": worst, "passes": compared, "selections_held": selections,
            "disagreed": disagreed, "tolerance": PASS_LOGITS_TOLERANCE}


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic, so that no later PR moves a share by
# recounting.  Norm weights are counted where bytes are.
def attention_params(c: Dict[str, Any]) -> int:
    E, hd = c["hidden_size"], c["head_dim"]
    return E * hd * (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"])


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Every parameter of the cut: embedding and head apart (untied)."""
    E = c["hidden_size"]
    norms = c["num_hidden_layers"] * (2 * E + 2 * c["head_dim"]) + E
    return (c["num_hidden_layers"] * (attention_params(c) + router_params(c) + c["num_experts"] * expert_params(c))
            + 2 * c["vocab_size"] * E + norms)


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but the routers (float32)."""
    return 2 * param_count(c) + 2 * c["num_hidden_layers"] * router_params(c)


def kv_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What a position leaves in the cache, all layers: K and V of every key head."""
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def slot_state_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """The open blocks: B ids (int32) and B masks (a byte) a slot, and its pass."""
    B = int(c["assumed"]["block_length"])
    return int(serve["slots"]) * (4 * B + B + 4)


def logits_bytes_per_pass(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    return int(serve["slots"]) * int(c["assumed"]["block_length"]) * c["vocab_size"] * 4


def pass_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, kv_pages_read_per_layer: float,
               experts_touched: float = None) -> float:
    """The bytes one pass must move: every weight held once (of the experts
    those that got a position: all, where ``experts_touched``, the count over
    all layers, is not given), the live K and V pages of every layer with the
    open block's, the logits written and read once (the selection reads them)."""
    layers = c["num_hidden_layers"]
    touched = layers * c["num_experts"] if experts_touched is None else experts_touched
    weights = weight_bytes(c) - 2 * expert_params(c) * (layers * c["num_experts"] - touched)
    kv = kv_pages_read_per_layer * int(serve["page_size"]) * kv_bytes_per_position(c)
    return weights + kv + 2 * logits_bytes_per_pass(c, serve)


def block_prefill_attention_flops(c: Dict[str, Any], bucket: int) -> float:
    """Attention of one layer over ``bucket`` positions under the block mask:
    scores and values (2 x 2 x head_dim a pair and head) over the (query, key)
    pairs the mask keeps, half the square and half a block's width more."""
    B = int(c["assumed"]["block_length"])
    return c["num_attention_heads"] * 4.0 * c["head_dim"] * bucket * (bucket + B) / 2.0


def block_prefill_attention_bytes(c: Dict[str, Any], bucket: int, itemsize: int = 2) -> float:
    """... and what it must move: queries and outputs of every head, keys and
    values of every key head, once."""
    return (2 * c["num_attention_heads"] + 2 * c["num_key_value_heads"]) * bucket * c["head_dim"] * float(itemsize)


def pass_attention_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """The decode attention of one layer: K and V of one cached position."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def pass_attention_flops_per_position(c: Dict[str, Any]) -> float:
    """... and its operations for one cached position: every head's score and
    its share of the mix, for each of the block's B queries."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * int(c["assumed"]["block_length"])


def prefill_rungs(serve: Dict[str, Any]) -> List[int]:
    """The engine's prefill ladder (``serve/engine.py:prefill_buckets``'s rule,
    written again because the benchmark imports no arithmetic of the program)."""
    top, rungs, b = int(serve["positions_per_slot"]), [], int(serve.get("prefill_chunk", 128))
    while b < top:
        steps = (b // 4, b // 2, 3 * b // 4) if b >= 4096 else (b // 2,) if b >= 1024 else ()
        rungs += [b] + [b + step for step in steps if b + step < top]
        b *= 2
    return rungs + [top]


# ------------------------------------------ which mechanism a device op is of
# As families/granite_hybrid.py: the chip's trace names a device event by its
# whole HLO instruction and carries no scope, so the table is of shapes, from
# the configuration alone.  An op belongs to the first mechanism one of whose
# signatures its text shows: the routed experts first (their arrays lead with
# the expert count, or with positions x experts a position of a pass or of some
# rung), then head-to-selection (everything as wide as the vocabulary, and the
# pass's (slots, B) decisions), then attention.
MECHANISMS = ("experts", "unmask", "attention")


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any]) -> Dict[str, Sequence[str]]:
    """For each mechanism, the substrings (kernel names, or runs of dimensions
    as an HLO shape prints them) that only its ops show."""
    S, B = int(serve["slots"]), int(c["assumed"]["block_length"])
    E, H, KV, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    X, F, k, V = c["num_experts"], c["moe_intermediate_size"], c["num_experts_per_tok"], c["vocab_size"]
    N = S * B
    tokens = [N] + prefill_rungs(serve)
    # (two-dimensional ones, but for a pass's own sorted vector: a rung's 256 x 8 is as long as the hidden size)
    pairs = [f"[{N * k}]"] + [t for n in tokens for t in (f"[{n * k},{E}]", f"[{n * k},{F}]", f"[{n},{k},", f"[{n},{k}]",
                                                          f"[{n},{X}]", f"[{n},{X + 1}]")]
    return {
        # (the experts' own arrays by their three dimensions: as many slots as experts, and a head as wide as
        # the router, would answer to the expert count alone)
        "experts": ("ragged-dot", f"[{X},{E},{F}]", f"[{X},{F},{E}]", f"[{E},{X}]", f"[{X + 1}]", *pairs),
        "unmask": (f",{V}]", f"[{V},{E}]", f"[{S},{B}]", f"[{S},{B},{B}]"),
        "attention": ("paged_decode", "block_flash_fwd", f",{H * hd}]", f"[{H * hd},{E}]", f",{KV * hd}]", f",{H},{hd}]",
                      f",{KV},{hd}]", f",{hd}]", f",{hd // 2}]", f"[{S},{B * H},", f",{B},{H // KV},"),
    }


def mechanism_of(op_text: str, signatures: Dict[str, Sequence[str]]) -> str:
    """One of ``MECHANISMS``, or ``other`` (norms and sums of the residual
    stream, the embedding's gather, small copies) for a device event's name."""
    for mechanism in MECHANISMS:
        if any(s in op_text for s in signatures[mechanism]):
            return mechanism
    return "other"
