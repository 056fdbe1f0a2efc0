"""The LongCat-Flash family (``"model": "longcat_flash"``; the language model of
``meituan-longcat/LongCat-Flash-Omni``, whose config the catalog's row holds):
a layer of TWO latent-attention (MLA) sublayers and two dense SwiGLUs whose
routed branch is computed from the FIRST sublayer's normed stream and added at
the layer's END (the shortcut), over a float32 softmax router of 768 outputs of
which the last 256 are zero-compute IDENTITY experts: 12 a token under a
selection bias, gates not renormalised, times 6; no shared expert, an untied
head; ``vescale_tpu/models/longcat_flash.py`` (the block itself is
``models/mla.py``'s) under ``vescale_tpu/serve/hybrid_engine.py`` in the program.
A family that only serves.  The names are those ``benchmark/README.md`` ("Adding
a family") fixes.  The audio and vision encoders and the codec decoder of the
release are left out: no key of the row's config describes them.

What a reader of this family needs beyond the README:

- **Sublayers.**  A model layer owns two layers of the latent pool (sublayer
  ``i`` of layer ``l`` is pool layer ``2 l + i``), so the cache holds ``2 x
  num_layers`` layers and every "x layers" below counts SUBLAYERS.
- **A chip's share** (``"share": {"chips": 32, "of": ["n_routed_experts",
  "vocab_size"]}``): ``n_routed_experts`` and ``vocab_size`` are what is held
  HERE (experts ``0 .. held - 1``, rows ``0 .. vocab_size - 1``), the source's
  values are under ``published``; the router keeps its 512 + 256 outputs and its
  12 a token.  A pair on an expert held elsewhere adds nothing, in program and
  reference alike; the identity part needs no weight and no exchange and is
  computed whole on every chip (in the sum over shares it counts once).
- **The two forms.**  The program prefills in the expanded form and decodes in
  the absorbed one; the reference below is the expanded form ONLY, so the
  runner's check (a 320-token prefill in the 512 rung, then four decode steps
  through the latent cache) holds the absorbed algebra, the multipliers as the
  cache keeps them, the rows' pool layers and the pad rule to the source's.
- **The counters** (``HybridServeEngine.trace_counters``): the engine's
  (``decode_pages_*`` are ONE pool layer's pages; ``moe_assignments`` counts all
  12 pairs a token) and the model's own ``latent_bytes_read``,
  ``prefill_attn_flops``, ``zero_expert_assignments``.
  ``layer_metrics/longcat_serve_reasoning.py`` reads them with the counts at the
  end of this file.

The reference is straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: a dense causal softmax (a block of heads at a time), a loop over the
held experts, the identity part written out; no kernel, cache, rung or batching,
and nothing imported from the program.  It follows the release's model code
(``LongcatFlashDecoderLayer``, ``LongcatFlashMLA``, ``LongcatFlashTopkRouter``,
``LongcatFlashMoE``) as the configuration's ``assumed`` reads it; the init rule
is the program's (the reference reads the program's tree).  The tree is read a
layer, and inside a layer an expert, at a time and cast inside each jitted call:
a float32 copy of the weights (21 GB) never exists.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.families import ServeSystem
from benchmark.spec import SpecError

# ------------------------------------------------------------------ tolerance
# As ``reference.rel_at_scale`` reads it: the largest difference as a share of
# the largest reference logit, over the runner's rows (a prefill of 320 tokens in
# the 512 rung and four teacher-forced decode steps through the latent cache)
# against the reference's full float32 forward in the expanded form.  The program
# multiplies in bf16 with float32 accumulation (2^-9 = 2e-3 a rounded operand,
# some six products deep a sublayer, sixteen sublayers and dense SwiGLUs deep at
# four layers), keeps the residual stream, norms, multipliers, rotary, router,
# selection bias, gates, the identity part and softmax in float32, and rounds a
# cached row to bf16 once (after its multiplier); the reference reads the same
# bf16 weights.  Two things set the sound program's reading: rounding (3.6e-3 to
# 6.8e-3 here), and a (token, output) pair that rounding moves across the
# router's cut: the kept outputs' gates are 6 p with p about 0.013 at the cut, so
# a pair that swaps an identity output for a real expert held elsewhere moves
# that token's stream by 0.08 h, which reads 1e-2 to 2e-2 of the largest logit
# and met about one row in sixty.  Readings on the chip at the published widths
# (PERF.md section 6, PR 54, my chip runs; fifteen seeds at the runner's lengths,
# and a prefill of 1,100 tokens on the 1,536 rung, whose routed branch goes in
# two pieces, with 40 decode steps, every row compared, at five seeds):
#
#   the sound program     the runner's lengths 3.6e-3 to 6.8e-3 (fifteen seeds, 75 rows,
#                         no pair across the cut among them); the long check 3.6e-3,
#                         6.7e-3, 1.04e-2, 2.00e-2, 2.04e-2 (205 rows: a pair across the
#                         cut on three of them)
#   fp8_weights           4.0e-2 to 5.8e-2 at the runner's lengths (fifteen seeds), 4.9e-2
#                         to 7.2e-2 on the long check: the reference with its weights in
#                         e4m3, the nearest type below the one the configuration states
#   no_q_multiplier       0.30, 0.31          no_kv_multiplier      0.39, 0.41
#   renormalised_gates    0.33, 0.36          no_scaling_factor     0.41, 0.49
#   no_identity           0.61, 0.70          identity_sign         1.18, 1.37
#   shortcut_returns_at_once 0.13, 0.15       shortcut_from_second  0.22, 0.35
#   rotary_halves         0.26, 0.26          no_selection_bias     1.5e-2 to 7.7e-2
#   bias_weighs           1.1e-2 to 3.4e-2    top11                 0.9e-2 to 5.3e-2:
#                         the last two move a pair or two a row, which is what rounding
#                         does at the cut too, and CANNOT be told from it by one check
#                         (the CPU tests hold them at float32, where nothing is rounded)
#
# With every matrix of the attention at variance 1 / fan-in the sound program
# read 0.26 (the LoRA multipliers make scores of deviation 5.8, and bfloat16
# rounding under so peaked a softmax is several per cent of a probability): the
# model's init rule draws ``W_qb``, ``W_uk`` and ``W_uv`` as narrow as their
# multiplier is large (``SCORE_DEVIATION`` in the model's file), and the
# readings above are under that rule.  The limit lies 1.5 times over the largest
# sound reading on any row (4.4 times over the largest at the runner's lengths)
# and 1.33 times under fp8's smallest.
SERVE_LOGITS_TOLERANCE = 3e-2

SHARED_KEYS = ("n_routed_experts", "vocab_size")
# the published keys whose values this family's block fixes: a file that says otherwise is another architecture
FIXED = {"attention_bias": False, "attention_method": "MLA", "zero_expert_type": "identity", "mla_scale_q_lora": True,
         "mla_scale_kv_lora": True}
# ... and the readings of what the row's config names but does not settle, as the file must state them under ``assumed``
ASSUMED = {"mla_scale_values": "(hidden_size/rank)**0.5", "zero_expert": "gate*input", "norm_topk_prob": False,
           "router_bias_term": False, "score_scale": "qk_head_dim**-0.5", "rotary_pairs": "interleaved"}


# --------------------------------------------------------------- the program
def _share(config: Dict[str, Any]):
    """(real experts in the model, experts held, first held id): the file's share."""
    share, published = config.get("share") or {}, config.get("published", {})
    for key in SHARED_KEYS:
        if key in config.get("reduced", ()) and key not in share.get("of", ()):
            raise SpecError(f"{key} is cut from {published.get(key)} to {config[key]}: the file must state the share "
                            "it is (share.of), a smaller model is not this family's")
    if set(share.get("of", ())) - set(SHARED_KEYS):
        raise SpecError(f"this family divides {SHARED_KEYS} over chips, not {share['of']}")
    total = int(published.get("n_routed_experts", config["n_routed_experts"]))
    held = int(config["n_routed_experts"])
    index = int(share.get("index", 0))
    if "n_routed_experts" in share.get("of", ()) and held * int(share["chips"]) != total:
        raise SpecError(f"{share['chips']} chips with {held} experts each do not hold the model's {total}")
    return total, held, index * held


def program_config(config: Dict[str, Any], *, max_positions: int = 0, prefill_chunk: int = 128):
    """The program's ``LongcatFlashConfig`` from a configuration file's object;
    the published keys go through unchanged.  ``n_routed_experts`` is what this
    chip HOLDS, the router's width is the ``published`` count plus
    ``zero_expert_num``.  ``max_positions`` sizes nothing (the rotary angles are
    computed from the positions)."""
    from vescale_tpu.models.longcat_flash import LongcatFlashConfig

    for key, value in FIXED.items():
        if config.get(key) != value:
            raise SpecError(f"this family's block has {key} = {value!r}; the file says {config.get(key)!r}")
    assumed = config.get("assumed") or {}
    for key, value in ASSUMED.items():
        if assumed.get(key) != value:
            raise SpecError(f"the program reads {key} as {value!r} (the source's config does not settle it): the file "
                            f"states it under assumed, and says {assumed.get(key)!r}")
    total, held, first = _share(config)
    return LongcatFlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"], num_layers=config["num_layers"],
        ffn_hidden_size=config["ffn_hidden_size"], expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_experts=total, zero_expert_num=config["zero_expert_num"], num_experts_per_tok=config["moe_topk"],
        routed_scaling_factor=float(config["routed_scaling_factor"]), experts_held=held, first_expert_held=first,
        num_attention_heads=config["num_attention_heads"], q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        mla_scale_q_lora=bool(config["mla_scale_q_lora"]), mla_scale_kv_lora=bool(config["mla_scale_kv_lora"]),
        rope_theta=float(config["rope_theta"]), rms_norm_eps=float(config["rms_norm_eps"]),
        prefill_chunk=int(prefill_chunk), dtype=jnp.bfloat16)


def _cache_config(cfg, serve: Dict[str, Any]):
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

    return hybrid_cache_config(cfg, num_slots=int(serve["slots"]), page_size=int(serve["page_size"]),
                               pages_per_slot=int(serve["positions_per_slot"]) // int(serve["page_size"]),
                               num_pages=int(serve["pool_pages"]) if serve.get("pool_pages") else None)


def _serve_config(config: Dict[str, Any], serve: Dict[str, Any]):
    if serve["weight_dtype"] != "bfloat16":
        raise ValueError("serve cells hold their weights in bfloat16")
    try:
        return program_config(config, prefill_chunk=int(serve.get("prefill_chunk", 128)))
    except ImportError as e:
        raise RuntimeError(f"this checkout's program cannot run the longcat_flash family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a latent paged cache of two pool layers a model
    layer; ``HybridServeEngine`` with every rung and the decode step compiled."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.longcat_flash import init_params
    from vescale_tpu.serve import HybridServeEngine, PagedKVCache

    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))
    cache = PagedKVCache(_cache_config(cfg, serve), mesh)
    return ServeSystem(params, cache, HybridServeEngine(cfg, mesh, params, cache).warm(), cfg.vocab_size)


def rehearse_serve(name: str, config: Dict[str, Any], serve: Dict[str, Any], devices):
    """Every prefill rung and the decode step, lowered for described devices:
    shapes where the cache would allocate (one function patched for the
    duration, here, not in the program)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.longcat_flash import init_params
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

    with mock.patch.object(kv_cache_module, "_zeros_global", pool_shapes):
        cache = PagedKVCache(_cache_config(cfg, serve), mesh)
        engine = HybridServeEngine(cfg, mesh, params, cache)
    S, page = cache.num_slots, cache.config.page_size
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)
    nbytes = lambda a: int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
    sizes = {"weights_bytes": sum(nbytes(a) for a in jax.tree_util.tree_leaves(params)),
             "kv_pool_bytes": nbytes(cache.k.data), "slot_state_bytes": 0}
    held = tuple(cache.arrays().values())
    programs = [(f"{name}: prefill, rung of {b} positions, depth {cfg.num_layers} x 2 sublayers",
                 engine._prefill_fn.lower(params, *held, i32(b), i32(), i32(b // page), i32()))
                for b in engine.buckets]
    programs.append((f"{name}: decode step, {S} slots x {cache.max_seq_len} positions",
                     engine._decode_fn.lower(params, *held, i32(S, cache.config.pages_per_slot), i32(S), i32(S))))
    return sizes, programs


# ------------------------------------------------------------- the reference
F32 = jnp.float32
HEAD_BLOCK = 8          # heads whose (T, T) scores exist at once
SUBLAYERS = 2
# what a wrong computation reads (``wrong=``: the tolerance's reasons, the tests, the builder's chip readings): the
# weights in the nearest type below the one the configuration states; either LoRA multiplier left out; the gates
# renormalised over the kept twelve; the factor 6 forgotten; the identity experts' part left out, or with its sign
# turned; the selection bias weighing (the gates taken of p + b), or left out of the choice; the routed branch added
# where it leaves (before the second sublayer sees the stream) or computed from the SECOND sublayer's normed stream;
# the rotary term over halves instead of interleaved pairs; one kept output fewer
FAULTS = ("fp8_weights", "no_q_multiplier", "no_kv_multiplier", "renormalised_gates", "no_scaling_factor", "no_identity",
          "identity_sign", "bias_weighs", "no_selection_bias", "shortcut_returns_at_once", "shortcut_from_second",
          "rotary_halves", "top11")


def _weights(wrong: str):
    """How a weight is read: as float32, or (the fault ``fp8_weights``) rounded to e4m3 first."""
    if wrong == "fp8_weights":
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(F32) if a.ndim >= 2 else a.astype(F32)
    return lambda a: a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rotate(x, cos, sin, *, halves: bool = False):
    """``apply_rotary_pos_emb_interleave``: the interleaved pairs are first
    brought to halves (``view(d / 2, 2).transpose``), then ``x cos +
    rotate_half(x) sin``.  ``halves`` (a fault) skips the permutation."""
    d = x.shape[-1]
    if not halves:
        x = jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1, -2).reshape(x.shape)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + turned * sin


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope", "v_dim", "rank", "theta", "eps", "q_scale", "kv_scale",
                                             "wrong"))
def attention(ap: Dict[str, Any], u, *, heads: int, nope: int, rope: int, v_dim: int, rank: int, theta: float, eps: float,
              q_scale: float, kv_scale: float, wrong: str = ""):
    """Latent attention in the EXPANDED form over one sequence ``u`` (T, E)
    from position 0, float32, dense causal softmax, ``HEAD_BLOCK`` heads at a time."""
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        # (assumed: the two multipliers are (hidden_size / rank) ** 0.5, on the normed low-rank activations; k_pe is not scaled)
        c_q = _rmsnorm(u @ f(ap["q_a"]), ap["q_a_norm"], eps) * q_scale
        q = (c_q @ f(ap["q_b"])).reshape(T, heads, nope + rope)
        kv = u @ f(ap["kv_a"])
        latent, k_pe = _rmsnorm(kv[:, :rank], ap["kv_a_norm"], eps) * kv_scale, kv[:, rank:]
        inv_freq = theta ** (-jnp.arange(0, rope, 2, dtype=F32) / rope)       # plain frequencies: no scaling of them
        angle = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
        emb = jnp.concatenate([angle, angle], axis=-1)
        cos, sin = jnp.cos(emb), jnp.sin(emb)
        q_pe = _rotate(q[..., nope:], cos[:, None, :], sin[:, None, :], halves=wrong == "rotary_halves")
        k_pe = _rotate(k_pe, cos, sin, halves=wrong == "rotary_halves")
        k_nope = jnp.einsum("tc,hdc->thd", latent, f(ap["kv_b_k"]))          # kv_b's key half, a head at a time
        v = jnp.einsum("tc,hcd->thd", latent, f(ap["kv_b_v"]))               # ... and its value half
        qq = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, heads, rope))], axis=-1)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        scale = (nope + rope) ** -0.5                                         # (assumed: the keys' whole width)

        def block(args):
            qb, kb, vb = args                                                # (hb, T, .)
            s = scale * jnp.einsum("hqd,hkd->hqk", qb, kb)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vb)

        hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else 1
        split = lambda a: a.transpose(1, 0, 2).reshape(heads // hb, hb, T, a.shape[-1])
        o = jax.lax.map(block, (split(qq), split(kk), split(v)))             # (H / hb, hb, T, v)
        return o.reshape(heads, T, v_dim).transpose(1, 0, 2).reshape(T, heads * v_dim) @ f(ap["o"])


@functools.partial(jax.jit, static_argnames=("k", "scale", "wrong"))
def _route(router, bias, h, *, k: int, scale: float, wrong: str = ""):
    """``LongcatFlashTopkRouter``: float32 softmax over ALL outputs (no bias
    term in the product); the ``k`` largest of ``p + b``; the gates are the kept
    ``p`` as they are (assumed: not renormalised), times ``scale``."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)
        choice = probs if wrong == "no_selection_bias" else probs + bias.astype(F32)
        _, idx = jax.lax.top_k(choice, k - 1 if wrong == "top11" else k)
        kept = jnp.take_along_axis(choice if wrong == "bias_weighs" else probs, idx, axis=-1)
        if wrong == "renormalised_gates":
            kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
        return idx, kept * (1.0 if wrong == "no_scaling_factor" else scale)


@functools.partial(jax.jit, static_argnames=("wrong",))
def _swiglu(h, w_gate, w_up, w_down, wrong: str = ""):
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def routed(ep: Dict[str, Any], h, config: Dict[str, Any], *, first_held: int, total: int, wrong: str = ""):
    """``LongcatFlashMoE``: every held expert on every token, weighted by the
    gate it has there (0 where it is not among the token's twelve), and the
    zero-compute experts (ids ``total ..``: assumed ``zero_expert_type``
    "identity" is ``gate x input``): the token itself under the sum of their
    gates, whole on every chip."""
    idx, gates = _route(ep["router"], ep["router_bias"], h, k=config["moe_topk"], scale=float(config["routed_scaling_factor"]),
                        wrong=wrong)
    same = jnp.sum(jnp.where(idx >= total, gates, 0.0), axis=-1)[:, None] * h
    out = {"no_identity": 0.0, "identity_sign": -1.0}.get(wrong, 1.0) * same
    for e in range(ep["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first_held + e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(h, ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e], wrong=wrong)
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, *, eps: float):
    return _rmsnorm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps", "wrong"))
def _head(norm_w, kernel, x, *, eps: float, wrong: str = ""):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_w, eps) @ _weights(wrong)(kernel)


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], wrong: str = ""):
    """The residual stream after the last layer, (T, E) float32.  ``wrong`` (one
    of ``FAULTS``) computes a wrong model on the same weights."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong is one of {FAULTS}")
    c, eps = config, float(config["rms_norm_eps"])
    total, _held, first = _share(c)
    E = c["hidden_size"]
    attn = dict(heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                rank=c["kv_lora_rank"], theta=float(c["rope_theta"]), eps=eps,
                q_scale=1.0 if wrong == "no_q_multiplier" else (E / c["q_lora_rank"]) ** 0.5,
                kv_scale=1.0 if wrong == "no_kv_multiplier" else (E / c["kv_lora_rank"]) ** 0.5, wrong=wrong)
    x = _weights(wrong)(jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)), axis=0))
    for l in range(c["num_layers"]):
        lp = params[f"layers_{l}"]
        shortcut = None
        for i in range(SUBLAYERS):                                           # ``LongcatFlashDecoderLayer.forward``
            x = x + attention(lp[f"self_attn_{i}"], _norm(lp[f"input_layernorm_{i}"]["weight"], x, eps=eps), **attn)
            h = _norm(lp[f"post_attention_layernorm_{i}"]["weight"], x, eps=eps)
            if i == (1 if wrong == "shortcut_from_second" else 0):
                shortcut = routed(lp["mlp"], h, c, first_held=first, total=total, wrong=wrong)   # the branch leaves here ...
                if wrong == "shortcut_returns_at_once":
                    x, shortcut = x + shortcut, 0.0
            mp = lp[f"mlps_{i}"]
            x = x + _swiglu(h, mp["gate"], mp["up"], mp["down"], wrong=wrong)
        x = x + shortcut                                                     # ... and returns at the layer's end
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int], wrong: str = ""):
    """Next-token logits (float32) over the held rows of the vocabulary, at the positions ``rows``."""
    x = hidden_states(params, config, tokens, wrong)[jnp.asarray(np.asarray(rows, np.int32))]
    return _head(params["norm"]["weight"], params["lm_head"]["kernel"], x, eps=float(config["rms_norm_eps"]), wrong=wrong)


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic (parameters that a token multiplies; norm
# weights and the selection bias are counted where bytes are), so that no later
# PR moves a share by recounting.  Widths are the REAL ones (a row of 576, scores
# 192 wide, values 128): what the program pads (the row to 640) is its own cost.
def _row(c: Dict[str, Any]) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def sublayers(c: Dict[str, Any]) -> int:
    """The attention sublayers of the cut: the latent pool's layers."""
    return SUBLAYERS * c["num_layers"]


def router_outputs(c: Dict[str, Any]) -> int:
    return _share(c)[0] + c["zero_expert_num"]


def attention_params(c: Dict[str, Any]) -> int:
    """One attention SUBLAYER's matrices."""
    H, E = c["num_attention_heads"], c["hidden_size"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (E * c["q_lora_rank"] + c["q_lora_rank"] * H * qk + E * _row(c)
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"]) + H * c["v_head_dim"] * E)


def dense_params(c: Dict[str, Any]) -> int:
    """One of a layer's two dense SwiGLUs."""
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_params(c: Dict[str, Any]) -> int:
    """The router's matrix and the selection bias, over ALL outputs (real and identity)."""
    return (c["hidden_size"] + 1) * router_outputs(c)


def layer_params(c: Dict[str, Any]) -> int:
    """One model layer on this chip: two sublayers, two dense SwiGLUs, the router, the held experts."""
    return SUBLAYERS * (attention_params(c) + dense_params(c)) + router_params(c) + c["n_routed_experts"] * expert_params(c)


def param_count(c: Dict[str, Any]) -> int:
    """Every parameter this chip holds: the layers, embedding and head apart (untied), the norms."""
    E, L = c["hidden_size"], c["num_layers"]
    norms = L * SUBLAYERS * (2 * E + c["q_lora_rank"] + c["kv_lora_rank"]) + E
    return L * layer_params(c) + 2 * c["vocab_size"] * E + norms


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but the routers and the selection biases (float32)."""
    return 2 * param_count(c) + 2 * c["num_layers"] * router_params(c)


def latent_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What a position's attention must read of ONE sublayer's cache: a row of 576."""
    return _row(c) * itemsize


def pool_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What a position occupies in the pool, every sublayer: the row padded to whole lane tiles (640)."""
    return sublayers(c) * -(-_row(c) // 128) * 128 * itemsize


def cache_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """The latent pool (``pool_pages`` pages, the null page among them)."""
    pages = int(serve.get("pool_pages") or int(serve["slots"]) * int(serve["positions_per_slot"]) // int(serve["page_size"]) + 1)
    return pages * int(serve["page_size"]) * pool_bytes_per_position(c)


def decode_step_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, latent_positions_read: float,
                      experts_touched: Optional[float] = None) -> float:
    """The bytes one decode step must move: every weight held once but the
    embedding (a row a slot is gathered) and the held experts that got no token
    (``experts_touched``: the count over all layers; all, where it is not
    given), the live latent rows (``latent_positions_read``: positions summed
    over slots AND sublayers, 576 wide), the logits written."""
    S, L = int(serve["slots"]), c["num_layers"]
    touched = L * c["n_routed_experts"] if experts_touched is None else experts_touched
    weights = (weight_bytes(c) - 2 * (c["vocab_size"] - S) * c["hidden_size"]
               - 2 * expert_params(c) * (L * c["n_routed_experts"] - touched))
    return weights + latent_positions_read * latent_bytes_per_position(c) + S * c["vocab_size"] * 4


def mla_decode_flops_per_position(c: Dict[str, Any]) -> float:
    """The absorbed form's operations for one cached position of one sublayer:
    every head's score over the row (576) and its share of the mix (512)."""
    return 2.0 * c["num_attention_heads"] * (_row(c) + c["kv_lora_rank"])


def mla_prefill_attention_flops(c: Dict[str, Any], bucket: int) -> float:
    """Causal attention of ONE sublayer over ``bucket`` positions in the expanded
    form at the real widths (scores 192, values 128; half the square)."""
    per_pair = 2.0 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return c["num_attention_heads"] * per_pair * bucket * bucket / 2.0


def mla_prefill_attention_bytes(c: Dict[str, Any], bucket: int, itemsize: int = 2) -> float:
    """... and what that sublayer's flash forward must move: queries and keys
    (192) of every head, values and outputs (128), once."""
    H = c["num_attention_heads"]
    return 2.0 * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]) * bucket * itemsize


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
# As families/mimo_v2.py: the chip's trace names a device event by its whole HLO
# instruction and carries no scope, so the table is of shapes, from the
# configuration alone, for a program over ``rows`` rows of the stream (a decode
# step's slots, a prefill's rung).  An op belongs to the first mechanism one of
# whose signatures its text shows: the head (everything as wide as the
# vocabulary; at a rung of 1,024 rows or more not the embedding's own shape: the
# sorted pairs of 1,024 rows are 16,384 rows, as many as the slice has), then
# the routed branch (arrays that lead with the held count, or
# are as wide as an expert or as the router, or hold the pairs), then latent
# attention (the kernels by name; projections, rotary parts and the pool by
# shape), then the two dense SwiGLUs.  A shape that two mechanisms share at some
# rung (the queries' 64 x 192 = 12,288 is the dense width; the 1,536 rung is
# ``q_lora_rank``, the 2,048 rung an expert's width) is left out of the table at
# that rung.
MECHANISMS = ("head", "routed", "mla", "mlp")
DECODE_KERNEL, PREFILL_KERNEL = "paged_decode_latent", "mla_flash_fwd"


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any], rows: Optional[int] = None) -> Dict[str, Sequence[str]]:
    S = int(serve["slots"])
    R = S if rows is None else int(rows)
    E, H, V, I = c["hidden_size"], c["num_attention_heads"], c["vocab_size"], c["ffn_hidden_size"]
    X, held, F, k = router_outputs(c), c["n_routed_experts"], c["expert_ffn_hidden_size"], c["moe_topk"]
    nope, rope, v, rank, qr = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"], c["q_lora_rank"]
    qk, row, padded = nope + rope, _row(c), -(-_row(c) // 128) * 128
    unless = lambda clash, *texts: () if clash else texts
    # the pairs' own arrays (sorted rows, their order, a token's k choices) at this program's rows
    pairs = [f"[{R * k}]", f"[{R * k},{E}]", f"[{R * k},{F}]", f"[{R},{k},", f"[{R},{k}]", f"[{R},{X}]", f"[{R},{held}]",
             f"[{R},{held + 1}]"]
    return {
        "head": (f",{V}]", *unless(R >= 1024, f"[{V},{E}]")),
        "routed": ("ragged-dot", "grouped_swiglu", f"[{held},{E},{F}]", f"[{held},{F},{E}]", f"[{E},{X}]", f"[{X}]", f",{X}]",
                   f"[{held + 1}]", f"[{held}]", f"[{held},{R},", f"[{held},128,", f"[{E},{F}]", f"[{R},{F}]",
                   *unless(F == R, f"[{F},{E}]"), f",{F}]", *pairs),
        "mla": (DECODE_KERNEL, PREFILL_KERNEL, f",{H},{qk}]", f",{H},{nope}]", f",{H},{rope}]", f"[{H},{R},", f"[{H},{nope},{rank}]",
                f"[{H},{rank},{v}]", f",{row}]", f",{padded}]", f"[{E},{qr}]", f"[{qr},{H},{qk}]", *unless(R == qr, f"[{qr},{H * qk}]"),
                f",{qr}]", f"[{E},{row}]", *unless(H * v == R, f"[{H * v},{E}]"), f",{H * v}]", f",{rank}]", f",{qk}]", f",{rope}]",
                f",{rope // 2}]", f"[{H},", f",{H},"),
        "mlp": (f"[{E},{I}]", *unless(R == I, f"[{I},{E}]"), f",{I}]"),
    }


# the kernels, known by the instruction's NAME before any shape is looked at (a kernel's event lists its operands, and
# a page table, or the sorted pairs' rows, are as wide as other things are)
KERNELS = {DECODE_KERNEL: "mla", PREFILL_KERNEL: "mla", "vs.attn": "mla", "grouped_swiglu": "routed"}


def mechanism_of(op_text: str, signatures: Dict[str, Sequence[str]]) -> str:
    """One of ``MECHANISMS``, or ``other`` (norms and sums of the residual
    stream, the embedding's gather, small copies) for a device event's name."""
    name = op_text.split(" = ", 1)[0]
    for kernel, mechanism in KERNELS.items():
        if kernel in name:
            return mechanism
    for mechanism in MECHANISMS:
        if any(s in op_text for s in signatures[mechanism]):
            return mechanism
    return "other"
