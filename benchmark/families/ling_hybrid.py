"""The Ling hybrid family (``"model": "ling_hybrid"``, HF ``model_type``
``bailing_hybrid``; Ling-3.0-flash): delta-rule LINEAR attention (Kimi Delta
Attention, arXiv:2510.26692) in five layers of six and multi-head LATENT
attention in the sixth; after the leading dense layers 512 sigmoid-routed
experts under a group limit (``noaux_tc``: 8 of 4 of 8 groups, a selection
bias, gates renormalised times 2.5) beside one shared expert; an untied head;
``vescale_tpu/models/ling_hybrid.py`` (with ``models/kda.py``, ``models/mla.py``,
``kernels/kda.py``) under ``vescale_tpu/serve/hybrid_engine.py`` in the program.
A family that only serves.  The names are those ``benchmark/README.md``
("Adding a family") fixes.

What a reader of this family needs beyond the README:

- **The cut** keeps the source's layers ``first_layer ..`` (``assumed.first_layer``:
  1 in the configuration that is there: one leading dense layer and one whole
  period of six behind it); a layer is latent iff ``(source index + 1) %
  layer_group_size == 0``.
- **The share** is ``families/deepseek_v2.py``'s with this family's keys
  (``"share": {"chips": 8, "of": ["num_experts", "vocab_size"], "index": 0}``):
  ``num_experts`` and ``vocab_size`` are what is held HERE, the source's values are
  under ``published``; the router keeps its 512 outputs, its 8 groups, 4 kept
  groups and 8 experts a token.  A share is whole routing groups (64 experts =
  ONE group).  Program and reference both add up only what the held experts
  give; the shared expert is whole on every chip.
- **The cache.**  ``cache.k`` holds the latent layers' rows (one pool layer in
  the cut; ``cache.v`` is None); ``cache.state["kda_state"]`` ``(6, slots, 32, 128,
  128)`` float32 and ``["kda_conv"]`` ``(6, slots, 3, 12288)`` the delta-rule
  layers' matrix states and convolution tails.
- **The counters** (``HybridServeEngine.trace_counters``): the engine's (the
  ``moe_*``, ``decode_pages_*`` of the latent pages) and the model's own
  ``kda_state_bytes_rw``, ``latent_bytes_read``, ``route_rows_held_group``.
- **The runner's check** (``serve_cell.py``: 320 prompt tokens on the 512 rung,
  4 decode steps) reaches everything this family has (three chunks of the delta
  rule, the state handed from prefill to decode, the tails, the latent rows);
  ``check_window`` below is the longer one (1,100 tokens on the 1,536 rung, 40
  decode steps, every row) and takes a fault.

The reference is straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, written from the equations position by position: the delta rule as a
``lax.scan`` over positions with the matrix state its carry (no chunks, no
triangular system), the latent attention in the expanded form under a dense
causal softmax, a loop over the held experts; no kernel, cache, rung, padding
or batching, and nothing imported from the program.  The program's tree is read
a layer, and inside a layer an expert, at a time and cast inside each jitted
call: a float32 copy of the weights never exists.
"""

from __future__ import annotations

import functools
import statistics
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
# the largest reference logit, over the runner's rows (a prefill of 320 tokens
# and four decode steps) and over ``check_window``'s (a prefill of 1,100 tokens on
# the 1,536 rung and 40 decode steps through the pool, the states and the tails).
# The program multiplies in bf16 with float32 accumulation (2^-9 = 2e-3 a rounded
# operand) through 7 layers, keeps the residual stream, norms, gates, router,
# rotary, softmax and the whole delta rule (``q``, ``k``, ``v``, the gates, the
# state) in float32, and rounds a cached latent row and a convolution tail to
# bf16 once; the reference reads the same bf16 weights.  Readings on the chip at
# the published widths (PERF.md section 6, PR 63, my chip runs; seeds beside them):
#
#   the sound program     the runner's lengths, sixteen runs of the cell and ``check_window`` there at eighteen seeds: 6.06e-3 to 8.3e-3 but for
#                         FIVE that hold a moved pair, 1.29e-2, 1.47e-2, 1.51e-2, 1.87e-2 and 2.76e-2;
#                         ``check_window`` at four seeds: 1.37e-2, 2.43e-2, 1.37e-2, 2.31e-2 (the prefill's row 4.8e-3 to 7.5e-3:
#                         the reading is ONE decode row of forty)
#   fp8_weights           0.140 and 0.154 (0.169 and 0.151 at the runner's lengths): the reference with its weights in e4m3, the
#                         nearest type below the one the configuration states, cast eagerly where they are stored (``_stored``:
#                         a first reading, 5.67e-2 to 6.92e-2, was of a cast inside the jitted calls, which the chip's compiler
#                         dropped for the experts' matrices)
#   group_swapped         5.46e-2 and 5.86e-2 (6.05e-2 and 5.20e-2): routing groups 0 and 1 exchanged
#   decay_after           0.269 and 0.243 (0.256 and 0.221); no_beta 0.444 and 0.448 (0.427, 0.464); gate_unbounded 0.445 and
#                         0.409 (0.411, 0.427)
#   state_bf16            1.78e-2 to 2.29e-2 at four seeds (1.32e-2 to 1.93e-2 at the runner's lengths, where the same seeds' sound
#                         program reads 6.7e-3 to 8.3e-3): the state rounded to bfloat16 after every position.  (An earlier reading, "the
#                         sound reading to five digits", was of a cast to bfloat16 and back, which the chip's compiler drops as excess
#                         precision: ``reduce_precision`` now.)
#   router_bf16           1.70e-2, 2.27e-2 and 1.37e-2 (7.3e-3 and 7.9e-3): a few more moved pairs of the same size
#
# A sound row of 6e-3 to 8e-3 is the bf16 products' rounding; a row of 1.3e-2 to 2.8e-2 is ONE (token, expert) pair that
# rounding moved across the router's cut (the mechanism ``families/mimo_v2.py`` and ``longcat_flash.py`` describe: one row
# in forty here, with a routing group of eight held and a swapped expert worth what ``HELD_DOWN_GAIN`` makes it; where the
# cut that rounding crosses is the GROUPS', a token's held experts, two on the mean, move at once).  The limit is set from
# its two readings: the largest the sound program has given anywhere (2.76e-2: 2.2 times under it) and the nearest precision
# below at its smallest (0.140: 2.3 times over it).  It stood at 3.5e-2 until that sound reading (1.27 times over it, and set
# from a ``fp8_weights`` that read a third of the true one).  What it does not catch: a routing group swapped reads 5.2e-2 to
# 6.1e-2, AT the limit, and a state or a router kept in bfloat16 reads UNDER one moved pair (1.3e-2 to 2.3e-2), which ISSUE 63
# asked of it: ``check_window`` reads all three where no moved pair reaches (its section, below).  The delta rule's own
# faults read 0.22 and more.
SERVE_LOGITS_TOLERANCE = 6e-2

SHARED_KEYS = ("num_experts", "vocab_size")
# the published keys whose values this family's block fixes: a file that says otherwise is another architecture
FIXED = {"topk_method": "noaux_tc", "score_function": "sigmoid", "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
         "use_qk_norm": True, "tie_word_embeddings": False, "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
         "linear_silu": True, "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
         "rope_interleave": True, "use_bias": False, "use_qkv_bias": False, "num_kv_heads_for_linear_attn": 0,
         "hidden_act": "silu", "scale_router_input": False, "value_norm": False, "up_proj_norm": False, "use_nGPT": False,
         "use_mla_nope": False, "q_lora_rank": None, "rope_scaling": None, "num_nextn_predict_layers": 0}
# ... and the readings of what the published config does not settle, as the file must state them under ``assumed``
ASSUMED = {"kda_rotary": "none", "output_gate": "head_wise_on_both_mixers", "use_qk_norm": "l2_on_kda_q_and_k_only",
           "kda_head_dims": "d_k = d_v = head_dim, as many key heads as query heads", "group_score": "sum_of_top_2",
           "max_window_layers": "no_term", "state_dtype": "float32", "conv_tail_dtype": "bfloat16"}


# --------------------------------------------------------------- the program
def _share(config: Dict[str, Any]):
    """(experts in the model, experts held, first held id): the file's share."""
    share, published = config.get("share") or {}, config.get("published", {})
    for key in SHARED_KEYS:
        if key in config.get("reduced", ()) and key not in share.get("of", ()):
            raise SpecError(f"{key} is cut from {published.get(key)} to {config[key]}: the file must state the share "
                            "it is (share.of), a smaller model is not this family's")
    if set(share.get("of", ())) - set(SHARED_KEYS):
        raise SpecError(f"this family divides {SHARED_KEYS} over chips, not {share['of']}")
    total = int(published.get("num_experts", config["num_experts"]))
    held = int(config["num_experts"])
    index = int(share.get("index", 0))
    if "num_experts" in share.get("of", ()) and held * int(share["chips"]) != total:
        raise SpecError(f"{share['chips']} chips with {held} experts each do not hold the model's {total}")
    if total % int(config["n_group"]) or held % (total // int(config["n_group"])):
        raise SpecError(f"a share of {held} experts is not whole routing groups of {total} / {config['n_group']}: "
                        "group-limited routing sends a token to whole groups")
    return total, held, index * held


def first_layer(config: Dict[str, Any]) -> int:
    """The source's index of the file's layer 0 (``assumed.first_layer``; 0 where the file does not cut the front)."""
    return int((config.get("assumed") or {}).get("first_layer", 0))


def layer_plan(config: Dict[str, Any]) -> List[str]:
    """What each layer's mixer is: ``kda`` | ``mla``."""
    first, group = first_layer(config), int(config["layer_group_size"])
    return ["mla" if (first + l + 1) % group == 0 else "kda" for l in range(config["num_hidden_layers"])]


def program_config(config: Dict[str, Any], *, max_positions: int = 0, prefill_chunk: int = 128):
    """The program's ``LingHybridConfig`` from a configuration file's object;
    the published keys go through unchanged.  Refused: a clamp on an expert's
    SwiGLU in a layer kept (the block has none), a query LoRA, a rope scaling,
    a multi-token-prediction layer.  ``max_positions`` sizes nothing."""
    from vescale_tpu.models.ling_hybrid import LingHybridConfig

    for key, value in FIXED.items():
        if config.get(key) != value:
            raise SpecError(f"this family's block has {key} = {value!r}; the file says {config.get(key)!r}")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = config.get(key) or []
        if len(limits) != config["num_hidden_layers"] or any(limits):
            raise SpecError(f"{key} is {limits}: one entry a layer kept, and this family's SwiGLU has no clamp (a non-zero "
                            "entry is another block, not one to ignore)")
    assumed = config.get("assumed") or {}
    for key, value in ASSUMED.items():
        if assumed.get(key) != value:
            raise SpecError(f"the program reads {key} as {value!r} (the source's config does not settle it): the file "
                            f"states it under assumed, and says {assumed.get(key)!r}")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] + config["qk_rope_head_dim"] or config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise SpecError("qk_head_dim is the two parts' sum and rotary_dim the latent mixer's rotary width")
    if config["head_dim"] != config["v_head_dim"]:
        raise SpecError("the two mixers' value outputs are one width: W_o of both is (heads x head_dim, hidden)")
    if config.get("num_key_value_heads") != config["num_attention_heads"]:
        raise SpecError("latent attention has as many key heads as query heads")
    total, held, first = _share(config)
    return LingHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"], num_hidden_layers=config["num_hidden_layers"],
        first_layer=first_layer(config), layer_group_size=config["layer_group_size"],
        first_k_dense_replace=config["first_k_dense_replace"], intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config["moe_shared_expert_intermediate_size"],
        num_shared_experts=config["num_shared_experts"], num_experts=total,
        num_experts_per_tok=config["num_experts_per_tok"], n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=float(config["routed_scaling_factor"]), experts_held=held, first_expert_held=first,
        num_attention_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        short_conv_kernel_size=config["short_conv_kernel_size"], kda_lower_bound=float(config["kda_lower_bound"]),
        kv_lora_rank=config["kv_lora_rank"], qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"], rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]), prefill_chunk=int(prefill_chunk), dtype=jnp.bfloat16)


def pool_pages(serve: Dict[str, Any]) -> int:
    """The pool's pages: what the file names, or every slot's and the null page."""
    return int(serve.get("pool_pages") or int(serve["slots"]) * int(serve["positions_per_slot"]) // int(serve["page_size"]) + 1)


def _cache_config(cfg, serve: Dict[str, Any]):
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

    return hybrid_cache_config(cfg, num_slots=int(serve["slots"]), page_size=int(serve["page_size"]),
                               pages_per_slot=int(serve["positions_per_slot"]) // int(serve["page_size"]),
                               num_pages=int(serve["pool_pages"]) if serve.get("pool_pages") else None)


def _serve_config(config: Dict[str, Any], serve: Dict[str, Any]):
    if serve["weight_dtype"] != "bfloat16" or serve.get("state_dtype", "float32") != "float32":
        raise ValueError("serve cells hold their weights in bfloat16 and the delta-rule states in float32")
    try:
        return program_config(config, prefill_chunk=int(serve.get("prefill_chunk", 128)))
    except ImportError as e:
        raise RuntimeError(f"this checkout's program cannot run the ling_hybrid family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a LATENT paged cache with the delta-rule layers'
    states and tails beside it; ``HybridServeEngine`` with every rung and the
    decode step compiled."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.ling_hybrid import init_params
    from vescale_tpu.serve import HybridServeEngine, PagedKVCache

    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))
    cache = PagedKVCache(_cache_config(cfg, serve), mesh)
    return ServeSystem(params, cache, HybridServeEngine(cfg, mesh, params, cache).warm(), cfg.vocab_size)


def rehearse_serve(name: str, config: Dict[str, Any], serve: Dict[str, Any], devices):
    """Every prefill rung and the decode step, lowered for described devices:
    shapes where the cache would allocate (two functions patched for the
    duration, here, not in the program)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.ling_hybrid import init_params
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

    with mock.patch.object(kv_cache_module, "_zeros_global", pool_shapes), \
            mock.patch.object(kv_cache_module, "_zeros_replicated",
                              lambda shape, dtype, _mesh: jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)):
        cache = PagedKVCache(_cache_config(cfg, serve), mesh)
        engine = HybridServeEngine(cfg, mesh, params, cache)
    S, page = cache.num_slots, cache.config.page_size
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)
    nbytes = lambda a: int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
    sizes = {"weights_bytes": sum(nbytes(a) for a in jax.tree_util.tree_leaves(params)),
             "kv_pool_bytes": nbytes(cache.k.data), "slot_state_bytes": sum(nbytes(a) for a in cache.state.values())}
    held = tuple(cache.arrays().values())
    programs = [(f"{name}: prefill, rung of {b} positions, depth {cfg.num_hidden_layers}",
                 engine._prefill_fn.lower(params, *held, i32(b), i32(), i32(b // page), i32()))
                for b in engine.buckets]
    programs.append((f"{name}: decode step, {S} slots x {cache.max_seq_len} positions",
                     engine._decode_fn.lower(params, *held, i32(S, cache.config.pages_per_slot), i32(S), i32(S))))
    return sizes, programs


# ------------------------------------------------------------- the reference
F32 = jnp.float32
HEAD_BLOCK = 8          # heads whose (T, T) scores exist at once
L2_EPS = 1e-6           # under the root of a head's l2 norm (the public ``fla`` layer's)
# what a wrong computation reads (``wrong=``: the tolerance's reasons, the tests, the builder's chip readings): the weights
# in the nearest type below the one the configuration states; the delta rule's state rounded to bfloat16 after every
# position; the router's product in bfloat16; the gate without its lower bound (``fla``'s plain gate, ``-exp(A_log)
# softplus(.)``); ``beta`` left out (1); the decay applied AFTER the correction; routing groups 0 and 1 exchanged
FAULTS = ("fp8_weights", "state_bf16", "router_bf16", "gate_unbounded", "no_beta", "decay_after", "group_swapped")


def _stored(tree, wrong: str):
    """A layer's weights as a computation reads them: as they are, or (the fault ``fp8_weights``) every matrix but the float32 router's
    rounded to e4m3 HERE, one eager cast a leaf: inside a jitted call the chip's compiler drops a cast there and back as excess
    precision (it did for the experts' matrices and not for the mixers': PERF.md section 6, PR 63 after the review)."""
    if wrong != "fp8_weights":
        return tree
    if isinstance(tree, dict):
        return {name: leaf if name == "router" else _stored(leaf, wrong) for name, leaf in tree.items()}
    return tree.astype(jnp.float8_e4m3fn) if tree.ndim >= 2 else tree


def _f(a):
    """A weight as the reference multiplies it."""
    return a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "lower_bound", "wrong"))
def kda(kp: Dict[str, Any], u, *, heads: int, eps: float, lower_bound: float, wrong: str = ""):
    """A delta-rule mixer over one sequence ``u`` (T, E), the normed stream:
    the recurrence one position at a time, the matrix state the scan's carry.
    Returns the mixer's output (T, E) and the state (H, d, d) after the last position."""
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        w = _f(kp["conv"])                                                    # (K, 3 H d): tap K - 1 is the position itself
        K, D = w.shape[0], w.shape[1] // (3 * heads)
        x = u @ _f(kp["qkv"])
        padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x], axis=0)
        q, k, v = (a.reshape(T, heads, D) for a in jnp.split(jax.nn.silu(sum(w[i] * padded[i: i + T] for i in range(K))), 3, axis=-1))
        unit = lambda a: a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + L2_EPS)
        q, k = unit(q) * D ** -0.5, unit(k)
        inner = jnp.exp(kp["A_log"].astype(F32))[None, :, None] * (u @ _f(kp["f"]) + kp["dt_bias"].astype(F32)).reshape(T, heads, D)
        g = -jax.nn.softplus(inner) if wrong == "gate_unbounded" else lower_bound * jax.nn.sigmoid(inner)
        beta = jnp.ones((T, heads), F32) if wrong == "no_beta" else jax.nn.sigmoid(u @ _f(kp["beta"]))

        def position(S, inp):
            q_t, k_t, v_t, g_t, b_t = inp                                    # (H, d), (H, d), (H, d), (H, d), (H,)
            decay = jnp.exp(g_t)[:, :, None]
            if wrong == "decay_after":
                S = decay * (S + k_t[:, :, None] * (b_t[:, None] * (v_t - jnp.einsum("hcd,hc->hd", S, k_t)))[:, None, :])
            else:
                S = decay * S
                S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - jnp.einsum("hcd,hc->hd", S, k_t)))[:, None, :]
            if wrong == "state_bf16":         # (``reduce_precision``: a TPU's compiler drops a cast to bfloat16 and back as excess precision)
                S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
            return S, jnp.einsum("hcd,hc->hd", S, q_t)

        S, o = jax.lax.scan(position, jnp.zeros((heads, D, D), F32), (q, k, v, g, beta))
        o = _rmsnorm(o, kp["o_norm"], eps) * jax.nn.sigmoid(u @ _f(kp["gate"]))[:, :, None]
        return o.reshape(T, heads * D) @ _f(kp["o"]), S


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope", "v_dim", "rank", "theta", "eps"))
def latent(ap: Dict[str, Any], u, *, heads: int, nope: int, rope: int, v_dim: int, rank: int, theta: float, eps: float):
    """Latent attention in the EXPANDED form over one sequence ``u`` (T, E)
    from position 0: a direct ``W_q``, plain rotary frequencies over interleaved
    pairs, a dense causal softmax ``HEAD_BLOCK`` heads at a time, the head-wise
    gate on the value output."""
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        q = (u @ _f(ap["q"])).reshape(T, heads, nope + rope)
        kv = u @ _f(ap["kv_a"])
        c, k_pe = _rmsnorm(kv[:, :rank], ap["kv_a_norm"], eps), kv[:, rank:]
        angle = jnp.arange(T, dtype=F32)[:, None] * (theta ** (-jnp.arange(0, rope, 2, dtype=F32) / rope))[None, :]
        cos, sin = jnp.repeat(jnp.cos(angle), 2, axis=-1), jnp.repeat(jnp.sin(angle), 2, axis=-1)      # (T, rope): a pair shares its angle

        def rotate(x, cos, sin):                                             # pairs (2 i, 2 i + 1): (a, b) -> (a cos - b sin, b cos + a sin)
            a, b = x[..., 0::2], x[..., 1::2]
            turned = jnp.stack([-b, a], axis=-1).reshape(x.shape)
            return x * cos + turned * sin

        q_pe, k_pe = rotate(q[..., nope:], cos[:, None, :], sin[:, None, :]), rotate(k_pe, cos, sin)
        k_nope = jnp.einsum("tc,hdc->thd", c, _f(ap["kv_b_k"]))
        v = jnp.einsum("tc,hcd->thd", c, _f(ap["kv_b_v"]))
        qq = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        kk = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, heads, rope))], axis=-1)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        scale = (nope + rope) ** -0.5

        def block(args):
            qb, kb, vb = args                                                # (hb, T, .)
            s = scale * jnp.einsum("hqd,hkd->hqk", qb, kb)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vb)

        hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else 1
        split = lambda a: a.transpose(1, 0, 2).reshape(heads // hb, hb, T, a.shape[-1])
        o = jax.lax.map(block, (split(qq), split(kk), split(v))).reshape(heads, T, v_dim).transpose(1, 0, 2)
        o = o * jax.nn.sigmoid(u @ _f(ap["gate"]))[:, :, None]
        return o.reshape(T, heads * v_dim) @ _f(ap["o"])


@functools.partial(jax.jit, static_argnames=("k", "n_group", "topk_group", "scale", "wrong"))
def _route(router, bias, h, *, k: int, n_group: int, topk_group: int, scale: float, wrong: str = ""):
    """``noaux_tc``: ids (N, k) and gates.  Sigmoid scores; the choice on score + bias: a group scores as the sum of its
    two best, ``topk_group`` groups kept, the ``k`` best of them; gates the scores of those, renormalised, times ``scale``."""
    with jax.default_matmul_precision("highest"):
        if wrong == "router_bf16":
            scores = jnp.dot(h.astype(jnp.bfloat16), router.astype(jnp.bfloat16), preferred_element_type=F32)
        else:
            scores = h @ router.astype(F32)
        s = jax.nn.sigmoid(scores)
        N, E = s.shape
        per = E // n_group
        if wrong == "group_swapped":                                         # groups 0 and 1 change places
            s = jnp.concatenate([s[:, per: 2 * per], s[:, :per], s[:, 2 * per:]], axis=1)
        c = s + bias.astype(F32)
        best, _ = jax.lax.top_k(c.reshape(N, n_group, per), 2)
        _, groups = jax.lax.top_k(best.sum(axis=-1), topk_group)
        mask = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], groups].set(True)
        _, idx = jax.lax.top_k(jnp.where(jnp.repeat(mask, per, axis=1), c, -jnp.inf), k)
        top = jnp.take_along_axis(s, idx, axis=-1)
        return idx, top / jnp.sum(top, axis=-1, keepdims=True) * scale


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ _f(w_gate)) * (h @ _f(w_up))) @ _f(w_down)


def expert_layer(ep: Dict[str, Any], h, config: Dict[str, Any], *, first_held: int, wrong: str = ""):
    """``sum g_e E_e(h) + S(h)``: every held expert on every token, weighted by
    the gate it has there (0 where it is not among the token's eight)."""
    idx, gates = _route(ep["router"], ep["router_bias"], h, k=config["num_experts_per_tok"], n_group=config["n_group"],
                        topk_group=config["topk_group"], scale=float(config["routed_scaling_factor"]), wrong=wrong)
    out = _swiglu(h, ep["shared"]["gate"], ep["shared"]["up"], ep["shared"]["down"])
    for e in range(ep["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first_held + e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(h, ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e])
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, *, eps: float):
    return _rmsnorm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, kernel, x, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_w, eps) @ _f(kernel)


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], wrong: str = "",
                  inside: Optional[Dict[str, list]] = None):
    """The residual stream after the last layer, (T, E) float32.  ``wrong``
    (one of ``FAULTS``) computes a wrong model on the same weights.  ``inside``,
    where given, is filled with what ``check_window`` compares beside the
    logits: ``kda_state`` (every delta-rule layer's state after the last
    position), ``expert_input`` and ``expert_output`` (every expert layer's
    normed stream and what the layer adds to it, every position)."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong is one of {FAULTS}")
    _total, _held, first = _share(config)
    c, eps = config, float(config["rms_norm_eps"])
    H = c["num_attention_heads"]
    x = _f(_stored(jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)), axis=0), wrong))
    for l, kind in enumerate(layer_plan(c)):
        lp = _stored(params[f"layers_{l}"], wrong)
        u = _norm(lp["input_layernorm"]["weight"], x, eps=eps)
        if kind == "mla":
            x = x + latent(lp["mixer"], u, heads=H, nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                           rank=c["kv_lora_rank"], theta=float(c["rope_theta"]), eps=eps)
        else:
            y, state = kda(lp["mixer"], u, heads=H, eps=eps, lower_bound=float(c["kda_lower_bound"]), wrong=wrong)
            x = x + y
            if inside is not None:
                inside.setdefault("kda_state", []).append(state)
        h = _norm(lp["post_attention_layernorm"]["weight"], x, eps=eps)
        if l < c["first_k_dense_replace"]:
            x = x + _swiglu(h, lp["mlp"]["gate"], lp["mlp"]["up"], lp["mlp"]["down"])
        else:
            y = expert_layer(lp["mlp"], h, c, first_held=first, wrong=wrong)
            x = x + y
            if inside is not None:
                inside.setdefault("expert_input", []).append(h)
                inside.setdefault("expert_output", []).append(y)
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int], wrong: str = "",
           inside: Optional[Dict[str, list]] = None):
    """Next-token logits (float32) over the held rows of the vocabulary, at the positions ``rows``."""
    x = hidden_states(params, config, tokens, wrong, inside)[jnp.asarray(np.asarray(rows, np.int32))]
    return _head(params["norm"]["weight"], _stored(params["lm_head"]["kernel"], wrong), x, eps=float(config["rms_norm_eps"]))


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# ----------------------------------------------------- the longer check, with faults
CHECK_PROMPT_TOKENS = 1100      # on the 1,536 rung, which it does not fill: nine chunks of the delta rule, three of them pad
CHECK_DECODE_STEPS = 40
# What the logits cannot see (``SERVE_LOGITS_TOLERANCE``, above: one moved (token, expert) pair sets their reading, and a
# delta-rule state or a router kept in bfloat16 reads under it), ``check_window`` reads where no moved pair reaches:
#
# - ``state_max_abs_diff_over_max``: the slot's matrix states after the last decode step against the reference scan's carry,
#   as ``reference.rel_at_scale`` reads them, of the delta-rule layers NO ROUTER COMES BEFORE (a layer's mixer runs before its
#   own feed-forward: the tree's layers 0 .. ``first_k_dense_replace``; two at the configuration that is there), the larger.
#   A sound state differs by what the bf16 products of ``W_qkv``, ``W_f`` and ``beta`` leave in ``k``, ``v`` and the gates; a
#   state rounded to bfloat16 after every position walks away from it over as many positions as its slow channels keep.
# - ``expert_rows_off``: each expert layer of the PROGRAM (``models/ling_hybrid.py:expert_layer``, the function both programs
#   call, on the engine's own weights) run on the REFERENCE's normed stream of that layer, every position of the check's
#   sequence, against what the reference's layer gave there: the rows whose difference is more than
#   ``EXPERT_ROW_TOLERANCE`` of the row's own norm.  On EQUAL inputs a float32 router and the reference keep the same
#   pairs (the two products differ in the last bits: a pair moves at one row in some hundred thousand), so a sound row
#   reads the three bf16 products' rounding; a router whose product is made in bfloat16 moves a pair at about one row in
#   two hundred, and a moved pair on a held expert is a few per cent of the row.
#
# Readings (PERF.md section 6, PR 63 after the review; the chip at the published widths, seeds beside them):
#
#   the states            sound 3.15e-3 to 4.31e-3 (four seeds) and, at the runner's lengths (320 tokens and four steps; eighteen
#                         seeds), 2.54e-3 to 5.51e-3 and one 6.36e-3; ``state_bf16`` 2.98e-2 to 3.92e-2 and 1.90e-2 to 3.39e-2 (``fp8_weights`` 8.5e-2 and 9.3e-2, ``decay_after``
#                         0.40, ``gate_unbounded`` 0.51, ``no_beta`` 0.57; ``router_bf16`` and ``group_swapped`` the sound reading:
#                         no router comes before these layers).  ``STATE_TOLERANCE`` lies 1.7 times over the largest sound reading
#                         and 1.7 under the smallest of the bfloat16 state, both at the runner's lengths; at this check's own 2.6
#                         times over and 2.7 under.
#   the expert layers     sound 0 rows of 6,840 at four seeds (and of 1,944 at eighteen), the worst row 3.65e-3 to 3.94e-3; ``router_bf16`` 41, 50 and 42 rows,
#                         the worst 6.1e-2 to 6.5e-2; ``group_swapped`` 5,025 rows (0.12); ``fp8_weights`` every row (7.0e-2).
#                         ``EXPERT_ROW_TOLERANCE`` lies 2.6
#                         times over the worst sound row and 6 times under a moved pair's; two rows are allowed for a pair that
#                         float32 rounding moves (none seen in 62,352 rows).
STATE_TOLERANCE = 1.1e-2
EXPERT_ROW_TOLERANCE = 1e-2
EXPERT_ROWS_OFF_MOST = 2


def _expert_rows_off(engine, inside: Dict[str, list]) -> Dict[str, Any]:
    """The program's expert layers on the reference's streams (``inside``: ``hidden_states`` filled it): how many rows
    differ by more than ``EXPERT_ROW_TOLERANCE`` of their norm, of how many, and the worst."""
    from vescale_tpu.models.ling_hybrid import expert_layer as program_layer

    cfg, params = engine.config, engine.params
    layer = jax.jit(lambda ep, h: program_layer(cfg, ep, h)[0])
    worst, off, rows = 0.0, 0, 0
    experts = [params[f"layers_{l}"]["mlp"] for l in range(cfg.first_k_dense_replace, cfg.num_hidden_layers)]
    for ep, h, want in zip(experts, inside["expert_input"], inside["expert_output"]):
        want = np.asarray(want, np.float64)
        diff = np.linalg.norm(np.asarray(layer(ep, h), np.float64) - want, axis=-1) / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
        worst, off, rows = max(worst, float(diff.max())), off + int((diff > EXPERT_ROW_TOLERANCE).sum()), rows + len(diff)
    return {"expert_rows_off": off, "expert_rows": rows, "expert_row_worst": worst}


def check_window(engine, config: Dict[str, Any], seed: int, prompt_tokens: int = CHECK_PROMPT_TOKENS,
                 steps: int = CHECK_DECODE_STEPS, wrong: str = "") -> Dict[str, Any]:
    """A prefill of one seeded prompt of ``prompt_tokens`` tokens and then
    ``steps`` teacher-forced decode steps through the pool, the states and the
    tails, EVERY row against the reference's full forward (with the fault
    ``wrong``, where given: what a program with that fault would read against
    the sound reference), logits as a share of the largest: the runner's
    procedure at a greater length; and the two readings no moved pair reaches
    (above): the slot's matrix states where no router comes before them, and
    the program's expert layers on the reference's streams.  ``ok`` is all
    three within their limits.  The engine's cache must be free; it is reset at
    the end."""
    cache = engine.cache
    vocab = int(config["vocab_size"])
    rng = np.random.default_rng([int(seed), 63])
    prompt = [int(t) for t in rng.integers(1, vocab - 1, prompt_tokens)]
    forced = [int(t) for t in rng.integers(1, vocab - 1, steps)]
    cache.reset()
    slot = cache.alloc(prompt_tokens, steps + 1)
    rows = [np.asarray(engine.prefill(prompt, slot))]
    cache.commit_prefill(slot, prompt_tokens)
    for tok in forced:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        rows.append(np.asarray(engine.decode(toks)[slot]))
        cache.advance(slot)
    clean = [i for i, l in enumerate(l for l, kind in enumerate(layer_plan(config)) if kind == "kda")
             if l <= int(config["first_k_dense_replace"])]
    states = [np.asarray(cache.state["kda_state"][i, slot]) for i in clean]
    cache.reset()
    got, inside = np.stack(rows), {}
    want = np.asarray(logits(engine.params, config, prompt + forced, range(prompt_tokens - 1, prompt_tokens + steps), wrong, inside))
    scale = float(np.max(np.abs(want))) or 1.0
    by_row = np.max(np.abs(got.astype(np.float64) - want), axis=-1) / scale
    err = reference.rel_at_scale(got, want)
    state_err = max(reference.rel_at_scale(state, inside["kda_state"][i]) for i, state in zip(clean, states))
    experts = _expert_rows_off(engine, inside)
    finite = bool(np.isfinite(got).all() and all(np.isfinite(state).all() for state in states))
    return {"logits_max_abs_diff_over_max": err, "tolerance": SERVE_LOGITS_TOLERANCE,
            "state_max_abs_diff_over_max": state_err, "state_tolerance": STATE_TOLERANCE, "state_layers": len(clean),
            **experts, "expert_row_tolerance": EXPERT_ROW_TOLERANCE, "expert_rows_off_most": EXPERT_ROWS_OFF_MOST,
            "ok": bool(finite and err <= SERVE_LOGITS_TOLERANCE and state_err <= STATE_TOLERANCE
                       and experts["expert_rows_off"] <= EXPERT_ROWS_OFF_MOST),
            "prefill_row": float(by_row[0]), "worst_decode_row": float(by_row[1:].max()) if steps else 0.0,
            "argmax_agreement": float(np.mean(np.argmax(got, -1) == np.argmax(want, -1))),
            "prompt_tokens": prompt_tokens, "decode_steps": steps, "wrong": wrong}


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic (every parameter that a token multiplies; the
# norms' gains and the small float32 leaves are counted where bytes are), so that
# no later PR moves a share by recounting.  REAL widths: a latent row of 576.
def _row(c: Dict[str, Any]) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def _inner(c: Dict[str, Any]) -> int:
    return c["num_attention_heads"] * c["head_dim"]


def kda_params(c: Dict[str, Any]) -> int:
    """One delta-rule mixer: ``W_qkv``, the convolution's taps, ``W_f``, ``beta`` and the output gate, ``W_o``."""
    E, I, H = c["hidden_size"], _inner(c), c["num_attention_heads"]
    return E * 3 * I + c["short_conv_kernel_size"] * 3 * I + E * I + 2 * E * H + I * E


def kda_float32_params(c: Dict[str, Any]) -> int:
    """``dt_bias`` and ``A_log``: the leaves the tree keeps in float32."""
    return _inner(c) + c["num_attention_heads"]


def mla_params(c: Dict[str, Any]) -> int:
    """One latent mixer: a direct ``W_q``, ``kv_a``, the two halves of ``kv_b``, the output gate, ``W_o``."""
    E, H = c["hidden_size"], c["num_attention_heads"]
    return (E * H * c["qk_head_dim"] + E * _row(c) + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + E * H + H * c["v_head_dim"] * E)


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["num_shared_experts"] * c["moe_shared_expert_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    """The router's matrix and its selection bias (both float32)."""
    total, _held, _first = _share(c)
    return (c["hidden_size"] + 1) * total


def dense_mlp_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    plan = layer_plan(c)
    dense = int(c["first_k_dense_replace"])
    return {"kda": plan.count("kda"), "mla": plan.count("mla"), "dense": dense, "expert": c["num_hidden_layers"] - dense}


def param_count(c: Dict[str, Any]) -> int:
    """Parameters this chip holds (the share): embedding and head apart (untied)."""
    n, E = layer_counts(c), c["hidden_size"]
    norms = c["num_hidden_layers"] * 2 * E + n["kda"] * c["head_dim"] + n["mla"] * c["kv_lora_rank"] + E
    return (n["kda"] * (kda_params(c) + kda_float32_params(c)) + n["mla"] * mla_params(c) + n["dense"] * dense_mlp_params(c)
            + n["expert"] * (shared_params(c) + router_params(c) + c["num_experts"] * expert_params(c))
            + 2 * c["vocab_size"] * E + norms)


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but the routers with their biases and the gates' ``dt_bias`` / ``A_log`` (float32)."""
    n = layer_counts(c)
    return 2 * param_count(c) + 2 * (n["expert"] * router_params(c) + n["kda"] * kda_float32_params(c))


def state_bytes_per_slot(c: Dict[str, Any], serve: Optional[Dict[str, Any]] = None) -> int:
    """A slot's matrix states (float32) and convolution tails (bf16), all delta-rule layers."""
    state = c["num_attention_heads"] * c["head_dim"] * c["head_dim"] * jnp.dtype((serve or {}).get("state_dtype", "float32")).itemsize
    return layer_counts(c)["kda"] * (state + (c["short_conv_kernel_size"] - 1) * 3 * _inner(c) * 2)


def matrix_state_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """ONE delta-rule layer's matrix states, every slot's."""
    return int(serve["slots"]) * c["num_attention_heads"] * c["head_dim"] * c["head_dim"] * 4


def latent_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """What a position leaves in ONE latent layer of the cache: a row of 576."""
    return _row(c) * itemsize


def pool_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """... as the pool keeps it, all latent layers: rows padded to whole 128-lane tiles."""
    return layer_counts(c)["mla"] * -(-_row(c) // 128) * 128 * itemsize


def cache_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """The latent pool and every slot's states and tails."""
    return (pool_pages(serve) * int(serve["page_size"]) * pool_bytes_per_position(c)
            + int(serve["slots"]) * state_bytes_per_slot(c, serve))


def decode_step_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, latent_positions_read: float,
                      experts_touched: Optional[float] = None) -> float:
    """The bytes one decode step must move: every weight held once but the
    embedding (a row a slot is gathered) and the held experts that got no token
    (``experts_touched``: the count over all layers; all, where it is not
    given); every slot's states and tails read and written; the live latent rows
    (``latent_positions_read``: positions summed over slots and latent layers,
    576 wide); the logits written."""
    S, layers = int(serve["slots"]), layer_counts(c)["expert"]
    touched = layers * c["num_experts"] if experts_touched is None else experts_touched
    weights = (weight_bytes(c) - 2 * (c["vocab_size"] - S) * c["hidden_size"]
               - 2 * expert_params(c) * (layers * c["num_experts"] - touched))
    return (weights + 2 * S * state_bytes_per_slot(c, serve) + latent_positions_read * latent_bytes_per_position(c)
            + S * c["vocab_size"] * 4)


def kda_step_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """What one call of ``kda_step`` (one layer, every slot) must move: twice the state it touches."""
    return 2 * matrix_state_bytes(c, serve)


def kda_chunk_flops(c: Dict[str, Any], rows: int, chunk: int = 128) -> int:
    """Operations of one ``kda_chunk`` call over ``rows`` positions, as the
    mathematics has them (the triangular solve by substitution, half of each
    triangular product; not the kernel's twelve products for an inverse): a
    chunk and head, the two products with the state at the chunk's start and the
    two that end it (``4 C d^2`` multiply-adds), ``A`` and ``B`` (``C^2 d``), the
    solve and ``B U`` (``C^2 d``)."""
    d = c["head_dim"]
    return 2 * c["num_attention_heads"] * (rows // chunk) * (4 * chunk * d * d + 2 * chunk * chunk * d)


def kda_chunk_bytes(c: Dict[str, Any], rows: int) -> int:
    """... and what it must move: ``q``, ``k``, ``v`` and the gates read and the
    outputs written (float32 rows of H d), ``beta``, every head's last state written."""
    H, d = c["num_attention_heads"], c["head_dim"]
    return 4 * (rows * (5 * H * d + H) + H * d * d)


def mla_prefill_attention_flops(c: Dict[str, Any], bucket: int) -> float:
    """Causal attention of ONE latent layer over ``bucket`` positions in the
    expanded form at the real widths (scores 192, values 128; half the square)."""
    per_pair = 2.0 * (c["qk_head_dim"] + c["v_head_dim"])
    return c["num_attention_heads"] * per_pair * bucket * bucket / 2.0


def mla_prefill_attention_bytes(c: Dict[str, Any], bucket: int, itemsize: int = 2) -> float:
    """... and what that layer's flash forward must move: queries and keys (192)
    of every head, values and outputs (128), once."""
    return 2.0 * c["num_attention_heads"] * (c["qk_head_dim"] + c["v_head_dim"]) * bucket * itemsize


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
# As families/longcat_flash.py: the chip's trace names a device event by its
# whole HLO instruction (output shapes, then every operand with its shape) and
# carries no scope (PR 61 found none), so the kernels are known by the
# instruction's NAME and everything else by a table of shapes, from the
# configuration alone, for a program over ``rows`` rows of the stream.  An op
# belongs to the first mechanism one of whose signatures its text shows: the
# head (everything as wide as the vocabulary), the routed experts (their arrays
# lead with the held count, or are as wide as the router or one expert), the
# delta rule (the fused ``q~ | k~ | v~`` of 12,288, the states, the tails, the
# per-head triples), the latent mixer, then the dense layer's MLP.  The delta
# rule comes BEFORE the latent mixer because both have 32 heads of 128 and an
# output matrix of one shape: what only the latent mixer has (192-wide heads,
# the row of 576 / 640, the rank) is listed under it, and the shapes both have
# go to the delta rule, five layers of six.  Where the latent mixer's ``W_q`` is
# as wide as the dense layer's MLP (32 x 192 = 6144 at the published widths) that
# one matrix's product reads as the MLP's, whose three matrices are three times it.
MECHANISMS = ("head", "routed", "kda", "mla", "mlp")
STEP_KERNEL, CHUNK_KERNEL, DECODE_KERNEL, PREFILL_KERNEL = "kda_step", "kda_chunk", "paged_decode_latent", "mla_flash_fwd"
KERNELS = {STEP_KERNEL: "kda", CHUNK_KERNEL: "kda", DECODE_KERNEL: "mla", PREFILL_KERNEL: "mla", "grouped_swiglu": "routed"}


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any], rows: Optional[int] = None) -> Dict[str, Sequence[str]]:
    S = int(serve["slots"])
    R = S if rows is None else int(rows)
    E, H, V, I, D = c["hidden_size"], c["num_attention_heads"], c["vocab_size"], c["intermediate_size"], c["head_dim"]
    X, held, F, k = _share(c)[0], c["num_experts"], c["moe_intermediate_size"], c["num_experts_per_tok"]
    Fs = c["num_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    nope, rope, v, rank, qk = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"], c["qk_head_dim"]
    row, padded, inner, K = _row(c), -(-_row(c) // 128) * 128, _inner(c), c["short_conv_kernel_size"]
    unless = lambda clash, *texts: () if clash else texts
    # the pairs' own arrays (sorted rows, their order, a token's k choices) at this program's rows, whole or in the equal
    # pieces a long rung's routed part takes (``moe/dropless.py:row_pieces``'s rule, written again as ``prefill_rungs`` is)
    pieces = -(-R // ((512 << 20) // (6 * k * E)))
    while R % pieces:
        pieces += 1
    P = R // pieces
    pairs = [t for n in {R, P} for t in (f"[{n * k}]", f"[{n * k},{E}]", f"[{n * k},{F}]", f"[{n},{k},", f"[{n},{k}]", f"[{n},{X}]",
                                          f"[{n},{held}]", f"[{n},{held + 1}]", f"[{n},{c['n_group']}")]
    return {
        "head": (f",{V}]", *unless(R >= 1024, f"[{V},{E}]")),
        "routed": ("ragged-dot", f"[{held},{E},{F}]", f"[{held},{F},{E}]", f"[{E},{X}]", f"[{X}]", f",{X}]", f"[{held + 1}]",
                   f"[{held}]", f"[{held},{R},", f"[{held},{R * k},", f"[{held},128,", f"[{held * 128},{E}]", f"[{E},{Fs}]", *unless(Fs == R, f"[{Fs},{E}]"), f",{Fs}]",
                   f",{F}]", *pairs),
        "kda": (f",{3 * inner}]", f"[{E},{3 * inner}]", f",{H},{D},{D}]", f",{K - 1},{3 * inner}]", f",{K},{3 * inner}]",
                f"[{E},{inner}]", *unless(inner == R, f"[{inner},{E}]"), f",{inner}]", f",{H},{D}]", f"[{E},{H}]", f",{H}]",
                f"[{R},{H},{D}", f",128,{D}]", f",2,{H},{D}]"),
        "mla": (f",{H},{qk}]", f",{H},{nope}]", f",{H},{rope}]", f"[{H},{R},", f"[{H},{nope},{rank}]", f"[{H},{rank},{v}]",
                f",{row}]", f",{padded}]", *unless(H * qk == I, f"[{E},{H * qk}]", f",{H * qk}]"), f"[{E},{row}]", f",{rank}]", f",{qk}]", f",{rope}]",
                f",{rope // 2}]", f"[{H},", f",{H},",
                # (the decode kernel's XLA leg, on a CPU, masks every slot's whole row of positions)
                *unless(rows is not None, f"[{S},{int(serve['positions_per_slot'])}]")),
        "mlp": (f"[{E},{I}]", *unless(R == I, f"[{I},{E}]"), f",{I}]"),
    }


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


# ------------------------------------------------- what a traced run reads of these layers
# benchmark/README.md, "Adding a family": the names a family returns from ``layer_readings``.  Device time is
# attributed to the traced DECODE AND PREFILL programs (the joined launches), the table of shapes at each launch's
# rows.  REAL widths: a row of 576, scores 192 and values 128 wide, live positions only.
LAYER_COUNTERS = {"kda_state_bytes_rw", "latent_bytes_read", "route_rows_held_group"}


def layer_readings(view) -> Dict[str, Any]:
    c, steps, config, serve = view.counters, view.steps, view.config, view.serve
    pairs = c.get("moe_assignments") or 0
    if not steps or not pairs:
        return {}
    n = layer_counts(config)
    # (``latent_gb_per_step``, ``experts_held_share``, ``experts_device_share`` and ``mla_decode_roofline`` are NOT returned:
    # ``tests/benchmark/test_bm_mla.py`` holds those four entries' lists to the cells of PR 59, and a family returns what
    # lists its cell; the next ``benchmark`` PR lists it and adds the four lines here, ROADMAP D14 a)
    out = {"kda_state_gb_per_step": view.per_step_gb("kda_state_bytes_rw"),
           # of the active rows of the decode steps, a layer at a time: those whose kept groups include the held one
           "held_group_row_share": 100.0 * c["route_rows_held_group"] / (pairs / config["num_experts_per_tok"]),
           "experts_load_imbalance": view.load_imbalance()}
    if view.programs is None:
        return out
    rate, flops = view.hbm_rate, view.flops
    decodes, prefills = view.launches("decode"), view.launches("prefill")
    kernels = (STEP_KERNEL, CHUNK_KERNEL, PREFILL_KERNEL)
    pick = lambda op, _name: op if op in kernels else None
    in_decodes, decode_kernels = view.launch_times(decodes, pick)
    in_prefills, prefill_kernels = view.launch_times(prefills, pick)
    both = {k: in_decodes.get(k, 0.0) + in_prefills.get(k, 0.0) for k in set(in_decodes) | set(in_prefills)}
    out["kda_device_share"] = view.share(both, "kda")
    out["mla_device_share"] = view.share(both, "mla")
    # live positions x latent layers of a traced decode step, from the bytes the engine counted (its rows are padded)
    positions = c["latent_bytes_read"] / steps / (pool_bytes_per_position(config) / n["mla"])
    if decodes:
        step_ns = decode_kernels.get(STEP_KERNEL)
        if step_ns:     # a call's must-move bytes over the HBM rate, against the mean of the calls' device times
            out["kda_step_roofline"] = 100.0 * (kda_step_bytes(config, serve) / rate) / (sum(step_ns) / len(step_ns) * 1e-9)
        moved = decode_step_bytes(config, serve, latent_positions_read=positions,
                                  experts_touched=(c.get("moe_experts_touched") or 0) / steps)
        program_ns = statistics.median(launch.program_ns for launch in decodes)
        out["step_hbm_roofline_share"] = 100.0 * (moved / rate) / (program_ns * 1e-9)
    rungs = [launch.rung for launch in prefills if launch.rung]
    chunk_ns = sum(prefill_kernels.get(CHUNK_KERNEL, ()))
    if chunk_ns and rungs:
        must = sum(n["kda"] * max(kda_chunk_flops(config, r) / flops, kda_chunk_bytes(config, r) / rate) for r in rungs)
        out["kda_chunk_roofline"] = 100.0 * must / (chunk_ns * 1e-9)
    flash_ns = sum(prefill_kernels.get(PREFILL_KERNEL, ()))
    if flash_ns and rungs:
        must = sum(n["mla"] * max(mla_prefill_attention_flops(config, r) / flops, mla_prefill_attention_bytes(config, r) / rate)
                   for r in rungs)
        out["mla_prefill_roofline"] = 100.0 * must / (flash_ns * 1e-9)
    return out
