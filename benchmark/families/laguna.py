"""The Laguna family (``"model": "laguna"``, HF ``model_type`` ``laguna``): layers
that MIX window and full attention (``layer_types``: a full layer, then three
sliding ones of window 512) on 8 key heads of 128, with MORE query heads on the
sliding layers (64 against 48 at XS.2) and another rotary term (the whole head
at theta 1e4 against half of it under YaRN), a gate on every head's output, a
leading dense SwiGLU and then 256 small routed experts by sigmoid routing beside
one shared expert, an untied head; ``vescale_tpu/models/laguna.py`` under
``vescale_tpu/serve/hybrid_engine.py`` in the program.  A family that only
serves.  The names are those ``benchmark/README.md`` ("Adding a family") fixes.

What a reader of this family needs beyond the README:

- **The cache.**  Two kinds of attention state a slot: ``cache.k`` / ``cache.v``
  hold PAGES of the full layers alone (``layers`` = their count; admission counts
  these), ``cache.state["ring_k"]`` / ``["ring_v"]`` ``(sliding layers, slots,
  window, 8, 128)`` a RING a slot of the newest ``window`` positions (position
  ``p`` at row ``p mod window``).  The pool is smaller than ``slots x
  positions_per_slot`` on purpose (``serve.pool_pages``): the traffic never needs
  the whole allotment, and it would not fit.
- **The runner's check cannot reach the window.**  ``serve_cell.py`` checks 320
  prompt tokens and 4 decode steps: no row of it is 512 positions from its first
  key, so it would pass with no window at all.  ``check_window`` below is the
  check that reaches it (a prompt LONGER than the window on a rung it does not
  fill, then decode steps through ring and pages, every row against the
  reference); the builder runs it on the chip, ``tests/test_laguna.py`` at a toy
  size.  Its readings stand beside ``SERVE_LOGITS_TOLERANCE``.
- **The counters** (``HybridServeEngine.trace_counters``): the engine's
  (``decode_pages_*`` are ONE full layer's pages; ``moe_*`` count the four expert
  layers of the cut), and the model's own ``ring_positions_read``,
  ``ring_positions_unwindowed``, ``ring_bytes_rw``, ``prefill_window_attn_flops``,
  ``prefill_full_attn_flops``.  ``layer_metrics/laguna_serve_mixedlen.py`` reads
  them with the counts at the end of this file.

The reference is straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: a dense ``(T, T)`` mask from ``layer_types`` and ``sliding_window``, a
softmax over it (a block of heads at a time), the rotary terms of both layer
types written out (YaRN's as HF ``_compute_yarn_parameters`` has it), a loop
over the held experts; no kernel, cache, ring, rung or batching, and nothing
imported from the program.  Departures from the published description, each
noted at its line: the three readings the configuration lists under ``assumed``
(the gate, the router, no per-head norm) and nothing else; the init rule is the
program's (the reference reads the program's tree).  The tree is read a layer,
and inside a layer an expert, at a time and cast inside each jitted call: a
float32 copy of the weights (15 GB) never exists.
"""

from __future__ import annotations

import functools
import math
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
# the 1,536 rung and 40 decode steps through ring and pages: 41 rows).  The
# program multiplies in bf16 with float32 accumulation (2^-9 = 2e-3 a rounded
# operand), keeps the residual stream, norms, rotary, gate, router and softmax
# in float32 and rounds K and V to bf16 once; the reference reads the same bf16
# weights.  Scores of deviation 2 (the init rule: ``SCORE_DEVIATION`` in the
# model's file) make a row's softmax peaked, which is what lets ONE key at the
# window's edge show, and also what rounding of q and k is multiplied by: at a
# deviation of 3 the sound program read 1.7e-2 and a window one short 7.7e-2, at
# 2 the readings below, the wider gap.  Readings on the chip at the published
# widths (PERF.md section 6, PR 45, my chip runs; seeds 2147484001, 2147485567,
# 998877665, and the cell's own runs):
#
#   the sound program     ``check_window`` 6.0e-3, 6.0e-3, 7.2e-3; the runner's
#                         lengths 6.9e-3, 8.1e-3, 7.2e-3 (the cell's runs 6e-3 to 8e-3)
#   fp8_weights           5.2e-2 to 6.2e-2: the reference with its weights in e4m3,
#                         the nearest type below the one the configuration states
#   no_window             0.28 to 0.34      window_minus_1   3.7e-2 to 6.1e-2
#   window_plus_1         2.1e-2 to 2.3e-2 (8.8e-2 at a fourth seed): the smallest
#                         fault that must fail: ONE key more of 512 in three layers
#   swapped_rotary        0.68 to 0.74      no_gate          0.26 to 0.31
#   ring not wrapped      0.47 to 0.55      pads in the ring 0.78 to 0.90 (the
#                         program's own faults: ``tests/test_laguna.py:RING_FAULTS``
#                         patched in while a second engine over the same cache traced;
#                         the prefill's own row reads sound under both, 4.8e-3 to 5.8e-3)
#   top7                  7.3e-3 to 7.9e-3: one kept expert of eight fewer CANNOT be
#                         told from rounding (the routed part is a few per cent of the
#                         stream by construction: ``ROUTED_DOWN_GAIN``, PR 34's rule)
#
# The limit lies 1.85 times over the largest sound reading, 1.4 times under the
# smallest reading of the smallest fault that must fail, and 3.5 times under
# fp8's smallest.
SERVE_LOGITS_TOLERANCE = 1.5e-2


FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# the published keys whose values this family's block fixes: a file that says otherwise is another architecture
FIXED = {"attention_bias": False, "tie_word_embeddings": False, "gating": True, "moe_apply_router_weight_on_input": False}
# ... and the readings of what the published config does not settle, as the file must state them under ``assumed``
ASSUMED = {"attention_gate": "softplus", "router": "sigmoid_topk_renormalised", "qk_norm": False}


# --------------------------------------------------------------- the program
def _rope(config: Dict[str, Any], kind: str) -> Dict[str, Any]:
    return config["rope_parameters"][kind]


def program_config(config: Dict[str, Any], *, max_positions: int = 0, prefill_chunk: int = 128):
    """The program's ``LagunaConfig`` from a configuration file's object; the
    published keys go through unchanged.  ``max_positions`` sizes nothing (the
    rotary terms are computed from the positions)."""
    from vescale_tpu.models.laguna import LagunaConfig

    for key, value in FIXED.items():
        if config.get(key) != value:
            raise SpecError(f"this family's block has {key} = {value!r}; the file says {config.get(key)!r}")
    assumed = config.get("assumed") or {}
    for key, value in ASSUMED.items():
        if assumed.get(key) != value:
            raise SpecError(f"the program reads {key} as {value!r} (the source's config does not settle it): the file "
                            f"states it under assumed, and says {assumed.get(key)!r}")
    full, sliding = _rope(config, FULL), _rope(config, SLIDING)
    if full.get("rope_type") != "yarn" or sliding.get("rope_type") != "default":
        raise SpecError("this family's full layers rotate under YaRN and its sliding layers plainly")
    return LagunaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"], num_hidden_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        num_key_value_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        sliding_window=config["sliding_window"], mlp_layer_types=tuple(config["mlp_layer_types"]),
        intermediate_size=config["intermediate_size"], moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config["shared_expert_intermediate_size"], num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"], experts_held=config["num_experts"], first_expert_held=0,
        moe_routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
        full_rope_theta=float(full["rope_theta"]), full_rope_factor=float(full["factor"]),
        full_rope_original_max_position_embeddings=int(full["original_max_position_embeddings"]),
        full_rope_beta_fast=float(full["beta_fast"]), full_rope_beta_slow=float(full["beta_slow"]),
        full_rope_attention_factor=float(full["attention_factor"]),
        full_partial_rotary_factor=float(full["partial_rotary_factor"]),
        sliding_rope_theta=float(sliding["rope_theta"]),
        sliding_partial_rotary_factor=float(sliding["partial_rotary_factor"]),
        rms_norm_eps=float(config["rms_norm_eps"]), prefill_chunk=int(prefill_chunk), dtype=jnp.bfloat16)


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
        raise RuntimeError(f"this checkout's program cannot run the laguna family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a paged cache of the full layers' pages with the
    sliding layers' rings beside it; ``HybridServeEngine`` with every rung and
    the decode step compiled."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.laguna import init_params
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
    from vescale_tpu.models.laguna import init_params
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
             "kv_pool_bytes": nbytes(cache.k.data) + nbytes(cache.v.data),
             "slot_state_bytes": sum(nbytes(a) for a in cache.state.values())}
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
# what a wrong computation reads (``wrong=``: the tolerance's reasons, the tests, the builder's chip readings):
# the weights in the nearest type below the one the configuration states; no window on the sliding layers; a
# window one position short and one long; the two layer types' rotary terms swapped; the gate left out; one
# kept expert fewer
FAULTS = ("fp8_weights", "no_window", "window_minus_1", "window_plus_1", "swapped_rotary", "no_gate", "top7")


def _weights(wrong: str):
    """How a weight is read: as float32, or (the fault ``fp8_weights``) rounded to e4m3 first."""
    if wrong == "fp8_weights":
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(F32) if a.ndim >= 2 else a.astype(F32)
    return lambda a: a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary_parameters(rope: Dict[str, Any], head_dim: int):
    """``(inv_freq (rotated / 2,), attention factor)`` of one entry of
    ``rope_parameters``.  ``default``: ``theta^(-2i / rotated)``.  ``yarn`` (HF
    ``_compute_yarn_parameters``): a pair that turns more than ``beta_fast``
    times over the original length keeps its frequency, one that turns fewer
    than ``beta_slow`` times has it divided by ``factor``, a linear ramp between
    (the corrections' dimensions floored and ceiled); cos and sin are multiplied
    by ``attention_factor``."""
    dim = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    factor, original = float(rope["factor"]), int(rope["original_max_position_embeddings"])
    correction = lambda rotations: dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction(float(rope["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / ((high if high != low else high + 0.001) - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = plain / factor * (1.0 - extrapolated) + plain * extrapolated
    return inv.astype(np.float32), float(rope.get("attention_factor") or (0.1 * math.log(factor) + 1.0))


def sees(kind: str, T: int, window: int, wrong: str = ""):
    """The dense (T, T) mask of a layer of ``kind``: ``j <= i``, and on a
    sliding layer ``i - j < window``."""
    i = np.arange(T)
    mask = i[None, :] <= i[:, None]
    if kind == SLIDING and wrong != "no_window":
        width = window + {"window_minus_1": -1, "window_plus_1": 1}.get(wrong, 0)
        mask = mask & (i[:, None] - i[None, :] < width)
    return mask


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "factor", "wrong"))
def attention(ap: Dict[str, Any], u, inv_freq, mask, *, heads: int, kv_heads: int, head_dim: int, factor: float,
              wrong: str = ""):
    """One layer's attention over one sequence ``u`` (T, E) from position 0,
    float32, under the dense ``mask`` (T, T), ``HEAD_BLOCK`` heads at a time."""
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        q = (u @ f(ap["q_proj"])).reshape(T, heads, head_dim)
        k = (u @ f(ap["k_proj"])).reshape(T, kv_heads, head_dim)         # (assumed: no per-head norm on q and k)
        v = (u @ f(ap["v_proj"])).reshape(T, kv_heads, head_dim)
        rot = 2 * inv_freq.shape[0]                                      # the rotated part; what lies past it passes
        angle = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
        cos, sin = (jnp.concatenate([t, t], axis=-1)[:, None, :] * factor for t in (jnp.cos(angle), jnp.sin(angle)))
        turn = lambda x: jnp.concatenate([x[..., :rot] * cos + _rotate_half(x[..., :rot]) * sin, x[..., rot:]], axis=-1)
        q, k = turn(q), turn(k)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))                            # repeat_kv

        def some_heads(args):
            qb, kb, vb = args                                                                        # (hb, T, hd)
            s = jnp.einsum("hqd,hkd->hqk", qb, kb) * head_dim ** -0.5
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1), vb)

        hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else 1
        split = lambda a: a.transpose(1, 0, 2).reshape(heads // hb, hb, T, head_dim)
        o = jax.lax.map(some_heads, (split(q), split(k), split(v))).reshape(heads, T, head_dim).transpose(1, 0, 2)
        if wrong != "no_gate":
            # (assumed: ``gating: true`` is a softplus gate, one scalar a head and position, from the normed input)
            o = o * jax.nn.softplus(u @ f(ap["g_proj"]))[:, :, None]
        return o.reshape(T, heads * head_dim) @ f(ap["o_proj"])


@functools.partial(jax.jit, static_argnames=("k", "scale"))
def _route(router, h, *, k: int, scale: float):
    """(assumed: the router) float32 sigmoid scores, the ``k`` largest, renormalised over the kept, times ``scale``."""
    with jax.default_matmul_precision("highest"):
        weights, idx = jax.lax.top_k(jax.nn.sigmoid(h @ router.astype(F32)), k)
        return idx, scale * weights / jnp.sum(weights, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("wrong",))
def _swiglu(h, w_gate, w_up, w_down, wrong: str = ""):
    f = _weights(wrong)
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def expert_layer(ep: Dict[str, Any], h, *, k: int, scale: float, wrong: str = ""):
    """``sum over the kept e of w_e E_e(h) + E_shared(h)``: every held expert on
    every token, weighted by the gate it has there (0 where it is not among the
    token's ``k``); ``top7`` (a fault) keeps one fewer."""
    idx, gates = _route(ep["router"], h, k=k - 1 if wrong == "top7" else k, scale=scale)
    out = _swiglu(h, ep["shared"]["gate"], ep["shared"]["up"], ep["shared"]["down"], wrong=wrong)
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


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], wrong: str = ""):
    """The residual stream after the last layer, (T, E) float32.  ``wrong`` (one
    of ``FAULTS``) computes a wrong model on the same weights."""
    if wrong and wrong not in FAULTS:
        raise ValueError(f"wrong is one of {FAULTS}")
    c, eps, T = config, float(config["rms_norm_eps"]), len(tokens)
    x = _weights(wrong)(jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)), axis=0))
    for l in range(c["num_hidden_layers"]):
        lp, kind = params[f"layers_{l}"], c["layer_types"][l]
        rope = _rope(c, ({FULL: SLIDING, SLIDING: FULL}[kind] if wrong == "swapped_rotary" else kind))
        inv_freq, factor = rotary_parameters(rope, c["head_dim"])
        x = x + attention(lp["self_attn"], _norm(lp["input_layernorm"]["weight"], x, eps=eps), jnp.asarray(inv_freq),
                          jnp.asarray(sees(kind, T, c["sliding_window"], wrong)),
                          heads=c["num_attention_heads_per_layer"][l], kv_heads=c["num_key_value_heads"],
                          head_dim=c["head_dim"], factor=factor, wrong=wrong)
        h = _norm(lp["post_attention_layernorm"]["weight"], x, eps=eps)
        if c["mlp_layer_types"][l] == DENSE:
            x = x + _swiglu(h, lp["mlp"]["gate"], lp["mlp"]["up"], lp["mlp"]["down"], wrong=wrong)
        else:
            x = x + expert_layer(lp["mlp"], h, k=c["num_experts_per_tok"], scale=float(c["moe_routed_scaling_factor"]),
                                 wrong=wrong)
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int], wrong: str = ""):
    """Next-token logits (float32) at the positions ``rows``."""
    x = hidden_states(params, config, tokens, wrong)[jnp.asarray(np.asarray(rows, np.int32))]
    return _head(params["norm"]["weight"], params["lm_head"]["kernel"], x, eps=float(config["rms_norm_eps"]), wrong=wrong)


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# ------------------------------------------- the check that reaches the window
CHECK_PROMPT_TOKENS = 1100      # longer than two windows; on the 1,536 rung, which it does not fill
CHECK_DECODE_STEPS = 40


def check_window(engine, config: Dict[str, Any], seed: int, prompt_tokens: int = CHECK_PROMPT_TOKENS,
                 steps: int = CHECK_DECODE_STEPS, wrong: str = "") -> Dict[str, Any]:
    """A prefill of one seeded prompt of ``prompt_tokens`` tokens and then
    ``steps`` teacher-forced decode steps through ring and pages, EVERY row
    against the reference's full forward (with the fault ``wrong``, where given:
    what a program with that fault would read against the sound reference),
    logits as a share of the largest: the runner's procedure at lengths that
    reach the window (longer than it, on a rung the prompt does not fill, for
    enough steps that the ring's write row moves on).  The engine's cache must be
    free; it is reset at the end."""
    cache = engine.cache
    vocab = int(config["vocab_size"])
    rng = np.random.default_rng([int(seed), 45])
    prompt = [int(t) for t in rng.integers(1, vocab - 1, prompt_tokens)]
    forced = [int(t) for t in rng.integers(1, vocab - 1, steps)]
    cache.reset()
    slot = cache.alloc(prompt_tokens, steps + 1)
    rows = [engine.prefill(prompt, slot)]
    cache.commit_prefill(slot, prompt_tokens)
    for tok in forced:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        rows.append(engine.decode(toks)[slot])
        cache.advance(slot)
    cache.reset()
    got = np.stack(rows)
    want = np.asarray(logits(engine.params, config, prompt + forced, range(prompt_tokens - 1, prompt_tokens + steps), wrong))
    scale = float(np.max(np.abs(want))) or 1.0
    by_row = np.max(np.abs(got.astype(np.float64) - want), axis=-1) / scale
    err = reference.rel_at_scale(got, want)
    return {"logits_max_abs_diff_over_max": err, "tolerance": SERVE_LOGITS_TOLERANCE,
            "ok": bool(np.isfinite(got).all() and err <= SERVE_LOGITS_TOLERANCE),
            "prefill_row": float(by_row[0]), "worst_decode_row": float(by_row[1:].max()) if steps else 0.0,
            "argmax_agreement": float(np.mean(np.argmax(got, -1) == np.argmax(want, -1))),
            "prompt_tokens": prompt_tokens, "decode_steps": steps, "wrong": wrong}


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic (parameters that a token multiplies; norm
# weights are counted where bytes are), so that no later PR moves a share by
# recounting.
def layers_of(c: Dict[str, Any], kind: str) -> List[int]:
    return [l for l, t in enumerate(c["layer_types"]) if t == kind]


def attention_params(c: Dict[str, Any], l: int) -> int:
    """q, k, v, o and the gate of layer ``l``."""
    E, hd, H, KV = c["hidden_size"], c["head_dim"], c["num_attention_heads_per_layer"][l], c["num_key_value_heads"]
    return E * hd * (2 * H + 2 * KV) + E * H


def dense_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["num_experts"]


def layer_params(c: Dict[str, Any], l: int) -> int:
    if c["mlp_layer_types"][l] == DENSE:
        return attention_params(c, l) + dense_params(c)
    return attention_params(c, l) + c["num_experts"] * expert_params(c) + shared_params(c) + router_params(c)


def param_count(c: Dict[str, Any]) -> int:
    """Every parameter of the cut: the layers, embedding and head apart (untied), the norms."""
    E, L = c["hidden_size"], c["num_hidden_layers"]
    return sum(layer_params(c, l) for l in range(L)) + 2 * c["vocab_size"] * E + (2 * L + 1) * E


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but the routers (float32)."""
    sparse = sum(t == SPARSE for t in c["mlp_layer_types"])
    return 2 * param_count(c) + 2 * sparse * router_params(c)


def position_bytes(c: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position in ONE layer: what a page or a ring row holds of it."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def page_bytes_per_position(c: Dict[str, Any]) -> int:
    """What a position leaves in the pages: the full layers' K and V."""
    return len(layers_of(c, FULL)) * position_bytes(c)


def ring_bytes_per_slot(c: Dict[str, Any]) -> int:
    """A slot's rings: ``window`` positions of every sliding layer, whatever the sequence's length."""
    return len(layers_of(c, SLIDING)) * c["sliding_window"] * position_bytes(c)


def cache_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """The pools (``pool_pages`` pages of the full layers) and every slot's rings."""
    pages = int(serve.get("pool_pages") or int(serve["slots"]) * int(serve["positions_per_slot"]) // int(serve["page_size"]) + 1)
    return pages * int(serve["page_size"]) * page_bytes_per_position(c) + int(serve["slots"]) * ring_bytes_per_slot(c)


def kept_pairs(T: int, window: Optional[int] = None) -> int:
    """The (query, key) pairs of ``T`` positions that the causal mask keeps, under a window of ``window`` or none."""
    if window is None or T <= window:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def prefill_attention_flops(c: Dict[str, Any], bucket: int, kind: str) -> int:
    """Useful operations of the ``kind`` layers' attention over a rung: scores
    and values (2 x 2 x head_dim a pair and head) over the pairs the mask keeps."""
    pairs = kept_pairs(bucket, c["sliding_window"] if kind == SLIDING else None)
    return 4 * c["head_dim"] * pairs * sum(c["num_attention_heads_per_layer"][l] for l in layers_of(c, kind))


def prefill_attention_bytes(c: Dict[str, Any], bucket: int, kind: str, itemsize: int = 2) -> int:
    """... and what those layers' flash forwards must move: queries and outputs
    of every head, keys and values of every key head, once."""
    return sum((2 * c["num_attention_heads_per_layer"][l] + 2 * c["num_key_value_heads"]) * bucket * c["head_dim"] * itemsize
               for l in layers_of(c, kind))


def decode_step_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, page_positions_read: float,
                      ring_positions_read: float) -> float:
    """The bytes one decode step must move: every weight held once but the
    embedding (a row a slot is gathered), the live pages of the full layers
    (``page_positions_read``: positions summed over slots, ONE layer's) and the
    rings' live rows (``ring_positions_read``: summed over slots and sliding
    layers), the logits written."""
    S = int(serve["slots"])
    weights = weight_bytes(c) - 2 * (c["vocab_size"] - S) * c["hidden_size"]
    return (weights + page_positions_read * page_bytes_per_position(c) + ring_positions_read * position_bytes(c)
            + S * c["vocab_size"] * 4)


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
# As families/falcon_h1.py: the chip's trace names a device event by its whole
# HLO instruction and carries no scope, so the table is of shapes, from the
# configuration alone, for a program over ``rows`` rows of the stream (a decode
# step's slots, a prefill's rung).  An op belongs to the first mechanism one of
# whose signatures its text shows: the head (everything as wide as the
# vocabulary), then the expert layers (their arrays lead with the expert count,
# or are as wide as an expert or as the router; the shared expert, whose width
# is a routed expert's at XS.2, with them), then attention of both layer types
# (the kernels by name; projections, rotary halves, gates, pools and rings by
# shape), then the leading dense layer's MLP.  At XS.2 that MLP's width, 8,192,
# IS the sliding layers' 64 heads x 128, so its three products answer to the
# sliding projections' shapes and count under attention: one layer of five,
# 0.10 GB of the 7.7 GB a decode step reads.  No metric reads ``mlp``.
MECHANISMS = ("head", "moe", "attention", "mlp")
WINDOW_KERNEL, DECODE_KERNEL = "window_flash_fwd", "paged_decode"


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any], rows: Optional[int] = None) -> Dict[str, Sequence[str]]:
    S, page = int(serve["slots"]), int(serve["page_size"])
    R = S if rows is None else int(rows)
    E, KV, hd, W = c["hidden_size"], c["num_key_value_heads"], c["head_dim"], c["sliding_window"]
    X, F, k, V, I = c["num_experts"], c["moe_intermediate_size"], c["num_experts_per_tok"], c["vocab_size"], c["intermediate_size"]
    Fs = c["shared_expert_intermediate_size"]
    heads = sorted(set(c["num_attention_heads_per_layer"]))
    attention = [DECODE_KERNEL, "flash", f",{KV},{hd}]", f"[{E},{KV * hd}]", f"[{R},{KV * hd}]", f",{hd // 2}]", f",{hd // 4}]",
                 f",{page},{KV},{hd}]", f",{W},{KV},{hd}]", f"[{R},{KV},{hd}]", f"[{S},{W // page}]"]
    for H in heads:
        # (an output projection is as the stream itself at the rung as long as its heads are wide: left out there)
        attention += [f",{H},{hd}]", f"[{H},{R},{hd}]", f"[{E},{H * hd}]", *([f"[{H * hd},{E}]"] if H * hd != R else []),
                      f"[{R},{H * hd}]", f"[{E},{H}]",
                      f"[{R},{H}]", f"[{R},{H},{hd}]", f"[{R},{H},{hd // 2}]", f",{H // KV},{hd}]", f"[{R},{KV},{H // KV},"]
    # the pairs' own arrays (sorted rows, their order, a token's k choices) at this program's rows
    pairs = [f"[{R * k}]", f"[{R * k},{E}]", f"[{R * k},{F}]", f"[{R},{k},{X}]", f"[{R},{k},{E}]", f"[{R},{k},1]", f"[{R},{k}]",
             f"[{R},{X}]", f"[{R},{X + 1}]"]
    mlp = [f"[{E},{I}]", f",{I}]"] + ([f"[{I},{E}]"] if R != I else [])
    return {
        "head": (f",{V}]", f"[{V},{E}]"),
        # (the shared expert's down projection is as the stream itself at the rung as long as it is wide: left out there)
        "moe": ("ragged-dot", f"[{X},{E},{F}]", f"[{X},{F},{E}]", f"[{E},{X}]", f"[{X + 1}]", f"[{X}]", f"[{X},{R},", f"[{X},128,",
                f"[{E},{F}]", f"[{R},{F}]", f"[{E},{Fs}]", f"[{R},{Fs}]", *(f"[{w},{E}]" for w in {F, Fs} if w != R), *pairs),
        "attention": tuple(attention),
        "mlp": tuple(mlp),
    }


# attention's kernels, known by the instruction's NAME before any shape is looked at: a kernel's event lists its
# operands, and the page table of a decode step, (slots, pages a slot) = (128, 512), is as wide as an expert (my chip
# run, PR 45: the pages' ``paged_decode``, 3.2 ms a step, was filed under the experts until this stood here); a
# Pallas call without a name of its own takes its scope's (``vs.attn``: the full layers' causal forward)
ATTENTION_KERNELS = (DECODE_KERNEL, "flash", "vs.attn")


def mechanism_of(op_text: str, signatures: Dict[str, Sequence[str]]) -> str:
    """One of ``MECHANISMS``, or ``other`` (norms and sums of the residual
    stream, the embedding's gather, small copies) for a device event's name."""
    if any(kernel in op_text.split(" = ", 1)[0] for kernel in ATTENTION_KERNELS):
        return "attention"
    for mechanism in MECHANISMS:
        if any(s in op_text for s in signatures[mechanism]):
            return mechanism
    return "other"


def ring_decode_heads(c: Dict[str, Any]) -> Optional[int]:
    """The query heads of the sliding layers, where they tell the ring's
    ``paged_decode`` events from the pages' (``f32[slots, heads, head_dim]``);
    None where both layer types have as many."""
    of = lambda kind: {c["num_attention_heads_per_layer"][l] for l in layers_of(c, kind)}
    sliding = of(SLIDING)
    return next(iter(sliding)) if len(sliding) == 1 and not sliding & of(FULL) else None
