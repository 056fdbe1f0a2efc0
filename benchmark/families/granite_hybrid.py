"""The Granite-4.0-H family (``"model": "granite_hybrid"``, HF ``model_type``
``granitemoehybrid``): Mamba-2 mixers with an attention layer (no positional
term) every few, 72 routed experts top-10 beside one shared expert in every
layer, four multipliers, a tied head; ``vescale_tpu/models/granite_hybrid.py``
and ``vescale_tpu/serve/hybrid_engine.py`` in the program.  A family that only
serves.  The names are those ``benchmark/README.md`` ("Adding a family") fixes.

What a reader of this family needs beyond the README:

- **The share.**  A configuration is one chip's share of a layer
  (``"share": {"chips": 2, "of": ["num_local_experts", "vocab_size"]``, and
  optionally ``"index"``, this chip's place among them, 0 where left out):
  ``num_local_experts`` and ``vocab_size`` are what is held HERE, their
  source's values are under ``published``; the router keeps its published
  width and its experts per token.  Held are expert ids ``index * held ..`` and
  the first ``vocab_size`` rows of the vocabulary.  The program's expert layer
  and the reference below both add up only what the held experts give; a token
  whose ten all lie elsewhere gets nothing from ``moe``, and the shared expert
  is whole on every chip.  ``tests/test_granite_hybrid.py`` shows that the
  shares add up to the uncut layer.
- **The buckets.**  The engine pads a prompt to the next of ``chunk, 2 chunk,
  4 chunk, ..., positions_per_slot`` (256, 512, 1024, 1536) and compiles one
  prefill program a bucket before it is handed over (``warm()``).  The
  runner's check prompt (320 tokens) falls into the 512 bucket, so the pad
  rule (step size 0 in the pad, the convolution tail from the last real
  inputs, the logits row of the last real position) is under the check.
  ``RunRecord.padded_prompt_len`` is the cache's positions a slot, not the
  bucket: read the pad from the counters.
- **The counters** (``HybridServeEngine.trace_counters``, reported by a trace
  session): those ``ServeEngine`` has, with ``prefill_tokens_padded`` =
  ``prefill_bucket_tokens`` = the bucket lengths and ``decode_pages_*`` of the
  one attention layer a period; and of decode steps ``moe_assignments``,
  ``moe_assignments_held``, ``moe_busiest_expert_tokens``,
  ``moe_expert_slots``, ``moe_layer_steps``, ``moe_experts_touched``,
  ``ssm_state_bytes_rw``.
  ``layer_metrics/hybrid_*.py`` read them with the counts at the end of this file.

The reference is straightforward ``jax.numpy`` in float32 at ``highest``
matmul precision: the recurrence one position at a time (``lax.scan``), a
dense softmax, a loop over the held experts; no kernels, cache, chunks or
batching, and nothing imported from the program.  It follows HF
``modeling_granitemoehybrid.py`` (``GraniteMoeHybridMambaLayer``'s Mamba-2
with one group; the gate before the norm).  Departures from the source: the
sum over experts runs over those held here (the share); nothing else.  The
program's tree is read a layer, and inside a layer an expert, at a time and
cast inside each jitted call: a float32 copy of the weights (19 GB) never exists.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.families import ServeSystem
from benchmark.spec import SpecError

# ------------------------------------------------------------------ tolerance
# Serve: prefill (the 512 bucket, 320 real tokens) then four teacher-forced
# decode steps through the cache, against the reference's full float32
# forward, as a share of the largest reference logit.  The program multiplies
# in bf16 with float32 accumulation (2^-9 = 2e-3 a rounded operand) and keeps
# the residual stream, the recurrence and the state in float32; the reference
# reads the same bf16 weights.  Readings on the chip (PERF.md, section 6, PR 29):
# this PR's change over 12 seeds 3.2e-3 to 1.4e-2, the largest single row of
# some 190 2.0e-2; the reference with its weights in fp8 (e4m3), the nearest
# type below the one the configuration states, 3.2e-2 to 4.0e-2, which this
# limit fails; computing in fp8 too would read more.  Where the readings above
# 5e-3 come from: a token whose tenth and eleventh router scores lie closer
# than bf16 rounding moves them keeps another expert than the reference does
# (its gate is about 0.06), and that alone moves a row by about 1e-2.  So this
# check cannot tell top-9 routing (1.7e-2 to 2.3e-2) from the program's own
# rounding, and fails one held expert left out (1.3e-2 to 7.3e-2) in half the
# seeds only; a state kept in bf16 reads as float32 does (four decode steps give
# its rounding nothing to accumulate over).  A wrong pad rule, tail, page,
# scale, multiplier or share moves logits by their own size (order 1).
SERVE_LOGITS_TOLERANCE = 3e-2

SHARED_KEYS = ("num_local_experts", "vocab_size")


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
    total = int(published.get("num_local_experts", config["num_local_experts"]))
    held = int(config["num_local_experts"])
    index = int(share.get("index", 0))
    if "num_local_experts" in share.get("of", ()) and held * int(share["chips"]) != total:
        raise SpecError(f"{share['chips']} chips with {held} experts each do not hold the model's {total}")
    return total, held, index * held


def program_config(config: Dict[str, Any], *, max_positions: int = 0, state_dtype: str = "float32"):
    """The program's ``GraniteHybridConfig`` from a configuration file's
    object; the published keys go through unchanged.  ``max_positions`` sizes
    nothing (no positional term)."""
    from vescale_tpu.models.granite_hybrid import GraniteHybridConfig

    if config.get("position_embedding_type") != "nope":
        raise SpecError(f"this family's attention has no positional term; the file says "
                        f"{config.get('position_embedding_type')!r}")
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise SpecError("layer_types does not list num_hidden_layers layers")
    whole = config.get("published", {}).get("layer_types", kinds)
    if len(whole) % len(kinds) or list(whole) != kinds * (len(whole) // len(kinds)):
        raise SpecError("layer_types is not a whole number of the source's periods (a cut keeps whole periods)")
    if config["hidden_size"] * config["mamba_expand"] != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise SpecError("mamba_expand x hidden_size is not mamba_n_heads x mamba_d_head")
    total, held, first = _share(config)
    return GraniteHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"], layer_types=tuple(kinds),
        num_attention_heads=config["num_attention_heads"], num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        intermediate_size=config["intermediate_size"], shared_intermediate_size=config["shared_intermediate_size"],
        num_experts=total, num_experts_per_tok=config["num_experts_per_tok"], experts_held=held,
        first_expert_held=first, mamba_n_heads=config["mamba_n_heads"], mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"], mamba_d_conv=config["mamba_d_conv"],
        mamba_n_groups=config["mamba_n_groups"], mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]), logits_scaling=float(config["logits_scaling"]),
        rms_norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16, state_dtype=jnp.dtype(state_dtype))


def _cache_config(cfg, serve: Dict[str, Any]):
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

    return hybrid_cache_config(cfg, num_slots=int(serve["slots"]), page_size=int(serve["page_size"]),
                               pages_per_slot=int(serve["positions_per_slot"]) // int(serve["page_size"]))


def _serve_config(config: Dict[str, Any], serve: Dict[str, Any]):
    if serve["weight_dtype"] != "bfloat16":
        raise ValueError("serve cells hold their weights in bfloat16")
    try:
        return program_config(config, state_dtype=serve["state_dtype"])
    except ImportError as e:
        raise RuntimeError(f"this checkout's program cannot run the granite_hybrid family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a paged cache of the attention layers' K and V
    with the state-space layers' slot state beside it; ``HybridServeEngine``
    with every bucket compiled."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.granite_hybrid import init_params
    from vescale_tpu.serve import HybridServeEngine, PagedKVCache

    mesh = DeviceMesh(("tp",), (1,), devices=list(devices[:1]))
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(seed))
    cache = PagedKVCache(_cache_config(cfg, serve), mesh)
    return ServeSystem(params, cache, HybridServeEngine(cfg, mesh, params, cache).warm(), cfg.vocab_size)


def rehearse_serve(name: str, config: Dict[str, Any], serve: Dict[str, Any], devices):
    """Every prefill bucket and the decode step, lowered for described
    devices: shapes where the cache would allocate (two functions patched for
    the duration, here, not in the program)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.granite_hybrid import init_params
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
             "kv_pool_bytes": 2 * nbytes(cache.k.data),
             "slot_state_bytes": sum(nbytes(a) for a in cache.state.values())}
    held = (cache.k.data, cache.v.data, cache.state["ssm"], cache.state["conv"])
    programs = [(f"{name}: prefill, bucket of {b} positions, depth {cfg.num_hidden_layers}",
                 engine._prefill_fn.lower(params, *held, i32(b), i32(), i32(b // page), i32()))
                for b in engine.buckets]
    programs.append((f"{name}: decode step, {S} slots x {cache.max_seq_len} positions",
                     engine._decode_fn.lower(params, *held, i32(S, cache.config.pages_per_slot), i32(S), i32(S))))
    return sizes, programs


# ------------------------------------------------------------- the reference
F32 = jnp.float32
f = lambda a: a.astype(F32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * f(w)


@functools.partial(jax.jit, static_argnames=("heads", "head_width", "state", "eps"))
def mamba_mixer(mp: Dict[str, Any], u, *, heads: int, head_width: int, state: int, eps: float):
    """Mamba-2 over one sequence ``u`` (T, E), float32, a position at a time."""
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        d_inner = heads * head_width
        zxbcdt = u @ f(mp["in_proj"])
        z, xBC, dt = zxbcdt[:, :d_inner], zxbcdt[:, d_inner: 2 * d_inner + 2 * state], zxbcdt[:, 2 * d_inner + 2 * state:]
        w = f(mp["conv_weight"])                                       # (K, conv_dim): w[K-1] meets the newest input
        K = w.shape[0]
        padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
        xBC = jax.nn.silu(f(mp["conv_bias"]) + sum(w[k] * padded[k: k + T] for k in range(K)))
        x = xBC[:, :d_inner].reshape(T, heads, head_width)
        B, C = xBC[:, d_inner: d_inner + state], xBC[:, d_inner + state:]
        dt = jax.nn.softplus(dt + f(mp["dt_bias"]))
        A, D = -jnp.exp(f(mp["A_log"])), f(mp["D"])

        def position(h, inp):
            x_t, B_t, C_t, dt_t = inp
            h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
            return h, jnp.einsum("hpn,n->hp", h, C_t) + D[:, None] * x_t

        _, y = jax.lax.scan(position, jnp.zeros((heads, head_width, state), F32), (x, B, C, dt))
        y = _rmsnorm(y.reshape(T, d_inner) * jax.nn.silu(z), mp["norm_weight"], eps)
        return y @ f(mp["out_proj"])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale"))
def attention_mixer(ap: Dict[str, Any], u, *, heads: int, kv_heads: int, scale: float):
    """Causal softmax attention with no positional term over one sequence."""
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        q = (u @ f(ap["q_proj"])).reshape(T, heads, -1)
        k = jnp.repeat((u @ f(ap["k_proj"])).reshape(T, kv_heads, -1), heads // kv_heads, axis=1)
        v = jnp.repeat((u @ f(ap["v_proj"])).reshape(T, kv_heads, -1), heads // kv_heads, axis=1)
        s = scale * jnp.einsum("qhd,khd->hqk", q, k)
        p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, -1) @ f(ap["o_proj"])


@functools.partial(jax.jit, static_argnames=("k",))
def _route(router, h, *, k: int):
    with jax.default_matmul_precision("highest"):
        top, idx = jax.lax.top_k(h @ f(router), k)
        return idx, jax.nn.softmax(top, axis=-1)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def expert_layer(ep: Dict[str, Any], h, *, k: int, first_held: int, keep=None):
    """``moe(h) + shared(h)``: every held expert on every token, weighted by
    the gate it has there (0 where it is not among the token's ``k``).
    ``keep`` (tests) drops held experts by local index, to show what one left out reads."""
    idx, gates = _route(ep["router"], h, k=k)
    out = _swiglu(h, ep["shared_gate"], ep["shared_up"], ep["shared_down"])
    for e in range(ep["w_gate"].shape[0]):
        if keep is not None and e not in keep:
            continue
        gate = jnp.sum(jnp.where(idx == first_held + e, gates, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(h, ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e])
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, *, eps: float):
    return _rmsnorm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, embedding, x, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_w, eps) @ f(embedding).T


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int]):
    """The residual stream after the last layer, (T, E) float32."""
    _total, _held, first = _share(config)
    eps, res = float(config["rms_norm_eps"]), float(config["residual_multiplier"])
    x = float(config["embedding_multiplier"]) * f(
        jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)), axis=0))
    for l, kind in enumerate(config["layer_types"]):
        lp = params[f"layers_{l}"]
        u = _norm(lp["input_layernorm"]["weight"], x, eps=eps)
        if kind == "mamba":
            y = mamba_mixer(lp["mixer"], u, heads=config["mamba_n_heads"], head_width=config["mamba_d_head"],
                            state=config["mamba_d_state"], eps=eps)
        else:
            y = attention_mixer(lp["mixer"], u, heads=config["num_attention_heads"],
                                kv_heads=config["num_key_value_heads"], scale=float(config["attention_multiplier"]))
        x = x + res * y
        h = _norm(lp["post_attention_layernorm"]["weight"], x, eps=eps)
        x = x + res * expert_layer(lp["moe"], h, k=config["num_experts_per_tok"], first_held=first)
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int]):
    """Next-token logits (float32) over the held rows of the vocabulary, at the positions ``rows``."""
    x = hidden_states(params, config, tokens)[jnp.asarray(np.asarray(rows, np.int32))]
    return _head(params["norm"]["weight"], params["embed_tokens"]["embedding"], x,
                 eps=float(config["rms_norm_eps"])) / float(config["logits_scaling"])


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic (parameters that a token multiplies; norm
# weights, biases, A, D and dt_bias are counted where bytes are), so that no
# later PR moves a roofline share by recounting.
def _dims(c: Dict[str, Any]):
    d_inner = c["mamba_n_heads"] * c["mamba_d_head"]
    conv_dim = d_inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return d_inner, conv_dim, d_inner + conv_dim + c["mamba_n_heads"]


def mamba_params(c: Dict[str, Any]) -> int:
    d_inner, conv_dim, in_proj = _dims(c)
    return (c["hidden_size"] * in_proj + d_inner * c["hidden_size"] + (c["mamba_d_conv"] + 1) * conv_dim
            + d_inner + 3 * c["mamba_n_heads"])


def attention_params(c: Dict[str, Any]) -> int:
    hd = c["hidden_size"] // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return c["hidden_size"] * (q + 2 * kv) + q * c["hidden_size"]


def shared_and_router_params(c: Dict[str, Any]) -> int:
    total, _held, _first = _share(c)
    return 3 * c["hidden_size"] * c["shared_intermediate_size"] + c["hidden_size"] * total


def expert_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters this chip holds (the share), the tied embedding once."""
    h = c["hidden_size"]
    kinds = c["layer_types"]
    per_layer = shared_and_router_params(c) + c["num_local_experts"] * expert_params(c) + 2 * h
    return (kinds.count("mamba") * mamba_params(c) + kinds.count("attention") * attention_params(c)
            + len(kinds) * per_layer + c["vocab_size"] * h + h)


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but the router (float32) and A_log, D, dt_bias (float32)."""
    total, _held, _first = _share(c)
    f32_extra = len(c["layer_types"]) * c["hidden_size"] * total + c["layer_types"].count("mamba") * 3 * c["mamba_n_heads"]
    return 2 * param_count(c) + 2 * f32_extra


def state_bytes_per_slot(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """A slot's recurrent state and convolution tail, all state-space layers."""
    _d_inner, conv_dim, _ = _dims(c)
    ssm = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] * jnp.dtype(serve["state_dtype"]).itemsize
    return c["layer_types"].count("mamba") * (ssm + (c["mamba_d_conv"] - 1) * conv_dim * 2)


def kv_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["layer_types"].count("attention") * c["num_key_value_heads"] * hd * itemsize


def decode_step_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, kv_pages_read_per_layer: float,
                      experts_touched: float = None) -> float:
    """The bytes one decode step must move: every weight held once (the held
    experts that got a token: all of them, where ``experts_touched``, the
    count over all layers, is not given), every slot's state read and written,
    the live K/V pages of each attention layer, the logits written."""
    layers = len(c["layer_types"])
    touched = layers * c["num_local_experts"] if experts_touched is None else experts_touched
    weights = weight_bytes(c) - 2 * expert_params(c) * (layers * c["num_local_experts"] - touched)
    state = 2 * int(serve["slots"]) * state_bytes_per_slot(c, serve)
    hd = c["hidden_size"] // c["num_attention_heads"]
    kv = (kv_pages_read_per_layer * c["layer_types"].count("attention") * int(serve["page_size"])
          * 2 * c["num_key_value_heads"] * hd * 2)
    return weights + state + kv + int(serve["slots"]) * c["vocab_size"] * 4


def ssm_step_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """What one call of the ``ssm_step`` kernel (one state-space layer, every
    slot) must move: the layer's state read and written, the rows of decay and
    ``dt x`` read and of ``y`` written, the columns of ``B`` and ``C`` read."""
    d_inner, _conv_dim, _ = _dims(c)
    S, N = int(serve["slots"]), c["mamba_d_state"]
    return S * (2 * N * d_inner * jnp.dtype(serve["state_dtype"]).itemsize + 3 * d_inner * 4 + 2 * N * 4)


def decode_step_flops(c: Dict[str, Any], active_slots: float, held_assignments: float) -> float:
    """Operations of one decode step: 2 a multiplied parameter a token (the
    routed experts by the assignments that fell here, all layers together),
    and the recurrence's 6 a state element."""
    kinds = c["layer_types"]
    mixers = kinds.count("mamba") * mamba_params(c) + kinds.count("attention") * attention_params(c)
    dense = mixers + len(kinds) * shared_and_router_params(c) + c["vocab_size"] * c["hidden_size"]
    scan = kinds.count("mamba") * 6 * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
    return active_slots * (2.0 * dense + scan) + 2.0 * held_assignments * expert_params(c)


def prefill_bucket_flops(c: Dict[str, Any], bucket: int, real_tokens: int = None) -> float:
    """Operations of one prefill of a bucket: the projections over every
    position of the bucket, the routed experts over the real tokens' share
    held here (experts_per_tok x held / total), the chunked scan (inside a
    chunk 2 Q (N + H P) a position, the chunk states and their read-out
    4 H P N), causal attention at half the square, and one head row."""
    total, held, _first = _share(c)
    real = bucket if real_tokens is None else real_tokens
    kinds, Q = c["layer_types"], c["mamba_chunk_size"]
    d_inner, _conv_dim, _ = _dims(c)
    mixers = kinds.count("mamba") * mamba_params(c) + kinds.count("attention") * attention_params(c)
    dense = 2.0 * bucket * (mixers + len(kinds) * shared_and_router_params(c))
    routed = 2.0 * real * len(kinds) * c["num_experts_per_tok"] * held / total * expert_params(c)
    scan = kinds.count("mamba") * bucket * (2.0 * Q * (c["mamba_d_state"] + d_inner) + 4.0 * d_inner * c["mamba_d_state"])
    attn = kinds.count("attention") * 2.0 * bucket * bucket * c["hidden_size"]
    return dense + routed + scan + attn + 2.0 * c["vocab_size"] * c["hidden_size"]


def prefill_bucket_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> float:
    """What a prefill must read and write whatever its bucket: every weight
    once, and the slot's state and its logits row written."""
    return weight_bytes(c) + state_bytes_per_slot(c, serve) + c["vocab_size"] * 4


# ------------------------------------------ which mechanism a device op is of
# The chip's trace names a device event by its whole HLO instruction (output
# shapes, then every operand with its shape) and carries no scope (its events'
# stats are the device offsets alone: PERF.md, PR 29), and the weights reach most
# ops through prefetch copies, so their parameter names are gone too.  So the
# table is of shapes: an op belongs to the first mechanism one of whose sizes
# its text shows.  The sizes come from the configuration, nothing is fitted.
MECHANISMS = ("mamba", "moe", "attn", "head")


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any]) -> Dict[str, Sequence[str]]:
    """For each mechanism, the substrings (kernel names, or runs of dimensions
    as an HLO shape prints them) that only its ops show, for a decode step of
    ``serve['slots']`` slots."""
    S, E = int(serve["slots"]), c["hidden_size"]
    d_inner, conv_dim, in_proj = _dims(c)
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    total, held, _first = _share(c)
    F, Fs, k = c["intermediate_size"], c["shared_intermediate_size"], c["num_experts_per_tok"]
    hd = E // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    page = int(serve["page_size"])
    return {
        "mamba": ("ssm_step", f"{S},{N},{d_inner}]", f"[{S},{H},{P}]", f",{in_proj}]", f",{conv_dim}]",
                  f"[{d_inner},{E}]", f"[{S},{d_inner}]", f"[{S},1,{d_inner}]", f"[{S},{N},1]", f"[{S},{H}]"),
        "moe": ("ragged-dot", f"[{held},", f",{F}]", f",{Fs}]", f"[{Fs},{E}]", f"[{S},{k},{E}]", f"[{S},{k}]",
                f"[{S},{total}]", f"[{E},{total}]", f"[{S * k}", f"[{S},{held * F}]", f"[{held * F},{E}]",
                f"[{S},{held}]", f"[{S},{held + 1}]"),
        "attn": ("paged_decode", f",{page},{c['num_key_value_heads']},{hd}]", f"[{S},{c['num_attention_heads']},{hd}]",
                 f"[{E},{kv}]", f"[{E},{q}]", f"[{q},{E}]", f"[{S},{kv}]", f",{c['num_attention_heads']},{hd}]"),
        "head": (f"[{S},{c['vocab_size']}]", f"[{c['vocab_size']},{E}]"),
    }


def mechanism_of(op_text: str, signatures: Dict[str, Sequence[str]]) -> str:
    """``mamba`` / ``moe`` / ``attn`` / ``head``, or ``other`` (norms and sums
    of the residual stream, small copies) for a device event's name."""
    for mechanism in MECHANISMS:
        if any(s in op_text for s in signatures[mechanism]):
            return mechanism
    return "other"
