"""The Falcon-H1 family (``"model": "falcon_h1"``, HF ``model_type``
``falcon_h1``): in EVERY layer a Mamba-2 state-space mixer (two groups of B and
C at 34B, a state of 256) and a rotary grouped-query attention mixer side by
side on the same normed input, a dense SwiGLU, twelve fixed multipliers, an
untied head; ``vescale_tpu/models/falcon_h1.py`` and
``vescale_tpu/serve/hybrid_engine.py`` in the program.  A family that only
serves.  The names are those ``benchmark/README.md`` ("Adding a family") fixes.

What a reader of this family needs beyond the README:

- **The share.**  ``"share": {"chips": 2, "of": ["vocab_size"]}``: the rows of
  the embedding and of the head are divided over 2 chips and this one holds the
  first ``vocab_size`` of them; the traffic and the check draw their ids from
  those rows, and the logits are over them.  The layers are whole here.
- **The cache.**  Every layer has pages AND slot state: ``cache.k`` / ``cache.v``
  of ``num_hidden_layers`` layers, ``cache.state["ssm"]`` (layers, slots, 256,
  4096) float32 and ``cache.state["conv"]`` (layers, slots, 3, 5120) bfloat16.
- **The buckets.**  The engine pads a prompt to the next of ``chunk, 2 chunk,
  4 chunk, ..., positions_per_slot`` (128, 256, 512, 1024, 1536) and compiles
  one prefill program a bucket (``warm()``).  The runner's check prompt (320
  tokens) falls into the 512 bucket and ends inside a chunk (320 = 2.5 x 128),
  so the pad rule is under the check.
- **The counters** (``HybridServeEngine.trace_counters``): the engine's
  (``decode_pages_read`` / ``decode_pages_capacity`` are ONE layer's: times
  ``num_hidden_layers`` here; ``moe_*`` stay 0: nothing is routed), and the
  model's own ``ssm_state_bytes_rw`` and ``prefill_scan_chunks``.
  ``layer_metrics/falconh1_serve_batch.py`` reads them with the counts at the
  end of this file.

The reference is straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: the recurrence one position at a time (``lax.scan``), dense causal
attention; no kernels, cache, chunks or batching, and nothing imported from the
program.  It follows HF ``modeling_falcon_h1.py`` (``FalconH1Mixer``:
``mup_vector`` over the in-projection's five segments, the gate before the norm
and the norm per group; ``FalconH1Attention``: the keys times
``key_multiplier`` before the rotary term; ``FalconH1MLP``: the gate's and the
down projection's multipliers).  Departures from the source: the logits are
over the held rows of the head (the share); nothing else.  The program's tree
is read a layer at a time and cast inside each jitted call: a float32 copy of
the weights never exists.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence
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
# reads the same bf16 weights.  A dense model: no router whose top-k could
# flip, so the error is the sum of the rounded operands' through six layers of
# three branches, each a quarter of the stream (``BRANCH_GAIN`` in the model's
# file), and the head's.  Readings on the chip (PERF.md, section 6, PR 43):
# this PR's change over fourteen seeds of the cell and five prompts of a scratch
# check (100 to 1,500 tokens, one with twelve decode steps) 2.5e-3 to 3.0e-3;
# the reference with its weights in fp8 (e4m3), the nearest type below the
# one the configuration states, 5.1e-2 to 5.7e-2, which this limit fails (the
# limit is 3.3 times the largest reading of the one and a fifth of the smallest
# of the other: 1.2e-2 is their geometric middle).  Each fault of
# tests/test_falcon_h1.py that can be made on the chip reads far above it
# there: the attention branch dropped 6.3e-2 to 9.3e-2, the gated norm over
# all of d_ssm 6.6e-2 to 7.4e-2, group 0's B and C for all heads 1.1e-1 to
# 1.3e-1, a multiplier of the in-projection left out 1.3e-1 to 4.3e-1, the
# keys' 4.9e-1, the state-space branch dropped 5.1e-1 to 5.6e-1, the MLP
# gate's 9.3e-1 to 9.6e-1; a state kept in bf16 cannot be told from float32
# by four decode steps (its rounding has nothing to accumulate over; the CPU
# test, float32 against float32 over sixteen steps, tells it).
SERVE_LOGITS_TOLERANCE = 1e-2

SHARED_KEYS = ("vocab_size",)
# the published keys that the program's config takes under their own names
_WHOLE = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
          "intermediate_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
          "mamba_chunk_size")
_REAL = ("rope_theta", "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
         "attention_in_multiplier", "attention_out_multiplier", "key_multiplier", "rms_norm_eps")
# what this family's block is, beyond its numbers: a file that says otherwise is another architecture
FIXED = {"mamba_rms_norm": True, "mamba_norm_before_gate": False, "mamba_conv_bias": True, "mamba_proj_bias": False,
         "attention_bias": False, "mlp_bias": False, "projectors_bias": False, "tie_word_embeddings": False,
         "rope_scaling": None, "attn_layer_indices": None, "hidden_act": "silu"}


# --------------------------------------------------------------- the program
def _check_share(config: Dict[str, Any]) -> None:
    share, published = config.get("share") or {}, config.get("published", {})
    for key in SHARED_KEYS:
        if key in config.get("reduced", ()) and key not in share.get("of", ()):
            raise SpecError(f"{key} is cut from {published.get(key)} to {config[key]}: the file must state the share "
                            "it is (share.of), a smaller model is not this family's")
    if set(share.get("of", ())) - set(SHARED_KEYS):
        raise SpecError(f"this family divides {SHARED_KEYS} over chips, not {share['of']}")


def program_config(config: Dict[str, Any], *, max_positions: int = 0, state_dtype: str = "float32"):
    """The program's ``FalconH1Config`` from a configuration file's object; the
    published keys go through unchanged.  ``max_positions`` sizes nothing (the
    rotary term is computed from the positions)."""
    from vescale_tpu.models.falcon_h1 import FalconH1Config

    for key, value in FIXED.items():
        if config.get(key) != value:
            raise SpecError(f"this family's block has {key} = {value!r}; the file says {config.get(key)!r}")
    if config["mamba_d_ssm"] != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise SpecError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    _check_share(config)
    return FalconH1Config(
        **{key: int(config[key]) for key in _WHOLE}, **{key: float(config[key]) for key in _REAL},
        ssm_multipliers=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in config["mlp_multipliers"]),
        dtype=jnp.bfloat16, state_dtype=jnp.dtype(state_dtype))


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
        raise RuntimeError(f"this checkout's program cannot run the falcon_h1 family: {e}") from e


def build_serve(config: Dict[str, Any], serve: Dict[str, Any], devices, seed: int) -> ServeSystem:
    """Weights made on the device in one jitted call from the seed, in the
    types they are served in; a paged cache with pages and slot state in every
    layer; ``HybridServeEngine`` with every bucket compiled."""
    cfg = _serve_config(config, serve)
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.falcon_h1 import init_params
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
    from vescale_tpu.models.falcon_h1 import init_params
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


@functools.partial(jax.jit, static_argnames=("heads", "head_width", "state", "groups", "multipliers", "eps", "norm_groups"))
def mamba_mixer(mp: Dict[str, Any], u, *, heads: int, head_width: int, state: int, groups: int,
                multipliers: Sequence[float], eps: float, norm_groups: Optional[int] = None):
    """Mamba-2 with ``groups`` groups of B and C over one sequence ``u`` (T, E),
    float32, a position at a time.  ``multipliers`` are the source's
    ``ssm_multipliers`` (z, x, B, C, dt).  ``norm_groups`` (tests: what a norm
    over all of ``d_ssm`` reads) is the groups the gated norm is taken over,
    ``groups`` where left out."""
    with jax.default_matmul_precision("highest"):
        T, d, GN = u.shape[0], heads * head_width, groups * state
        p = u @ f(mp["in_proj"])
        edges = np.cumsum([0, d, d, GN, GN, heads])
        z, x, B, C, dt = (m * p[:, a:b] for m, a, b in zip(multipliers, edges[:-1], edges[1:]))
        w = f(mp["conv_weight"])                                       # (K, conv_dim): w[K-1] meets the newest input
        K = w.shape[0]
        xBC = jnp.concatenate([x, B, C], axis=1)
        padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
        xBC = jax.nn.silu(f(mp["conv_bias"]) + sum(w[k] * padded[k: k + T] for k in range(K)))
        x = xBC[:, :d].reshape(T, heads, head_width)
        # head h reads group h // (heads / groups)
        per = heads // groups
        B = jnp.repeat(xBC[:, d: d + GN].reshape(T, groups, state), per, axis=1)        # (T, heads, state)
        C = jnp.repeat(xBC[:, d + GN:].reshape(T, groups, state), per, axis=1)
        dt = jax.nn.softplus(dt + f(mp["dt_bias"]))
        A, D = -jnp.exp(f(mp["A_log"])), f(mp["D"])

        def position(h, inp):
            x_t, B_t, C_t, dt_t = inp
            h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, C_t) + D[:, None] * x_t

        _, y = jax.lax.scan(position, jnp.zeros((heads, head_width, state), F32), (x, B, C, dt))
        # the gate first, then each group of d / groups channels normed by its own mean square
        n = groups if norm_groups is None else norm_groups
        gated = (y.reshape(T, d) * jax.nn.silu(z)).reshape(T, n, d // n)
        y = _rmsnorm(gated, mp["norm_weight"].reshape(n, d // n), eps).reshape(T, d)
        return y @ f(mp["out_proj"])


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "key_multiplier"))
def attention_mixer(ap: Dict[str, Any], u, *, heads: int, kv_heads: int, head_dim: int, theta: float,
                    key_multiplier: float):
    """Causal softmax attention over one sequence from position 0: the keys
    times ``key_multiplier``, rotary over the whole head on q and k, scores over
    ``sqrt(head_dim)``, query head ``h`` on key head ``h // (heads / kv_heads)``."""
    with jax.default_matmul_precision("highest"):
        T = u.shape[0]
        q = (u @ f(ap["q_proj"])).reshape(T, heads, head_dim)
        k = key_multiplier * (u @ f(ap["k_proj"])).reshape(T, kv_heads, head_dim)
        v = (u @ f(ap["v_proj"])).reshape(T, kv_heads, head_dim)
        inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim)
        angle = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
        cos, sin = (jnp.concatenate([t, t], axis=-1)[:, None, :] for t in (jnp.cos(angle), jnp.sin(angle)))
        q, k = q * cos + _rotate_half(q) * sin, k * cos + _rotate_half(k) * sin
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(head_dim)
        p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, -1) @ f(ap["o_proj"])


@functools.partial(jax.jit, static_argnames=("gate_multiplier", "down_multiplier"))
def mlp(fp: Dict[str, Any], h, *, gate_multiplier: float, down_multiplier: float):
    with jax.default_matmul_precision("highest"):
        return down_multiplier * (((h @ f(fp["up_proj"])) * jax.nn.silu(gate_multiplier * (h @ f(fp["gate_proj"]))))
                                  @ f(fp["down_proj"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(w, x, *, eps: float):
    return _rmsnorm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, kernel, x, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_w, eps) @ f(kernel)


def hidden_states(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], *,
                  norm_groups: Optional[int] = None):
    """The residual stream after the last layer, (T, E) float32."""
    c, eps = config, float(config["rms_norm_eps"])
    x = float(c["embedding_multiplier"]) * f(
        jnp.take(params["embed_tokens"]["embedding"], jnp.asarray(np.asarray(tokens, np.int32)), axis=0))
    for l in range(c["num_hidden_layers"]):
        lp = params[f"layers_{l}"]
        u = _norm(lp["input_layernorm"]["weight"], x, eps=eps)
        ym = mamba_mixer(lp["mamba"], float(c["ssm_in_multiplier"]) * u, heads=c["mamba_n_heads"],
                         head_width=c["mamba_d_head"], state=c["mamba_d_state"], groups=c["mamba_n_groups"],
                         multipliers=tuple(float(m) for m in c["ssm_multipliers"]), eps=eps, norm_groups=norm_groups)
        ya = attention_mixer(lp["self_attn"], float(c["attention_in_multiplier"]) * u, heads=c["num_attention_heads"],
                             kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"], theta=float(c["rope_theta"]),
                             key_multiplier=float(c["key_multiplier"]))
        x = x + float(c["ssm_out_multiplier"]) * ym + float(c["attention_out_multiplier"]) * ya
        h = _norm(lp["pre_ff_layernorm"]["weight"], x, eps=eps)
        x = x + mlp(lp["feed_forward"], h, gate_multiplier=float(c["mlp_multipliers"][0]),
                    down_multiplier=float(c["mlp_multipliers"][1]))
    return x


def logits(params: Dict[str, Any], config: Dict[str, Any], tokens: Sequence[int], rows: Sequence[int], **kw):
    """Next-token logits (float32) over the held rows of the head, at the positions ``rows``."""
    x = hidden_states(params, config, tokens, **kw)[jnp.asarray(np.asarray(rows, np.int32))]
    return float(config["lm_head_multiplier"]) * _head(params["final_layernorm"]["weight"], params["lm_head"]["kernel"],
                                                       x, eps=float(config["rms_norm_eps"]))


loss_and_logits = functools.partial(reference.loss_and_logits, logits)


# -------------------------------------------- operations and bytes from shapes
# The benchmark's own arithmetic (parameters that a token multiplies; norm
# weights, biases, A, D and dt_bias are counted where bytes are), so that no
# later PR moves a roofline share by recounting.
def _dims(c: Dict[str, Any]):
    d = c["mamba_d_ssm"]
    conv_dim = d + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return d, conv_dim, d + conv_dim + c["mamba_n_heads"]


def mamba_params(c: Dict[str, Any]) -> int:
    d, conv_dim, in_proj = _dims(c)
    return c["hidden_size"] * in_proj + d * c["hidden_size"] + (c["mamba_d_conv"] + 1) * conv_dim + d + 3 * c["mamba_n_heads"]


def attention_params(c: Dict[str, Any]) -> int:
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    return c["hidden_size"] * (q + 2 * kv) + q * c["hidden_size"]


def mlp_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_params(c: Dict[str, Any]) -> int:
    return mamba_params(c) + attention_params(c) + mlp_params(c) + 2 * c["hidden_size"]


def param_count(c: Dict[str, Any]) -> int:
    """Parameters this chip holds: the layers whole, its rows of the embedding and of the untied head."""
    return c["num_hidden_layers"] * layer_params(c) + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]


def weight_bytes(c: Dict[str, Any]) -> int:
    """The tree's bytes: bf16 but A_log, D and dt_bias (float32)."""
    return 2 * param_count(c) + 2 * c["num_hidden_layers"] * 3 * c["mamba_n_heads"]


def state_bytes_per_slot(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """A slot's recurrent state and convolution tail, all layers."""
    d, conv_dim, _ = _dims(c)
    ssm = d * c["mamba_d_state"] * jnp.dtype(serve["state_dtype"]).itemsize
    return c["num_hidden_layers"] * (ssm + (c["mamba_d_conv"] - 1) * conv_dim * 2)


def kv_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one position, all layers."""
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * itemsize


def decode_step_bytes(c: Dict[str, Any], serve: Dict[str, Any], *, kv_pages_read_per_layer: float) -> float:
    """The bytes one decode step must move: every weight held once but the
    embedding (a row a slot is gathered), every slot's state read and written,
    the live K/V pages of every layer, the logits written."""
    S = int(serve["slots"])
    weights = weight_bytes(c) - 2 * (c["vocab_size"] - S) * c["hidden_size"]
    state = 2 * S * state_bytes_per_slot(c, serve)
    kv = kv_pages_read_per_layer * int(serve["page_size"]) * kv_bytes_per_position(c)
    return weights + state + kv + S * c["vocab_size"] * 4


def ssm_step_bytes(c: Dict[str, Any], serve: Dict[str, Any]) -> int:
    """What one call of the ``ssm_step`` kernel (one layer, every slot) must
    move: the layer's state read and written, the rows of decay and ``dt x``
    read and of ``y`` written, the columns of ``B`` and ``C`` of every group read."""
    d, _conv_dim, _ = _dims(c)
    S, N, G = int(serve["slots"]), c["mamba_d_state"], c["mamba_n_groups"]
    return S * (2 * N * d * jnp.dtype(serve["state_dtype"]).itemsize + 3 * d * 4 + 2 * G * N * 4)


def decode_step_flops(c: Dict[str, Any], active_slots: float) -> float:
    """Operations of one decode step: 2 a multiplied parameter a token (the
    head's, not the embedding's) and the recurrence's 6 a state element."""
    dense = c["num_hidden_layers"] * layer_params(c) + c["vocab_size"] * c["hidden_size"]
    return active_slots * (2.0 * dense + c["num_hidden_layers"] * 6 * c["mamba_d_ssm"] * c["mamba_d_state"])


def prefill_bucket_flops(c: Dict[str, Any], bucket: int) -> float:
    """Operations of one prefill of a bucket: the projections and the MLP over
    every position of it, the chunked scan (inside a chunk 2 Q (G N + H P) a
    position, the chunk states and their read-out 4 H P N), causal attention at
    half the square, and one head row."""
    d, _conv_dim, _ = _dims(c)
    Q, L = c["mamba_chunk_size"], c["num_hidden_layers"]
    scan = L * bucket * (2.0 * Q * (c["mamba_n_groups"] * c["mamba_d_state"] + d) + 4.0 * d * c["mamba_d_state"])
    attn = L * 2.0 * bucket * bucket * c["num_attention_heads"] * c["head_dim"]
    return 2.0 * bucket * L * layer_params(c) + scan + attn + 2.0 * c["vocab_size"] * c["hidden_size"]


# ------------------------------------------ which mechanism a device op is of
# The chip's trace names a device event by its whole HLO instruction (output
# shapes, then every operand with its shape) and carries no scope, and the
# weights reach most ops through prefetch copies, so their parameter names are
# gone too (``families/granite_hybrid.py`` says more).  So the table is of
# shapes: an op belongs to the first mechanism one of whose sizes its text
# shows.  The sizes come from the configuration, nothing is fitted.  At 34B the
# convolution's width (4096 + 2 x 2 x 256) IS the hidden size, 5120: a bare
# ``[rows,5120]`` names nothing, so the convolution is known by its window
# (``,d_conv,5120]`` / ``,d_conv - 1,5120]``) and what is left of that width
# (norms and sums of the residual stream, and the convolution's activation,
# 2.6 MB a layer) goes to ``other``.  The compiler reads the in-projection in
# halves or quarters of its columns (``slice-start`` / ``slice-done`` of
# ``[5120,2312]``): those widths are the in-projection's too.
MECHANISMS = ("mamba", "attention", "mlp", "head")


def mechanism_signatures(c: Dict[str, Any], serve: Dict[str, Any], rows: Optional[int] = None) -> Dict[str, Sequence[str]]:
    """For each mechanism, the substrings (kernel names, or runs of dimensions
    as an HLO shape prints them) that only its ops show, for a program over
    ``rows`` rows of the stream: a decode step's ``serve['slots']`` where left
    out, a prefill's rung."""
    S, E, F = int(serve["slots"]), c["hidden_size"], c["intermediate_size"]
    R = S if rows is None else int(rows)
    d, conv_dim, in_proj = _dims(c)
    H, P, N, G, K = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"]
    A, KV, hd, page = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], int(serve["page_size"])
    q, kv, Q = A * hd, KV * hd, c["mamba_chunk_size"]
    # a decode step's XLA leg of paged attention (VESCALE_KERNELS=off; the CPU): a slot's pages gathered and scored whole
    T = int(serve["positions_per_slot"])
    xla_leg = (f"[{R},{T},{KV},{hd}]", f",{A // KV},{T}]", f",1,{T}]", f"[{R},{KV},{A // KV},{hd}]", f"[{R},{T // page}]",
               f"[{R},{T // page},{page},{KV},{hd}]") if rows is None else ()
    return {
        "mamba": ("ssm_step", f",{N},{d}]", f"[{R},{H},{P}]", f",{in_proj}]", f"[{E},{in_proj // 2}]",
                  f"[{E},{in_proj // 4}]", f",{K},{conv_dim}]", f",{K - 1},{conv_dim}]", f"[{d},{E}]", f"[{R},{d}]",
                  f"[{R},1,{d}]", f",{N},1]", f"[{R},{H}]", f"[{R},{G},{N}]", f"[{R},{G * N}]", f"[{R},{G},{d // G}]",
                  f",{H},{P},{N}]", f",{H // G},{P},{N}]", f",{H // G},{P}]", f",{Q},{Q},{H // G}]", f",{Q},{Q}]"),
        "attention": ("paged_decode", "flash", f",{page},{KV},{hd}]", f"[{R},{A},{hd}]", f"[{R},{KV},{hd}]", f"[{E},{q}]",
                      f"[{E},{kv}]", f"[{q},{E}]", f"[{R},{q}]", f"[{R},{kv}]", f",{A},{hd // 2}]", f",{KV},{hd // 2}]",
                      f"[{A},{R},{hd}]", f"[{KV},{R},{hd}]", f",{hd // 2}]") + xla_leg,
        "mlp": (f",{F}]", f"[{F},{E}]"),
        "head": (f",{c['vocab_size']}]", f"[{c['vocab_size']},{E}]"),
    }


def mechanism_of(op_text: str, signatures: Dict[str, Sequence[str]]) -> str:
    """``mamba`` / ``attention`` / ``mlp`` / ``head``, or ``other`` (norms and
    sums of the residual stream, small copies) for a device event's name."""
    for mechanism in MECHANISMS:
        if any(s in op_text for s in signatures[mechanism]):
            return mechanism
    return "other"
