"""Model-family tests (mirrors reference legacy/test/model/{open_llama,
mixtral}: per-layer + whole-model parity vs golden single-device run)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import vescale_tpu as vt
from vescale_tpu.dmodule import parallelize_module
from vescale_tpu.models.llama import Llama, LlamaConfig, llama_plan
from vescale_tpu.models.mixtral import Mixtral, MixtralConfig, mixtral_plan
from vescale_tpu.models.nanogpt import cross_entropy_loss

TINY_LLAMA = LlamaConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,  # GQA
    max_position_embeddings=64,
    dtype=jnp.float32,
)

TINY_MIXTRAL = MixtralConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    num_local_experts=4,
    num_experts_per_tok=2,
    capacity_factor=4.0,
    dtype=jnp.float32,
)


def test_llama_forward_shapes_and_gqa():
    model = Llama(TINY_LLAMA)
    idx = jnp.ones((2, 16), jnp.int32)
    variables = model.init(jax.random.key(0), idx)
    out = model.apply(variables, idx)
    assert out.shape == (2, 16, 128)
    # GQA: k_proj output dim = kv_heads * head_dim = 2*8
    k = variables["params"]["layers_0"]["self_attn"]["k_proj"]["kernel"]
    assert k.shape == (32, 16)


def test_llama_tp_sp_matches_single(mesh2d):
    model = Llama(TINY_LLAMA)
    dm = parallelize_module(model, mesh2d, llama_plan(mesh2d))
    idx = jax.random.randint(jax.random.key(1), (4, 16), 0, 128)
    variables = dm.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))
    q = variables["params"]["layers_0"]["self_attn"]["q_proj"]["kernel"]
    assert "tp" in str(q.sharding.spec)
    out = dm.apply(variables, idx)
    golden = model.apply(variables, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_llama_trains(mesh2d):
    import optax
    from vescale_tpu.train import make_train_step

    model = Llama(TINY_LLAMA)
    dm = parallelize_module(model, mesh2d, llama_plan(mesh2d))
    variables = dm.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))
    params = variables["params"]
    tx = optax.adamw(1e-3)
    opt = tx.init(params)
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=False)
    toks = jax.random.randint(jax.random.key(10), (4, 17), 0, 128)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    losses = []
    for i in range(4):
        params, opt, l = step(params, opt, batch)
        losses.append(float(l))
    assert losses[-1] < losses[0]  # overfits one batch


@pytest.mark.slow
def test_mixtral_ep_matches_single():
    mesh = vt.DeviceMesh(("dp", "ep"), (2, 4))
    model = Mixtral(TINY_MIXTRAL)
    dm = parallelize_module(model, mesh, mixtral_plan(mesh))
    idx = jax.random.randint(jax.random.key(1), (4, 16), 0, 128)
    variables = dm.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))
    w = variables["params"]["layers_0"]["block_sparse_moe"]["w_in"]
    assert "ep" in str(w.sharding.spec)
    out = dm.apply(variables, idx, mutable=["losses"])[0]
    golden = model.apply(variables, idx, mutable=["losses"])[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=3e-5, atol=3e-5)


@pytest.mark.slow
def test_mixtral_trains_with_aux_loss():
    import optax

    mesh = vt.DeviceMesh(("dp", "ep"), (2, 4))
    model = Mixtral(TINY_MIXTRAL)
    dm = parallelize_module(model, mesh, mixtral_plan(mesh))
    variables = dm.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))
    params = variables["params"]
    tx = optax.adamw(1e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt_state, batch):
        def lf(p):
            logits, aux_vars = dm.apply({"params": p}, batch["input"], mutable=["losses"])
            aux = sum(jax.tree_util.tree_leaves(aux_vars["losses"]))
            return cross_entropy_loss(logits, batch["target"]) + aux

        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax as o

        return o.apply_updates(params, updates), opt_state, loss

    toks = jax.random.randint(jax.random.key(20), (4, 17), 0, 128)
    batch = {"input": toks[:, :-1], "target": toks[:, 1:]}
    losses = []
    for i in range(4):
        params, opt, l = step(params, opt, batch)
        losses.append(float(l))
    assert losses[-1] < losses[0]  # overfits one batch


def test_llama_scan_layers_matches_loop():
    """scan_layers=True computes the same function: stack the loop model's
    per-layer params into the scanned layout and compare logits."""
    import dataclasses

    loop_cfg = TINY_LLAMA
    scan_cfg = dataclasses.replace(TINY_LLAMA, scan_layers=True)
    idx = jnp.ones((2, 16), jnp.int32)
    loop_params = Llama(loop_cfg).init(jax.random.key(0), idx)["params"]

    per_layer = [loop_params[f"layers_{i}"] for i in range(loop_cfg.num_hidden_layers)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *per_layer)
    scan_params = {
        k: v for k, v in loop_params.items() if not k.startswith("layers_")
    }
    scan_params["layers"] = {"block": stacked}

    out_loop = Llama(loop_cfg).apply({"params": loop_params}, idx)
    out_scan = Llama(scan_cfg).apply({"params": scan_params}, idx)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_loop), rtol=1e-5, atol=1e-5)

    # remat composes with scan
    remat_cfg = dataclasses.replace(scan_cfg, remat=True)
    out_remat = Llama(remat_cfg).apply({"params": scan_params}, idx)
    np.testing.assert_allclose(np.asarray(out_remat), np.asarray(out_scan), rtol=1e-6)


@pytest.mark.slow
def test_llama_scan_remat_mlp_grad_parity():
    """The long-context config (scan_layers + remat_scope='mlp') must have
    the same LOSS AND GRADIENTS as the plain loop model."""
    import dataclasses

    from vescale_tpu.models.nanogpt import cross_entropy_loss

    loop_cfg = TINY_LLAMA
    bench_cfg = dataclasses.replace(
        TINY_LLAMA, scan_layers=True, remat=True, remat_scope="mlp"
    )
    toks = jax.random.randint(jax.random.key(3), (2, 17), 0, TINY_LLAMA.vocab_size)
    idx, tgt = toks[:, :-1], toks[:, 1:]
    loop_params = Llama(loop_cfg).init(jax.random.key(0), idx)["params"]
    per_layer = [loop_params[f"layers_{i}"] for i in range(loop_cfg.num_hidden_layers)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *per_layer)
    scan_params = {k: v for k, v in loop_params.items() if not k.startswith("layers_")}
    scan_params["layers"] = {"block": stacked}

    def loss_of(cfg, params):
        return lambda p: cross_entropy_loss(Llama(cfg).apply({"params": p}, idx), tgt)

    l_loop, g_loop = jax.value_and_grad(loss_of(loop_cfg, loop_params))(loop_params)
    l_scan, g_scan = jax.value_and_grad(loss_of(bench_cfg, scan_params))(scan_params)
    np.testing.assert_allclose(float(l_scan), float(l_loop), rtol=1e-6)
    # re-stack the loop grads into the scanned layout and compare leaf-wise
    g_stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *[g_loop[f"layers_{i}"] for i in range(loop_cfg.num_hidden_layers)]
    )
    for (kp, a), (_kp, b) in zip(
        jax.tree_util.tree_flatten_with_path(g_scan["layers"]["block"])[0],
        jax.tree_util.tree_flatten_with_path(g_stacked)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6, err_msg=str(kp)
        )
    for k in g_scan:
        if k == "layers":
            continue
        for (kp, a), (_kp, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_scan[k])[0],
            jax.tree_util.tree_flatten_with_path(g_loop[k])[0],
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6, err_msg=f"{k}:{kp}"
            )


@pytest.mark.slow
def test_llama_scanned_plan_shards_stack(mesh2d):
    """llama_plan(scanned=True) shifts block tp-shards past the (L,) stack
    axis; parallelize_module on the scanned model lands tp on the right dim."""
    import dataclasses
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(TINY_LLAMA, scan_layers=True)
    dm = parallelize_module(
        Llama(cfg), mesh2d, llama_plan(mesh2d, sequence_parallel=False, scanned=True)
    )
    params = dm.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))["params"]
    blk = params["layers"]["block"]
    L = cfg.num_hidden_layers
    def norm(spec, ndim):
        return tuple(spec) + (None,) * (ndim - len(tuple(spec)))

    q = blk["self_attn"]["q_proj"]["kernel"]
    assert q.shape[0] == L
    assert norm(q.sharding.spec, 3) == (None, None, "tp")  # col: stacked (L, in, out/tp)
    o = blk["self_attn"]["o_proj"]["kernel"]
    assert norm(o.sharding.spec, 3) == (None, "tp", None)  # row: (L, in/tp, out)
    emb = params["embed_tokens"]["embedding"]
    assert norm(emb.sharding.spec, 2) == (None, "tp")      # unstacked keeps dims
    # scanned model trains under the plan
    toks = jnp.ones((4, 17), jnp.int32)
    out = dm.apply({"params": params}, toks[:, :-1])
    assert out.shape == (4, 16, cfg.vocab_size)


def test_llama_remat_policy_without_remat_raises():
    import dataclasses

    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(TINY_LLAMA, remat_policy="dots_saveable")


@pytest.mark.slow
def test_llama_remat_scope_mlp_matches():
    """remat_scope='mlp' (attention residuals live, MLP rematerialized) is a
    pure scheduling choice: loss and grads bitwise-match remat_scope='block'
    and no-remat, and param FQNs are unchanged."""
    import dataclasses

    from vescale_tpu.models.llama import Llama
    from vescale_tpu.models.nanogpt import cross_entropy_loss

    base = dataclasses.replace(TINY_LLAMA, dtype=jnp.float32)
    idx = jax.random.randint(jax.random.key(0), (2, 17), 0, base.vocab_size)
    batch = {"input": idx[:, :-1], "target": idx[:, 1:]}
    params = Llama(base).init(jax.random.key(1), batch["input"])["params"]

    def loss_grads(cfg):
        def f(p):
            return cross_entropy_loss(
                Llama(cfg).apply({"params": p}, batch["input"]), batch["target"]
            )
        return jax.value_and_grad(f)(params)

    l0, g0 = loss_grads(base)
    for cfg in (
        dataclasses.replace(base, remat=True, remat_scope="block"),
        dataclasses.replace(base, remat=True, remat_scope="mlp"),
    ):
        # same tree structure (FQNs unchanged by the remat wrapper)
        l1, g1 = loss_grads(cfg)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        assert jax.tree_util.tree_structure(g1) == jax.tree_util.tree_structure(g0)
        for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g0), strict=True
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="remat_scope"):
        dataclasses.replace(base, remat=True, remat_scope="attention")
    with pytest.raises(ValueError, match="remat_scope"):
        dataclasses.replace(base, remat_scope="mlp")  # remat=False: silent no-op guarded
    with pytest.raises(ValueError, match="block"):
        dataclasses.replace(base, remat=True, remat_scope="mlp", remat_policy="dots_saveable")
