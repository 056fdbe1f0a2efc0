"""5D composition: PP x DP x EP + ZeRO + distributed checkpoint on the
virtual 8-device mesh (2x2x2) — a toy-scale "Llama-3-405B 5D + distributed
checkpoint" config."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import vescale_tpu as vt
import vescale_tpu.checkpoint as ckpt
from vescale_tpu.models.nanogpt import cross_entropy_loss
from vescale_tpu.moe.layer import MoEConfig, MoEMLP
from vescale_tpu.parallel.optimizer import zero_sharded
from vescale_tpu.pipe.spmd import pipeline_blocks, stack_stage_params

import flax.linen as nn


class MoEBlock(nn.Module):
    """Attention-free MoE block (keeps the 5D test fast): LN + routed MLP."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(name="ln")(x)
        y, _aux = MoEMLP(self.cfg, name="moe")(h)
        return x + y


@pytest.mark.slow
def test_5d_train_step_and_checkpoint(tmp_path):
    """pp=2 x dp=2 x ep=2 (+ tp axis present for attention-free tp=1 compat)
    on 8 devices; blocks pipelined via ppermute with EP expert sharding auto
    inside each stage; ZeRO-sharded optimizer; checkpoint save+reshard."""
    mesh = vt.DeviceMesh(("pp", "dp", "ep"), (2, 2, 2))
    cfg = MoEConfig(num_experts=4, d_model=32, d_ff=64, top_k=2, capacity_factor=4.0)
    blk = MoEBlock(cfg)
    B, T, E = 4, 8, 32
    vocab = 64

    emb = nn.Embed(vocab, E, name="emb")
    head = nn.Dense(vocab, use_bias=False, name="head")
    x0 = jnp.ones((B, T, E))
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    p_emb = emb.init(ks[0], jnp.ones((B, T), jnp.int32))["params"]
    p_head = head.init(ks[1], x0)["params"]
    stacked = stack_stage_params([blk.init(ks[2 + i], x0)["params"] for i in range(2)])

    def shard_leaf(path, leaf):
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        if any(s in name for s in ("w_in", "w_out", "b_in", "b_out")):
            # (pp, E_experts, ...) -> experts over ep
            return jax.device_put(leaf, NamedSharding(mesh.jax_mesh, P("pp", "ep")))
        return jax.device_put(leaf, NamedSharding(mesh.jax_mesh, P("pp")))

    stacked = jax.tree_util.tree_map_with_path(shard_leaf, stacked)
    params = {"emb": p_emb, "head": p_head, "blocks": stacked}
    pspecs = jax.tree_util.tree_map(
        lambda p: p.sharding.spec if isinstance(p.sharding, NamedSharding) else P(), params
    )
    tx = zero_sharded(optax.adamw(1e-3), mesh, pspecs, dp_dims=("dp",))
    opt_state = tx.init(params)

    def block_fn(p, xm):
        return blk.apply({"params": p}, xm)

    def loss_fn(params, batch):
        x = emb.apply({"params": params["emb"]}, batch["input"])
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh.jax_mesh, P("dp")))
        x = pipeline_blocks(block_fn, params["blocks"], x, mesh, num_microbatches=2)
        logits = head.apply({"params": params["head"]}, x)
        return cross_entropy_loss(logits, batch["target"])

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    toks = jax.random.randint(jax.random.key(9), (B, T + 1), 0, vocab)
    batch = {
        "input": jax.device_put(toks[:, :-1], NamedSharding(mesh.jax_mesh, P("dp"))),
        "target": jax.device_put(toks[:, 1:], NamedSharding(mesh.jax_mesh, P("dp"))),
    }
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]

    # distributed checkpoint of the 5D state + reshard to a 1-D mesh
    ckpt.save(str(tmp_path / "c5d"), {"model": params})
    flat_mesh = vt.DeviceMesh(("x",), (8,))
    tmpl = jax.tree_util.tree_map(
        lambda p: jax.device_put(jnp.zeros(p.shape, p.dtype), NamedSharding(flat_mesh.jax_mesh, P())),
        params,
    )
    loaded = ckpt.load(str(tmp_path / "c5d"), {"model": tmpl})
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(loaded["model"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
