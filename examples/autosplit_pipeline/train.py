"""Auto-split pipeline training: arbitrary model -> balanced stages.

The reference needs an fx tracer to stage models that are not block lists
(legacy/vescale/pipe/pipe_parser.py).  Here the model function is traced to
a jaxpr and cut by FLOP cost (`vescale_tpu.pipe.split_graph`); the eager
PipeEngine then runs any schedule (1F1B below; pass --zero-bubble for the
dgrad/wgrad-split zero-bubble schedule).

Run (CPU is fine):
    python examples/autosplit_pipeline/train.py [--stages 4] [--zero-bubble]
"""

import argparse
import sys

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
import optax

sys.path.insert(0, ".")

from vescale_tpu.pipe import PipeEngine, split_graph
from vescale_tpu.plan import PipelineParallelPlan, PipelineScheduleType


class TangledLM(nn.Module):
    """Tied embedding + long skip: not stageable as a plain block list."""

    vocab: int = 512
    width: int = 128
    depth: int = 6

    @nn.compact
    def __call__(self, idx):
        emb = nn.Embed(self.vocab, self.width, name="emb")
        x = emb(idx)
        skip = x
        for i in range(self.depth):
            h = nn.Dense(self.width * 4, name=f"up{i}")(nn.LayerNorm(name=f"ln{i}")(x))
            x = x + nn.Dense(self.width, name=f"down{i}")(nn.gelu(h))
        return emb.attend(nn.LayerNorm(name="lnf")(x + skip))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--zero-bubble", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    from vescale_tpu.compile_cache import use_compile_cache

    use_compile_cache()

    model = TangledLM()
    B, T = 8, 32
    micro = jnp.ones((B // args.microbatches, T), jnp.int32)
    params = model.init(jax.random.key(0), micro)["params"]

    def fn(p, x):
        return model.apply({"params": p}, x)

    plan = PipelineParallelPlan(
        num_stages=args.stages,
        schedule_type=PipelineScheduleType.SIMPLE_1F1B,
        use_zero_bubble=args.zero_bubble,
    )
    gm = split_graph(fn, params, micro, plan)  # trace at MICROBATCH shape
    print(f"{gm.num_groups} groups; tied groups: {list(gm.shared_groups)}")
    for g in range(gm.num_groups):
        print(f"  stage {g}: {len(gm.group_param_names(g))} param leaves")

    def loss_fn(logits, tgt):
        oh = jax.nn.one_hot(tgt, logits.shape[-1])
        return -jnp.mean(jnp.sum(oh * jax.nn.log_softmax(logits), axis=-1))

    engine = PipeEngine(gm, plan, loss_fn)
    tx = optax.adamw(3e-3)
    full = params
    opt = tx.init(full)
    rng = np.random.default_rng(0)
    for step in range(args.steps):
        toks = jnp.asarray(rng.integers(0, model.vocab, (B, T + 1)), jnp.int32)
        loss, grads_pg = engine.forward_backward(
            gm.partition_params(full),
            {"input": toks[:, :-1], "target": toks[:, 1:]},
            num_microbatches=args.microbatches,
        )
        grads = gm.merge_params([dict(g) for g in grads_pg])
        updates, opt = tx.update(grads, opt, full)
        full = optax.apply_updates(full, updates)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:3d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
