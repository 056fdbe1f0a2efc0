"""Compile a cell's real program, at its real sizes, for a *described* v5e —
no chip attached, no chip time:

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name>

Prints the compiler's argument and temporary bytes per device, the count of
``tpu_custom_call`` (Pallas kernels) and the collective census.  What the
TPU's compiler refuses (a program that does not fit, a kernel it cannot
partition) it refuses here, before a chip call is spent.  Nothing runs: this
says nothing about results or times, and is never reported as a chip run.

The program builds its mesh from real devices and places its own arrays, so
this script hands it the described devices and shapes.  A train cell's step is
assembled here from the family's module and plan; what a serve cell compiles is
the family's own (``rehearse_serve`` of ``benchmark/families/<model>.py``: it
knows its engine's programs and its cache's arrays).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _report(name: str, compiled) -> dict:
    from vescale_tpu.debug.comm_mode import count_collectives

    text = compiled.as_text()
    mem = compiled.memory_analysis()
    row = {
        "program": name,
        "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collectives": {k: v for k, v in count_collectives(text).items() if v},
    }
    print(json.dumps(row), flush=True)
    if not row["tpu_custom_calls"]:
        print("[rehearse] no Pallas kernel in this program: code that asks jax.default_backend() sees the CPU here "
              "and takes its dense branch (flash on the chip), so temporaries are an upper estimate", flush=True)
    return row


def rehearse_train(spec, topo_devices) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from vescale_tpu.dmodule import parallelize_module
    from vescale_tpu.mesh import DeviceMesh
    from vescale_tpu.models.nanogpt import cross_entropy_loss
    from vescale_tpu.parallel.optimizer import adamw_lowmem, zero_sharded
    from vescale_tpu.train import make_train_step

    c, t, traffic = spec.config, spec.config["train"], spec.traffic
    dp, tp = int(t["mesh"]["dp"]), int(t["mesh"]["tp"])
    T, B = int(traffic["seq_len"]), int(traffic["global_batch"])
    mesh = DeviceMesh(("dp", "tp"), (dp, tp), devices=topo_devices[: dp * tp])
    system = spec.family().build_train(c, t, mesh, T)
    dm = parallelize_module(system.module, mesh, system.plan)
    abstract = jax.eval_shape(lambda r: system.module.init(r, jnp.ones((1, T), jnp.int32)), jax.random.key(0))
    shardings = dm.variables_shardings(abstract)
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), abstract, shardings)["params"]
    tx = adamw_lowmem(float(traffic["learning_rate"]))
    if t["zero"]:
        pspecs = jax.tree_util.tree_map(lambda p: p.sharding.spec, params)
        tx = zero_sharded(tx, mesh, pspecs, dp_dims=("dp",))
    replicated = NamedSharding(mesh.jax_mesh, P())
    init = jax.jit(tx.init).lower(params).compile()
    # what init computes from no argument (the step count) lands on the default device: replicate it
    on_mesh = lambda s: s if getattr(s, "mesh", None) == mesh.jax_mesh else replicated
    opt_state = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_mesh(s)),
        jax.eval_shape(tx.init, params), init.output_shardings)
    batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=replicated) for k in ("input", "target")}
    step = make_train_step(dm, tx, lambda lg, b: cross_entropy_loss(lg, b["target"]), donate=True, with_metrics=False)
    _report(f"{spec.name}: train step, mesh dp {dp} x tp {tp}, {B} x {T} tokens, depth {c['num_hidden_layers']}",
            step.lower(params, opt_state, batch).compile())
    # the forward that train_cell compares with the reference's logits, after the window
    forward = jax.jit(lambda p, x: dm.apply({"params": p}, x, deterministic=True, rngs=None)[0, jnp.arange(8)])
    _report(f"{spec.name}: forward for the logits check", forward.lower(params, batch["input"]).compile())


def rehearse_serve(spec, topo_devices) -> None:
    sizes, programs = spec.family().rehearse_serve(spec.name, spec.config, spec.config["serve"], topo_devices)
    print(json.dumps({"cell": spec.name, **sizes}), flush=True)
    for title, lowered in programs:
        _report(title, lowered.compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmark.spec import load_cell

    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_enable_compilation_cache", False)   # a described compile cannot be read back
    spec = load_cell(args.workload, ROOT)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    print(f"[rehearse] a compile for a described v5e:2x2, not a chip run; cell {spec.name}, {spec.chips} chip(s)",
          flush=True)
    {"train": rehearse_train, "serve": rehearse_serve}[spec.kind](spec, list(topo.devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
