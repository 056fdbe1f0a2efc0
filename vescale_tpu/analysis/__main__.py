"""``python -m vescale_tpu.analysis`` — the analysis CLI.

Commands (default with no command: ``lint`` + ``examples``):

  lint [paths...]      vescale-lint over the given paths (default: the
                       whole repo — package, scripts, examples,
                       tests)
  examples             validate the examples/ training configs: every
                       model sharding plan audited (VSC107), and the
                       nanogpt config's forward program shardchecked
                       end-to-end
  demo {good,bad}      built-in shardcheck demo programs: ``bad`` is a
                       program that (a) implicitly materializes a sharded
                       operand (VSC101) and (b) redistributes across a
                       pair the multi-hop planner declines (VSC106 +
                       VSC12x decline code); ``good`` is the clean twin
  envdoc [--write P]   print (or write) the generated configuration doc
  whatif [...]         re-score candidate (dp, tp, pp) meshes against the
                       audited calibration table (telemetry/costaudit.py):
                       predicted step time + audit-backed confidence per
                       layout

Flags: ``--strict`` fails (exit 1) on warning-severity findings too (and
is how CI gates); ``--json`` emits machine-readable reports.
``VESCALE_SHARDCHECK=off`` disables program checks but the CLI still runs
them explicitly — the mode gates *implicit* integration points, not an
explicit invocation.
"""

from __future__ import annotations

# Device env must be decided before the first jax backend query: the demo
# and examples commands build 8-device CPU meshes.
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "cpu" in os.environ.get("JAX_PLATFORMS", "") and (
    "host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import argparse
import json
import sys
from typing import List

from .findings import FindingReport

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _default_lint_paths() -> List[str]:
    paths = []
    for rel in ("vescale_tpu", "scripts", "examples", "tests", "chip_smoke.py",
                "__graft_entry__.py"):
        p = os.path.join(_REPO, rel)
        if os.path.exists(p):
            paths.append(p)
    return paths or [os.path.dirname(os.path.dirname(__file__))]


def cmd_lint(args) -> List[FindingReport]:
    from .lint import lint_paths

    paths = args.paths or _default_lint_paths()
    return [lint_paths(paths)]


def cmd_demo(args) -> List[FindingReport]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from . import check_transition, shardcheck
    from ..mesh import DeviceMesh
    from ..placements import RaggedShard, Shard
    from ..spec import DArraySpec, TensorMeta

    axis_sizes = {"dp": 2, "tp": 4}
    x = jax.ShapeDtypeStruct((1024, 4096), jnp.float32)

    if args.which == "bad":
        # (a) flattening (B, H) with H tp-sharded merges the sharded dim
        # under the batch dim: GSPMD must all-gather x on every device
        def flatten_hidden(a):
            return jnp.reshape(a, (1024 * 4096,))

        report = shardcheck(
            flatten_hidden, x, in_specs=[P(None, "tp")], mesh=axis_sizes,
            name="demo-known-bad", min_bytes=0, check_source=False,
        )
        # (b) the redistribute pair that used to hit (and still declines
        # into) the logical-materializing fallback: skewed ragged -> even
        # Shard, whose only bridge is full replication (over budget)
        mesh8 = DeviceMesh(("x",), (8,))
        meta = TensorMeta((1 << 20,), jnp.float32)
        src = DArraySpec(mesh8, (RaggedShard((0,), (1, 2, 1, 2, 1, 3, 3, 3)),), meta)
        dst = DArraySpec(mesh8, (Shard(0),), meta)
        report.extend(check_transition(src, dst, where="demo ragged -> Shard(0)"))
        return [report]

    # good: batch-dp elementwise + mean over the (replicated) hidden dim,
    # sharding preserved end to end — and the same-spec redistribute is free
    def clean(a):
        return jnp.mean(a * 2.0, axis=1)

    report = shardcheck(
        clean, x, in_specs=[P("dp", None)], mesh=axis_sizes,
        name="demo-known-good", min_bytes=0, check_source=False,
    )
    mesh8 = DeviceMesh(("x",), (8,))
    meta = TensorMeta((1 << 20,), jnp.float32)
    src = DArraySpec(mesh8, (Shard(0),), meta)
    report.extend(check_transition(src, src.with_placements((Shard(0),)), where="demo no-op"))
    return [report]


def cmd_examples(args) -> List[FindingReport]:
    import jax
    import jax.numpy as jnp

    from . import check_param_plan, shardcheck
    from ..mesh import DeviceMesh

    reports: List[FindingReport] = []
    mesh = DeviceMesh(("dp", "tp"), (2, 4))

    from ..models.llama import llama_plan
    from ..models.mixtral import mixtral_plan
    from ..models.nanogpt import GPT, GPTConfig, nanogpt_plan

    for label, plan in (
        ("nanogpt_plan", nanogpt_plan(mesh)),
        ("nanogpt_plan[sp]", nanogpt_plan(mesh, sequence_parallel=True)),
        ("llama_plan", llama_plan(mesh)),
        ("llama_plan[scan]", llama_plan(mesh, scanned=True)),
    ):
        reports.append(check_param_plan(plan.get("parameter", {}), mesh, name=label))
    mesh_ep = DeviceMesh(("dp", "ep"), (2, 4))
    reports.append(check_param_plan(
        mixtral_plan(mesh_ep).get("parameter", {}), mesh_ep, name="mixtral_plan"
    ))

    # end-to-end: trace the nanogpt example's forward+loss under its plan
    # and shardcheck the program (the same trace jit/AOT lowering sees)
    from ..dmodule import parallelize_module
    from ..models.nanogpt import cross_entropy_loss

    cfg = GPTConfig(block_size=64, vocab_size=256, n_layer=2, n_head=4,
                    n_embd=64, dropout=0.0)
    dm = parallelize_module(GPT(cfg), mesh, nanogpt_plan(mesh))
    idx = jnp.ones((8, 64), jnp.int32)
    variables = jax.eval_shape(lambda: GPT(cfg).init(jax.random.key(0), idx))

    def fwd(params, batch_idx, batch_tgt):
        logits = dm.apply({"params": params}, batch_idx, deterministic=True)
        return cross_entropy_loss(logits, batch_tgt)

    reports.append(shardcheck(
        fwd, variables["params"], idx, jnp.zeros((8, 64), jnp.int32),
        mesh=mesh, name="examples/nanogpt_4d_finetune forward",
        check_source=False,
    ))
    return reports


def cmd_whatif(args) -> int:
    """Re-score candidate (dp, tp, pp) meshes against the live audited
    calibration table (telemetry/costaudit.py) — predicted step time per
    layout plus audit-backed confidence per collective term."""
    from ..telemetry import costaudit
    from ..telemetry.calibrate import load_table, set_active

    if args.table:
        set_active(load_table(args.table))
    num = args.devices
    if not num:
        import jax

        num = len(jax.devices())
    device = None
    if args.device:
        # a named chip instead of the local device: a shim carrying the one
        # attr calibrate.device_peaks reads
        device = type("_Dev", (), {"device_kind": args.device})()
    cands = costaudit.mesh_candidates(num)
    ranked = costaudit.score_candidates(
        cands,
        params_bytes=args.params_bytes,
        activation_bytes=args.activation_bytes,
        flops_per_step=args.flops,
        device=device,
    )
    if args.top:
        ranked = ranked[: args.top]
    if args.json:
        print(json.dumps({"num_devices": num, "candidates": ranked}, indent=2))
        return 0
    print(f"what-if plan scores over {num} devices "
          f"({len(cands)} (dp, tp, pp) layouts):")
    print(f"  {'mesh':>14} {'step_us':>12} {'compute_us':>12} "
          f"{'comm_us':>10} {'conf':>5}  sources")
    for r in ranked:
        m = r["mesh"]
        srcs = ",".join(sorted({t["source"] for t in r["terms"]})) or "-"
        print(f"  ({m['dp']:>3},{m['tp']:>3},{m['pp']:>3}) "
              f"{r['predicted_step_us']:>12.1f} {r['compute_us']:>12.1f} "
              f"{r['comm_us']:>10.1f} {r['confidence']:>5.2f}  {srcs}")
    return 0


def cmd_envdoc(args) -> List[FindingReport]:
    from .envreg import configuration_markdown

    doc = configuration_markdown()
    if args.write:
        with open(args.write, "w", encoding="utf-8") as f:
            f.write(doc)
        print(f"wrote {args.write}")
    else:
        print(doc)
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m vescale_tpu.analysis")
    ap.add_argument("--strict", action="store_true",
                    help="fail on warning-severity findings too")
    ap.add_argument("--json", action="store_true", help="JSON reports")
    sub = ap.add_subparsers(dest="cmd")
    p_lint = sub.add_parser("lint", help="vescale-lint over paths")
    p_lint.add_argument("paths", nargs="*", default=None)
    sub.add_parser("examples", help="validate examples/ training configs")
    p_demo = sub.add_parser("demo", help="built-in shardcheck demo programs")
    p_demo.add_argument("which", choices=("good", "bad"))
    p_env = sub.add_parser("envdoc", help="generated configuration doc")
    p_env.add_argument("--write", default=None, metavar="PATH")
    p_wi = sub.add_parser(
        "whatif", help="re-score candidate (dp, tp, pp) meshes against the "
        "audited calibration table")
    p_wi.add_argument("--devices", type=int, default=0,
                      help="world size (default: local device count)")
    p_wi.add_argument("--params-bytes", type=float, default=1e9)
    p_wi.add_argument("--activation-bytes", type=float, default=1e8)
    p_wi.add_argument("--flops", type=float, default=1e12,
                      help="model FLOPs per step")
    p_wi.add_argument("--table", default=None, metavar="PATH",
                      help="calibration table JSON (default: active table)")
    p_wi.add_argument("--device", default=None,
                      help='device_kind whose published peak prices compute '
                      '(e.g. "TPU v5 lite"; default: local device)')
    p_wi.add_argument("--top", type=int, default=0,
                      help="print only the best N layouts")
    args = ap.parse_args(argv)

    if args.cmd == "lint":
        reports = cmd_lint(args)
    elif args.cmd == "examples":
        reports = cmd_examples(args)
    elif args.cmd == "demo":
        reports = cmd_demo(args)
    elif args.cmd == "envdoc":
        cmd_envdoc(args)
        return 0
    elif args.cmd == "whatif":
        return cmd_whatif(args)
    else:
        args.paths = None
        reports = cmd_lint(args) + cmd_examples(args)

    ok = True
    for r in reports:
        if args.json:
            print(json.dumps(r.to_dict(), indent=2))
        else:
            print(r.format())
        ok = ok and r.ok(strict=args.strict)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
