"""Per-layer metrics of a LongCat-Flash configuration (two latent-attention
sublayers a layer at 64 heads over a latent paged cache; a shortcut branch of a
share of 512 routed experts beside 256 zero-compute identity experts) under a
closed-loop mix (suffix ``.batch``), from the program's trace session
(``--trace 2``).  Device operations are attributed to PROGRAMS, through the join
of ``_programs.py`` (a launch's ``XLA Modules`` events and the ops inside them).
They read the counters that ``HybridServeEngine.trace_counters`` reports for
``models/longcat_flash.py`` and the counts and the table of shapes of
``families/longcat_flash.py`` (REAL widths: a row of 576, scores 192 and values
128 wide, live positions only; every "x layers" counts SUBLAYERS):

- ``mla64_latent_gb_per_step.batch``: ``latent_bytes_read`` / ``decode_steps``,
  the latent pages a decode step reads, all eight pool layers (as the pool
  keeps them: rows of 640);
- ``zero_expert_pair_share.batch``: ``zero_expert_assignments`` /
  ``moe_assignments``, of the kept (active row, output) pairs of the decode
  steps those on identity experts (256 / 768 under an even router);
- ``mla64_device_share.batch`` / ``scmoe_routed_device_share.batch``: of the
  device time of the ops inside the traced DECODE AND PREFILL programs, the share
  of latent attention's (projections, rotary, the pool's writes,
  ``paged_decode_latent``, ``mla_flash_fwd``) and of the shortcut branch's
  (router, bias, the held experts, the identity part's sums): the family's table
  of shapes at each launch's rows, the chip's events carry no scope;
- ``mla64_decode_roofline.batch``: the LARGER of what ``paged_decode_latent``
  must read of the live rows (``latent_bytes_read`` brought to rows of 576) over
  the HBM rate and its operations (every head's score over 576 and mix over 512)
  over the MXU peak, against the device time a traced decode program spends in
  the kernel's events (eight a step); at 64 heads it sits on the memory side;
- ``mla64_prefill_roofline.batch``: the ``mla_flash_fwd`` kernel's events inside
  the traced prefill programs against the LARGER of causal attention's useful
  operations (the family's count at each launch's rung, what
  ``prefill_attn_flops`` counts) over the MXU peak and its must-move bytes over
  the HBM rate;
- ``scmoe_step_hbm_roofline_share.batch``: the bytes one decode step must move
  (the family's count: the weights held with only the TOUCHED experts, the live
  latent rows, the logits) over the median device time of a traced decode
  program times the HBM rate.

A run of another family (``mla_serve_batch.py``'s answers to
``latent_bytes_read`` too: this reader asks for ``zero_expert_assignments``
beside it, which only this model counts), of a program without these counters
or without numbered launches, or without a session (any untraced run; a CPU
run) leaves every metric out.
"""

import os
import statistics

from benchmark import spec, xplane
from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

FAMILY = "longcat_flash"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
MLA, EXPERTS, CACHE, DEVICE = "Latent attention", "Expert layer", "Latent cache", "Device"
METRICS = {
    "mla64_latent_gb_per_step.batch": {"unit": "GB", "layer": CACHE, "moves": MOVES},
    "zero_expert_pair_share.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
    "mla64_device_share.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "scmoe_routed_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "mla64_decode_roofline.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "mla64_prefill_roofline.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "scmoe_step_hbm_roofline_share.batch": {"unit": "%", "layer": DEVICE, "moves": MOVES},
}
COUNTERS = {"latent_bytes_read", "zero_expert_assignments"}


def _configuration(run):
    """The one configuration of this checkout that is of the family and has the run's cache geometry."""
    try:
        declared = spec.load_benchmark(ROOT)["configs"]
    except spec.SpecError:
        return None
    found = []
    for entry in declared:
        try:
            config = spec._load_json(os.path.join(ROOT, entry["file"]))
        except spec.SpecError:
            continue
        serve = config.get("serve") or {}
        if (config.get("model") == FAMILY and serve.get("slots") == run.slots
                and serve.get("positions_per_slot") == run.padded_prompt_len):
            found.append(config)
    return found[0] if len(found) == 1 else None


def device_times(launches, family, config):
    """``{mechanism: ns}`` of the ops inside the launches' programs (the table
    of shapes at a prefill's rung, at the slots for a decode step) and ``{kernel
    name: ns}`` of the two attention kernels' events there."""
    serve, tables, known = config["serve"], {}, {}
    total, by_kernel = {}, {family.DECODE_KERNEL: 0.0, family.PREFILL_KERNEL: 0.0}
    for launch in launches:
        rows = launch.rung if launch.kind == "prefill" else None
        if rows not in tables:
            tables[rows] = family.mechanism_signatures(config, serve, rows)
        for start, end, name in launch.ops:
            kinds = known.get((rows, name))
            if kinds is None:
                kinds = known[(rows, name)] = (family.mechanism_of(name, tables[rows]), xplane.op_family(name))
            total[kinds[0]] = total.get(kinds[0], 0.0) + (end - start)
            if kinds[1] in by_kernel:
                by_kernel[kinds[1]] += end - start
    return total, by_kernel


def _share(times, mechanism):
    whole = sum(times.values())
    return 100.0 * times.get(mechanism, 0.0) / whole if whole else None


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or not COUNTERS <= set(session["counters"]):
        return {}
    c = session["counters"]
    steps, pairs = c.get("decode_steps") or 0, c.get("moe_assignments") or 0
    if not steps or not pairs:
        return {}
    out = {"mla64_latent_gb_per_step.batch": c["latent_bytes_read"] / steps / 1e9,
           "zero_expert_pair_share.batch": c["zero_expert_assignments"] / pairs}
    config, programs = _configuration(run), p.reduced(run)
    if config is None or not p.trusted(programs):
        return out
    family = spec.load_family(FAMILY, ROOT)
    peaks = spec.device_peaks(run.device_kind, ROOT)
    rate, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    decodes, prefills = p.of_kind(programs, "decode"), p.of_kind(programs, "prefill")
    in_decodes, decode_kernels = device_times(decodes, family, config)
    in_prefills, prefill_kernels = device_times(prefills, family, config)
    both = {k: in_decodes.get(k, 0.0) + in_prefills.get(k, 0.0) for k in set(in_decodes) | set(in_prefills)}
    out["mla64_device_share.batch"] = _share(both, "mla")
    out["scmoe_routed_device_share.batch"] = _share(both, "routed")
    # live positions x sublayers of a traced decode step, from the bytes the engine counted (its rows are padded)
    positions = c["latent_bytes_read"] / steps / (family.pool_bytes_per_position(config) / family.sublayers(config))
    if decodes:
        kernel_ns = decode_kernels[family.DECODE_KERNEL] / len(decodes)
        if kernel_ns:
            must = max(positions * family.latent_bytes_per_position(config) / rate,
                       positions * family.mla_decode_flops_per_position(config) / flops)
            out["mla64_decode_roofline.batch"] = 100.0 * must / (kernel_ns * 1e-9)
        moved = family.decode_step_bytes(config, config["serve"], latent_positions_read=positions,
                                         experts_touched=(c.get("moe_experts_touched") or 0) / steps)
        program_ns = statistics.median(launch.program_ns for launch in decodes)
        out["scmoe_step_hbm_roofline_share.batch"] = 100.0 * (moved / rate) / (program_ns * 1e-9)
    if prefill_kernels[family.PREFILL_KERNEL]:
        n = family.sublayers(config)
        must = sum(n * max(family.mla_prefill_attention_flops(config, launch.rung) / flops,
                           family.mla_prefill_attention_bytes(config, launch.rung) / rate)
                   for launch in prefills if launch.rung)
        out["mla64_prefill_roofline.batch"] = 100.0 * must / (prefill_kernels[family.PREFILL_KERNEL] * 1e-9)
    return {name: value for name, value in out.items() if value is not None}      # (a share of no traced program: left out)
