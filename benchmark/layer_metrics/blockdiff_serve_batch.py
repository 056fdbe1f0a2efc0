"""Per-layer metrics of a configuration that generates by DIFFUSION OVER BLOCKS
(grouped-query attention, routed experts) under a closed-loop mix (suffix
``.batch``), from the program's trace session (``--trace 2``).  They read the
counters that ``HybridServeEngine.trace_counters`` reports for a block engine
(``models/sdar_moe.py``) and the counts and the table of shapes of
``families/sdar_moe.py``:

- ``blockdiff_tokens_per_pass.batch``: ``block_tokens_emitted`` /
  ``block_passes``, the tokens the host took over the slot-passes the device
  ran: B / (T + 1) = 0.8 at B = T = 4, less what the first block of a prompt
  that is no whole number of blocks and the last block of a budget that is none
  lose; ``blockdiff_commit_pass_share.batch``: ``block_commit_passes`` /
  ``block_passes`` (1 / (T + 1) of a whole block: passes that decide nothing
  and leave the block's K and V); ``blockdiff_masked_row_share.batch``:
  ``block_positions_masked`` / (B x ``block_passes``), the share of a pass's
  query rows that still had something to decide;
- ``unmask_device_share.batch`` / ``block_attn_device_share.batch`` /
  ``experts128_device_share.batch``: of the device time of the ops that ran
  inside the traced DECODE PROGRAMS (the first chip's ``XLA Modules`` events
  whose name holds ``decode``: since PR 35 a ``vs.serve-decode`` span no longer
  brackets its program), the share of head-to-selection (the head's product,
  the softmax over 151,936, the selection), of attention (projections, norms,
  rotary, the pool's writes, the ``paged_decode`` kernel) and of the routed
  experts (the family's table of shapes: the chip's events carry no scope);
- ``experts128_load_imbalance.batch``: the busiest expert's positions over the
  mean (``moe_busiest_expert_tokens`` / ``moe_layer_steps`` over
  ``moe_assignments_held`` / ``moe_expert_slots``);
- ``blockdiff_pass_hbm_roofline_share.batch``: the bytes one pass must move (the
  family's count: the weights held, of the experts those that got a position;
  the live K and V pages with the open block; the logits written and read
  once) over the decode program's device time at the median (its ``XLA
  Modules`` events) times the HBM rate;
- ``block_prefill_attn_roofline.batch``: the flash forward under the block mask
  (the ``block_flash_fwd`` kernel's events) against the LARGER of its useful
  operations (``prefill_attn_flops``: the pairs the mask keeps) over the MXU
  peak and its must-move bytes over the HBM rate, of the traced prefills;
- ``block_decode_attn_roofline.batch``: the ``paged_decode`` kernel's events in
  the traced passes against the larger of the live K and V pages' bytes over
  the HBM rate and the B x 32 queries' operations over them over the MXU peak.

A run of another family, of a program without these counters (the parent of
the PR that added them), or without a session (any untraced run; a CPU run)
leaves every metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _session as s

FAMILY = "sdar_moe"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
BLOCK, ATTENTION, EXPERTS, DEVICE = "Block diffusion", "Block attention", "Expert layer", "Device"
METRICS = {
    "blockdiff_tokens_per_pass.batch": {"unit": "tokens/pass", "layer": BLOCK, "moves": MOVES},
    "blockdiff_commit_pass_share.batch": {"unit": "%", "layer": BLOCK, "moves": MOVES},
    "blockdiff_masked_row_share.batch": {"unit": "%", "layer": BLOCK, "moves": MOVES},
    "unmask_device_share.batch": {"unit": "%", "layer": BLOCK, "moves": MOVES},
    "block_attn_device_share.batch": {"unit": "%", "layer": ATTENTION, "moves": MOVES},
    "experts128_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "experts128_load_imbalance.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
    "blockdiff_pass_hbm_roofline_share.batch": {"unit": "%", "layer": DEVICE, "moves": MOVES},
    "block_prefill_attn_roofline.batch": {"unit": "%", "layer": ATTENTION, "moves": MOVES},
    "block_decode_attn_roofline.batch": {"unit": "%", "layer": ATTENTION, "moves": MOVES},
}
DECODE_PROGRAM, DECODE_KERNEL, PREFILL_KERNEL = "decode", "paged_decode", "block_flash_fwd"


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


def device_times(profile, family, config):
    """Of the first chip: ``programs``, the durations (ns) of the decode
    programs' ``XLA Modules`` events; ``{mechanism: ns}`` of the ops that began
    inside one of them; and under the two kernels' names the ns of their
    events, wherever they ran.  None without a decode program or an op."""
    programs = sorted((a, b) for a, b, n in s._first_device_modules(profile) if DECODE_PROGRAM in n)
    per_device = {k: v for k, v in xplane.device_events(profile).items() if v}
    if not programs or not per_device:
        return None
    signatures = family.mechanism_signatures(config, config["serve"])
    known, total, i = {}, {DECODE_KERNEL: 0.0, PREFILL_KERNEL: 0.0}, 0
    for start, end, name in sorted(per_device[sorted(per_device)[0]]):
        kinds = known.get(name)
        if kinds is None:
            kinds = known[name] = (family.mechanism_of(name, signatures), xplane.op_family(name))
        if kinds[1] in (DECODE_KERNEL, PREFILL_KERNEL):
            total[kinds[1]] += end - start
        while i < len(programs) and programs[i][1] <= start:
            i += 1
        if i < len(programs) and programs[i][0] <= start:
            total[kinds[0]] = total.get(kinds[0], 0.0) + (end - start)
    total["programs"] = [b - a for a, b in programs]
    return total


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or "block_passes" not in session["counters"]:
        return {}
    c = session["counters"]
    steps, passes, held = c.get("decode_steps") or 0, c.get("block_passes") or 0, c.get("moe_assignments_held") or 0
    if not steps or not passes or not held:
        return {}
    config = _configuration(run)
    if config is None:
        return {}
    B = int(config["assumed"]["block_length"])
    out = {
        "blockdiff_tokens_per_pass.batch": c["block_tokens_emitted"] / passes,
        "blockdiff_commit_pass_share.batch": 100.0 * c["block_commit_passes"] / passes,
        "blockdiff_masked_row_share.batch": 100.0 * c["block_positions_masked"] / (B * passes),
        "experts128_load_imbalance.batch": (c["moe_busiest_expert_tokens"] / c["moe_layer_steps"]) / (held / c["moe_expert_slots"]),
    }
    family = spec.load_family(FAMILY, ROOT)
    peaks = spec.device_peaks(run.device_kind, ROOT)
    rate, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    serve, layers = config["serve"], config["num_hidden_layers"]
    times = device_times(run.session.profile, family, config)
    if times is None:
        return out
    program_ms = s.p50([ns / 1e6 for ns in times["programs"]])
    if program_ms:
        moved = family.pass_bytes(config, serve, kv_pages_read_per_layer=c.get("decode_pages_read", 0) / steps,
                                  experts_touched=c["moe_experts_touched"] / steps)
        out["blockdiff_pass_hbm_roofline_share.batch"] = 100.0 * moved / (program_ms * 1e-3 * rate)
    whole = sum(v for k, v in times.items() if k not in ("programs", DECODE_KERNEL, PREFILL_KERNEL))
    if whole:
        out["unmask_device_share.batch"] = 100.0 * times.get("unmask", 0.0) / whole
        out["block_attn_device_share.batch"] = 100.0 * times.get("attention", 0.0) / whole
        out["experts128_device_share.batch"] = 100.0 * times.get("experts", 0.0) / whole
    if times[PREFILL_KERNEL] and c.get("prefill_attn_flops"):
        must = max(c["prefill_attn_flops"] / flops,
                   layers * family.block_prefill_attention_bytes(config, c["prefill_bucket_tokens"]) / rate)
        out["block_prefill_attn_roofline.batch"] = 100.0 * must / (times[PREFILL_KERNEL] * 1e-9)
    if times[DECODE_KERNEL] and c.get("decode_pages_read"):
        positions = c["decode_pages_read"] * int(serve["page_size"]) * layers      # cached positions read, all layers
        must = max(positions * family.pass_attention_bytes_per_position(config) / rate,
                   positions * family.pass_attention_flops_per_position(config) / flops)
        out["block_decode_attn_roofline.batch"] = 100.0 * must / (times[DECODE_KERNEL] * 1e-9)
    return out
