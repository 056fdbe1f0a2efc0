"""Per-layer metrics of a latent-attention (MLA) configuration with routed
experts under the closed-loop batch mix (suffix ``.batch``), from the
program's trace session (``--trace 2``).  They read the counters that
``HybridServeEngine.trace_counters`` reports for ``models/deepseek_v2.py`` and
the counts and the table of shapes of ``families/deepseek_v2.py``:

- ``mla_device_share.batch`` / ``routed_device_share.batch``: of the device
  time of the ops that ran inside the traced ``vs.serve-prefill`` AND
  ``vs.serve-decode`` calls, the share of latent attention's (projections,
  rotary, the flash forward, the decode attention) and of the routed experts'
  (the family's table of shapes: the chip's events carry no scope);
- ``mla_decode_roofline.batch``: the decode attention (the
  ``paged_decode_latent`` kernel's events, or the XLA leg's ops over the
  gathered rows) against the LARGER of its must-read bytes over the HBM rate
  and its operations over the MXU peak, both at the real widths (a row of 576,
  values 512) over the positions the engine's counters say were live: it sits
  at the ridge, so both are counted;
- ``mla_prefill_roofline.batch``: causal attention's useful operations at the
  real widths (``prefill_attn_flops``: 128 x 640 x rung^2 / 2 a layer, of the
  traced prefills) over the flash forward's device time times the MXU peak;
- ``latent_gb_per_step.batch``: the latent pages a decode step reads, all layers;
- ``routed_held_share.batch``: of the (active token, kept expert) pairs of the
  traced decode steps, the share on an expert held here (25% for one of 4 under
  an even router); ``routed_load_imbalance.batch``: the busiest held expert's
  tokens over the mean;
- ``mla_step_hbm_roofline_share.batch``: the bytes one decode step must move
  (the family's count: the weights held, of the held experts those that got a
  token; the live latent pages; the logits) over the device time of a decode
  call at the median times the HBM rate.

``routed_held_share`` / ``routed_load_imbalance`` are ``moe_held_share`` /
``moe_load_imbalance`` of ``hybrid_serve_batch.py`` under names of their own
(two readers may not share a name); PERF.md, section 7, asks the next
``benchmark`` PR to merge them.  A run of another family, of a program without
these counters, or without a session (any untraced run; a CPU run) leaves
every metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _session as s

FAMILY = "deepseek_v2"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
MLA, EXPERTS, CACHE, DEVICE = "Latent attention", "Expert layer", "Latent cache", "Device"
METRICS = {
    "mla_device_share.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "routed_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "mla_decode_roofline.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "mla_prefill_roofline.batch": {"unit": "%", "layer": MLA, "moves": MOVES},
    "latent_gb_per_step.batch": {"unit": "GB", "layer": CACHE, "moves": MOVES},
    "routed_held_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "routed_load_imbalance.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
    "mla_step_hbm_roofline_share.batch": {"unit": "%", "layer": DEVICE, "moves": MOVES},
}
DECODE_KERNEL, PREFILL_KERNEL = "paged_decode_latent", "mla_flash_fwd"


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
    """Of the first chip's ops that began inside a traced ``vs.serve-prefill``
    or ``vs.serve-decode`` call: ``{mechanism: ns}``, and under
    ``decode_attention`` / ``prefill_attention`` the ns of the decode
    attention's ops inside decode calls and of the flash forward's inside
    prefill calls.  None without such ops."""
    calls = sorted((a, b, n) for a, b, n in xplane.host_spans(profile, s.PROGRAM_PREFIX)
                   if n in ("vs.serve-decode", "vs.serve-prefill"))
    per_device = {k: v for k, v in xplane.device_events(profile).items() if v}
    if not calls or not per_device:
        return None
    serve = config["serve"]
    signatures = family.mechanism_signatures(config, serve)
    # the XLA leg's decode attention works on every slot's whole row of positions
    xla_decode = (f"[{serve['slots']},{serve['positions_per_slot']},", f",{serve['positions_per_slot']}]")
    known, total, i = {}, {"decode_attention": 0.0, "prefill_attention": 0.0}, 0
    for start, end, name in sorted(per_device[sorted(per_device)[0]]):
        while i < len(calls) and calls[i][1] <= start:
            i += 1
        if i == len(calls):
            break
        if start < calls[i][0]:
            continue
        kinds = known.get(name)
        if kinds is None:
            op = xplane.op_family(name)
            kinds = known[name] = (family.mechanism_of(name, signatures),
                                   op == DECODE_KERNEL or any(x in name for x in xla_decode), op == PREFILL_KERNEL)
        total[kinds[0]] = total.get(kinds[0], 0.0) + (end - start)
        if calls[i][2] == "vs.serve-decode" and kinds[1]:
            total["decode_attention"] += end - start
        elif calls[i][2] == "vs.serve-prefill" and kinds[2]:
            total["prefill_attention"] += end - start
    return total


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or "latent_bytes_read" not in session["counters"]:
        return {}
    c = session["counters"]
    steps, held = c.get("decode_steps") or 0, c.get("moe_assignments_held") or 0
    if not steps or not held:
        return {}
    out = {
        "latent_gb_per_step.batch": c["latent_bytes_read"] / steps / 1e9,
        "routed_held_share.batch": 100.0 * held / c["moe_assignments"],
        "routed_load_imbalance.batch": (c["moe_busiest_expert_tokens"] / c["moe_layer_steps"]) / (held / c["moe_expert_slots"]),
    }
    config = _configuration(run)
    if config is None:
        return out
    family = spec.load_family(FAMILY, ROOT)
    peaks = spec.device_peaks(run.device_kind, ROOT)
    rate, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    serve, layers = config["serve"], config["num_hidden_layers"]
    # live positions x layers of the traced decode steps, from the bytes the engine counted (its rows are padded)
    padded_row = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) // 128) * 128
    positions = c["latent_bytes_read"] / (padded_row * 2)
    device_ms = s.p50(session["decode_device_ms"])
    if device_ms:
        moved = family.decode_step_bytes(
            config, serve, latent_pages_read_per_layer=positions / steps / layers / int(serve["page_size"]),
            experts_touched=c["moe_experts_touched"] / steps)
        out["mla_step_hbm_roofline_share.batch"] = 100.0 * moved / (device_ms * 1e-3 * rate)
    times = device_times(run.session.profile, family, config)
    if times is None:
        return out
    whole = sum(v for k, v in times.items() if k not in ("decode_attention", "prefill_attention"))
    if whole:
        out["mla_device_share.batch"] = 100.0 * times.get("mla", 0.0) / whole
        out["routed_device_share.batch"] = 100.0 * times.get("routed", 0.0) / whole
    if times["decode_attention"]:
        must = max(positions * family.latent_bytes_per_position(config) / layers / rate,
                   positions * family.mla_decode_flops_per_position(config) / flops)
        out["mla_decode_roofline.batch"] = 100.0 * must / (times["decode_attention"] * 1e-9)
    if times["prefill_attention"] and c.get("prefill_attn_flops"):
        out["mla_prefill_roofline.batch"] = 100.0 * c["prefill_attn_flops"] / flops / (times["prefill_attention"] * 1e-9)
    return out
