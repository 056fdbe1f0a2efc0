"""Per-layer metrics of a MiMo-V2 configuration (keys of 192 beside values of
128; the full layers' folded pages beside the window layers' rings with a sink;
a share of many routed experts) under a closed-loop mix (suffix ``.batch``),
from the program's trace session (``--trace 2``).  Device operations are
attributed to PROGRAMS, through the join of ``_programs.py`` (a launch's ``XLA
Modules`` events and the ops inside them).  They read the counters that
``HybridServeEngine.trace_counters`` reports for ``models/mimo_v2.py`` and the
counts and the table of shapes of ``families/mimo_v2.py`` (LOGICAL bytes: keys
192 and values 128 wide, live positions only):

- ``qk192_pages_gb_per_step.batch`` / ``qk192_ring_gb_per_step.batch``:
  ``page_bytes_read`` / ``decode_steps`` and ``ring_bytes_rw`` / ``decode_steps``,
  what a decode step reads of the full layers' pages, and reads and writes of the
  rings;
- ``qk192_attn_device_share.batch`` / ``experts16of256_device_share.batch``: of
  the device time of the ops inside the traced DECODE AND PREFILL programs, the
  share of attention's, both kinds (projections, rotary, the pools' and rings'
  writes, the folded ``paged_decode``, the flash forwards), and of the expert
  layers' (router, bias, the held experts): the family's table of shapes at each
  launch's rows, the chip's events carry no scope;
- ``qk192_paged_decode_roofline.batch`` / ``sink_ring_decode_roofline.batch``:
  the bytes a decode step must read of the pages (``page_bytes_read`` /
  ``decode_steps``) and of the rings (``ring_positions_read`` x a position's K
  and V / ``decode_steps``) over the HBM rate, against the device time a traced
  decode program spends in the folded decode kernel's events at the full layers'
  key heads (``paged_decode_kv4``) and at the window layers' (``paged_decode_kv8``,
  the one with the sink); memory-bound;
- ``sink_window_flash_roofline.batch`` / ``qk192_full_flash_roofline.batch``: the
  ``window_flash_fwd`` and ``causal_flash_fwd`` kernels' events inside the traced
  prefill programs against the LARGER of that kind's useful operations (the
  family's count at each launch's rung: what ``prefill_window_attn_flops`` and
  ``prefill_full_attn_flops`` count) over the MXU peak and their must-move bytes
  over the HBM rate;
- ``experts16of256_nowhere_share.batch``: ``rows_routed_nowhere`` over the rows
  that went through an expert layer (``moe_assignments`` / experts a token): the
  share of rows none of whose experts this chip holds.

A run of another family, of a program without these counters or without
numbered launches, or without a session (any untraced run; a CPU run) leaves
every metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

FAMILY = "mimo_v2"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
TWO_WIDTH, WINDOW, RING, EXPERTS = "Two-width attention", "Window attention", "Ring cache", "Expert layer"
METRICS = {
    "qk192_pages_gb_per_step.batch": {"unit": "GB", "layer": TWO_WIDTH, "moves": MOVES},
    "qk192_ring_gb_per_step.batch": {"unit": "GB", "layer": RING, "moves": MOVES},
    "qk192_attn_device_share.batch": {"unit": "%", "layer": TWO_WIDTH, "moves": MOVES},
    "qk192_paged_decode_roofline.batch": {"unit": "%", "layer": TWO_WIDTH, "moves": MOVES},
    "sink_ring_decode_roofline.batch": {"unit": "%", "layer": RING, "moves": MOVES},
    "sink_window_flash_roofline.batch": {"unit": "%", "layer": WINDOW, "moves": MOVES},
    "qk192_full_flash_roofline.batch": {"unit": "%", "layer": TWO_WIDTH, "moves": MOVES},
    "experts16of256_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "experts16of256_nowhere_share.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
}
COUNTERS = {"page_bytes_read", "ring_positions_read", "ring_bytes_rw", "rows_routed_nowhere"}


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
    name: ns}`` of the four attention kernels' events there."""
    serve, tables, known = config["serve"], {}, {}
    kernels = (family.WINDOW_KERNEL, family.CAUSAL_KERNEL, family.decode_kernel_of(config, family.FULL),
               family.decode_kernel_of(config, family.SWA))
    total, by_kernel = {}, dict.fromkeys(kernels, 0.0)
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
    steps = c.get("decode_steps") or 0
    if not steps:
        return {}
    out = {"qk192_pages_gb_per_step.batch": c["page_bytes_read"] / steps / 1e9,
           "qk192_ring_gb_per_step.batch": c["ring_bytes_rw"] / steps / 1e9}
    config, programs = _configuration(run), p.reduced(run)
    if config is None:
        return out
    rows = (c.get("moe_assignments") or 0) / config["num_experts_per_tok"]
    if rows:
        out["experts16of256_nowhere_share.batch"] = c["rows_routed_nowhere"] / rows
    if not p.trusted(programs):
        return out
    family = spec.load_family(FAMILY, ROOT)
    peaks = spec.device_peaks(run.device_kind, ROOT)
    rate, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    decodes, prefills = p.of_kind(programs, "decode"), p.of_kind(programs, "prefill")
    in_decodes, decode_kernels = device_times(decodes, family, config)
    in_prefills, prefill_kernels = device_times(prefills, family, config)
    both = {k: in_decodes.get(k, 0.0) + in_prefills.get(k, 0.0) for k in set(in_decodes) | set(in_prefills)}
    out["qk192_attn_device_share.batch"] = _share(both, "attention")
    out["experts16of256_device_share.batch"] = _share(both, "moe")
    for name, kernel, kind in (("sink_window_flash_roofline.batch", family.WINDOW_KERNEL, family.SWA),
                               ("qk192_full_flash_roofline.batch", family.CAUSAL_KERNEL, family.FULL)):
        if prefill_kernels[kernel]:
            must = sum(max(family.prefill_attention_flops(config, launch.rung, kind) / flops,
                           family.prefill_attention_bytes(config, launch.rung, kind) / rate)
                       for launch in prefills if launch.rung)
            out[name] = 100.0 * must / (prefill_kernels[kernel] * 1e-9)
    for name, kind, step_bytes in (
            ("qk192_paged_decode_roofline.batch", family.FULL, c["page_bytes_read"] / steps),
            ("sink_ring_decode_roofline.batch", family.SWA,
             c["ring_positions_read"] / steps * family.position_bytes(config, family.SWA))):
        kernel_ns = decode_kernels[family.decode_kernel_of(config, kind)]
        if kernel_ns and decodes:
            out[name] = 100.0 * (step_bytes / rate) / (kernel_ns / len(decodes) * 1e-9)
    return {name: value for name, value in out.items() if value is not None}      # (a share of no traced program: left out)
