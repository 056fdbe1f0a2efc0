"""Per-layer metrics of a Laguna configuration (window and full attention
mixed: the sliding layers' rings beside the full layers' pages; many small
routed experts) under a closed-loop mix (suffix ``.batch``), from the
program's trace session (``--trace 2``).  Device operations are attributed to
PROGRAMS, through the join of ``_programs.py`` (a launch's ``XLA Modules``
events and the ops inside them).  They read the counters that
``HybridServeEngine.trace_counters`` reports for ``models/laguna.py`` and the
counts and the table of shapes of ``families/laguna.py``:

- ``swa_window_read_share.batch``: ``ring_positions_read`` /
  ``ring_positions_unwindowed``, of the positions the sliding layers' decode
  attention would have read from pages the share it reads from the rings: what
  the window saves a step (1 where no sequence is longer than the window);
- ``swa_ring_gb_per_step.batch``: ``ring_bytes_rw`` / ``decode_steps``, the
  rings' bytes a decode step reads and writes;
- ``swa_attn_device_share.batch`` / ``experts256_device_share.batch``: of the
  device time of the ops inside the traced DECODE AND PREFILL programs, the
  share of attention's, both layer types (projections, rotary, gate, the pools'
  and rings' writes, ``paged_decode``, the flash forwards), and of the expert
  layers' (router, routed and shared experts): the family's table of shapes at
  each launch's rows, the chip's events carry no scope;
- ``swa_window_flash_roofline.batch``: the ``window_flash_fwd`` kernel's events
  inside the traced prefill programs against the LARGER of the sliding layers'
  useful operations under the window (the family's count at each launch's rung:
  what ``prefill_window_attn_flops`` counts) over the MXU peak and their
  must-move bytes over the HBM rate;
- ``swa_ring_decode_roofline.batch``: the ring positions a decode step reads
  (``ring_positions_read`` / ``decode_steps``) times a position's K and V over
  the HBM rate, against the device time a traced decode program spends in the
  ``paged_decode`` events of the sliding layers (told from the pages' by their
  count of query heads: 64 against 48); memory-bound;
- ``experts256_load_imbalance.batch``: the busiest held expert's rows over the
  mean (``moe_busiest_expert_tokens`` / ``moe_layer_steps`` over
  ``moe_assignments_held`` / ``moe_expert_slots``).

A run of another family, of a program without these counters or without
numbered launches, or without a session (any untraced run; a CPU run) leaves
every metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

FAMILY = "laguna"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
WINDOW, RING, EXPERTS = "Window attention", "Ring cache", "Expert layer"
METRICS = {
    "swa_window_read_share.batch": {"unit": "ratio", "layer": RING, "moves": MOVES},
    "swa_ring_gb_per_step.batch": {"unit": "GB", "layer": RING, "moves": MOVES},
    "swa_attn_device_share.batch": {"unit": "%", "layer": WINDOW, "moves": MOVES},
    "swa_window_flash_roofline.batch": {"unit": "%", "layer": WINDOW, "moves": MOVES},
    "swa_ring_decode_roofline.batch": {"unit": "%", "layer": RING, "moves": MOVES},
    "experts256_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "experts256_load_imbalance.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
}
COUNTERS = {"ring_positions_read", "ring_positions_unwindowed", "ring_bytes_rw"}


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
    of shapes at a prefill's rung, at the slots for a decode step), the ns of
    the ``window_flash_fwd`` events there, and the ns of the ``paged_decode``
    events at the sliding layers' count of query heads."""
    serve, tables, known = config["serve"], {}, {}
    ring_heads = family.ring_decode_heads(config)
    ring_shape = None if ring_heads is None else f"f32[{int(serve['slots'])},{ring_heads},{config['head_dim']}]"
    total, window_ns, ring_ns = {}, 0.0, 0.0
    for launch in launches:
        rows = launch.rung if launch.kind == "prefill" else None
        if rows not in tables:
            tables[rows] = family.mechanism_signatures(config, serve, rows)
        for start, end, name in launch.ops:
            kinds = known.get((rows, name))
            if kinds is None:
                kinds = known[(rows, name)] = (family.mechanism_of(name, tables[rows]), xplane.op_family(name))
            total[kinds[0]] = total.get(kinds[0], 0.0) + (end - start)
            if kinds[1] == family.WINDOW_KERNEL:
                window_ns += end - start
            elif kinds[1] == family.DECODE_KERNEL and ring_shape is not None and ring_shape in name:
                ring_ns += end - start
    return total, window_ns, ring_ns


def _share(times, mechanism):
    whole = sum(times.values())
    return 100.0 * times.get(mechanism, 0.0) / whole if whole else None


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or not COUNTERS <= set(session["counters"]):
        return {}
    c = session["counters"]
    steps = c.get("decode_steps") or 0
    if not steps or not c["ring_positions_unwindowed"]:
        return {}
    out = {"swa_window_read_share.batch": c["ring_positions_read"] / c["ring_positions_unwindowed"],
           "swa_ring_gb_per_step.batch": c["ring_bytes_rw"] / steps / 1e9}
    if c.get("moe_assignments_held") and c.get("moe_layer_steps"):
        out["experts256_load_imbalance.batch"] = ((c["moe_busiest_expert_tokens"] / c["moe_layer_steps"])
                                                  / (c["moe_assignments_held"] / c["moe_expert_slots"]))
    config, programs = _configuration(run), p.reduced(run)
    if config is None or not p.trusted(programs):
        return out
    family = spec.load_family(FAMILY, ROOT)
    peaks = spec.device_peaks(run.device_kind, ROOT)
    rate, flops = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    decodes, prefills = p.of_kind(programs, "decode"), p.of_kind(programs, "prefill")
    in_decodes, _none, ring_ns = device_times(decodes, family, config)
    in_prefills, window_ns, _none = device_times(prefills, family, config)
    both = {k: in_decodes.get(k, 0.0) + in_prefills.get(k, 0.0) for k in set(in_decodes) | set(in_prefills)}
    out["swa_attn_device_share.batch"] = _share(both, "attention")
    out["experts256_device_share.batch"] = _share(both, "moe")
    if window_ns:
        must = sum(max(family.prefill_attention_flops(config, launch.rung, family.SLIDING) / flops,
                       family.prefill_attention_bytes(config, launch.rung, family.SLIDING) / rate)
                   for launch in prefills if launch.rung)
        out["swa_window_flash_roofline.batch"] = 100.0 * must / (window_ns * 1e-9)
    if ring_ns and decodes:
        must = c["ring_positions_read"] / steps * family.position_bytes(config) / rate
        out["swa_ring_decode_roofline.batch"] = 100.0 * must / (ring_ns / len(decodes) * 1e-9)
    return {name: value for name, value in out.items() if value is not None}      # (a share of no traced program: left out)
