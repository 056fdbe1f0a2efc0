"""Per-layer metrics of a Falcon-H1 configuration (a state-space mixer and an
attention mixer side by side in every layer, a dense MLP) under the
closed-loop batch mix (suffix ``.batch``), from the program's trace session
(``--trace 2``).  Device operations are attributed to PROGRAMS, through the
join of ``_programs.py`` (a launch's ``XLA Modules`` events and the ops inside
them), not to host spans, which since the decode pipeline no longer bracket a
step.  They read the counters that ``HybridServeEngine.trace_counters`` reports
for ``models/falcon_h1.py`` and the byte counts and the table of shapes of
``families/falcon_h1.py``:

- ``h1_state_gb_per_step.batch``: the slot state a decode step reads and writes,
  all layers (``ssm_state_bytes_rw`` / ``decode_steps``);
- ``h1_ssm_device_share.batch`` / ``h1_attn_device_share.batch`` /
  ``h1_mlp_device_share.batch``: of the device time of the ops inside the traced
  DECODE programs, the share of the state-space mixers' (projections,
  convolution, the ``ssm_step`` kernel, the gated norm), of attention's
  (projections, rotary, the pool's writes, ``paged_decode``) and of the MLPs'
  (the family's table of shapes: the chip's events carry no scope);
- ``h1_scan_prefill_device_share.batch``: the state-space mixers' share inside
  the traced PREFILL programs (the table at each launch's rung);
- ``h1_ssm_step_roofline.batch``: the bytes one call of the ``ssm_step`` kernel
  must move (the family's count: one layer's state read and written, with B, C,
  decay, ``dt x`` and ``y``) over the mean device time of the kernel's events in
  the traced decode programs times the HBM rate (``peaks.json``); memory-bound
  (6 operations an element of the state).  Left out where the engine took the
  XLA leg (no event bears the kernel's name);
- ``h1_step_hbm_roofline_share.batch``: the bytes one decode step must move (the
  family's count: the weights held, the state read and written, the live K/V
  pages of every layer from the counters, the logits) over the decode
  program's device time at the median times the HBM rate.

The configuration is the one of this checkout's ``BENCHMARK.json`` whose
``model`` is the family's and whose cache geometry is the run's.  A run of
another family, of a program without these counters or without numbered
launches, or without a session (any untraced run; a CPU run) leaves every
metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

FAMILY = "falcon_h1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
MIXER, CACHE, ENGINE, DEVICE = "State-space mixer", "Hybrid cache", "Serve engine", "Device"
METRICS = {
    "h1_state_gb_per_step.batch": {"unit": "GB", "layer": CACHE, "moves": MOVES},
    "h1_ssm_device_share.batch": {"unit": "%", "layer": MIXER, "moves": MOVES},
    "h1_attn_device_share.batch": {"unit": "%", "layer": ENGINE, "moves": MOVES},
    "h1_mlp_device_share.batch": {"unit": "%", "layer": ENGINE, "moves": MOVES},
    "h1_scan_prefill_device_share.batch": {"unit": "%", "layer": MIXER, "moves": MOVES},
    "h1_ssm_step_roofline.batch": {"unit": "%", "layer": MIXER, "moves": MOVES},
    "h1_step_hbm_roofline_share.batch": {"unit": "%", "layer": DEVICE, "moves": MOVES},
}
KERNEL = "ssm_step"


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
    of shapes at a prefill's rung, at the slots for a decode step), and the
    durations (ns) of ``KERNEL``'s events there."""
    serve, tables, known = config["serve"], {}, {}
    total, kernel = {}, []
    for launch in launches:
        rows = launch.rung if launch.kind == "prefill" else None
        if rows not in tables:
            tables[rows] = family.mechanism_signatures(config, serve, rows)
        for start, end, name in launch.ops:
            mechanism = known.get((rows, name))
            if mechanism is None:
                mechanism = known[(rows, name)] = family.mechanism_of(name, tables[rows])
            total[mechanism] = total.get(mechanism, 0.0) + (end - start)
            if xplane.op_family(name) == KERNEL:
                kernel.append(end - start)
    return total, kernel


def _share(times, mechanism):
    whole = sum(times.values())
    return 100.0 * times.get(mechanism, 0.0) / whole if whole else None


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or not {"ssm_state_bytes_rw", "prefill_scan_chunks"} <= set(session["counters"]):
        return {}
    c = session["counters"]
    steps = c.get("decode_steps") or 0
    if not steps:
        return {}
    out = {"h1_state_gb_per_step.batch": c["ssm_state_bytes_rw"] / steps / 1e9}
    config, programs = _configuration(run), p.reduced(run)
    if config is None or not p.trusted(programs):
        return out
    family = spec.load_family(FAMILY, ROOT)
    rate = spec.device_peaks(run.device_kind, ROOT)["hbm_bytes_per_s"]
    decodes, prefills = p.of_kind(programs, "decode"), p.of_kind(programs, "prefill")
    program_ms = s.p50([x.program_ns / 1e6 for x in decodes])
    if program_ms:
        moved = family.decode_step_bytes(config, config["serve"], kv_pages_read_per_layer=c.get("decode_pages_read", 0) / steps)
        out["h1_step_hbm_roofline_share.batch"] = 100.0 * moved / (program_ms * 1e-3 * rate)
    in_decodes, kernel_ns = device_times(decodes, family, config)
    out["h1_ssm_device_share.batch"] = _share(in_decodes, "mamba")
    out["h1_attn_device_share.batch"] = _share(in_decodes, "attention")
    out["h1_mlp_device_share.batch"] = _share(in_decodes, "mlp")
    out["h1_scan_prefill_device_share.batch"] = _share(device_times(prefills, family, config)[0], "mamba")
    if kernel_ns:
        mean_ns = sum(kernel_ns) / len(kernel_ns)
        out["h1_ssm_step_roofline.batch"] = 100.0 * family.ssm_step_bytes(config, config["serve"]) / (mean_ns * 1e-9 * rate)
    return {name: value for name, value in out.items() if value is not None}      # (a share of no traced program: left out)
