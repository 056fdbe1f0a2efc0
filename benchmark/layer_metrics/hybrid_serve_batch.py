"""Per-layer metrics of a hybrid (state-space + attention, routed experts)
configuration under the closed-loop batch mix (suffix ``.batch``), from the
program's trace session (``--trace 2``).  They read the counters that
``HybridServeEngine.trace_counters`` adds to ``ServeEngine``'s and the byte
counts and the table of shapes of ``families/granite_hybrid.py``:

- ``moe_held_share.batch``: of the (active token, kept expert) pairs of the
  traced decode steps, the share that fell on an expert held here
  (``moe_assignments_held`` / ``moe_assignments``): the chip's share of the
  experts, 50% for one of 2, if the router is even;
- ``moe_load_imbalance.batch``: the busiest held expert's tokens over the mean
  (``moe_busiest_expert_tokens`` / ``moe_layer_steps`` over ``moe_assignments_held`` / ``moe_expert_slots``);
- ``ssm_state_gb_per_step.batch``: the slot state a decode step reads and writes;
- ``decode_hbm_roofline_share.batch``: the bytes one decode step must move (the
  family's count: the weights held, with the held experts that got a token from
  the counters; the slot state read and written; the live K/V pages from the
  counters; the logits) over the device time of a decode call at the median
  times the device's HBM rate (``peaks.json``);
- ``ssm_step_roofline.batch``: the bytes one call of the ``ssm_step`` kernel
  must move (the family's count: a layer's state read and written) over the
  mean device time of the kernel's events in the traced decode calls times the
  HBM rate; memory-bound (6 operations an element of the state).  Left out
  where the engine took the XLA leg (no event bears the kernel's name);
- ``mamba_device_share.batch`` / ``moe_device_share.batch``: of the device time
  of the ops that ran inside the traced ``vs.serve-decode`` calls, the share of
  the state-space mixers' and of the expert layers' (the family's table of
  shapes: the chip's events carry no scope).

The configuration is the one of this checkout's ``BENCHMARK.json`` whose
``model`` is the family's and whose cache geometry is the run's.  A run of
another family, of a program without these counters, or without a session
(any untraced run; a CPU run) leaves every metric out.
"""

import os

from benchmark import spec, xplane
from benchmark.layer_metrics import _session as s

FAMILY = "granite_hybrid"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
EXPERTS, MIXER, CACHE, DEVICE = "Expert layer", "State-space mixer", "Hybrid cache", "Device"
METRICS = {
    "moe_held_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
    "moe_load_imbalance.batch": {"unit": "ratio", "layer": EXPERTS, "moves": MOVES},
    "ssm_state_gb_per_step.batch": {"unit": "GB", "layer": CACHE, "moves": MOVES},
    "decode_hbm_roofline_share.batch": {"unit": "%", "layer": DEVICE, "moves": MOVES},
    "ssm_step_roofline.batch": {"unit": "%", "layer": MIXER, "moves": MOVES},
    "mamba_device_share.batch": {"unit": "%", "layer": MIXER, "moves": MOVES},
    "moe_device_share.batch": {"unit": "%", "layer": EXPERTS, "moves": MOVES},
}


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


KERNEL = "ssm_step"


def device_shares(profile, family, config):
    """``{mechanism: share of the device time}`` of the ops that began inside a
    ``vs.serve-decode`` call, on the first chip, and under ``KERNEL`` the mean
    nanoseconds of that kernel's events there (absent without one); None
    without such ops."""
    decodes = sorted((a, b) for a, b, n in xplane.host_spans(profile, s.PROGRAM_PREFIX) if n == "vs.serve-decode")
    per_device = {k: v for k, v in xplane.device_events(profile).items() if v}
    if not decodes or not per_device:
        return None
    signatures = family.mechanism_signatures(config, config["serve"])
    known, total, kernel, i = {}, {}, [], 0
    for start, end, name in sorted(per_device[sorted(per_device)[0]]):
        while i < len(decodes) and decodes[i][1] <= start:
            i += 1
        if i == len(decodes):
            break
        if start < decodes[i][0]:
            continue
        mechanism = known.get(name)
        if mechanism is None:
            mechanism = known[name] = family.mechanism_of(name, signatures)
        total[mechanism] = total.get(mechanism, 0.0) + (end - start)
        if xplane.op_family(name) == KERNEL:
            kernel.append(end - start)
    whole = sum(total.values())
    if not whole:
        return None
    shares = {m: 100.0 * t / whole for m, t in total.items()}
    if kernel:
        shares[KERNEL] = sum(kernel) / len(kernel)
    return shares


def read(run):
    session = s.reduced(run) if run.traffic_kind == "closed_loop" else None
    if session is None or "ssm_state_bytes_rw" not in session["counters"]:
        return {}
    c = session["counters"]
    steps, held = c.get("decode_steps") or 0, c.get("moe_assignments_held") or 0
    if not steps or not held:
        return {}
    out = {
        "moe_held_share.batch": 100.0 * held / c["moe_assignments"],
        "moe_load_imbalance.batch": (c["moe_busiest_expert_tokens"] / c["moe_layer_steps"]) / (held / c["moe_expert_slots"]),
        "ssm_state_gb_per_step.batch": c["ssm_state_bytes_rw"] / steps / 1e9,
    }
    config = _configuration(run)
    if config is None:
        return out
    family = spec.load_family(FAMILY, ROOT)
    device_ms = s.p50(session["decode_device_ms"])
    rate = spec.device_peaks(run.device_kind, ROOT)["hbm_bytes_per_s"]
    if device_ms:
        moved = family.decode_step_bytes(config, config["serve"], kv_pages_read_per_layer=c["decode_pages_read"] / steps,
                                         experts_touched=c["moe_experts_touched"] / steps)
        out["decode_hbm_roofline_share.batch"] = 100.0 * moved / (device_ms * 1e-3 * rate)
    shares = device_shares(run.session.profile, family, config)
    if shares is not None:
        out["mamba_device_share.batch"] = shares.get("mamba", 0.0)
        out["moe_device_share.batch"] = shares.get("moe", 0.0)
        if KERNEL in shares:
            out["ssm_step_roofline.batch"] = 100.0 * family.ssm_step_bytes(config, config["serve"]) / (
                shares[KERNEL] * 1e-9 * rate)
    return out
