"""A program's OWN time in a serve cell, from the program's trace session
(``--trace 2``) through the join of ``_programs.py``: the engine numbers its
launches and gives the enqueue a span of its own (``vs.serve-decode.launch`` /
``vs.serve-prefill.launch``, PR 38), and each launch is joined to the ``XLA
Modules`` events it started.  What the older readers take off a host span
(``decode_device_ms_p50.*``, ``prefill_device_ms_p50.*``: since the decode
pipeline a span holds the wait for the step BEFORE) these take off the device:

- ``decode_program_ms_p50.chat`` / ``.batch``: the duration of the decode
  program's module event (``jit_decode``) of each joined decode launch;
- ``prefill_program_ms_p50.*``: the sum of the durations of one prefill
  launch's module events (four a rung in ``ServeEngine``, one in
  ``HybridServeEngine``), the spaces between them left out;
- ``prefill_start_wait_ms_p50.*``: a prefill's first module event's start less
  its ``.launch`` span's start: what it waits behind the decode step in
  flight (with the enqueue itself);
- ``decode_launch_ms_p50.*``: the ``vs.serve-decode.launch`` span, by the
  ring's durations: the host's enqueue of a decode step alone.

Under nine launches in ten joined, without a ``.launch`` span (a program
before PR 38) or without a device trace, every metric is left out."""

from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
DEVICE, ENGINE = "Device", "Serve engine"
METRICS = {}
for _sfx, _moves in MOVES.items():
    METRICS.update({
        f"decode_program_ms_p50.{_sfx}": {"unit": "ms", "layer": DEVICE, "moves": _moves},
        f"prefill_program_ms_p50.{_sfx}": {"unit": "ms", "layer": DEVICE, "moves": _moves},
        f"prefill_start_wait_ms_p50.{_sfx}": {"unit": "ms", "layer": ENGINE, "moves": _moves},
        f"decode_launch_ms_p50.{_sfx}": {"unit": "ms", "layer": ENGINE, "moves": _moves}})


def read(run):
    sfx = s.suffix(run)
    if sfx not in MOVES:
        return {}
    programs, session = p.reduced(run), s.reduced(run)
    if session is None or not p.trusted(programs):
        return {}
    decodes, prefills = p.of_kind(programs, "decode"), p.of_kind(programs, "prefill")
    return {
        f"decode_program_ms_p50.{sfx}": s.p50([x.program_ns / 1e6 for x in decodes]),
        f"prefill_program_ms_p50.{sfx}": s.p50([x.program_ns / 1e6 for x in prefills]),
        f"prefill_start_wait_ms_p50.{sfx}": s.p50([x.start_wait_ns / 1e6 for x in prefills]),
        f"decode_launch_ms_p50.{sfx}": s.p50(session["ring_ms"].get("vs.serve-decode.launch")),
    }
