"""How many prompts rode a decode step, and what such a step takes, from the
program's trace session (``--trace 2``).

A single-stage ``ServeEngine`` launches no program for a prompt alone where the
serve loop has a decode step about to be launched (PR 53): the step CARRIES the
prompt, its rows beside the decode rows under one read of every weight.

- ``prefill_ride_share.chat`` / ``.batch`` = the engine's counter
  ``prefill_rides`` over ``prefill_launches``: of the prompts whose program was
  enqueued in the traced seconds, the share that a decode step carried (the
  rest went alone: no step in flight to ride, or a second admission of one
  iteration).  A program without the counter (before PR 53, or an engine that
  carries no prompt), or a session that launched no prompt, leaves it out.
- ``ride_step_program_ms_p50.batch``: the duration of the decode program's
  module event (``jit_decode``) of each joined decode launch of ``_programs.py``
  whose ``.launch`` span says ``rung`` (a step that carries a prompt says how
  wide; the few prompts that went alone are such steps with every decode row
  idle, and count).  Beside ``decode_program_ms_p50.batch`` it is what a prompt
  adds to a step.  Under nine launches in ten joined, or where no decode launch
  says ``rung`` (every program before PR 53), it is left out."""

from benchmark.layer_metrics import _programs as p
from benchmark.layer_metrics import _session as s

MOVES = {"chat": "itl_p95_ms", "batch": "serve_tokens_per_s"}
ENGINE = "Serve engine"
METRICS = {f"prefill_ride_share.{sfx}": {"unit": "%", "layer": ENGINE, "moves": moves} for sfx, moves in MOVES.items()}
METRICS["ride_step_program_ms_p50.batch"] = {"unit": "ms", "layer": ENGINE, "moves": MOVES["batch"]}


def read(run):
    sfx, session = s.suffix(run), s.reduced(run)
    if sfx not in MOVES or session is None:
        return {}
    out = {}
    counters = session["counters"]
    launches = counters.get("prefill_launches") or 0
    if launches and "prefill_rides" in counters:
        out[f"prefill_ride_share.{sfx}"] = 100.0 * counters["prefill_rides"] / launches
    programs = p.reduced(run)
    if sfx == "batch" and p.trusted(programs):
        carrying = [x.program_ns / 1e6 for x in p.of_kind(programs, "decode") if x.rung is not None]
        if carrying:
            out["ride_step_program_ms_p50.batch"] = s.p50(carrying)
    return out
