"""A serve cell: the program's normal path (the family's engine and cache +
``ContinuousBatchingScheduler`` + ``run_serve_resilient``, fed through a
``RequestInbox`` as a fleet replica is) under an open or a closed loop of
generated requests.  The model family's own file builds the system
(``benchmark/families/<model>.py``: weights, cache, engine, vocabulary,
reference, tolerance); this runner keeps the warm-up, the instrumentation, the
window, the ledger and the close, and touches the engine and the cache only
through the surface that ``benchmark/README.md`` ("Adding a family") writes down.

The benchmark takes from the program only the system under test.  It wraps
its own calls into the engine and the scheduler (instance attributes on the
objects it built, no change to the program) to record raw per-request and
per-token times on one clock, and puts its own annotations (``bm.prefill``,
``bm.decode``) into the profiler's trace.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import reference, stats, trafficgen
from .harness import (TRACE_SECONDS, CompileCounter, SessionTracer, Tracer, annotate, memory_in_use_bytes, memory_peak_bytes,
                      wait_until)
from .record import RequestRecord, RunRecord
from .spec import CellSpec

# the reference check's procedure; its tolerance, with the reason for it, is the family's
CHECK_PROMPT_TOKENS = 320
CHECK_DECODE_STEPS = 4
WINDOW_CLOSED = "benchmark window closed"
# --trace 2: seconds of open-loop arrivals planned past the window (the
# profiler's first start, the traced seconds, and room to spare), and how long
# the traced seconds may stretch while they have not yet seen a prefill (the
# per-layer metrics of prefill need one)
EXTENSION_S = 20.0
TRACE_STRETCH = 3.0


class ServeCell:
    """Builds the system once; ``run`` drives one traffic mix through it."""

    def __init__(self, spec: CellSpec, devices):
        if spec.chips != 1 or len(devices) < 1:
            raise ValueError("a serve cell runs one replica on one chip")
        self.spec = spec
        self.devices = list(devices[:1])
        self._rec: Optional[RunRecord] = None
        self._sched = None
        self.window_notes: Dict[str, Any] = {}    # for the [bm] line: what on_step saw as the window closed

    # ------------------------------------------------------------- set-up
    def build(self, seed: int) -> None:
        """The family builds weights, cache and engine from the seed; then
        every shape the traffic uses is warmed (one padded prefill and one
        decode step, twice: a program whose second call recompiles must do it
        here)."""
        c = self.spec.config
        self.family = self.spec.family()
        system = self.family.build_serve(c, c["serve"], self.devices, seed)
        self.cache, self.engine, self.vocab = system.cache, system.engine, system.vocab
        self._instrument_engine()
        for _ in range(2):
            slot = self.cache.alloc(8, 2)
            self.engine.prefill([1] * 8, slot)
            self.cache.commit_prefill(slot, 8)
            self.engine.decode(np.zeros((self.cache.num_slots,), np.int32))
            self.cache.reset()

    def _instrument_engine(self) -> None:
        engine, cell = self.engine, self
        prefill, decode = engine.prefill, engine.decode

        def timed_prefill(prompt, slot):
            rec, sched = cell._rec, cell._sched
            t0 = time.perf_counter()
            with annotate("bm.prefill"):
                out = prefill(prompt, slot)
            if rec is not None:
                rid = sched.active[slot].req.rid
                rec.requests[rid].admitted = t0
                rec.prefills.append((t0, time.perf_counter(), rid, len(prompt)))
            return out

        def timed_decode(tokens):
            rec, sched = cell._rec, cell._sched
            t0 = time.perf_counter()
            with annotate("bm.decode"):
                out = decode(tokens)
            if rec is not None:
                rec.decodes.append((t0, time.perf_counter(), len(sched.active)))
            return out

        engine.prefill, engine.decode = timed_prefill, timed_decode

    # ---------------------------------------------------------------- run
    def run(self, traffic: Dict[str, Any], seed: int, seconds: float, *, traced: int,
            compiles: CompileCounter, setup_from: float) -> RunRecord:
        """One lead-in and one window of ``traffic``.  ``setup_from`` is the
        instant set-up is counted from (the process's start).  ``traced`` is
        the command's ``--trace``; with 2 the window's plan is that of 0 to
        the letter, and the traffic carries on past it under the program's
        trace session."""
        from vescale_tpu import telemetry
        from vescale_tpu.ndtimeline import api as ndtimeline
        from vescale_tpu.resilience.preempt import PreemptionHandler
        from vescale_tpu.serve import ContinuousBatchingScheduler, Request, run_serve_resilient
        from vescale_tpu.serve.fleet import RequestInbox

        kind = traffic["kind"]
        if kind == "open_loop":
            planned = trafficgen.open_loop_requests(traffic, seed, seconds, self.vocab)
            if traced == 2:
                # the window's plan stays as it is; a second plan of the same mix, from a
                # sub-seed of its own, is shifted past the window and appended
                past = float(traffic["lead_in_s"]) + float(seconds)
                more = trafficgen.open_loop_requests(
                    dict(traffic, lead_in_s=0.0), int(np.random.default_rng([int(seed), 7]).integers(1 << 31)),
                    EXTENSION_S, self.vocab)
                planned = planned + [dataclasses.replace(p, rid=len(planned) + p.rid, due_s=past + p.due_s)
                                     for p in more]
        elif kind == "closed_loop":
            planned = trafficgen.closed_loop_requests(traffic, seed, self.vocab)
            done_shares = trafficgen.first_wave_done_shares(traffic, seed)
        else:
            raise trafficgen.TrafficError(f"a serve cell takes open_loop or closed_loop traffic, not {kind!r}")

        self.cache.reset()
        sched = ContinuousBatchingScheduler(self.cache)   # queue bound and SLO shedding at the program's defaults
        rec = RunRecord(kind="serve", chips=1, traffic_kind=kind, slots=self.cache.num_slots,
                        padded_prompt_len=self.cache.max_seq_len, device_kind=self.devices[0].device_kind)
        inbox, handler, stop = RequestInbox(), PreemptionHandler(), threading.Event()
        tracer = Tracer(self.spec, traced == 1, seconds)
        after = SessionTracer(self.spec) if traced == 2 else None
        sched_open: Optional[Dict[str, Any]] = None
        completed_now: List[int] = []

        record_token, complete = sched.record_token, sched.complete

        def timed_record_token(slot, token):
            record_token(slot, token)
            rec.requests[sched.active[slot].req.rid].token_times.append(time.perf_counter())

        def noted_complete(slot):
            rid = sched.active[slot].req.rid
            out = complete(slot)
            completed_now.append(rid)
            return out

        sched.record_token, sched.complete = timed_record_token, noted_complete

        t0 = time.perf_counter()
        window = (t0 + float(traffic["lead_in_s"]), t0 + float(traffic["lead_in_s"]) + float(seconds))
        rec.window = window
        rec.setup_s = window[0] - setup_from
        grace = float(traffic.get("grace_s", 5.0))

        def send(p: trafficgen.PlannedRequest, rid: int, due: float) -> None:
            rec.requests[rid] = RequestRecord(rid=rid, due=due, sent=time.perf_counter(),
                                              prompt_len=len(p.prompt), max_new_tokens=p.max_new_tokens)
            inbox.push(Request(rid=rid, prompt=p.prompt, max_new_tokens=p.max_new_tokens))

        def open_loop_generator() -> None:
            for p in planned:
                if wait_until(t0 + p.due_s, stop.wait):
                    return
                send(p, p.rid, t0 + p.due_s)

        next_planned = 0

        def closed_loop_next() -> None:
            nonlocal next_planned
            i, next_planned = next_planned, next_planned + 1
            p = planned[i % len(planned)]
            if i < len(done_shares):
                p = trafficgen.cut_first_wave(p, done_shares[i])
            send(p, i, time.perf_counter())

        def window_is_answered() -> bool:
            return all(r.token_times or r.status is not None for r in rec.requests.values()
                       if stats.in_window(r.due, window))

        closing = False

        def on_step(_step: int, active: int) -> None:
            nonlocal closing, sched_open
            now = time.perf_counter()
            rec.loop_steps.append((now, active, len(sched.queue)))
            if closing:
                return
            if sched_open is None and now >= window[0]:
                sched_open = telemetry.host_sched_stats()     # the loop's own thread, as the window opens
            if kind == "closed_loop":
                for _rid in completed_now:
                    if now < window[1] or (after is not None and after.stopped is None):
                        closed_loop_next()
                completed_now.clear()
            tracer.maybe_start(now, window[1])
            if now < window[1]:
                return
            if rec.host_sched is None:
                # the window has closed: what is read at its close is read here, before anything is traced
                closed = telemetry.host_sched_stats()
                rec.host_sched = telemetry.host_sched_delta(sched_open or closed, closed)
                self.window_notes = {
                    "host_sched_in_window": rec.host_sched,
                    "program_tracing_in_window": {"ndtimeline": ndtimeline.is_active(),
                                                  "telemetry": telemetry.is_active()}}
                if after is not None:
                    rec.memory_peak_bytes = memory_peak_bytes(self.devices)
                    rec.compile_times = list(compiles.times)
                    after.begin()
                    return
            tracer.maybe_stop(now, window[1])
            if after is not None and after.stopped is None:
                seen_a_prefill = any(p[0] >= after.started for p in rec.prefills[-8:])
                if not after.due(now) or (not seen_a_prefill and now < after.started + TRACE_STRETCH * TRACE_SECONDS):
                    return
                rec.session = after.end()
                rec.traced_window = (after.started, after.stopped)
                self.window_notes["session_cost_s"] = after.cost_s
            for rid, out in sched.outcomes.items():     # a shed request is answered: it failed
                if rid in rec.requests and rec.requests[rid].status is None:
                    rec.requests[rid].status = out["status"]
            if kind == "open_loop" and not window_is_answered() and now < window[1] + grace:
                return
            # close: stop the generator, cancel what is in flight (its tokens up
            # to here are counted) and let the program's own drain end the loop
            closing = True
            stop.set()
            inbox.close()
            for slot in list(sched.active):
                sched.timeout(slot, reason=WINDOW_CLOSED)
            handler.request()

        self._rec, self._sched = rec, sched
        generator = None
        try:
            if kind == "open_loop":
                generator = threading.Thread(target=open_loop_generator, name="bm-generator")
                generator.start()
            else:
                for _ in range(int(traffic["clients"])):
                    closed_loop_next()
            run_serve_resilient(engine=self.engine, scheduler=sched, arrivals=[], inbox=inbox,
                                preemption=handler, install_signal_handlers=False, coordinate=False,
                                on_step=on_step)
        finally:
            stop.set()
            if generator is not None:
                generator.join(timeout=30.0)
            self._rec = self._sched = None
        if generator is not None and generator.is_alive():
            raise RuntimeError("the load generator did not stop")
        if after is None:
            rec.memory_peak_bytes = memory_peak_bytes(self.devices)
            rec.compile_times = list(compiles.times)
        for rid, out in sched.outcomes.items():
            if rid in rec.requests:
                rec.requests[rid].status = out["status"]
        rec.trace = tracer.summary() if after is None else after.summary()
        self.last_scheduler = sched
        return rec

    # ------------------------------------------------------------- checks
    def ledger(self, rec: RunRecord) -> Tuple[bool, Dict[str, Any]]:
        """Every submission has exactly one terminal outcome, every completed
        request has as many tokens as it asked for, and the only timeouts are
        the benchmark's own cancellations at the window's close."""
        from vescale_tpu.serve.scheduler import TERMINAL

        sched = self.last_scheduler
        problems: List[str] = []
        try:
            sched.ledger_check()
        except AssertionError as e:
            problems.append(f"ledger: {e}")
        for rid, out in sched.outcomes.items():
            r = rec.requests.get(rid)
            if r is None or out["status"] not in TERMINAL:
                problems.append(f"request {rid}: outcome {out['status']!r} without a record or not terminal")
            elif out["status"] == "completed" and len(out["tokens"]) != r.max_new_tokens:
                problems.append(f"request {rid}: {len(out['tokens'])} tokens of {r.max_new_tokens}")
            elif out["status"] == "timed_out" and out.get("reason") != WINDOW_CLOSED:
                problems.append(f"request {rid}: timed out ({out.get('reason')})")
        return not problems, {"counts": dict(sched.counts), "problems": problems[:5]}

    def check_reference(self, seed: int) -> Tuple[bool, Dict[str, Any]]:
        """Prefill of one seeded prompt and then teacher-forced decode steps
        through the cache, against the family's reference's full forward:
        logits, never tokens."""
        rng = np.random.default_rng([int(seed), 4])
        n = min(CHECK_PROMPT_TOKENS, self.cache.max_seq_len - CHECK_DECODE_STEPS - 1)
        prompt = [int(t) for t in rng.integers(1, self.vocab - 1, n)]
        forced = [int(t) for t in rng.integers(1, self.vocab - 1, CHECK_DECODE_STEPS)]
        self.cache.reset()
        slot = self.cache.alloc(n, CHECK_DECODE_STEPS + 1)
        rows = [self.engine.prefill(prompt, slot)]
        self.cache.commit_prefill(slot, n)
        for tok in forced:
            toks = np.zeros((self.cache.num_slots,), np.int32)
            toks[slot] = tok
            rows.append(self.engine.decode(toks)[slot])
            self.cache.advance(slot)
        got = np.stack(rows)
        want = np.asarray(self.family.logits(self.engine.params, self.spec.config, prompt + forced,
                                             range(n - 1, n + CHECK_DECODE_STEPS)))
        self.cache.reset()
        err = reference.rel_at_scale(got, want)
        agree = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
        tolerance = self.family.SERVE_LOGITS_TOLERANCE
        ok = bool(np.isfinite(got).all() and err <= tolerance)
        return ok, {"logits_max_abs_diff_over_max": err, "tolerance": tolerance,
                    "argmax_agreement": agree, "prompt_tokens": n, "decode_steps": CHECK_DECODE_STEPS}

    def attempted_failed(self, rec: RunRecord) -> Tuple[int, int]:
        if rec.traffic_kind == "open_loop":
            samples, failed = stats.first_token_delays(rec.request_rows(), rec.window)
            return len(samples) + failed, failed
        sent = [r for r in rec.requests.values() if r.sent < rec.window[1]]
        return len(sent), sum(1 for r in sent if r.status in ("shed", "failed"))


def run_cell(spec: CellSpec, devices, seed: int, seconds: float, traced: int, setup_from: float):
    """The whole of a serve cell's run: (record, correct, attempted, failed, notes)."""
    compiles = CompileCounter().install()
    try:
        cell = ServeCell(spec, devices)
        cell.build(seed)
        rec = cell.run(spec.traffic, seed, seconds, traced=traced, compiles=compiles, setup_from=setup_from)
        ledger_ok, ledger = cell.ledger(rec)
        ref_ok, ref = cell.check_reference(seed)
    finally:
        compiles.close()
    attempted, failed = cell.attempted_failed(rec)
    correct = ledger_ok and ref_ok and rec.compiles_in_window() == 0
    slowest = sorted(((d[1] - d[0], d[0] - rec.window[0]) for d in rec.in_window(rec.decodes)), reverse=True)[:3]
    gaps = stats.token_gaps(rec.token_times(), rec.window)
    decode_ms, prefill_ms = ([round(stats.ms(stats.percentile([x[1] - x[0] for x in rec.in_window(spans)], q)) or 0.0, 2)
                              for q in (1, 50, 99)] for spans in (rec.decodes, rec.prefills))
    # what a tail of the token gaps is made of: a gap holds one decode step and the prefills that ran before it
    holds = lambda k: sum(g * 1e3 > decode_ms[1] + (k - 0.5) * prefill_ms[1] for g in gaps) / max(len(gaps), 1)
    notes = {"ledger": ledger, "reference": ref, "compiles_in_window": rec.compiles_in_window(),
             "token_gaps": {"n": len(gaps), "share_holding_a_prefill": holds(1), "share_holding_two": holds(2)},
             "decode_ms_p1_p50_p99": decode_ms, "prefill_ms_p1_p50_p99": prefill_ms,
             "memory_in_use_bytes_at_close": memory_in_use_bytes(cell.devices),
             "slowest_decodes_ms_at_s": [[round(d * 1e3, 2), round(at, 2)] for d, at in slowest],
             **cell.window_notes}
    if rec.session is not None:     # what the session costs while it is on: the calls under it against the window's
        under = lambda spans: stats.ms(stats.percentile(
            [x[1] - x[0] for x in spans if stats.in_window(x[0], rec.traced_window)], 50))
        notes.update(traced_decode_ms_p50=under(rec.decodes), traced_prefill_ms_p50=under(rec.prefills),
                     session_counters=rec.session.counters, session_clock_offset_ns=rec.session.clock_offset_ns)
    return rec, correct, attempted, failed, notes
