"""Hang watchdog — a per-host heartbeat with a step-progress deadline.

At multi-host scale the dominant production failure is not a crash but a
HANG: one rank stuck in a collective (dead peer, wedged DMA, deadlocked
host thread) stalls every healthy rank forever, burning the whole
allocation while producing zero signal (arXiv:1811.02084-scale jobs make
this a daily event).  A crash restarts; a hang needs something on the host
that notices the training loop stopped making progress and turns the
silence into a diagnosable artifact.

``Watchdog`` runs one daemon thread per process.  The training loop calls
``beat(step)`` at every step boundary; if no beat lands within
``timeout_s`` the watchdog fires:

  1. dumps every thread's Python stack (``sys._current_frames``) plus the
     memory flight-recorder bundle (telemetry/memtrack.py) to
     ``watchdog_hang_*.json`` — the forensic record of WHERE each thread
     was stuck;
  2. emits ``resilience_hang_detected_total`` / a ``resilience_hang``
     event line so dashboards see the stall;
  3. optionally aborts the process (``os._exit(exit_code)``) so the
     external supervisor's restart path takes over — the only way out of
     a wedged collective, since no Python-level unwind can cancel it.

Pairs with ``distributed.barrier(timeout_s=...)``: the barrier timeout
diagnoses a dead peer at an explicit sync point; the watchdog catches
everything else (hangs inside compiled steps, storage stalls, deadlocks).

Env knobs (read by ``run_resilient`` when arming from the environment):

  VESCALE_WATCHDOG_TIMEOUT    step-progress deadline in seconds (unset/0:
                              watchdog disarmed)
  VESCALE_WATCHDOG_ABORT      "1" (default): abort the process on hang
  VESCALE_WATCHDOG_EXIT_CODE  process exit code on abort (default 17 —
                              distinguishable from crash/OOM codes so the
                              supervisor can count hangs separately)

Quiescent cost: one ``time.monotonic()`` + two attribute writes per
``beat`` and a sleeping thread; the armed-but-quiescent per-step
overhead is not measured on the chip.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

__all__ = ["Watchdog", "dump_all_stacks", "DEFAULT_EXIT_CODE"]

DEFAULT_EXIT_CODE = 17


def dump_all_stacks() -> Dict[str, List[str]]:
    """Every live thread's Python stack, keyed by ``name (tid=...)`` —
    the core of the hang forensic bundle.  Pure-read: safe to call from
    the watchdog thread while the main thread is wedged."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    out: Dict[str, List[str]] = {}
    for ident, frame in frames.items():
        label = f"{names.get(ident, 'unknown')} (tid={ident})"
        out[label] = traceback.format_stack(frame)
    return out


class Watchdog:
    """Per-host heartbeat thread with a step-progress deadline.

        wd = Watchdog(timeout_s=300, abort=True).start()
        for step in ...:
            wd.beat(step)
            ...
        wd.stop()

    ``beat`` re-arms the deadline; a beat-free window longer than
    ``timeout_s`` triggers the hang dump (once per stall — a later beat
    re-arms detection).  ``on_hang(bundle)`` runs before any abort, so
    tests and orchestrators can observe the firing without dying."""

    def __init__(
        self,
        timeout_s: float,
        poll_s: Optional[float] = None,
        abort: bool = True,
        exit_code: int = DEFAULT_EXIT_CODE,
        dump_dir: Optional[str] = None,
        on_hang: Optional[Callable[[Dict[str, Any]], None]] = None,
    ):
        if timeout_s <= 0:
            raise ValueError("watchdog timeout_s must be positive")
        self.timeout_s = float(timeout_s)
        self.poll_s = float(poll_s) if poll_s else min(1.0, self.timeout_s / 4.0)
        self.abort = bool(abort)
        self.exit_code = int(exit_code)
        self.dump_dir = dump_dir
        self.on_hang = on_hang
        self.fired = 0  # stalls detected (tests read this)
        self.last_bundle: Optional[Dict[str, Any]] = None
        self._last_beat = time.monotonic()
        self._step: Optional[int] = None
        self._phase = "startup"
        self._tripped = False  # one dump per stall
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "Watchdog":
        if self._thread is not None:
            return self
        self.beat(self._step, phase="startup")
        self._thread = threading.Thread(
            target=self._run, name="vescale-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2 * self.poll_s)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------ heartbeat
    def beat(self, step: Optional[int] = None, phase: str = "step") -> None:
        """Record progress: the deadline restarts now.  Cheap enough for
        every step boundary (no locks — monotonic float + attribute
        writes; the watchdog thread reads a slightly-stale view at worst,
        which only ever DELAYS a firing by one poll)."""
        if step is not None:
            self._step = int(step)
        self._phase = phase
        # _last_beat BEFORE _tripped: the reverse order opens a window
        # where the watchdog thread sees the trip latch cleared while the
        # stale timestamp still reads as a stall — a duplicate dump (or
        # abort) for a stall that just ended
        self._last_beat = time.monotonic()
        if self._tripped:
            # progress after a trip: the stall episode is over — resolve
            # the alert (no-op while the engine is dormant)
            from ..telemetry import alerts as _alerts

            _alerts.resolve("watchdog-stall")
        self._tripped = False

    @property
    def stalled_s(self) -> float:
        return time.monotonic() - self._last_beat

    # ------------------------------------------------------------- firing
    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            if self._tripped:
                continue  # already dumped this stall; wait for a beat
            if self.stalled_s > self.timeout_s:
                self._tripped = True
                self._trigger()

    def _trigger(self) -> None:
        self.fired += 1
        bundle: Dict[str, Any] = {
            "reason": "hang",
            "step": self._step,
            "phase": self._phase,
            "stalled_s": round(self.stalled_s, 3),
            "timeout_s": self.timeout_s,
            "pid": os.getpid(),
            "ts": time.time(),
            "threads": dump_all_stacks(),
        }
        from .. import telemetry as _tel
        from ..telemetry import memtrack as _memtrack

        try:  # the flight recorder rides along when memtrack is live
            mem = _memtrack.dump_now(reason=f"watchdog_hang@step{self._step}")
            if mem is not None:
                bundle["flight_record"] = mem.get("path", "<in-memory>")
        except Exception:
            pass  # diagnostics must never mask the hang handling itself
        path = self._dump_path()
        if path is not None:
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path, "w") as f:
                    json.dump(bundle, f, indent=2, default=str)
                bundle["path"] = path
            except OSError:
                path = None
        _tel.count("resilience_hang_detected_total")
        _tel.record_event(
            "resilience_hang",
            at_step=self._step,
            phase=self._phase,
            stalled_s=bundle["stalled_s"],
            dump=path,
            abort=self.abort,
        )
        # the stall watcher routes through the alert engine (one lifecycle,
        # /alerts visibility, ALERT timeline span); the stderr print below
        # stays — an aborting process must leave SOMETHING on the console
        from ..telemetry import alerts as _alerts

        _alerts.raise_alert(
            "watchdog-stall",
            message=(
                f"no step progress for {bundle['stalled_s']:.1f}s (deadline "
                f"{self.timeout_s:g}s) at step={self._step} "
                f"phase={self._phase}; stacks -> {path or '<not written>'}"
            ),
            severity="critical",
            value=bundle["stalled_s"],
        )
        print(
            f"[watchdog] no step progress for {bundle['stalled_s']:.1f}s "
            f"(deadline {self.timeout_s:g}s) at step={self._step} "
            f"phase={self._phase}; stacks -> {path or '<not written>'}"
            + ("; aborting" if self.abort else ""),
            file=sys.stderr,
        )
        for label, stack in bundle["threads"].items():
            print(f"[watchdog] --- {label} ---\n{''.join(stack)}", file=sys.stderr)
        self.last_bundle = bundle
        if self.on_hang is not None:
            try:
                self.on_hang(bundle)
            except Exception:
                pass
        if self.abort:
            _tel.count("resilience_hang_aborts_total")
            sys.stderr.flush()
            sys.stdout.flush()
            # os._exit, not sys.exit: the main thread is wedged in a
            # collective no exception can unwind — this is the restart
            # path's entry point, not an error to handle
            os._exit(self.exit_code)

    def _dump_path(self) -> Optional[str]:
        if self.dump_dir is not None:
            root: Optional[str] = self.dump_dir
        else:
            from ..telemetry import api as _api

            st = _api.get_state()
            root = st.out_dir if st is not None else None
            if root is None:
                from ..analysis import envreg

                root = envreg.get_str("VESCALE_WATCHDOG_DIR")
        if root is None:
            return None
        from .faultsim import _process_rank

        # rank-qualified: in a multi-host run every rank's watchdog dumps
        # into the same shared dir and each rank's stacks matter (the hung
        # rank shows WHERE it wedged; the healthy ranks show the collective
        # they were blocked in)
        return os.path.join(
            root, f"watchdog_hang_rank{_process_rank()}_step{self._step}_{self.fired}.json"
        )

    # --------------------------------------------------------- env arming
    @classmethod
    def from_env(
        cls, dump_dir: Optional[str] = None, timeout_s: Optional[float] = None
    ) -> Optional["Watchdog"]:
        """A Watchdog per VESCALE_WATCHDOG_* (module docstring); None when
        the deadline is unset/<=0.  ``timeout_s`` overrides the env
        deadline (an explicit 0 disables even with the env set) while
        abort/exit-code still come from the env — the single parser both
        direct callers and ``run_resilient`` share."""
        from ..analysis import envreg

        if timeout_s is None:
            timeout_s = envreg.get_float("VESCALE_WATCHDOG_TIMEOUT")
        if timeout_s is None or timeout_s <= 0:
            return None
        return cls(
            timeout_s=float(timeout_s),
            abort=envreg.get_bool("VESCALE_WATCHDOG_ABORT"),
            exit_code=envreg.get_int("VESCALE_WATCHDOG_EXIT_CODE"),
            dump_dir=dump_dir,
        )
