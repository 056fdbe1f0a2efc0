"""What one run leaves for the metric readers: raw host samples on one clock
(``time.perf_counter``), the compile events, and the reduced device trace.
End-to-end metrics and every ``layer_metrics`` reader compute from this."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from . import stats


@dataclasses.dataclass
class RequestRecord:
    rid: int
    due: float                      # the instant the schedule says it was due
    sent: float                     # when the generator pushed it
    prompt_len: int
    max_new_tokens: int
    admitted: Optional[float] = None    # its own prefill began
    token_times: List[float] = dataclasses.field(default_factory=list)
    status: Optional[str] = None        # the scheduler's terminal outcome


@dataclasses.dataclass
class RunRecord:
    kind: str                       # "train" | "serve"
    chips: int
    traffic_kind: str = ""          # the traffic file's kind
    window: Tuple[float, float] = (0.0, 0.0)
    setup_s: float = 0.0
    device_kind: str = ""
    peak_flops_per_chip: Optional[float] = None
    memory_peak_bytes: int = 0
    compile_times: List[float] = dataclasses.field(default_factory=list)  # instants of backend compiles
    trace: Optional[Dict[str, Any]] = None      # benchmark.xplane.summarize(), traced runs only
    # what the kernel counted over the measured window for the thread that drives the chip and for the
    # machine (vescale_tpu.telemetry.host_sched_delta of two reads, at the window's two ends); every mode
    host_sched: Optional[Dict[str, Any]] = None
    # ---- --trace 2: the seconds traced after the window
    session: Any = None             # what the program's stop_trace_session returned: .xplane_path, .spans,
                                    # .counters, .clock_offset_ns / .to_trace_ns(), .profile (the loaded trace)
    traced_window: Optional[Tuple[float, float]] = None   # the session's start and stop on this record's clock
    memory_peak_bytes_run: int = 0  # the peak at the run's end (memory_peak_bytes is read as the window closes)
    traced_steps: List[Tuple[float, float, float, float]] = dataclasses.field(default_factory=list)  # train: t0,
                                    # batch ready, step call returned, step done, of the steps under the session
    # ---- train
    tokens_per_step: int = 0
    flops_per_token: float = 0.0
    step_end: List[float] = dataclasses.field(default_factory=list)
    step_s: List[float] = dataclasses.field(default_factory=list)
    data_wait_s: List[float] = dataclasses.field(default_factory=list)
    dispatch_s: List[float] = dataclasses.field(default_factory=list)   # inside the step's call, before it returned
    losses: List[float] = dataclasses.field(default_factory=list)
    # ---- serve
    slots: int = 0
    padded_prompt_len: int = 0
    requests: Dict[int, RequestRecord] = dataclasses.field(default_factory=dict)
    prefills: List[Tuple[float, float, int, int]] = dataclasses.field(default_factory=list)  # t0, t1, rid, prompt tokens
    decodes: List[Tuple[float, float, int]] = dataclasses.field(default_factory=list)        # t0, t1, active slots
    loop_steps: List[Tuple[float, int, int]] = dataclasses.field(default_factory=list)       # end of iteration, active, queued

    # -- helpers the readers share
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def train_tokens_per_s(self) -> Optional[float]:
        """Tokens of the window's steps over the time from its opening to the
        end of its last step (all chips together); the window closes there."""
        rate = stats.steps_per_s(self.step_end, self.window[0])
        return None if rate is None else rate * self.tokens_per_step

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compile_times if stats.in_window(t, self.window))

    def token_times(self) -> List[List[float]]:
        return [r.token_times for r in self.requests.values()]

    def request_rows(self) -> List[Dict[str, Any]]:
        return [{"due": r.due, "token_times": r.token_times, "status": r.status} for r in self.requests.values()]

    def in_window(self, spans):
        """The spans (tuples that start with t0, t1) that ended inside the window."""
        return [s for s in spans if stats.in_window(s[1], self.window)]
