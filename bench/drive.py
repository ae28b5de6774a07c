"""What every loop (``bench/loops/<kind>.py``) drives the served path
with: ``QueryService.submit(..., stream=True)`` and ``QueryService.step()``,
from one thread, and the records the metrics read.

Every ticket is timed on the client's clock (``time.perf_counter``): from
its due instant in an open loop, from its submission in a closed one, to
its first streamed partial and to its final.  Each call into the service
is recorded as a span named ``submit``, ``step``, ``await_arrival`` or
``drain`` on the same clock, with the wall-clock instant of the window's
start, so that a traced run can place the spans on the profiler's clock
and say what the host was doing in each gap of the device.  The spans
are the harness's own and not the profiler's host trace: that trace also
records the runtime's host transposes, thousands of events per chunk.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

from bench import traffic as traffic_lib

#: Host span names, as :meth:`Client.span` records them.
SPANS = ("submit", "step", "await_arrival", "drain")


@dataclasses.dataclass
class TicketRecord:
    """One submission, as the client saw it (seconds from window start)."""
    ticket: int
    query: traffic_lib.Query
    t_start: float                  # due (open loop) or submitted (closed)
    t_submit: float
    t_first: Optional[float] = None
    t_final: Optional[float] = None
    status: str = ""
    batch_id: int = -1


@dataclasses.dataclass
class StepRecord:
    """One dispatch window: its span, the tickets it finished and the
    service counters' deltas."""
    tickets: List[int]
    span: str
    t_start: float
    t_end: float
    jobs_run: int
    batches: int
    events_scanned: int


@dataclasses.dataclass
class WindowRecord:
    """What a measured window left for the metrics to read."""
    loop: str
    seconds: float
    tickets: List[TicketRecord]
    steps: List[StepRecord]
    t_end: float                    # last final, seconds from window start
    compiles: int = 0               # JAX compile events inside the window
    late_s: float = 0.0             # open loop: most the generator ran late
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)       # (name, start, end), from window start
    origin_ns: int = 0              # wall clock (time.time_ns) at start

    def finals(self) -> List[TicketRecord]:
        return [t for t in self.tickets if t.t_final is not None]


class CompileCounter:
    """JAX's own compile events (``/jax/core/compile/*``) and their time."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class GcClock:
    """Seconds the interpreter's cyclic garbage collector paused the run,
    and its longest pause, while it is installed."""

    def __init__(self):
        import gc
        self.seconds = self.longest = 0.0
        self._t = None
        self._gc = gc
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            pause = time.perf_counter() - self._t
            self.seconds += pause
            self.longest = max(self.longest, pause)
            self._t = None

    def close(self) -> None:
        self._gc.callbacks.remove(self._on)


class Client:
    """Submits tickets and notes when their partials arrive."""

    def __init__(self, svc, calib_iters: int, t0: float):
        self.svc = svc
        self.calib = calib_iters
        self.t0 = t0
        self.origin_ns = time.time_ns() - round(
            (time.perf_counter() - t0) * 1e9)
        self.records: Dict[int, TicketRecord] = {}
        self.finished: List[int] = []
        self.spans: List[Tuple[str, float, float]] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    @contextlib.contextmanager
    def span(self, name: str):
        """Records the time spent in the block as a span ``name``."""
        t = self.now()
        try:
            yield
        finally:
            self.spans.append((name, t, self.now()))

    def submit(self, q: traffic_lib.Query, t_start: float) -> TicketRecord:
        tid = self.svc.submit(q.expr, tenant=q.tenant,
                              calib_iters=self.calib, stream=True)
        rec = TicketRecord(tid, q, t_start, self.now())
        self.records[tid] = rec
        stream = self.svc.stream(tid)

        def on_snapshot(snap, rec=rec):
            if rec.t_first is None:
                rec.t_first = self.now()
            if snap.final:
                rec.t_final = self.now()
                self.finished.append(rec.ticket)

        stream.subscribe(on_snapshot)
        if stream.closed and stream.done and rec.t_final is None:
            # answered at the door (a cache hit publishes before subscribe)
            rec.t_first = rec.t_final = self.now()
            self.finished.append(tid)
        return rec


def step(svc, client: Client, span: str, steps: List[StepRecord]) -> None:
    s = svc.stats
    before = (s.jobs_run, s.batches, s.events_scanned, len(client.finished))
    t = client.now()
    with client.span(span):
        svc.step()
    steps.append(StepRecord(list(client.finished[before[3]:]), span, t,
                            client.now(), s.jobs_run - before[0],
                            s.batches - before[1],
                            s.events_scanned - before[2]))


def record(svc, loop: str, seconds: float, client: Client,
           steps: List[StepRecord], compiles: int, late: float
           ) -> WindowRecord:
    """The window's record, each ticket's final status read back."""
    recs = sorted(client.records.values(), key=lambda r: r.ticket)
    for r in recs:
        t = svc.result(r.ticket)
        r.status, r.batch_id = t.status, t.batch_id
    finals = [r.t_final for r in recs if r.t_final is not None]
    return WindowRecord(loop, seconds, recs, steps,
                        max(finals) if finals else client.now(),
                        compiles, late, client.spans, client.origin_ns)
