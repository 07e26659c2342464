"""The client side of a run: it offers the traffic to the engine and
stamps what comes back.

The engine is driven through ``submit()`` and ``step()`` only.  Each
request is timed from when it was due, not from when ``submit()`` ran:
a request that fell due while a step was running waits for that step,
and that wait is part of what the user sees.  A token is stamped when
the ``step()`` that produced it returns.  Every host call the loop makes
into the program, and every wait, sits in a ``TraceAnnotation`` span
(``client.submit``, ``engine.step``, ``client.wait``), so a traced run
can say what the host was doing in each gap of the device.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

from benchlib.traffic import Request, Traffic


@dataclasses.dataclass(eq=False)
class Record:
    req: Request
    due: float                        # perf_counter time it was due
    rid: int = -1
    t_submit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    outcome: Optional[str] = None
    hit: int = 0                      # prompt tokens the prefix cache held
    chunk: int = 0                    # the engine's prefill chunk
    prog: Optional[dict] = None       # the program's own stamps


class Client:
    """Submits requests, steps the engine, stamps tokens."""

    def __init__(self, eng, busy: Callable, *, annotate=None,
                 clock=time.perf_counter, on_step=None):
        self.eng = eng
        self.busy = busy
        self.clock = clock
        self.by_rid = {}
        self.records: List[Record] = []
        self.on_step = on_step
        # seconds the loop was held by something that is not the system
        # under test (a traced run's profiler starting and stopping);
        # an open loop's schedule is pushed back by as much
        self.held_s = 0.0
        self._span = annotate or (lambda name: contextlib.nullcontext())

    def submit(self, rec: Record) -> Record:
        with self._span("client.submit"):
            rec.rid = self.eng.submit(rec.req.prompt,
                                      max_new_tokens=rec.req.max_new,
                                      temperature=0.0)
        rec.t_submit = self.clock()
        self.by_rid[rec.rid] = rec
        self.records.append(rec)
        return rec

    def step(self) -> List[Record]:
        """One engine step; returns the records that completed in it."""
        if self.on_step is not None:
            self.on_step("before")
        with self._span("engine.step"):
            emitted = self.eng.step()
        now = self.clock()
        if self.on_step is not None:
            self.on_step("after")
        touched = {}
        for rid, tok in emitted:
            rec = self.by_rid.get(rid)
            if rec is None:
                continue
            if rec.t_first is None:
                rec.t_first = now
            rec.stamps.append(now)
            rec.tokens.append(int(tok))
            touched[rid] = rec
        done = []
        for rid, rec in touched.items():
            r = self.eng.registry[rid]
            if r.done and rec.t_done is None:
                rec.t_done = now
                rec.outcome = r.outcome
                done.append(rec)
        return done

    def wait_until(self, t: float) -> None:
        with self._span("client.wait"):
            while True:
                dt = t - self.clock()
                if dt <= 0:
                    return
                time.sleep(min(dt, 0.002))

    def pending(self, records) -> bool:
        return any(r.t_done is None for r in records)


@dataclasses.dataclass
class Window:
    t0: float                         # window opens
    t_close: float                    # the last step started in it ended
    t_end: float                      # the drain ended
    records: List[Record]             # requests due in the window
    lateness: List[float]             # submit time - due time
    closed_loop: bool


def run_open(client: Client, traffic: Traffic, seconds: float,
             on_open=None, on_close=None) -> Window:
    """Offer ``traffic.requests`` at their due times for ``seconds``,
    then drain the requests already due for up to ``traffic.drain_s``.
    Time the loop is held (``client.held_s``, 0 but in a traced run)
    pushes every later due time, and the window's end, back by as much,
    so that a hold leaves no backlog behind it."""
    clock = client.clock
    pending = collections.deque(traffic.requests)
    t0 = clock()
    if on_open is not None:
        on_open(t0)
    due_recs: List[Record] = []

    def at(due):
        return t0 + client.held_s + due

    def submit_due(now):
        while pending and at(pending[0].due) <= now \
                and pending[0].due < seconds:
            r = pending.popleft()
            due_recs.append(client.submit(Record(r, at(r.due))))

    while True:
        now = clock()
        if now >= at(seconds):
            break
        submit_due(now)
        if client.busy(client.eng):
            client.step()
        else:
            nxt = at(pending[0].due) if pending else at(seconds)
            client.wait_until(min(nxt, at(seconds)))
    submit_due(clock())               # fell due during the last step
    t_close = clock()
    if on_close is not None:
        on_close()
    while client.pending(due_recs) and clock() < t_close + traffic.drain_s:
        client.step()
    return Window(t0, t_close, clock(), due_recs,
                  [r.t_submit - r.due for r in due_recs], False)


class ClosedLoop:
    """One client per slot; each sends its next request as soon as its
    previous one completes."""

    def __init__(self, client: Client, traffic: Traffic):
        self.client = client
        self.traffic = traffic
        self.backlog = collections.deque(traffic.requests)
        self.records: List[Record] = []
        self.lateness: List[float] = []

    def _step(self) -> None:
        for done in self.client.step():
            if self.backlog:
                rec = self.client.submit(
                    Record(self.backlog.popleft(), done.t_done))
                self.lateness.append(rec.t_submit - rec.due)
                self.records.append(rec)

    def start(self) -> None:
        """Set-up: submit the first wave and step until every request in
        flight has its first token."""
        now = self.client.clock()
        self.records = [self.client.submit(Record(r, now))
                        for r in self.traffic.first_wave]
        while any(r.t_first is None for r in self.records):
            self._step()
        self.records = [r for r in self.records if r.t_done is None]
        self.lateness = []

    def run(self, seconds: float, on_open=None, on_close=None) -> Window:
        """The window: ``seconds`` of the loop.  Requests still running
        at the close are not drained (see ``drain_s``)."""
        clock = self.client.clock
        t0 = clock()
        if on_open is not None:
            on_open(t0)
        while clock() < t0 + seconds:
            self._step()
        t_close = clock()
        if on_close is not None:
            on_close()
        while self.client.pending(self.records) \
                and clock() < t_close + self.traffic.drain_s:
            self._step()
        return Window(t0, t_close, clock(), self.records, self.lateness,
                      True)


# ---------------------------------------------------------------------------
# end-to-end metrics


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), ``q`` in 0..100."""
    return float(np.percentile(np.asarray(xs, float), q))


def ttft_s(w: Window) -> list:
    """Due time to first token of every request due in the window; a
    request that never got one counts up to the end of the run."""
    return [(r.t_first if r.t_first is not None else w.t_end) - r.due
            for r in w.records]


def tpot_s(w: Window) -> list:
    """Each request's mean gap between the output tokens it received in
    the window, after the first of them (requests still running at the
    close included)."""
    out = []
    for r in w.records:
        s = [t for t in r.stamps if w.t0 <= t <= w.t_close]
        if len(s) >= 2:
            out.append((s[-1] - s[0]) / (len(s) - 1))
    return out


def window_tokens(w: Window) -> int:
    return sum(1 for r in w.records for t in r.stamps
               if w.t0 <= t <= w.t_close)


def outcome_counts(w: Window) -> tuple:
    """(attempted, failed).  Open loop: a request not done by the end of
    the drain, or done with another outcome than ``ok`` or with another
    number of tokens than it asked for, failed.  Closed loop: a request
    still running at the close has not failed; one that ended has failed
    if it ended otherwise than as asked."""
    failed = 0
    for r in w.records:
        if r.t_done is None:
            failed += not w.closed_loop
        elif r.outcome != "ok" or len(r.tokens) != r.req.max_new:
            failed += 1
    return len(w.records), failed


def end_to_end(w: Window) -> dict:
    """Every end-to-end quantity the client can give, unrounded."""
    ttft = ttft_s(w)
    tpot = tpot_s(w)
    out = {"output_tokens_per_s": window_tokens(w) / (w.t_close - w.t0)}
    if ttft and not w.closed_loop:
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if tpot:
        out["tpot_p95_ms"] = 1e3 * percentile(tpot, 95)
    return out
