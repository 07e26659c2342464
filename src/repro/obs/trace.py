"""Request-lifecycle tracing in Chrome/Perfetto trace-event JSON, and
the engines' program spans.

A :class:`Tracer` records two kinds of tracks:

* **pid 0 — "engine"**: one complete ("X") event per program span
  (:func:`span`: ``sched.step`` and the host phases inside it, among
  them ``engine.decode.launch`` / ``engine.decode.wait``, which together
  cover one fused decode dispatch and its sync) and per ``spec_round``,
  so the engine's duty cycle and host phases are visible at a glance,
  plus counter ("C") tracks sampling queue depth, live slots and
  page-pool occupancy at the block boundaries;
* **pid 1 — "requests"**: one thread (tid = request id) per request,
  carrying its lifecycle spans — ``request`` (submit → retire) encloses
  ``queue`` (submit → admit, re-opened after a preemption: the readmit
  wait), then per-dispatch ``prefill_chunk`` / ``decode_block`` /
  ``spec_round`` complete events whose args carry tokens / pages /
  policy labels, plus ``preempt`` instant markers.  The closing
  ``request`` span's args carry the request's terminal ``outcome``
  (``ok | shed | timed_out | failed`` — ``repro.resil``), and resilient
  engines add ``fault`` instants on the engine track (an injected or
  real transient dispatch error, with its kind) and ``cancel`` instants
  on the request track (deadline expiry / retries exhausted).

Every timestamp is a host ``time.perf_counter()`` the engines already
take for their existing latency accounting — tracing never adds a
device sync (the ``sync_count`` audit is unchanged with tracing on).
A disabled tracer (the default) is a no-op on every call.

``write()`` emits ``{"traceEvents": [...]}`` JSON that loads directly
in https://ui.perfetto.dev or ``chrome://tracing``; a whole Poisson
drive becomes one scrollable timeline.

A program span (:func:`span`) times one host phase of an engine tick
three ways at once: as a ``jax.profiler.TraceAnnotation``, so a running
profile records it on the device trace's clock; as seconds and a count
in the engine's registry (``serve_span_seconds_total{span=}``,
``serve_spans_total{span=}``), always on; and as an engine-track event
when the tracer is enabled.  It reads only the host clock.
"""
from __future__ import annotations

import json
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs.metrics import series_key

PID_ENGINE = 0
PID_REQUESTS = 1

SPAN_SECONDS = "serve_span_seconds_total"
SPAN_COUNT = "serve_spans_total"
SPAN_SECONDS_HELP = "host seconds inside each program span"
SPAN_COUNT_HELP = "program spans closed"
_SPAN_KEYS: dict = {}           # span name -> its two series keys


class _Span:
    """One program span (see :func:`span`).  ``t0``/``t1`` are the host
    clock readings at entry and exit, for callers that account the same
    interval elsewhere; ``args`` set inside the span ride the tracer's
    event."""
    __slots__ = ("name", "metrics", "tracer", "args", "t0", "t1", "_ann")

    def __init__(self, name: str, metrics, tracer):
        self.name = name
        self.metrics = metrics
        self.tracer = tracer
        self.args = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        keys = _SPAN_KEYS.get(self.name)
        if keys is None:
            keys = _SPAN_KEYS[self.name] = (
                series_key(SPAN_SECONDS, {"span": self.name}),
                series_key(SPAN_COUNT, {"span": self.name}))
        m = self.metrics
        m.counter(SPAN_SECONDS, SPAN_SECONDS_HELP).inc_series(
            keys[0], self.t1 - self.t0)
        m.counter(SPAN_COUNT, SPAN_COUNT_HELP).inc_series(keys[1])
        if self.tracer.enabled:
            self.tracer.complete(self.name, 0, self.t0, self.t1,
                                 pid=PID_ENGINE, args=self.args)
        return False


def span(name: str, metrics, tracer: Tracer) -> _Span:
    """Context manager timing one host phase of an engine as a program
    span, into ``metrics`` (the engine's ``MetricsRegistry``) and, when
    enabled, ``tracer`` (see the module docstring).  Spans of one engine
    nest or follow each other; they never partly overlap."""
    return _Span(name, metrics, tracer)


class Tracer:
    """Chrome trace-event recorder (see module docstring)."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.events: list = []
        self._t0 = time.perf_counter()
        self._named_tids: set = set()
        if enabled:
            for pid, name in ((PID_ENGINE, "engine"),
                              (PID_REQUESTS, "requests")):
                self.events.append({"ph": "M", "name": "process_name",
                                    "pid": pid, "tid": 0,
                                    "args": {"name": name}})

    # ------------------------------------------------------------------
    def _us(self, t_s: Optional[float]) -> float:
        """Host seconds (perf_counter domain) -> trace microseconds."""
        t = time.perf_counter() if t_s is None else t_s
        return (t - self._t0) * 1e6

    def name_thread(self, tid: int, name: str,
                    pid: int = PID_REQUESTS) -> None:
        if not self.enabled or (pid, tid) in self._named_tids:
            return
        self._named_tids.add((pid, tid))
        self.events.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": name}})

    def begin(self, name: str, tid: int, *, pid: int = PID_REQUESTS,
              ts: Optional[float] = None, args: Optional[dict] = None):
        """Open a nesting span ("B"); close with :meth:`end`."""
        if not self.enabled:
            return
        ev = {"ph": "B", "name": name, "pid": pid, "tid": tid,
              "ts": self._us(ts)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def end(self, name: str, tid: int, *, pid: int = PID_REQUESTS,
            ts: Optional[float] = None, args: Optional[dict] = None):
        if not self.enabled:
            return
        ev = {"ph": "E", "name": name, "pid": pid, "tid": tid,
              "ts": self._us(ts)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def complete(self, name: str, tid: int, t0_s: float, t1_s: float, *,
                 pid: int = PID_REQUESTS, args: Optional[dict] = None):
        """Record a closed span ("X") from host timestamps already
        taken (the per-dispatch t0/t1 the engines measure anyway)."""
        if not self.enabled:
            return
        ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
              "ts": self._us(t0_s),
              "dur": max((t1_s - t0_s) * 1e6, 0.0)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, tid: int, *, pid: int = PID_REQUESTS,
                ts: Optional[float] = None, args: Optional[dict] = None):
        if not self.enabled:
            return
        ev = {"ph": "i", "name": name, "pid": pid, "tid": tid,
              "ts": self._us(ts), "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, values: dict, *, pid: int = PID_ENGINE,
                tid: int = 0, ts: Optional[float] = None):
        """Perfetto counter track ("C"): one sampled value per series in
        ``values``.  Engines emit these at block boundaries (queue depth,
        live slots, page-pool occupancy) from host state they already
        hold, so utilization timelines render alongside the spans at
        zero added syncs."""
        if not self.enabled:
            return
        self.events.append({"ph": "C", "name": name, "pid": pid,
                            "tid": tid, "ts": self._us(ts),
                            "args": {k: float(v) for k, v in values.items()}})

    # ------------------------------------------------------------------
    def to_json(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)


def request_span_trees(trace: dict) -> dict:
    """Rebuild each request track's span tree from a trace-event dict
    (the shape :meth:`Tracer.to_json` writes).  Returns ``{rid:
    {"complete": bool, "spans": [...], "stack_ok": bool}}`` where
    ``spans`` is every closed span on the track as ``(name, t0_us,
    t1_us, args)`` — the test/CI helper for span invariants; raises on
    malformed B/E nesting only via ``stack_ok=False`` so callers can
    assert with context."""
    tracks: dict = {}
    for ev in trace["traceEvents"]:
        if ev.get("pid") != PID_REQUESTS or ev.get("ph") == "M":
            continue
        tracks.setdefault(ev["tid"], []).append(ev)
    out = {}
    for tid, evs in tracks.items():
        evs.sort(key=lambda e: e["ts"])
        stack, spans, ok = [], [], True
        for ev in evs:
            if ev["ph"] == "B":
                stack.append(ev)
            elif ev["ph"] == "E":
                if not stack or stack[-1]["name"] != ev["name"]:
                    ok = False
                    continue
                b = stack.pop()
                spans.append((b["name"], b["ts"], ev["ts"],
                              {**b.get("args", {}), **ev.get("args", {})}))
            elif ev["ph"] == "X":
                spans.append((ev["name"], ev["ts"],
                              ev["ts"] + ev.get("dur", 0.0),
                              ev.get("args", {})))
        out[tid] = {"complete": ok and not stack
                    and any(s[0] == "request" for s in spans),
                    "spans": spans, "stack_ok": ok and not stack}
    return out
