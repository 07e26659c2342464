"""Device trace of a traced run, and its reduction to numbers.

``capture`` reads the profiler's ``.xplane.pb`` into a plain record:
the device's operations (the ``XLA Ops`` line of each ``/device:TPU:n``
plane) and programs (``XLA Modules``), and the harness's own host spans
(``TraceAnnotation``), all on the profiler's one clock in nanoseconds.
``reduce`` turns the record into the device's busy time (the union of
the intervals in which a leaf operation ran, clipped to the traced
window), the device time of each
kernel found by name, the operations that took most time, and the
longest idle gaps labelled by the harness span the host was in.
"""
from __future__ import annotations

import bisect
import glob
import re
import time

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("client.wait", "client.submit", "engine.step")
# control flow whose events enclose other operations' events
_ENCLOSING = re.compile(r"^(while|conditional|call)(\.\d+)?$")


class Recorder:
    """Profiles one stretch of the window: from the first step that
    starts after ``start_at`` to the first that ends after ``stop_at``,
    inside a ``WINDOW_SPAN`` annotation (set both before the window)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.start_at = self.stop_at = None
        self.t0 = self.t1 = None
        self._span = None

    def before_step(self, now: float) -> float:
        """Starts the profiler once ``start_at`` has passed; returns the
        seconds that held the caller (0 where nothing was done)."""
        if self.t0 is None and self.start_at is not None \
                and now >= self.start_at:
            import jax
            jax.profiler.start_trace(self.log_dir)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self.t0 = time.perf_counter()
            return self.t0 - now
        return 0.0

    def after_step(self, now: float) -> float:
        """Stops the profiler once ``stop_at`` has passed; returns the
        seconds that held the caller."""
        if self.stop_at is not None and now >= self.stop_at:
            return self.stop()
        return 0.0

    def stop(self) -> float:
        if self.t0 is not None and self.t1 is None:
            import jax
            self.t1 = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            return time.perf_counter() - self.t1
        return 0.0


def capture(log_dir: str) -> dict:
    """The plain record of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    pb = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(pb) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {pb}")
    pd = ProfileData.from_file(pb[0])
    rec = {"devices": {}, "host_spans": []}
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name.split(" = ")[0].lstrip("%")
                    dev[key].append([name, e.start_ns, e.duration_ns])
            rec["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        rec["host_spans"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return rec


def _window(rec: dict) -> tuple:
    spans = [s for s in rec["host_spans"] if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, got "
                           f"{len(spans)}")
    return spans[0][1], spans[0][1] + spans[0][2]


def _union(intervals, lo, hi) -> list:
    out = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _in_window(events, lo, hi):
    """The events that overlap ``[lo, hi)``, clipped to it."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([name, a, b - a])
    return out


def reduce(rec: dict, kernels: dict):
    """Numbers of a trace record, or None when it holds no device.
    ``kernels`` maps a kernel's name to the regular expression its
    operations' names match.  Times are in seconds and averaged over the
    devices."""
    ndev = len(rec["devices"])
    if not ndev:
        return None
    lo, hi = _window(rec)
    busy = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    per_op: dict = {}
    gaps = []
    spans = sorted(s for s in rec["host_spans"] if s[0] in HOST_SPANS)
    for dev in rec["devices"].values():
        # leaf operations only: a loop's or a branch's event spans its
        # body's operations and the gaps between them
        ops = [o for o in _in_window(dev["ops"], lo, hi)
               if not _ENCLOSING.match(o[0])]
        u = _union(ops, lo, hi)
        busy += sum(e - s for s, e in u) / 1e9
        for name, _, d in ops:
            for k, pat in kernels.items():
                if re.match(pat, name):
                    kernel_s[k] += d / 1e9
                    kernel_n[k] += 1
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, d in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0].split("(")[0] if i >= 0 \
                and s < mods[i][1] + mods[i][2] else "?"
            key = f"{mod}/{name}"
            per_op[key] = per_op.get(key, 0.0) + d / 1e9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                 if e > s]
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy / ndev,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": {k: v / ndev for k, v in kernel_s.items()},
        "kernel_calls": {k: v // ndev for k, v in kernel_n.items()},
        "device_ops": [[k, v / ndev] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_label(spans, s, e), g / 1e9]
                      for g, s, e in gaps[:10]],
    }


def _label(spans, s, e) -> str:
    """The harness span that overlaps the gap ``[s, e)`` most."""
    best, label = 0, "none"
    for name, st, d in spans:
        ov = min(e, st + d) - max(s, st)
        if ov > best:
            best, label = ov, name
    return label
