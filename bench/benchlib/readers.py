"""Per-layer metric readers: one file each, ``bench/metrics/<name>.py``,
found by the metric's name in ``BENCHMARK.json``.

A reader defines ``read(ctx) -> float | None``.  It returns None when
the run gave it nothing to read (no kernel of its name in the trace, no
request admitted in the window, ...); the harness then leaves the metric
out of the result line.

A metric named as another plus a dotted suffix, such as
``sched.prefix_hit_share.code`` or ``ttft_p95_ms.code``, is read as
that other one (:func:`resolve`).  A cell added later brings entries of
its own under such names, with their own ``workloads`` (and, for an
end-to-end metric, its own bound), and no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Optional

from benchlib import work
from benchlib.flops import Dims

METRICS_DIR = Path(__file__).resolve().parents[1] / "metrics"


@dataclasses.dataclass
class Context:
    window: object            # benchlib.client.Window
    counters: dict            # program counters, change over the window
    requests: list            # program stamps of the window's requests
    steps: list               # benchlib.work.StepWork of the window
    traced_steps: list        # the steps inside the traced window
    trace: Optional[dict]     # benchlib.tracing.reduce(...) or None
    dims: Dims
    peaks: dict

    def counter(self, name: str, **labels) -> float:
        """A counter's change over the window (0 when absent)."""
        if labels:
            inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
            name = f"{name}{{{inner}}}"
        return float(self.counters.get(name, 0.0))

    def model_flops(self, **kw) -> float:
        return work.model_flops(self.dims, self.steps, **kw)


# quantities that more than one metric reads (split by the end-to-end
# metric each moves in its cells)


def step_mfu(ctx: Context, *, prefill: bool = True):
    """The model step's share of the chip's peak, in percent: the model
    operations of the window's computed prompt tokens (with ``prefill``)
    and decoded tokens, over the matching phases' seconds (program
    counters) times the peak rate."""
    if ctx.peaks is None:
        return None
    s = ctx.counter("serve_phase_seconds_total", phase="decode")
    if prefill:
        s += ctx.counter("serve_phase_seconds_total", phase="prefill")
    f = ctx.model_flops(prefill=prefill)
    return 100.0 * f / (s * ctx.peaks["bf16_flops"]) if s and f else None


def idle_share(ctx: Context):
    """Share of the traced window in which no operation ran on the
    device, in percent (profiler trace)."""
    t = ctx.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def resolve(name: str, known) -> Optional[str]:
    """The longest of ``name`` and its dotted prefixes (``a.b.c``,
    ``a.b``, ``a``) for which ``known`` holds; None if none does."""
    parts = name.split(".")
    for i in range(len(parts), 0, -1):
        if known(".".join(parts[:i])):
            return ".".join(parts[:i])
    return None


def load(name: str):
    """The reader of metric ``name`` (see :func:`resolve`)."""
    base = resolve(name, lambda n: (METRICS_DIR / f"{n}.py").is_file())
    if base is None:
        raise FileNotFoundError(f"no reader for metric {name!r} under "
                                f"{METRICS_DIR}")
    path = METRICS_DIR / f"{base}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels(names) -> dict:
    """``{kernel: name pattern}`` of the readers that read a kernel's
    time from the trace (those that define ``KERNEL`` and ``PATTERN``)."""
    out = {}
    for n in names:
        mod = load(n)
        if hasattr(mod, "KERNEL"):
            out[mod.KERNEL] = mod.PATTERN
    return out


def read_all(names, ctx: Context) -> dict:
    out = {}
    for n in names:
        v = load(n).read(ctx)
        if v is not None:
            out[n] = float(v)
    return out
