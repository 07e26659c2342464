"""The client loop and the end-to-end arithmetic, on a smoke-size
engine of the program, driven through the harness's own functions."""
import time

import numpy as np
import pytest

from benchlib import client as cl
from benchlib import system, weights
from benchlib import traffic as tr
from benchlib.flops import Dims
from smoke_cell import smoke_config


@pytest.fixture(scope="module")
def engine():
    cfg = smoke_config()
    lm = system.make_lm(cfg)
    params = system.program_params(
        weights.make_weights(cfg, Dims.from_config(cfg), 5), lm)
    eng = system.build_engine(cfg, params, lm, 5)
    # compile the programs these tests reach before any timing
    c = cl.Client(eng, system.engine_busy)
    recs = [c.submit(cl.Record(r, 0.0)) for r in _reqs([(40, 6), (20, 6)])]
    while c.pending(recs):
        c.step()
    return eng


def _reqs(spec, dues=None):
    rng = np.random.default_rng(0)
    return [tr.Request(i, rng.integers(0, 512, p).astype(np.int32), n,
                       due=None if dues is None else dues[i])
            for i, (p, n) in enumerate(spec)]


def _open(reqs, drain):
    return tr.Traffic("open", reqs, [], [], drain)


class _Slow:
    """Every step of ``eng`` takes at least ``s`` seconds more."""

    def __init__(self, eng, s):
        self.eng, self.s = eng, s

    def __enter__(self):
        step = self.eng.step

        def slow():
            time.sleep(self.s)
            return step()
        self.eng.step = slow

    def __exit__(self, *exc):
        del self.eng.step


def test_due_time_ttft_includes_a_stalled_step(engine):
    """A request that falls due while a step stalls is timed from when
    it was due: the stall is in its TTFT, not only in its queue wait."""
    stall = 0.6
    c = cl.Client(engine, system.engine_busy)
    step = engine.step
    calls = []

    def stalled():
        if not calls:
            time.sleep(stall)
        calls.append(1)
        return step()

    engine.step = stalled
    try:
        w = cl.run_open(c, _open(_reqs([(40, 4), (40, 4)], [0.0, 0.1]),
                                 30.0), 1.0)
    finally:
        del engine.step
    late = w.records[1]
    assert late.t_submit - late.due >= stall - 0.1 - 0.02
    assert cl.ttft_s(w)[1] >= stall - 0.1
    assert cl.ttft_s(w)[1] > late.t_first - late.t_submit + 0.3
    assert cl.outcome_counts(w) == (2, 0)


def test_a_hold_of_the_loop_pushes_the_schedule_back(engine):
    """Seconds the loop is held by the profiler (``held_s``) move every
    later due time, and the window's end, back: a request due during
    the hold is not made late by it."""
    hold = 0.6
    c = cl.Client(engine, system.engine_busy)

    def on_step(when):
        if when == "before" and not c.held_s:
            time.sleep(hold)
            c.held_s += hold

    c.on_step = on_step
    w = cl.run_open(c, _open(_reqs([(40, 4), (40, 4)], [0.0, 0.1]), 30.0),
                    1.0)
    first, second = w.records
    assert second.due == pytest.approx(first.due + hold + 0.1, abs=0.02)
    assert second.t_submit - second.due < hold / 2
    assert w.t_close - w.t0 >= 1.0 + hold
    assert cl.outcome_counts(w) == (2, 0)


def test_unfinished_requests_fail_and_stay_in_the_tail(engine):
    """With no drain, a request that is not done counts as failed, and
    one with no first token counts in TTFT up to the end of the run."""
    c = cl.Client(engine, system.engine_busy)
    reqs = _reqs([(40, 200), (40, 4)], [0.0, 0.5])
    with _Slow(engine, 1.2):       # the second falls due in the first step
        w = cl.run_open(c, _open(reqs, 0.0), 1.0)
    assert cl.outcome_counts(w) == (2, 2)
    last = w.records[1]
    assert last.t_first is None
    assert cl.ttft_s(w)[1] == pytest.approx(w.t_end - last.due)
    # the tail holds every request due in the window
    assert len(cl.ttft_s(w)) == 2
    assert cl.end_to_end(w)["ttft_p95_ms"] == pytest.approx(
        1e3 * cl.percentile(cl.ttft_s(w), 95))


def test_tokens_per_second_counts_requests_in_flight(engine):
    """Every token emitted in the window counts, also those of requests
    still running at the close."""
    c = cl.Client(engine, system.engine_busy)
    with _Slow(engine, 0.05):
        w = cl.run_open(c, _open(_reqs([(40, 200)], [0.0]), 0.0), 1.0)
    (r,) = w.records
    assert r.t_done is None and len(r.tokens) > 1
    inside = [t for t in r.stamps if w.t0 <= t <= w.t_close]
    assert cl.window_tokens(w) == len(inside) > 1
    e = cl.end_to_end(w)
    assert e["output_tokens_per_s"] == pytest.approx(
        len(inside) / (w.t_close - w.t0))
    assert e["tpot_p95_ms"] == pytest.approx(
        1e3 * (inside[-1] - inside[0]) / (len(inside) - 1))


def test_percentile_is_numpys_linear_interpolation():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert cl.percentile(xs, 50) == 2.5
    assert cl.percentile(xs, 95) == pytest.approx(3.85)
