"""The trace reduction, on two cuts of a v5e trace of the decode block
(48 slots, qwen2-1.5b width) in the form ``tracing.capture`` writes:
``kernel`` spans one call of the paged decode kernel and its
neighbours, ``block_end`` the end of a decode block and the host's gap
before the next.  The expected numbers are counted by hand from the
file, in nanoseconds, as the comments show."""
import json

import pytest

from benchlib import tracing
from smoke_cell import BENCH

KERNELS = {"paged_decode": r"^paged_attention_pallas(\.\d+)?$",
           "prefix_extend": r"^paged_prefix_extend_pallas(\.\d+)?$"}


@pytest.fixture(scope="module")
def recs():
    return json.loads(
        (BENCH / "harness_tests" / "data" / "v5e_decode_trace.json").read_text())


def test_kernel_time_and_busy_time(recs):
    r = tracing.reduce(recs["kernel"], KERNELS)
    # window [0, 1309697]; leaf ops clipped to it:
    # pad_maximum_fusion.4 [0, 84], broadcast_add_fusion.4 [86, 396],
    # copy-done.2 [397, 399], paged_attention_pallas.9 [400, 1309197],
    # reshape.317 [1309197, 1309470], fusion.186 [1309471, 1309697];
    # while/conditional events enclose the rest and are left out
    assert r["window_s"] == pytest.approx(1309697e-9)
    assert r["busy_s"] == pytest.approx((84 + 310 + 2 + 1309070 + 226) * 1e-9)
    assert r["kernel_s"]["paged_decode"] == pytest.approx(1308797e-9)
    assert r["kernel_calls"] == {"paged_decode": 1, "prefix_extend": 0}
    assert r["kernel_s"]["prefix_extend"] == 0.0
    assert r["device_ops"][0] == [
        "jit__decode_impl/paged_attention_pallas.9",
        pytest.approx(1308797e-9)]


def test_idle_share_and_gaps_by_host_span(recs):
    r = tracing.reduce(recs["block_end"], KERNELS)
    # window [0, 8001000]; busy: copy.38 clipped to [0, 678],
    # copy-done.20 [679, 682], pad_add_fusion.1 [684, 999],
    # copy.1 [7766260, 7766800]
    busy = 678 + 3 + 315 + 540
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(
        (8001000 - busy) / 8001000)
    # the longest gap, [999, 7766260], lies mostly in the first
    # engine.step span (the host finishing the step the device ended)
    assert r["idle_gaps"][0] == ["engine.step", pytest.approx(7765261e-9)]
    assert r["idle_gaps"][1] == ["engine.step", pytest.approx(234200e-9)]
    assert r["kernel_calls"]["paged_decode"] == 0
    names = [n for n, _ in r["device_ops"]]
    assert names[:2] == ["jit__decode_impl/copy.38",
                         "jit_broadcast_in_dim/copy.1"]


def test_a_record_without_a_device_gives_nothing():
    assert tracing.reduce({"devices": {}, "host_spans": []}, KERNELS) is None
