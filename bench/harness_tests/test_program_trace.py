"""The program's spans and named regions in the trace reduction, on two
cuts of a reasoning-batch traced run on a v5e (48 slots, qwen2-1.5b
width) in the form ``program_trace.capture`` writes: ``step_gap`` holds
the end of a decode block, the wait's tail, the emit loop and the next
tick's admission and page growth with its small programs; ``regions``
one paged decode kernel call and the operations around it in one layer
step.  The expected numbers are counted by hand from the files, in
nanoseconds, as the comments show."""
import json

import pytest

import run
from benchlib import program_trace, readers, tracing
from smoke_cell import BENCH, smoke_cell

KERNELS = {"paged_decode": r"^paged_attention_pallas(\.\d+)?$",
           "prefix_extend": r"^paged_prefix_extend_pallas(\.\d+)?$"}
DATA = BENCH / "harness_tests" / "data"


@pytest.fixture(scope="module")
def spans():
    return json.loads((DATA / "v5e_decode_spans_trace.json").read_text())


@pytest.mark.parametrize("cut", ["kernel", "block_end"])
def test_a_record_without_program_spans_reads_as_before(cut):
    rec = json.loads((DATA / "v5e_decode_trace.json").read_text())[cut]
    old = tracing.reduce(rec, KERNELS)
    new = program_trace.reduce(rec, KERNELS)
    assert {k: new[k] for k in old} == old
    assert new["span_s"] == {}
    # with no named regions, the decode program's operations are copies
    # or unscoped, and no pool time is read
    assert set(new["scope_s"]) <= {"copy", "unscoped"}
    assert program_trace.pool_copy_s(new) is None


def test_idle_under_each_program_span(spans):
    r = program_trace.reduce(spans["step_gap"], KERNELS)
    # window [0, 10399999]; busy: copy.38 clipped to [0, 887592],
    # copy-done.20 [887593, 887595], pad_add_fusion.1 [887595, 887912],
    # copy.1 [9209528, 9210068]; idle: [887592, 887593],
    # [887912, 9209528], [9210068, 10399999]
    idle = 1 + 8321616 + 1189931
    assert r["window_s"] - r["busy_s"] == pytest.approx(idle * 1e-9)
    # the innermost program span over each idle instant, else the
    # harness span, else none: the wait runs to 5503683, then the old
    # tick's glue to 5529173, emit to 6460193, glue to 6571933, the old
    # engine.step to 6583313, no span (the client between ticks) to
    # 7474703, the new engine.step to 7479943, glue to 7484583, admit to
    # 7488553, glue to 7501363, then page growth past the window
    want = {"engine.decode.wait": 1 + 4615771,
            "sched.step": 25490 + 111740 + 4640 + 12810,
            "engine.decode.emit": 931020,
            "engine.step": 11380 + 5240,
            "none": 891390,
            "sched.admit": 3970,
            "sched.grow": 1708165 + 1189931}
    assert r["idle_by_span"] == {k: pytest.approx(v * 1e-9)
                                 for k, v in want.items()}
    assert sum(want.values()) == idle
    # program spans clipped to the window: the old tick to 6571933, the
    # new one from 7479943; the wait to 5503683; growth from 7501363
    assert r["span_s"] == {
        "sched.step": pytest.approx((6571933 + 10399999 - 7479943) * 1e-9),
        "engine.decode.wait": pytest.approx(5503683e-9),
        "engine.decode.emit": pytest.approx(931020e-9),
        "sched.admit": pytest.approx(3970e-9),
        "sched.grow": pytest.approx((10399999 - 7501363) * 1e-9)}


def test_idle_gaps_name_the_program_phase(spans):
    r = program_trace.reduce(spans["step_gap"], KERNELS)
    # [887912, 9209528] overlaps the wait by 4615771, growth by 1708165,
    # emit by 931020; [9210068, 10399999] lies in growth
    assert r["idle_gaps"][:3] == [
        ["engine.decode.wait", pytest.approx(8321616e-9)],
        ["sched.grow", pytest.approx(1189931e-9)],
        ["engine.decode.wait", pytest.approx(1e-9)]]
    # the harness's own reduction of the same record names its span
    assert [g[0] for g in tracing.reduce(spans["step_gap"], KERNELS)
            ["idle_gaps"][:3]] == ["engine.step"] * 3


def test_idle_inside_running_programs(spans):
    r = program_trace.reduce(spans["step_gap"], KERNELS)
    # idle ∩ programs: the decode program ends at 887913 (1 ns of each
    # of the first two gaps), jit_convert_element_type [8377802,
    # 8378395] (593), jit_broadcast_in_dim from 9209524 (4 before
    # copy.1), jit_less [10264125, 10265071] (946)
    assert r["idle_in_program_s"] == pytest.approx(
        (1 + 1 + 593 + 4 + 946) * 1e-9)
    # decode program ops by region: copy.38 and copy-done.20 carry no
    # scope and are copies; pad_add_fusion.1 has a path but no region
    assert r["scope_s"] == {"copy": pytest.approx((887592 + 2) * 1e-9),
                            "unscoped": pytest.approx(317e-9)}


def test_decode_program_time_by_region(spans):
    r = program_trace.reduce(spans["regions"], KERNELS)
    # leaf ops clipped to [0, 1200000], by innermost region:
    # kv_write: copy.50 [0, 55880]; kv_pool: constant_dynamic-slice_
    # fusion.8 7065, dynamic-slice_reduce_fusion.2 417 (the layer loop's
    # weight slices); proj_mlp: fusion.176 1708, copy.45 138, copy.46
    # 138, pad_maximum_fusion.4 207, fusion.186 8342, add_rsqrt_fusion.6
    # 20, convert_reduce_fusion.6 527, fusion.187 clipped 35011;
    # attn_kernel: broadcast_add_fusion.4 312, paged_attention_pallas.9
    # 1089701; copy: copy-done.4/.6/.2 2 + 3 + 2; unscoped (no path):
    # subtract_convert_fusion.3 233, reshape.318 273
    want = {"kv_write": 55880, "kv_pool": 7065 + 417,
            "proj_mlp": 1708 + 138 + 138 + 207 + 8342 + 20 + 527 + 35011,
            "attn_kernel": 312 + 1089701, "copy": 2 + 3 + 2,
            "unscoped": 233 + 273}
    assert r["scope_s"] == {k: pytest.approx(v * 1e-9)
                            for k, v in want.items()}
    assert r["kernel_s"]["paged_decode"] == pytest.approx(1089701e-9)
    # every idle instant lies inside the running program, under the wait
    idle = r["window_s"] - r["busy_s"]
    assert idle == pytest.approx((1200000 - sum(want.values())) * 1e-9)
    assert r["idle_in_program_s"] == pytest.approx(idle)
    assert r["idle_by_span"] == {"engine.decode.wait": pytest.approx(idle)}
    assert r["span_s"] == {"sched.step": pytest.approx(1200000e-9),
                           "engine.decode.wait": pytest.approx(1200000e-9)}


def test_decode_pool_copy_seconds(spans):
    r = program_trace.reduce(spans["regions"], KERNELS)
    # kv_pool + kv_write + copies with no region
    assert program_trace.pool_copy_s(r) == pytest.approx(
        1e-9 * (7065 + 417 + 55880 + 2 + 3 + 2))
    # a program without named regions (the parent's) gives nothing
    assert program_trace.pool_copy_s(
        program_trace.reduce(spans["step_gap"], KERNELS)) is None
    assert program_trace.pool_copy_s(None) is None


def _ctx(counters):
    return readers.Context(window=None, counters=counters, requests=[],
                           steps=[], traced_steps=[], trace=None,
                           dims=None, peaks=None)


def test_host_ms_per_step_reader():
    read = readers.load("engine.host_ms_per_step").read
    c = {'serve_span_seconds_total{span="sched.step"}': 2.0,
         'serve_span_seconds_total{span="sched.prefill.wait"}': 0.3,
         'serve_span_seconds_total{span="engine.decode.wait"}': 1.5,
         'serve_spans_total{span="sched.step"}': 4.0}
    assert read(_ctx(c)) == pytest.approx(1e3 * 0.2 / 4)
    # a program without spans (the parent's) gives nothing
    assert read(_ctx({"serve_phase_seconds_total": 1.0})) is None


def test_host_ms_per_step_on_the_smoke_cell():
    """A traced run at smoke size on the CPU: the program's spans are
    counted, so the host time per tick is read."""
    res = run.run("smoke", 2 ** 40 + 5, 2.0, True,
                  cell=smoke_cell("reasoning-batch"), check_device=False)
    m = res["metrics"]
    assert 0 < m["engine.host_ms_per_step"]["value"] < 1e3
    assert m["engine.host_ms_per_step"]["unit"] == "ms"
    assert res["correct"] is True
