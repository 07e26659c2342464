"""Operation and byte counts against hand counts, and the peak table."""
import types

import pytest

from benchlib import flops, peaks, work

# qwen2-1.5b: d 1536, ff 8960, 12 query heads, 2 kv heads of 128, 28 layers
M = flops.Dims(d=1536, f=8960, h=12, kh=2, hd=128, layers=28, vocab=151936)


def test_decode_kernel_hand_count():
    # two slots whose queries attend 100 and 200 keys:
    # ops 4 * 12 * 128 * 300; K and V of 300 tokens at 2 heads * 128 * 2 B
    # twice; a query and an output of 12 * 128 bf16 per slot
    f, b = flops.paged_decode_cost(M, [100, 200])
    assert f == 4 * 12 * 128 * 300 == 1_843_200
    assert b == 2 * 2 * 128 * 300 * 2 + 2 * (2 * 12 * 128 * 2) == 319_488


def test_prefix_extend_kernel_hand_count():
    # one row: 4 queries at positions 128..131 attend 128 cached keys and,
    # causally, 1+2+3+4 chunk keys: 4 * 128 + 10 = 522 key visits
    f, b = flops.prefix_extend_cost(M, [(128, 4)])
    assert f == 4 * 12 * 128 * 522 == 3_207_168
    # cached K and V read once; the chunk's q (12 heads), k and v (2 heads
    # each) read and its output (12 heads) written, bf16
    assert b == 2 * 2 * 128 * 128 * 2 + 4 * (2 * 12 * 128 + 2 * 2 * 128) * 2


def test_cached_prompt_tokens_cost_no_projection():
    # 64 uncached tokens after a 512-token hit: projections for 64 tokens
    # only; attention over the cached keys still counts
    with_hit = flops.prefill_flops(M, 512, 64, logits=True)
    lin = M.layers * flops.linear_flops_per_token(M) * 64
    att = M.layers * 4 * 12 * 128 * (64 * 512 + 64 * 65 // 2)
    assert with_hit == lin + att + 2 * 1536 * 151936


def test_padded_bucket_rows_are_not_counted():
    """The work log takes each chunk's real width from the program's
    per-request progress, never the bucket the batch was padded to."""
    reqs = {0: types.SimpleNamespace(progress=0, out_tokens=[], t_admit=None,
                                     prefix_hit_tokens=0, prompt=[0] * 40),
            1: types.SimpleNamespace(progress=0, out_tokens=[], t_admit=None,
                                     prefix_hit_tokens=32, prompt=[0] * 45)}
    recs = [types.SimpleNamespace(rid=i, t_done=None) for i in reqs]
    client = types.SimpleNamespace(eng=types.SimpleNamespace(registry=reqs),
                                   records=recs)
    log = work.WorkLog(client)
    log("before")
    # one step: request 0 prefills 40 tokens (a 64-wide bucket) and
    # decodes 2 more; request 1 hits 32 cached tokens and prefills 13
    reqs[0].progress, reqs[0].out_tokens, reqs[0].t_admit = 40, [1, 2, 3], 1.0
    reqs[1].progress, reqs[1].out_tokens, reqs[1].t_admit = 45, [7], 1.0
    log("after")
    (s,) = log.steps
    assert s.chunks == [(0, 40, True), (32, 13, True)]
    assert s.decode_keys == [41, 42]
    assert work.model_flops(M, log.steps) == (
        flops.prefill_flops(M, 0, 40, True)
        + flops.prefill_flops(M, 32, 13, True)
        + flops.decode_flops(M, 41) + flops.decode_flops(M, 42))


def test_least_time_names_its_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert flops.least_time(197e12, 1.0, p) == (pytest.approx(1.0), "compute")
    assert flops.least_time(1.0, 819e9, p) == (pytest.approx(1.0), "memory")


def test_peak_table_has_its_source_and_refuses_an_unknown_chip():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
