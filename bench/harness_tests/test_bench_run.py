"""A whole run at smoke size on the CPU, with the look for a chip
skipped: the clean program is ``correct``, and each fault planted in the
timed path underneath (``faults.py``), or the control put in the
program's place, makes ``correct`` come out false; and a cell added
by files and entries alone reports the metrics of its own entries."""
import json

import pytest

import faults
import run
from smoke_cell import ROOT, smoke_cell, smoke_config, smoke_mix


@pytest.mark.parametrize("fault", ("none",) + faults.FAULTS)
def test_correct_sees_each_fault(monkeypatch, fault):
    if fault != "none":
        for owner, attr, value in faults.patches(fault):
            monkeypatch.setattr(owner, attr, value)
    # dropping the scaling is a fault only of a file that scales
    cell = smoke_cell("reasoning-batch", scaled=fault == "rope_unscaled")
    res = run.run("smoke", 2 ** 40 + 3, 2.0, False, cell=cell,
                  check_device=False)
    assert res["attempted"] > 0 and res["check"]["tokens_compared"][
        "value"] >= 20
    assert res["correct"] is (fault == "none"), res["check"]
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("seed", (17, 2 ** 40 + 3, 2200000101))
def test_control_reads_above_the_program(seed):
    """The fp8 control, put in the program's place and judged by the
    same comparison, comes out not correct."""
    res = run.run("smoke", seed, 2.0, False,
                  cell=smoke_cell("reasoning-batch"), check_device=False,
                  control=True)
    c = res["check"]
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
    assert c["widest_gap"]["value"] > c["widest_gap"]["limit"]
    assert res["correct"] is False


def test_a_cell_added_by_files_alone_reports_its_own_metrics(tmp_path):
    """A later cell adds a configuration file, a mix file, its
    ``configs``/``workloads`` entries and, for each metric that lists
    only other cells, an entry of its own under that name plus a dotted
    suffix.  ``bench/run.py`` then reports those metrics in the new cell
    (TTFT, queue wait, prefix hits, prefill time) with no file of the
    harness changed."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    like, cell = "qwen2-1.5b.chat-sessions", "smoke.repo"
    manifest["configs"].append(
        {"name": "smoke", "source": "https://example.org/smoke",
         "file": "bench/configs/smoke.json", "reduced": [], "why": "smoke"})
    manifest["workloads"].append(
        {"name": cell, "config": "smoke", "traffic": "smoke-open",
         "chips": 1, "why": "an open loop on shared prefixes"})
    listed = {m["name"] for m in manifest["end_to_end"]
              if "workloads" in m}
    for kind in ("end_to_end", "per_layer"):
        for m in list(manifest[kind]):
            if like in m.get("workloads", []):
                own = dict(m, name=f"{m['name']}.repo", workloads=[cell])
                if m.get("moves") in listed:
                    own["moves"] = f"{m['moves']}.repo"
                manifest[kind].append(own)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "bench" / "configs" / "smoke.json").write_text(
        json.dumps(smoke_config()))
    (tmp_path / "bench" / "traffic" / "smoke-open.json").write_text(
        json.dumps(smoke_mix("chat-sessions")))

    c = run.load_cell(cell, root=tmp_path)
    e2e = {m["name"] for m in c["end_to_end"]}
    assert e2e == {"ttft_p50_ms.repo", "ttft_p95_ms.repo", "tpot_p95_ms",
                   "setup_s"}
    assert all(m["moves"] in e2e for m in c["per_layer"])
    plain = run.run(cell, 2 ** 40 + 9, 2.0, False, cell=c,
                    check_device=False)
    assert set(plain["metrics"]) == e2e
    assert plain["metrics"]["ttft_p95_ms.repo"]["value"] >= \
        plain["metrics"]["ttft_p50_ms.repo"]["value"] > 0
    traced = run.run(cell, 2 ** 40 + 9, 2.0, True, cell=c,
                     check_device=False)
    # the shares of the chip's peak and the device's idle share need a
    # chip; the rest reads at smoke size
    assert {"sched.queue_wait_p95_ms.repo", "sched.prefix_hit_share.repo",
            "engine.prefill_ms_per_ktok.repo", "engine.decode_block_ms",
            "engine.host_ms_per_step"} <= set(traced["metrics"])
    assert traced["metrics"]["sched.prefix_hit_share.repo"]["value"] > 0
    assert plain["correct"] is traced["correct"] is True
