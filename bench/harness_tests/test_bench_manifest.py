"""``BENCHMARK.json`` and the files it names agree, and the command
refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from benchlib import readers
from smoke_cell import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def _reports(cell, metrics):
    return [m["name"] for m in metrics
            if cell in m.get("workloads", [cell])]


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_every_cell_finds_its_files_and_reports_what_its_metrics_move(cell):
    c = {w["name"]: w for w in MANIFEST["workloads"]}[cell]
    assert (BENCH / "configs" / f"{c['config']}.json").is_file()
    assert (BENCH / "traffic" / f"{c['traffic']}.json").is_file()
    e2e = _reports(cell, MANIFEST["end_to_end"])
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in MANIFEST["per_layer"]
             if cell in m.get("workloads", [cell])]
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(readers.load(m["name"]).read)


def test_config_files_are_the_manifest_files():
    for c in MANIFEST["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
