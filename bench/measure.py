"""Run one cell several times and report each metric's spread.

    python3 bench/measure.py --workload <name> --seconds <s> \
        --seeds 101 102 103 [--sets 2] [--trace 0] [--out DIR] [-- extra]

Each run is its own process (``bench/run.py``), one after another, so
only one process holds the chip at a time; this process never imports
JAX.  With ``--sets 2`` the seeds are run twice, in the same order.
Every result line goes to ``DIR/<workload>.jsonl``; the summary gives
each metric's median and its spread, the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median, per set.  Arguments after ``--`` go to every run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    extra = []
    if "--" in argv:
        i = argv.index("--")
        argv, extra = argv[:i], argv[i + 1:]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=str(BENCH.parent / "bench_out"))
    a = ap.parse_args(argv)
    out = Path(a.out)
    out.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(a.sets):
        rows = []
        for seed in a.seeds:
            cmd = [sys.executable, str(BENCH / "run.py"), "--workload",
                   a.workload, "--seed", str(seed), "--seconds", a.seconds,
                   "--trace", a.trace] + extra
            t = time.perf_counter()
            p = subprocess.run(cmd, capture_output=True, text=True)
            wall = time.perf_counter() - t
            tail = [ln for ln in p.stderr.splitlines()
                    if ln.startswith("[bench]")]
            row = {"set": k, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "log": tail}
            try:
                row["result"] = json.loads(p.stdout.strip().splitlines()[-1])
            except (IndexError, ValueError):
                row["stderr_tail"] = p.stderr[-4000:]
            rows.append(row)
            with open(out / f"{a.workload}.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
            res = row.get("result", {})
            print(f"set {k} seed {seed}: rc {p.returncode} wall {wall:.1f}s "
                  f"correct {res.get('correct')} "
                  f"{ {m: v['value'] for m, v in res.get('metrics', {}).items()} }"
                  f" {res.get('check')}", flush=True)
            for ln in tail[:1]:
                print("   ", ln, flush=True)
        sets.append(rows)
    for k, rows in enumerate(sets):
        ok = [r["result"] for r in rows if "result" in r]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if len(vals) >= 2:
                print(f"set {k} {m}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals):.4f} n {len(vals)} "
                      f"values {vals}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
