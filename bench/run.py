"""Run one cell of the serving benchmark once, on the chip it finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process loads the cell's configuration and traffic mix (found by the
names that ``BENCHMARK.json`` gives the cell, under ``bench/configs`` and
``bench/traffic``), makes the weights on the device from ``--seed``,
builds the program's ``SchedEngine``, warms up every program shape the
traffic can reach and the prefix cache the traffic expects, then offers
the traffic for ``--seconds`` and drains what fell due.  With
``--trace 1`` it records a stretch of the window with the profiler and
reports the cell's per-layer metrics (one reader each, under
``bench/metrics``); with ``--trace 0`` it reports the end-to-end ones.
A metric named as another plus a dotted suffix (``ttft_p95_ms.code``)
is read as that one (``benchlib.readers.resolve``), so a cell added
later brings metric entries of its own and no file here changes.

After the window the engine is freed and a sample of the served
requests is compared with the plain float32 reference
(``benchlib.reference``); ``correct`` says whether every served token of
the sample lies within the configuration's limit of the reference's
best logit.  The numbers compared close standard error and the result
line, which is the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, or without
the program beside the benchmark, the run exits non-zero and prints no
result.  ``--control`` puts the control (the reference at fp8, see
``benchlib.reference``) in the program's place for the check, which
must then come out not correct; the program's own reading is logged
beside it.  ``--rate`` overrides an open loop's session rate (for
finding the knee).  Neither is used by a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


class NoDevice(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, mix and metrics, from the
    ``BENCHMARK.json`` under ``root``: the metrics whose ``workloads``
    name the cell, or that have no such list."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    c = cells[workload]
    bench = root / BENCH.relative_to(ROOT)

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": c,
        "config": json.loads((bench / "configs" /
                              f"{c['config']}.json").read_text()),
        "mix": json.loads((bench / "traffic" /
                           f"{c['traffic']}.json").read_text()),
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [m for m in manifest["per_layer"] if mine(m)],
    }


class CompileClock:
    """Backend compiles and tracing misses, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.traces = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


def setup_jax(chips: int, check_device: bool):
    import jax
    devs = jax.devices()
    if check_device:
        if devs[0].platform != "tpu" or len(devs) < chips:
            raise NoDevice(f"found {len(devs)} {devs[0].platform} "
                           f"device(s); this cell needs {chips} TPU chip(s) "
                           "and does not fall back to another device")
        # the persistent compile cache, at a fixed path in the checkout
        # unless the environment names one; every program is kept
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not (ROOT / "src" / "repro").is_dir():
        raise NoDevice(f"the program (src/repro) is not beside {BENCH}")
    sys.path.insert(0, str(ROOT / "src"))
    return jax, devs


def serve_warm(client, requests, group: int) -> None:
    """Serve set-up requests in groups of ``group``, each to the end."""
    from benchlib.client import Record
    for i in range(0, len(requests), group):
        now = client.clock()
        recs = [client.submit(Record(r, now)) for r in requests[i:i + group]]
        while client.pending(recs):
            client.step()


def pick_samples(records, n: int, seed: int) -> list:
    """A sample of the served requests, drawn from the seed: the one
    with the most served tokens, one that prefilled from scratch, one
    that continued after a prefix-cache hit and one whose prefill took
    more than one chunk (where the run has them), the rest at random."""
    import numpy as np
    served = [r for r in records if len(r.tokens) >= 1]
    if not served:
        return []
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    chosen = [max(served, key=lambda r: (len(r.tokens), r.req.idx))]
    classes = [lambda r: r.hit == 0, lambda r: r.hit > 0,
               lambda r: len(r.req.prompt) - r.hit > r.chunk]
    for cls in classes:
        pool = [r for r in served if cls(r) and r not in chosen]
        if pool and not any(cls(r) for r in chosen):
            chosen.append(pool[rng.integers(len(pool))])
    rest = [r for r in served if r not in chosen]
    k = max(0, n - len(chosen))
    for i in rng.permutation(len(rest))[:k]:
        chosen.append(rest[i])
    return chosen


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        cell: dict = None, check_device: bool = True, control: bool = False,
        rate: float = None) -> dict:
    """One run of a cell; returns the result line as a dict."""
    cell = cell or load_cell(workload)
    cfg, mix = cell["config"], dict(cell["mix"])
    if rate is not None:
        mix["session_rate_per_s"] = rate
    jax, devs = setup_jax(cell["cell"]["chips"], check_device)
    clock = CompileClock()

    from benchlib import client as cl
    from benchlib import peaks as pk
    from benchlib import readers, reference, system, tracing
    from benchlib import traffic as tr
    from benchlib import weights, work
    from benchlib.flops import Dims

    dev = devs[0]
    # peaks only for a chip the table knows; elsewhere (a test on the CPU)
    # no share of a peak is read at all
    peaks = pk.peaks_for(dev.device_kind) if check_device else None
    s = cfg["serving"]
    dims = Dims.from_config(cfg)

    # --- set-up: weights, engine, warm-up --------------------------------
    lm = system.make_lm(cfg)
    params = system.program_params(weights.make_weights(cfg, dims, seed), lm)
    eng = system.build_engine(cfg, params, lm, seed)
    traffic = tr.generate(mix, seed, seconds, vocab=dims.vocab,
                          max_len=s["max_len"], slots=s["slots"])
    client = cl.Client(eng, system.engine_busy,
                       annotate=jax.profiler.TraceAnnotation)
    plan = system.shape_plan(cfg, mix, traffic)
    system.warm_shapes(eng, plan)
    wm = mix["warm"]
    serve_warm(client, traffic.warm, wm["stage_rows"])
    if traffic.loop == "open":
        # the decode program, on a request that the window never sees
        decode = tr.Request(
            -1, traffic.requests[0].prompt[:s["prefill_chunk"]],
            2 * s["decode_block"])
        serve_warm(client, [decode], 1)
        client.records = []
    else:
        loop = cl.ClosedLoop(client, traffic)
        loop.start()
        client.records = list(loop.records)
    gc.collect()

    # --- the window -------------------------------------------------------
    hooks = {}
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = tracing.Recorder(tdir)
    log_work = work.WorkLog(client)

    def on_open(t0):
        hooks["setup_s"] = t0 - T_START
        hooks["snap"] = eng.metrics.snapshot()
        hooks["compiles"] = (clock.compiles, clock.traces)
        rec.start_at = t0 + wm["trace_from"] * seconds
        rec.stop_at = rec.start_at + wm["trace_s"]

    def on_close():
        hooks["delta"] = eng.metrics.delta(hooks["snap"])
        hooks["window_compiles"] = (clock.compiles - hooks["compiles"][0],
                                    clock.traces - hooks["compiles"][1])

    def on_step(when):
        # the profiler's start and stop hold the loop for seconds; the
        # open loop's schedule waits them out (client.held_s)
        now = time.perf_counter()
        if when == "before":
            client.held_s += rec.before_step(now)
        log_work(when)
        if when == "after":
            client.held_s += rec.after_step(now)

    if trace:
        client.on_step = on_step
    if traffic.loop == "open":
        win = cl.run_open(client, traffic, seconds, on_open=on_open,
                          on_close=on_close)
    else:
        win = loop.run(seconds, on_open=on_open, on_close=on_close)
    rec.stop()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    attempted, failed = cl.outcome_counts(win)
    for r in win.records:
        q = eng.registry[r.rid]
        r.hit, r.chunk = q.prefix_hit_tokens, eng.prefill_chunk
        r.prog = {"t_submit": q.t_submit, "t_admit": q.t_admit}
    late = [x * 1e3 for x in win.lateness] or [0.0]
    log(f"{workload} seed {seed}: set-up {hooks['setup_s']:.3f}s, window "
        f"{win.t_close - win.t0:.3f}s, drain {win.t_end - win.t_close:.3f}s; "
        f"{attempted} requests, {failed} failed; generator lateness ms "
        f"p50 {cl.percentile(late, 50):.3f} p95 {cl.percentile(late, 95):.3f}"
        f" max {max(late):.3f}; compiles in window "
        f"{hooks['window_compiles'][0]}, traces {hooks['window_compiles'][1]}"
        f"; shapes warmed {sum(len(v) for v in plan.values())}; loop held "
        f"by the profiler {client.held_s:.3f}s")

    # --- metrics ----------------------------------------------------------
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}}
    if trace:
        names = [m["name"] for m in cell["per_layer"]]
        red = None
        if rec.t1 is not None:
            red = tracing.reduce(tracing.capture(tdir),
                                 readers.kernels(names))
        shutil.rmtree(tdir, ignore_errors=True)
        if red is not None:
            result["device"].update(busy_s=red["busy_s"],
                                    window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            log(f"trace: busy {red['busy_s']:.6f}s of {red['window_s']:.6f}s"
                f", kernels {red['kernel_s']} calls {red['kernel_calls']}")
        steps = log_work.between(win.t0, win.t_close)
        log(f"prefill shapes in the window: "
            f"{system.shapes_used(cfg, steps)}")
        ctx = readers.Context(
            window=win, counters=hooks["delta"]["counters"],
            requests=[r.prog for r in win.records], steps=steps,
            traced_steps=log_work.between(rec.t0, rec.t1) if red else [],
            trace=red, dims=dims, peaks=peaks)
        units = {m["name"]: m["unit"] for m in cell["per_layer"]}
        result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in
                             readers.read_all(names, ctx).items()}
    else:
        e2e = cl.end_to_end(win)
        e2e["setup_s"] = hooks["setup_s"]
        # a suffixed name reads its base quantity (readers.resolve)
        for m in cell["end_to_end"]:
            k = readers.resolve(m["name"], e2e.__contains__)
            if k is not None:
                result["metrics"][m["name"]] = {"value": e2e[k],
                                                "unit": m["unit"]}

    # --- correct: the sample against the reference -----------------------
    chk = cfg["check"]
    sample = pick_samples(win.records, chk["sample_requests"], seed)
    samples = [(r.req.prompt, r.tokens) for r in sample]
    cover = {"requests": len(sample),
             "from_scratch": sum(r.hit == 0 for r in sample),
             "after_hit": sum(r.hit > 0 for r in sample),
             "multi_chunk": sum(len(r.req.prompt) - r.hit > r.chunk
                                for r in sample)}
    # the reference starts once the program's state is freed, and makes
    # its weights anew
    del eng, client, log_work, params, lm, win, sample
    gc.collect()
    t_ref = time.perf_counter()
    g = reference.gaps(weights.make_weights(cfg, dims, seed), dims, cfg,
                       samples, s["max_len"],
                       controls=("fp8",) if control else ())
    served = g.get("served", {})
    # the control's tokens stand in for the served ones, judged alike
    judged = g.get("fp8", {}) if control else served
    checks = {"widest_gap": {"value": judged.get("widest_gap"),
                             "limit": chk["gap_limit"]},
              "tokens_compared": {"value": judged.get("tokens", 0),
                                  "limit": chk["min_tokens"]}}
    result["correct"] = (
        checks["widest_gap"]["value"] is not None
        and checks["widest_gap"]["value"] <= chk["gap_limit"]
        and checks["tokens_compared"]["value"] >= chk["min_tokens"]
        and all(0 <= t < dims.vocab for _, toks in samples for t in toks))
    log(f"reference: {time.perf_counter() - t_ref:.3f}s; sample {cover}; "
        f"served tokens equal to the reference's best: "
        f"{served.get('exact_share')}")
    if control:
        log(f"control fp8 in the program's place; the program's own "
            f"widest_gap {served.get('widest_gap')} over "
            f"{served.get('tokens', 0)} tokens")
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    result["check"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  control=args.control, rate=args.rate)
    except NoDevice as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
