"""Serving driver: continuous-batching engine over the decode step.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --requests 8 [--paged] [--kv-style gqa] [--quant int8] \
        [--policy edf --slo-ttft 2000 --prefix-cache --arrival-rate 4]

``--smoke`` runs the reduced config on CPU; the Engine + decode step are
the same objects the dry-run lowers for the production mesh.
``--policy`` switches to the SLO-aware scheduler (``repro.sched``):
policy-ordered admission, prefix caching over the paged pools, chunked
prefill, and preemption with recompute-on-readmit; ``--arrival-rate``
paces submissions open-loop (Poisson) instead of queueing everything
upfront.  ``--spec ngram|draft`` adds speculative decoding on top
(``repro.spec``): draft -> batched paged verify -> exact accept/commit
rounds, greedy output token-identical to non-speculative decode;
``--admission-control`` turns on EDF's goodput-optimal dropping of
SLO-infeasible requests.  ``--chaos`` arms the seeded fault-injection
harness (``repro.resil``), ``--degrade`` the graceful-degradation
ladder, ``--max-request-s`` per-request wall-clock deadlines — the
overload-resilience stack.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import LM
from repro.serve.engine import Engine


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV + Pallas decode kernel + fused "
                         "multi-token decode loop (PagedEngine)")
    ap.add_argument("--decode-block", type=int, default=8,
                    help="tokens per host sync in the paged engine")
    ap.add_argument("--page-size", type=int, default=64,
                    help="KV page size for --paged (tokens per page)")
    ap.add_argument("--policy", default=None,
                    choices=["fcfs", "sjf", "edf"],
                    help="serve through the SLO-aware scheduler "
                         "(repro.sched.SchedEngine) with this admission "
                         "policy; implies the paged engine")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="refcounted prefix caching over the paged pools "
                         "(--policy only)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="scheduler prefill chunk in tokens (multiple of "
                         "--page-size; default 8 pages — the fused "
                         "prefix-extend kernel streams the prefix, so "
                         "chunk size no longer bounds an eager context)")
    ap.add_argument("--chunk-prefill-impl", default="fused",
                    choices=["fused", "eager"],
                    help="chunked-prefill / spec-verify attention against "
                         "the paged pools: 'fused' streams pages through "
                         "the width-parameterized prefix-extend Pallas "
                         "kernel; 'eager' is the ref.py full-horizon "
                         "gather oracle (debug / A-B only)")
    ap.add_argument("--slo-ttft", type=float, default=None,
                    help="TTFT SLO target in ms (EDF deadlines + "
                         "telemetry)")
    ap.add_argument("--slo-tpot", type=float, default=None,
                    help="TPOT SLO target in ms (telemetry)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrivals, requests/sec "
                         "(0: submit everything upfront)")
    ap.add_argument("--admission-control", action="store_true",
                    help="drop requests whose cost-model prefill estimate "
                         "already overruns their TTFT deadline at "
                         "admission (EDF; goodput-optimal dropping)")
    ap.add_argument("--spec", default="none",
                    choices=["none", "ngram", "draft"],
                    help="speculative decoding (repro.spec.SpecEngine, "
                         "implies the scheduler): model-free n-gram "
                         "prompt-lookup drafts or a small draft LM "
                         "sharing the vocab; greedy output is token-"
                         "identical to non-speculative decode")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max draft tokens per verify round (adaptive "
                         "controller tunes per-slot k below this)")
    ap.add_argument("--draft-config", default="auto",
                    help="--spec draft: arch id for the draft model, or "
                         "'auto' for a shrunk copy of the target config "
                         "(random-init; 'self' = self-speculation oracle)")
    ap.add_argument("--spec-adaptive", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="adapt per-slot draft length from the measured "
                         "acceptance EMA via the cost model")
    ap.add_argument("--spec-slack", type=float, default=None,
                    help="disable speculation for a tick when a queued "
                         "EDF deadline is closer than this many ms")
    ap.add_argument("--kv-style", default="full",
                    choices=["full", "gqa", "mqa"])
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "bfloat16", "int8", "fp8"],
                    help="KV-cache storage dtype (repro.kvcache): int8/fp8 "
                         "caches carry amax scales and halve KV HBM")
    ap.add_argument("--quant", default="bf16",
                    choices=["bf16", "fp8", "int8", "int4"],
                    help="weight quantization for the SERVING path "
                         "(quant.qops.quantize_tree); every engine "
                         "streams the quantized weights — decode, spec "
                         "verify, chunked prefill, draft LM included")
    ap.add_argument("--quant-impl", default="fused",
                    choices=["fused", "ref"],
                    help="quantized-matmul execution: 'fused' streams "
                         "weights through the decode-shaped Pallas "
                         "kernels (activation quant + scale/bias "
                         "epilogue fused); 'ref' is the jnp oracle "
                         "(debug / A-B only)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="serve over a mesh with this 'model'-axis size "
                         "(kv-head-sharded paged attention + TP weights + "
                         "sequence-parallel chunked prefill; implies the "
                         "paged engine).  On CPU force host devices first: "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N")
    ap.add_argument("--tp-attn-impl", default="kv_shard",
                    choices=["kv_shard", "gather"],
                    help="sharded paged-attention arm: 'kv_shard' keeps "
                         "KV local per shard; 'gather' is the naive "
                         "output-all-gather TP baseline (collective-byte "
                         "A/B only)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="seeded fault injection (repro.resil.inject; "
                         "--policy/--spec engines only): e.g. "
                         "'seed=1,oom=0.1,fault=0.1,spike=0.05,draft=0.3,"
                         "shrink=2' — forced page exhaustion, transient "
                         "dispatch faults, latency spikes, degenerate "
                         "draft proposals, pool shrinkage")
    ap.add_argument("--degrade", action="store_true",
                    help="graceful-degradation ladder "
                         "(repro.resil.degrade): under metrics-registry "
                         "pressure disable spec -> shrink prefill chunks "
                         "-> shed load with policy retry-after hints; "
                         "monotone rungs with hysteresis")
    ap.add_argument("--max-request-s", type=float, default=None,
                    help="per-request wall-clock deadline: requests "
                         "(queued or running) past it are cancelled, "
                         "pages freed, outcome 'timed_out'")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the engine's metrics-registry snapshot "
                         "here after the drive: Prometheus text for "
                         ".prom/.txt, JSON otherwise (repro.obs.metrics; "
                         "includes cost-model byte splits and, on a mesh, "
                         "the compiled decode dispatch's collective "
                         "bytes)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-request lifecycle spans and write "
                         "Chrome/Perfetto trace-event JSON here (open in "
                         "ui.perfetto.dev); adds zero host syncs")
    ap.add_argument("--profile", action="store_true",
                    help="per-dispatch device-time profiling "
                         "(repro.obs.profile): attribute measured "
                         "wall-clock to every admit / prefill-chunk / "
                         "decode-block / spec-round dispatch by config "
                         "arm and fold drift + roofline-attainment "
                         "gauges into --metrics; adds zero host syncs")
    ap.add_argument("--calibration-out", default=None, metavar="PATH",
                    help="fit a CalibratedCostModel from the profiled "
                         "dispatches (implies --profile) and write the "
                         "JSON calibration artifact here")
    ap.add_argument("--calibration-in", default=None, metavar="PATH",
                    help="seed the calibration from a previous "
                         "--calibration-out artifact (corrections keep "
                         "updating online from this drive's samples)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.kvcache import normalize_dtype
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.with_(kv_cache_style=args.kv_style
                    if cfg.attention is not None else "full",
                    kv_cache_dtype=normalize_dtype(args.kv_dtype)
                    if cfg.attention is not None else "bfloat16",
                    chunk_prefill_impl=args.chunk_prefill_impl,
                    # cfg.quant makes the cost model price the quantized
                    # weight stream (SJF/EDF ordering + spec controller);
                    # quant_matmul_impl selects the fused Pallas kernels
                    # for every inference forward
                    quant=args.quant,
                    quant_matmul_impl=args.quant_impl,
                    tp_attn_impl=args.tp_attn_impl)
    mesh = None
    if args.model_parallel > 1:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.model_parallel)
        print(f"[serve] mesh {dict(mesh.shape)} over "
              f"{len(jax.devices())} {jax.default_backend()} devices")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(args.seed))
    if args.quant != "bf16":
        from repro.quant.qops import quantize_tree
        params = quantize_tree(params, quant=args.quant)
        print(f"[serve] weights quantized to {args.quant} "
              f"({args.quant_impl} matmuls)")

    from repro.obs import DispatchProfiler, Tracer
    tracer = Tracer(enabled=args.trace_out is not None)
    profile_on = (args.profile or args.calibration_out is not None
                  or args.calibration_in is not None)
    profiler = DispatchProfiler(enabled=profile_on)
    injector = None
    if args.chaos:
        from repro.resil import FaultInjector
        injector = FaultInjector.from_spec(args.chaos)
        print(f"[serve] chaos armed: {injector.describe()}")
    if args.spec != "none" or args.policy:
        sched_kw = dict(n_slots=args.slots,
                        max_len=args.max_len, seed=args.seed,
                        tracer=tracer, profiler=profiler,
                        page_size=args.page_size,
                        decode_block=args.decode_block, mesh=mesh,
                        policy=args.policy or "fcfs",
                        prefix_cache=args.prefix_cache,
                        prefill_chunk=args.prefill_chunk,
                        admission_control=args.admission_control,
                        slo_ttft=None if args.slo_ttft is None
                        else args.slo_ttft / 1e3,
                        slo_tpot=None if args.slo_tpot is None
                        else args.slo_tpot / 1e3,
                        injector=injector,
                        ladder=True if args.degrade else None,
                        max_request_s=args.max_request_s)
        if args.spec != "none":
            from repro.spec import SpecEngine, draft_config_of
            draft_lm = draft_params = None
            if args.spec == "draft":
                if args.draft_config == "self":
                    draft_lm, draft_params = lm, params
                else:
                    dcfg = (draft_config_of(cfg)
                            if args.draft_config == "auto"
                            else get_smoke_config(args.draft_config)
                            if args.smoke else get_config(args.draft_config))
                    # the drafter streams quantized weights too — its
                    # forward passes run the same fused serving path
                    dcfg = dcfg.with_(quant=args.quant,
                                      quant_matmul_impl=args.quant_impl)
                    draft_lm = LM(dcfg)
                    draft_params = draft_lm.init(
                        jax.random.PRNGKey(args.seed + 1))
                    if args.quant != "bf16":
                        from repro.quant.qops import quantize_tree
                        draft_params = quantize_tree(draft_params,
                                                     quant=args.quant)
                    print(f"[serve] draft model {dcfg.name}: "
                          f"{dcfg.num_layers}L d={dcfg.d_model} "
                          f"quant={args.quant}")
            eng = SpecEngine(lm, params, spec=args.spec,
                             draft_k=args.draft_k, draft_lm=draft_lm,
                             draft_params=draft_params,
                             adaptive=args.spec_adaptive,
                             spec_slack_s=None if args.spec_slack is None
                             else args.spec_slack / 1e3, **sched_kw)
        else:
            from repro.sched import SchedEngine
            eng = SchedEngine(lm, params, **sched_kw)
    elif args.paged or mesh is not None:
        # --model-parallel implies the paged engine: the sharded serving
        # path is the kv-head-sharded paged attention stack
        from repro.serve.engine import PagedEngine
        eng = PagedEngine(lm, params, n_slots=args.slots,
                          max_len=args.max_len, seed=args.seed,
                          page_size=args.page_size,
                          decode_block=args.decode_block, mesh=mesh,
                          tracer=tracer, profiler=profiler,
                          injector=injector)
    else:
        eng = Engine(lm, params, n_slots=args.slots, max_len=args.max_len,
                     seed=args.seed, tracer=tracer, profiler=profiler)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (args.prompt_len,)).tolist()
               for _ in range(args.requests)]
    # the drive runs under try/finally: a mid-drive exception still
    # flushes whatever telemetry exists (partial metrics / trace /
    # calibration) for post-mortem, then propagates
    try:
        t0 = time.perf_counter()
        if args.arrival_rate > 0:
            from repro.serve.engine import run_open_loop
            offsets = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                                args.requests))
            ids = run_open_loop(eng, prompts, offsets,
                                max_new_tokens=args.max_new,
                                temperature=args.temperature)
            done = dict(eng.registry)
        else:
            ids = [eng.submit(p, max_new_tokens=args.max_new,
                              temperature=args.temperature)
                   for p in prompts]
            done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        n_tok = sum(len(done[i].out_tokens) for i in ids)
        if args.spec != "none":
            mode = (f"sched/{args.policy or 'fcfs'} + spec/{args.spec}, "
                    f"{eng.sync_count} host syncs")
        elif args.policy:
            mode = f"sched/{args.policy}, {eng.sync_count} host syncs"
        elif args.paged or mesh is not None:
            mode = f"paged, {eng.sync_count} host syncs"
        else:
            mode = "eager, 1 sync/token"
        print(f"[serve] {cfg.name}: {len(ids)} requests, {n_tok} tokens in "
              f"{dt:.1f}s ({n_tok/dt:.1f} tok/s, continuous batching over "
              f"{args.slots} slots, {mode})")
        if args.spec != "none" or args.policy:
            print(f"[serve] sched telemetry: {eng.telemetry()}")
            if injector is not None:
                print(f"[serve] injected faults: {dict(injector.counts)}")
            if args.degrade and getattr(eng, "ladder", None) is not None:
                lad = eng.ladder
                print(f"[serve] degrade ladder: rung={lad.name} "
                      f"spec_off={lad.spec_off} "
                      f"chunk={lad.chunk_for(eng.prefill_chunk, eng.page_size)}"
                      f" kv_dtype_hint={lad.kv_dtype_hint or 'unchanged'}")
        for i in ids[:3]:
            print(f"  req {i}: {len(done[i].out_tokens)} tokens "
                  f"{done[i].out_tokens[:8]}…")
    finally:
        _write_artifacts(args, cfg, eng, mesh, tracer, profiler)
    return 0


def _write_artifacts(args, cfg, eng, mesh, tracer, profiler):
    """Flush --metrics / --trace-out / --calibration-out.  Runs in the
    drive's ``finally`` so a mid-drive exception still leaves partial
    telemetry on disk."""
    calib = None
    if profiler.enabled:
        from repro.core.costmodel import CalibratedCostModel, tier_for_devices
        calib = (CalibratedCostModel.load(args.calibration_in)
                 if args.calibration_in else CalibratedCostModel())
        records = calib.fit_profile(profiler, eng.lm.cfg)
        calib.register_metrics(eng.metrics)
        devices = (jax.devices()[:1] if mesh is None
                   else list(mesh.devices.flat))
        profiler.export_gauges(eng.metrics, tier_for_devices(devices))
        print(f"[serve] profiled {len(records)} dispatches across "
              f"{len(calib.factors)} (kind × arm) calibration series")
    if args.calibration_out and calib is not None:
        calib.save(args.calibration_out)
        print(f"[serve] calibration -> {args.calibration_out}")
    if args.metrics:
        # one snapshot carries engine counters, cost-model byte splits
        # and (on a mesh) the compiled decode dispatch's collective bytes
        from repro.core.costmodel import service_estimate
        est = service_estimate(cfg, prompt=args.prompt_len,
                               gen=args.max_new, chunk=args.prefill_chunk)
        eng.metrics.set_gauges(
            {f"costmodel_{k}": v for k, v in est.items()},
            help="cost-model roofline estimate at the drive's "
                 "prompt/gen shape")
        if mesh is not None and hasattr(eng, "_decode_jit"):
            from repro.launch.roofline import parse_collectives
            a2 = (eng.params, eng.cache,
                  np.zeros((args.slots,), np.int32),
                  np.zeros((args.slots,), np.int32),
                  np.zeros((args.slots,), bool),
                  np.zeros((args.slots,), np.int32),
                  np.zeros((args.slots,), np.float32),
                  jax.random.PRNGKey(0))
            with eng._mesh_ctx():
                hlo = eng._decode_jit.lower(*a2).compile().as_text()
            parse_collectives(hlo).register_metrics(
                eng.metrics, steps=args.decode_block)
        if str(args.metrics).endswith((".prom", ".txt")):
            body = eng.metrics.to_prometheus_text()
        else:
            body = eng.metrics.to_json(arch=cfg.name,
                                       engine=type(eng).__name__)
        with open(args.metrics, "w") as f:
            f.write(body)
        print(f"[serve] metrics snapshot -> {args.metrics}")
    if args.trace_out:
        tracer.write(args.trace_out)
        print(f"[serve] trace ({len(tracer.events)} events) -> "
              f"{args.trace_out} (open in ui.perfetto.dev)")


if __name__ == "__main__":
    raise SystemExit(main())
