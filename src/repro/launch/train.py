"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --steps 50 [--autotune] [--ckpt-dir /tmp/ckpt]

On this CPU container ``--smoke`` selects the reduced config of the
same family; on a TPU fleet the full config + production mesh apply
unchanged (the Trainer/step factory is the one the dry-run lowered).
``--autotune`` first runs the AE-LLM search (Algorithm 1) for the
deployment scenario and applies the recommended EfficiencyConfig.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.apply import apply_efficiency_config, apply_to_params
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import LM
from repro.optim.adamw import cosine_schedule
from repro.peft.lora import trainable_mask
from repro.sharding.rules import make_param_shardings
from repro.train.loop import Trainer


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the same family (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", default="none",
                    choices=["none", "topk", "int8"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--autotune", action="store_true",
                    help="run AE-LLM (Algorithm 1) and apply c*")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.with_(max_seq_len=max(cfg.max_seq_len, args.seq_len))

    mask = None
    if args.autotune:
        from repro.core.evaluator import Evaluator
        from repro.core.features import TaskSpec
        from repro.core.costmodel import TIERS
        from repro.core.tuner import AutoTuner, recommend
        from repro.core.space import space_for_family
        task = TaskSpec("lm", "understanding", 0.5, args.seq_len)
        ev = Evaluator(cfg, task, TIERS["datacenter"], seed=args.seed)
        tuner = AutoTuner(ev, mask=space_for_family(cfg.family),
                          generations=8, pop_size=24, refine_iters=1,
                          seed=args.seed)
        report = tuner.run()
        eff, obj = recommend(report.archive)
        print(f"[train] AE-LLM selected: {eff} (predicted obj {obj})")
        cfg = apply_efficiency_config(cfg, eff)

    lm = LM(cfg)
    mesh = make_host_mesh(model=args.model_parallel) \
        if args.model_parallel > 1 else None
    pipe = SyntheticLMData(cfg.vocab_size, args.seq_len, args.global_batch,
                           seed=args.seed)
    lr = cosine_schedule(args.lr, args.warmup, args.steps)
    trainer = Trainer(lm, pipe, lr=lr, ckpt_dir=args.ckpt_dir, mesh=mesh,
                      num_microbatches=args.microbatches,
                      compress=args.compress, ckpt_every=args.ckpt_every)
    params = trainer.init_or_resume(jax.random.PRNGKey(args.seed))
    if args.autotune:
        params = apply_to_params(params, eff, jax.random.PRNGKey(args.seed + 1))
        mask = trainable_mask(params, eff.ft.method) \
            if eff.ft.method != "full" else None
        trainer.set_params(params, mask=mask,
                           num_microbatches=args.microbatches)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.global_batch}×{args.seq_len}")
    history = trainer.run(args.steps)
    first = history[0]["loss"] if history else float("nan")
    last = history[-1]["loss"] if history else float("nan")
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({len(trainer.watchdog.events)} straggler events)")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
