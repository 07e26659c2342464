"""Faults planted in the program's timed path, for the check to catch.

    python3 bench/harness_tests/faults.py <fault> --workload <name> \
        --seed <n> --seconds <s>

plants ``<fault>`` and runs ``bench/run.py`` with the arguments that
follow, on the chip, at the cell's own size; its ``correct`` must come
out false.  The tests plant the same faults at smoke size.

- ``token_altered``: greedy sampling hands back the token after the
  best one, where the token is produced;
- ``state_unchanged``: every decode step hands back the cache it was
  given, so the step's keys and values are never written;
- ``rope_unscaled``: the program is built from the file without its
  ``rope_scaling``, so it serves unscaled positions where the file
  scales them (a cell whose file has no ``rope_scaling`` cannot have
  this fault).
"""
import sys
from pathlib import Path

import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
FAULTS = ("token_altered", "state_unchanged", "rope_unscaled")


def _next_token(logits, temps, key):
    return ((jnp.argmax(logits, axis=-1) + 1) % logits.shape[-1]).astype(
        jnp.int32)


def patches(fault: str) -> list:
    """``[(owner, attribute, replacement)]`` that plant ``fault``."""
    if fault == "token_altered":
        import repro.sched.scheduler as sch
        import repro.serve.engine as eng
        return [(eng, "_sample_batch", _next_token),
                (sch, "_sample_batch", _next_token)]
    if fault == "state_unchanged":
        from repro.models.model import LM
        step = LM.decode_step

        def stale(self, params, token, cache, pos):
            logits, _ = step(self, params, token, cache, pos)
            return logits, cache
        return [(LM, "decode_step", stale)]
    if fault == "rope_unscaled":
        from benchlib import system
        mapping = system.program_config

        def unscaled(cfg):
            return mapping({k: v for k, v in cfg.items()
                            if k != "rope_scaling"})
        return [(system, "program_config", unscaled)]
    raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")


def main(argv) -> int:
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import run
    for owner, attr, value in patches(argv[0]):
        setattr(owner, attr, value)
    return run.main(argv[1:])


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
