"""What the engine computed in each step, for the requests the client
sent, read from the program's per-request records before and after the
step: each prefill chunk as ``(start, width)`` and each decode token as
the number of keys its query attended.  The FLOP and byte functions
turn these into the work a step needed."""
from __future__ import annotations

import dataclasses
import time
from typing import List

from benchlib import flops


@dataclasses.dataclass
class StepWork:
    t0: float
    t1: float
    chunks: list          # (start, width, ends_prompt)
    decode_keys: list     # keys attended by each decode token


class WorkLog:
    """``on_step`` hook for :class:`benchlib.client.Client`."""

    def __init__(self, client, clock=time.perf_counter):
        self.client = client
        self.clock = clock
        self.steps: List[StepWork] = []
        self._before = None

    def __call__(self, when: str) -> None:
        reg = self.client.eng.registry
        live = [r for r in self.client.records if r.t_done is None]
        if when == "before":
            self._t0 = self.clock()
            self._before = {
                r.rid: (reg[r.rid].progress, len(reg[r.rid].out_tokens),
                        reg[r.rid].t_admit is not None) for r in live}
            return
        chunks, keys = [], []
        for r in live:
            q = reg[r.rid]
            p0, n0, admitted = self._before.get(r.rid, (0, 0, False))
            start = p0 if admitted else q.prefix_hit_tokens
            plen = len(q.prompt)
            if q.progress > start:
                chunks.append((start, q.progress - start,
                               q.progress >= plen))
            n1 = len(q.out_tokens)
            for j in range(max(n0, 1), n1):
                keys.append(plen + j)
        self.steps.append(StepWork(self._t0, self.clock(), chunks, keys))

    def between(self, lo: float, hi: float) -> List[StepWork]:
        return [s for s in self.steps if lo <= s.t0 and s.t1 <= hi]


def model_flops(m: flops.Dims, steps: List[StepWork], *,
                prefill=True) -> float:
    """Model operations of the decoded tokens in ``steps``, and of the
    prefill chunks with ``prefill``."""
    total = 0
    for s in steps:
        if prefill:
            total += sum(flops.prefill_flops(m, a, w, end)
                         for a, w, end in s.chunks)
        total += sum(flops.decode_flops(m, k) for k in s.decode_keys)
    return float(total)
