"""Speculative-decoding engine layered on the SLO-aware scheduler.

``SpecEngine`` keeps every scheduler behaviour (policy-ordered
admission, prefix caching, chunked prefill, lazy growth, preemption) and
replaces the plain fused-decode dispatch with DRAFT → VERIFY → COMMIT
rounds:

1. **Draft** — a proposer (``repro.spec.drafter``: model-free n-gram
   prompt lookup, or a small draft LM sharing the vocab) suggests up to
   ``k`` next tokens per active slot; the adaptive controller
   (``repro.spec.controller``) picks each slot's ``k`` from its measured
   acceptance EMA via the cost model's speedup prediction.
2. **Verify** — ONE jitted dispatch scores all slots' chunks (last
   accepted token + drafts) with multi-query paged attention
   (``LM.verify_paged`` → ``kernels/paged_attention`` verify variant):
   K+1 query positions against the paged prefix plus the chunk itself,
   fresh K/V held in a bf16 staging cache — the pages are NOT written.
3. **Accept** — exact rejection sampling on device
   (:func:`spec_accept`): greedy rows accept a draft iff it equals the
   target argmax, sampled rows accept with probability p(d) against the
   deterministic proposal and fall back to the renormalized residual —
   the emitted stream is distributed exactly as non-speculative
   decoding, and greedy output is token-identical to it.
4. **Commit / roll back** — only the accepted prefix is written into
   the pages, replaying the baseline's sequential per-token quantized
   writes (``serve/paged.commit_spec_cache``); rejection is a pure
   length truncation (``repro.spec.rollback``).  Shared / prefix-cache-
   held pages are copy-on-written before the round ever writes.

Every verify round costs ONE host sync and emits 1..k+1 tokens per slot;
a round where no slot has drafts (or where EDF deadlines are too tight
to gamble prefill budget on rejected drafts — ``spec_slack_s``) falls
back to the base fused ``decode_block`` dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import PID_ENGINE
from repro.sched.policy import EDF
from repro.sched.scheduler import SchedEngine
from repro.serve.engine import _pow2_bucket
from repro.serve.paged import commit_spec_cache
from repro.spec.controller import AdaptiveDraftController
from repro.spec.drafter import DraftLMDrafter, NgramDrafter
from repro.spec.rollback import ensure_exclusive_tail


def spec_accept(logits, fed, widths, active, temps, remaining, lengths,
                eos: int, max_len: int, key):
    """Exact acceptance for one speculative verify round (device math).

    logits: (S, W, V) target logits — position ``j`` predicts the token
    AFTER ``fed[:, j]``; ``fed[:, 0]`` is the last accepted token and
    ``fed[:, 1:]`` the (deterministic) draft proposals, real up to
    ``widths[s] - 1`` drafts.  Greedy rows (temps <= 0) accept draft
    ``d_j`` iff it equals ``argmax(logits[:, j-1])``; sampled rows run
    exact rejection sampling against the deterministic proposal — accept
    with probability ``p_{j-1}(d_j)``, else emit a sample from the
    renormalized residual (p with ``d_j`` zeroed) — so the emitted
    stream is distributed exactly as target-model sampling (Leviathan et
    al., 2023, for a point-mass draft distribution).  The round's final
    token (correction / bonus) always comes from the target model.

    Emission is then capped EXACTLY like the baseline decode loop: stop
    at the first EOS, at remaining-budget exhaustion, and at
    ``max_len - 1``.  Returns ``(y, n_emit, n_match)``: emitted tokens
    (S, W) (garbage past ``n_emit``), tokens emitted per slot (0 for
    inactive slots), and the pre-cap accepted-draft count (the
    controller's acceptance signal)."""
    s_n, w, v = logits.shape
    key_u, key_r, key_f = jax.random.split(key, 3)
    temps_c = jnp.maximum(temps, 1e-6)[:, None, None]
    probs = jax.nn.softmax(logits / temps_c, axis=-1)            # (S,W,V)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (S,W)

    # --- accept flags: draft at fed col j+1 vs target position j ------
    d = fed[:, 1:]                                               # (S,W-1)
    p_d = jnp.take_along_axis(probs[:, :-1], d[..., None],
                              axis=-1)[..., 0]
    u = jax.random.uniform(key_u, d.shape)
    acc = jnp.where(temps[:, None] > 0, u < p_d, d == greedy[:, :-1])
    real = jnp.arange(1, w)[None, :] < widths[:, None]           # (S,W-1)
    acc = acc & real
    n_match = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)

    # --- emitted tokens ----------------------------------------------
    # col j < n_match: the accepted draft itself; col n_match: residual
    # sample (a real draft was rejected) / fresh target sample (padding
    # column or full acceptance).  Greedy rows are just the argmax row.
    res = probs[:, :-1] * (1.0 - jax.nn.one_hot(d, v, dtype=probs.dtype))
    res = res / jnp.maximum(res.sum(-1, keepdims=True), 1e-30)
    res_tok = jax.random.categorical(
        key_r, jnp.log(jnp.maximum(res, 1e-30)), axis=-1).astype(jnp.int32)
    fresh_tok = jax.random.categorical(key_f, logits / temps_c,
                                       axis=-1).astype(jnp.int32)
    cor = jnp.where(real, res_tok, fresh_tok[:, :-1])
    samp = jnp.concatenate([jnp.where(acc, d, cor), fresh_tok[:, -1:]],
                           axis=1)                               # (S,W)
    y = jnp.where(temps[:, None] > 0, samp, greedy).astype(jnp.int32)

    # --- caps: EOS / budget / max_len, exactly like decode_block ------
    def body(carry, xs):
        alive, n_emit, len_c, rem_c = carry
        j, tok = xs
        can = alive & (j <= n_match)
        n_emit = n_emit + can
        len_c = len_c + can
        rem_c = rem_c - can
        done = can & ((tok == eos) | (rem_c <= 0) | (len_c >= max_len - 1))
        alive = alive & ~done
        return (alive, n_emit, len_c, rem_c), None

    carry = (active, jnp.zeros((s_n,), jnp.int32),
             lengths.astype(jnp.int32), remaining.astype(jnp.int32))
    (alive, n_emit, _, _), _ = jax.lax.scan(body, carry,
                                            (jnp.arange(w), y.T))
    return y, n_emit, n_match


@dataclasses.dataclass
class SpecStats:
    verify_steps: int = 0           # draft->verify->commit rounds
    slot_steps: int = 0             # (active slot, round) pairs verified
    drafts_proposed: int = 0
    drafts_accepted: int = 0        # capped at what was actually emitted
    spec_tokens: int = 0            # tokens emitted by verify rounds
    fallback_steps: int = 0         # plain decode blocks (no drafts)
    skipped_urgent: int = 0         # rounds gated off by EDF urgency
    cow_pages: int = 0              # shared tail pages copy-on-written


class SpecEngine(SchedEngine):
    """Scheduler + speculative decoding (see module docstring).

    ``spec``: "ngram" (default) | "draft" | "none" (plain SchedEngine
    behaviour).  ``draft_lm``/``draft_params`` supply the draft model
    for the "draft" arm (see ``repro.spec.drafter.draft_config_of``;
    passing the target model itself is self-speculation — a useful
    oracle).  ``spec_slack_s`` disables speculation for a tick whenever
    a queued request's EDF deadline is closer than the slack: rejected
    drafts would waste decode budget the urgent request needs."""

    def __init__(self, lm, params, *, spec: str = "ngram", draft_k: int = 4,
                 draft_lm=None, draft_params=None, adaptive: bool = True,
                 ngram_n: int = 3, spec_slack_s: float = None, **kw):
        super().__init__(lm, params, **kw)
        if spec not in ("none", "ngram", "draft"):
            raise ValueError(f"unknown spec arm {spec!r}")
        self.spec_arm = spec
        self.k_max = int(draft_k)
        self.w_max = self.k_max + 1
        if spec == "ngram":
            self.drafter = NgramDrafter(k_max=self.k_max, n_max=ngram_n)
        elif spec == "draft":
            if draft_lm is None or draft_params is None:
                raise ValueError("spec='draft' needs draft_lm/draft_params")
            mp = 1 if self.mesh is None \
                else int(self.mesh.shape.get("model", 1))
            if mp > 1:
                # the draft LM serves on the same mesh: TP-shard its
                # weights and mark its cfg so its dense matmuls f32-
                # accumulate too (drafts only steer acceptance — output
                # identity comes from verify — but a replicated draft
                # would serialize every shard on identical work)
                from repro.sharding.rules import make_param_shardings
                draft_lm = type(draft_lm)(draft_lm.cfg.with_(
                    model_parallel=mp))
                draft_params = jax.device_put(
                    draft_params,
                    make_param_shardings(draft_params, self.mesh))
            self.drafter = DraftLMDrafter(
                draft_lm, draft_params, n_slots=self.n_slots,
                max_len=self.max_len + 2 * self.w_max, k_max=self.k_max)
        else:
            self.drafter = None
        self.controller = AdaptiveDraftController(
            self.n_slots, k_max=self.k_max, arm=spec, adaptive=adaptive,
            cfg=lm.cfg)
        self.spec_slack_s = spec_slack_s
        self.spec_stats = SpecStats()
        # fn-backed registry bridges (SpecStats stays the writer)
        m = self.metrics
        for f in dataclasses.fields(SpecStats):
            m.counter(f"spec_{f.name}_total", f.name.replace("_", " "),
                      fn=lambda f=f.name: getattr(self.spec_stats, f))
        m.gauge("spec_arm_info", "1, labelled with the speculation arm",
                fn=lambda: 1.0, arm=self.spec_arm)
        self._verify_jit = jax.jit(self._verify_impl, donate_argnums=(1,),
                                   static_argnames=("max_pages",))
        from repro.kernels.paged_attention.ops import check_paged_kernels
        check_paged_kernels(self.cache, lm.cfg.attention.heads_padded,
                            self.lm.dtype, decode=False,
                            widths=(self.w_max,))

    # ------------------------------------------------------------------
    # device program

    def _verify_impl(self, params, cache, fed, lengths, widths, active,
                     remaining, temps, key, max_pages=None):
        """One verify round: multi-query scoring of every slot's chunk,
        exact accept/reject, then commit of ONLY the accepted prefix —
        the paged pools (incl. quantized page scales) evolve exactly as
        ``n_emit`` baseline decode steps would have written them.
        ``max_pages`` (static, pow2-bucketed) narrows the prefix-extend
        kernel's page grid to the batch's deepest prefix instead of the
        full slot horizon — the same narrowing the scheduler's chunked
        prefill continuation got in PR 5."""
        s_n, w = fed.shape
        stage = self.lm.init_cache(s_n, w, kv_dtype="bfloat16")
        logits, stage = self.lm.verify_paged(params, fed, cache, stage,
                                             lengths, widths,
                                             max_pages=max_pages)
        y, n_emit, n_match = spec_accept(logits, fed, widths, active,
                                         temps, remaining, lengths,
                                         self.eos, self.max_len, key)
        new_cache = commit_spec_cache(cache, stage, lengths, n_emit)
        new_lengths = lengths + n_emit
        new_remaining = remaining - n_emit
        idx = jnp.maximum(n_emit - 1, 0)
        last = jnp.take_along_axis(y, idx[:, None], axis=1)[:, 0]
        last = jnp.where(n_emit > 0, last, fed[:, 0])
        done = (last == self.eos) | (new_remaining <= 0) \
            | (new_lengths >= self.max_len - 1)
        new_active = active & ~done
        # requant accounting rides the round's output tuple out at the
        # one existing sync (see serve.engine._kv_scale_change_count)
        from repro.serve.engine import _kv_scale_change_count
        nrq = _kv_scale_change_count(cache, new_cache)
        return (new_cache, y, n_emit, n_match, last, new_lengths,
                new_active, new_remaining, nrq)

    # ------------------------------------------------------------------
    # host loop

    def _spec_allowed(self) -> bool:
        """EDF urgency gate: don't gamble the decode budget on drafts
        while a queued request's deadline is within ``spec_slack_s``."""
        if self.spec_slack_s is None or not isinstance(self.policy, EDF):
            return True
        now = time.perf_counter()
        return all(self.policy.deadline(r) - now >= self.spec_slack_s
                   for r in self.queue)

    def _ensure_decode_pages(self) -> None:
        """A verify round writes up to ``w_max`` accepted tokens past
        each slot's length — reserve that horizon instead of (only) the
        base decode block."""
        if self.spec_arm == "none":
            return super()._ensure_decode_pages()
        grow_by = max(self.decode_block, self.w_max)
        for slot in list(self.active):
            if slot not in self.active:      # preempted by an earlier grow
                continue
            horizon = min(int(self.lengths[slot]) + grow_by, self.max_len)
            need = self.alloc.pages_needed(horizon, self.page_size) \
                - len(self.alloc.owned(slot))
            if need > 0:
                self._grow(slot, need)

    def _dispatch_decode(self, emitted: list) -> None:
        if self.spec_arm == "none":
            return super()._dispatch_decode(emitted)
        if self.ladder is not None and self.ladder.spec_off:
            # degradation rung >= spec_off: stop gambling decode budget
            # on drafts; plain fused decode is token-identical for the
            # greedy stream, just slower per emitted token
            self.spec_stats.fallback_steps += 1
            return super()._dispatch_decode(emitted)
        if not self._spec_allowed():
            self.spec_stats.skipped_urgent += 1
            self.spec_stats.fallback_steps += 1
            return super()._dispatch_decode(emitted)
        return self._spec_round(emitted)

    def _spec_round(self, emitted: list) -> None:
        # chaos hook BEFORE any draft/verify state is built: a raise
        # here preempts cleanly (same contract as the decode hook)
        self._maybe_inject("spec_round")
        reqs = list(self.active.items())
        # --- draft ----------------------------------------------------
        batch = []
        for slot, req in reqs:
            room = min(int(self.remaining[slot]) - 1,
                       self.max_len - 2 - int(self.lengths[slot]))
            k = min(self.controller.k_for(slot), max(room, 0))
            hist = np.concatenate([np.asarray(req.prompt, np.int32),
                                   np.asarray(req.out_tokens, np.int32)])
            batch.append((slot, req.rid, hist, k))
        t_round0 = t0 = time.perf_counter()   # spec_round span covers
        with self._mesh_ctx():                # draft + verify + commit
            proposals = self.drafter.propose_batch(batch, self.k_max)
        if self.injector is not None and self.injector.enabled:
            # degenerate-proposal injection: exact verify/accept must
            # reject garbage drafts without perturbing the greedy stream
            proposals = self.injector.mangle_proposals(proposals,
                                                       self.k_max)
        # drafting is decode-phase work (the draft-LM arm is a real
        # dispatch + sync): charge it, or the benchmark's phase split
        # would overstate spec decode throughput
        t_draft1 = time.perf_counter()
        self.t_decode_s += t_draft1 - t0
        fed = np.zeros((self.n_slots, self.w_max), np.int32)
        widths = np.zeros((self.n_slots,), np.int32)
        ndraft = np.zeros((self.n_slots,), np.int32)
        active_mask = np.zeros((self.n_slots,), bool)
        for slot, req in reqs:
            drafts = proposals.get(slot)
            nd = 0 if drafts is None else len(drafts)
            fed[slot, 0] = self.last_tok[slot]
            if nd:
                fed[slot, 1:1 + nd] = drafts
            widths[slot] = 1 + nd
            ndraft[slot] = nd
            active_mask[slot] = True
        prof = self.profiler
        if prof.enabled:
            prof.record("draft_propose", t0, t_draft1,
                        tokens=int(ndraft.sum()), rows=len(reqs),
                        bucket=self.k_max, ctx=int(self.lengths.max()))
        if ndraft.sum() == 0:            # nothing to verify: plain decode
            self.spec_stats.fallback_steps += 1
            return super()._dispatch_decode(emitted)
        # --- shared-tail guard (copy-on-write; normally a no-op) ------
        for slot, _req in reqs:
            start = int(self.lengths[slot])
            row_before = self.alloc.table[slot].copy()
            self.cache = ensure_exclusive_tail(
                self.cache, self.alloc, slot, start,
                min(start + int(widths[slot]), self.max_len),
                self.page_size)
            self.spec_stats.cow_pages += int(
                np.sum(row_before != self.alloc.table[slot]))
        # --- verify + commit (one dispatch, one sync) -----------------
        self.key, sub = jax.random.split(self.key)
        t0 = time.perf_counter()
        # page grid sized by the deepest prefix across slots (pow2-
        # bucketed static), not the slot horizon — the chunk K/V is
        # fresh (staged, never paged), so only positions < lengths[s]
        # are ever read from the pools
        mp = min(_pow2_bucket(-(-int(self.lengths.max())
                               // self.page_size), lo=1),
                 self.alloc.max_pages_per_slot)
        with self._mesh_ctx():
            out = self._verify_jit(
                self.params, self.cache, jnp.asarray(fed),
                jnp.asarray(self.lengths), jnp.asarray(widths),
                jnp.asarray(active_mask), jnp.asarray(self.remaining),
                jnp.asarray(self.temps), sub, max_pages=mp)
        self.cache = out[0]
        y, n_emit, n_match, last, lengths, active, remaining, nrq = (
            np.array(x) for x in out[1:])
        self.sync_count += 1
        now = time.perf_counter()
        self.t_decode_s += now - t0
        if prof.enabled:
            prof.record("spec_round", t0, now, tokens=int(n_emit.sum()),
                        rows=len(reqs), bucket=self.w_max,
                        ctx=int(self.lengths.max()),
                        cost=(self._verify_jit,
                              (self.params, self.cache, fed, self.lengths,
                               widths, active_mask, self.remaining,
                               self.temps, sub), {"max_pages": mp}))
        self.spec_stats.verify_steps += 1
        self._c_requant.inc(int(nrq))
        self._c_tokens.inc(int(n_emit.sum()))
        self.lengths, self.last_tok, self.remaining = (lengths, last,
                                                       remaining)
        tr = self.tracer
        if tr.enabled:
            tr.complete("spec_round", 0, t_round0, now, pid=PID_ENGINE,
                        args={"rows": len(reqs),
                              "proposed": int(ndraft.sum()),
                              "tokens": int(n_emit.sum())})
        for slot, req in reqs:
            ne = int(n_emit[slot])
            for t in y[slot, :ne]:
                req.out_tokens.append(int(t))
                emitted.append((req.rid, int(t)))
            req.pos += ne
            self.controller.update(slot, int(ndraft[slot]),
                                   int(n_match[slot]))
            self.spec_stats.slot_steps += 1
            self.spec_stats.drafts_proposed += int(ndraft[slot])
            acc = min(int(n_match[slot]), max(ne - 1, 0))
            self.spec_stats.drafts_accepted += acc
            self.spec_stats.spec_tokens += ne
            if tr.enabled:
                tr.complete("spec_round", req.rid, t_round0, now,
                            args={"proposed": int(ndraft[slot]),
                                  "accepted": acc, "tokens": ne})
        for slot, _req in reqs:
            if not active[slot]:
                self._retire(slot, now)

    def _retire(self, slot: int, now: float):
        self.controller.reset(slot)
        super()._retire(slot, now)

    def _cancel_slot(self, slot: int, now: float, outcome: str):
        self.controller.reset(slot)
        super()._cancel_slot(slot, now, outcome)

    # ------------------------------------------------------------------
    def telemetry(self, since=None) -> dict:
        out = super().telemetry(since)
        snap = (self.metrics.snapshot() if since is None
                else self.metrics.delta(since))
        c = snap["counters"]
        st = {f.name: int(c.get(f"spec_{f.name}_total", 0))
              for f in dataclasses.fields(SpecStats)}
        st["arm"] = self.spec_arm
        st["k_max"] = self.k_max
        st["acceptance_rate"] = (
            round(st["drafts_accepted"] / st["drafts_proposed"], 4)
            if st["drafts_proposed"] else None)
        # per SLOT-step means: the baseline decode loop emits exactly 1
        # token per active slot per step, so tokens_per_step > 1 is the
        # decode-step reduction speculation bought
        st["accepted_per_step"] = (
            round(st["drafts_accepted"] / st["slot_steps"], 3)
            if st["slot_steps"] else None)
        st["tokens_per_step"] = (
            round(st["spec_tokens"] / st["slot_steps"], 3)
            if st["slot_steps"] else None)
        st["controller"] = self.controller.stats()
        out["spec"] = st
        return out
