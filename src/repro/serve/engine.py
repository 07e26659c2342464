"""Continuous-batching serving engines.

Slot-based (JetStream-style for TPU): a fixed decode batch of ``n_slots``;
each incoming request is prefilled into a free slot, then all active slots
decode in lock-step.  Finished slots (EOS or max_new_tokens) free
immediately and new requests join without draining the batch — that *is*
continuous batching.

Two engines share the Request/registry surface:

``Engine`` — the eager baseline: contiguous per-slot cache regions,
batch-1 prefill per admission, host-side sampling, and one device→host
sync per generated token.

``PagedEngine`` — the hot path (decode_attn_impl="paged_pallas"): KV lives
in paged pools driven by the Pallas flash-decoding kernel
(kernels/paged_attention); sampling happens on device (greedy +
temperature via a per-step folded ``jax.random`` key); decode runs
``decode_block`` tokens per dispatch inside one jitted ``lax.scan`` with
per-slot EOS/budget masks, so the host syncs once per block instead of
once per token (``sync_count`` audits this); and queued requests are
admitted in ONE batched, length-bucketed prefill call instead of a Python
loop of batch-1 launches.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import DispatchProfiler
from repro.obs.trace import (PID_ENGINE, SPAN_COUNT, SPAN_COUNT_HELP,
                             SPAN_SECONDS, SPAN_SECONDS_HELP, Tracer, span)
from repro.resil.errors import OUTCOMES
from repro.serve.paged import (PAGE, OutOfPagesError, PageAllocator,
                               scatter_prefill_cache, set_block_table_rows)


def _kv_scale_change_count(before, after):
    """Device-side requant accounting: number of quantized page-scale
    entries (page, kv_head) whose value differs between two cache
    pytrees — a changed entry means that page was re-scaled by a write
    this dispatch (fresh-page reset or an amax-growth requantize).
    Constant 0 for bf16 pools (no scale leaves).  Pure array math inside
    the existing jitted dispatch; the count rides the dispatch's output
    tuple out at the block-boundary sync, costing zero extra host
    syncs."""
    from jax.tree_util import keystr, tree_flatten_with_path
    b = {keystr(p): x for p, x in tree_flatten_with_path(before)[0]
         if "_scales" in keystr(p)}
    total = jnp.zeros((), jnp.int32)
    for p, x in tree_flatten_with_path(after)[0]:
        k = keystr(p)
        if k in b:
            total = total + jnp.sum((b[k] != x).astype(jnp.int32))
    return total


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (plen,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: list = dataclasses.field(default_factory=list)
    slot: int = -1
    pos: int = 0                       # next position to write
    done: bool = False
    t_submit: float = 0.0
    t_admit: Optional[float] = None    # first slot grant (queue wait end)
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # --- scheduler surface (repro.sched; inert under the base engines) ---
    slo_ttft: Optional[float] = None   # per-request TTFT target, seconds
    slo_tpot: Optional[float] = None   # per-request TPOT target, seconds
    prefix_hit_tokens: int = 0         # prompt tokens served from cache
    preemptions: int = 0
    progress: int = 0                  # prefill tokens already cached
    rejected: bool = False             # admission-time SLO-infeasible drop
    # --- resilience surface (repro.resil; inert without chaos/ladder) ----
    outcome: Optional[str] = None      # one of resil.OUTCOMES, set at retire
    retries: int = 0                   # transient-fault recovery attempts
    not_before: float = 0.0            # backoff gate for re-admission
    retry_after_s: Optional[float] = None   # shed hint for the client


class _EngineBase:
    """Request intake + slot bookkeeping shared by both engines."""

    def __init__(self, lm, params, *, n_slots: int, max_len: int,
                 eos_id: int, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 profiler: Optional[DispatchProfiler] = None):
        self.lm = lm
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos = eos_id
        self.free = deque(range(n_slots))
        self.active: Dict[int, Request] = {}     # slot -> req
        self.queue: deque[Request] = deque()
        self.registry: Dict[int, Request] = {}   # rid -> req (all ever seen)
        self._next_rid = 0
        # phase wall-clock (device dispatch + its host sync), so the
        # benchmark can report prefill-phase vs decode-phase tokens/sec
        # separately instead of hiding prefill behind decode throughput
        self.t_prefill_s = 0.0
        self.t_decode_s = 0.0
        # observability: a per-engine registry (fn-backed over the
        # accumulators above where one exists) and an off-by-default
        # tracer; every timestamp below is a host clock the engine
        # already reads, so instrumentation adds zero device syncs
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        # per-dispatch device-time profiling (off by default): record()
        # only consumes the t0/t1 host timestamps taken below anyway, so
        # sync_count and token streams are identical with it on or off
        self.profiler = (profiler if profiler is not None
                         else DispatchProfiler(enabled=False))
        self.profiler.bind(lm.cfg,
                           model_parallel=getattr(lm.cfg, "model_parallel",
                                                  1))
        m = self.metrics
        self._c_submitted = m.counter(
            "serve_requests_submitted_total", "requests accepted by submit()")
        self._c_retired = m.counter(
            "serve_requests_retired_total",
            "requests finished (incl. admission-time rejects)")
        self._c_tokens = m.counter(
            "serve_tokens_emitted_total", "tokens appended across requests")
        self._c_outcome = m.counter(
            "resil_requests_total",
            "request retirements by terminal outcome")
        for o in OUTCOMES:       # pre-create every series at 0
            self._c_outcome.inc(0.0, outcome=o)
        self._h_queue = m.histogram(
            "serve_queue_wait_seconds", "submit -> first slot grant")
        self._h_ttft = m.histogram(
            "serve_ttft_seconds", "submit -> first token")
        self._h_tpot = m.histogram(
            "serve_tpot_seconds", "mean per-token latency after the first")
        m.counter("serve_phase_seconds_total",
                  "dispatch+sync wall-clock by phase",
                  fn=lambda: self.t_prefill_s, phase="prefill")
        m.counter("serve_phase_seconds_total",
                  fn=lambda: self.t_decode_s, phase="decode")
        m.gauge("serve_queue_depth", "requests waiting for a slot",
                fn=lambda: len(self.queue))
        m.gauge("serve_slots_active", "slots currently decoding",
                fn=lambda: len(self.active))

    # ------------------------------------------------------------------
    # observability hooks (host-clock only; no device syncs)

    def _obs_submit(self, req: Request):
        self._c_submitted.inc()
        tr = self.tracer
        if tr.enabled:
            tr.name_thread(req.rid, f"req {req.rid}")
            tr.begin("request", req.rid, ts=req.t_submit,
                     args={"rid": req.rid, "prompt_tokens": len(req.prompt),
                           "max_new_tokens": req.max_new_tokens})
            tr.begin("queue", req.rid, ts=req.t_submit)

    def _obs_admit(self, req: Request, now: float, first: bool, **args):
        if first:
            self._h_queue.observe(now - req.t_submit)
        self.tracer.end("queue", req.rid, ts=now, args=args or None)

    def _obs_first(self, req: Request):
        if req.t_first is not None:
            self._h_ttft.observe(req.t_first - req.t_submit)

    def _obs_retire(self, req: Request):
        self._c_retired.inc()
        # every request retires with exactly ONE outcome: recovery paths
        # (repro.resil) set it explicitly before retiring; the default
        # vocabulary maps the legacy admission-reject to "shed" and a
        # normal completion to "ok"
        if req.outcome is None:
            req.outcome = "shed" if req.rejected else "ok"
        self._c_outcome.inc(outcome=req.outcome)
        if (req.t_done is not None and req.t_first is not None
                and len(req.out_tokens) > 1):
            self._h_tpot.observe((req.t_done - req.t_first)
                                 / (len(req.out_tokens) - 1))
        self.tracer.end("request", req.rid, ts=req.t_done,
                        args={"tokens": len(req.out_tokens),
                              "preemptions": req.preemptions,
                              "rejected": req.rejected,
                              "outcome": req.outcome})

    def submit(self, prompt, **kw) -> int:
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens >= max_len={self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      t_submit=time.perf_counter(), **kw)
        self.queue.append(req)
        self.registry[rid] = req
        self._obs_submit(req)
        return rid

    def step(self) -> List[tuple]:
        raise NotImplementedError

    def run_to_completion(self) -> Dict[int, Request]:
        while self.queue or self.active:
            self.step()
        return dict(self.registry)


class Engine(_EngineBase):
    def __init__(self, lm, params, *, n_slots: int = 4, max_len: int = 512,
                 eos_id: int = -1, seed: int = 0, metrics=None, tracer=None,
                 profiler=None):
        super().__init__(lm, params, n_slots=n_slots, max_len=max_len,
                         eos_id=eos_id, metrics=metrics, tracer=tracer,
                         profiler=profiler)
        self.rng = np.random.default_rng(seed)
        self.cache = lm.init_cache(n_slots, max_len)

        self._prefill_one = jax.jit(self._prefill_impl)
        self._decode = jax.jit(lm.decode_step)

    # ------------------------------------------------------------------
    def _prefill_impl(self, params, cache, tokens, slot):
        """Prefill a single slot: run batch-1 prefill and splice its cache
        entries into the engine cache at batch index ``slot``."""
        sub_cache = jax.tree.map(
            lambda c: jax.lax.dynamic_slice_in_dim(
                c, slot, 1, axis=self._batch_axis(c)), cache)
        logits, new_sub = self.lm.prefill(params, tokens[None], sub_cache)
        cache = jax.tree.map(
            lambda c, ns: jax.lax.dynamic_update_slice_in_dim(
                c, ns.astype(c.dtype), slot, axis=self._batch_axis(c)),
            cache, new_sub)
        return logits[0], cache

    @staticmethod
    def _batch_axis(leaf) -> int:
        # stacked group caches: (G, B, ...) -> batch axis 1; else 0
        return 1 if leaf.ndim >= 2 else 0

    def _sample(self, logits: np.ndarray, temp: float) -> int:
        if temp <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / temp)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    # ------------------------------------------------------------------
    def step(self) -> List[tuple]:
        """One engine tick: admit waiting requests into free slots
        (prefill), then one batched decode step.  Returns
        [(rid, token), ...] emitted this tick."""
        emitted = []
        # admit
        while self.queue and self.free:
            req = self.queue.popleft()
            slot = self.free.popleft()
            req.slot = slot
            req.t_admit = time.perf_counter()
            plen = len(req.prompt)
            logits, self.cache = self._prefill_one(
                self.params, self.cache, jnp.asarray(req.prompt),
                jnp.int32(slot))
            logits = np.asarray(logits)
            t1 = time.perf_counter()
            self.t_prefill_s += t1 - req.t_admit
            prof = self.profiler
            if prof.enabled:
                prof.record(
                    "admit", req.t_admit, t1, tokens=plen, rows=1,
                    bucket=plen, ctx=plen,
                    cost=(self._prefill_one,
                          (self.params, self.cache,
                           jax.ShapeDtypeStruct((plen,), jnp.int32),
                           jax.ShapeDtypeStruct((), jnp.int32)), None))
            self._obs_admit(req, req.t_admit, first=True)
            tok = self._sample(logits, req.temperature)
            req.out_tokens.append(tok)
            req.pos = plen
            req.t_first = time.perf_counter()
            self.tracer.complete("prefill", req.rid, req.t_admit,
                                 req.t_first, args={"tokens": plen,
                                                    "emitted": 1})
            self._obs_first(req)
            self._c_tokens.inc()
            emitted.append((req.rid, tok))
            if (tok == self.eos or req.max_new_tokens <= 1
                    or req.pos >= self.max_len - 1):
                req.done = True           # EOS/budget hit on first token
                req.t_done = req.t_first
                self.free.append(slot)
                self._obs_retire(req)
            else:
                self.active[slot] = req

        if not self.active:
            return emitted

        # batched decode: every slot steps (inactive slots decode garbage
        # into their own region — masked out below)
        tokens = np.zeros((self.n_slots,), np.int32)
        pos_by_slot = np.zeros((self.n_slots,), np.int32)
        for slot, req in self.active.items():
            tokens[slot] = req.out_tokens[-1]
            pos_by_slot[slot] = req.pos
        # lock-step position: engine decodes per-slot positions via the max;
        # per-slot masking happens inside attention via each slot's cache
        # contents.  We decode each active slot at its own pos by running
        # the step with per-slot positions (vector pos).
        t0 = time.perf_counter()
        logits, self.cache = self._decode(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.asarray(pos_by_slot))
        logits = np.asarray(logits)
        t1 = time.perf_counter()
        self.t_decode_s += t1 - t0
        tr = self.tracer
        if tr.enabled:
            tr.complete("decode_step", 0, t0, t1, pid=PID_ENGINE,
                        args={"rows": len(self.active)})
            tr.counter("utilization", {"queue_depth": len(self.queue),
                                       "slots_active": len(self.active)},
                       ts=t1)
        prof = self.profiler
        if prof.enabled:
            prof.record("decode_block", t0, t1, tokens=len(self.active),
                        rows=len(self.active), steps=1, bucket=1,
                        ctx=int(pos_by_slot.max()),
                        cost=(self._decode,
                              (self.params, tokens, self.cache,
                               pos_by_slot), None))

        for slot, req in list(self.active.items()):
            tok = self._sample(logits[slot], req.temperature)
            req.out_tokens.append(tok)
            req.pos += 1
            self._c_tokens.inc()
            if tr.enabled:
                tr.complete("decode_step", req.rid, t0, t1,
                            args={"tokens": 1})
            emitted.append((req.rid, tok))
            if (tok == self.eos or
                    len(req.out_tokens) >= req.max_new_tokens or
                    req.pos >= self.max_len - 1):
                req.done = True
                req.t_done = time.perf_counter()
                del self.active[slot]
                self.free.append(slot)
                self._obs_retire(req)
        return emitted


# ---------------------------------------------------------------------------
# Open-loop driving (shared by launch/serve and the benchmark)


def engine_busy(eng) -> bool:
    """True while the engine has queued or in-flight work (including a
    scheduler's mid-prefill slots)."""
    return bool(eng.queue or eng.active or getattr(eng, "_prefilling",
                                                   None))


def run_open_loop(eng, prompts, offsets, **submit_kw):
    """Submit ``prompts[i]`` at wall-clock offset ``offsets[i]`` seconds
    from now (open-loop arrivals), stepping the engine between arrivals
    and sleeping only when it is idle.  Returns the request ids in
    prompt order; drive results out of ``eng.registry``."""
    t0 = time.perf_counter()
    pending = sorted(zip(offsets, range(len(prompts))))
    ids: List[Optional[int]] = [None] * len(prompts)
    while pending or engine_busy(eng):
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, i = pending.pop(0)
            ids[i] = eng.submit(prompts[i], **submit_kw)
        if not engine_busy(eng):
            if pending:
                time.sleep(min(pending[0][0] - now, 0.005))
            continue
        eng.step()
    return ids


# ---------------------------------------------------------------------------
# Paged engine


def _sample_batch(logits: jax.Array, temps: jax.Array,
                  key: jax.Array) -> jax.Array:
    """Device-side sampling: greedy where temps<=0, else temperature
    sampling via jax.random.categorical.  logits: (S,V); temps: (S,)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / t, axis=-1).astype(
        jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class PagedEngine(_EngineBase):
    """Continuous batching over a paged KV cache with a host-sync-free
    inner loop (see module docstring).  Requires an attention-only
    decoder (no MLA / SSM blocks / cross-attention / sliding window)."""

    def __init__(self, lm, params, *, n_slots: int = 4, max_len: int = 512,
                 eos_id: int = -1, seed: int = 0, page_size: int = PAGE,
                 decode_block: int = 8, n_pages: Optional[int] = None,
                 mesh=None, metrics=None, tracer=None, profiler=None,
                 injector=None):
        cfg = lm.cfg
        a = cfg.attention
        assert a is not None and a.kind != "mla" and a.window is None \
            and cfg.encoder is None and cfg.cross_attn_every == 0 \
            and all(k == "attn" for k in cfg.block_pattern), \
            "PagedEngine needs an attention-only decoder"
        # sharded serving: a mesh with a "model" axis > 1 turns on
        # kv-head-sharded paged attention (kernels/paged_attention/ops),
        # TP weight sharding (sharding/rules) and sequence-parallel
        # chunked prefill; mesh=None is byte-identical to the old path
        self.mesh = mesh
        mp = 1 if mesh is None else int(mesh.shape.get("model", 1))
        cfg_kw = {}
        if cfg.decode_attn_impl != "paged_pallas":
            cfg_kw["decode_attn_impl"] = "paged_pallas"
        if mp > 1:
            cfg_kw.update(model_parallel=mp, seq_parallel=True)
        if cfg_kw:
            lm = type(lm)(cfg.with_(**cfg_kw))
        super().__init__(lm, params, n_slots=n_slots, max_len=max_len,
                         eos_id=eos_id, metrics=metrics, tracer=tracer,
                         profiler=profiler)
        self.page_size = page_size
        self.decode_block = decode_block
        from repro.kvcache import paged_pool_shape
        pages_per_slot, default_pages = paged_pool_shape(n_slots, max_len,
                                                         page_size)
        if n_pages is None:
            n_pages = default_pages                  # incl. null page 0
        self.alloc = PageAllocator(n_pages, pages_per_slot, n_slots)
        # chaos harness (repro.resil.inject): hooks at the allocator and
        # the host side of every dispatch.  None / disabled is
        # sync-count- and token-identical to the pre-resilience engine.
        self.injector = injector
        if injector is not None:
            self.alloc.injector = injector
            injector.register_metrics(self.metrics)
        self.cache = lm.init_paged_cache(n_slots, n_pages, pages_per_slot,
                                         page_size=page_size)
        if mp > 1:
            from repro.serve.paged import paged_cache_shardings
            from repro.sharding.rules import make_param_shardings
            self.params = jax.device_put(
                params, make_param_shardings(params, mesh))
            self.cache = jax.device_put(
                self.cache, paged_cache_shardings(self.cache, mesh))
        self.lengths = np.zeros((n_slots,), np.int32)
        self.temps = np.zeros((n_slots,), np.float32)
        self.remaining = np.zeros((n_slots,), np.int32)
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.key = jax.random.PRNGKey(seed)
        self.sync_count = 0                      # device->host transitions
        self.steps_dispatched = 0                # decode steps traced+run
        m = self.metrics
        m.counter("serve_host_syncs_total", "device->host sync points",
                  fn=lambda: self.sync_count)
        m.counter("serve_decode_steps_total",
                  "decode scan steps dispatched (incl. overrun no-ops)",
                  fn=lambda: self.steps_dispatched)
        m.gauge("serve_pages_free", "allocator free pages",
                fn=lambda: len(self.alloc.free))
        m.gauge("serve_pages_total", "allocator pool size (incl. null page)",
                fn=lambda: self.alloc.n_pages)
        # device-counted step accumulators: summed inside the decode scan,
        # read out at the one existing block-boundary sync
        self._c_decode_tokens = m.counter(
            "serve_decode_tokens_total",
            "tokens emitted by fused decode blocks (device-counted)")
        self._c_eos = m.counter(
            "serve_eos_total", "EOS fires inside decode blocks "
            "(device-counted)")
        self._c_requant = m.counter(
            "serve_kv_requant_events_total",
            "quantized page-scale entries changed by device KV writes")
        self._c_prefill_disp = m.counter(
            "serve_prefill_dispatches_total",
            "batched prefill / chunk dispatches")
        self._c_decode_disp = m.counter(
            "serve_decode_dispatches_total", "fused decode-block dispatches")
        # program spans (repro.obs.trace.span): host phases of a tick
        m.counter(SPAN_SECONDS, SPAN_SECONDS_HELP)
        m.counter(SPAN_COUNT, SPAN_COUNT_HELP)

        # the old cache is dead the moment a dispatch returns — donate it
        # so the page pools aren't double-resident.  Donated on every
        # backend, so the CPU tests catch any host read of a dead cache.
        self._admit_jit = jax.jit(self._admit_impl, donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(1,))
        from repro.kernels.paged_attention.ops import check_paged_kernels
        check_paged_kernels(self.cache, cfg.attention.heads_padded,
                            self.lm.dtype)

    # ------------------------------------------------------------------
    # device programs

    def _mesh_ctx(self):
        """Mesh scope for jit dispatches: inside it ``current_mesh()``
        resolves for the sharded-attention shard_maps and activation
        constraints; a no-op for single-device engines."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.sharding.ctx import use_mesh
        return use_mesh(self.mesh)

    def _admit_impl(self, params, cache, tokens, slot_ids, plens, temps,
                    key):
        """Batched admission: ONE padded prefill for every queued request
        admitted this tick, scattered into the paged pools, first token
        sampled on device.  tokens: (nb, plen_pad) right-padded.  The
        staging cache is bf16 regardless of cfg.kv_cache_dtype: the
        scatter quantizes once, with exact per-page amax scales."""
        nb, t = tokens.shape
        with jax.named_scope("kv_write"):
            tmp = self.lm.init_cache(nb, t, kv_dtype="bfloat16")
        logits, tmp = self.lm.prefill(params, tokens, tmp, lengths=plens)
        with jax.named_scope("kv_write"):
            cache = scatter_prefill_cache(cache, tmp, slot_ids, plens)
        with jax.named_scope("sample"):
            tok = _sample_batch(logits, temps, key)
        return tok, cache

    def _decode_impl(self, params, cache, tokens, lengths, active,
                     remaining, temps, key):
        """``decode_block`` fused decode steps: sample on device, advance
        per-slot lengths/budgets, mask finished slots.  Steps where no
        slot is active are skipped via lax.cond (block overrun).  A
        2-vector of step stats ([tokens emitted, EOS fires]) rides the
        scan carry, and quantized-page requant events are counted by
        comparing scale leaves before/after — both read out at the same
        block-boundary sync, never on their own.  Named regions as in
        ``repro.models.transformer``; sampling and the per-slot
        bookkeeping are ``sample``."""
        eos, max_len = self.eos, self.max_len

        def real_step(carry):
            tokens, lengths, active, remaining, cache, key, stats = carry
            logits, cache = self.lm.decode_step(params, tokens, cache,
                                                lengths)
            with jax.named_scope("sample"):
                key, sub = jax.random.split(key)
                nxt = _sample_batch(logits, temps, sub)
                nxt = jnp.where(active, nxt, tokens)
                stats = stats + jnp.stack(
                    [jnp.sum(active.astype(jnp.int32)),
                     jnp.sum((active & (nxt == eos)).astype(jnp.int32))])
                lengths = jnp.where(active, lengths + 1, lengths)
                remaining = jnp.where(active, remaining - 1, remaining)
                done = (nxt == eos) | (remaining <= 0) \
                    | (lengths >= max_len - 1)
                active = active & ~done
            return (nxt, lengths, active, remaining, cache, key, stats)

        def step(carry, _):
            emit = carry[2]                      # active at step start
            carry = jax.lax.cond(jnp.any(emit), real_step, lambda c: c,
                                 carry)
            return carry, (carry[0], emit)

        carry = (tokens, lengths, active, remaining, cache, key,
                 jnp.zeros((2,), jnp.int32))
        carry, (toks, emits) = jax.lax.scan(step, carry, None,
                                            length=self.decode_block)
        tokens, lengths, active, remaining, new_cache, _, stats = carry
        dstats = jnp.concatenate(
            [stats, _kv_scale_change_count(cache, new_cache)[None]])
        return (new_cache, toks, emits, tokens, lengths, active, remaining,
                dstats)

    # ------------------------------------------------------------------
    # host loop

    def _span(self, name: str):
        """A program span of this engine (``repro.obs.trace.span``)."""
        return span(name, self.metrics, self.tracer)

    def _maybe_inject(self, kind: str) -> None:
        """Chaos hook at the host side of a dispatch boundary: no-op
        without an enabled injector; may sleep (latency spike) or raise
        :class:`~repro.resil.errors.InjectedFault` BEFORE any state for
        the dispatch is committed."""
        inj = self.injector
        if inj is not None and inj.enabled:
            inj.pre_dispatch(kind)

    def _retire(self, slot: int, now: float):
        req = self.active.pop(slot)
        req.done = True
        req.t_done = now
        self._obs_retire(req)
        self.alloc.release(slot)                 # zeroes the host bt row
        self.lengths[slot] = 0
        self.temps[slot] = 0.0
        self.free.append(slot)
        # point the device row at the null page so the retired slot's
        # lock-step garbage writes can't land in reallocated pages
        self.cache = set_block_table_rows(
            self.cache, np.asarray([slot]), self.alloc.table[[slot]])

    def _try_admit(self) -> List[Request]:
        """Pop queue entries into free slots while pages last."""
        admitted = []
        while self.queue and self.free:
            req = self.queue[0]
            plen = len(req.prompt)
            horizon = min(plen + req.max_new_tokens, self.max_len)
            slot = self.free[0]
            try:
                self.alloc.alloc(slot, self.alloc.pages_needed(
                    horizon, self.page_size))
            except OutOfPagesError:
                if not self.active and not admitted:
                    raise            # nothing will ever free these pages
                break                # decode on; retirements free pages
            self.queue.popleft()
            self.free.popleft()
            req.slot = slot
            req.t_admit = time.perf_counter()
            self._obs_admit(req, req.t_admit, first=True,
                            pages=len(self.alloc.owned(slot)))
            admitted.append(req)
        return admitted

    def _dispatch_admit(self, admitted: List[Request], emitted: list):
        self._maybe_inject("admit")
        plens = np.asarray([len(r.prompt) for r in admitted], np.int32)
        slot_ids = np.asarray([r.slot for r in admitted], np.int32)
        plen_pad = _pow2_bucket(int(plens.max()))
        tokens = np.zeros((len(admitted), plen_pad), np.int32)
        for i, r in enumerate(admitted):
            tokens[i, :plens[i]] = r.prompt
            self.temps[r.slot] = r.temperature
        self.cache = set_block_table_rows(self.cache, slot_ids,
                                          self.alloc.table[slot_ids])
        self.key, sub = jax.random.split(self.key)
        with self._span("engine.prefill.launch") as launch:
            with self._mesh_ctx():
                tok0, self.cache = self._admit_jit(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(slot_ids), jnp.asarray(plens),
                    jnp.asarray(self.temps[slot_ids]), sub)
        with self._span("engine.prefill.wait") as wait:
            tok0 = np.asarray(tok0)              # <- sync (1 per admit batch)
            wait.args = {"rows": len(admitted), "tokens": int(plens.sum())}
        self.sync_count += 1
        t0, now = launch.t0, wait.t1
        self.t_prefill_s += now - t0
        self._c_prefill_disp.inc()
        self._c_tokens.inc(len(admitted))
        tr = self.tracer
        prof = self.profiler
        if prof.enabled:
            prof.record("admit", t0, now, tokens=int(plens.sum()),
                        rows=len(admitted), bucket=plen_pad, ctx=plen_pad,
                        cost=(self._admit_jit,
                              (self.params, self.cache, tokens, slot_ids,
                               plens, self.temps[slot_ids], sub), None))
        for i, req in enumerate(admitted):
            t = int(tok0[i])
            req.out_tokens.append(t)
            req.pos = int(plens[i])
            req.t_first = now
            if tr.enabled:
                tr.complete("prefill", req.rid, t0, now,
                            args={"tokens": int(plens[i]), "emitted": 1})
            self._obs_first(req)
            self.active[req.slot] = req
            self.lengths[req.slot] = plens[i]
            self.remaining[req.slot] = req.max_new_tokens - 1
            self.last_tok[req.slot] = t
            emitted.append((req.rid, t))
            if (t == self.eos or req.max_new_tokens <= 1
                    or req.pos >= self.max_len - 1):
                self._retire(req.slot, now)

    def _dispatch_decode(self, emitted: list):
        """One fused decode block, in four program spans: ``prep`` (the
        active mask, the key split, the uploads), ``launch`` (the jit
        call), ``wait`` (the block's one sync) and ``emit`` (counters,
        the emit loop, retirements).  The decode phase's seconds are
        launch plus wait."""
        self._maybe_inject("decode_block")
        with self._span("engine.decode.prep"):
            active_mask = np.zeros((self.n_slots,), bool)
            for slot in self.active:
                active_mask[slot] = True
            self.key, sub = jax.random.split(self.key)
            args = (jnp.asarray(self.last_tok), jnp.asarray(self.lengths),
                    jnp.asarray(active_mask), jnp.asarray(self.remaining),
                    jnp.asarray(self.temps), sub)
        with self._span("engine.decode.launch") as launch:
            with self._mesh_ctx():
                out = self._decode_jit(self.params, self.cache, *args)
        self.cache = out[0]
        with self._span("engine.decode.wait") as wait:
            # ONE sync for the whole K-token block (writable host
            # copies); the device-counted step stats ride the same tuple
            toks, emits, last, lengths, active, remaining, dstats = (
                np.array(x) for x in out[1:])
            wait.args = {"rows": len(self.active),
                         "steps": self.decode_block,
                         "tokens": int(dstats[0])}
        self.sync_count += 1
        t0, now = launch.t0, wait.t1
        self.t_decode_s += now - t0
        with self._span("engine.decode.emit"):
            self.steps_dispatched += self.decode_block
            self._c_decode_disp.inc()
            self._c_decode_tokens.inc(int(dstats[0]))
            self._c_tokens.inc(int(dstats[0]))
            self._c_eos.inc(int(dstats[1]))
            self._c_requant.inc(int(dstats[2]))
            prof = self.profiler
            if prof.enabled:
                prof.record("decode_block", t0, now,
                            tokens=int(dstats[0]), rows=len(self.active),
                            steps=self.decode_block,
                            bucket=self.decode_block,
                            ctx=int(self.lengths.max()),
                            cost=(self._decode_jit,
                                  (self.params, self.cache, self.last_tok,
                                   self.lengths, active_mask,
                                   self.remaining, self.temps, sub), None))
            tr = self.tracer
            if tr.enabled:
                tr.counter("utilization",
                           {"queue_depth": len(self.queue),
                            "slots_active": len(self.active),
                            "pages_used": self.alloc.n_pages
                            - len(self.alloc.free)}, ts=now)
                for slot, req in self.active.items():
                    n = int(emits[:, slot].sum())
                    if n:
                        tr.complete("decode_block", req.rid, t0, now,
                                    args={"tokens": n})
            for i in range(self.decode_block):
                for slot in list(self.active):
                    if emits[i, slot]:
                        req = self.active[slot]
                        req.out_tokens.append(int(toks[i, slot]))
                        req.pos += 1
                        emitted.append((req.rid, int(toks[i, slot])))
            self.last_tok, self.lengths, self.remaining = (last, lengths,
                                                           remaining)
            for slot in list(self.active):
                if not active[slot]:
                    self._retire(slot, now)

    def step(self) -> List[tuple]:
        """One engine tick: batched admission (if anything is queued),
        then one fused ``decode_block``-token decode dispatch.  Returns
        [(rid, token), ...] emitted this tick."""
        emitted: List[tuple] = []
        if self.queue and self.free:
            admitted = self._try_admit()
            if admitted:
                self._dispatch_admit(admitted, emitted)
        if self.active:
            self._dispatch_decode(emitted)
        return emitted
