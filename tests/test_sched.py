"""Scheduler subsystem (repro.sched): policy ordering, refcounted
prefix caching (warm == cold greedy tokens on bf16 AND int8 pools, with
the >= 2x prefill-token reduction), chunked prefill, preemption with
recompute-on-readmit (token-equal to uninterrupted decode), and
PageAllocator refcount invariants (hypothesis).

Engine tests run the same CPU/interpret dispatch as the TPU artifact,
sized like tests/test_serving.py.
"""
import jax
import numpy as np
import pytest

from repro.sched import PrefixCache, make_policy
from repro.serve.engine import Request
from repro.serve.paged import OutOfPagesError, PageAllocator


# ---------------------------------------------------------------------------
# policies


def _req(rid, t_submit, plen, max_new, slo=None):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new_tokens=max_new, t_submit=t_submit, slo_ttft=slo)


def test_fcfs_orders_by_arrival():
    pol = make_policy("fcfs")
    a, b, c = _req(0, 1.0, 8, 8), _req(1, 0.5, 8, 8), _req(2, 2.0, 8, 8)
    order = sorted([a, b, c], key=lambda r: pol.priority(r, 3.0))
    assert order == [b, a, c]
    # victim: the latest arrival is preempted first
    assert max([a, b, c], key=lambda r: pol.victim(r, 3.0)) is c


def test_sjf_orders_by_costmodel_estimate():
    from repro.configs import get_smoke_config
    pol = make_policy("sjf", cfg=get_smoke_config("qwen2-1.5b"))
    small = _req(0, 0.0, 8, 4)
    mid = _req(1, 0.0, 64, 16)
    big = _req(2, 0.0, 256, 64)
    order = sorted([big, small, mid], key=lambda r: pol.priority(r, 1.0))
    assert order == [small, mid, big]
    # remaining work shrinks as prefill progresses / tokens are emitted
    big2 = _req(3, 0.0, 256, 64)
    big2.progress = 200
    assert pol.remaining_s(big2) < pol.remaining_s(big)
    # victim: the longest remaining job is preempted first
    assert max([small, mid, big], key=lambda r: pol.victim(r, 1.0)) is big


def test_sjf_aging_prevents_starvation():
    """Under pure SJF a continuous stream of short arrivals starves one
    long request forever; queue-wait aging must eventually rank the long
    job first.  Simulated admission: each tick one new short request
    arrives and ONE queued request admits."""
    from repro.configs import get_smoke_config
    from repro.sched.policy import SJF
    cfg = get_smoke_config("qwen2-1.5b")

    def admitted_by(pol, ticks=200):
        long_req = _req(0, 0.0, 512, 128)
        queue = [long_req]
        for t in range(1, ticks + 1):
            queue.append(_req(t, float(t), 8, 4))     # fresh short job
            queue.sort(key=lambda r: pol.priority(r, float(t)))
            if queue.pop(0) is long_req:
                return t
        return None

    assert admitted_by(SJF(cfg, aging=0.0)) is None    # starves forever
    tick = admitted_by(SJF(cfg, aging=0.05))
    assert tick is not None                            # aging admits it
    # victim selection stays pure longest-remaining (aging is for
    # admission): the long job is still the preferred preemption victim
    pol = SJF(cfg, aging=0.05)
    fresh_short, old_long = _req(1, 99.0, 8, 4), _req(0, 0.0, 512, 128)
    assert max([fresh_short, old_long],
               key=lambda r: pol.victim(r, 100.0)) is old_long


def test_edf_admission_control_drops_infeasible():
    """EDF admission-time SLO feasibility: a request whose deadline is
    already unmeetable at admission is dropped (distinct telemetry
    counter), while feasible requests complete normally."""
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist()
               for _ in range(2)]
    eng = _sched(lm, params, policy="edf", prefix_cache=False,
                 admission_control=True)
    ok = eng.submit(prompts[0], max_new_tokens=6, slo_ttft=60.0)
    doomed = eng.submit(prompts[1], max_new_tokens=6, slo_ttft=-1.0)
    done = eng.run_to_completion()
    assert done[doomed].rejected and done[doomed].done
    assert done[doomed].out_tokens == []
    assert not done[ok].rejected
    assert len(done[ok].out_tokens) == 6
    assert eng.stats.slo_rejected == 1
    assert eng.telemetry()["slo_rejected"] == 1
    # without admission control the same doomed request is still served
    eng2 = _sched(lm, params, policy="edf", prefix_cache=False)
    late = eng2.submit(prompts[1], max_new_tokens=6, slo_ttft=-1.0)
    done2 = eng2.run_to_completion()
    assert not done2[late].rejected
    assert len(done2[late].out_tokens) == 6


def test_edf_orders_by_ttft_deadline():
    pol = make_policy("edf", slo_ttft=0.5)
    a = _req(0, 1.0, 8, 8)                  # deadline 1.5 (policy default)
    b = _req(1, 0.2, 8, 8)                  # deadline 0.7
    c = _req(2, 1.4, 8, 8, slo=0.05)        # per-request SLO: 1.45
    order = sorted([a, b, c], key=lambda r: pol.priority(r, 2.0))
    assert order == [b, c, a]
    # victim: most slack (latest deadline) goes first
    assert max([a, b, c], key=lambda r: pol.victim(r, 2.0)) is a


# ---------------------------------------------------------------------------
# prefix cache index


def test_prefix_cache_lookup_insert_evict():
    al = PageAllocator(n_pages=10, max_pages_per_slot=8, n_slots=2)
    pc = PrefixCache(al, page_size=4)
    toks = np.arange(13, dtype=np.int32)
    pages = al.alloc(0, 3)                       # covers tokens [0, 12)
    pc.insert(toks[:12], pages)
    assert [al.refs[p] for p in pages] == [2, 2, 2]   # slot + cache

    hit, hp = pc.lookup(toks)
    assert hit == 12 and hp == pages
    # an exact-page-multiple prompt is capped one token short: 2 pages
    hit, hp = pc.lookup(toks[:12])
    assert hit == 8 and hp == pages[:2]
    # divergence after the first page stops the chain walk
    other = np.concatenate([toks[:4], np.full(9, 99, np.int32)])
    hit, hp = pc.lookup(other)
    assert hit == 4 and hp == pages[:1]
    assert pc.lookup(np.full(9, 7, np.int32)) == (0, [])

    # eviction never drops nodes whose pages a slot still maps (freeing
    # nothing would just destroy the warm index); once the slot releases
    # them, the oldest leaves evict and their pages actually free
    assert pc.evict_pages(3) == 0                # slot 0 still maps them
    assert pc.n_pages == 3                       # index intact
    al.release(0)
    assert len(al.free) == al.n_pages - 1 - 3    # cache refs keep them
    assert pc.evict_pages(3) == 3
    assert pc.n_pages == 0
    assert len(al.free) == al.n_pages - 1
    assert pc.lookup(toks) == (0, [])


def test_prefix_cache_hit_capped_below_prompt_len():
    """A fully cached prompt must still leave >= 1 suffix token so the
    final chunk produces last-token logits to sample from."""
    al = PageAllocator(n_pages=6, max_pages_per_slot=4, n_slots=1)
    pc = PrefixCache(al, page_size=4)
    toks = np.arange(8, dtype=np.int32)
    pages = al.alloc(0, 2)
    pc.insert(toks, pages)
    hit, hp = pc.lookup(toks)                    # same 8-token prompt
    assert hit == 4 and hp == pages[:1]


# ---------------------------------------------------------------------------
# allocator refcount invariants (property-based)


try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:                              # CI installs it; local
    _HAS_HYPOTHESIS = False                      # runs skip just this test


def _allocator_refcount_invariants(ops):
    """No double-free, no leak, no aliasing across arbitrary
    alloc/share/extend/release/ref/unref interleavings: every non-null
    page is free XOR referenced, and each refcount equals (#slots
    mapping the page) + (#cache-held references)."""
    from collections import Counter
    n_pages, n_slots = 12, 4
    al = PageAllocator(n_pages, max_pages_per_slot=6, n_slots=n_slots)
    held = []                                    # cache-held references
    for op, a, b in ops:
        slot = a % n_slots
        try:
            if op == 0:
                al.alloc(slot, b)
            elif op == 1:                        # share a neighbour's prefix
                shared = al.owned((slot + 1) % n_slots)[:b]
                al.assign(slot, shared, 1)
            elif op == 2:
                al.extend(slot, b)
            elif op == 3:
                al.release(slot)
            elif op == 4:
                pages = al.owned(slot)
                if pages:
                    al.ref(pages[0])
                    held.append(pages[0])
            elif op == 5 and held:
                al.unref(held.pop())
        except OutOfPagesError:
            pass
        free = al.free
        assert len(set(free)) == len(free), "page duplicated in free list"
        assert 0 not in free, "null page leaked into the free list"
        want = Counter(held)
        for s in range(n_slots):
            want.update(al.owned(s))
        for p in range(1, n_pages):
            assert al.refs[p] == want[p], f"page {p} refcount drift"
            assert (al.refs[p] == 0) == (p in free), \
                f"page {p} neither free nor referenced (leak/double-free)"


if _HAS_HYPOTHESIS:
    @settings(deadline=None, max_examples=60)
    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 3),
                              st.integers(1, 4)), max_size=50))
    def test_allocator_refcount_invariants(ops):
        _allocator_refcount_invariants(ops)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_allocator_refcount_invariants():
        pass


def _spec_rollback_invariants(ops):
    """No page leak, no double-free, and no speculative write span ever
    covering a page the prefix cache holds or another slot maps, across
    arbitrary interleavings of admit (with prefix-hit sharing) /
    spec-grow+write / rollback / retire-and-insert / evict / tail-fork
    (beam-style sharing of a mid-page tail — the case the copy-on-write
    guard exists for)."""
    import jax.numpy as jnp
    from collections import Counter
    from repro.sched import PrefixCache
    from repro.serve.paged import set_block_table_rows
    from repro.spec import (ensure_exclusive_tail, rollback_length,
                            span_pages)
    page, n_pages, n_slots, w_max = 4, 14, 3, 4
    al = PageAllocator(n_pages, max_pages_per_slot=5, n_slots=n_slots)
    pc = PrefixCache(al, page)
    cache = {"kv": {
        "k_pages": jnp.zeros((n_pages, 1, page, 4), jnp.bfloat16),
        "v_pages": jnp.zeros((n_pages, 1, page, 4), jnp.bfloat16),
        "k_scales": jnp.zeros((n_pages, 1), jnp.float32),
        "v_scales": jnp.zeros((n_pages, 1), jnp.float32),
        "block_table": jnp.zeros((n_slots, 5), jnp.int32),
    }}
    lengths, prompts = {}, {}
    for kind, a, b in ops:
        slot = a % n_slots
        try:
            if kind == 0 and slot not in lengths:
                # admit: prompts are prefixes of one shared stream, so
                # prefix-cache hits (page sharing) actually happen
                plen = (b % 3 + 1) * page + 1
                toks = np.arange(plen, dtype=np.int32) % 3
                hit, pages = pc.lookup(toks)
                al.assign(slot, pages,
                          al.pages_needed(plen + w_max, page) - len(pages))
                cache = set_block_table_rows(cache, np.asarray([slot]),
                                             al.table[[slot]])
                lengths[slot], prompts[slot] = plen, toks
            elif kind == 1 and slot in lengths:
                # spec round: grow for the verify span, COW any shared
                # tail page, then advance by the accepted count
                w = b % w_max + 1
                start = lengths[slot]
                need = al.pages_needed(start + w, page) \
                    - len(al.owned(slot))
                if need > 0:
                    al.extend(slot, need)
                    cache = set_block_table_rows(cache, np.asarray([slot]),
                                                 al.table[[slot]])
                cache = ensure_exclusive_tail(cache, al, slot, start,
                                              start + w, page)
                for li in span_pages(start, start + w, page):
                    p = int(al.table[slot, li])
                    assert al.refs[p] == 1, \
                        "write span covers a shared/cache-held page"
                assert list(np.asarray(cache["kv"]["block_table"])[slot]) \
                    == list(al.table[slot])
                lengths[slot] = start + b % (w + 1)   # rejected tail:
            elif kind == 2 and slot in lengths:       # implicit rollback
                # the engine always COWs the verify span BEFORE any spec
                # work, so rollback's shared-page audit runs on an
                # exclusive tail — replicate that protocol here
                old = lengths[slot]
                new = max(old - b % w_max, len(prompts[slot]))
                cache = ensure_exclusive_tail(cache, al, slot, new, old,
                                              page)
                rollback_length(al, slot, old, new, page)
                lengths[slot] = new
            elif kind == 3 and slot in lengths:
                toks = prompts[slot]
                n_full = len(toks) // page
                if n_full:
                    pc.insert(toks[:n_full * page],
                              al.owned(slot)[:n_full])
                al.release(slot)
                del lengths[slot]
            elif kind == 4:
                pc.evict_pages(b % 3 + 1)
            elif kind == 5 and slot in lengths:
                # beam-style fork: another slot maps the SAME pages
                # (incl. the mid-page tail) — the next spec round on
                # either slot must copy-on-write, never share-write
                other = (slot + 1) % n_slots
                if other not in lengths:
                    al.assign(other, al.owned(slot), 0)
                    cache = set_block_table_rows(
                        cache, np.asarray([other]), al.table[[other]])
                    lengths[other] = lengths[slot]
                    prompts[other] = prompts[slot]
        except OutOfPagesError:
            pass
        free = al.free
        assert len(set(free)) == len(free), "page duplicated in free list"
        assert 0 not in free, "null page leaked into the free list"
        want = Counter(nd["page"] for nd in pc.nodes.values())
        for s in range(n_slots):
            want.update(al.owned(s))
        for p in range(1, n_pages):
            assert al.refs[p] == want[p], f"page {p} refcount drift"
            assert (al.refs[p] == 0) == (p in free), \
                f"page {p} neither free nor referenced (leak/double-free)"


if _HAS_HYPOTHESIS:
    @settings(deadline=None, max_examples=40)
    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 3),
                              st.integers(0, 6)), max_size=40))
    def test_spec_rollback_invariants(ops):
        _spec_rollback_invariants(ops)
else:
    def test_spec_rollback_invariants():
        _spec_rollback_invariants(
            [(0, 0, 2), (1, 0, 3), (5, 0, 0), (1, 0, 3), (1, 1, 2),
             (2, 0, 2), (3, 0, 0), (0, 0, 1), (1, 0, 1), (4, 0, 2),
             (3, 1, 0), (3, 0, 0), (4, 0, 5)])


def test_unref_below_zero_raises():
    al = PageAllocator(n_pages=4, max_pages_per_slot=2, n_slots=1)
    (page,) = al.alloc(0, 1)
    al.release(0)
    with pytest.raises(ValueError, match="double free"):
        al.unref(page)
    with pytest.raises(ValueError, match="unallocated"):
        al.ref(page)


# ---------------------------------------------------------------------------
# engine end-to-end


def _setup(kv_dtype=None):
    from repro.configs import get_smoke_config
    from repro.models.model import LM
    cfg = get_smoke_config("qwen2-1.5b").with_(dtype="float32")
    params = LM(cfg).init(jax.random.PRNGKey(0))
    if kv_dtype:
        cfg = cfg.with_(kv_cache_dtype=kv_dtype)
    rng = np.random.default_rng(0)
    return LM(cfg), params, rng


def _sched(lm, params, **kw):
    from repro.sched import SchedEngine
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_chunk", 16)
    return SchedEngine(lm, params, **kw)


def test_sched_fcfs_cold_matches_paged_engine_and_sync_count():
    """With FCFS, no prefix cache, and single-chunk prompts the
    scheduler must reproduce the base paged engine's greedy streams —
    and spend exactly one host sync per prefill dispatch + one per
    decode block (the device-side scale reset removed the only other
    candidate round trip)."""
    from repro.serve.engine import PagedEngine
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (n,)).tolist()
               for n in (8, 5, 12, 8, 3)]
    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    pids = [peng.submit(p, max_new_tokens=9) for p in prompts]
    pdone = peng.run_to_completion()
    seng = _sched(lm, params, policy="fcfs", prefix_cache=False)
    sids = [seng.submit(p, max_new_tokens=9) for p in prompts]
    sdone = seng.run_to_completion()
    for a, b in zip(pids, sids):
        assert pdone[a].out_tokens == sdone[b].out_tokens
    assert seng.sync_count == seng.stats.chunks \
        + seng.steps_dispatched // seng.decode_block, \
        "host syncs regressed beyond 1/prefill-dispatch + 1/decode-block"
    assert all(sdone[i].t_admit is not None for i in sids)


def test_sched_tracing_is_sync_free_even_under_preemption():
    """Scheduler instrumentation (chunk spans, preempt instants,
    readmit queue spans) must not change sync_count or the greedy
    streams — audited on the preemption-forcing tight pool, the
    scheduler's most trace-dense path."""
    from repro.obs import Tracer
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist(),
               rng.integers(0, lm.cfg.vocab_size, (5,)).tolist()]

    def run(tracer=None):
        eng = _sched(lm, params, policy="fcfs", prefix_cache=False,
                     prefill_chunk=8, max_len=48, n_pages=7,
                     tracer=tracer)
        ids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        done = eng.run_to_completion()
        return [done[i].out_tokens for i in ids], eng

    base_toks, base = run()
    tr = Tracer(enabled=True)
    toks, traced = run(tracer=tr)
    assert base.stats.preemptions > 0
    assert toks == base_toks
    assert traced.sync_count == base.sync_count
    assert traced.stats.preemptions == base.stats.preemptions
    assert any(e.get("ph") == "i" and e["name"] == "preempt"
               for e in tr.events)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_shared_prefix_warm_matches_cold(kv_dtype):
    """Prefix-cache admissions skip the shared prompt pages yet stay
    token-identical to a cold cache (warm continuation chunks run the
    SAME computation over bit-identical shared pages), with >= 2x fewer
    prefill tokens computed — on bf16 and quantized int8 pools."""
    lm, params, rng = _setup(kv_dtype)
    shared = rng.integers(0, lm.cfg.vocab_size, (24,)).tolist()
    prompts = [shared + rng.integers(0, lm.cfg.vocab_size,
                                     (int(rng.integers(3, 8)),)).tolist()
               for _ in range(6)]

    def run(prefix_cache):
        eng = _sched(lm, params, policy="fcfs", prefix_cache=prefix_cache)
        ids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        done = eng.run_to_completion()
        return [done[i].out_tokens for i in ids], eng

    cold_toks, cold = run(False)
    warm_toks, warm = run(True)
    assert cold_toks == warm_toks
    assert all(len(t) == 8 for t in warm_toks)
    assert cold.stats.prefill_tokens / warm.stats.prefill_tokens >= 2.0
    st_ = warm.prefix.stats()
    assert st_["hits"] >= 4 and st_["hit_tokens"] >= 4 * 24
    assert warm.stats.prefix_hit_tokens == st_["hit_tokens"]


def test_preemption_readmit_matches_uninterrupted():
    """A pool too small for both requests' full horizons forces a lazy-
    growth preemption; the preempted request recomputes its KV on
    readmission and must emit exactly the tokens an ample pool yields.
    All pages drain back to the free list at the end."""
    lm, params, rng = _setup()
    prompts = [rng.integers(0, lm.cfg.vocab_size, (8,)).tolist(),
               rng.integers(0, lm.cfg.vocab_size, (5,)).tolist()]

    def run(n_pages=None):
        eng = _sched(lm, params, policy="fcfs", prefix_cache=False,
                     prefill_chunk=8, max_len=48, n_pages=n_pages)
        ids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        done = eng.run_to_completion()
        return [done[i].out_tokens for i in ids], eng

    tight_toks, tight = run(n_pages=7)           # null + 6 pages
    ample_toks, ample = run()
    assert tight.stats.preemptions > 0
    assert ample.stats.preemptions == 0
    assert tight_toks == ample_toks
    assert all(len(t) == 20 for t in tight_toks)
    assert len(tight.alloc.free) == tight.alloc.n_pages - 1
    preempted = [r for r in tight.registry.values() if r.preemptions][0]
    assert preempted.done


def test_chunked_prefill_long_prompt_matches_unchunked():
    """A prompt longer than prefill_chunk is admitted in page-aligned
    chunks interleaved with decode; the result matches the base engine's
    single-shot prefill, and decode keeps running between chunks."""
    from repro.serve.engine import PagedEngine
    lm, params, rng = _setup()
    long_p = rng.integers(0, lm.cfg.vocab_size, (40,)).tolist()
    short_p = rng.integers(0, lm.cfg.vocab_size, (6,)).tolist()
    peng = PagedEngine(lm, params, n_slots=2, max_len=64, seed=0,
                       page_size=8, decode_block=4)
    pids = [peng.submit(short_p, max_new_tokens=12),
            peng.submit(long_p, max_new_tokens=12)]
    pdone = peng.run_to_completion()

    seng = _sched(lm, params, policy="fcfs", prefix_cache=False,
                  prefill_chunk=16)
    sids = [seng.submit(short_p, max_new_tokens=12),
            seng.submit(long_p, max_new_tokens=12)]
    sdone = seng.run_to_completion()
    for a, b in zip(pids, sids):
        assert pdone[a].out_tokens == sdone[b].out_tokens
    assert seng.stats.chunks >= 3          # the long prompt took >= 3


def test_edf_admits_urgent_request_first():
    """Two queued requests, one slot: EDF admits the tighter-deadline
    request first even though it arrived second."""
    lm, params, rng = _setup()
    relaxed = rng.integers(0, lm.cfg.vocab_size, (8,)).tolist()
    urgent = rng.integers(0, lm.cfg.vocab_size, (8,)).tolist()
    eng = _sched(lm, params, policy="edf", prefix_cache=False, n_slots=1)
    r1 = eng.submit(relaxed, max_new_tokens=4, slo_ttft=10.0)
    r2 = eng.submit(urgent, max_new_tokens=4, slo_ttft=0.001)
    done = eng.run_to_completion()
    assert done[r2].t_first < done[r1].t_first
    assert done[r2].t_admit <= done[r1].t_admit
    # per-request SLO attainment lands in telemetry: the relaxed 10 s
    # TTFT is met, the 1 ms one is not -> 1 of 2
    slo = eng.telemetry()["slo"]
    assert slo["ttft_attainment"] == 0.5
    assert slo["tpot_attainment"] is None      # no TPOT targets supplied
