"""Paged KV cache host bookkeeping (PagedAttention adapted for TPU).

vLLM pages are 16-token and pointer-chased per token — efficient on GPUs
with per-thread gathers, hostile to TPU's vector memory system.  The TPU
adaptation (DESIGN.md §3): large lane-aligned pages (256-token default), a
per-slot block table, and a Pallas flash-decoding kernel
(``kernels/paged_attention``) whose BlockSpec index maps stream pages
straight from HBM, one (page, head_dim) tile per grid step, for ALL active
slots in one launch.

This module owns the HOST side: the free list / block-table accounting and
the engine-facing cache-tree walkers.  Device-side page arrays, quantized
(int8/fp8) pools with their per-page scales, and all write ops live in
``repro.kvcache`` — the one cache implementation.

Page 0 is the NULL page: free slots' block-table rows point at it, and
masked writes (padding tokens, retired slots) are routed into it, so device
code never needs a branch for "no page allocated here".

Equivalence with contiguous caches is property-tested in
tests/test_serving.py and tests/test_kvcache.py.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import CacheSpec, dequantize, paged_scatter_prefill

PAGE = 256


class OutOfPagesError(RuntimeError):
    """Raised when an allocation cannot be satisfied by the free list."""


class PageAllocator:
    """Host-side page accounting: refcounted pages + a host block table.

    Device arrays (the page pools, the device block table inside the
    engine cache) are owned elsewhere; this class only decides WHICH
    physical pages a slot owns.  Page 0 is reserved as the null page.

    Pages carry a reference count so one physical page can back several
    block-table rows at once: full pages are immutable (writes only ever
    land past a slot's length), so a shared prompt prefix can be mapped
    into every slot that carries it (``assign`` with ``shared``), and the
    prefix cache (``repro.sched.prefix``) can keep pages alive after
    their slot retires (``ref``/``unref``).  A page returns to the free
    list exactly when its last reference drops.
    """

    def __init__(self, n_pages: int, max_pages_per_slot: int, n_slots: int):
        self.n_pages = n_pages
        self.max_pages_per_slot = max_pages_per_slot
        self.free: List[int] = list(range(n_pages - 1, 0, -1))
        self.table = np.zeros((n_slots, max_pages_per_slot), np.int32)
        self.refs = np.zeros((n_pages,), np.int32)
        self._owned: Dict[int, List[int]] = {}
        # optional chaos harness (repro.resil.inject.FaultInjector): when
        # set AND enabled, _take consults it for spurious page faults and
        # forced pool shrinkage.  None (the default) is the untouched
        # pre-resilience allocation path.
        self.injector = None

    def pages_needed(self, seq_len: int, page_size: int = PAGE) -> int:
        return (seq_len + page_size - 1) // page_size

    def occupancy(self, top: int = 3) -> dict:
        """Point-in-time pool snapshot for post-mortems: free/total
        pages (null page excluded), pages pinned beyond slot ownership
        (prefix-cache references), and the largest slot holders."""
        holders = sorted(((s, len(p)) for s, p in self._owned.items() if p),
                         key=lambda x: -x[1])[:top]
        slot_pages = sum(len(p) for p in self._owned.values())
        referenced = int((self.refs > 0).sum())
        used = self.n_pages - 1 - len(self.free)
        return {"free": len(self.free), "total": self.n_pages - 1,
                "used": used, "slot_pages": slot_pages,
                "cache_only_pages": used - len(
                    {p for ps in self._owned.values() for p in ps}),
                "referenced": referenced,
                "top_holders": holders}

    def occupancy_summary(self, top: int = 3) -> str:
        """One-line occupancy rendering appended to every
        OutOfPagesError message (post-mortem debuggability)."""
        o = self.occupancy(top)
        holders = ", ".join(f"slot {s}: {n}p" for s, n in o["top_holders"]) \
            or "none"
        return (f"pool {o['used']}/{o['total']} pages used "
                f"({o['free']} free, {o['cache_only_pages']} cache-held), "
                f"top holders: {holders}")

    def _take(self, need: int) -> List[int]:
        avail = len(self.free)
        inj = self.injector
        if inj is not None and inj.enabled:
            inj.page_fault_check(self)     # may raise InjectedPageFault
            avail = max(avail - inj.reserved_pages(), 0)
        if need > avail:
            raise OutOfPagesError(
                f"need {need} pages, {avail} free; "
                f"{self.occupancy_summary()}")
        return [self.free.pop() for _ in range(need)]

    def alloc(self, slot: int, need: int) -> List[int]:
        """Reserve ``need`` fresh pages for ``slot``.  Atomic: on failure
        the free list is left exactly as it was and OutOfPagesError
        raised."""
        return self.assign(slot, (), need)

    def assign(self, slot: int, shared, need: int) -> List[int]:
        """Give ``slot`` the already-allocated pages ``shared`` (each
        gains a reference — the prefix-cache hit path) followed by
        ``need`` fresh pages.  Atomic like :meth:`alloc`."""
        if self._owned.get(slot):
            raise OutOfPagesError(f"slot {slot} already holds pages")
        total = len(shared) + need
        if total > self.max_pages_per_slot:
            raise OutOfPagesError(
                f"need {total} pages > {self.max_pages_per_slot} per slot; "
                f"{self.occupancy_summary()}")
        fresh = self._take(need)
        for p in shared:
            self.refs[p] += 1
        for p in fresh:
            self.refs[p] = 1
        pages = list(shared) + fresh
        self.table[slot, :] = 0
        self.table[slot, :total] = pages
        self._owned[slot] = pages
        return pages

    def extend(self, slot: int, extra: int) -> List[int]:
        """Lazily grow ``slot``'s allocation by ``extra`` fresh pages
        (appended to its block-table row).  Atomic."""
        owned = self._owned.get(slot)
        if owned is None:
            raise OutOfPagesError(f"slot {slot} owns no pages")
        n0 = len(owned)
        if n0 + extra > self.max_pages_per_slot:
            raise OutOfPagesError(
                f"{n0}+{extra} pages > {self.max_pages_per_slot} per slot; "
                f"{self.occupancy_summary()}")
        fresh = self._take(extra)
        for p in fresh:
            self.refs[p] = 1
        self.table[slot, n0:n0 + extra] = fresh
        owned.extend(fresh)
        return fresh

    def owned(self, slot: int) -> List[int]:
        return list(self._owned.get(slot, ()))

    def ref(self, page: int) -> None:
        """Take an extra reference on an allocated page (prefix cache)."""
        if self.refs[page] <= 0:
            raise ValueError(f"ref on unallocated page {page}")
        self.refs[page] += 1

    def unref(self, page: int) -> None:
        """Drop a reference; the page frees when the count hits zero."""
        if self.refs[page] <= 0:
            raise ValueError(f"double free of page {page}")
        self.refs[page] -= 1
        if self.refs[page] == 0:
            self.free.append(page)

    def cow(self, slot: int, index: int) -> int:
        """Copy-on-write: replace the SHARED page at ``slot``'s block-
        table position ``index`` with a fresh exclusive page (the caller
        copies the device contents).  The old page keeps its other
        references (prefix cache / other rows); this row's reference
        moves to the fresh page.  Atomic: on OutOfPagesError nothing
        changed.  Returns the fresh physical page id."""
        owned = self._owned.get(slot)
        if owned is None or index >= len(owned):
            raise ValueError(f"slot {slot} owns no page at index {index}")
        old = owned[index]
        if self.refs[old] <= 1:
            raise ValueError(f"cow of exclusive page {old} (refs <= 1)")
        (fresh,) = self._take(1)
        self.refs[fresh] = 1
        owned[index] = fresh
        self.table[slot, index] = fresh
        self.unref(old)
        return fresh

    def release(self, slot: int) -> None:
        for p in self._owned.pop(slot, ()):
            self.unref(p)
        self.table[slot, :] = 0


class PagedKVPool:
    """Single-layer paged K/V pool (allocator + kvcache device arrays).

    The serving engine holds per-layer pools inside the model cache and
    uses :class:`PageAllocator` directly; this class is the self-contained
    unit the kernel tests and examples drive.  ``dtype`` accepts the
    CacheSpec names (bf16 | int8 | fp8); quantized pools carry per-page
    scales (see ``repro.kvcache``).
    """

    def __init__(self, n_pages: int, kv_heads: int, head_dim: int,
                 max_pages_per_slot: int, n_slots: int,
                 dtype: str = "bf16", page_size: int = PAGE):
        from repro.configs.base import AttentionConfig
        from repro.kvcache import alloc_paged
        self.n_pages = n_pages
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.page_size = page_size
        self.spec = CacheSpec(layout="paged", dtype=dtype,
                              page_size=page_size)
        self.allocator = PageAllocator(n_pages, max_pages_per_slot, n_slots)
        a = AttentionConfig(kind="mha", num_heads=kv_heads,
                            num_kv_heads=kv_heads, head_dim=head_dim)
        self.cache = alloc_paged(self.spec, a, n_slots, n_pages,
                                 max_pages_per_slot)

    @property
    def free(self) -> List[int]:
        return self.allocator.free

    @property
    def k_pages(self) -> jax.Array:
        return self.cache["k_pages"]

    @property
    def v_pages(self) -> jax.Array:
        return self.cache["v_pages"]

    @property
    def block_table(self) -> jax.Array:
        return jnp.asarray(self.allocator.table)

    def alloc(self, slot: int, seq_len: int) -> List[int]:
        """Reserve pages covering ``seq_len`` tokens for ``slot``.
        Raises :class:`OutOfPagesError` (free list unchanged) when the
        pool cannot satisfy the request."""
        need = self.allocator.pages_needed(seq_len, self.page_size)
        return self.allocator.alloc(slot, need)

    def release(self, slot: int) -> None:
        self.allocator.release(slot)


# ---------------------------------------------------------------------------
# Engine-facing cache-tree walkers (device ops themselves: repro.kvcache)


def scatter_prefill_cache(paged_cache, contig_cache, slot_ids, lengths,
                          starts=None):
    """Scatter a whole model's batched-prefill cache into the paged cache.

    Walks the two cache pytrees in parallel; every paged attention node
    ({k_pages, v_pages[, scales], block_table}) receives the matching
    contiguous node's rows via ``repro.kvcache.paged_scatter_prefill``
    (vmapped over the stacked-groups axis when cfg.scan_layers).
    ``starts`` (B,) offsets each row's logical write positions (chunked
    prefill continuation; must be page-aligned — see the kvcache
    docstring).  Staging caches are expected bf16; a quantized staging
    node is dequantized before the scatter re-quantizes per page.
    Position-free state nodes (SSM, cross-attn) are not supported — the
    paged engine gates on attention-only models.
    """
    if isinstance(paged_cache, dict) and "k_pages" in paged_cache:
        from repro.kvcache import constrain_paged_pools
        k_rows, v_rows = contig_cache["k"], contig_cache["v"]
        if "k_scale" in contig_cache:
            k_rows = dequantize(k_rows, contig_cache["k_scale"])
            v_rows = dequantize(v_rows, contig_cache["v_scale"])
        if paged_cache["k_pages"].ndim == 5:   # (G, N, KH, page, D) stacked
            out = jax.vmap(paged_scatter_prefill,
                           in_axes=(0, None, None, 0, 0, None))(
                paged_cache, slot_ids, lengths, k_rows, v_rows, starts)
        else:
            out = paged_scatter_prefill(paged_cache, slot_ids, lengths,
                                        k_rows, v_rows, starts)
        # re-pin (kv-head sharding; ndim-relative, so the stacked case
        # pins the same dims) so the admitted pools leave the jit sharded
        return constrain_paged_pools(out)
    if isinstance(paged_cache, dict):
        return {k: scatter_prefill_cache(paged_cache[k], contig_cache[k],
                                         slot_ids, lengths, starts)
                for k in paged_cache}
    raise NotImplementedError(
        f"paged engine: unsupported cache leaf {type(paged_cache)}")


def commit_spec_cache(paged_cache, stage_cache, lengths, n_write):
    """Commit a speculative-verify round's ACCEPTED tokens into the paged
    cache (write-after-accept; ``repro.spec``).

    ``stage_cache`` is the bf16 staging tree ``LM.verify_paged`` filled —
    per attention node ``{"k"/"v": (S, W, KH, D)}`` — and ``n_write``
    (S,) says how many leading chunk tokens each slot accepted.  The
    writes REPLAY the baseline decode path exactly: a ``lax.scan`` of
    per-token ``kvcache.paged_write_batch`` calls in chunk order, masked
    to ``i < n_write[s]`` (masked writes land in the null page), so the
    pools — including a quantized pool's per-page running amax scales
    and requant events — evolve just as ``decode_block`` steps would
    have.  Rejected draft K/V is simply never written: rollback is a
    pure host-side length truncation."""
    from repro.kvcache import constrain_paged_pools, paged_write_batch
    if isinstance(paged_cache, dict) and "k_pages" in paged_cache:
        k_rows, v_rows = stage_cache["k"], stage_cache["v"]
        w = k_rows.shape[-3]

        def commit_node(node, k_r, v_r):
            def body(c, i):
                return paged_write_batch(c, lengths + i, k_r[:, i],
                                         v_r[:, i],
                                         mask=i < n_write), None
            node, _ = jax.lax.scan(body, node, jnp.arange(w))
            return constrain_paged_pools(node)

        if paged_cache["k_pages"].ndim == 5:   # (G, N, KH, page, D) stacked
            return jax.vmap(commit_node)(paged_cache, k_rows, v_rows)
        return commit_node(paged_cache, k_rows, v_rows)
    if isinstance(paged_cache, dict):
        return {k: commit_spec_cache(paged_cache[k], stage_cache[k],
                                     lengths, n_write)
                for k in paged_cache}
    raise NotImplementedError(
        f"spec commit: unsupported cache leaf {type(paged_cache)}")


def set_block_table_rows(cache, slots, rows):
    """Push host block-table rows into every layer's device block table.
    slots: (n,) slot indices; rows: (n, pages_per_slot) int32.

    Per-page scales are deliberately NOT touched: a quantized page's
    scale lifecycle is tied to its first device write — the prefill
    scatter resets every page it touches, and a decode write at page
    offset 0 resets the page it opens (``repro.kvcache``) — so slot
    (re)allocation needs no host round trip over the scale tensors, and
    shared prefix pages mapped into several rows keep their scales."""
    slots = jnp.asarray(slots, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)

    def leaf(path, l):
        if "block_table" in jax.tree_util.keystr(path):
            if l.ndim == 3:                    # (G, S, P) stacked groups
                return l.at[:, slots, :].set(rows[None])
            return l.at[slots].set(rows)
        return l

    return jax.tree_util.tree_map_with_path(leaf, cache)


def paged_cache_shardings(cache, mesh):
    """NamedSharding pytree for a paged model cache on a serving mesh:
    page pools (…, KH, page, D) and scale tensors (…, KH) sharded BY KV
    HEAD over the "model" axis (matching the kernel's shard_map specs —
    see ``kernels/paged_attention/ops.py``), block tables and anything
    else replicated.  KV-head dims the axis does not divide replicate.
    Engines ``jax.device_put`` their freshly-allocated cache through this
    once so the pools START life sharded instead of being resharded on
    the first dispatch."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = mesh.shape.get("model", 1)

    def leaf(path, l):
        key = jax.tree_util.keystr(path)
        if ("k_pages" in key or "v_pages" in key) \
                and l.shape[l.ndim - 3] % m == 0:
            axes = (None,) * (l.ndim - 3) + ("model", None, None)
        elif ("k_scales" in key or "v_scales" in key) \
                and l.shape[l.ndim - 1] % m == 0:
            axes = (None,) * (l.ndim - 1) + ("model",)
        else:
            axes = (None,) * l.ndim
        return NamedSharding(mesh, P(*axes))

    return jax.tree_util.tree_map_with_path(leaf, cache)
