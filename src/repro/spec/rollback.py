"""Paged-cache rollback invariants for speculative decoding.

The spec engine is WRITE-AFTER-ACCEPT (``repro.spec.engine``): a verify
round holds the draft chunk's K/V in a bf16 staging cache and commits
only the accepted prefix, replaying the baseline's sequential token
writes.  Rejected drafts therefore never touch a live page — no
quantized page scale can be grown by a rejected tail, no requant of
accepted entries ever happens on their behalf — and rolling back IS a
host-side length truncation (:func:`rollback_length`).  Positions past
the truncated length hold stale bytes only on the NULL page (masked
writes) or nothing at all; the next committed write at a page's offset 0
resets its running amax scale exactly as plain decode does
(``kvcache._quant_token_write`` — the requant-on-next-write behaviour).

What still needs guarding is sharing: a page mapped by several
block-table rows, or held alive by the prefix cache, must NEVER receive
a speculative commit — other readers see its bytes.  In the current
admission flow shared pages are always FULL prompt pages strictly below
a slot's length (prefix hits are page-aligned; ``_finish_prefill``
inserts only full prompt pages), so the write span past ``lengths`` can
never overlap one — but :func:`ensure_exclusive_tail` enforces it
structurally with copy-on-write, which also future-proofs flows that do
share decode-tail pages (beam / n-best — a ROADMAP open item).
Invariants are property-tested in tests/test_sched.py.
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np

from repro.serve.paged import PageAllocator, set_block_table_rows


def span_pages(start: int, end: int, page_size: int) -> List[int]:
    """Logical page indices a write span [start, end) touches."""
    if end <= start:
        return []
    return list(range(start // page_size, (end - 1) // page_size + 1))


def copy_page_device(cache, src: int, dst: int):
    """Copy one physical page's K/V contents AND its quantized scales
    from ``src`` to ``dst`` in every layer's pools (stacked-group layouts
    included) — the device half of a copy-on-write."""
    def leaf(path, l):
        ks = jax.tree_util.keystr(path)
        if "k_pages" in ks or "v_pages" in ks:
            if l.ndim == 5:                       # (G, N, KH, page, D)
                return l.at[:, dst].set(l[:, src])
            return l.at[dst].set(l[src])
        if "k_scales" in ks or "v_scales" in ks:
            if l.ndim == 3:                       # (G, N, KH)
                return l.at[:, dst].set(l[:, src])
            return l.at[dst].set(l[src])
        return l

    return jax.tree_util.tree_map_with_path(leaf, cache)


def ensure_exclusive_tail(cache, alloc: PageAllocator, slot: int,
                          start: int, end: int, page_size: int):
    """Make every page in the speculative write span [start, end) of
    ``slot`` exclusively owned (refcount 1) before a verify round: any
    shared page — mapped by another row or held by the prefix cache —
    is copy-on-written (fresh page, device copy of contents + scales,
    block-table row update host AND device).  Never rolls back into /
    writes through a shared page.  Returns the (possibly updated) cache;
    a no-op in the common case where the tail is already exclusive."""
    touched = False
    for li in span_pages(start, end, page_size):
        if li >= alloc.max_pages_per_slot:
            break
        owned = alloc.owned(slot)
        if li >= len(owned):
            break                          # lazy growth allocates later
        p = int(alloc.table[slot, li])
        if p != 0 and alloc.refs[p] > 1:
            fresh = alloc.cow(slot, li)
            cache = copy_page_device(cache, p, fresh)
            touched = True
    if touched:
        cache = set_block_table_rows(cache, np.asarray([slot]),
                                     alloc.table[[slot]])
    return cache


def rollback_length(alloc: PageAllocator, slot: int, old_len: int,
                    new_len: int, page_size: int) -> List[int]:
    """Roll a slot back from ``old_len`` to ``new_len`` cached tokens
    after a rejected speculative tail.  Under write-after-accept this is
    pure bookkeeping: no page frees (the slot keeps its lazily-grown
    pages for the next round) and no device work.  Asserts the rejected
    span's pages were exclusively owned — a shared page there would mean
    :func:`ensure_exclusive_tail` was skipped.  Returns the rejected
    span's physical pages (for tests / audits)."""
    assert 0 <= new_len <= old_len, (new_len, old_len)
    pages = []
    owned = alloc.owned(slot)
    for li in span_pages(new_len, old_len, page_size):
        if li >= len(owned):
            break
        p = int(owned[li])
        assert alloc.refs[p] == 1, \
            f"rollback into shared page {p} (refs={alloc.refs[p]})"
        pages.append(p)
    return pages
