"""The program's own spans and named regions in a device trace.

An addition to ``benchlib.tracing`` that leaves its record and its
numbers as they are.  ``capture`` is ``tracing.capture`` plus the
program's host spans (``PROGRAM_SPANS``, the ``TraceAnnotation``s that
``repro.obs.trace.span`` opens) and, for each device, the named-scope
path of each operation that ran.  ``reduce`` is ``tracing.reduce`` plus
the host seconds of each program span, the device's idle time under
each span, the idle time inside running programs and the decode
program's device time by named region; its ``idle_gaps`` name the
program phase the host was in.

The scope path comes from the program's HLO, which the profiler keeps
in its ``/host:metadata`` plane (one ``Hlo Proto`` stat per program):
each instruction's ``metadata.op_name``, the ``jax.named_scope`` path
JAX gave it.  ``ProfileData`` does not expose those stats, so
``capture`` reads that plane with a small protobuf reader of its own.
XLA adds copies and layout changes that carry no scope; inside the
decode program those are counted as ``copy`` by their opcode.
"""
from __future__ import annotations

import bisect
import glob
import re

from benchlib import tracing

# the program's spans of one scheduler tick (``repro.obs.trace.span``):
# the tick, then the leaf spans inside it, which never overlap
STEP_SPAN = "sched.step"
PROGRAM_SPANS = (STEP_SPAN, "sched.admit", "sched.prefill.prep",
                 "sched.prefill.launch", "sched.prefill.wait",
                 "sched.prefill.finish", "sched.grow",
                 "engine.decode.prep", "engine.decode.launch",
                 "engine.decode.wait", "engine.decode.emit")
# the program's named regions (``jax.named_scope``); an operation
# belongs to the innermost one on its path
PROGRAM_SCOPES = ("kv_pool", "kv_write", "attn_kernel", "proj_mlp",
                  "sample")
DECODE_MODULE = "jit__decode_impl"
# the decode program's regions that move the KV pool: each layer's cache
# sliced out of the stacked pool and written back, the page write, and
# the copies XLA adds that carry no region
POOL_REGIONS = ("kv_pool", "kv_write", "copy")
_COPY = re.compile(r"^copy(-start|-done)?$")


def capture(log_dir: str) -> dict:
    """``tracing.capture``'s record of the one trace under ``log_dir``,
    with the program's spans among its host spans and each device's
    ``scopes``: ``{module: {operation: [opcode, scope path]}}`` for the
    operations that ran."""
    from jax.profiler import ProfileData
    rec = tracing.capture(log_dir)
    pb = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    for plane in ProfileData.from_file(pb).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        rec["host_spans"].append(
                            [e.name, e.start_ns, e.duration_ns])
    ran = {}                            # program -> its operations' names
    for dev in rec["devices"].values():
        mods, starts = _modules(dev)
        for name, s, _ in dev["ops"]:
            ran.setdefault(_module_of(mods, starts, s), set()).add(name)
    with open(pb, "rb") as f:
        hlo = _hlo_ops(f.read(), set(ran))
    for dev in rec["devices"].values():
        dev["scopes"] = {
            m: {op: v for op, v in hlo[m].items() if op in ran[m]}
            for m in sorted({d[0] for d in dev["modules"]} & set(hlo))}
    return rec


def reduce(rec: dict, kernels: dict):
    """``tracing.reduce``'s numbers, or None when the record holds no
    device, with four more (seconds, averaged over the devices):
    ``span_s`` (host seconds of each program span in the window),
    ``idle_by_span`` (idle under the innermost program span, else the
    innermost harness span, else ``none``; it sums to the window's
    idle), ``idle_in_program_s`` (idle while a program ran: gaps between
    its operations, not the host's doing) and ``scope_s`` (the decode
    program's device time by innermost named region; operations with
    none are ``copy`` or ``unscoped``).  Its ``idle_gaps`` name the
    program leaf span that overlaps each gap most, else the tick, else
    the harness span."""
    red = tracing.reduce(rec, kernels)
    if red is None:
        return None
    ndev = len(rec["devices"])
    lo, hi = tracing._window(rec)
    harness = sorted(s for s in rec["host_spans"]
                     if s[0] in tracing.HOST_SPANS)
    prog = sorted(s for s in rec["host_spans"] if s[0] in PROGRAM_SPANS)
    leaves = [s for s in prog if s[0] != STEP_SPAN]
    steps = [s for s in prog if s[0] == STEP_SPAN]
    span_s: dict = {}
    for name, s, d in tracing._in_window(prog, lo, hi):
        span_s[name] = span_s.get(name, 0.0) + d / 1e9
    segments = _segments(prog, harness, lo, hi)
    idle_by: dict = {}
    in_program = 0.0
    scope_s: dict = {}
    gaps = []
    for dev in rec["devices"].values():
        # the leaf operations and idle gaps of ``tracing.reduce``
        ops = [o for o in tracing._in_window(dev["ops"], lo, hi)
               if not tracing._ENCLOSING.match(o[0])]
        u = tracing._union(ops, lo, hi)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        dev_gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s]
        gaps += [(e - s, s, e) for s, e in dev_gaps]
        for label, ns in _overlaps(dev_gaps, segments):
            idle_by[label] = idle_by.get(label, 0.0) + ns / 1e9
        mods, starts = _modules(dev)
        running = tracing._union(mods, lo, hi)
        in_program += sum(ns for _, ns in _overlaps(
            dev_gaps, [(s, e, None) for s, e in running])) / 1e9
        scopes = dev.get("scopes", {})
        for name, s, d in ops:
            mod = _module_of(mods, starts, s)
            if mod.startswith(DECODE_MODULE + "("):
                key = _region(scopes.get(mod, {}).get(name), name)
                scope_s[key] = scope_s.get(key, 0.0) + d / 1e9
    gaps.sort(key=lambda g: -g[0])
    red.update(
        idle_gaps=[[_label((leaves, steps, harness), s, e), g / 1e9]
                   for g, s, e in gaps[:10]],
        span_s=span_s,
        idle_by_span={k: v / ndev for k, v in idle_by.items()},
        idle_in_program_s=in_program / ndev,
        scope_s={k: v / ndev for k, v in scope_s.items()})
    return red


def pool_copy_s(red):
    """Device seconds the decode program spent moving the KV pool in the
    traced window (``POOL_REGIONS``), or None where the program has no
    named regions."""
    if not red or "kv_pool" not in red.get("scope_s", {}):
        return None
    return sum(red["scope_s"].get(k, 0.0) for k in POOL_REGIONS)


def _modules(dev: dict) -> tuple:
    mods = sorted(dev["modules"], key=lambda m: m[1])
    return mods, [m[1] for m in mods]


def _module_of(mods, starts, t) -> str:
    """The program (``XLA Modules`` event name) running at ``t``, or
    ``?``."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][0] if i >= 0 and t < mods[i][1] + mods[i][2] else "?"


def _label(groups, s, e) -> str:
    """The span that overlaps the gap ``[s, e)`` most, from the first
    of ``groups`` (program leaf spans, the tick, harness spans) that has
    one overlapping it."""
    for spans in groups:
        best, label = 0, None
        for name, st, d in spans:
            ov = min(e, st + d) - max(s, st)
            if ov > best:
                best, label = ov, name
        if label is not None:
            return label
    return "none"


def _segments(prog, harness, lo, hi) -> list:
    """``[lo, hi)`` cut into ``(start, end, label)``: the innermost
    program span over each instant (a leaf, else the tick), else the
    innermost harness span, else ``none``."""
    cuts = sorted({lo, hi} | {min(max(x, lo), hi) for _, s, d in
                               prog + harness for x in (s, s + d)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        label = "none"
        for spans in (harness, prog):
            inner = [(s, -d, n) for n, s, d in spans if s <= a and b <= s + d]
            if inner:
                label = max(inner)[2]
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def _overlaps(gaps, segments):
    """``(label, ns)`` of each overlap of sorted gaps ``(s, e)`` with
    sorted, disjoint ``(start, end, label)`` segments."""
    j = 0
    for s, e in gaps:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, label = segments[k]
            ov = min(e, b) - max(s, a)
            if ov > 0:
                yield label, ov
            k += 1


def _region(op, name: str) -> str:
    """An operation's innermost named region; one without a region is
    ``copy`` when it is a copy and ``unscoped`` otherwise."""
    opcode, path = op if op else ("", "")
    for part in reversed(path.split("/")):
        if part in PROGRAM_SCOPES:
            return part
    base = opcode or re.sub(r"\.\d+$", "", name)
    return "copy" if _COPY.match(base) else "unscoped"


# ---------------------------------------------------------------------------
# the HLO of each program, from the profiler's metadata plane


def _varint(b, i):
    r = sh = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << sh
        if c < 0x80:
            return r, i
        sh += 7


def _fields(b):
    """``(field number, value)`` of a protobuf message: ints for
    varints, memoryviews for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif kind in (1, 5):
            ln = 8 if kind == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _hlo_ops(xspace: bytes, modules) -> dict:
    """``{module: {instruction: [opcode, op_name path]}}`` for the
    programs named in ``modules`` (``XLA Modules`` event names, which
    are the metadata plane's event names), from their ``Hlo Proto``
    stats (XSpace planes=1; XPlane name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata name=2, stats=5; XStat
    metadata_id=1, bytes_value=6; HloProto hlo_module=1; module
    computations=3; computation instructions=2; instruction name=1,
    opcode=2, metadata=7; OpMetadata op_name=2)."""
    out: dict = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        pf = list(_fields(plane))
        if not any(k == 2 and bytes(v) == b"/host:metadata"
                   for k, v in pf):
            continue
        stat_ids = set()
        for k, v in pf:
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                if bytes(meta.get(2, b"")) == b"Hlo Proto":
                    stat_ids.add(entry.get(1, 0))
        for k, v in pf:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            name = next((bytes(x).decode() for j, x in meta if j == 2), "")
            if name not in modules:
                continue
            for j, stat in meta:
                st = dict(_fields(stat)) if j == 5 else {}
                if st.get(1) in stat_ids and 6 in st:
                    out[name] = _instructions(st[6])
    return out


def _instructions(hlo_proto) -> dict:
    ops = {}
    for f, mod in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(mod):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                d = dict(_fields(ins))
                path = dict(_fields(d.get(7, b""))).get(2, b"")
                ops[bytes(d.get(1, b"")).decode()] = [
                    bytes(d.get(2, b"")).decode(), bytes(path).decode()]
    return ops
