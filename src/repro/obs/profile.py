"""repro.obs.profile — sync-free per-dispatch device-time profiling.

Attributes measured wall-clock to every engine dispatch kind — ``admit``
(batched prefill), ``prefill_chunk`` (scheduler continuation chunk),
``decode_block`` (fused multi-token decode), ``spec_round`` (draft
verify) and ``draft_propose`` — labeled by the live config arm (KV
dtype, weight quant + matmul impl, pow2 chunk/width bucket, draft_k,
mesh shape).

Sync-free by construction: ``record()`` consumes only the two host
``time.perf_counter()`` timestamps the engines already take around each
dispatch (before the jit call, after the existing block-boundary sync),
plus host-side shape/dtype metadata (``.shape``/``.dtype`` attribute
reads never touch device buffers).  The compiled ``cost_analysis()``
FLOPs / HBM bytes per dispatch signature are resolved *lazily* — at
summary/export time, off the hot path — by lowering the engine's own
jit function against captured ``ShapeDtypeStruct`` trees, so each
sample family carries measured *attainment*: achieved FLOP/s (or HBM
B/s) over the :class:`~repro.core.costmodel.HwTier` peak.

``sync_count`` and greedy token streams are bit-identical with
profiling on and off (``tests/test_profile.py`` audits this the same
way PR 8 audited tracing).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Tuple

__all__ = ["DispatchProfiler", "ProfileSample", "DISPATCH_KINDS"]

DISPATCH_KINDS = ("admit", "prefill_chunk", "decode_block", "spec_round",
                  "draft_propose")


@dataclasses.dataclass
class ProfileSample:
    """One measured dispatch.  ``dur_s`` covers device dispatch + the
    block-boundary host sync the engine pays anyway."""
    kind: str                  # one of DISPATCH_KINDS
    arm: str                   # config-arm label incl. pow2 bucket
    dur_s: float
    tokens: int = 0            # real (unpadded) tokens processed
    rows: int = 0              # batch rows in the dispatch
    steps: int = 1             # scan steps (decode_block) in the dispatch
    bucket: int = 0            # pow2 pad bucket (plen/chunk/width/block)
    ctx: int = 0               # live context length (host lengths max)
    cost_key: Optional[tuple] = None   # -> lazy cost_analysis signature


def _sig(abstract_args, static_kwargs) -> tuple:
    import jax
    leaves = jax.tree_util.tree_leaves(abstract_args)
    return (tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
            tuple(sorted(static_kwargs.items())))


def _abstract(args):
    import jax
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)


class DispatchProfiler:
    """Per-dispatch wall-clock attribution.  Disabled by default: every
    method is a no-op until constructed with ``enabled=True`` (mirrors
    :class:`repro.obs.trace.Tracer`)."""

    def __init__(self, enabled: bool = False, *, tier=None):
        self.enabled = bool(enabled)
        self.samples: List[ProfileSample] = []
        self.arm = ""                       # bound config-arm label
        self.tier = tier                    # HwTier for attainment math
        # cost-analysis signatures: key -> (jitfn, abstract_args, static)
        self._cost_specs: Dict[tuple, tuple] = {}
        self._cost_cache: Dict[tuple, Optional[Tuple[float, float]]] = {}

    # ------------------------------------------------------------------
    # binding + hot-path record (host-only, zero syncs)

    def bind(self, cfg, *, model_parallel: int = 1):
        """Derive the config-arm label from the live ModelConfig."""
        if not self.enabled:
            return self
        self.arm = (f"kv={cfg.kv_cache_dtype},"
                    f"q={cfg.quant}:{cfg.quant_matmul_impl},"
                    f"k={cfg.spec_draft_k},mp={int(model_parallel)}")
        return self

    def record(self, kind: str, t0: float, t1: float, *, tokens: int = 0,
               rows: int = 0, steps: int = 1, bucket: int = 0,
               ctx: int = 0, cost=None):
        """Store one sample from timestamps the engine already took.

        ``cost`` is an optional ``(jitfn, args, static_kwargs)`` triple;
        only shape/dtype metadata is captured here (sync-free), the
        compiled cost_analysis is resolved lazily in :meth:`flops_bytes`.
        """
        if not self.enabled:
            return
        cost_key = None
        if cost is not None:
            jitfn, args, static_kwargs = cost
            static_kwargs = static_kwargs or {}
            abstract = _abstract(args)
            cost_key = (kind, _sig(abstract, static_kwargs))
            if cost_key not in self._cost_specs:
                self._cost_specs[cost_key] = (jitfn, abstract, static_kwargs)
        self.samples.append(ProfileSample(
            kind=kind, arm=f"{self.arm},b={int(bucket)}", dur_s=t1 - t0,
            tokens=int(tokens), rows=int(rows), steps=int(steps),
            bucket=int(bucket), ctx=int(ctx), cost_key=cost_key))

    # ------------------------------------------------------------------
    # lazy cost_analysis (off the hot path)

    def flops_bytes(self, cost_key) -> Optional[Tuple[float, float]]:
        """(FLOPs, HBM bytes) for one dispatch signature, from the
        compiled program's cost_analysis.  Compiles at most once per
        signature; returns None when XLA reports nothing.  A compile
        failure propagates: it is the same program the engine runs."""
        if cost_key is None:
            return None
        if cost_key in self._cost_cache:
            return self._cost_cache[cost_key]
        from repro.launch.roofline import resolve_cost_analysis
        jitfn, abstract, static_kwargs = self._cost_specs[cost_key]
        compiled = jitfn.lower(*abstract, **static_kwargs).compile()
        ca = resolve_cost_analysis(compiled)
        out = ((float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0))) if ca else None)
        self._cost_cache[cost_key] = out
        return out

    # ------------------------------------------------------------------
    # aggregation

    def summary(self, tier=None) -> Dict[str, dict]:
        """Per-(kind × arm) aggregates: sample count, total measured
        seconds, tokens, FLOPs/HBM bytes (compiled cost_analysis × call
        count) and roofline attainment vs the HwTier peak."""
        tier = tier or self.tier
        agg: Dict[tuple, dict] = {}
        for s in self.samples:
            a = agg.setdefault((s.kind, s.arm), {
                "kind": s.kind, "arm": s.arm, "count": 0, "seconds": 0.0,
                "tokens": 0, "rows": 0, "flops": 0.0, "hbm_bytes": 0.0})
            a["count"] += 1
            a["seconds"] += s.dur_s
            a["tokens"] += s.tokens
            a["rows"] += s.rows
            fb = self.flops_bytes(s.cost_key)
            if fb is not None:
                a["flops"] += fb[0]
                a["hbm_bytes"] += fb[1]
        out = {}
        for (kind, arm), a in agg.items():
            if a["seconds"] > 0 and (a["flops"] or a["hbm_bytes"]):
                a["achieved_flops_per_s"] = a["flops"] / a["seconds"]
                a["achieved_hbm_bytes_per_s"] = a["hbm_bytes"] / a["seconds"]
                if tier is not None:
                    from repro.launch.mesh import HW
                    chips = tier.chips
                    peak_f = chips * HW["peak_flops_bf16"]
                    peak_b = chips * HW["hbm_bw"]
                    a["attainment"] = max(
                        a["achieved_flops_per_s"] / peak_f,
                        a["achieved_hbm_bytes_per_s"] / peak_b)
            out[f"{kind}|{arm}"] = a
        return out

    # ------------------------------------------------------------------
    # export

    def export_gauges(self, registry, tier=None):
        """Fold the per-(kind × arm) aggregates into a MetricsRegistry.
        Called at artifact-write time (never on the hot path), so the
        lazy compiles land here.  No-op when profiling is disabled, so
        the default metric schema is untouched."""
        if not self.enabled:
            return
        g_sec = registry.gauge(
            "profile_dispatch_seconds_total",
            "measured dispatch+sync wall-clock by kind and config arm")
        g_cnt = registry.gauge(
            "profile_dispatch_count", "profiled dispatches by kind and arm")
        g_att = registry.gauge(
            "profile_roofline_attainment",
            "achieved work rate over HwTier peak (max of FLOP/s and HBM "
            "B/s fractions)")
        for a in self.summary(tier).values():
            lbl = dict(kind=a["kind"], arm=a["arm"])
            g_sec.set(a["seconds"], **lbl)
            g_cnt.set(a["count"], **lbl)
            if "attainment" in a:
                g_att.set(a["attainment"], **lbl)

    def to_json(self) -> dict:
        return {"arm": self.arm,
                "samples": [dataclasses.asdict(s) for s in self.samples]}

    def write(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, default=str)
