"""Host time of the prefill phase per thousand prompt tokens computed
(program counters: prefill-phase seconds over computed prompt tokens)."""


def read(ctx):
    toks = ctx.counter("sched_prefill_tokens_total")
    s = ctx.counter("serve_phase_seconds_total", phase="prefill")
    return 1e3 * s / (toks / 1e3) if toks else None
