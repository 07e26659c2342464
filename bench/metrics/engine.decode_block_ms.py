"""Host time of one fused decode block, dispatch plus its sync
(program counters: decode-phase seconds over decode dispatches)."""


def read(ctx):
    n = ctx.counter("serve_decode_dispatches_total")
    s = ctx.counter("serve_phase_seconds_total", phase="decode")
    return 1e3 * s / n if n else None
