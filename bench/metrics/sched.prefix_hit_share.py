"""Share of the prompt tokens admitted in the window that the prefix
cache served, in percent: hit tokens over hit plus computed tokens
(program counters)."""


def read(ctx):
    hit = ctx.counter("sched_prefix_hit_tokens_total")
    computed = ctx.counter("sched_prefill_tokens_total")
    return 100.0 * hit / (hit + computed) if hit + computed else None
