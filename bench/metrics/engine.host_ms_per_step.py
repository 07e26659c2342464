"""Host time of one scheduler tick outside its syncs, in ms: the
seconds of the program span ``sched.step`` less those of its waits for
the device (``sched.prefill.wait``, ``engine.decode.wait``), over the
ticks (program span counters, over the window).  Nothing to read where
the program has no such spans."""


def read(ctx):
    def span_s(name):
        return ctx.counter("serve_span_seconds_total", span=name)

    n = ctx.counter("serve_spans_total", span="sched.step")
    if not n:
        return None
    host = span_s("sched.step") - span_s("sched.prefill.wait") \
        - span_s("engine.decode.wait")
    return 1e3 * host / n
