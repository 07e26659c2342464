"""The prefix-extend kernel's share of its roofline, in percent: the
least time its calls in the traced window need (each row's cached keys
read once, its chunk attended causally) over their device time in the
trace."""
from benchlib import flops

# the name the trace gives the kernel's operations
PATTERN = r"^paged_prefix_extend_pallas(\.\d+)?$"
KERNEL = "prefix_extend"


def read(ctx):
    t = ctx.trace
    if not t or not t["kernel_s"].get(KERNEL):
        return None
    rows = [(a, w) for s in ctx.traced_steps for a, w, _ in s.chunks if a > 0]
    if not rows:
        return None
    f, b = flops.prefix_extend_cost(ctx.dims, rows)
    need, _ = flops.least_time(f * ctx.dims.layers, b * ctx.dims.layers,
                               ctx.peaks)
    return 100.0 * need / t["kernel_s"][KERNEL]
