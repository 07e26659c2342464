"""The paged decode kernel's share of its roofline, in percent: the
least time its calls in the traced window need (memory-bound: every key
and value read once) over their device time in the trace."""
from benchlib import flops

# the name the trace gives the kernel's operations
PATTERN = r"^paged_attention_pallas(\.\d+)?$"
KERNEL = "paged_decode"


def read(ctx):
    t = ctx.trace
    if not t or not t["kernel_s"].get(KERNEL):
        return None
    keys = [k for s in ctx.traced_steps for k in s.decode_keys]
    if not keys:
        return None
    f, b = flops.paged_decode_cost(ctx.dims, keys)
    need, _ = flops.least_time(f * ctx.dims.layers, b * ctx.dims.layers,
                               ctx.peaks)
    return 100.0 * need / t["kernel_s"][KERNEL]
