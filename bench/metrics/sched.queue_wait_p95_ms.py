"""95th percentile of the scheduler's queue wait (program stamps
``t_admit - t_submit``) of the requests first admitted in the window."""
from benchlib.client import percentile


def read(ctx):
    w = ctx.window
    waits = [r["t_admit"] - r["t_submit"] for r in ctx.requests
             if r["t_admit"] is not None and w.t0 <= r["t_admit"] <= w.t_close]
    return 1e3 * percentile(waits, 95) if waits else None
