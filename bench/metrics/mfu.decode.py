"""The decode phase's share of the chip's peak: the model operations of
the tokens decoded in the window over the decode phase's seconds."""
from benchlib.readers import step_mfu


def read(ctx):
    return step_mfu(ctx, prefill=False)
