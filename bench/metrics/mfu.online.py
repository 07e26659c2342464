"""The whole model step's share of the chip's peak in the online cells
(prompt tokens computed and tokens decoded, over the prefill and decode
phases' seconds)."""
from benchlib.readers import step_mfu as read  # noqa: F401
