"""The whole model step's share of the chip's peak in the batch cell
(the same quantity as ``mfu.online``)."""
from benchlib.readers import step_mfu as read  # noqa: F401
