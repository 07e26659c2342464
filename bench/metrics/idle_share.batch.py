"""Share of the traced window in which no operation ran on the device,
in percent, in the batch cell."""
from benchlib.readers import idle_share as read  # noqa: F401
