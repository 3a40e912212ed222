"""device: the share of the traced window in which no operation ran on
the chip, in percent (``devtrace.idle_percent``)."""
from devtrace import idle_percent as read  # noqa: F401
