"""Share of the traced window in which the device ran no operation."""

from benchlib.trace import idle_percent as read  # noqa: F401
