"""Share of the traced window in which the chips ran no operation,
averaged over the four."""

from benchlib.trace import idle_percent as read  # noqa: F401
