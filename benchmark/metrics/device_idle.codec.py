"""Share of the traced window of a codec cell in which no operation ran
on the chip: 1 - busy union / window, from the device plane's op events."""

from benchmark.trace import idle_percent as read  # noqa: F401
