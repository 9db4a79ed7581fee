"""Device idle share of the traced window of the PD cell
(:func:`bench.trace.idle_percent`)."""

from bench.trace import idle_percent as read  # noqa: F401
