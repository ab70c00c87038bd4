"""Share of the traced window of nemotron3.train in which no operation
ran on the device: 1 - (union of device-operation intervals) /
(window)."""
import tracing


def read(ctx):
    return tracing.idle_percent(ctx["trace"])
