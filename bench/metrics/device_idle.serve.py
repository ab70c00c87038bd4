"""Share of the traced window of appc.serve in which no operation ran on
the device: 1 - (union of device-operation intervals) / (window)."""
import tracing


def read(ctx):
    return tracing.idle_percent(ctx["trace"])
