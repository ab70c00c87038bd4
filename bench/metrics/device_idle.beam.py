"""Share of the traced stretch of a beam search in which no operation ran
on the device: 1 - (union of device-operation intervals) / (stretch)."""
import tracing


def read(ctx):
    return tracing.idle_percent(ctx["trace"])
