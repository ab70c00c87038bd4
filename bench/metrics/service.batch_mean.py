"""Mean requests per dispatched batch: ``batch`` of the program's
``service.dispatch`` spans over the traced window."""
import numpy as np


def read(ctx):
    batches = ctx["driver"].counters.get("dispatch_batches")
    if not batches:
        return None
    return float(np.mean(batches))
