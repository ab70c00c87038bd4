"""99th percentile of the time requests waited in the service's queue
before their batch formed: ``queue_wait_s`` of the program's
``service.request`` spans over the traced window."""
import numpy as np


def read(ctx):
    waits = ctx["driver"].counters.get("queue_waits_s")
    if not waits:
        return None
    return float(np.percentile(waits, 99) * 1e3)
