"""Share of the chip's roofline reached by a whole coordinate sweep.

The least time one sweep's work needs on this chip (``work.cd_sweep``,
from n and p; the HBM bound applies) over the device's busy time per
sweep: the busy share of the traced stretch of a solve times the wall
time per sweep of the window's solves. The count does not depend on
what implements the sweep."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv, trace = ctx["driver"], ctx["trace"]
    if trace["busy_s"] <= 0 or trace["window_s"] <= 0:
        return None
    work = ctx["work"]
    least, _ = work.least_seconds(work.cd_sweep(drv.cfg["n"], drv.cfg["p"]),
                                  ctx["peak"])
    busy_per_sweep = trace["busy_s"] / trace["window_s"] \
        * drv.counters["sweep_s"]
    return 100.0 * least / busy_per_sweep
