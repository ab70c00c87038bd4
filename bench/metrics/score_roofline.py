"""Share of the chip's roofline reached by the scoring work: the least
time for the work of every batch dispatched in the traced window
(``work.score_batch`` at each batch's request count, the model's feature
count and grid), over the device's busy time in the window."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv, trace = ctx["driver"], ctx["trace"]
    batches = drv.counters.get("dispatch_batches")
    if not batches or trace["busy_s"] <= 0:
        return None
    work, peak = ctx["work"], ctx["peak"]
    p, g = drv.counters["features"], drv.cfg["serve"]["grid"]
    least = sum(work.least_seconds(work.score_batch(b, p, g), peak)[0]
                for b in batches)
    return 100.0 * least / trace["busy_s"]
