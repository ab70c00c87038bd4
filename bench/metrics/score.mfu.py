"""The whole scoring work's share of the chip's bf16 peak: the
operations of every batch dispatched in the traced window
(``work.score_batch`` at each batch's request count, the model's feature
count and grid), over the traced window, over the peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv, trace = ctx["driver"], ctx["trace"]
    batches = drv.counters.get("dispatch_batches")
    if not batches or trace["window_s"] <= 0:
        return None
    p, g = drv.counters["features"], drv.cfg["serve"]["grid"]
    flops = sum(ctx["work"].score_batch(b, p, g)["flops"] for b in batches)
    return 100.0 * flops / trace["window_s"] / ctx["peak"]["bf16_flops_per_s"]
