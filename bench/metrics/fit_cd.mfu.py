"""The whole solve's share of the chip's bf16 peak: the operations one
coordinate sweep needs (``work.cd_sweep``, from n and p) times the
sweeps per second of the window's solves, over the peak. It bounds any
kernel's share from above: a kernel taken off the path leaves its own
roofline silent, this one keeps reading."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv = ctx["driver"]
    sweep_s = drv.counters.get("sweep_s")
    if not sweep_s:
        return None
    flops = ctx["work"].cd_sweep(drv.cfg["n"], drv.cfg["p"])["flops"]
    return 100.0 * flops / sweep_s / ctx["peak"]["bf16_flops_per_s"]
