"""Share of the chip's roofline reached by the held experts' part of the
step: the least time of the work it needs (``work_hybrid.held_experts_step``:
the up and down projections of the routed pairs the program counted,
the pairs' rows and the experts' weights moved once) over the device
time under the program's ``moe.dispatch``, ``moe.experts`` and
``moe.combine`` scopes, per step of the traced window. The count comes
from the routed-pair counter and leaves out recomputation and the work
an implementation does on tokens that did not choose an expert, so it
stays a lower bound."""
import scopes_hybrid
import work_hybrid


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv = ctx["driver"]
    ms = scopes_hybrid.ms_per_step(ctx, "moe.dispatch", "moe.experts",
                                   "moe.combine")
    if ms is None:
        return None
    pairs = float(drv.counters["expert_pairs"].sum()) / drv.counters["steps"]
    least, _ = ctx["work"].least_seconds(
        work_hybrid.held_experts_step(drv.cfg, pairs), ctx["peak"])
    return 100.0 * least / (ms * 1e-3)
