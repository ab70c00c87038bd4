"""Device microseconds of the coordinate update per coordinate (the
prox and the ``beta`` and ``eta`` update): the device self time under
the program's ``cd.update`` scope in the traced stretch of a solve, over
that stretch, times the wall time per sweep of the window's solves,
over p."""
import scopes


def read(ctx):
    t = scopes.seconds(ctx, scopes.fit_hlo, "cd.update")
    if t is None or ctx["trace"]["window_s"] <= 0:
        return None
    drv = ctx["driver"]
    return 1e6 * t / ctx["trace"]["window_s"] * drv.counters["sweep_s"] \
        / drv.cfg["p"]
