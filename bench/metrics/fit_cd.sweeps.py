"""Coordinate sweeps per solve: ``FitResult.n_iters``, the program's own
count, averaged over the solves of the window. A count that repeats
exactly for a cohort; fewer sweeps to the same tolerance show here
before they show in ``solve_s``."""


def read(ctx):
    return ctx["driver"].counters.get("sweeps_mean")
