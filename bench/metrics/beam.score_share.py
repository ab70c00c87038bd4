"""Share of the window a beam search spends scoring candidates: the
durations of the program's ``beam.score`` spans (each support size's
``score_candidates`` calls and their top-n picks) over the searches of
the traced run's window, over the window."""


def read(ctx):
    return ctx["driver"].span_share("beam.score")
