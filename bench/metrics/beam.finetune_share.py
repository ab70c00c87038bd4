"""Share of the window a beam search spends finetuning candidate
supports: the durations of the program's ``beam.finetune`` spans (each
support size's ``finetune`` calls, one per unique candidate) over the
searches of the traced run's window, over the window."""


def read(ctx):
    return ctx["driver"].span_share("beam.finetune")
