"""Device milliseconds per train step of the optimizer (the gradient's
clip and the AdamW update): the device self time under the program's
``optim.adamw`` scope in the traced window over the steps completed in
it."""
import scopes


def read(ctx):
    t = scopes.seconds(ctx, scopes.train_hlo, "optim.adamw")
    if t is None:
        return None
    steps = ctx["driver"].counters.get("steps")
    if not steps:
        return None
    return 1e3 * t / steps
