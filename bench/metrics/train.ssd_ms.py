"""Device milliseconds per train step of the SSD (the discretization,
the chunked state scan and the skip): the device self time under the
program's ``ssm.ssd`` scope in the traced window, forward, backward and
recomputation alike, over the steps completed in it."""
import scopes


def read(ctx):
    t = scopes.seconds(ctx, scopes.train_hlo, "ssm.ssd")
    if t is None:
        return None
    steps = ctx["driver"].counters.get("steps")
    if not steps:
        return None
    return 1e3 * t / steps
