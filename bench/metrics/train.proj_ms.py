"""Device milliseconds per train step of every layer's projections in
and out: the device self time under the program's ``ssm.in_proj`` and
``ssm.out_proj`` scopes in the traced window, forward, backward and
recomputation alike, over the steps completed in it."""
import scopes


def read(ctx):
    t = scopes.seconds(ctx, scopes.train_hlo, "ssm.in_proj", "ssm.out_proj")
    if t is None:
        return None
    steps = ctx["driver"].counters.get("steps")
    if not steps:
        return None
    return 1e3 * t / steps
