"""Device milliseconds per train step of the expert layers (routing,
dispatch, the held experts, combine and the shared expert): the device
self time under the program's ``moe.*`` scopes in the traced window,
forward, backward and recomputation alike, over the steps in it."""
import scopes_hybrid


def read(ctx):
    return scopes_hybrid.ms_per_step(
        ctx, "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
        "moe.shared")
