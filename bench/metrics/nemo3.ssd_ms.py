"""Device milliseconds per train step of the Mamba-2 blocks' SSD (the
discretization, the chunked state scan over groups of B and C, the
skip): the device self time under the program's ``ssm.ssd`` scope in
the traced window over the steps in it."""
import scopes_hybrid


def read(ctx):
    return scopes_hybrid.ms_per_step(ctx, "ssm.ssd")
