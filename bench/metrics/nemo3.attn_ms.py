"""Device milliseconds per train step of the attention block: the device
self time under the program's ``attn.proj`` and ``attn.core`` scopes in
the traced window over the steps in it."""
import scopes_hybrid


def read(ctx):
    return scopes_hybrid.ms_per_step(ctx, "attn.proj", "attn.core")
