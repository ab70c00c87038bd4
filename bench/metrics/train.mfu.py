"""The whole train step's share of the chip's bf16 peak: model
operations per token (``work.mamba2_flops_per_token``, from the
configuration's shapes; no recomputation, no embedding gather) times the
tokens per second of the traced window, over the peak."""


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv = ctx["driver"]
    rate = drv.counters.get("tokens_per_s")
    if not rate:
        return None
    flops = ctx["work"].mamba2_flops_per_token(drv.cfg,
                                                drv.traffic["seq_len"])
    return 100.0 * flops * rate / ctx["peak"]["bf16_flops_per_s"]
