"""The whole train step's share of the chip's bf16 peak in the hybrid
cell: model operations per token (``work_hybrid.flops_per_token``, from
the configuration's shapes and the routed pairs the program counted in
the window; no recomputation, no embedding gather) times the tokens per
second of the traced window, over the peak."""
import work_hybrid


def read(ctx):
    if ctx["peak"] is None:
        return None
    drv = ctx["driver"]
    rate, tokens = drv.counters.get("tokens_per_s"), drv.counters.get("tokens")
    if not rate or not tokens:
        return None
    pairs = float(drv.counters["expert_pairs"].sum()) / tokens
    flops = work_hybrid.flops_per_token(drv.cfg, drv.traffic["seq_len"],
                                        pairs)
    return 100.0 * flops * rate / ctx["peak"]["bf16_flops_per_s"]
