"""Operations and bytes of the layer-pattern hybrid's train step, from
its shapes and the routed-pair counter alone (``work.py`` holds the
other counts). Every count is a lower bound, as in ``work.py``: what the
algorithm needs, whatever implements it; recomputation is not counted.
"""
from __future__ import annotations

F32 = 4


def mamba_flops(cfg: dict) -> float:
    """Forward operations per token of one Mamba-2 block, counted as
    ``work.mamba2_flops_per_token`` counts them, with G groups of B and
    C and an inner width of heads x head size."""
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    e = h * p
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    n = cfg["ssm_state_size"]
    w = cfg["conv_kernel"]
    return (2.0 * d * (2 * e + 2 * gn + h) + 2.0 * w * (e + 2 * gn)
            + 5.0 * h * p * n + 2.0 * h * p + 2.0 * e * d)


def attention_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations per token of one attention block: the four
    projections and, under the causal mask, scores and values over
    (seq_len + 1) / 2 keys on average."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2.0 * d * (2 * q + 2 * kv) + 4.0 * q * (seq_len + 1) / 2.0


def expert_pair_flops(cfg: dict) -> float:
    """Forward operations of one routed (token, expert) pair: up and
    down projections of one expert."""
    return 4.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_flops(cfg: dict) -> float:
    """Forward operations per token of one expert layer besides its
    routed pairs: the router over every expert and the shared expert."""
    d = cfg["hidden_size"]
    return (2.0 * d * cfg["published"]["n_routed_experts"]
            + 4.0 * d * cfg["moe_shared_expert_intermediate_size"])


def flops_per_token(cfg: dict, seq_len: int, pairs_per_token: float
                    ) -> float:
    """Model operations per token of one train step (forward and
    backward, 3x forward) of the stack under a pooled linear head;
    ``pairs_per_token`` is the routed pairs of the held experts, over
    every expert layer, per token, as the program counted them."""
    pattern = cfg["hybrid_override_pattern"]
    fwd = (pattern.count("M") * mamba_flops(cfg)
           + pattern.count("*") * attention_flops(cfg, seq_len)
           + pattern.count("E") * expert_layer_flops(cfg)
           + pairs_per_token * expert_pair_flops(cfg))
    return 3.0 * fwd + 3.0 * 2.0 * cfg["hidden_size"] / seq_len


def held_experts_step(cfg: dict, pairs: float) -> dict:
    """Operations and bytes of the held experts' part of one train step,
    for ``pairs`` routed pairs over every expert layer: each pair's up
    and down projections, forward and backward (3x forward); the least
    bytes are each pair's input row read and output row written, forward
    and backward, and each held expert's weights read forward and
    backward and their gradient written once."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = cfg["hybrid_override_pattern"].count("E")
    weights = layers * cfg["n_routed_experts"] * 2.0 * d * ff
    return {"flops": 3.0 * pairs * expert_pair_flops(cfg),
            "bytes": F32 * (4.0 * pairs * d + 3.0 * weights)}
