"""nemotron-3-nano-30b-a3b [pattern] — Nemotron-H hybrid: 52 blocks in the
pattern below (23 Mamba-2, 23 expert layers, 6 attention), each
x + mixer(RMSNorm(x)). Mamba-2: 64 heads x 64, state 128, 8 groups of
B/C, conv 4, gated norm per group. Expert layer: 128 routed relu^2
experts of width 1856, top-6 of a sigmoid router with a score-correction
bias, normalized and scaled by 2.5, plus one shared expert of width 3712.
Attention: 32 query heads, 2 KV heads of 128, causal, no positional
encoding. Untied 131,072-row vocabulary, eps 1e-5.
[huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json]"""
from .base import ModelConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b", family="pattern", n_layers=len(PATTERN),
    d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128, d_ff=1856,
    vocab_size=131072, layer_pattern=PATTERN, ssm_state=128,
    ssm_head_dim=64, ssm_heads=64, ssm_groups=8, ssm_chunk=128,
    ssm_norm_eps=1e-5, rms_eps=1e-5, n_experts=128, n_experts_per_tok=6,
    routed_scaling=2.5, shared_expert_ff=3712, supports_long_context=True,
)
