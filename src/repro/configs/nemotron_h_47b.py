"""Nemotron-H-47B-Base-8K [hf: nvidia/Nemotron-H-47B-Base-8K, config.json].

Hybrid: 98 layers laid out by `hybrid_override_pattern`, each one mixer with
its own pre-RMSNorm and residual: 45 Mamba2 (`M`: 256 heads of 64, 8 groups
of B/C, state 256, conv 4), 48 squared-ReLU MLPs (`-`: width 30720, no
gate) and 5 GQA attention layers (`*`: 64 heads, 8 KV heads of 128, no
bias, no position encoding). d_model 8192, vocab 131072, untied head.
"""
from repro.configs.base import ModelConfig

PATTERN = ("M-M-M-M-M-M-M-M-M*-M-M-M-M-M-M-M-M-M-M*-M-M-M-M-M*-M-M-M-M-M*-"
           "M-M-M-M-M-M-M---MM---M-M*-M-M-M-M-M-")

CONFIG = ModelConfig(
    name="nemotron-h-47b",
    family="hybrid",
    n_layers=len(PATTERN),
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=30720,
    vocab=131072,
    attn_kind="full",
    mlp_kind="relu2",
    tie_embeddings=False,
    rope="none",
    norm_eps=1e-5,
    max_seq_len=8192,
    ssm_kind="mamba2",
    ssm_state=256,
    ssm_head_dim=64,
    ssm_groups=8,
    layer_pattern=PATTERN,
)
