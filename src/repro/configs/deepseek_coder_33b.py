"""DeepSeek-Coder 33B (arXiv:2401.14196; hf). llama-arch.

62L d_model=7168 56H (GQA kv=8) d_ff=19200 vocab=32256 untied, head_dim=128;
RoPE base 1e5 with linear scaling x4 over 16384 positions, as the
published config.json states.
"""
from repro.config import GateConfig, ModelConfig, RopeScaling

CONFIG = ModelConfig(
    arch_id="deepseek_coder_33b",
    family="dense",
    num_layers=62,
    d_model=7168,
    n_heads=56,
    n_kv_heads=8,
    head_dim=128,
    d_ff=19200,
    vocab_size=32256,
    rope_theta=100000.0,
    rope_scaling=RopeScaling("linear", 4.0),
    max_position_embeddings=16384,
    norm_eps=1e-6,
    tie_embeddings=False,
    gate=GateConfig(enabled=True, block_size=64, d_gate=128,
                    token_budget=4096),
)
