"""qwen2.5-3b [dense]: GQA kv=2, QKV bias, tied embeddings.
[hf:Qwen/Qwen2.5-3B config.json: tie_word_embeddings=true, rms_norm_eps=1e-6]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b", family="dense",
    n_layers=36, d_model=2048, n_heads=16, n_kv_heads=2,
    d_ff=11008, vocab_size=151936,
    qkv_bias=True, activation="silu", rope_theta=1e6,
    norm_eps=1e-6, tie_embeddings=True,
)
