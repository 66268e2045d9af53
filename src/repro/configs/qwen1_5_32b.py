"""Qwen1.5-32B — dense decoder with QKV bias and grouped-query attention.

Source: the model's own published ``config.json`` (Hugging Face
``Qwen/Qwen1.5-32B``, architecture ``Qwen2ForCausalLM``): hidden_size
5120, 64 layers, 40 attention heads over 8 key/value heads (GQA, head
dim 128), intermediate_size 27392, vocab_size 152064, QKV bias.

Assumed — recalled from that file, which cannot be re-read offline:
``rope_theta`` 1e6, ``rms_norm_eps`` 1e-6 and ``tie_word_embeddings``
false (a separate LM head).
"""
from repro.core.config import ModelConfig

FULL = ModelConfig(
    name="qwen1.5-32b",
    arch_type="dense",
    source="hf:Qwen/Qwen1.5-32B/config.json",
    n_layers=64,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    head_dim=128,
    d_ff=27392,
    vocab_size=152064,
    attn_type="gqa",
    qkv_bias=True,
    rope_theta=1000000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
    remat="full",
)

SMOKE = ModelConfig(
    name="qwen-smoke",
    arch_type="dense",
    n_layers=2,
    d_model=256,
    n_heads=4,
    n_kv_heads=4,
    head_dim=64,
    d_ff=512,
    vocab_size=512,
    attn_type="gqa",
    qkv_bias=True,
    vocab_pad_multiple=64,
)
