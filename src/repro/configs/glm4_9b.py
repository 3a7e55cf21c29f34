"""glm4-9b [dense] — the paper's own RL-training workload (Table 1,
Fig. 10a/12: weight tensors collected during GLM4-9B training) [hf:THUDM].

40L d_model=4096 32H (GQA kv=2) d_ff=13696 vocab=151552.  GLM-4's attention:
bias on q, k and v (``add_qkv_bias``; none on the output or the MLP), RoPE on
the first 64 of each head's 128 dimensions in adjacent pairs, and RMSNorm
epsilon 1.5625e-07 (``layernorm_epsilon``).
Used by examples/rl_weight_sync.py to reproduce the paper's weight-update
experiment (gate_up_proj 214 MB-class tensors).
"""
from repro.models.config import ArchConfig, LayerSpec

CONFIG = ArchConfig(
    name="glm4-9b",
    d_model=4096,
    n_heads=32,
    kv_heads=2,
    d_ff=13696,
    vocab=151552,
    pattern=(LayerSpec(mixer="attn", ffn="swiglu"),),
    repeats=40,
    qkv_bias=True,
    rope_dims=64,
    rope_interleaved=True,
    norm_eps=1.5625e-07,
)

SMOKE = ArchConfig(
    name="glm4-smoke",
    d_model=64,
    n_heads=4,
    kv_heads=2,
    d_ff=160,
    vocab=256,
    pattern=(LayerSpec(mixer="attn", ffn="swiglu"),),
    repeats=2,
    qkv_bias=True,
    rope_dims=8,
    rope_interleaved=True,
    norm_eps=1.5625e-07,
)
