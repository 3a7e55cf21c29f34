"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, shared+routed MoE
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048, 16 MLA heads (no query LoRA; per head 128 no-rope + 64
rope dims for q/k, 128 for v; RMSNorm on the 512-dim latent), YaRN RoPE
(factor 40 over 4,096 original positions, mscale 0.707 of all dims) in
adjacent pairs, vocab 102400, untied head.  The first layer is dense
(d_ff=10944); the other 26 are MoE: 64 routed experts of hidden 1408,
top-6 of a softmax router without renormalisation, plus 2 shared experts.
"""
from repro.models.config import ArchConfig, LayerSpec, MLACfg, MoECfg

_MLA = MLACfg(kv_lora=512, rope_dim=64, kv_norm=True, yarn_factor=40.0,
              yarn_original_len=4096, yarn_beta_fast=32.0,
              yarn_beta_slow=1.0, yarn_mscale=0.707,
              yarn_mscale_all_dim=0.707)

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    d_model=2048,
    n_heads=16,
    kv_heads=16,
    d_ff=10944,  # the dense first layer; experts are moe.d_expert wide
    vocab=102400,
    head_dim=128,
    prefix=(LayerSpec(mixer="mla", ffn="swiglu"),),
    pattern=(LayerSpec(mixer="mla", ffn="moe"),),
    repeats=26,
    moe=MoECfg(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
               norm_topk=False),
    mla=_MLA,
    rope_interleaved=True,
)

SMOKE = ArchConfig(
    name="deepseek-v2-lite-smoke",
    d_model=64,
    n_heads=4,
    kv_heads=4,
    d_ff=128,
    vocab=256,
    head_dim=16,
    prefix=(LayerSpec(mixer="mla", ffn="swiglu"),),
    pattern=(LayerSpec(mixer="mla", ffn="moe"),),
    repeats=2,
    moe=MoECfg(n_experts=16, top_k=6, n_shared=2, d_expert=32,
               norm_topk=False),
    mla=MLACfg(kv_lora=32, rope_dim=8, kv_norm=True, yarn_factor=40.0,
               yarn_original_len=4096, yarn_beta_fast=32.0,
               yarn_beta_slow=1.0, yarn_mscale=0.707,
               yarn_mscale_all_dim=0.707),
    rope_interleaved=True,
)
