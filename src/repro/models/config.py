"""Architecture configuration schema for the model zoo.

Every assigned architecture is expressed as an ``ArchConfig``; the generic
stack in ``transformer.py`` interprets it.  Layer heterogeneity (gemma's
5:1 local:global, jamba's 1:7 attn:mamba + alternating MoE, xlstm's
mLSTM/sLSTM mix, deepseek's leading dense layers) is expressed as a
*super-block pattern* that repeats: parameters for each pattern position are
stacked over repeats and scanned, which keeps the lowered HLO compact (one
unrolled super-block per pattern, `lax.scan` over repeats).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int = 0  # routed experts
    top_k: int = 0
    n_shared: int = 0  # always-on shared experts
    d_expert: int = 0  # expert FFN hidden size


@dataclasses.dataclass(frozen=True)
class MLACfg:
    kv_lora: int = 512  # latent dim for compressed KV
    q_lora: int = 0  # 0 = full-rank queries
    rope_dim: int = 64  # decoupled RoPE sub-dim per head


@dataclasses.dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One position inside the repeating super-block."""

    mixer: str = "attn"  # attn | mla | mamba | mlstm | slstm
    ffn: str = "swiglu"  # swiglu | moe | none
    window: Optional[int] = None  # sliding-window size; None = global attn


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    # layer layout: prefix (unrolled) + pattern x repeats (scanned)
    pattern: Sequence[LayerSpec] = (LayerSpec(),)
    repeats: int = 1
    prefix: Sequence[LayerSpec] = ()
    head_dim: Optional[int] = None  # default d_model // n_heads
    moe: MoECfg = MoECfg()
    mla: MLACfg = MLACfg()
    mamba: MambaCfg = MambaCfg()
    # encoder-decoder (whisper): encoder of n_enc homogeneous attn layers,
    # frontend stubbed (precomputed frame embeddings enter the encoder).
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500  # stub frontend sequence length
    frontend: str = "none"  # none | audio_stub | vision_stub
    rope_theta: float = 10000.0
    # RoPE on the first rope_dims of each head (None: all of them), as two
    # halves or, interleaved, as adjacent pairs (x[2i], x[2i+1])
    rope_dims: Optional[int] = None
    rope_interleaved: bool = False
    mrope: bool = False  # qwen2-vl M-RoPE (text-only degenerate = RoPE; stub)
    qkv_bias: bool = False  # bias vectors on the q, k and v projections
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # serving
    sub_quadratic: bool = False  # eligible for long_500k
    notes: str = ""

    @property
    def n_layers(self) -> int:
        return len(self.prefix) + len(self.pattern) * self.repeats

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def param_count(self) -> int:
        """Analytic parameter count — exact: tests assert it equals the
        element count of a real ``transformer.init`` (used for 6ND FLOPs)."""
        d, v = self.d_model, self.vocab
        total = v * d  # embed
        if not self.tie_embeddings:
            total += v * d
        total += d  # final_norm
        specs = list(self.prefix) + list(self.pattern) * self.repeats
        for s in specs:
            total += self._mixer_params(s.mixer) + self._ffn_params(s.ffn)
            total += d  # norm1
            if s.ffn != "none":
                total += d  # norm2
            if self.enc_dec:
                total += d  # normx (pre-cross-attention norm)
        if self.enc_dec:
            total += self.n_enc_layers * (
                self._mixer_params("attn") + self._ffn_params("swiglu") + 2 * d
            )
            total += d  # enc_norm
            total += self.enc_seq * d  # enc_pos
            # cross-attention in every decoder layer
            total += self.n_layers * self._mixer_params("attn")
        return total

    def _mixer_params(self, mixer: str) -> int:
        d, hd = self.d_model, self.hd
        if mixer == "attn":
            q = d * self.n_heads * hd
            kv = 2 * d * self.kv_heads * hd
            o = self.n_heads * hd * d
            bias = (self.n_heads + 2 * self.kv_heads) * hd if self.qkv_bias else 0
            return q + kv + o + bias
        if mixer == "mla":
            m = self.mla
            q = d * self.n_heads * (hd + m.rope_dim) if not m.q_lora else (
                d * m.q_lora + m.q_lora * self.n_heads * (hd + m.rope_dim)
            )
            kv_down = d * (m.kv_lora + m.rope_dim)
            kv_up = m.kv_lora * self.n_heads * 2 * hd
            o = self.n_heads * hd * d
            return q + kv_down + kv_up + o
        if mixer == "mamba":
            di = self.mamba.expand * d
            return (
                d * 2 * di  # in_proj
                + di * self.mamba.d_conv  # conv
                + di * (2 * self.mamba.d_state + 1)  # B, C, dt proj (fused)
                + di * self.mamba.d_state  # A
                + di * d  # out_proj
                + 2 * di  # d_skip + dt_bias
            )
        if mixer in ("mlstm", "slstm"):
            q = d * self.n_heads * hd
            kv = 2 * d * self.kv_heads * hd
            gates = 2 * d * self.n_heads  # i/f gate projections
            o = self.n_heads * hd * d
            return q + kv + gates + o
        raise ValueError(mixer)

    def _ffn_params(self, ffn: str) -> int:
        d = self.d_model
        if ffn == "swiglu":
            return 3 * d * self.d_ff
        if ffn == "moe":
            m = self.moe
            routed = m.n_experts * 3 * d * m.d_expert
            shared = m.n_shared * 3 * d * m.d_expert
            router = d * m.n_experts
            return routed + shared + router
        if ffn == "none":
            return 0
        raise ValueError(ffn)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k + shared experts)."""
        if all(s.ffn != "moe" for s in list(self.prefix) + list(self.pattern)):
            return self.param_count()
        d = self.d_model
        total = self.param_count()
        specs = list(self.prefix) + list(self.pattern) * self.repeats
        for s in specs:
            if s.ffn == "moe":
                m = self.moe
                inactive = (m.n_experts - m.top_k) * 3 * d * m.d_expert
                total -= inactive
        return total
