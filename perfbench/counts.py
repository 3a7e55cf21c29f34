"""Operations and bytes the work needs, from shapes alone.

Each count is what the algorithm needs, whatever implements it, so a
roofline share or a utilization reads the same for any codec or kernel.
Nothing under ``src/`` reads these.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add its published numbers")
    return table[device_kind]


def sync_round_bytes(raw_bytes: int) -> int:
    """HBM bytes a lossless weight-sync round cannot avoid: read the new
    version, read the replica's base, write the replica's copy."""
    return 3 * raw_bytes


def matmul_params(*, hidden: int, heads: int, kv_heads: int, head_dim: int,
                  ffn: int, layers: int, vocab: int) -> int:
    """Parameters that take part in a matmul per token, for a decoder of
    GQA attention and a gated (three-matrix) MLP: the layers' projections
    and the LM head.  The embedding lookup is no matmul; a tied embedding
    counts once, as the head.  Norm scales are left out."""
    attn = hidden * heads * head_dim * 2 + hidden * kv_heads * head_dim * 2
    mlp = 3 * hidden * ffn
    return layers * (attn + mlp) + vocab * hidden


def train_flops_per_token(*, n_matmul: int, layers: int, heads: int,
                          head_dim: int, seq: int) -> int:
    """Forward and backward operations per trained token: 6 N for the
    weights, and 12 L (heads x head_dim) seq for attention's scores and
    weighted sum (no causal halving, no recomputation)."""
    return 6 * n_matmul + 12 * layers * heads * head_dim * seq


def mfu(*, tokens_per_s: float, flops_per_token: int, chips: int,
        peak_flops: float) -> float:
    """Model FLOP/s utilization as a share (0..1) of ``chips`` peaks."""
    return tokens_per_s * flops_per_token / (chips * peak_flops)


def codec_bytes(*, rs_raw: int, rs_wire: int, acc_bytes: int,
                ag_raw: int, ag_wire: int) -> int:
    """HBM bytes of one ZeRO-1 step's codec work on one chip, from its
    plan: the reduce-scatter's encode reads the raw gradient and writes
    the wire; its decode-reduce reads the wire and reads and writes the
    f32 accumulator; the all-gather's encode reads the raw parameters and
    writes the wire; its decode reads the wire and writes the raw bytes."""
    encode = (rs_raw + rs_wire) + (ag_raw + ag_wire)
    decode = (rs_wire + 2 * acc_bytes) + (ag_wire + ag_raw)
    return encode + decode


def roofline_share(*, flops: float = 0.0, hbm_bytes: float = 0.0,
                   seconds: float, peak: dict) -> float:
    """The least time the work needs on the chip (the larger of its
    operations over the FLOP/s peak and its bytes over the HBM peak),
    over ``seconds``, as a share (0..1)."""
    least = max(flops / peak["bf16_flops_per_s"],
                hbm_bytes / peak["hbm_bytes_per_s"])
    return least / seconds
