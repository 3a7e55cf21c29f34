"""DeepSeek-V2's forward as its published modeling code
(``modeling_deepseek.py``) states it: the benchmark's reference for the
``deepseek_v2_lite`` configuration.

Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, and
nothing of the program under test.  It reads the weights of a parameter
tree by the names the program gives them (the tree is the data) and every
size from the configuration file's published keys:

* pre-norm RMSNorm, ``x * rsqrt(mean(x**2) + rms_norm_eps) * w``;
* MLA with full-rank queries (``q_lora_rank`` null): per head
  ``qk_nope_head_dim`` + ``qk_rope_head_dim`` query dims; the latent
  ``kv_a_proj_with_mqa`` (``kv_lora_rank`` + one shared rope key) with
  ``kv_a_layernorm`` on the latent before ``kv_b_proj`` gives per head a
  no-rope key and ``v_head_dim`` values; causal softmax at
  ``q_head_dim**-0.5 * mscale**2``, ``mscale = 0.1 * mscale_all_dim *
  ln(factor) + 1``;
* YaRN RoPE (``DeepseekV2YarnRotaryEmbedding``): frequencies blended
  between ``base**(-2i/d)`` and that over ``factor`` by a linear ramp
  between the correction dims of ``beta_fast`` and ``beta_slow``; cos and
  sin times ``mscale(mscale) / mscale(mscale_all_dim)``; applied as
  ``apply_rotary_pos_emb`` does, to the rope dims regrouped from adjacent
  pairs into halves;
* the first ``first_k_dense_replace`` layers a SwiGLU MLP of
  ``intermediate_size``; the others MoE: a float32 softmax router over
  ``n_routed_experts_published`` experts, greedy top ``num_experts_per_tok``,
  renormalised only if ``norm_topk_prob``, else scaled by
  ``routed_scaling_factor``; experts ``moe_intermediate_size`` wide; plus
  ``n_shared_experts`` shared experts as one MLP of their summed width;
* a final RMSNorm and the untied LM head.

Departures, each forced by the cut (``reduced``): the layer holds
``n_routed_experts`` of the router's experts, from ``first`` on, and gives
only their part of the routed result, as the program does; what the
experts held elsewhere would add is left out.  The vocabulary is the
file's slice.  The program's tree splits ``kv_a_proj_with_mqa`` into
``w_dkv`` / ``w_krope`` and ``kv_b_proj`` into ``w_uk`` / ``w_uv``, which
are the same matrices.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Largest logit error, over the reference logits' RMS, allowed to the
# program in bfloat16.  It holds weights and activations in bfloat16, so
# each matmul and the residual stream round at 2**-9, and its router's
# logits are rounded too: a token whose 6th and 7th gates lie within that
# rounding can take the other expert, which moves its output by a whole
# expert's share.  At small sizes on the CPU (three layers of width 64,
# head 16 + 8 rope, 16 experts routed top-6) the largest error read
# 0.09-0.14 with routing made exact (top-k over every expert, 6 seeds), and
# 0.085-0.36 (forward) and 0.05-0.31 (prefill and 4 decode steps) with it
# as published (10 seeds; at top-2 of 8 experts the flips took it to
# 0.73).  The limit is 1.4 times the largest.  Weights rounded through
# float8_e4m3fn (4 significant bits) read 0.97-1.89.
TOL_BF16 = 0.5

# The same error allowed to the replica at the ``deepseek_v2_lite`` cell's
# own cut (8 of 64 experts held, the published widths), which
# ``check_deepseek_v2.py`` reads on the chip.  Flips weigh less there: one
# moves the output only when one of its two experts is held here, and an
# expert is a smaller share of a wider residual stream.  Through the check
# on the CPU (a full and a delta round, prefill and 4 decode steps), with
# the stage's layers, experts and heads at narrower widths, the replica
# read 0.0275-0.0327 at hidden 512 (9 seeds) and 0.0414-0.0475 at hidden
# 1,024 (3 seeds); its float8 control read 0.438-0.647 and 0.619-0.720.
# Both grow with the width.  The limit is four times the largest replica
# reading and under half the smallest control's.
TOL_STAGE_BF16 = 0.2


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(conf: dict):
    """(inv_freq (d/2,), cos/sin factor, softmax scale) of the file's
    ``rope_scaling``, as ``DeepseekV2YarnRotaryEmbedding`` and
    ``DeepseekV2Attention`` compute them."""
    rs, d, base = conf["rope_scaling"], conf["qk_rope_head_dim"], \
        conf["rope_theta"]
    factor = rs["factor"]

    def correction_dim(rotations):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    freq_extra = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freq_inter = 1.0 / (factor * base ** (jnp.arange(0, d, 2,
                                                      dtype=jnp.float32) / d))
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    cos_sin = (_mscale(factor, rs["mscale"])
               / _mscale(factor, rs["mscale_all_dim"]))
    q_head_dim = conf["qk_nope_head_dim"] + d
    scale = q_head_dim ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv_freq, cos_sin, scale


def _rotate(x, cos, sin):
    """``apply_rotary_pos_emb`` on x (B, S, heads, d): adjacent pairs
    regrouped into halves, then ``x * cos + rotate_half(x) * sin``."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def mla(a, x, conf):
    """Latent attention of one layer on the normed stream x (B, S, hidden)."""
    B, S, _ = x.shape
    H = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    inv_freq, cos_sin, scale = yarn(conf)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([ang, ang], -1)
    cos = (jnp.cos(emb) * cos_sin)[None, :, None]
    sin = (jnp.sin(emb) * cos_sin)[None, :, None]
    q = (x @ a["wq"]).reshape(B, S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    c = rms_norm(x @ a["w_dkv"], a["kv_norm"], conf["rms_norm_eps"])
    k_pe = (x @ a["w_krope"])[:, :, None]
    k_nope = (c @ a["w_uk"]).reshape(B, S, H, nope)
    v = (c @ a["w_uv"]).reshape(B, S, H, conf["v_head_dim"])
    q_pe, k_pe = _rotate(q_pe, cos, sin), _rotate(k_pe, cos, sin)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkxd->bhqk", q_pe, k_pe)) * scale
    pos = jnp.arange(S)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, -1) @ a["wo"]


def mlp(f, x):
    return (jax.nn.silu(x @ f["w1"]) * (x @ f["w3"])) @ f["w2"]


def moe(f, x, conf, first: int = 0):
    """The held experts' part of the routed result, plus the shared
    experts, on the normed stream x (B, S, hidden)."""
    scores = jax.nn.softmax(x @ f["router"], -1)  # float32 router
    assert scores.shape[-1] == conf["n_routed_experts_published"]
    w, idx = jax.lax.top_k(scores, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    else:
        w = w * conf["routed_scaling_factor"]
    held = first + jnp.arange(conf["n_routed_experts"])
    gate = jnp.sum(w[..., None] * (idx[..., None] == held), -2)  # (B,S,E)
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, f["we1"])) * jnp.einsum(
        "bsd,edf->bsef", x, f["we3"])
    routed = jnp.einsum("bsef,efd,bse->bsd", h, f["we2"], gate)
    return routed + mlp(f["shared"], x)


def layer(p, h, conf, dense: bool, first: int = 0):
    eps = conf["rms_norm_eps"]
    h = h + mla(p["mixer"], rms_norm(h, p["norm1"], eps), conf)
    x = rms_norm(h, p["norm2"], eps)
    return h + (mlp(p["ffn"], x) if dense else moe(p["ffn"], x, conf, first))


def forward(params, tokens, conf: dict, first: int = 0):
    """Logits, float32 (B, S, vocab_size), of ``tokens`` (B, S); the MoE
    layers hold experts ``first`` .. ``first + n_routed_experts - 1``."""
    params = jax.tree.map(_f32, params)
    n_dense = conf["first_k_dense_replace"]
    (blocks,) = params["blocks"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens]
        for i in range(conf["num_hidden_layers"]):
            p = (params[f"prefix_{i}"] if i < n_dense else
                 jax.tree.map(lambda w: w[i - n_dense], blocks))
            h = layer(p, h, conf, i < n_dense, first)
        h = rms_norm(h, params["final_norm"], conf["rms_norm_eps"])
        return h @ params["lm_head"].T
