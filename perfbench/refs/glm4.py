"""GLM-4's forward as its published modeling code states it: the
benchmark's reference for the ``glm4_9b`` configuration.

Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, and
nothing of the program under test.  It reads the weights of a parameter
tree by the names the program gives them (the tree is the data) and every
size from the configuration file's published keys:

* pre-norm RMSNorm, ``x * rsqrt(mean(x**2) + layernorm_epsilon) * w``;
* q, k and v projections with bias (``add_qkv_bias``), no bias on the
  output projection or the MLP (``add_bias_linear: false``);
* ``num_attention_heads`` query heads over ``multi_query_group_num`` KV
  groups of ``kv_channels`` dims, head ``h`` on group
  ``h // (heads / groups)``, scale ``1/sqrt(kv_channels)``, causal;
* RoPE on the first ``kv_channels // 2`` dims of each head in adjacent
  pairs ``(x[2i], x[2i+1])``, frequencies ``1/base**(2i/(kv_channels//2))``;
  the other dims pass unrotated;
* SwiGLU ``silu(x W_gate) * (x W_up)`` (GLM's fused ``dense_h_to_4h`` is
  ``[W_gate | W_up]``), then ``dense_4h_to_h``;
* a final RMSNorm and the untied LM head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROPE_BASE = 10000.0  # 10000 * rope_ratio; the configuration has no rope_ratio
# Largest logit error, over the reference logits' RMS, allowed to the
# program in bfloat16.  It holds weights and activations in bfloat16 (8
# significant bits), so each matmul and the residual stream round at 2**-9;
# at a small size (two layers of width 64) the largest error came to
# 4.6-5.8% on five seeds.  The limit is 2.5 times that.  Weights rounded
# through float8_e4m3fn (4 significant bits) read 67-94%.
TOL_BF16 = 0.15


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, dims):
    """x (B, S, heads, head_dim): the first ``dims`` rotated pairwise."""
    inv = 1.0 / ROPE_BASE ** (jnp.arange(0, dims, 2, dtype=jnp.float32)
                              / dims)
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, dims/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    pairs = x[..., :dims].reshape(*x.shape[:-1], dims // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return jnp.concatenate([out.reshape(*x.shape[:-1], dims),
                            x[..., dims:]], -1)


def layer(p, h, conf):
    """One decoder layer on the residual stream ``h`` (B, S, hidden)."""
    B, S, _ = h.shape
    heads, groups = conf["num_attention_heads"], conf["multi_query_group_num"]
    hd, eps = conf["kv_channels"], conf["layernorm_epsilon"]
    a, f = p["mixer"], p["ffn"]
    pos = jnp.arange(S)
    x = rms_norm(h, p["norm1"], eps)
    q = (x @ a["wq"] + a["bq"]).reshape(B, S, heads, hd)
    k = (x @ a["wk"] + a["bk"]).reshape(B, S, groups, hd)
    v = (x @ a["wv"] + a["bv"]).reshape(B, S, groups, hd)
    q, k = rope(q, pos, hd // 2), rope(k, pos, hd // 2)
    k = jnp.repeat(k, heads // groups, axis=2)
    v = jnp.repeat(v, heads // groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    h = h + o.reshape(B, S, heads * hd) @ a["wo"]
    x = rms_norm(h, p["norm2"], eps)
    return h + (jax.nn.silu(x @ f["w1"]) * (x @ f["w3"])) @ f["w2"]


def forward(params, tokens, conf: dict):
    """Logits, float32 (B, S, padded_vocab_size), of ``tokens`` (B, S)."""
    (blocks,) = params["blocks"]
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[tokens]
        for i in range(conf["num_layers"]):
            h = layer(jax.tree.map(lambda w: _f32(w[i]), blocks), h, conf)
        h = rms_norm(h, _f32(params["final_norm"]), conf["layernorm_epsilon"])
        return h @ _f32(params["lm_head"]).T
