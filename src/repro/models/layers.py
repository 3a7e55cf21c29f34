"""Model-zoo building blocks: attention (GQA / MLA / sliding-window /
cross), RoPE & M-RoPE, SwiGLU, MoE (capacity-based EP dispatch), Mamba SSM,
xLSTM (mLSTM/sLSTM) — all pure JAX, scan-friendly, shardable.

Conventions:
  * params are nested dicts of arrays; each ``init_*`` has a matching
    ``spec_*`` returning a PartitionSpec pytree (TP over the ``model`` axis).
  * activations: (B, S, D); caches: dict per layer.
  * attention is q-chunked (online full-KV per chunk) to bound live memory
    on 32k+ sequences; decode is a single-query fast path with optional
    context-parallel KV (sequence sharded over the manual ``data`` axis,
    combined with a logsumexp reduction) for ``long_500k`` cells.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig, LayerSpec, MLACfg

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# ---------------------------------------------------------------------------
# RoPE (M-RoPE degenerates to RoPE for text-only positions; vision/temporal
# sections are stubbed per the assignment: frontends provide embeddings).
# ---------------------------------------------------------------------------

def rope_table(positions, dim, theta, inv=None):
    """positions (..., S) -> cos/sin (..., S, dim//2); ``inv`` replaces the
    frequencies ``theta**(-2i/dim)``."""
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-scale factor (``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, m: MLACfg) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies (``DeepseekV2YarnRotaryEmbedding``):
    each of the ``dim // 2`` frequencies blends ``theta**(-2i/dim)`` with
    it divided by the factor, by a ramp clamped to [0, 1] that runs from
    ``low`` to ``high``, the dims at which ``beta_fast`` and ``beta_slow``
    rotations fit into the original context."""
    def corr_dim(rotations):
        return (dim * math.log(m.yarn_original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(m.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(m.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    return (extra / m.yarn_factor * ramp + extra * (1 - ramp)).astype(
        np.float32)


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x (B, S, H, hd); cos/sin (B, S, r//2) or (S, r//2).  Rotates the
    first r dims of each head, as two halves or, ``interleaved``, as
    adjacent pairs (x[2i], x[2i+1]); the other hd - r dims pass."""
    r = 2 * cos.shape[-1]
    xf = x.astype(jnp.float32)
    xr = xf[..., :r] if r < x.shape[-1] else xf
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    y1, y2 = x1 * cos - x2 * sin, x1 * sin + x2 * cos
    if interleaved:
        y = jnp.stack([y1, y2], -1).reshape(xr.shape)
    else:
        y = jnp.concatenate([y1, y2], -1)
    if r < x.shape[-1]:
        y = jnp.concatenate([y, xf[..., r:]], -1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, sliding window, chunked online softmax)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, cfg.n_heads * hd), dtype),
        "wk": _dense_init(ks[1], (d, cfg.kv_heads * hd), dtype),
        "wv": _dense_init(ks[2], (d, cfg.kv_heads * hd), dtype),
        "wo": _dense_init(ks[3], (cfg.n_heads * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_heads * hd,), dtype)
    return p


def spec_attention(cfg: ArchConfig):
    s = {
        "wq": P(None, "model"), "wk": P(None, "model"),
        "wv": P(None, "model"), "wo": P("model", None),
    }
    if cfg.qkv_bias:
        s.update(bq=P("model"), bk=P("model"), bv=P("model"))
    return s


def project(params, x, name: str):
    """``x @ params["w" + name]``, plus ``params["b" + name]`` where the
    projection has a bias (``ArchConfig.qkv_bias``)."""
    y = x @ params["w" + name]
    b = params.get("b" + name)
    return y if b is None else y + b


def _tile_mask(qpos, kpos, causal, window):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= qpos[:, None] - kpos[None, :] < window
    return mask


from functools import lru_cache as _lru_cache


@_lru_cache(maxsize=None)
def _make_flash(causal: bool, window, q_chunk: int, kv_chunk: int):
    """Flash attention with a hand-written two-pass tiled backward.

    Forward saves only (q, k, v, O, L) — L the per-query logsumexp — and
    the backward recomputes score tiles, so live memory in BOTH directions
    is one (B,Hkv,G,q_chunk,kv_chunk) f32 tile.  This is the pure-jnp twin
    of the Pallas kernel layout (VMEM-tile-bounded working set)."""

    def fwd_chunks(q5, kh, vh):
        # q5 (n_q, B, Hkv, G, C, hd) f32; kh/vh (n_kv, B, Hkv, kc, hd)
        n_kv, kv_c = kh.shape[0], kh.shape[3]
        C = q5.shape[4]
        dv = vh.shape[-1]

        def one_q(args):
            qh, qidx = args
            qpos = qidx * q_chunk + jnp.arange(C)

            def kv_step(carry, inp):
                m, l, acc = carry
                k_t, v_t, kidx = inp
                kpos = kidx * kv_c + jnp.arange(kv_c)
                s = jnp.einsum("bhgcd,bhsd->bhgcs", qh,
                               k_t.astype(jnp.float32))
                s = jnp.where(_tile_mask(qpos, kpos, causal, window)[
                    None, None, None], s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - m_safe[..., None])
                p = jnp.where(jnp.isfinite(s), p, 0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                l = l * corr + jnp.sum(p, axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bhgcs,bhsd->bhgcd", p, v_t.astype(jnp.float32))
                return (m_new, l, acc), None

            B, Hkv = qh.shape[0], qh.shape[1]
            G = qh.shape[2]
            m0 = jnp.full((B, Hkv, G, C), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, Hkv, G, C), jnp.float32)
            a0 = jnp.zeros((B, Hkv, G, C, dv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (kh, vh, jnp.arange(n_kv)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            # logsumexp; +inf for fully-masked rows so bwd p == 0 exactly
            L = jnp.where(l > 0, jnp.where(jnp.isfinite(m), m, 0.0)
                          + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
            return out, L

        return jax.lax.map(one_q, (q5, jnp.arange(q5.shape[0])))

    def flash(q5, kh, vh):
        out, _ = fwd_chunks(q5, kh, vh)
        return out

    def flash_fwd(q5, kh, vh):
        out, L = fwd_chunks(q5, kh, vh)
        return out, (q5, kh, vh, L, out)

    def flash_bwd(res, dO):
        q5, kh, vh, L, out = res
        n_q, B, Hkv, G, C, hd = q5.shape
        n_kv, kv_c = kh.shape[0], kh.shape[3]
        dv = vh.shape[-1]
        delta = jnp.sum(dO * out, axis=-1)

        # pass 1: dq — map over q chunks, scan over kv tiles
        def dq_one(args):
            qh, dO_c, L_c, delta_c, qidx = args
            qpos = qidx * q_chunk + jnp.arange(C)

            def kv_step(dq, inp):
                k_t, v_t, kidx = inp
                kpos = kidx * kv_c + jnp.arange(kv_c)
                s = jnp.einsum("bhgcd,bhsd->bhgcs", qh,
                               k_t.astype(jnp.float32))
                s = jnp.where(_tile_mask(qpos, kpos, causal, window)[
                    None, None, None], s, -jnp.inf)
                p = jnp.exp(s - L_c[..., None])
                dp = jnp.einsum("bhgce,bhse->bhgcs", dO_c,
                                v_t.astype(jnp.float32))
                ds = p * (dp - delta_c[..., None])
                return dq + jnp.einsum("bhgcs,bhsd->bhgcd", ds,
                                       k_t.astype(jnp.float32)), None

            dq0 = jnp.zeros((B, Hkv, G, C, hd), jnp.float32)
            dq, _ = jax.lax.scan(kv_step, dq0, (kh, vh, jnp.arange(n_kv)))
            return dq

        dq = jax.lax.map(dq_one, (q5, dO, L, delta, jnp.arange(n_q)))

        # pass 2: dk, dv — map over kv tiles, scan over q chunks
        def dkv_one(args):
            k_t, v_t, kidx = args
            kpos = kidx * kv_c + jnp.arange(kv_c)

            def q_step(carry, inp):
                dk_t, dv_t = carry
                qh, dO_c, L_c, delta_c, qidx = inp
                qpos = qidx * q_chunk + jnp.arange(C)
                s = jnp.einsum("bhgcd,bhsd->bhgcs", qh,
                               k_t.astype(jnp.float32))
                s = jnp.where(_tile_mask(qpos, kpos, causal, window)[
                    None, None, None], s, -jnp.inf)
                p = jnp.exp(s - L_c[..., None])
                dv_t = dv_t + jnp.einsum("bhgcs,bhgce->bhse", p, dO_c)
                dp = jnp.einsum("bhgce,bhse->bhgcs", dO_c,
                                v_t.astype(jnp.float32))
                ds = p * (dp - delta_c[..., None])
                dk_t = dk_t + jnp.einsum("bhgcs,bhgcd->bhsd", ds, qh)
                return (dk_t, dv_t), None

            dk0 = jnp.zeros((B, Hkv, kv_c, hd), jnp.float32)
            dv0 = jnp.zeros((B, Hkv, kv_c, dv), jnp.float32)
            (dk_t, dv_t), _ = jax.lax.scan(
                q_step, (dk0, dv0), (q5, dO, L, delta, jnp.arange(n_q)))
            return dk_t, dv_t

        dk, dvv = jax.lax.map(dkv_one, (kh, vh, jnp.arange(n_kv)))
        return dq, dk, dvv

    f = jax.custom_vjp(flash)
    f.defvjp(flash_fwd, flash_bwd)
    return f


def _attend_chunked(q, k, v, *, causal, window, q_offset=0, q_chunk=512,
                    kv_chunk=1024, scale=None):
    """Double-chunked flash attention (pure jnp, custom tiled VJP).

    q (B,Sq,H,hd), k/v (B,Sk,Hkv,hd).  Memory in both directions is bounded
    by one (B,Hkv,G,q_chunk,kv_chunk) f32 score tile.  ``q_offset`` shifts
    query positions (must be a static int here; decode uses
    ``_decode_attend``); ``scale`` replaces the softmax scale
    ``1/sqrt(hd)``."""
    assert q_offset == 0, "non-zero q_offset not used by current callers"
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    if Sq % q_chunk:
        q_chunk = Sq
    if Sk % kv_chunk:
        kv_chunk = Sk
    n_q, n_kv = Sq // q_chunk, Sk // kv_chunk
    dv = v.shape[-1]
    # pre-scale q so the kernel computes plain dot products.  Inputs stay in
    # their storage dtype (bf16): tiles are cast to f32 inside the kernel,
    # matching the MXU's bf16xbf16->f32 path and halving the staged q/k/v
    # buffers (§Perf iteration: memory term).
    q5 = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(
        B, n_q, q_chunk, Hkv, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kh = k.transpose(0, 2, 1, 3).reshape(
        B, Hkv, n_kv, kv_chunk, hd).transpose(2, 0, 1, 3, 4)
    vh = v.transpose(0, 2, 1, 3).reshape(
        B, Hkv, n_kv, kv_chunk, dv).transpose(2, 0, 1, 3, 4)
    f = _make_flash(bool(causal), window, q_chunk, kv_chunk)
    out = f(q5, kh, vh)
    # (n_q, B, Hkv, G, C, dv) -> (B, Sq, H, dv)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, dv).astype(q.dtype)


def attention(params, x, cfg: ArchConfig, *, spec: LayerSpec, positions,
              cache=None, cache_pos=None, kv_override=None, cp_axis=None,
              prefill=False):
    """Self-attention.  cache: {"k","v"} (B,Smax,Hkv,hd) updated in place at
    cache_pos (decode) or filled at [0, S) (prefill).  kv_override:
    (k_in, v_in) for cross-attention.  cp_axis: manual mesh axis over which
    the KV cache's sequence dim is sharded (context-parallel decode)."""
    B, S, D = x.shape
    hd = cfg.hd
    q = project(params, x, "q").reshape(B, S, cfg.n_heads, hd)
    if kv_override is None:
        k = project(params, x, "k").reshape(B, S, cfg.kv_heads, hd)
        v = project(params, x, "v").reshape(B, S, cfg.kv_heads, hd)
        cos, sin = rope_table(positions, cfg.rope_dims or hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin, cfg.rope_interleaved)
        k = apply_rope(k, cos, sin, cfg.rope_interleaved)
    else:
        k, v = kv_override
        causal = False

    new_cache = None
    if cache is not None and prefill and kv_override is None:
        # prefill: write fresh K/V into the cache head, attend causally
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, 1)
        new_cache = {"k": ck, "v": cv}
        out = _attend_chunked(q, k, v, causal=True, window=spec.window)
        return out.reshape(B, S, -1) @ params["wo"], new_cache
    if cache is not None and kv_override is None:
        # decode: splice new kv into the cache at cache_pos
        ck, cv = cache["k"], cache["v"]
        if cp_axis is None:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, 1)
        else:
            # context-parallel: the owner shard of position cache_pos writes
            shard = jax.lax.axis_index(cp_axis)
            s_loc = ck.shape[1]
            local_pos = cache_pos - shard * s_loc
            write = (local_pos >= 0) & (local_pos < s_loc)
            upd_k = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), jnp.clip(local_pos, 0, s_loc - 1), 1)
            upd_v = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), jnp.clip(local_pos, 0, s_loc - 1), 1)
            ck = jnp.where(write, upd_k, ck)
            cv = jnp.where(write, upd_v, cv)
        new_cache = {"k": ck, "v": cv}
        out = _decode_attend(q, ck, cv, cache_pos, spec.window, cp_axis)
        return out.reshape(B, S, -1) @ params["wo"], new_cache

    causal = kv_override is None
    out = _attend_chunked(q, k, v, causal=causal, window=spec.window)
    return out.reshape(B, S, -1) @ params["wo"], new_cache


def _decode_attend(q, ck, cv, cache_pos, window, cp_axis, scale=None):
    """Single-token decode attention over the cache (q (B,1,H,hd)).

    With cp_axis set, ck/cv hold only this shard's sequence slice; partial
    attention is combined across shards with a logsumexp reduction (the
    sequence-parallel decode path for long_500k).  ``scale`` replaces the
    softmax scale ``1/sqrt(hd)``."""
    B, _, H, hd = q.shape
    Hkv = ck.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    s_loc = ck.shape[1]
    if cp_axis is None:
        kpos = jnp.arange(s_loc)
        valid = kpos <= cache_pos
    else:
        shard = jax.lax.axis_index(cp_axis)
        kpos = shard * s_loc + jnp.arange(s_loc)
        valid = kpos <= cache_pos
    if window is not None:
        valid &= (cache_pos - kpos) < window
    qh = q.reshape(B, Hkv, G, hd)
    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qh.astype(jnp.float32),
        ck.astype(jnp.float32)
    ) * scale
    scores = jnp.where(valid[None, None, None], scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)
    if cp_axis is not None:
        m = jax.lax.pmax(m, cp_axis)
    e = jnp.exp(scores - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhgs,bshd->bhgd", e, cv.astype(jnp.float32))
    if cp_axis is not None:
        l = jax.lax.psum(l, cp_axis)
        o = jax.lax.psum(o, cp_axis)
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(B, 1, H, cv.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig, dtype):
    d, hd, m = cfg.d_model, cfg.hd, cfg.mla
    ks = jax.random.split(key, 6)
    p = {
        "w_dkv": _dense_init(ks[0], (d, m.kv_lora), dtype),
        "w_krope": _dense_init(ks[1], (d, m.rope_dim), dtype),
        "w_uk": _dense_init(ks[2], (m.kv_lora, cfg.n_heads * hd), dtype),
        "w_uv": _dense_init(ks[3], (m.kv_lora, cfg.n_heads * hd), dtype),
        "wq": _dense_init(ks[4], (d, cfg.n_heads * (hd + m.rope_dim)), dtype),
        "wo": _dense_init(ks[5], (cfg.n_heads * hd, d), dtype),
    }
    if m.kv_norm:
        p["kv_norm"] = jnp.ones((m.kv_lora,), dtype)
    return p


def spec_mla(cfg: ArchConfig):
    s = {
        "w_dkv": P(None, None), "w_krope": P(None, None),
        "w_uk": P(None, "model"), "w_uv": P(None, "model"),
        "wq": P(None, "model"), "wo": P("model", None),
    }
    if cfg.mla.kv_norm:
        s["kv_norm"] = P(None)
    return s


def _mla_rope(positions, cfg: ArchConfig):
    """cos/sin of the rope dims, YaRN's where ``cfg.mla`` asks for it, and
    the softmax scale (None: ``1/sqrt(hd + rope_dim)``), which YaRN
    multiplies by ``mscale**2`` of ``mscale_all_dim``."""
    m = cfg.mla
    if m.yarn_factor == 1.0:
        cos, sin = rope_table(positions, m.rope_dim, cfg.rope_theta)
        return cos, sin, None
    cos, sin = rope_table(positions, m.rope_dim, cfg.rope_theta,
                          inv=yarn_inv_freq(m.rope_dim, cfg.rope_theta, m))
    r = (yarn_mscale(m.yarn_factor, m.yarn_mscale)
         / yarn_mscale(m.yarn_factor, m.yarn_mscale_all_dim))
    scale = (yarn_mscale(m.yarn_factor, m.yarn_mscale_all_dim) ** 2
             / np.sqrt(cfg.hd + m.rope_dim))
    return cos * r, sin * r, scale


def mla_attention(params, x, cfg: ArchConfig, *, spec: LayerSpec, positions,
                  cache=None, cache_pos=None, cp_axis=None, prefill=False):
    """Latent attention: the cache stores (c_kv, k_rope) — the MLA memory
    saving, the latent after its RMSNorm where ``cfg.mla.kv_norm`` — and
    per-head K/V are reconstructed from the latent."""
    B, S, D = x.shape
    hd, m = cfg.hd, cfg.mla
    H = cfg.n_heads
    c_kv = x @ params["w_dkv"]  # (B,S,r)
    if m.kv_norm:
        c_kv = rms_norm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = x @ params["w_krope"]  # (B,S,rope)
    cos, sin, scale = _mla_rope(positions, cfg)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin,
                        cfg.rope_interleaved)[:, :, 0]
    q = (x @ params["wq"]).reshape(B, S, H, hd + m.rope_dim)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, cos, sin, cfg.rope_interleaved)

    new_cache = None
    if cache is not None and prefill:
        # prefill: store the fresh latents at the cache head; attend locally
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, 1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, 1)
        new_cache = {"c_kv": ck, "k_rope": cr}
        Sk = S
    elif cache is not None:
        ck, cr = cache["c_kv"], cache["k_rope"]
        ck = jax.lax.dynamic_update_slice_in_dim(ck, c_kv.astype(ck.dtype), cache_pos, 1)
        cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope.astype(cr.dtype), cache_pos, 1)
        new_cache = {"c_kv": ck, "k_rope": cr}
        c_kv, k_rope = ck, cr
        Sk = c_kv.shape[1]
    else:
        Sk = S

    # Reduce to standard attention on augmented vectors:
    #   score = q_nope . k_nope + q_rope . k_rope  ==  [q_nope|q_rope].[k_nope|k_rope]
    # (the CACHE stays latent — per-head K/V are reconstructed transiently).
    k_nope = (c_kv @ params["w_uk"]).reshape(B, Sk, H, hd)
    v = (c_kv @ params["w_uv"]).reshape(B, Sk, H, hd)
    k_aug = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, Sk, H, m.rope_dim))],
        axis=-1,
    )
    q_aug = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cache is not None and not prefill:
        out = _decode_attend(q_aug, k_aug, v, cache_pos, None, cp_axis,
                             scale)
    else:
        out = _attend_chunked(q_aug, k_aug, v, causal=True, window=spec.window,
                              scale=scale)
    return out.reshape(B, S, -1) @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# FFN: SwiGLU and MoE
# ---------------------------------------------------------------------------

def init_swiglu(key, d, f, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w1": _dense_init(ks[0], (d, f), dtype),
        "w3": _dense_init(ks[1], (d, f), dtype),
        "w2": _dense_init(ks[2], (f, d), dtype),
    }


def spec_swiglu():
    return {"w1": P(None, "model"), "w3": P(None, "model"), "w2": P("model", None)}


def swiglu(params, x):
    return (jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])) @ params["w2"]


def init_moe(key, cfg: ArchConfig, dtype):
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, m.n_experts), dtype, scale=0.02),
        "we1": _dense_init(ks[1], (m.held, d, m.d_expert), dtype),
        "we3": _dense_init(ks[2], (m.held, d, m.d_expert), dtype),
        "we2": _dense_init(ks[3], (m.held, m.d_expert, d), dtype),
    }
    if m.n_shared:
        p["shared"] = init_swiglu(ks[4], d, m.n_shared * m.d_expert, dtype)
    return p


def spec_moe(cfg: ArchConfig):
    s = {
        "router": P(None, None),
        "we1": P("model", None, None),  # EP: experts over the model axis
        "we3": P("model", None, None),
        "we2": P("model", None, None),
    }
    if cfg.moe.n_shared:
        s["shared"] = spec_swiglu()
    return s


def _expert_sharding_hint(x, n_experts: int):
    """Keep expert-major buffers sharded over 'model' (EP) through the MoE
    dispatch: without the hint GSPMD materializes the (E, C, D) dispatch
    and expert activations REPLICATED on every device (measured: ~30x the
    minimal all-to-all traffic and GBs of temp on deepseek-v3)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            return x
        if n_experts % mesh.shape["model"] != 0:
            return x
        spec = P(*(("model",) + (None,) * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def moe(params, x, cfg: ArchConfig, *, capacity_factor: float = 1.25,
        dropless_below: int = 512):
    """Capacity-based top-k MoE with sort-free static dispatch.

    Tokens are routed to their top-k experts; each expert processes at most
    C tokens (overflow dropped — weighted by gates so the residual path
    covers dropped tokens).  Dispatch/return are gathers, which GSPMD turns
    into all_to_alls over the EP (model) axis when experts are sharded.

    Decode regime (T <= dropless_below): capacity is set to T, which is
    provably dropless (an expert can receive at most one slot per token), so
    single-token decode agrees exactly with prefill.

    Expert share (``MoECfg.n_held``): tokens are routed over all
    ``n_experts``, and the layer computes the part of the result that its
    held experts give; the shared experts are added whole."""
    B, S, D = x.shape
    m = cfg.moe
    E = m.held
    T = B * S
    xt = x.reshape(T, D)
    logits = (xt @ params["router"]).astype(jnp.float32)
    gates, eids = jax.lax.top_k(jax.nn.softmax(logits, -1), m.top_k)  # (T,k)
    if m.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if E < m.n_experts:
        # assignments to experts held elsewhere go to row E, which the
        # slot table drops
        eids = eids - m.first_held
        eids = jnp.where((eids >= 0) & (eids < E), eids, E)

    if T <= dropless_below:
        C = T
    else:
        C = max(1, int(T * m.top_k / m.n_experts * capacity_factor))
    flat_e = eids.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    within = jnp.arange(T * m.top_k) - grp_start[jnp.minimum(sorted_e, E - 1)]
    keep = within < C
    # slot table: (E, C) -> index into the flat (token, k) assignment list
    slot = jnp.full((E, C), T * m.top_k, jnp.int32)
    slot = slot.at[sorted_e, jnp.clip(within, 0, C - 1)].set(
        jnp.where(keep, order, T * m.top_k).astype(jnp.int32), mode="drop"
    )
    tok_of_slot = jnp.where(slot < T * m.top_k, slot // m.top_k, T)  # sentinel T
    tok_of_slot = _expert_sharding_hint(tok_of_slot, E)
    xg = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])[tok_of_slot]  # (E,C,D)
    xg = _expert_sharding_hint(xg, E)
    h = jnp.einsum("ecd,edf->ecf", jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, params["we1"])) *
                   jnp.einsum("ecd,edf->ecf", xg, params["we3"]), params["we2"])
    h = _expert_sharding_hint(h, E)
    # combine: scatter expert outputs back, weighted by gates
    gate_of_slot = jnp.where(
        slot < T * m.top_k,
        jnp.concatenate([gates.reshape(-1), jnp.zeros((1,), gates.dtype)])[
            jnp.minimum(slot, T * m.top_k)
        ],
        0.0,
    )
    out = jnp.zeros((T + 1, D), jnp.float32)
    out = out.at[tok_of_slot.reshape(-1)].add(
        (h * gate_of_slot[..., None]).reshape(-1, D).astype(jnp.float32), mode="drop"
    )
    y = out[:T].astype(x.dtype)
    if m.n_shared:
        y = y + swiglu(params["shared"], xt)
    return y.reshape(B, S, D)


# ---------------------------------------------------------------------------
# Mamba SSM (jamba)
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    mc = cfg.mamba
    di = mc.expand * d
    ks = jax.random.split(key, 6)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * di), dtype),
        "conv_w": _dense_init(ks[1], (mc.d_conv, di), dtype, scale=0.5),
        "w_bc_dt": _dense_init(ks[2], (di, 2 * mc.d_state + 1), dtype),
        "a_log": (jax.random.uniform(ks[3], (di, mc.d_state)) * 2 + 0.5).astype(
            jnp.float32
        ),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(ks[4], (di, d), dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
    }


def spec_mamba(cfg: ArchConfig):
    return {
        "in_proj": P(None, "model"), "conv_w": P(None, "model"),
        "w_bc_dt": P("model", None), "a_log": P("model", None),
        "d_skip": P("model"), "out_proj": P("model", None),
        "dt_bias": P("model"),
    }


def mamba(params, x, cfg: ArchConfig, *, state=None, chunk: int = 256,
          return_state: bool = False):
    """Selective SSM; chunked associative scan for train/prefill, single-step
    recurrence for decode (state: {"h": (B,di,ds), "conv": (B,k-1,di)}).
    ``return_state`` makes the parallel path also emit the final recurrent
    state (prefill -> decode handoff)."""
    B, S, D = x.shape
    mc = cfg.mamba
    di = mc.expand * D
    ds = mc.d_state
    xz = x @ params["in_proj"]
    xs, z = xz[..., :di], xz[..., di:]

    k = mc.d_conv
    if state is None:
        # causal depthwise conv via shifted adds
        acc = jnp.zeros_like(xs)
        for i in range(k):
            shifted = jnp.pad(xs, ((0, 0), (i, 0), (0, 0)))[:, :S]
            acc = acc + shifted * params["conv_w"][k - 1 - i]
        xc = jax.nn.silu(acc)
    else:
        hist = jnp.concatenate([state["conv"], xs], axis=1)  # (B, k-1+S, di)
        acc = jnp.zeros_like(xs)
        for i in range(k):
            acc = acc + hist[:, k - 1 - i : k - 1 - i + S] * params["conv_w"][k - 1 - i]
        xc = jax.nn.silu(acc)
        new_conv = hist[:, -(k - 1):]

    bcd = xc @ params["w_bc_dt"]
    Bm, Cm, dt = bcd[..., :ds], bcd[..., ds : 2 * ds], bcd[..., -1:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,di)
    A = -jnp.exp(params["a_log"])  # (di, ds)
    da = jnp.exp(dt[..., None] * A)  # (B,S,di,ds)
    db = (dt[..., None] * Bm[:, :, None, :]).astype(jnp.float32) * xc.astype(
        jnp.float32
    )[..., None]

    if state is not None:  # decode: S == 1
        h = state["h"] * da[:, 0] + db[:, 0]
        y = jnp.einsum("bds,bs->bd", h, Cm[:, 0].astype(jnp.float32))[:, None]
        y = (y + xc.astype(jnp.float32) * params["d_skip"]) * jax.nn.silu(
            z.astype(jnp.float32)
        )
        out = y.astype(x.dtype) @ params["out_proj"]
        return out, {"h": h, "conv": new_conv}

    n_ch = max(1, S // chunk)
    assert S % n_ch == 0
    ch = S // n_ch

    # associative scan within each chunk; carry h across chunks
    def scan_body(h0, args):
        da_c, db_c, C_c = args
        def assoc(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, b1 * a2 + b2
        a_all, b_all = jax.lax.associative_scan(assoc, (da_c, db_c), axis=0)
        h = h0[None] * a_all + b_all  # (ch,B,di,ds) -- scanning time-major
        y = jnp.einsum("sbdn,sbn->sbd", h, C_c)
        return h[-1], y

    da_t = da.transpose(1, 0, 2, 3).reshape(n_ch, ch, B, di, ds)
    db_t = db.transpose(1, 0, 2, 3).reshape(n_ch, ch, B, di, ds)
    C_t = Cm.astype(jnp.float32).transpose(1, 0, 2).reshape(n_ch, ch, B, ds)
    h0 = jnp.zeros((B, di, ds), jnp.float32)
    h_last, ys = jax.lax.scan(scan_body, h0, (da_t, db_t, C_t))
    y = ys.reshape(S, B, di).transpose(1, 0, 2)
    y = (y + xc.astype(jnp.float32) * params["d_skip"]) * jax.nn.silu(
        z.astype(jnp.float32)
    )
    out = y.astype(x.dtype) @ params["out_proj"]
    if return_state:
        # conv history for decode: the last (k-1) pre-activation inputs
        tail = xs[:, S - (k - 1):] if k > 1 else jnp.zeros((B, 0, di), xs.dtype)
        return out, {"h": h_last, "conv": tail}
    return out, None


# ---------------------------------------------------------------------------
# xLSTM blocks (mLSTM: matrix memory; sLSTM: scalar memory) — sequential
# scan form; production would use chunkwise-parallel kernels (DESIGN.md §7).
# ---------------------------------------------------------------------------

def init_xlstm(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, cfg.n_heads * hd), dtype),
        "wk": _dense_init(ks[1], (d, cfg.kv_heads * hd), dtype),
        "wv": _dense_init(ks[2], (d, cfg.kv_heads * hd), dtype),
        "wi": _dense_init(ks[3], (d, cfg.n_heads), dtype, scale=0.02),
        "wf": _dense_init(ks[4], (d, cfg.n_heads), dtype, scale=0.02),
        "wo": _dense_init(ks[5], (cfg.n_heads * hd, d), dtype),
    }


spec_xlstm = spec_attention  # same projection shapes; gates replicated


def spec_xlstm_full(cfg):
    s = dict(spec_attention(cfg))
    s["wi"] = P(None, "model")
    s["wf"] = P(None, "model")
    return s


def mlstm(params, x, cfg: ArchConfig, *, state=None):
    """mLSTM: per-head matrix memory C (hd x hd) with exp input gate and
    sigmoid forget gate (stabilized).  state: {"C","n","m"}."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q = (x @ params["wq"]).reshape(B, S, H, hd).astype(jnp.float32)
    k = (x @ params["wk"]).reshape(B, S, cfg.kv_heads, hd).astype(jnp.float32)
    v = (x @ params["wv"]).reshape(B, S, cfg.kv_heads, hd).astype(jnp.float32)
    G = H // cfg.kv_heads
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    logi = (x @ params["wi"]).astype(jnp.float32)  # (B,S,H)
    logf = jax.nn.log_sigmoid((x @ params["wf"]).astype(jnp.float32))
    k = k / np.sqrt(hd)

    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state["C"], state["n"], state["m"]

    def step(carry, t):
        C, n, m = carry
        m_new = jnp.maximum(logf[:, t] + m, logi[:, t])
        i_g = jnp.exp(logi[:, t] - m_new)[..., None, None]
        f_g = jnp.exp(logf[:, t] + m - m_new)[..., None, None]
        C = f_g * C + i_g * jnp.einsum("bhd,bhe->bhde", k[:, t], v[:, t])
        n = f_g[..., 0] * n + i_g[..., 0] * k[:, t]
        num = jnp.einsum("bhd,bhde->bhe", q[:, t], C)
        den = jnp.abs(jnp.einsum("bhd,bhd->bh", q[:, t], n))[..., None]
        y = num / jnp.maximum(den, 1.0)
        return (C, n, m_new), y

    (C, n, m), ys = jax.lax.scan(step, (C0, n0, m0), jnp.arange(S))
    y = ys.transpose(1, 0, 2, 3).reshape(B, S, H * hd).astype(x.dtype)
    return y @ params["wo"], {"C": C, "n": n, "m": m}


def slstm(params, x, cfg: ArchConfig, *, state=None):
    """sLSTM: per-head scalar-memory cell with exponential gating and a
    normalizer state.  state: {"c","n","m"}."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    v = (x @ params["wv"]).reshape(B, S, cfg.kv_heads, hd).astype(jnp.float32)
    v = jnp.repeat(v, H // cfg.kv_heads, axis=2)
    o = jax.nn.sigmoid((x @ params["wq"]).reshape(B, S, H, hd).astype(jnp.float32))
    logi = (x @ params["wi"]).astype(jnp.float32)
    logf = jax.nn.log_sigmoid((x @ params["wf"]).astype(jnp.float32))

    if state is None:
        c0 = jnp.zeros((B, H, hd), jnp.float32)
        n0 = jnp.zeros((B, H), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        c0, n0, m0 = state["c"], state["n"], state["m"]

    def step(carry, t):
        c, n, m = carry
        m_new = jnp.maximum(logf[:, t] + m, logi[:, t])
        i_g = jnp.exp(logi[:, t] - m_new)
        f_g = jnp.exp(logf[:, t] + m - m_new)
        c = f_g[..., None] * c + i_g[..., None] * v[:, t]
        n = f_g * n + i_g
        y = o[:, t] * c / jnp.maximum(n, 1.0)[..., None]
        return (c, n, m_new), y

    (c, n, m), ys = jax.lax.scan(step, (c0, n0, m0), jnp.arange(S))
    y = ys.transpose(1, 0, 2, 3).reshape(B, S, H * hd).astype(x.dtype)
    return y @ params["wo"], {"c": c, "n": n, "m": m}
