"""Generic model stack interpreting ``ArchConfig``.

One code path serves all ten assigned architectures:
  * homogeneous or patterned layers (super-block scan keeps HLO compact),
  * mixers: GQA attention (global / sliding-window), MLA, Mamba, mLSTM,
    sLSTM; FFN: SwiGLU / MoE / none,
  * decoder-only or encoder-decoder (whisper) with stubbed modality
    frontends (precomputed frame/patch embeddings enter via the batch),
  * training forward (remat-wrapped blocks) and cached decode.

Params are nested dicts; ``specs()`` returns the matching PartitionSpec
tree (TP/EP over the ``model`` mesh axis; the ``data``/``pod`` axes are
manual shard_map axes owned by the training loop).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.config import ArchConfig, LayerSpec


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# per-layer init / specs
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, spec: LayerSpec, cross: bool):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if spec.mixer == "attn":
        p["mixer"] = L.init_attention(ks[0], cfg, dt)
    elif spec.mixer == "mla":
        p["mixer"] = L.init_mla(ks[0], cfg, dt)
    elif spec.mixer == "mamba":
        p["mixer"] = L.init_mamba(ks[0], cfg, dt)
    elif spec.mixer in ("mlstm", "slstm"):
        p["mixer"] = L.init_xlstm(ks[0], cfg, dt)
    else:
        raise ValueError(spec.mixer)
    if cross:
        p["normx"] = jnp.ones((cfg.d_model,), dt)
        p["cross"] = L.init_attention(ks[1], cfg, dt)
    if spec.ffn != "none":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = (
            L.init_moe(ks[2], cfg, dt)
            if spec.ffn == "moe"
            else L.init_swiglu(ks[2], cfg.d_model, cfg.d_ff, dt)
        )
    return p


def _spec_layer(cfg: ArchConfig, spec: LayerSpec, cross: bool):
    s = {"norm1": P(None)}
    if spec.mixer == "attn":
        s["mixer"] = L.spec_attention(cfg)
    elif spec.mixer == "mla":
        s["mixer"] = L.spec_mla(cfg)
    elif spec.mixer == "mamba":
        s["mixer"] = L.spec_mamba(cfg)
    elif spec.mixer in ("mlstm", "slstm"):
        s["mixer"] = L.spec_xlstm_full(cfg)
    if cross:
        s["normx"] = P(None)
        s["cross"] = L.spec_attention(cfg)
    if spec.ffn != "none":
        s["norm2"] = P(None)
        s["ffn"] = L.spec_moe(cfg) if spec.ffn == "moe" else L.spec_swiglu()
    return s


def _apply_layer(p, h, cfg: ArchConfig, spec: LayerSpec, *, positions,
                 cache=None, cache_pos=None, enc_out=None, cp_axis=None,
                 prefill=False):
    mix_in = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    new_cache = cache
    if spec.mixer == "attn":
        out, kv = L.attention(
            p["mixer"], mix_in, cfg, spec=spec, positions=positions,
            cache=None if cache is None else cache.get("kv"),
            cache_pos=cache_pos, cp_axis=cp_axis, prefill=prefill,
        )
        if kv is not None:
            new_cache = dict(cache, kv=kv)
    elif spec.mixer == "mla":
        out, kv = L.mla_attention(
            p["mixer"], mix_in, cfg, spec=spec, positions=positions,
            cache=None if cache is None else cache.get("kv"),
            cache_pos=cache_pos, cp_axis=cp_axis, prefill=prefill,
        )
        if kv is not None:
            new_cache = dict(cache, kv=kv)
    elif spec.mixer == "mamba":
        out, st = L.mamba(
            p["mixer"], mix_in, cfg,
            state=None if (cache is None or prefill) else cache.get("ssm"),
            return_state=prefill and cache is not None,
        )
        if st is not None:
            new_cache = dict(cache, ssm=st)
    elif spec.mixer == "mlstm":
        out, st = L.mlstm(p["mixer"], mix_in, cfg,
                          state=None if (cache is None or prefill)
                          else cache.get("rnn"))
        if cache is not None:
            new_cache = dict(cache, rnn=st)
    elif spec.mixer == "slstm":
        out, st = L.slstm(p["mixer"], mix_in, cfg,
                          state=None if (cache is None or prefill)
                          else cache.get("rnn"))
        if cache is not None:
            new_cache = dict(cache, rnn=st)
    else:
        raise ValueError(spec.mixer)
    h = h + out
    if enc_out is not None and "cross" in p:
        xin = L.rms_norm(h, p["normx"], cfg.norm_eps)
        out, _ = L.attention(
            p["cross"], xin, cfg, spec=spec, positions=positions,
            kv_override=enc_out,
        )
        h = h + out
    if spec.ffn != "none":
        f_in = L.rms_norm(h, p["norm2"], cfg.norm_eps)
        if spec.ffn == "moe":
            h = h + L.moe(p["ffn"], f_in, cfg)
        else:
            h = h + L.swiglu(p["ffn"], f_in)
    return h, new_cache


def _init_cache_layer(cfg: ArchConfig, spec: LayerSpec, batch: int,
                      max_len: int, cp_shards: int = 1):
    dt = _dtype(cfg)
    s_loc = max_len // cp_shards
    if spec.mixer == "attn":
        return {"kv": {
            "k": jnp.zeros((batch, s_loc, cfg.kv_heads, cfg.hd), dt),
            "v": jnp.zeros((batch, s_loc, cfg.kv_heads, cfg.hd), dt),
        }}
    if spec.mixer == "mla":
        return {"kv": {
            "c_kv": jnp.zeros((batch, s_loc, cfg.mla.kv_lora), dt),
            "k_rope": jnp.zeros((batch, s_loc, cfg.mla.rope_dim), dt),
        }}
    if spec.mixer == "mamba":
        di = cfg.mamba.expand * cfg.d_model
        return {"ssm": {
            "h": jnp.zeros((batch, di, cfg.mamba.d_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.mamba.d_conv - 1, di), dt),
        }}
    if spec.mixer == "mlstm":
        return {"rnn": {
            "C": jnp.zeros((batch, cfg.n_heads, cfg.hd, cfg.hd), jnp.float32),
            "n": jnp.zeros((batch, cfg.n_heads, cfg.hd), jnp.float32),
            "m": jnp.full((batch, cfg.n_heads), -1e30, jnp.float32),
        }}
    if spec.mixer == "slstm":
        return {"rnn": {
            "c": jnp.zeros((batch, cfg.n_heads, cfg.hd), jnp.float32),
            "n": jnp.zeros((batch, cfg.n_heads), jnp.float32),
            "m": jnp.full((batch, cfg.n_heads), -1e30, jnp.float32),
        }}
    raise ValueError(spec.mixer)


# ---------------------------------------------------------------------------
# whole-model API
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(ks[1], (cfg.vocab, cfg.d_model)) * 0.02
        ).astype(dt)
    cross = cfg.enc_dec
    for i, spec in enumerate(cfg.prefix):
        params[f"prefix_{i}"] = _init_layer(jax.random.fold_in(ks[2], i), cfg, spec, cross)
    blocks = []
    for pi, spec in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(ks[3], pi), cfg.repeats)
        blocks.append(jax.vmap(lambda k: _init_layer(k, cfg, spec, cross))(keys))
    params["blocks"] = tuple(blocks)
    if cfg.enc_dec:
        enc_spec = LayerSpec(mixer="attn", ffn="swiglu")
        keys = jax.random.split(ks[4], cfg.n_enc_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_layer(k, cfg, enc_spec, cross=False)
        )(keys)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
        params["enc_pos"] = (
            jax.random.normal(ks[5], (cfg.enc_seq, cfg.d_model)) * 0.02
        ).astype(dt)
    return params


def specs(cfg: ArchConfig):
    s = {"embed": P("model", None), "final_norm": P(None)}
    if not cfg.tie_embeddings:
        s["lm_head"] = P("model", None)
    cross = cfg.enc_dec
    for i, spec in enumerate(cfg.prefix):
        s[f"prefix_{i}"] = _spec_layer(cfg, spec, cross)
    blocks = []
    for spec in cfg.pattern:
        ls = _spec_layer(cfg, spec, cross)
        blocks.append(jax.tree.map(
            lambda sp: P(*((None,) + tuple(sp))), ls,
            is_leaf=lambda x: isinstance(x, P)))
    s["blocks"] = tuple(blocks)
    if cfg.enc_dec:
        ls = _spec_layer(cfg, LayerSpec(), cross=False)
        s["enc_blocks"] = jax.tree.map(
            lambda sp: P(*((None,) + tuple(sp))), ls,
            is_leaf=lambda x: isinstance(x, P))
        s["enc_norm"] = P(None)
        s["enc_pos"] = P(None, None)
    return s


def _run_encoder(params, frames, cfg: ArchConfig):
    """Whisper-style encoder over stubbed frame embeddings (B, T, D)."""
    h = frames + params["enc_pos"][None, : frames.shape[1]]
    pos = jnp.arange(frames.shape[1])
    enc_spec = LayerSpec(mixer="attn", ffn="swiglu")

    def body(h, p):
        # bidirectional: kv_override with own kv (no causal mask)
        mix_in = L.rms_norm(h, p["norm1"], cfg.norm_eps)
        B, S, _ = mix_in.shape
        k = L.project(p["mixer"], mix_in, "k").reshape(B, S, cfg.kv_heads, cfg.hd)
        v = L.project(p["mixer"], mix_in, "v").reshape(B, S, cfg.kv_heads, cfg.hd)
        out, _ = L.attention(
            p["mixer"], mix_in, cfg, spec=enc_spec, positions=pos,
            kv_override=(k, v),
        )
        h = h + out
        f_in = L.rms_norm(h, p["norm2"], cfg.norm_eps)
        return h + L.swiglu(p["ffn"], f_in), None

    h, _ = jax.lax.scan(lambda c, p: body(c, p), h, params["enc_blocks"])
    return L.rms_norm(h, params["enc_norm"], cfg.norm_eps)


def forward(params, batch: dict, cfg: ArchConfig, *, remat: bool = True,
            cp_axis=None, block_param_fn=None):
    """Training/prefill forward.  batch: {"tokens": (B,S) int32,
    optional "frames": (B,T,D) (enc-dec stub), optional "vision_embeds":
    (B,Sv,D) (VLM stub)}.  Returns hidden states (B,S,D) pre-head.

    ``block_param_fn(layer_params, pattern_index)`` is the FSDP hook: it is
    applied to each layer's params *inside* the scan body (and to prefix
    layers), so compressed param all-gathers happen per-block and their
    transposed reduce-scatters produce sharded gradients (optim/fsdp.py)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = params["embed"][tokens]
    if "vision_embeds" in batch:  # VLM stub: patches replace leading positions
        ve = batch["vision_embeds"].astype(h.dtype)
        h = jnp.concatenate([ve, h[:, ve.shape[1] :]], axis=1)
    positions = jnp.arange(S)
    enc_out = None
    if cfg.enc_dec:
        enc_out = _run_encoder(params, batch["frames"].astype(h.dtype), cfg)
        # per-layer cross-attention K/V are computed inside each block
    bpf = block_param_fn or (lambda p, i: p)

    def apply(p, h, spec_i, eo):
        spec = cfg.pattern[spec_i] if spec_i >= 0 else cfg.prefix[-spec_i - 1]
        p = bpf(p, spec_i)
        if eo is not None:
            B_, T_, _ = eo.shape
            k = L.project(p["cross"], eo, "k").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            v = L.project(p["cross"], eo, "v").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            eo = (k, v)
        h, _ = _apply_layer(p, h, cfg, spec, positions=positions,
                            enc_out=eo, cp_axis=cp_axis)
        return h

    apply_r = jax.checkpoint(apply, static_argnums=(2,)) if remat else apply

    for i, spec in enumerate(cfg.prefix):
        h = apply_r(params[f"prefix_{i}"], h, -i - 1, enc_out)
    # interleaved pattern: scan over repeats applying the whole super-block
    if cfg.pattern:
        def super_block(carry, ps):
            hh = carry
            for pi in range(len(cfg.pattern)):
                hh = apply_r(ps[pi], hh, pi, enc_out)
            return hh, None
        h, _ = jax.lax.scan(super_block, h, params["blocks"])
    return L.rms_norm(h, params["final_norm"], cfg.norm_eps)


def prefill(params, batch: dict, cfg: ArchConfig, cache, *, cp_axis=None):
    """Prefill forward: runs the causal forward pass AND fills the caches at
    positions [0, S).  Returns (last-position logits (B,1,V), cache).

    The serving engine uses this on the prefill workers; the returned cache
    is what PD-disaggregation ships to the decode workers (paper §5.3.2)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = params["embed"][tokens]
    if "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(h.dtype)
        h = jnp.concatenate([ve, h[:, ve.shape[1] :]], axis=1)
    positions = jnp.arange(S)
    enc_out = None
    if cfg.enc_dec:
        enc_out = _run_encoder(params, batch["frames"].astype(h.dtype), cfg)
    new_cache = {"pos": jnp.asarray(S, jnp.int32)}

    def apply(p, h, spec, c, eo):
        if eo is not None and "cross" in p:
            B_, T_, _ = eo.shape
            k = L.project(p["cross"], eo, "k").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            v = L.project(p["cross"], eo, "v").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            eo = (k, v)
        return _apply_layer(p, h, cfg, spec, positions=positions, cache=c,
                            cache_pos=None, enc_out=eo, cp_axis=cp_axis,
                            prefill=True)

    for i, spec in enumerate(cfg.prefix):
        h, c = apply(params[f"prefix_{i}"], h, spec, cache[f"prefix_{i}"],
                     enc_out)
        new_cache[f"prefix_{i}"] = c
    if cfg.pattern:
        def super_block(carry, xs):
            hh = carry
            ps, cs = xs
            new_cs = []
            for pi, spec in enumerate(cfg.pattern):
                hh, nc = apply(ps[pi], hh, spec, cs[pi], enc_out)
                new_cs.append(nc)
            return hh, tuple(new_cs)
        h, nc = jax.lax.scan(super_block, h, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = nc
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = logits_from_hidden(params, h[:, -1:], cfg)
    return logits, new_cache


def logits_from_hidden(params, h, cfg: ArchConfig):
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return h @ head.T


def init_cache(cfg: ArchConfig, batch: int, max_len: int, cp_shards: int = 1):
    caches = {"pos": jnp.zeros((), jnp.int32)}
    for i, spec in enumerate(cfg.prefix):
        caches[f"prefix_{i}"] = _init_cache_layer(cfg, spec, batch, max_len, cp_shards)
    blocks = []
    for spec in cfg.pattern:
        one = _init_cache_layer(cfg, spec, batch, max_len, cp_shards)
        blocks.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.repeats,) + x.shape), one))
    caches["blocks"] = tuple(blocks)
    return caches


def decode_step(params, tokens, cache, cfg: ArchConfig, *, enc_out=None,
                cp_axis=None):
    """One decode step: tokens (B,1) -> (logits (B,1,V), new cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    h = params["embed"][tokens]
    positions = jnp.full((B, 1), pos, jnp.int32)
    new_cache = {"pos": pos + 1}

    def apply(p, h, spec, c, eo):
        if eo is not None and "cross" in p:
            B_, T_, _ = eo.shape
            k = L.project(p["cross"], eo, "k").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            v = L.project(p["cross"], eo, "v").reshape(B_, T_, cfg.kv_heads, cfg.hd)
            eo = (k, v)
        return _apply_layer(p, h, cfg, spec, positions=positions, cache=c,
                            cache_pos=pos, enc_out=eo, cp_axis=cp_axis)

    for i, spec in enumerate(cfg.prefix):
        h, c = apply(params[f"prefix_{i}"], h, spec, cache[f"prefix_{i}"], enc_out)
        new_cache[f"prefix_{i}"] = c
    if cfg.pattern:
        def super_block(carry, xs):
            hh = carry
            ps, cs = xs
            new_cs = []
            for pi, spec in enumerate(cfg.pattern):
                hh, nc = apply(ps[pi], hh, spec, cs[pi], enc_out)
                new_cs.append(nc)
            return hh, tuple(new_cs)
        h, nc = jax.lax.scan(super_block, h, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = nc
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    return logits_from_hidden(params, h, cfg), new_cache


def abstract_params(cfg: ArchConfig):
    """ShapeDtypeStruct pytree of params — used by the dry-run (no alloc)."""
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
