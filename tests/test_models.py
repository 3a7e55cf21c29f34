"""Per-architecture smoke tests: reduced same-family configs, one forward /
train-loss / prefill / decode step on CPU, asserting shapes and finiteness.
The FULL configs are exercised only via the dry-run (ShapeDtypeStruct)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import registry, transformer

ARCHS = configs.ARCHS


@pytest.fixture(scope="module")
def setup():
    out = {}
    for name in ARCHS:
        cfg = configs.get_smoke(name)
        params = transformer.init(jax.random.PRNGKey(0), cfg)
        out[name] = (cfg, params)
    return out


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes_and_finite(setup, name):
    cfg, params = setup[name]
    batch = registry.make_batch(cfg, 2, 32)
    h = transformer.forward(params, batch, cfg, remat=False)
    assert h.shape == (2, 32, cfg.d_model)
    logits = transformer.logits_from_hidden(params, h, cfg)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), name


@pytest.mark.parametrize("name", ARCHS)
def test_train_grads_finite(setup, name):
    cfg, params = setup[name]
    batch = registry.make_batch(cfg, 2, 16)

    def loss(p):
        h = transformer.forward(p, batch, cfg, remat=True)
        logits = transformer.logits_from_hidden(p, h, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                   -1)[..., 0]
        return jnp.mean(lse - gold)

    l, g = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(l)), name
    assert np.log(cfg.vocab) * 0.2 < float(l) < np.log(cfg.vocab) * 3
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                 for x in jax.tree_util.tree_leaves(g))
    assert finite, f"{name}: non-finite gradients"


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_matches_forward(setup, name):
    cfg, params = setup[name]
    batch = registry.make_batch(cfg, 2, 16)
    cache = transformer.init_cache(cfg, 2, 32)
    lg_p, cache = transformer.prefill(params, batch, cfg, cache)
    h = transformer.forward(params, batch, cfg, remat=False)
    lg_f = transformer.logits_from_hidden(params, h[:, -1:], cfg)
    err = float(jnp.max(jnp.abs(lg_p.astype(jnp.float32)
                                - lg_f.astype(jnp.float32))))
    assert err < 1e-4, (name, err)
    assert int(cache["pos"]) == 16


@pytest.mark.parametrize("name", ARCHS)
def test_decode_agrees_with_prefill(setup, name):
    cfg, params = setup[name]
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)
    batch = registry.make_batch(cfg32, 2, 8)
    batch.pop("vision_embeds", None)  # decode path carries no vision stub
    enc_out = None
    if cfg.enc_dec:
        enc_out = transformer._run_encoder(params32, batch["frames"], cfg32)
    cache = transformer.init_cache(cfg32, 2, 16)
    lg_p, _ = transformer.prefill(params32, batch, cfg32, cache)
    cache2 = transformer.init_cache(cfg32, 2, 16)
    lg_d = None
    for t in range(8):
        lg_d, cache2 = transformer.decode_step(
            params32, batch["tokens"][:, t:t + 1], cache2, cfg32,
            enc_out=enc_out)
    scale = float(jnp.max(jnp.abs(lg_p))) + 1e-6
    rel = float(jnp.max(jnp.abs(lg_p - lg_d))) / scale
    assert rel < 1e-3, (name, rel)


@pytest.mark.parametrize("name", ARCHS)
def test_param_count_matches_shapes(setup, name):
    """Analytic 6ND param count must equal the real init's element count."""
    cfg, params = setup[name]
    actual = sum(int(np.prod(l.shape))
                 for l in jax.tree_util.tree_leaves(params))
    assert actual == cfg.param_count(), (
        name, actual, cfg.param_count())


def test_full_configs_match_assignment():
    """Spot-check the FULL configs against the assignment table."""
    c = configs.get("tinyllama_1_1b")
    assert (c.n_layers, c.d_model, c.n_heads, c.kv_heads, c.d_ff,
            c.vocab) == (22, 2048, 32, 4, 5632, 32000)
    c = configs.get("deepseek_v3_671b")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == (61, 7168, 128,
                                                           129280)
    assert c.moe.n_experts == 256 and c.moe.top_k == 8 and c.moe.n_shared == 1
    assert c.mla.kv_lora == 512
    c = configs.get("gemma3_27b")
    assert c.n_layers == 62 and c.vocab == 262144
    # 5:1 local:global pattern
    assert sum(1 for s in c.pattern if s.window is None) * 5 == sum(
        1 for s in c.pattern if s.window is not None)
    c = configs.get("jamba_v0_1_52b")
    assert c.n_layers == 32
    n_attn = sum(1 for s in (list(c.prefix) + list(c.pattern) * c.repeats)
                 if s.mixer == "attn")
    n_mamba = sum(1 for s in (list(c.prefix) + list(c.pattern) * c.repeats)
                  if s.mixer == "mamba")
    assert n_mamba == 7 * n_attn  # 1:7 attn:mamba
    c = configs.get("whisper_small")
    assert c.enc_dec and c.n_layers == 12 and c.d_model == 768
    c = configs.get("xlstm_350m")
    assert {s.mixer for s in c.pattern} == {"mlstm", "slstm"}
    c = configs.get("qwen2_vl_72b")
    assert c.n_layers == 80 and c.d_model == 8192 and c.frontend == "vision_stub"


@pytest.mark.parametrize("name", ["tinyllama_1_1b", "deepseek_v2_lite_16b",
                                  "jamba_v0_1_52b"])
def test_active_params_less_than_total_for_moe(name):
    cfg = configs.get(name)
    if any(s.ffn == "moe" for s in cfg.pattern):
        assert cfg.active_param_count() < cfg.param_count()
    else:
        assert cfg.active_param_count() == cfg.param_count()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batched", [False, True])
def test_rope_whole_head_in_halves_is_unchanged(dtype, batched):
    """RoPE over the whole head in two halves (every config but GLM-4's)
    is bit-identical to the rotary as it was before ``rope_dims`` and
    ``rope_interleaved``."""
    def before(x, cos, sin):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        if cos.ndim == 2:
            cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        else:
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               -1).astype(x.dtype)

    from repro.models import layers as L

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 64)).astype(dtype)
    pos = jnp.arange(12) + 1000
    cos, sin = L.rope_table(jnp.stack([pos, pos]) if batched else pos, 64,
                            10000.0)
    got = jax.jit(L.apply_rope)(x, cos, sin)
    want = jax.jit(before)(x, cos, sin)
    u = jnp.uint32 if dtype == "float32" else jnp.uint16
    assert (jax.lax.bitcast_convert_type(got, u)
            == jax.lax.bitcast_convert_type(want, u)).all()


def _moe_before(params, x, cfg, *, capacity_factor=1.25,
                dropless_below=512):
    """``layers.moe`` as it was before ``MoECfg``'s expert share and
    ``norm_topk``: every expert held, the top-k gates renormalised."""
    from repro.models import layers as L

    B, S, D = x.shape
    m = cfg.moe
    T, E, K = B * S, m.n_experts, m.top_k
    xt = x.reshape(T, D)
    logits = (xt @ params["router"]).astype(jnp.float32)
    gates, eids = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    C = T if T <= dropless_below else max(
        1, int(T * K / E * capacity_factor))
    flat_e = eids.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    within = jnp.arange(T * K) - grp_start[sorted_e]
    slot = jnp.full((E, C), T * K, jnp.int32).at[
        sorted_e, jnp.clip(within, 0, C - 1)].set(
        jnp.where(within < C, order, T * K).astype(jnp.int32), mode="drop")
    tok_of_slot = jnp.where(slot < T * K, slot // K, T)
    tok_of_slot = L._expert_sharding_hint(tok_of_slot, E)
    xg = L._expert_sharding_hint(
        jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])[tok_of_slot], E)
    h = jnp.einsum("ecd,edf->ecf", jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", xg, params["we1"])) * jnp.einsum(
        "ecd,edf->ecf", xg, params["we3"]), params["we2"])
    h = L._expert_sharding_hint(h, E)
    gate_of_slot = jnp.where(slot < T * K, jnp.concatenate(
        [gates.reshape(-1), jnp.zeros((1,), gates.dtype)])[
        jnp.minimum(slot, T * K)], 0.0)
    out = jnp.zeros((T + 1, D), jnp.float32).at[tok_of_slot.reshape(-1)].add(
        (h * gate_of_slot[..., None]).reshape(-1, D).astype(jnp.float32),
        mode="drop")
    y = out[:T].astype(x.dtype)
    if m.n_shared:
        y = y + L.swiglu(params["shared"], xt)
    return y.reshape(B, S, D)


def _mla_rope_before(positions, cfg):
    """MLA's rope table and softmax scale before ``MLACfg``'s YaRN: plain
    frequencies, ``1/sqrt(hd + rope_dim)``."""
    from repro.models import layers as L

    cos, sin = L.rope_table(positions, cfg.mla.rope_dim, cfg.rope_theta)
    return cos, sin, None


@pytest.mark.parametrize("name", ["deepseek_v3_671b", "jamba_v0_1_52b"])
def test_default_moe_and_mla_fields_keep_the_model(setup, monkeypatch, name):
    """The MoE and MLA configurations other than DeepSeek-V2-Lite leave the
    expert share, ``norm_topk``, the latent norm and YaRN at their
    defaults: their trees hold what they held before those fields, and
    their forward, prefill and decode are bit-identical to the layers as
    they were."""
    from repro.models import layers as L

    cfg, params = setup[name]
    assert (cfg.moe.held, cfg.moe.norm_topk) == (cfg.moe.n_experts, True)
    assert not cfg.mla.kv_norm and cfg.mla.yarn_factor == 1.0
    assert not any("kv_norm" in str(path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(params))
    batch = registry.make_batch(cfg, 2, 16)

    def outputs():
        h = transformer.forward(params, batch, cfg, remat=False)
        cache = transformer.init_cache(cfg, 2, 24)
        lg_p, cache = transformer.prefill(params, batch, cfg, cache)
        lg_d, _ = transformer.decode_step(params, batch["tokens"][:, :1],
                                          cache, cfg)
        return [transformer.logits_from_hidden(params, h, cfg), lg_p, lg_d]

    got = outputs()
    monkeypatch.setattr(L, "moe", _moe_before)
    monkeypatch.setattr(L, "_mla_rope", _mla_rope_before)
    want = outputs()
    for a, b in zip(got, want):
        u = jnp.uint32 if a.dtype == jnp.float32 else jnp.uint16
        assert (jax.lax.bitcast_convert_type(a, u)
                == jax.lax.bitcast_convert_type(b, u)).all(), name
