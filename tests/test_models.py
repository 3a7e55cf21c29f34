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
