"""The glm4_9b configuration: its cell resolves to GLM-4's tree at the
published widths, and the program's forward and serving path agree with
the benchmark's plain reference (``perfbench/refs/glm4.py``) within stated
tolerances, which weights rounded through float8 do not meet."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import archcfg, harness
from perfbench.refs import glm4

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm4_9b.wsync_rl"
# GLM-4's layer at a small size: head size 16 rotated on its first 8 dims
SMALL = {"hidden_size": 64, "ffn_hidden_size": 160, "kv_channels": 16,
         "num_attention_heads": 4, "multi_query_group_num": 2,
         "num_layers": 2, "padded_vocab_size": 256}
# Both sides in float32 at the highest matmul precision: they differ only
# in the order of their sums (largest seen 3.3e-6 of the logits' RMS).
TOL_F32 = 1e-4
TOL_BF16 = glm4.TOL_BF16  # its reason is beside it


def small_conf() -> dict:
    with open(os.path.join(REPO, "perfbench", "configs",
                           "glm4_9b_stage.json")) as f:
        conf = json.load(f)
    conf.update(SMALL)
    conf["arch"] = dict(conf["arch"], rope_dims=SMALL["kv_channels"] // 2)
    return conf


def random_params(cfg, seed: int):
    """Seeded weights of every leaf: unit-variance projections, biases and
    norm scales away from their init."""
    from repro.models import transformer

    flat, tdef = jax.tree_util.tree_flatten_with_path(
        transformer.abstract_params(cfg))
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        if "norm" in name:
            w = 1 + 0.1 * z
        elif name.startswith("b"):
            w = 0.1 * z
        else:
            w = z / np.sqrt(s.shape[-2])
        out.append(w.astype(s.dtype))
    return jax.tree_util.tree_unflatten(tdef, out)


def rel_err(got, want) -> float:
    """Largest logit error over the reference logits' RMS."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.sqrt(jnp.mean(want * want)))


def program_logits(params, tokens, cfg):
    from repro.models import transformer

    with jax.default_matmul_precision("highest"):
        h = transformer.forward(params, {"tokens": tokens}, cfg, remat=False)
        return transformer.logits_from_hidden(params, h, cfg)


def test_cell_builds_glm4_at_published_widths():
    from repro.models import transformer

    cell = harness.resolve(REPO, CELL)
    assert cell.chips == 1 and cell.mix["generator"] == "wsync"
    assert {m["name"] for m in cell.end_to_end} == {"sync_ms", "setup_s"}
    assert "sync.bitplane_roofline" in {m["name"] for m in cell.per_layer}
    cfg = archcfg.arch_config(cell.config)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd, cfg.d_ff) == \
        (4096, 32, 2, 128, 13696)
    assert (cfg.n_layers, cfg.vocab, cfg.tie_embeddings) == (1, 18944, False)
    assert (cfg.qkv_bias, cfg.rope_dims, cfg.rope_interleaved,
            cfg.rope_theta, cfg.norm_eps) == (True, 64, True, 10000.0,
                                              1.5625e-07)
    shapes = transformer.abstract_params(cfg)
    n = sum(l.size for l in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.param_count() == 359_154_176
    mixer = shapes["blocks"][0]["mixer"]
    assert (mixer["bq"].shape, mixer["bk"].shape) == ((1, 4096), (1, 256))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_reference(dtype, seed):
    conf = small_conf()
    cfg = dataclasses.replace(archcfg.arch_config(conf), dtype=dtype)
    params = random_params(cfg, seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, SMALL["padded_vocab_size"], (2, 24)), jnp.int32)
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    assert rel_err(program_logits(params, tokens, cfg),
                   glm4.forward(params, tokens, conf)) < tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_prefill_and_decode_match_reference(dtype):
    """``ServeEngine``'s prefill of a prompt, then 4 greedy decode steps
    through its cache, against the reference's full-forward logits of the
    same sequence."""
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine

    conf = small_conf()
    cfg = dataclasses.replace(archcfg.arch_config(conf), dtype=dtype)
    params = random_params(cfg, 7)
    scfg = ServeConfig(batch_slots=1, max_len=32)
    eng = ServeEngine(cfg, params, scfg)
    prompt = np.random.default_rng(7).integers(
        0, SMALL["padded_vocab_size"], 20).astype(np.int32)
    cache = transformer.init_cache(cfg, 1, scfg.max_len)
    with jax.default_matmul_precision("highest"):
        lg, cache = eng.prefill_step(
            params, {"tokens": jnp.asarray(prompt[None])}, cache)
        got, seq = [lg[0, -1]], list(prompt)
        for _ in range(4):
            seq.append(int(jnp.argmax(got[-1])))
            lg, cache = eng.decode_step(
                params, jnp.asarray([[seq[-1]]], jnp.int32), cache)
            got.append(lg[0, -1])
    want = glm4.forward(params, jnp.asarray([seq]), conf)[0, len(prompt) - 1:]
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    assert rel_err(jnp.stack(got), want) < tol


@pytest.mark.parametrize("seed", [0, 1])
def test_float8_weights_fail_the_reference(seed):
    """The control: the program on its weights rounded one precision below
    (``wsync.lower_precision``) lies outside the bfloat16 tolerance."""
    wsync = harness.load_module(os.path.join(
        REPO, "perfbench", "generators", "wsync.py"))
    conf = small_conf()
    cfg = archcfg.arch_config(conf)
    params = random_params(cfg, seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, SMALL["padded_vocab_size"], (2, 24)), jnp.int32)
    err = rel_err(program_logits(wsync.lower_precision(params), tokens, cfg),
                  glm4.forward(params, tokens, conf))
    assert err > TOL_BF16
