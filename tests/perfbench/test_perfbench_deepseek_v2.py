"""The deepseek_v2_lite configuration: its cell resolves to DeepSeek-V2-Lite's
expert-parallel stage at the published widths, the expert shares add up
to the whole layer, the YaRN table is DeepSeek-V2's, and the program's
forward and serving path agree with the benchmark's plain reference
(``perfbench/refs/deepseek_v2.py``) within stated tolerances, which weights
rounded through float8 do not meet."""
import dataclasses
import itertools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import archcfg, harness
from perfbench.refs import deepseek_v2 as ref

from test_perfbench_glm4 import program_logits, rel_err

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek_v2_lite.wsync_rl"
CONFIG_FILE = os.path.join(REPO, "perfbench", "configs",
                           "deepseek_v2_lite_stage.json")
# Both sides in float32 at the highest matmul precision: they differ only
# in the order of their sums.
TOL_F32 = 1e-4
TOL_BF16 = ref.TOL_BF16  # its reason is beside it


def stage_conf() -> dict:
    with open(CONFIG_FILE) as f:
        return json.load(f)


def nested_from_published(conf: dict) -> dict:
    """The ``arch`` block's sizes that repeat published keys, from them."""
    rs = conf["rope_scaling"]
    return {
        "repeats": conf["num_hidden_layers"] - conf["first_k_dense_replace"],
        "prefix": [{"mixer": "mla", "ffn": "swiglu"}]
        * conf["first_k_dense_replace"],
        "moe": {"n_experts": conf["n_routed_experts_published"],
                "n_held": conf["n_routed_experts"],
                "top_k": conf["num_experts_per_tok"],
                "n_shared": conf["n_shared_experts"],
                "d_expert": conf["moe_intermediate_size"],
                "norm_topk": conf["norm_topk_prob"]},
        "mla": {"kv_lora": conf["kv_lora_rank"],
                "q_lora": conf["q_lora_rank"] or 0,
                "rope_dim": conf["qk_rope_head_dim"],
                "yarn_factor": rs["factor"],
                "yarn_original_len": rs["original_max_position_embeddings"],
                "yarn_beta_fast": rs["beta_fast"],
                "yarn_beta_slow": rs["beta_slow"],
                "yarn_mscale": rs["mscale"],
                "yarn_mscale_all_dim": rs["mscale_all_dim"]},
    }


def with_sizes(conf: dict, **sizes) -> dict:
    """``conf`` with published keys replaced, its ``arch`` block rebuilt
    from them."""
    conf = dict(conf, **sizes)
    nested = nested_from_published(conf)
    arch = dict(conf["arch"], repeats=nested["repeats"],
                prefix=nested["prefix"])
    arch["moe"] = dict(arch["moe"], **nested["moe"])
    arch["mla"] = dict(arch["mla"], **nested["mla"])
    conf["arch"] = arch
    return conf


# DeepSeek-V2's layers at a small size: 16 experts routed top-6, 4 held
SMALL = {"hidden_size": 64, "intermediate_size": 160, "kv_lora_rank": 32,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "n_routed_experts_published": 16, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 3,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "vocab_size": 256}


def small_conf(**extra) -> dict:
    return with_sizes(stage_conf(), **dict(SMALL, **extra))


def random_params(cfg, seed: int):
    """Seeded weights of every leaf: unit-variance projections, norm
    scales away from their init."""
    from repro.models import transformer

    flat, tdef = jax.tree_util.tree_flatten_with_path(
        transformer.abstract_params(cfg))
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, s) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(jax.random.fold_in(key, i), s.shape)
        w = 1 + 0.1 * z if "norm" in name else z / np.sqrt(s.shape[-2])
        out.append(w.astype(s.dtype))
    return jax.tree_util.tree_unflatten(tdef, out)


def served_logits(params, prompt, cfg, steps: int = 4):
    """``ServeEngine``'s prefill of ``prompt``, then ``steps`` greedy decode
    steps through its latent cache: the logits of each, and the sequence."""
    from repro.models import transformer
    from repro.serve.engine import ServeConfig, ServeEngine

    scfg = ServeConfig(batch_slots=1, max_len=32)
    eng = ServeEngine(cfg, params, scfg)
    cache = transformer.init_cache(cfg, 1, scfg.max_len)
    with jax.default_matmul_precision("highest"):
        lg, cache = eng.prefill_step(
            params, {"tokens": jnp.asarray(prompt[None])}, cache)
        got, seq = [lg[0, -1]], list(prompt)
        for _ in range(steps):
            seq.append(int(jnp.argmax(got[-1])))
            lg, cache = eng.decode_step(
                params, jnp.asarray([[seq[-1]]], jnp.int32), cache)
            got.append(lg[0, -1])
    return jnp.stack(got), seq


def test_cell_builds_the_stage_at_published_widths():
    from repro.models import transformer
    from repro.sched.compile import WSYNC_BUCKET_BYTES, wsync_buckets

    cell = harness.resolve(REPO, CELL)
    assert cell.chips == 1 and cell.mix["generator"] == "wsync"
    assert {m["name"] for m in cell.end_to_end} == {"sync_ms", "setup_s"}
    assert {"sync.bitplane_roofline", "sync.buckets_per_round"} <= {
        m["name"] for m in cell.per_layer}
    cfg = archcfg.arch_config(cell.config)
    assert (cfg.d_model, cfg.n_heads, cfg.hd, cfg.d_ff, cfg.vocab) == \
        (2048, 16, 128, 10944, 12800)
    assert (cfg.n_layers, len(cfg.prefix), cfg.tie_embeddings,
            cfg.rope_interleaved, cfg.norm_eps) == (5, 1, False, True, 1e-6)
    assert cfg.moe == cfg.moe.__class__(
        n_experts=64, top_k=6, n_shared=2, d_expert=1408, n_held=8,
        first_held=0, norm_topk=False)
    assert (cfg.mla.kv_lora, cfg.mla.q_lora, cfg.mla.rope_dim,
            cfg.mla.kv_norm, cfg.mla.yarn_factor) == (512, 0, 64, True, 40)
    shapes = transformer.abstract_params(cfg)
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(l.size for l in leaves) == cfg.param_count() == 535_060_992
    ffn = shapes["blocks"][0]["ffn"]
    assert ffn["router"].shape == (4, 2048, 64)
    assert ffn["we1"].shape == ffn["we3"].shape == (4, 8, 2048, 1408)
    assert ffn["we2"].shape == (4, 8, 1408, 2048)
    assert shapes["blocks"][0]["mixer"]["kv_norm"].shape == (4, 512)
    buckets, raw = wsync_buckets(leaves)
    assert raw == () and len(buckets) > 1
    for _, members in buckets:
        assert (len(members) == 1 or sum(m[2] for m in members) * 2
                <= WSYNC_BUCKET_BYTES)


def test_nested_arch_sizes_repeat_the_published_keys():
    conf = stage_conf()
    nested = nested_from_published(conf)
    arch = conf["arch"]
    assert arch["repeats"] == nested["repeats"]
    assert arch["prefix"] == nested["prefix"]
    assert {k: arch["moe"][k] for k in nested["moe"]} == nested["moe"]
    assert {k: arch["mla"][k] for k in nested["mla"]} == nested["mla"]
    assert arch["moe"]["first_held"] == 0  # experts 0-7, as deployed
    assert conf["qk_nope_head_dim"] == conf["v_head_dim"]  # one head_dim
    assert conf["num_key_value_heads"] == conf["num_attention_heads"]
    assert conf["rope_scaling"]["type"] == "yarn"
    assert (conf["scoring_func"], conf["topk_method"], conf["n_group"],
            conf["topk_group"], conf["routed_scaling_factor"],
            conf["moe_layer_freq"]) == ("softmax", "greedy", 1, 1, 1, 1)


def _yarn_transcribed(dim, base, factor, orig, beta_fast, beta_slow,
                      mscale, mscale_all_dim):
    """``DeepseekV2YarnRotaryEmbedding``'s equations, line by line, in
    float64: (low, high, inv_freq, cos/sin factor, softmax mscale**2)."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1 - ramp
    inv = freq_inter * (1 - mask) + freq_extra * mask
    return (low, high, inv,
            get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim),
            get_mscale(factor, mscale_all_dim) ** 2)


@pytest.mark.parametrize("dim,factor,orig,mscale,all_dim", [
    (64, 40.0, 4096, 0.707, 0.707),  # DeepSeek-V2-Lite
    (64, 40.0, 4096, 1.0, 0.0),
    (8, 40.0, 4096, 0.707, 0.707),  # the small configuration's
    (32, 4.0, 2048, 1.0, 1.0),
])
def test_yarn_table_is_deepseek_v2s(dim, factor, orig, mscale, all_dim):
    from repro.models import layers
    from repro.models.config import ArchConfig, MLACfg

    m = MLACfg(rope_dim=dim, yarn_factor=factor, yarn_original_len=orig,
               yarn_mscale=mscale, yarn_mscale_all_dim=all_dim)
    low, high, inv, cos_sin, mscale2 = _yarn_transcribed(
        dim, 10000.0, factor, orig, 32, 1, mscale, all_dim)
    if (dim, factor, orig, mscale) == (64, 40.0, 4096, 0.707):
        assert (low, high) == (10, 23)
        assert mscale2 == pytest.approx(1.58963, abs=5e-6)
        assert math.sqrt(mscale2) == pytest.approx(1.26080, abs=5e-6)
    np.testing.assert_allclose(layers.yarn_inv_freq(dim, 10000.0, m), inv,
                               rtol=1e-6)
    cfg = ArchConfig(name="t", d_model=64, n_heads=4, kv_heads=4, d_ff=64,
                     vocab=64, head_dim=16, mla=m)
    pos = jnp.arange(40)
    cos, sin, scale = layers._mla_rope(pos, cfg)
    ang = np.arange(40)[:, None] * inv
    np.testing.assert_allclose(cos, np.cos(ang) * cos_sin, atol=2e-6)
    np.testing.assert_allclose(sin, np.sin(ang) * cos_sin, atol=2e-6)
    assert scale == pytest.approx(mscale2 / math.sqrt(16 + dim), rel=1e-12)
    conf = {"rope_scaling": {"factor": factor, "beta_fast": 32,
                             "beta_slow": 1, "mscale": mscale,
                             "mscale_all_dim": all_dim,
                             "original_max_position_embeddings": orig},
            "qk_rope_head_dim": dim, "qk_nope_head_dim": 16,
            "rope_theta": 10000.0}
    r_inv, r_cos_sin, r_scale = ref.yarn(conf)
    np.testing.assert_allclose(r_inv, inv, rtol=1e-6)
    assert r_cos_sin == pytest.approx(cos_sin, rel=1e-12)
    assert r_scale == pytest.approx(mscale2 / math.sqrt(16 + dim), rel=1e-12)


def test_default_mla_keeps_plain_rope():
    from repro.models import layers
    from repro.models.config import ArchConfig, MLACfg

    cfg = ArchConfig(name="t", d_model=64, n_heads=4, kv_heads=4, d_ff=64,
                     vocab=64, head_dim=16, mla=MLACfg(rope_dim=8))
    cos, sin, scale = layers._mla_rope(jnp.arange(5), cfg)
    want = layers.rope_table(jnp.arange(5), 8, cfg.rope_theta)
    assert scale is None
    np.testing.assert_array_equal(cos, want[0])
    np.testing.assert_array_equal(sin, want[1])


def _shares(n: int):
    """Every split of experts 0..n-1 into contiguous shares."""
    for cuts in itertools.product((0, 1), repeat=n - 1):
        bounds = [0] + [i + 1 for i, c in enumerate(cuts) if c] + [n]
        yield list(zip(bounds[:-1], bounds[1:]))


@pytest.mark.parametrize("norm_topk", [False, True])
def test_expert_shares_add_up_to_the_layer(norm_topk):
    """Over every split of the 6 experts into shares, each share's routed
    part (the layer holding only those experts), summed, plus the shared
    experts once, is the uncut layer: the reference's and the program's."""
    from repro.models import layers
    from repro.models.config import ArchConfig, MoECfg

    E = 6
    whole = ArchConfig(name="t", d_model=32, n_heads=4, kv_heads=4, d_ff=64,
                       vocab=64, dtype="float32",
                       moe=MoECfg(n_experts=E, top_k=3, n_shared=2,
                                  d_expert=16, norm_topk=norm_topk))
    kx, *ks = jax.random.split(jax.random.PRNGKey(3), 6)
    p = layers.init_moe(ks[0], whole, jnp.float32)
    p["router"] = jax.random.normal(ks[1], p["router"].shape)
    x = jax.random.normal(kx, (2, 9, 32), jnp.float32)

    def routed(first, last):
        cfg = dataclasses.replace(whole, moe=dataclasses.replace(
            whole.moe, n_shared=0, n_held=last - first, first_held=first))
        held = {k: p[k][first:last] for k in ("we1", "we3", "we2")}
        with jax.default_matmul_precision("highest"):
            return layers.moe(dict(held, router=p["router"]), x, cfg)

    part = {(a, b): routed(a, b) for a in range(E) for b in range(a + 1,
                                                                  E + 1)}
    with jax.default_matmul_precision("highest"):
        shared = layers.swiglu(p["shared"], x)
        program = layers.moe(p, x, whole)
    conf = {"n_routed_experts_published": E, "n_routed_experts": E,
            "num_experts_per_tok": 3, "norm_topk_prob": norm_topk,
            "routed_scaling_factor": 1}
    with jax.default_matmul_precision("highest"):
        reference = ref.moe(p, x, conf)
    np.testing.assert_allclose(program, reference, rtol=1e-5, atol=1e-5)
    n = 0
    for split in _shares(E):
        total = shared + sum(part[s] for s in split)
        np.testing.assert_allclose(total, reference, rtol=1e-5, atol=1e-5)
        n += 1
    assert n == 2 ** (E - 1)
    # a share is the reference's layer holding only those experts
    conf_share = dict(conf, n_routed_experts=2)
    share_p = dict(p, **{k: p[k][2:4] for k in ("we1", "we3", "we2")})
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            part[(2, 4)] + shared, ref.moe(share_p, x, conf_share, first=2),
            rtol=1e-5, atol=1e-5)


def test_smoke_and_full_config_follow_the_published_model():
    from repro import configs
    from repro.models import transformer

    full = configs.get("deepseek_v2_lite_16b")
    assert (full.d_ff, full.moe.d_expert, full.moe.norm_topk,
            full.mla.kv_norm, full.mla.yarn_factor, full.rope_interleaved) \
        == (10944, 1408, False, True, 40.0, True)
    assert full.param_count() == 15_706_484_224  # 15.7B, as published
    smoke = configs.get_smoke("deepseek_v2_lite_16b")
    shapes = transformer.abstract_params(smoke)
    assert sum(l.size for l in jax.tree_util.tree_leaves(shapes)) == \
        smoke.param_count()


def smoke_conf() -> dict:
    """The published keys of ``deepseek_v2_lite_16b``'s SMOKE."""
    from repro import configs

    c = configs.get_smoke("deepseek_v2_lite_16b")
    m = c.mla
    return {"hidden_size": c.d_model, "num_attention_heads": c.n_heads,
            "qk_nope_head_dim": c.hd, "v_head_dim": c.hd,
            "qk_rope_head_dim": m.rope_dim, "kv_lora_rank": m.kv_lora,
            "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "rope_scaling": {"factor": m.yarn_factor, "beta_fast": 32,
                             "beta_slow": 1, "mscale": m.yarn_mscale,
                             "mscale_all_dim": m.yarn_mscale_all_dim,
                             "original_max_position_embeddings":
                                 m.yarn_original_len},
            "first_k_dense_replace": len(c.prefix),
            "num_hidden_layers": c.n_layers,
            "n_routed_experts_published": c.moe.n_experts,
            "n_routed_experts": c.moe.held,
            "num_experts_per_tok": c.moe.top_k,
            "norm_topk_prob": c.moe.norm_topk, "routed_scaling_factor": 1}


def _cases():
    from repro import configs

    smoke = configs.get_smoke("deepseek_v2_lite_16b")
    return {"smoke": (smoke, smoke_conf()),
            "stage_small": (archcfg.arch_config(small_conf()), small_conf())}


@pytest.mark.parametrize("case", ["smoke", "stage_small"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_reference(case, dtype, seed):
    cfg, conf = _cases()[case]
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = random_params(cfg, seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (2, 24)), jnp.int32)
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    assert rel_err(program_logits(params, tokens, cfg),
                   ref.forward(params, tokens, conf)) < tol


@pytest.mark.parametrize("case", ["smoke", "stage_small"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_serve_prefill_and_decode_match_reference(case, dtype):
    """``ServeEngine``'s prefill of a prompt, then 4 greedy decode steps
    through its latent cache, against the reference's full-forward logits
    of the same sequence."""
    cfg, conf = _cases()[case]
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = random_params(cfg, 7)
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab, 20).astype(np.int32)
    got, seq = served_logits(params, prompt, cfg)
    want = ref.forward(params, jnp.asarray([seq]), conf)[0, len(prompt) - 1:]
    tol = TOL_F32 if dtype == "float32" else TOL_BF16
    assert rel_err(got, want) < tol


@pytest.mark.parametrize("case", ["smoke", "stage_small"])
@pytest.mark.parametrize("seed", [0, 1])
def test_float8_weights_fail_the_reference(case, seed):
    """The control: the program on its weights rounded one precision below
    (``wsync.lower_precision``) lies outside the bfloat16 tolerance."""
    wsync = harness.load_module(os.path.join(
        REPO, "perfbench", "generators", "wsync.py"))
    cfg, conf = _cases()[case]
    params = random_params(cfg, seed)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (2, 24)), jnp.int32)
    err = rel_err(program_logits(wsync.lower_precision(params), tokens, cfg),
                  ref.forward(params, tokens, conf))
    assert err > TOL_BF16


# DeepSeek-V2's stage at a size whose buckets, cut at 2 MiB, are over the
# default policy's 1 MB and so ride the compressed wire
TINY_STAGE = dict(SMALL, hidden_size=256, moe_intermediate_size=128,
                  intermediate_size=512, vocab_size=2048)


def _tiny_root(tmp_path) -> str:
    """The benchmark's files under ``tmp_path``, the cell's configuration
    file at ``TINY_STAGE``'s sizes."""
    import shutil

    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    (tmp_path / "perfbench" / "configs" / "deepseek_v2_lite_stage.json"
     ).write_text(json.dumps(small_conf(**TINY_STAGE)))
    return str(tmp_path)


def test_cell_runs_on_the_cpu_over_several_buckets(tmp_path, monkeypatch):
    """A traced run of the cell at a tiny size with the bucket limit
    lowered so that its tree splits: correct, and the new metric reads the
    plan's buckets."""
    import io

    from perfbench import counts
    from repro import kernels, sched
    from repro.models import transformer
    from repro.sched import compile as sched_compile

    root = _tiny_root(tmp_path)
    cfg = archcfg.arch_config(small_conf(**TINY_STAGE))
    leaves = jax.tree_util.tree_leaves(transformer.abstract_params(cfg))
    monkeypatch.setattr(sched_compile, "WSYNC_BUCKET_BYTES", 2 << 20)
    n_buckets = len(sched_compile.wsync_buckets(leaves)[0])
    assert n_buckets >= 3
    monkeypatch.setattr(harness.ReadContext, "peaks",
                        lambda self: counts.peaks("TPU v5 lite"))
    kernels.clear_fallbacks()
    sched.default_cache().clear()
    r = harness.run_cell(root, CELL, 2**31 + 19, 0.3, True,
                         require_tpu=False, err=io.StringIO())
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["sync.buckets_per_round"] == {
        "value": n_buckets, "unit": "count"}
    assert 0 < r["metrics"]["sync.wire_ratio"]["value"] < 1
