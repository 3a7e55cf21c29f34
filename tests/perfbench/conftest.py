"""Fixtures of the benchmark's CPU tests: a copy of the benchmark with a
tiny cell added as files alone, and a program whose process-wide plan
cache and fallback counts start empty."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "smollm_135m.wsync_rl"
TINY_CELL = "tiny.wsync_tiny"
TINY_METRIC = "sync.round_count"
# 3.3 MB of bf16 weights: above the default policy's 1 MB, so the bucket
# rides the compressed wire
TINY_SIZES = {"hidden_size": 256, "ffn_hidden_size": 512, "kv_channels": 64,
              "num_attention_heads": 4, "multi_query_group_num": 2,
              "num_layers": 2, "padded_vocab_size": 1024}


@pytest.fixture
def bench_root(tmp_path):
    """The benchmark's files copied under ``tmp_path``, plus a new
    configuration file, a new mix file, a new per-layer metric's reader
    and their entries in BENCHMARK.json: no existing file edited."""
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "perfbench", "configs",
                           "glm4_9b.json")) as f:
        conf = json.load(f)
    conf.update(TINY_SIZES, name="tiny")
    (tmp_path / "perfbench" / "configs" / "tiny.json").write_text(
        json.dumps(conf))
    with open(os.path.join(REPO, "perfbench", "mixes",
                           "wsync_rl.json")) as f:
        mix = json.load(f)
    (tmp_path / "perfbench" / "mixes" / "wsync_tiny.json").write_text(
        json.dumps(mix))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "wsync_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(TINY_CELL)
    (tmp_path / "perfbench" / "metrics" / f"{TINY_METRIC}.py").write_text(
        "def read(ctx):\n    return len(ctx.counters['rounds'])\n")
    bench["per_layer"].append({
        "name": TINY_METRIC, "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "sync round",
        "moves": "sync_ms", "workloads": [TINY_CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


@pytest.fixture
def fresh_program():
    from repro import kernels, sched

    kernels.clear_fallbacks()
    sched.default_cache().clear()
    yield
    kernels.clear_fallbacks()
