"""BENCHMARK.json and the files it names: the contract's shape, every file
found by name, and a new cell added as files alone."""
import json
import os
import re

import pytest

from perfbench import archcfg, harness

from conftest import REPO, TINY_CELL

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(REPO)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_and_units(bench):
    assert set(bench) == KEYS["top"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        entries = bench[kind]
        key = kind[:-1] if kind in ("configs", "workloads") else kind
        names = [e["name"] for e in entries]
        assert len(names) == len(set(names)), kind
        for e in entries:
            extra = set(e) - KEYS[key]
            assert set(e) >= KEYS[key] and extra <= {"workloads"}, e
            assert NAME.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e and kind != "end_to_end":
                    assert _line(e[text]), (e["name"], text)
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher"), e
                assert e["source"] in SOURCES, e
    assert len(json.dumps(bench)) <= 64 * 1024


def test_metrics_bounds_and_cells(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for name, w in cells.items():
        mine = [m for m in e2e.values() if name in m.get("workloads", [name])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(name in m.get("workloads", [name])
                   for m in bench["per_layer"]), name
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for w in m.get("workloads", []):
            assert w in cells and w in e2e[m["moves"]].get("workloads", [w])


def test_run_seconds_fits_a_full_check(bench):
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_file_is_found_by_name(bench):
    assert bench["command"] == ["python3", "perfbench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, p)) and ".." not in p
    for w in bench["workloads"]:
        cell = harness.resolve(REPO, w["name"])
        assert harness.generator_of(REPO, cell).run
        for m in cell.per_layer:
            assert harness.reader_of(REPO, m["name"]).read
    for c in bench["configs"]:
        assert c["file"].startswith("perfbench/configs/")


def test_config_files_state_their_cuts(bench):
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in conf and not key.endswith(("_dim", "_rank"))
        assert conf["deployment"] and conf["assumed"]
        fields = archcfg.arch_fields(conf)
        assert all(conf[k] == fields[f]
                   for f, k in conf["arch_keys"].items())


@pytest.mark.parametrize("name, widths, layers, vocab, tied, params", [
    # (d_model, heads, kv heads, head_dim, d_ff) as published
    ("smollm_135m", (576, 9, 3, 64, 1536), 30, 49152, True, 134_515_008),
    ("glm4_9b", (4096, 32, 2, 128, 13696), 1, 18944, False, 359_149_568),
])
def test_configs_at_published_widths(name, widths, layers, vocab, tied,
                                     params):
    import jax
    from repro.models import transformer

    with open(os.path.join(REPO, "perfbench", "configs", f"{name}.json")) as f:
        cfg = archcfg.arch_config(json.load(f))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hd, cfg.d_ff) == \
        widths
    assert (cfg.n_layers, cfg.vocab, cfg.tie_embeddings) == (layers, vocab,
                                                             tied)
    shapes = transformer.abstract_params(cfg)
    assert sum(l.size for l in jax.tree_util.tree_leaves(shapes)) == params


def test_a_cell_added_as_files_alone(bench_root):
    cell = harness.resolve(bench_root, TINY_CELL)
    assert cell.config["hidden_size"] == 256
    assert cell.mix["generator"] == "wsync"
    assert {m["name"] for m in cell.end_to_end} == {"sync_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {"sync.idle_share"}
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve(bench_root, "absent.cell")
