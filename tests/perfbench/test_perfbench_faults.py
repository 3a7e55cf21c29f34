"""``correct`` has to come out false when the timed path is broken: for the
control (the reference in the program's place, one precision below) and
for each fault a weight-sync cell can have, planted in the program."""
import io

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness

from conftest import TINY_CELL


def _run(root, hooks=None):
    r = harness.run_cell(root, TINY_CELL, 2**31 + 99, 0.3, False,
                         require_tpu=False, hooks=hooks, err=io.StringIO())
    return r, r["checks"]["mismatched_elements"]["value"]


def test_sound_run_is_correct(bench_root, fresh_program):
    r, bad = _run(bench_root)
    assert r["correct"] is True and bad == 0


def test_control_is_not_correct(bench_root, fresh_program):
    cell = harness.resolve(bench_root, TINY_CELL)
    hooks = harness.generator_of(bench_root, cell).control_hooks()
    r, bad = _run(bench_root, hooks)
    assert r["correct"] is False and bad > 0


def _unchanged(real):
    """The replica's apply returns its state unchanged."""
    def apply_update(update, base_params=None):
        return real(update) if base_params is None else base_params
    return apply_update


def _half(real):
    """Half of the leaves left out of the apply."""
    def apply_update(update, base_params=None):
        new = real(update, base_params)
        if base_params is None:
            return new
        leaves, treedef = jax.tree_util.tree_flatten(new)
        old = jax.tree_util.tree_leaves(base_params)
        k = len(leaves) // 2
        return jax.tree_util.tree_unflatten(treedef, leaves[:k] + old[k:])
    return apply_update


@pytest.mark.parametrize("fault", [_unchanged, _half])
def test_broken_apply_is_not_correct(bench_root, fresh_program, monkeypatch,
                                     fault):
    from repro.sync import engine as sync_engine

    monkeypatch.setattr(sync_engine, "apply_update",
                        fault(sync_engine.apply_update))
    r, bad = _run(bench_root)
    assert r["correct"] is False and bad > 0


def test_answer_altered_where_produced_is_not_correct(
        bench_root, fresh_program, monkeypatch):
    from repro.core import packing

    real = packing.decode_delta

    def decode_delta(m, base):
        out = real(m, base)
        flat = out.reshape(-1)
        return flat.at[0].set(-flat[0]).reshape(out.shape)

    monkeypatch.setattr(packing, "decode_delta", decode_delta)
    r, bad = _run(bench_root)
    assert r["correct"] is False and bad >= 1


def test_kernel_fallback_is_not_correct(bench_root, fresh_program):
    from repro import kernels

    kernels.record_fallback("encode_fused", "planted by the test")
    r, _ = _run(bench_root)
    assert r["correct"] is False
    assert r["checks"]["kernel_fallbacks"]["value"] >= 1


def test_plan_off_the_chip_kernels_is_not_correct(bench_root, fresh_program,
                                                  monkeypatch):
    # a chip run whose plans recorded the CPU reference
    monkeypatch.setattr(harness, "program_checks",
                        lambda platform, kinds, real=harness.program_checks:
                        real("tpu", kinds))
    r, _ = _run(bench_root)
    assert r["correct"] is False
    assert r["checks"]["plans_off_kernels"]["value"] >= 1


def test_mismatch_counts_bits():
    from perfbench.generators import wsync

    a = {"x": jnp.array([0.0, 1.0, jnp.nan], jnp.bfloat16)}
    b = {"x": jnp.array([-0.0, 1.0, jnp.nan], jnp.bfloat16)}
    assert int(wsync.mismatches(a, b)) == 1
    c = {"x": jax.lax.bitcast_convert_type(
        jnp.array([0, 0x3F80, 0x7FC1], jnp.uint16), jnp.bfloat16)}
    assert int(wsync.mismatches(a, c)) == 1  # another NaN payload
