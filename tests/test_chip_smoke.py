"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its phases
drive the smoke twin of the model through train -> weight sync -> serve."""
import pytest

import chip_smoke
from repro.launch.mesh import make_mesh


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


@pytest.fixture
def smoke_sizes(monkeypatch, tmp_path):
    for name, value in (("SMOKE_CONFIG", True), ("BATCH", 2), ("SEQ", 32),
                        ("WARMUP_STEPS", 1), ("TIMED_STEPS", 2),
                        ("N_REQUESTS", 2), ("PROMPT_LEN", 16),
                        ("MAX_NEW", 4), ("CKPT_DIR", str(tmp_path))):
        monkeypatch.setattr(chip_smoke, name, value)


def test_phases_on_smoke_twin(smoke_sizes, capsys):
    mesh = make_mesh((1, 1), ("data", "model"))
    counter = chip_smoke.CompileCounter()
    cfg, state, runner = chip_smoke.train_twins(mesh, counter, "cpu")
    replica, params = chip_smoke.weight_sync(cfg, state, runner, "cpu")
    chip_smoke.serve(cfg, replica, params, "cpu")
    out = capsys.readouterr().out
    assert '"losses_bit_identical": true' in out
    assert '"mode": "delta"' in out and '"mode": "full"' in out
    assert '"tokens_identical": true' in out
    assert counter.compiles > 0
    # on the CPU the plans record the jnp reference, which the chip check
    # refuses
    with pytest.raises(chip_smoke.SmokeFailure, match="off the tpu kernels"):
        chip_smoke.check_plans("tpu", ["zero1"])


def test_check_spread_refuses_state_on_one_device():
    import jax
    import jax.numpy as jnp

    state = {"opt": {"buckets": ({"master": jnp.zeros((1, 8))},)}}
    other = type("Device", (), {"id": 99, "memory_stats": lambda self: None})
    with pytest.raises(chip_smoke.SmokeFailure, match="not spread"):
        chip_smoke.check_spread(state, [jax.devices()[0], other()])
