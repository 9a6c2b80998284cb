"""CPU rehearsal of ``chip_smoke.py``: every phase the script runs on the
chip, at a tiny fleet with the kernels in interpret mode (chosen here,
not by the script), plus the checks that keep the script from passing
anywhere but on a TPU."""

import types

import jax
import pytest

import chip_smoke


def test_platform_check_refuses_cpu():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.require_tpu(1)


@pytest.mark.parametrize("interpret, text", [
    (False, "func.func @main() { }"),
    (True, 'stablehlo.custom_call @tpu_custom_call() { }'),
])
def test_kernel_program_check_refuses_the_wrong_mode(interpret, text):
    lowered = types.SimpleNamespace(as_text=lambda: text)
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.check_kernel_program("kernel", lowered, interpret)


def test_one_chip_phases_interpreted():
    chip_smoke.one_chip(n_dimms=1024, chunk_steps=16, n_chunks=2,
                        decision_steps=4, error_rate=2e-3, seed=0,
                        interpret=True)


def test_mesh_phase_interpreted():
    chip_smoke.mesh_phase(jax.device_count(), shard_dimms=1024,
                          chunk_steps=16, n_chunks=2, error_rate=2e-3, seed=0,
                          interpret=True)


def test_quarter_scores_combine_to_fleet_means():
    scores = [
        {"speedup_realized_mean": 0.1, "speedup_realized_min": 0.05,
         "switches_total": 3.0},
        {"speedup_realized_mean": 0.3, "speedup_realized_min": 0.01,
         "switches_total": 5.0},
    ]
    assert chip_smoke.combine_quarter_scores(scores) == {
        "speedup_realized_mean": 0.2, "speedup_realized_min": 0.01,
        "switches_total": 8.0,
    }
