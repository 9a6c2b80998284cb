"""Compile the fleet controller's main path for a described TPU v5e chip.

Nothing runs. Each test compiles at the size ``chip_smoke.py`` runs
(10⁶ DIMMs, 5 temperature bins, chunk 256; the chunk kernel also at a
day's 1,440 steps) for one chip of a ``v5e:2x2``
topology described here, so a kernel that Mosaic refuses, or one that
overruns VMEM or the chip's memory, fails on a machine without a chip.
The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU compiler's library, and the
suite runs in several. The compilation cache is off around the compiles,
because an entry compiled for a described chip cannot be read back
without one.
"""

import os
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import controller
from repro.kernels.charge_sweep import ref as sweep_ref
from repro.kernels.charge_sweep.kernel import N_INVARIANTS, charge_sweep_tiled
from repro.kernels.charge_sweep.ops import kernel_scalars
from repro.kernels.replay_step import ref as replay_ref
from repro.kernels.replay_step.kernel import (
    DIMMS_PER_TILE,
    ROW_SLOTS,
    accumulate_tiled,
    replay_chunk_tiled,
)
from repro.kernels.replay_step.ops import replay_scalars

N_DIMMS = 1_000_000
N_BINS = len(controller.DEFAULT_TEMP_BINS)
CHUNK = 256
#: Steps of one day of telemetry at minute cadence.
DAY_STEPS = 1440
#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9
#: Lane rows of a DIMM-axis operand padded to whole 1,024-DIMM tiles.
ROWS = -(-N_DIMMS // DIMMS_PER_TILE) * DIMMS_PER_TILE // 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    sigterm = signal.getsignal(signal.SIGTERM)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # Loading the TPU compiler installs a SIGTERM handler that prints a
        # stack trace; a suite stopped by SIGTERM would get that trace
        # spliced into its progress output.
        signal.signal(signal.SIGTERM, sigterm)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """``compile(fn, *shapes)`` for the described chip, cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_charge_sweep_kernel_compiles_at_fleet_size(compile_for_chip):
    rows = -(-N_BINS * N_DIMMS // 1024) * 8     # 5 × 10⁶ cells in (8, 128) tiles
    compiled = compile_for_chip(
        lambda inv: charge_sweep_tiled(
            inv, n_grid=sweep_ref.SEARCH_GRID_SIZES, scal=kernel_scalars()
        ),
        ((N_INVARIANTS, rows, 128), jnp.float32),
    )
    assert _has_kernel(compiled)


def _compile_replay_chunk(compile_for_chip, chunk):
    scal = replay_scalars(controller.DEFAULT_TEMP_BINS,
                          controller.ControllerParams())
    i32, f32 = jnp.int32, jnp.float32
    return compile_for_chip(
        lambda *a: replay_chunk_tiled(*a, scal=scal),
        ((3, ROWS, 128), i32),
        ((N_BINS + 1, ROWS, 128), i32),
        ((ROWS, 128), i32),
        ((ROW_SLOTS, ROWS, 128), f32),
        ((N_BINS * ROW_SLOTS, ROWS, 128), f32),
        ((chunk, ROWS, 128), f32),
        ((chunk, ROWS, 128), f32),
    )


def test_replay_chunk_kernel_compiles_at_fleet_size(compile_for_chip):
    assert _has_kernel(_compile_replay_chunk(compile_for_chip, CHUNK))


def test_replay_chunk_kernel_compiles_for_a_day_long_chunk(compile_for_chip):
    """A whole day at minute cadence in one chunk, past the length at
    which a whole-chunk telemetry block would overrun scoped VMEM."""
    assert _has_kernel(_compile_replay_chunk(compile_for_chip, DAY_STEPS))


def test_accumulate_kernel_compiles_at_fleet_size(compile_for_chip):
    i32, f32 = jnp.int32, jnp.float32
    compiled = compile_for_chip(
        accumulate_tiled,
        ((CHUNK, ROWS, 128), i32),
        ((CHUNK, ROWS, 128), i32),
        ((CHUNK * ROW_SLOTS, ROWS, 128), f32),
        ((N_BINS + 1, ROWS, 128), i32),
        ((ROWS, 128), i32),
        ((ROW_SLOTS, ROWS, 128), f32),
    )
    assert _has_kernel(compiled)


def test_ref_chunk_scan_fits_one_chip(compile_for_chip):
    i32, f32 = jnp.int32, jnp.float32
    n, b = N_DIMMS, N_BINS

    def scan(stack, edges, guard, hyst, steps, bins, streak, fused,
             occ, sw, sums, n_steps, temps, errors):
        return replay_ref.chunk_scan(
            stack, edges, controller.ControllerParams(guard, hyst, steps),
            controller.ControllerState(bins, streak, fused),
            occ, sw, sums, n_steps, temps, errors,
        )

    mem = compile_for_chip(
        scan,
        ((n, b, 2, 4), f32), ((b,), f32), ((), f32), ((), f32), ((), i32),
        ((n,), i32), ((n,), i32), ((n,), jnp.bool_),
        ((n, b + 1), i32), ((n,), i32), ((n, 2, 4), f32), ((), i32),
        ((CHUNK, n), f32), ((CHUNK, n), jnp.bool_),
    ).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= V5E_HBM_BYTES
