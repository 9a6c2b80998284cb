"""Pallas TPU kernel: fused replay chunk scan (step + lookup + partials).

The reference chunk scan (:mod:`.ref`) runs each step of a chunk as
separate XLA ops — vmapped controller transition, ``(N, 2, 4)`` timing
gather from the table stack, :func:`trace_score_accumulate` — so even
though the scan's per-step outputs are dead-code-eliminated, every step
still materializes its ``(n_dimms, 2, 4)`` realized-timing block in HBM
between ops. This kernel fuses the whole chunk for a tile of DIMMs: the
controller registers, the running :class:`ScorePartials` accumulators and
the tile's resident slice of the :class:`DimmTimingTable` stack live in
VMEM/registers for the entire ``fori_loop`` over steps, and only the
final state + partials leave the kernel. The per-step timing rows are
never materialized AT ALL — not even transiently — which is exactly the
ROADMAP's "fuse the replay scan" item.

Bit-exactness contract: the per-step transition mirrors
:func:`repro.core.controller._advance_dimm` expression by expression —

* ``searchsorted(edges, t_eff, side="left")`` becomes the equivalent
  ``Σ_b (t_eff > edges[b])`` (for strictly ascending edges the insertion
  point IS the count of edges strictly below the value, equality cases
  included);
* the target-edge gather and the ``(2, 4)`` row gather become reversed
  ``where``-chains over the (static, small) bin axis — selects of the
  same stored f32 values, no arithmetic, hence bit-exact;
* ``target_edge - hysteresis_c`` and ``temp + guard_band_c`` are computed
  in f32 *inside* the kernel (the scalars are f32-round-tripped Python
  floats — see :func:`.ops.replay_scalars`), never pre-folded in f64;
* the timing sums accumulate ``S ← S + row_j`` once per step — the SAME
  single f32 add per step, in the SAME step order, as the ref's per-step
  ``partials.timing_sums + timings.sum(axis=0)`` with a one-step block.
  Parity is therefore UNCONDITIONAL — it does not even need the
  cycle-quantization envelope that makes chunking exact.

The occupancy/switch accumulators are int32 (exact under any order). A
formulation that post-multiplies final occupancy by the stack rows
(``sums = Σ_b occ[b] · stack[b]``) was rejected: it computes the same
mathematical sum with different f32 rounding and would break the bitwise
gates.

Layout (:mod:`.ops` builds it): DIMMs ride the VPU lanes as (8, 128)
tiles; every per-DIMM operand arrives stacked on a leading axis —
state as (3, 8, 128) int32 [bin, streak, fused], occupancy as
(n_bins+1, 8, 128), timing sums and each bin's (2, 4) block flattened to
8 slots. The step axis walks a ``fori_loop`` whose carry is the full
register set; the grid walks DIMM tiles and, within each, blocks of
steps in order.

VMEM budget: every block is a whole number of 4 KiB (8, 128) tiles, and
the Pallas pipeline double-buffers each input and output block. The
chunk kernel streams the telemetry :data:`CHUNK_STEP_BLOCK` steps at a
time (grid axis 1) while the registers and partials stay resident in its
outputs, so its blocks per 1024-DIMM tile are ``3 + (n_bins+1) + 1 + 8 +
8·n_bins + 2·steps`` in and ``3 + (n_bins+1) + 1 + 8`` out, and it holds
``2 · (10·n_bins + 2·steps + 26) · 4 KiB`` — 4.6 MiB at 5 bins and 256
steps, inside the 16 MiB scoped VMEM limit whatever the chunk length
(taking a whole chunk as one block reaches the limit near chunk 980).
The accumulate kernel streams its decision block :data:`ACC_STEP_BLOCK`
steps at a time, so it holds ``2 · (10·steps + 2·(n_bins+1) + 18) · 4
KiB`` — 2.7 MiB at 32 steps and 5 bins, whatever the chunk length.
Taking the whole chunk as one block would need 20.2 MiB at chunk 256.
(8, 128) is the f32 VPU register shape and stays fixed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Flattened (access, param) slots per timing row: 2 access types × 4
#: timing parameters, slot index ``a * 4 + p``.
ROW_SLOTS: int = 8

#: DIMM-tile shape: 8 sublanes × 128 lanes (f32 VPU tile).
TILE: Tuple[int, int] = (8, 128)
DIMMS_PER_TILE: int = TILE[0] * TILE[1]


@dataclasses.dataclass(frozen=True)
class ReplayScalars:
    """Static controller policy closed over by the kernel body.

    All floats are Python floats that round-trip f64→f32 exactly (built
    by :func:`.ops.replay_scalars` via ``float(np.float32(x))``), so the
    in-kernel f32 arithmetic sees bit-identical operands to the ref
    path's traced f32 scalars."""

    edges: Tuple[float, ...]    # bin upper edges, ascending (n_bins,)
    guard_band_c: float
    hysteresis_c: float
    hysteresis_steps: int
    jedec: Tuple[float, ...]    # flattened (2, 4) JEDEC sentinel row


def _replay_chunk_kernel(
    state_ref,   # (3, 8, 128) i32  [bin_idx, cool_streak, fused]
    occ_ref,     # (n_bins+1, 8, 128) i32
    sw_ref,      # (8, 128) i32
    sums_ref,    # (ROW_SLOTS, 8, 128) f32
    stack_ref,   # (n_bins · ROW_SLOTS, 8, 128) f32
    temps_ref,   # (steps, 8, 128) f32
    errs_ref,    # (steps, 8, 128) f32 (0.0 / 1.0)
    state_out,   # (3, 8, 128) i32
    occ_out,     # (n_bins+1, 8, 128) i32
    sw_out,      # (8, 128) i32
    sums_out,    # (ROW_SLOTS, 8, 128) f32
    *,
    steps: int,
    scal: ReplayScalars,
):
    """One block of ``steps`` steps for one DIMM tile. The outputs stay
    resident across the step-block grid axis and carry the registers and
    partials from one block to the next, so every slot still takes one
    f32 add per step, in step order."""
    n_bins = len(scal.edges)
    guard = jnp.float32(scal.guard_band_c)
    hyst = jnp.float32(scal.hysteresis_c)
    edges = tuple(jnp.float32(e) for e in scal.edges)
    jedec = tuple(jnp.float32(v) for v in scal.jedec)

    # The tile's entire register file, resident for the whole chunk.
    rows = tuple(stack_ref[i] for i in range(n_bins * ROW_SLOTS))

    def one_step(k, carry):
        # ``fused`` is carried as int32 0/1: Mosaic refuses a boolean
        # vector loop carry.
        bin_idx, streak, fused_i, sw, occ, sums = carry
        temp = temps_ref[k]
        err = errs_ref[k] > 0.5

        # --- controller transition (mirrors controller._advance_dimm) ---
        fused = (fused_i > 0) | err
        t_eff = temp + guard
        target = jnp.zeros(TILE, jnp.int32)
        for e in edges:
            target = target + (t_eff > e).astype(jnp.int32)
        hotter = target > bin_idx
        cooler = target < bin_idx
        # edges[target] with the beyond-last sentinel → +inf; a reversed
        # where-chain so bin 0 wins last, matching the ref's clip-gather.
        target_edge = jnp.full(TILE, jnp.inf, jnp.float32)
        for b in range(n_bins - 1, -1, -1):
            target_edge = jnp.where(target == b, edges[b], target_edge)
        calm = t_eff <= target_edge - hyst
        streak_if_cooler = jnp.where(calm, streak + 1, 0)
        recover = cooler & (streak_if_cooler >= scal.hysteresis_steps)
        new_bin = jnp.where(hotter | recover, target, bin_idx)
        new_streak = jnp.where(cooler & ~recover, streak_if_cooler, 0)
        switched = (hotter | recover) & ~fused
        new_bin = jnp.where(fused, bin_idx, new_bin)
        new_streak = jnp.where(fused, streak, new_streak)
        eff_bin = jnp.where(fused, n_bins, new_bin)

        # --- score partials (mirrors trace_score_accumulate, chunk=1) ---
        occ = tuple(
            occ[b] + (eff_bin == b).astype(jnp.int32) for b in range(n_bins + 1)
        )
        sw = sw + switched.astype(jnp.int32)
        # Realized (2, 4) row per DIMM: select by effective bin (n_bins =
        # the JEDEC sentinel) and accumulate — same stored values, one f32
        # add per (step, slot), identical to the ref's per-step order.
        new_sums = []
        for j in range(ROW_SLOTS):
            row_j = jnp.full(TILE, jedec[j], jnp.float32)
            for b in range(n_bins - 1, -1, -1):
                row_j = jnp.where(eff_bin == b, rows[b * ROW_SLOTS + j], row_j)
            new_sums.append(sums[j] + row_j)
        return (new_bin, new_streak, fused.astype(jnp.int32), sw, occ,
                tuple(new_sums))

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_out[...] = state_ref[...]
        occ_out[...] = occ_ref[...]
        sw_out[...] = sw_ref[...]
        sums_out[...] = sums_ref[...]

    init = (
        state_out[0],
        state_out[1],
        state_out[2],
        sw_out[...],
        tuple(occ_out[b] for b in range(n_bins + 1)),
        tuple(sums_out[j] for j in range(ROW_SLOTS)),
    )
    bin_idx, streak, fused, sw, occ, sums = jax.lax.fori_loop(
        0, steps, one_step, init
    )
    state_out[0] = bin_idx
    state_out[1] = streak
    state_out[2] = fused
    for b in range(n_bins + 1):
        occ_out[b] = occ[b]
    sw_out[...] = sw
    for j in range(ROW_SLOTS):
        sums_out[j] = sums[j]


#: Steps per chunk-kernel block. The telemetry streams through VMEM
#: ``CHUNK_STEP_BLOCK`` steps at a time (grid axis 1) while the registers
#: and partials stay resident, so the kernel's VMEM use does not grow with
#: the chunk length.
CHUNK_STEP_BLOCK: int = 256


def _replay_blocks(state3, occ, sw, sums, stack, temps, errs, *,
                   steps: int, scal: ReplayScalars, interpret: bool):
    """One ``pallas_call`` over every DIMM tile and the first
    ``len(temps) // steps`` blocks of ``steps`` steps."""
    n_bins = len(scal.edges)
    rows_, lanes = sw.shape
    n_blocks = temps.shape[0] // steps

    def stacked_spec(n):
        return pl.BlockSpec((n, TILE[0], TILE[1]), lambda i, k: (0, i, 0))

    def step_spec(n):
        return pl.BlockSpec((n, TILE[0], TILE[1]), lambda i, k: (k, i, 0))

    flat_spec = pl.BlockSpec((TILE[0], TILE[1]), lambda i, k: (i, 0))
    return pl.pallas_call(
        functools.partial(_replay_chunk_kernel, steps=steps, scal=scal),
        grid=(rows_ // TILE[0], n_blocks),
        in_specs=[
            stacked_spec(3),
            stacked_spec(n_bins + 1),
            flat_spec,
            stacked_spec(ROW_SLOTS),
            stacked_spec(n_bins * ROW_SLOTS),
            step_spec(steps),
            step_spec(steps),
        ],
        out_specs=(
            stacked_spec(3),
            stacked_spec(n_bins + 1),
            flat_spec,
            stacked_spec(ROW_SLOTS),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((3, rows_, lanes), jnp.int32),
            jax.ShapeDtypeStruct((n_bins + 1, rows_, lanes), jnp.int32),
            jax.ShapeDtypeStruct((rows_, lanes), jnp.int32),
            jax.ShapeDtypeStruct((ROW_SLOTS, rows_, lanes), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="replay_chunk",
    )(state3, occ, sw, sums, stack, temps, errs)


def replay_chunk_tiled(
    state3: jax.Array,   # (3, R, 128) i32
    occ: jax.Array,      # (n_bins+1, R, 128) i32
    sw: jax.Array,       # (R, 128) i32
    sums: jax.Array,     # (ROW_SLOTS, R, 128) f32
    stack: jax.Array,    # (n_bins · ROW_SLOTS, R, 128) f32
    temps: jax.Array,    # (chunk, R, 128) f32
    errs: jax.Array,     # (chunk, R, 128) f32
    *,
    scal: ReplayScalars,
    interpret: bool = False,
):
    """Run the fused chunk scan over tiled DIMM operands.

    R % 8 == 0 (ops pads/reshapes the DIMM axis). Returns
    ``(state3, occ, sw, sums)`` with input shapes/dtypes. The whole
    :data:`CHUNK_STEP_BLOCK`-step blocks of the chunk run in one kernel
    launch; a remainder shorter than a block runs in a second launch that
    starts from the first one's registers and partials, so every step
    still runs once, in step order."""
    n_bins = len(scal.edges)
    rows_, lanes = sw.shape
    chunk = temps.shape[0]
    assert lanes == TILE[1] and rows_ % TILE[0] == 0, sw.shape
    assert state3.shape == (3, rows_, lanes), state3.shape
    assert occ.shape == (n_bins + 1, rows_, lanes), occ.shape
    assert sums.shape == (ROW_SLOTS, rows_, lanes), sums.shape
    assert stack.shape == (n_bins * ROW_SLOTS, rows_, lanes), stack.shape
    assert temps.shape == errs.shape == (chunk, rows_, lanes), temps.shape

    steps = min(chunk, CHUNK_STEP_BLOCK)
    out = _replay_blocks(state3, occ, sw, sums, stack, temps, errs,
                         steps=steps, scal=scal, interpret=interpret)
    rest = chunk % steps
    if rest:
        out = _replay_blocks(*out, stack, temps[chunk - rest:],
                             errs[chunk - rest:], steps=rest, scal=scal,
                             interpret=interpret)
    return out


#: Steps per accumulate-kernel block. The decision block streams through
#: VMEM ``ACC_STEP_BLOCK`` steps at a time (grid axis 1) while the running
#: partials stay resident, so the kernel's VMEM use does not grow with the
#: chunk length.
ACC_STEP_BLOCK: int = 32


def _step_block(chunk: int) -> int:
    """Largest divisor of ``chunk`` that is at most :data:`ACC_STEP_BLOCK`."""
    return max(d for d in range(1, min(chunk, ACC_STEP_BLOCK) + 1)
               if chunk % d == 0)


def _accumulate_kernel(
    bins_ref,    # (steps, 8, 128) i32 effective bins
    swd_ref,     # (steps, 8, 128) i32 switch flags
    tim_ref,     # (steps · ROW_SLOTS, 8, 128) f32 realized rows
    occ_ref,     # (n_bins1, 8, 128) i32 running occupancy
    sw_ref,      # (8, 128) i32 running switches
    sums_ref,    # (ROW_SLOTS, 8, 128) f32 running sums
    occ_out, sw_out, sums_out,
    *,
    steps: int,
    n_bins1: int,
):
    """Fused ``trace_score_accumulate`` over one step block of a
    materialized decision block, folding bins/switches/timings into the
    running partials. The outputs stay resident across the step-block
    grid axis and carry the partials from one block to the next, so every
    slot still takes one f32 add per step in step order. int accumulators
    are exact; the f32 timing sums match the ref's ``timings.sum(axis=0)``
    under the cycle-quantization envelope that already makes chunked
    accumulation exact (see ScorePartials)."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        occ_out[...] = occ_ref[...]
        sw_out[...] = sw_ref[...]
        sums_out[...] = sums_ref[...]

    def one_step(k, carry):
        sw, occ, sums = carry
        b = bins_ref[k]
        occ = tuple(
            occ[i] + (b == i).astype(jnp.int32) for i in range(n_bins1)
        )
        sw = sw + swd_ref[k]
        sums = tuple(
            sums[j] + tim_ref[k * ROW_SLOTS + j] for j in range(ROW_SLOTS)
        )
        return sw, occ, sums

    init = (
        sw_out[...],
        tuple(occ_out[i] for i in range(n_bins1)),
        tuple(sums_out[j] for j in range(ROW_SLOTS)),
    )
    sw, occ, sums = jax.lax.fori_loop(0, steps, one_step, init)
    for i in range(n_bins1):
        occ_out[i] = occ[i]
    sw_out[...] = sw
    for j in range(ROW_SLOTS):
        sums_out[j] = sums[j]


def accumulate_tiled(
    bins: jax.Array,    # (chunk, R, 128) i32
    swd: jax.Array,     # (chunk, R, 128) i32
    tim: jax.Array,     # (chunk · ROW_SLOTS, R, 128) f32
    occ: jax.Array,     # (n_bins1, R, 128) i32
    sw: jax.Array,      # (R, 128) i32
    sums: jax.Array,    # (ROW_SLOTS, R, 128) f32
    *,
    interpret: bool = False,
):
    """Fused partials accumulation over tiled decision blocks; returns
    ``(occ, sw, sums)`` with input shapes/dtypes. The grid walks DIMM
    tiles (axis 0) and, within each tile, step blocks in order (axis 1)."""
    chunk = bins.shape[0]
    n_bins1 = occ.shape[0]
    rows_, lanes = sw.shape
    assert lanes == TILE[1] and rows_ % TILE[0] == 0, sw.shape
    assert tim.shape == (chunk * ROW_SLOTS, rows_, lanes), tim.shape
    steps = _step_block(chunk)

    def stacked_spec(n):
        return pl.BlockSpec((n, TILE[0], TILE[1]), lambda i, k: (0, i, 0))

    def step_spec(n):
        return pl.BlockSpec((n, TILE[0], TILE[1]), lambda i, k: (k, i, 0))

    flat_spec = pl.BlockSpec((TILE[0], TILE[1]), lambda i, k: (i, 0))
    return pl.pallas_call(
        functools.partial(_accumulate_kernel, steps=steps, n_bins1=n_bins1),
        grid=(rows_ // TILE[0], chunk // steps),
        in_specs=[
            step_spec(steps),
            step_spec(steps),
            step_spec(steps * ROW_SLOTS),
            stacked_spec(n_bins1),
            flat_spec,
            stacked_spec(ROW_SLOTS),
        ],
        out_specs=(
            stacked_spec(n_bins1),
            flat_spec,
            stacked_spec(ROW_SLOTS),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_bins1, rows_, lanes), jnp.int32),
            jax.ShapeDtypeStruct((rows_, lanes), jnp.int32),
            jax.ShapeDtypeStruct((ROW_SLOTS, rows_, lanes), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(bins, swd, tim, occ, sw, sums)
