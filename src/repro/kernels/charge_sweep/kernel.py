"""Pallas TPU kernel: fused one-pass charge-sweep grid search.

The reference path (:mod:`.ref`) re-evaluates the FULL exponential charge
model — retention, charge sharing, restore target, sense time, equalizer
margin — at every candidate timing on the grid, for every search. But per
(cell, search) only ONE cheap exponential actually varies with the
candidate; everything else is a per-cell invariant. This kernel hoists
those invariants out of the grid loop (``dv0``, the restore target /
thresholds, the sense-latch time, every ``r·τ`` product — computed once in
:mod:`.ops` and streamed in as a stacked tile) and walks the shared timing
grid ONCE, evaluating all seven searches per candidate cycle and folding
the monotone ``ok_at`` predicate into a running first-True reduction — the
min-safe grid index is emitted directly, never a materialized
(grid × cells) pass/fail matrix. That is the ~10× FLOPs cut the ROADMAP
flagged: ~1 transcendental per (cell, candidate, search) instead of ~10.

Bit-exactness contract: per candidate the kernel evaluates the SAME
floating-point expression the forward predicates in
:mod:`repro.core.charge` evaluate — same operand order, same Python-scalar
constants folding at the same points, one fresh ``exp`` per candidate. A
multiplicative carry (``E_{k+1} = E_k · e^{Δt/τ}``, one MUL per candidate)
was deliberately rejected: its accumulated rounding (~n·ulp) can flip a
threshold comparison that the model's ``_EPS`` slack does not cover for a
cell landing near a grid threshold, and the parity gate demands bit-exact
min-safe indices against :mod:`.ref`. Hoisting is where the FLOPs win
lives anyway; the exp itself is a single VPU op.

Layout: cells (any (DIMM × temperature × pattern) tile, flattened by
:mod:`.ops`) ride the VPU lanes as (8, 128) f32 tiles; the grid walks cell
tiles; the timing grid is a ``fori_loop`` carrying 7 × (index, found)
running reductions in registers (found as int32 0/1: Mosaic refuses a
boolean vector loop carry). Inputs arrive as ONE stacked
(N_INVARIANTS, 8, 128) block per tile; outputs leave as one
(N_SEARCHES, 8, 128) int32 block of min-safe indices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.charge_sweep.ref import SEARCH_NAMES

#: Rows of the stacked invariant input, in order. The first block are the
#: per-cell model invariants; the ``m_*`` rows are the fixed-parameter
#: masks — the other three JEDEC-held parameters' pass/fail at this cell,
#: pre-ANDed per search (0.0 / 1.0).
INVARIANT_NAMES: Tuple[str, ...] = (
    "dv0_r",       # initial read bitline differential (full restore)
    "rts",         # r · τ_sa
    "t_sense_r",   # sense-latch time from dv0_r
    "thr_rest",    # restore-target threshold v_tgt · (1 − eps)
    "rtr",         # r · τ_restore
    "rtb",         # r · τ_bl
    "thr_trp",     # precharge residual threshold δ_ok · (1 + eps)
    "tau_wr",      # r · τ_write · drive_factor(T)
    "t_sense_w",   # sense-latch time from the write-assisted dv0
    "thr_trcd_w",  # min_trcd_write · (1 − eps)
    "thr_trp_w",   # min_trp_write · (1 − eps)
    "m_r_trcd", "m_r_tras", "m_r_trp",
    "m_w_trcd", "m_w_tras", "m_w_twr", "m_w_trp",
)
N_INVARIANTS: int = len(INVARIANT_NAMES)
N_SEARCHES: int = len(SEARCH_NAMES)

#: Cell-tile shape: 8 sublanes × 128 lanes (f32 VPU tile).
TILE: Tuple[int, int] = (8, 128)
CELLS_PER_TILE: int = TILE[0] * TILE[1]


@dataclasses.dataclass(frozen=True)
class SweepScalars:
    """Python-float model constants closed over by the kernel body.

    Each is computed from :class:`repro.core.charge.ChargeModelConstants`
    by the same Python expression the forward predicates fold at trace
    time, so the f32 value inside the kernel is bit-identical to the ref
    path's (see :func:`.ops.kernel_scalars`)."""

    tck: float
    ovh_rcd: float
    ovh_ras: float
    ovh_wr: float
    ovh_rp: float
    thr_sense: float          # v_sense_target · (1 − eps)
    one_minus_vrs: float      # 1 − v_restore_start
    v_half: float             # v_half_swing
    v_over: float             # v_overdrive
    v_over_minus_vrs: float   # v_overdrive − v_restore_start


def _charge_sweep_kernel(
    inv_ref, idx_ref, *, n_grid: Tuple[int, ...], scal: SweepScalars
):
    inv = [inv_ref[i] for i in range(N_INVARIANTS)]
    (dv0_r, rts, t_sense_r, thr_rest, rtr, rtb, thr_trp, tau_wr,
     t_sense_w, thr_trcd_w, thr_trp_w) = inv[:11]
    masks = [m > 0.5 for m in inv[11:]]
    max_n = max(n_grid)

    def candidate(k, carry):
        idxs, founds = carry
        # Candidate timing value: grid point k is (k + 1) cycles, exactly
        # like ref.param_grid's arange(1, n + 1) · tck (bit-identical f32).
        t = (k + 1).astype(jnp.float32) * scal.tck

        # r_trcd: sense-amp latch from dv0 (read_ok's sense_pass).
        dv = dv0_r * jnp.exp((t - scal.ovh_rcd) / rts)
        p_r_trcd = dv >= scal.thr_sense
        # r_tras: restore to the adaptive target (read_ok's restore_pass).
        ta_r = t - scal.ovh_ras - t_sense_r
        v_reached = 1.0 - scal.one_minus_vrs * jnp.exp(
            -jnp.maximum(ta_r, 0.0) / rtr
        )
        p_r_tras = v_reached >= thr_rest
        # r_trp: bitline equalization (read_ok's prech_pass).
        delta = scal.v_half * jnp.exp(-(t - scal.ovh_rp) / rtb)
        p_r_trp = delta <= thr_trp
        # w_trcd / w_trp: write-assisted thresholds (write_ok compares the
        # candidate against the hoisted min_t*_write directly).
        p_w_trcd = t >= thr_trcd_w
        p_w_trp = t >= thr_trp_w
        # w_tras: row restore under write drive (write_ok's tras_pass).
        ta_w = t - scal.ovh_ras - t_sense_w
        v_row = scal.v_over - scal.v_over_minus_vrs * jnp.exp(
            -jnp.maximum(ta_w, 0.0) / tau_wr
        )
        p_w_tras = v_row >= thr_rest
        # w_twr: write recovery from the opposite rail (write_pass).
        v_wr = scal.v_over * (1.0 - jnp.exp(-(t - scal.ovh_wr) / tau_wr))
        p_w_twr = v_wr >= thr_rest

        passes = (p_r_trcd, p_r_tras, p_r_trp, p_w_trcd, p_w_tras,
                  p_w_twr, p_w_trp)
        new_idxs, new_founds = [], []
        for j in range(N_SEARCHES):
            ok = passes[j] & masks[j] & (k < n_grid[j])
            found = founds[j] > 0
            new_idxs.append(jnp.where(ok & ~found, k, idxs[j]))
            new_founds.append((found | ok).astype(jnp.int32))
        return tuple(new_idxs), tuple(new_founds)

    init = (
        # All-False searches keep the last grid index — the JEDEC pin.
        tuple(jnp.full(TILE, n - 1, jnp.int32) for n in n_grid),
        tuple(jnp.zeros(TILE, jnp.int32) for _ in n_grid),
    )
    idxs, _ = jax.lax.fori_loop(0, max_n, candidate, init)
    for j in range(N_SEARCHES):
        idx_ref[j] = idxs[j]


def charge_sweep_tiled(
    inv: jax.Array,
    *,
    n_grid: Tuple[int, ...],
    scal: SweepScalars,
    interpret: bool = False,
) -> jax.Array:
    """Run the fused sweep over stacked invariants.

    ``inv``: (N_INVARIANTS, R, 128) f32 with R % 8 == 0 (ops pads/reshapes
    the flattened cell axis). Returns (N_SEARCHES, R, 128) int32 min-safe
    grid indices in ``SEARCH_NAMES`` order."""
    n_inv, rows, lanes = inv.shape
    assert n_inv == N_INVARIANTS and lanes == TILE[1] and rows % TILE[0] == 0, (
        inv.shape
    )
    assert len(n_grid) == N_SEARCHES
    return pl.pallas_call(
        functools.partial(_charge_sweep_kernel, n_grid=n_grid, scal=scal),
        grid=(rows // TILE[0],),
        in_specs=[
            pl.BlockSpec((N_INVARIANTS, TILE[0], TILE[1]), lambda i: (0, i, 0))
        ],
        out_specs=pl.BlockSpec((N_SEARCHES, TILE[0], TILE[1]), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N_SEARCHES, rows, lanes), jnp.int32),
        interpret=interpret,
    )(inv)
