"""AL-DRAM memory-controller mechanism (paper §1.4).

AL-DRAM requires *no DRAM chip or interface changes* — only that the memory
controller store multiple pre-validated timing sets per DIMM and select
among them by the current operating temperature. The paper's controller
keeps **per-access-type register sets**: read accesses are bound by
tRCD/tRAS/tRP and write accesses by tRCD/tWR/tRP, the two modes are
profiled by different tests (Fig. 2a vs 2b), and the reported 55 °C
reductions (27/32/33/18 % for tRCD/tRAS/tWR/tRP) assume each access type
runs at *its own* profiled margin. Collapsing the two sets into one merged
register file forfeits exactly the margin the slower mode doesn't have —
historically this pipeline merged with write-mode tRAS pinned at JEDEC, so
programmed tables never reduced tRAS at all (the "tRAS-at-JEDEC merge
bug"). This module is that controller, split sets and all, in
struct-of-arrays form:

* :class:`DimmTimingTable` — the controller's timing registers: one
  ``(n_dimms, n_bins, 2, 4)`` timing stack (access-type axis ordered as
  :data:`repro.core.timing.ACCESS_TYPES` = read, write) — or, for
  region-profiled DIMMs (design-induced variation), a rank-5
  ``(n_dimms, n_bins, n_regions, 2, 4)`` stack whose region axis orders
  distance-from-sense-amp classes nearest → farthest — plus the bin
  edges and an optional temperature-driven
  :class:`repro.core.refresh.RefreshPolicy` (so bin selection sees the
  refresh cost of running hot, not just the slower timings), built
  directly from a :class:`repro.core.fleet.SweepResult` or
  :class:`repro.core.fleet.RegionSweepResult` (no per-DIMM Python
  object plumbing) and persisted with a schema version (v5; v1–v4
  files still load — v1/v2 merged sets duplicated into both slots,
  pre-v4 refresh policy absent, pre-v5 region axis broadcast).
* The **pure state machine**: controller state is a
  :class:`ControllerState` pytree (``bin_idx`` / ``cool_streak`` /
  ``fused`` arrays over the DIMM axis) advanced by :func:`step` — one
  per-DIMM transition ``vmap``-ped across the fleet — and replayed over
  whole temperature traces by :func:`replay`, a single jitted
  ``lax.scan`` covering n_dimms × n_steps with per-step error-injection
  masks driving the fuse.
* :class:`ALDRAMController` — a thin stateful wrapper over the same
  transition (via :func:`repro.core.binning.advance_bin`) with the
  original per-observation API: thermal guard band, hysteresis (the paper
  measured server DRAM drifting <0.1 °C/s and never above 34 °C, so
  infrequent conservative switching is safe), and an error fuse that
  drops a DIMM back to JEDEC timings permanently (the reliability
  fallback).

The same select-with-fallback state machine is reused by the TPU
embodiment (:mod:`repro.core.altune.runtime`) through the shared scalar
kernel in :mod:`repro.core.binning`; :func:`replay` is property-tested
bit-exact against the wrapper's observe loop (tests/test_replay.py).
Because every per-DIMM register is one column of a struct-of-arrays
pytree, :func:`replay` also runs distributed: pass ``mesh=`` to shard the
DIMM axis over a device mesh (:mod:`repro.core.shard`) — state, table
stack and replay outputs stay partitioned, and results remain bit-exact
vs the single-device scan. For streams longer than device memory,
:func:`replay_stream` (:mod:`repro.core.stream`) runs the SAME transition
kernel in chunked scans that carry only the state pytree plus running
score partials — final state, switch counts and score stay bit-exact vs
:func:`replay` for every chunking.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core import charge, shard
from repro.core.binning import advance_bin, bin_index
from repro.core.charge import CellParams, ChargeModelConstants, DEFAULT_CONSTANTS
from repro.core.refresh import BinRefresh, RefreshPolicy, bin_refresh as _bin_refresh
from repro.core.timing import (
    ACCESS_TYPES,
    AccessTimings,
    JEDEC_ACCESS,
    JEDEC_DDR3_1600,
    PARAM_NAMES,
    TimingParams,
)

#: Temperature bins (°C upper edges) for which timing sets are profiled.
#: 85 °C is the standard's qualification point; the paper evaluates 55 °C.
DEFAULT_TEMP_BINS: Tuple[float, ...] = (45.0, 55.0, 65.0, 75.0, 85.0)

#: Guard band added to the measured temperature before bin selection: the
#: controller always assumes the DIMM is slightly hotter than measured.
GUARD_BAND_C: float = 5.0

#: Hysteresis: switch to a *faster* (cooler) bin only after the temperature
#: has stayed below the bin edge minus this margin for `HYSTERESIS_STEPS`
#: consecutive observations. Switching to a slower bin is immediate.
HYSTERESIS_C: float = 2.0
HYSTERESIS_STEPS: int = 3

#: Persisted-table format version. v1 (PR 1, implicit) stored nested
#: per-DIMM lists of timing dicts; v2 stored a single merged
#: ``(n_dimms, n_bins, 4)`` stack; v3 stores the per-access-type
#: ``(n_dimms, n_bins, 2, 4)`` stack; v4 adds the optional temperature
#: → refresh-rate policy (``"refresh"``, nullable); v5 adds the region
#: axis — ``"stack"`` is always region-explicit ``(n_dimms, n_bins,
#: n_regions, 2, 4)`` with an ``"n_regions"`` field. ``from_json`` loads
#: all five — v1/v2 merged sets are duplicated into both access slots,
#: pre-v4 files load with no refresh policy, and pre-v5 files (plus v5
#: files with ``n_regions == 1``) load REGION-BROADCAST: the in-memory
#: stack is the canonical rank-4 form, bitwise equal to a v1–v4 load of
#: the same timings.
TABLE_SCHEMA_VERSION: int = 5

_JEDEC_ROW = np.asarray(
    [getattr(JEDEC_DDR3_1600, p) for p in PARAM_NAMES], np.float32
)
#: JEDEC duplicated over the access-type axis: the (2, 4) sentinel row the
#: state machine selects beyond the last bin or after a fuse.
_JEDEC_ROWS = np.broadcast_to(_JEDEC_ROW, (len(ACCESS_TYPES), 4)).copy()


@dataclasses.dataclass(eq=False)
class DimmTimingTable:
    """Per-DIMM, per-access-type timing sets, one per temperature bin,
    array-backed.

    ``stack[dimm, bin]`` is a ``(2, 4)`` block: the read and the write
    timing set (ns, cycle-quantized; axes ordered as ``ACCESS_TYPES`` ×
    ``PARAM_NAMES``). Temperatures above the last bin edge select JEDEC
    for both access types — the beyond-last sentinel rows, not stored.

    Region-profiled tables (schema v5) carry a rank-5 ``(n_dimms,
    n_bins, n_regions, 2, 4)`` stack instead: ``stack[dimm, bin,
    region]`` is that distance-from-sense-amp class's own profiled
    ``(2, 4)`` block, ordered nearest (fastest) → farthest (slowest,
    the per-DIMM worst case). The rank-4 form is CANONICAL for
    ``n_regions == 1``: a one-region rank-5 stack is squeezed at
    construction, so a v5 file with ``n_regions == 1`` loads bitwise
    equal to the same timings persisted as v1–v4. Consumers that need a
    single per-(DIMM, bin) register view of a region table use
    :meth:`oblivious_stack` (max over regions — safe for every region);
    region-resolved lookups go through :meth:`region_stack`.

    A negative entry is the profiler's *untested* sentinel and is refused
    at construction: a table must never program a timing that was not
    actually validated (the guard that makes the old silent
    tRAS-at-JEDEC write profile impossible to reintroduce).

    ``refresh`` — optional temperature-driven
    :class:`repro.core.refresh.RefreshPolicy` (schema v4): the DDR3
    1×/2× extended-temperature staircase (or a pluggable 4× variant)
    this table's DIMMs refresh under. Tables without one (``None``,
    the pre-v4 default) score latency-only."""

    temp_bins: Tuple[float, ...]
    #: (n_dimms, n_bins, 2, 4) float32 ns — or (n_dimms, n_bins,
    #: n_regions, 2, 4) for region-profiled tables (n_regions >= 2; a
    #: one-region rank-5 stack is squeezed to the canonical rank-4 form).
    stack: np.ndarray
    refresh: Optional[RefreshPolicy] = None

    def __post_init__(self) -> None:
        if self.refresh is not None and not isinstance(self.refresh, RefreshPolicy):
            raise TypeError(
                f"refresh must be a RefreshPolicy or None, got "
                f"{type(self.refresh).__name__}"
            )
        self.stack = np.asarray(self.stack, np.float32)
        if self.stack.ndim == 5 and self.stack.shape[2] == 1:
            # Canonical form: one region IS the region-free table.
            self.stack = self.stack[:, :, 0]
        tail = (len(ACCESS_TYPES), len(PARAM_NAMES))
        ok = (
            self.stack.ndim == 4
            and self.stack.shape[1:] == (len(self.temp_bins),) + tail
        ) or (
            self.stack.ndim == 5
            and self.stack.shape[1:2] == (len(self.temp_bins),)
            and self.stack.shape[2] >= 2
            and self.stack.shape[3:] == tail
        )
        if not ok:
            raise ValueError(
                f"stack shape {self.stack.shape} does not match "
                f"{len(self.temp_bins)} bins × [n_regions ×] "
                f"{len(ACCESS_TYPES)} access types × {len(PARAM_NAMES)} "
                f"params"
            )
        if bool((self.stack < 0.0).any()):
            raise ValueError(
                "timing stack contains negative entries (the profiler's "
                "untested sentinel): refusing to program untested timings"
            )

    # -- shape ------------------------------------------------------------
    @property
    def n_dimms(self) -> int:
        return int(self.stack.shape[0])

    @property
    def n_bins(self) -> int:
        return len(self.temp_bins)

    @property
    def n_regions(self) -> int:
        """Distance-from-sense-amp classes per DIMM (1 for rank-4 tables)."""
        return int(self.stack.shape[2]) if self.stack.ndim == 5 else 1

    def region_stack(self) -> np.ndarray:
        """Region-explicit ``(n_dimms, n_bins, n_regions, 2, 4)`` view —
        rank-4 tables gain a length-1 region axis (no copy)."""
        if self.stack.ndim == 5:
            return self.stack
        return self.stack[:, :, None]

    def oblivious_stack(self) -> np.ndarray:
        """Region-OBLIVIOUS ``(n_dimms, n_bins, 2, 4)`` registers: the max
        over regions per (bin, access, param) — the only single set safe
        for every region, i.e. what a controller without region-resolved
        scheduling must program. Identical to :attr:`stack` for rank-4
        tables (each region's profiled minima are upper-bounded by the
        farthest region, which anchors the region-free profile)."""
        if self.stack.ndim == 5:
            return self.stack.max(axis=2)
        return self.stack

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, DimmTimingTable)
            and self.temp_bins == other.temp_bins
            and self.refresh == other.refresh
            and np.array_equal(self.stack, other.stack)
        )

    def bin_refresh(self) -> Optional[BinRefresh]:
        """Per-effective-bin refresh load under this table's policy — the
        ``refresh=`` argument of the :func:`repro.core.perfmodel.trace_score`
        family. ``None`` (no policy) means latency-only scoring."""
        if self.refresh is None:
            return None
        return _bin_refresh(self.refresh, self.temp_bins)

    # -- construction -----------------------------------------------------
    @classmethod
    def profile(
        cls,
        cells: CellParams,
        temp_bins: Sequence[float] = DEFAULT_TEMP_BINS,
        window_s: float = charge.REFRESH_WINDOW_S,
        consts: ChargeModelConstants = DEFAULT_CONSTANTS,
        refresh: Optional[RefreshPolicy] = None,
        n_regions: int = 1,
    ) -> "DimmTimingTable":
        """Boot-time profiling: minimal safe timings per DIMM per bin.

        Runs the fleet engine once over all bins (a single jitted
        (DIMM × temperature) sweep at the worst-case data pattern) and
        programs one read set and one write set per bin — each access type
        at its own profiled margin (the paper's per-access-type register
        sets), never the elementwise merge. ``refresh`` records the
        temperature-driven refresh policy the DIMMs run under (v4 tables;
        scoring then reports combined latency+refresh figures).
        ``n_regions > 1`` profiles each distance-from-sense-amp class
        separately (one region-tiled sweep) and builds a rank-5 v5 table;
        ``n_regions=1`` is the legacy region-free profile, bitwise."""
        from repro.core import fleet as fleet_mod

        if n_regions == 1:
            result = fleet_mod.sweep(
                cells, temps_c=tuple(temp_bins), patterns=(1.0,),
                window_s=window_s, consts=consts,
            )
        else:
            result = fleet_mod.sweep_regions(
                cells, temps_c=tuple(temp_bins), patterns=(1.0,),
                n_regions=n_regions, window_s=window_s, consts=consts,
            )
        return cls.from_fleet(result, temp_bins=temp_bins, refresh=refresh)

    @classmethod
    def from_fleet(
        cls,
        result,
        temp_bins: Optional[Sequence[float]] = None,
        refresh: Optional[RefreshPolicy] = None,
    ) -> "DimmTimingTable":
        """Build the stacked per-(DIMM, temperature-bin, access-type) table
        straight from a :class:`repro.core.fleet.SweepResult` — no
        re-profiling, no Python list plumbing: the sweep's ``(T, N, 2, 4)``
        stacked sets are transposed into the controller's ``(N, T, 2, 4)``
        registers in one device-to-host transfer. A
        :class:`repro.core.fleet.RegionSweepResult` (rank-5 ``(T, R, N,
        2, 4)`` stacked sets) lands the same way in ``(N, T, R, 2, 4)``
        registers — a v5 region table (one region squeezes to rank-4).

        The sweep's temperature grid becomes the bin edges; each (bin,
        access) entry is that access type's profiled requirement at the
        worst-case pattern. Pass ``temp_bins`` to override the sweep's
        record of them; by default the sweep's exact caller-provided
        temperatures are used (never the float32 grid, which would perturb
        edges like 40.1 and make ``lookup`` at that exact temperature miss
        its own bin)."""
        if temp_bins is None:
            temp_bins = result.bin_edges()
        else:
            temp_bins = tuple(float(t) for t in temp_bins)
            if len(temp_bins) != result.read.shape[0]:
                raise ValueError(
                    f"{len(temp_bins)} temp_bins for a "
                    f"{result.read.shape[0]}-temperature sweep"
                )
        stacked = np.asarray(result.stacked_timings(), np.float32)
        if stacked.ndim == 5:  # region sweep: (T, R, N, 2, 4) → (N, T, R, 2, 4)
            stack = stacked.transpose(2, 0, 1, 3, 4)
        else:  # (T, N, 2, 4) → (N, T, 2, 4)
            stack = stacked.transpose(1, 0, 2, 3)
        return cls(temp_bins=temp_bins, stack=stack, refresh=refresh)

    @classmethod
    def from_sets(
        cls,
        temp_bins: Sequence[float],
        sets: Sequence[Sequence[TimingParams | AccessTimings]],
    ) -> "DimmTimingTable":
        """Build from nested per-DIMM timing-set lists. Plain
        :class:`TimingParams` entries (the v1 merged layout) are duplicated
        into both access slots; :class:`AccessTimings` entries keep their
        split sets."""
        def block(entry: TimingParams | AccessTimings):
            if isinstance(entry, TimingParams):
                entry = AccessTimings.merged(entry)
            return [[getattr(t, p) for p in PARAM_NAMES] for t in entry]

        stack = np.asarray(
            [[block(t) for t in per_dimm] for per_dimm in sets], np.float32
        )
        return cls(temp_bins=tuple(float(t) for t in temp_bins), stack=stack)

    # -- access -----------------------------------------------------------
    def row(
        self, dimm: int, bin_idx: int, region: Optional[int] = None
    ) -> AccessTimings:
        """Read + write timing sets at ``(dimm, bin)``; the beyond-last
        sentinel (``bin_idx >= n_bins``) is JEDEC for both access types.
        ``region`` selects one distance class of a region table
        (``region=None`` on a rank-5 table returns the region-oblivious
        max — the set a region-unaware scheduler must program)."""
        if bin_idx >= self.n_bins:
            return JEDEC_ACCESS
        if region is None:
            block = self.oblivious_stack()[dimm, bin_idx]
        else:
            if not 0 <= region < self.n_regions:
                raise IndexError(
                    f"region {region} out of range for a "
                    f"{self.n_regions}-region table"
                )
            block = self.region_stack()[dimm, bin_idx, region]
        return AccessTimings(
            read=TimingParams(*(float(v) for v in block[0])),
            write=TimingParams(*(float(v) for v in block[1])),
        )

    @property
    def sets(self) -> List[List[AccessTimings]]:
        """Nested-list view ``sets[dimm][bin]`` (compatibility shim for
        per-DIMM consumers; the storage is :attr:`stack`). Region tables
        present the region-oblivious view."""
        return [
            [
                AccessTimings(
                    read=TimingParams(*(float(v) for v in block[0])),
                    write=TimingParams(*(float(v) for v in block[1])),
                )
                for block in per_dimm
            ]
            for per_dimm in self.oblivious_stack()
        ]

    def lookup(self, dimm: int, temp_c: float) -> AccessTimings:
        """Timing sets for the smallest bin covering ``temp_c``
        (guard-banded by the caller); above the last bin → JEDEC."""
        return self.row(dimm, bin_index(self.temp_bins, temp_c))

    # -- persistence (the controller's "timing registers" survive reboot) --
    def to_json(self) -> str:
        refresh = None
        if self.refresh is not None:
            refresh = {
                "boundaries": list(self.refresh.boundaries),
                "multipliers": list(self.refresh.multipliers),
                "trefi_base_ns": self.refresh.trefi_base_ns,
                "trfc_ns": self.refresh.trfc_ns,
            }
        return json.dumps(
            {
                "schema_version": TABLE_SCHEMA_VERSION,
                "params": list(PARAM_NAMES),
                "access_types": list(ACCESS_TYPES),
                "temp_bins": list(self.temp_bins),
                "n_regions": self.n_regions,
                # v5 files are always region-explicit (N, B, R, 2, 4);
                # one-region stacks round-trip back to canonical rank-4.
                "stack": self.region_stack().tolist(),
                "refresh": refresh,
            }
        )

    @classmethod
    def from_json(cls, text: str) -> "DimmTimingTable":
        obj = json.loads(text)
        version = obj.get("schema_version", 1)
        if version == 1:
            # PR-1 layout: nested per-DIMM lists of merged timing dicts,
            # duplicated into both access slots by from_sets.
            return cls.from_sets(
                obj["temp_bins"],
                [[TimingParams(**d) for d in per_dimm] for per_dimm in obj["sets"]],
            )
        if version in (2, 3, 4, 5):
            if obj.get("params", list(PARAM_NAMES)) != list(PARAM_NAMES):
                raise ValueError(
                    f"persisted parameter order {obj['params']} does not "
                    f"match {list(PARAM_NAMES)}"
                )
        if version == 2:
            # PR-2 layout: one merged (N, B, 4) stack → duplicate over the
            # access axis (the merge is safe for both types, just slower).
            merged = np.asarray(obj["stack"], np.float32)
            return cls(
                temp_bins=tuple(obj["temp_bins"]),
                stack=np.repeat(merged[:, :, None, :], len(ACCESS_TYPES), axis=2),
            )
        if version in (3, 4, 5):
            if obj.get("access_types", list(ACCESS_TYPES)) != list(ACCESS_TYPES):
                raise ValueError(
                    f"persisted access-type order {obj['access_types']} does "
                    f"not match {list(ACCESS_TYPES)}"
                )
            refresh = None
            if version >= 4 and obj.get("refresh") is not None:
                r = obj["refresh"]
                refresh = RefreshPolicy(
                    boundaries=tuple(float(b) for b in r["boundaries"]),
                    multipliers=tuple(float(m) for m in r["multipliers"]),
                    trefi_base_ns=float(r["trefi_base_ns"]),
                    trfc_ns=float(r["trfc_ns"]),
                )
            stack = np.asarray(obj["stack"], np.float32)
            if version == 5:
                n_regions = int(obj.get("n_regions", 1))
                if stack.ndim != 5 or stack.shape[2] != n_regions:
                    raise ValueError(
                        f"v5 stack shape {stack.shape} does not carry the "
                        f"declared n_regions={n_regions} region axis"
                    )
                # __post_init__ squeezes n_regions == 1 to the canonical
                # rank-4 form — bitwise equal to the v1–v4 load path.
            return cls(
                temp_bins=tuple(obj["temp_bins"]),
                stack=stack,
                refresh=refresh,
            )
        raise ValueError(f"unknown DimmTimingTable schema_version {version!r}")


# ---------------------------------------------------------------------------
# Pure scan state machine
# ---------------------------------------------------------------------------
class ControllerParams(NamedTuple):
    """Static policy of the runtime selector (a pytree of scalars)."""

    guard_band_c: float = GUARD_BAND_C
    hysteresis_c: float = HYSTERESIS_C
    hysteresis_steps: int = HYSTERESIS_STEPS


class ControllerState(NamedTuple):
    """Per-DIMM controller registers, struct-of-arrays (a jax pytree).

    ``bin_idx`` may hold the beyond-last sentinel ``n_bins`` (JEDEC) after
    an above-last-bin excursion; ``fused`` DIMMs are frozen at JEDEC
    forever (the reliability fallback)."""

    bin_idx: Array      # (..., ) int32
    cool_streak: Array  # (..., ) int32
    fused: Array        # (..., ) bool


@functools.partial(jax.jit, static_argnames=("n_dimms", "n_bins"))
def init_state(n_dimms: int, n_bins: int) -> ControllerState:
    """Boot state: every DIMM in the most conservative *profiled* bin.

    Jitted (both args static) so steady-state callers — e.g. a
    ``replay_stream`` loop inside a ``jax.transfer_guard("disallow")``
    scope — materialize the constants from the compile cache instead of
    an implicit host→device transfer per call."""
    return ControllerState(
        bin_idx=jnp.full((n_dimms,), n_bins - 1, jnp.int32),
        cool_streak=jnp.zeros((n_dimms,), jnp.int32),
        fused=jnp.zeros((n_dimms,), bool),
    )


def _advance_dimm(
    edges: Array,       # (B,)
    params: ControllerParams,
    rows: Array,        # (B, 2, 4) this DIMM's per-access timing registers
    bin_idx: Array,     # () int32
    streak: Array,      # () int32
    fused: Array,       # () bool
    temp_c: Array,      # () float32
    error: Array,       # () bool
):
    """One DIMM, one observation — the array mirror of
    :func:`repro.core.binning.advance_bin` plus the error fuse. Scalar in,
    scalar out; :func:`step` vmaps it over the fleet."""
    n_bins = edges.shape[0]
    fused = jnp.logical_or(fused, error)
    t_eff = temp_c + params.guard_band_c
    target = jnp.searchsorted(edges, t_eff, side="left").astype(jnp.int32)
    hotter = target > bin_idx
    cooler = target < bin_idx
    target_edge = jnp.where(
        target < n_bins, edges[jnp.clip(target, 0, n_bins - 1)], jnp.inf
    )
    calm = t_eff <= target_edge - params.hysteresis_c
    streak_if_cooler = jnp.where(calm, streak + 1, 0)
    recover = cooler & (streak_if_cooler >= params.hysteresis_steps)
    new_bin = jnp.where(hotter | recover, target, bin_idx)
    new_streak = jnp.where(cooler & ~recover, streak_if_cooler, 0)
    switched = (hotter | recover) & ~fused
    # A fused DIMM's registers are frozen (the wrapper early-returns).
    new_bin = jnp.where(fused, bin_idx, new_bin)
    new_streak = jnp.where(fused, streak, new_streak)
    # Effective selected rows (read + write sets): n_bins = JEDEC sentinel.
    eff_bin = jnp.where(fused, n_bins, new_bin).astype(jnp.int32)
    row = jnp.where(
        eff_bin >= n_bins,
        jnp.asarray(_JEDEC_ROWS),
        rows[jnp.clip(new_bin, 0, n_bins - 1)],
    )
    return new_bin, new_streak, fused, row, switched, eff_bin


def step(
    stack: Array,
    edges: Array,
    params: ControllerParams,
    state: ControllerState,
    temps_c: Array,
    errors: Optional[Array] = None,
    impl: str = "ref",
    interpret: Optional[bool] = None,
) -> Tuple[ControllerState, Array, Array, Array]:
    """Advance the whole fleet one observation (pure; jit/scan-safe).

    ``temps_c``/``errors`` are ``(n_dimms,)``; errors fuse *before* the
    temperature is considered, exactly like ``report_error`` followed by
    ``observe``. Returns ``(state, timing_rows (n_dimms, 2, 4),
    switched (n_dimms,), effective_bin (n_dimms,))`` — the timing rows
    carry both access-type sets (read = 0, write = 1).

    ``impl="pallas"`` runs the fused replay-step kernel for one chunk-1
    launch (bit-exact vs the ref; requires concrete ``edges``/``params``
    since the policy bakes into the kernel — don't select it inside an
    outer jit trace). ``interpret=None`` auto-enables interpret mode
    off-TPU."""
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be one of ('ref', 'pallas'), got {impl!r}")
    if impl == "pallas":
        from repro.kernels.replay_step import ops as replay_ops

        return replay_ops.step_pallas(
            stack, edges, params, state, temps_c, errors, interpret
        )
    if errors is None:
        errors = jnp.zeros(temps_c.shape, bool)
    new_bin, new_streak, fused, rows, switched, eff = jax.vmap(
        _advance_dimm, in_axes=(None, None, 0, 0, 0, 0, 0, 0)
    )(edges, params, stack, state.bin_idx, state.cool_streak, state.fused,
      temps_c, errors)
    return ControllerState(new_bin, new_streak, fused), rows, switched, eff


class ReplayResult(NamedTuple):
    """Dense output of a trace replay (all arrays over (n_steps, n_dimms))."""

    timings: Array      # (S, N, 2, 4) realized per-access timing rows, ns
    bin_idx: Array      # (S, N) int32 effective row (n_bins = JEDEC sentinel)
    switched: Array     # (S, N) bool
    fused: Array        # (S, N) bool (post-step fuse state)
    state: ControllerState  # final registers

    @property
    def switch_counts(self) -> Array:
        """(N,) per-DIMM timing-set switches over the trace."""
        return self.switched.sum(axis=0)

    @property
    def total_switches(self) -> int:
        return int(self.switched.sum())


@jax.jit
def _replay_scan(
    stack: Array,
    edges: Array,
    params: ControllerParams,
    state: ControllerState,
    traces: Array,
    errors: Array,
):
    def body(st: ControllerState, xs):
        temps, errs = xs
        st, rows, switched, eff = step(stack, edges, params, st, temps, errs)
        return st, (rows, switched, eff, st.fused)

    final, (rows, switched, eff, fused) = jax.lax.scan(body, state, (traces, errors))
    return final, rows, switched, eff, fused


def replay(
    table: DimmTimingTable,
    traces: Array,
    errors: Optional[Array] = None,
    params: ControllerParams = ControllerParams(),
    state: Optional[ControllerState] = None,
    mesh=None,
    impl: str = "ref",
) -> ReplayResult:
    """Replay whole temperature traces through the controller in ONE
    jitted ``lax.scan`` — n_dimms × n_steps transitions, no Python loop.

    Array contract:

    * ``traces`` — ``(n_steps, n_dimms)`` °C observations.
    * ``errors`` — optional same-shaped bool mask of per-step error
      injections (each fuses its DIMM to JEDEC from that step on).
    * ``state`` — optional starting :class:`ControllerState` (leaves
      ``(n_dimms,)``); defaults to the boot state (most conservative
      profiled bin).
    * Result stacks: ``timings`` is ``(n_steps, n_dimms, 2, 4)`` realized
      per-access rows, ``bin_idx`` / ``switched`` / ``fused`` are
      ``(n_steps, n_dimms)``.

    Bit-exact with feeding the same observations to
    :meth:`ALDRAMController.observe` one at a time.

    ``mesh`` — optional 1-D device mesh carrying the ``"dimm"`` axis
    (:func:`repro.core.shard.fleet_mesh`). The table stack, the
    ``ControllerState`` pytree, the trace/error columns and the
    ``(S, N, 2, 4)`` replay timings all live distributed over the DIMM
    axis; each device scans its contiguous block of DIMMs with the same
    jitted scan, padding (edge replication) + output slicing handle
    non-divisible fleet sizes. Sharded replays are BIT-EXACT vs
    ``mesh=None`` (property-tested in tests/test_shard.py).

    ``impl`` — only ``"ref"`` is meaningful here: this function's whole
    point is the dense ``(n_steps, n_dimms, 2, 4)`` history, which is
    exactly what the fused kernel exists to avoid materializing. The
    kwarg is validated for a uniform replay-path API and raises with a
    pointer at :func:`replay_stream` (whose ``impl="pallas"`` is the
    fused path)."""
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be one of ('ref', 'pallas'), got {impl!r}")
    if impl == "pallas":
        raise ValueError(
            "replay(impl='pallas') is not supported: the dense per-step "
            "timing history this function returns is what the fused "
            "replay-step kernel exists to avoid materializing — use "
            "replay_stream(impl='pallas') (final state + score partials, "
            "bit-exact) instead"
        )
    traces = jnp.asarray(traces, jnp.float32)
    if traces.ndim != 2:
        raise ValueError(f"traces must be (n_steps, n_dimms), got {traces.shape}")
    if traces.shape[1] != table.n_dimms:
        raise ValueError(
            f"trace has {traces.shape[1]} DIMMs, table has {table.n_dimms}"
        )
    if errors is None:
        errors = jnp.zeros(traces.shape, bool)
    else:
        errors = jnp.asarray(errors, bool)
        if errors.shape != traces.shape:
            raise ValueError(
                f"errors shape {errors.shape} != traces shape {traces.shape}"
            )
    if state is None:
        state = init_state(table.n_dimms, table.n_bins)
    # Region tables replay on the region-OBLIVIOUS registers: bin dynamics
    # depend only on temperature, and the dense (S, N, 2, 4) row history
    # cannot carry a region axis. Region-resolved timings are recovered at
    # scoring time from the effective-bin history (`bin_idx`) + the trace's
    # per-step region-access mix (repro.core.perfmodel.region_trace_score).
    args = (
        jnp.asarray(table.oblivious_stack()),
        jnp.asarray(table.temp_bins, jnp.float32),
        ControllerParams(*(jnp.asarray(p) for p in params)),
        state,
        traces,
        errors,
    )
    if mesh is None:
        final, rows, switched, eff, fused = _replay_scan(*args)
    else:
        run = _sharded_replay_runner(mesh, table.n_dimms)
        final, rows, switched, eff, fused = run(*args)
    return ReplayResult(rows, eff, switched, fused, final)


def replay_stream(table, traces, errors=None, params=ControllerParams(),
                  state=None, chunk_steps=None, mesh=None, impl=None,
                  interpret=None):
    """Streamed (chunked-scan) replay: same state machine, O(n_dimms ·
    chunk) device memory, no materialized history. Lazy delegate to
    :func:`repro.core.stream.replay_stream` (stream imports this module,
    so the import cannot be top-level); see there for the full contract —
    final state, switch counts and score are bit-exact vs :func:`replay`
    + ``trace_score`` for every chunking, and the chunk scan is chosen by
    platform (the fused replay-step kernel on TPU, also bit-exact; the
    ref elsewhere) unless ``impl`` names one."""
    from repro.core import stream as _stream

    kwargs = {} if chunk_steps is None else {"chunk_steps": chunk_steps}
    return _stream.replay_stream(
        table, traces, errors=errors, params=params, state=state,
        mesh=mesh, impl=impl, interpret=interpret, **kwargs,
    )


@functools.lru_cache(maxsize=32)
def _sharded_replay_runner(mesh, n_dimms: int):
    """Cached (pad → shard_map → slice) wrapper around the replay scan:
    repeated sharded replays of the same (mesh, fleet size) hit the jit
    cache instead of re-tracing the scan."""
    return shard.sharded_dimm_map(
        _replay_scan, mesh,
        in_axes=(0, None, None, 0, 1, 1),
        out_axes=(0, 1, 1, 1, 1),
        n_dimms=n_dimms,
    )


# ---------------------------------------------------------------------------
# Stateful wrapper (the original per-observation API)
# ---------------------------------------------------------------------------
class ALDRAMController:
    """Runtime timing selection with guard band, hysteresis and error fuse.

    A thin stateful wrapper over the shared transition kernel: every
    ``observe`` is one :func:`repro.core.binning.advance_bin` call on this
    DIMM's registers. For whole traces use :meth:`replay` (or the pure
    :func:`replay`) — one jitted scan instead of n_dimms × n_steps Python
    dispatches."""

    def __init__(
        self,
        table: DimmTimingTable,
        guard_band_c: float = GUARD_BAND_C,
        hysteresis_c: float = HYSTERESIS_C,
        hysteresis_steps: int = HYSTERESIS_STEPS,
    ):
        self.table = table
        self.guard_band_c = guard_band_c
        self.hysteresis_c = hysteresis_c
        self.hysteresis_steps = hysteresis_steps
        n, b = table.n_dimms, table.n_bins
        self._bin = np.full((n,), b - 1, np.int32)
        self._streak = np.zeros((n,), np.int32)
        self._fused = np.zeros((n,), bool)
        self.switch_count = 0
        self.fallback_count = 0

    @property
    def params(self) -> ControllerParams:
        return ControllerParams(
            self.guard_band_c, self.hysteresis_c, self.hysteresis_steps
        )

    def _bin_for(self, temp_c: float) -> int:
        """Guard-banded target bin (kept for API compatibility; delegates
        to the shared :func:`repro.core.binning.bin_index`)."""
        return bin_index(self.table.temp_bins, temp_c + self.guard_band_c)

    def observe(self, dimm: int, temp_c: float) -> AccessTimings:
        """Feed a temperature observation; returns the read + write timing
        sets to program (both access types, each at its own margin)."""
        if self._fused[dimm]:
            return JEDEC_ACCESS
        new_bin, streak, switched = advance_bin(
            self.table.temp_bins,
            int(self._bin[dimm]),
            int(self._streak[dimm]),
            temp_c,
            guard=self.guard_band_c,
            margin=self.hysteresis_c,
            hysteresis_steps=self.hysteresis_steps,
        )
        self._bin[dimm] = new_bin
        self._streak[dimm] = streak
        if switched:
            self.switch_count += 1
        return self.current(dimm)

    def current(self, dimm: int) -> AccessTimings:
        if self._fused[dimm]:
            return JEDEC_ACCESS
        return self.table.row(dimm, int(self._bin[dimm]))

    def report_error(self, dimm: int) -> AccessTimings:
        """Reliability fallback: any observed error fuses the DIMM to JEDEC
        timings (the paper's ultimate guarantee — at worst, AL-DRAM degrades
        to the baseline)."""
        self._fused[dimm] = True
        self.fallback_count += 1
        return JEDEC_ACCESS

    def bin_of(self, dimm: int) -> Optional[int]:
        return None if self._fused[dimm] else int(self._bin[dimm])

    # -- pure-state-machine bridge ----------------------------------------
    def state(self) -> ControllerState:
        """Current registers as a :class:`ControllerState` pytree."""
        return ControllerState(
            bin_idx=jnp.asarray(self._bin),
            cool_streak=jnp.asarray(self._streak),
            fused=jnp.asarray(self._fused),
        )

    def load_state(self, state: ControllerState) -> None:
        self._bin = np.asarray(state.bin_idx, np.int32).copy()
        self._streak = np.asarray(state.cool_streak, np.int32).copy()
        self._fused = np.asarray(state.fused, bool).copy()

    def replay(self, traces, errors=None, mesh=None) -> ReplayResult:
        """Advance this controller over whole traces in one jitted scan,
        then absorb the final registers and counters — equivalent to (and
        ~100×+ faster than) calling :meth:`observe` per (step, DIMM).
        ``mesh`` shards the DIMM axis as in the module-level
        :func:`replay`."""
        result = replay(  # the module-level pure function, not this method
            self.table, traces, errors=errors, params=self.params,
            state=self.state(), mesh=mesh,
        )
        self.load_state(result.state)
        self.switch_count += result.total_switches
        if errors is not None:
            self.fallback_count += int(np.asarray(errors, bool).sum())
        return result

    def replay_stream(self, traces, errors=None, chunk_steps=None, mesh=None,
                      impl=None, interpret=None):
        """Advance this controller over a temperature STREAM in chunked
        scans — identical state/counter absorption to :meth:`replay`
        (property-tested equal), but O(n_dimms · chunk) device memory and
        no materialized history: ``traces`` may be a ``(n_steps,
        n_dimms)`` array or any iterable of ``(temps_chunk, errors_chunk)``
        pairs longer than memory allows. The chunk scan is chosen by
        platform unless ``impl`` names one (the fused replay-step kernel
        on TPU, bit-exact; the ref elsewhere). Returns a
        :class:`repro.core.stream.StreamResult` (``.score()`` gives the
        bit-exact ``trace_score`` dict)."""
        result = replay_stream(
            self.table, traces, errors=errors, params=self.params,
            state=self.state(), chunk_steps=chunk_steps, mesh=mesh,
            impl=impl, interpret=interpret,
        )
        self.load_state(result.state)
        self.switch_count += result.total_switches
        self.fallback_count += result.errors_total
        return result
