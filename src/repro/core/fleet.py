"""Fleet-scale DIMM characterization engine.

The paper's core artifact is a *population study*: 115 DIMMs characterized
across temperatures and data patterns to find per-module timing margins
(§1.5, Fig. 2). The seed pipeline ran one ``profile_*`` call per
(temperature, pattern) point with Python-level dict plumbing between them;
at fleet scale (thousands of modules, the ROADMAP's production target) that
Python loop dominates wall-clock. This module batches the whole study into
**one jitted computation**:

* A fleet is a **struct-of-arrays pytree** (:class:`Fleet`): per-DIMM cell
  RC multiplier ``r``, worst-cell capacitance ``c``, leakage ``leak``
  (together a :class:`~repro.core.charge.CellParams`) plus a vendor index.
  SoA — one contiguous array per physical quantity, never a list of per-DIMM
  objects — is what lets a single vectorized predicate evaluation cover the
  entire population, and it is the layout every downstream consumer
  (controller tables, perf model, benchmarks) now reads directly.
* :func:`sweep` runs the read-mode, write-mode and joint profilers over the
  full (DIMM × temperature × data-pattern) grid as one ``jax.vmap``-batched,
  ``jax.jit``-compiled call built on the *pure* stacked-array functions of
  :mod:`repro.core.profiler` (``individual_min_timings`` & friends). No
  Python loop over modules, temperatures or patterns; no per-call dict
  rebuilding inside the traced region.
* :class:`SweepResult` holds the dense outputs — ``read`` / ``write`` /
  ``joint`` timing stacks of shape ``(n_temps, n_patterns, n_dimms, 4)``
  (last axis in ``PARAM_NAMES`` order) — with reduction / summary helpers.
  ``stacked_timings`` (the read and write sets at the worst pattern,
  stacked on an access-type axis) is exactly what a per-access-type
  controller programs, and :meth:`~SweepResult.to_table` hands it to
  :class:`repro.core.controller.DimmTimingTable` as one
  ``(T, N, 2, 4)`` array — straight into the controller's array-backed
  registers, no re-profiling and no per-DIMM list plumbing.
  ``merged_timings`` (elementwise max of the two sets) remains as a
  deprecated compat shim for single-register-set consumers.

Scaling note: grid-search cost is O(n_dimms · n_temps · n_patterns ·
Σ grid sizes) fused into a handful of XLA kernels; 1,000+ modules × 5
temperatures × 7 patterns characterizes in well under a second on CPU
(see ``benchmarks/fleet_sweep.py`` for measured speedups vs the loop).
Beyond one device, :func:`sweep` takes ``mesh=`` and shards the DIMM axis
across a 1-D device mesh (:mod:`repro.core.shard`): each shard runs the
same fused kernel on its contiguous block of modules, padding +
validity-masking handle non-divisible fleet sizes, and the sharded result
is bit-exact against the single-device sweep (property-tested and gated
by ``benchmarks/fleet_sweep.py --sharded``).
"""

from __future__ import annotations

import functools
import warnings
from functools import partial
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core import charge, dimm, profiler, shard
from repro.core.charge import CellParams, ChargeModelConstants, DEFAULT_CONSTANTS
from repro.core.timing import PARAM_NAMES
from repro.kernels.charge_sweep import ops as charge_sweep

#: Default characterization temperatures (°C): the paper's operating points
#: plus the JEDEC qualification corner.
DEFAULT_TEMPS_C: Tuple[float, ...] = (45.0, 55.0, 65.0, 75.0, 85.0)

#: Default data-pattern margin factors (worst-case first — the guarantee
#: pattern), mirroring :data:`repro.core.profiler.PATTERNS`.
DEFAULT_PATTERNS: Tuple[float, ...] = (1.0, 1.02, 1.03, 1.08)


class Fleet(NamedTuple):
    """A DIMM population in struct-of-arrays layout (a jax pytree).

    Every field is an array whose leading axis is the DIMM axis; there is
    deliberately no per-DIMM Python object anywhere."""

    cells: CellParams   # leaves shaped (n_dimms,)
    vendor: Array       # (n_dimms,) int32 vendor index

    @property
    def n_dimms(self) -> int:
        return int(self.cells.r.shape[0])

    def take(self, idx: Array | slice) -> "Fleet":
        """Sub-fleet selection (same SoA layout, every leaf)."""
        return jax.tree.map(lambda a: a[idx], self)


def synthesize(
    key: jax.Array,
    n_dimms: int,
    vendors: Sequence[dimm.VendorModel] = dimm.VENDORS,
    consts: ChargeModelConstants = DEFAULT_CONSTANTS,
) -> Fleet:
    """Sample a synthetic fleet of ``n_dimms`` modules.

    Scales the paper's 115-module vendor split (40/40/35 from three
    manufacturers) proportionally to any population size."""
    base = dimm.VENDOR_SPLIT
    total = sum(base)
    split = [n_dimms * b // total for b in base]
    split[0] += n_dimms - sum(split)
    cells, vidx = dimm.sample_population(
        key, n_dimms=n_dimms, vendors=vendors, split=split, consts=consts
    )
    return Fleet(cells=cells, vendor=vidx)


def from_population(cells: CellParams, vendor: Array | None = None) -> Fleet:
    """Wrap an existing sampled population as a fleet."""
    if vendor is None:
        vendor = jnp.zeros(cells.r.shape, jnp.int32)
    return Fleet(cells=cells, vendor=vendor)


class SweepResult(NamedTuple):
    """Dense characterization output over the (temp × pattern × DIMM) grid.

    Timing stacks are ns, cycle-quantized, last axis ordered as
    ``PARAM_NAMES`` = (trcd, tras, twr, trp)."""

    temps_c: Array      # (T,)
    patterns: Array     # (P,)
    read: Array         # (T, P, N, 4) read-mode individual minima
    write: Array        # (T, P, N, 4) write-mode minima (tras = JEDEC)
    joint: Array        # (T, P, N, 4) simultaneous-reduction minima
    #: The caller's exact Python temperatures. ``temps_c`` is float32, which
    #: perturbs values like 40.1 — bin edges and summary keys must come from
    #: here so controller lookups at the swept temperature hit their bin.
    temps_exact: Tuple[float, ...] = ()

    def bin_edges(self) -> Tuple[float, ...]:
        if self.temps_exact:
            return self.temps_exact
        return tuple(float(t) for t in self.temps_c.tolist())

    # -- reductions ---------------------------------------------------------
    @property
    def read_reductions(self) -> Array:
        return profiler.stack_reductions(self.read)

    @property
    def write_reductions(self) -> Array:
        return profiler.stack_reductions(self.write)

    @property
    def joint_reductions(self) -> Array:
        return profiler.stack_reductions(self.joint)

    # -- controller-facing views -------------------------------------------
    def worst_pattern_idx(self) -> int:
        """Index of the guarantee pattern (smallest margin factor)."""
        return int(jnp.argmin(self.patterns))

    def _guarantee_pattern_idx(self) -> int:
        """Worst-pattern index, refusing sweeps that never tested the
        guarantee pattern (margin factor 1.0): timings profiled only under
        benign patterns are not safe to program."""
        p = self.worst_pattern_idx()
        worst = float(self.patterns[p])
        if worst > 1.0:
            raise ValueError(
                f"sweep lacks the worst-case guarantee pattern: min margin "
                f"factor is {worst} (> 1.0); re-sweep with pattern 1.0 "
                "before programming controller tables"
            )
        return p

    def read_timings(self) -> Array:
        """(T, N, 4) read-access timing sets at the worst-case pattern —
        what the controller programs into the *read* register file."""
        return self.read[:, self._guarantee_pattern_idx()]

    def write_timings(self) -> Array:
        """(T, N, 4) write-access timing sets at the worst-case pattern —
        what the controller programs into the *write* register file.

        Refuses a sweep whose write profile carries the
        :data:`repro.core.profiler.WRITE_TRAS_UNTESTED_NS` sentinel (tRAS
        never tested under write stress): an untested parameter must be
        re-profiled, not silently programmed."""
        w = self.write[:, self._guarantee_pattern_idx()]
        if bool((jnp.asarray(w) < 0.0).any()):
            raise ValueError(
                "write-mode sweep carries the untested-tRAS sentinel "
                f"({profiler.WRITE_TRAS_UNTESTED_NS} ns): re-sweep with "
                "tras_mode='profiled' before programming write registers"
            )
        return w

    def stacked_timings(self) -> Array:
        """(T, N, 2, 4) per-access-type timing sets (axis order
        ``ACCESS_TYPES`` = read, write) at the worst-case pattern — the
        dense form :class:`repro.core.controller.DimmTimingTable` ingests."""
        return jnp.stack([self.read_timings(), self.write_timings()], axis=-2)

    def merged_timings(self) -> Array:
        """(T, N, 4) elementwise max of read/write requirements at the
        worst-case pattern.

        .. deprecated:: PR 3
            Compat shim for single-register-set consumers. The merge is
            strictly more conservative than programming per-access-type
            sets (:meth:`stacked_timings`): a merged set must satisfy both
            access types at once, so each parameter inherits the slower
            mode's requirement. Now that write-mode tRAS is actually
            profiled, even the merged set reduces tRAS below JEDEC — but
            new consumers should take the split sets."""
        warnings.warn(
            "SweepResult.merged_timings() is a deprecated compat shim for "
            "single-register-set consumers: it programs the elementwise max "
            "of the read/write sets, re-inheriting each parameter's slower-"
            "mode conservatism. Program the per-access-type sets instead "
            "(stacked_timings() / read_timings() / write_timings()).",
            DeprecationWarning,
            stacklevel=2,
        )
        return jnp.maximum(self.read_timings(), self.write_timings())

    def table_entries(self):
        """Iterate ``(bin_idx, temp_c, dimm_idx, access_type,
        [trcd, tras, twr, trp], margin)`` over the per-access-type sets at
        the worst pattern; ``margin`` is the mean fractional reduction vs
        JEDEC of that set.

        Ingestion point for *per-entry* consumers (altune
        ``TimingTable.from_fleet`` keys registers by entry); the DRAM
        controller's ``DimmTimingTable.from_fleet`` consumes
        :meth:`stacked_timings` as one stacked array instead — no per-DIMM
        Python plumbing on that path."""
        from repro.core.timing import ACCESS_TYPES

        stacked = self.stacked_timings()                      # (T, N, 2, 4)
        grid = stacked.tolist()
        margins = profiler.stack_reductions(stacked).mean(axis=-1).tolist()
        for b, t in enumerate(self.bin_edges()):
            for i, per_access in enumerate(grid[b]):
                for a, access in enumerate(ACCESS_TYPES):
                    yield b, t, i, access, per_access[a], margins[b][i][a]

    def to_table(self):
        """Build a :class:`repro.core.controller.DimmTimingTable` directly
        from the sweep (no re-profiling)."""
        from repro.core.controller import DimmTimingTable

        return DimmTimingTable.from_fleet(self)

    # -- paper-style aggregates --------------------------------------------
    def summary(self) -> Dict[float, Dict[str, Tuple[float, float, float]]]:
        """Fig. 2 / Table-style aggregate: per temperature, per parameter
        (min, mean, max) fractional reduction across the fleet at the
        worst-case pattern (tWR taken from the write test, like the paper's
        headline numbers)."""
        p = self.worst_pattern_idx()
        red = self.read_reductions[:, p]          # (T, N, 4)
        wred = self.write_reductions[:, p]
        out: Dict[float, Dict[str, Tuple[float, float, float]]] = {}
        for ti, t in enumerate(self.bin_edges()):
            per_param = {}
            for pi, name in enumerate(PARAM_NAMES):
                col = wred[ti, :, pi] if name == "twr" else red[ti, :, pi]
                per_param[name] = (
                    float(col.min()), float(col.mean()), float(col.max())
                )
            out[float(t)] = per_param
        return out


class RegionSweepResult(NamedTuple):
    """Dense characterization output over the (temp × pattern × region ×
    DIMM) grid — the rank-raised sibling of :class:`SweepResult` for
    design-induced per-region variation (ISSUE 10 / Lee et al.).

    Timing stacks are ns, cycle-quantized, last axis in ``PARAM_NAMES``
    order. Region axis order follows :func:`repro.core.charge.region_fracs`:
    index 0 = nearest the sense amps (fastest), index R-1 = farthest (the
    anchor class, identical to the region-free per-DIMM profile)."""

    temps_c: Array        # (T,)
    patterns: Array       # (P,)
    region_fracs: Array   # (R,) normalized distance classes
    read: Array           # (T, P, R, N, 4) read-mode individual minima
    write: Array          # (T, P, R, N, 4) write-mode minima
    temps_exact: Tuple[float, ...] = ()

    @property
    def n_regions(self) -> int:
        return int(self.region_fracs.shape[0])

    def bin_edges(self) -> Tuple[float, ...]:
        if self.temps_exact:
            return self.temps_exact
        return tuple(float(t) for t in self.temps_c.tolist())

    def worst_pattern_idx(self) -> int:
        return int(jnp.argmin(self.patterns))

    def _guarantee_pattern_idx(self) -> int:
        p = self.worst_pattern_idx()
        worst = float(self.patterns[p])
        if worst > 1.0:
            raise ValueError(
                f"sweep lacks the worst-case guarantee pattern: min margin "
                f"factor is {worst} (> 1.0); re-sweep with pattern 1.0 "
                "before programming controller tables"
            )
        return p

    def read_timings(self) -> Array:
        """(T, R, N, 4) read-access sets at the worst-case pattern."""
        return self.read[:, self._guarantee_pattern_idx()]

    def write_timings(self) -> Array:
        """(T, R, N, 4) write-access sets at the worst-case pattern;
        refuses the untested-tRAS sentinel like :class:`SweepResult`."""
        w = self.write[:, self._guarantee_pattern_idx()]
        if bool((jnp.asarray(w) < 0.0).any()):
            raise ValueError(
                "write-mode sweep carries the untested-tRAS sentinel "
                f"({profiler.WRITE_TRAS_UNTESTED_NS} ns): re-sweep with "
                "tras_mode='profiled' before programming write registers"
            )
        return w

    def stacked_timings(self) -> Array:
        """(T, R, N, 2, 4) per-access-type sets at the worst-case pattern —
        what a per-region :class:`repro.core.controller.DimmTimingTable`
        (schema v5, ``(N, B, R, 2, 4)`` stack) ingests after transposing
        the DIMM axis to the front."""
        return jnp.stack([self.read_timings(), self.write_timings()], axis=-2)

    def to_table(self):
        """Build a per-region :class:`repro.core.controller.DimmTimingTable`
        (rank-5 ``(N, B, R, 2, 4)`` stack) directly from the sweep."""
        from repro.core.controller import DimmTimingTable

        return DimmTimingTable.from_fleet(self)


@partial(jax.jit, static_argnames=("window_s", "consts", "write_tras"))
def _sweep_grid(
    cells: CellParams,
    temps_c: Array,
    patterns: Array,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
) -> Tuple[Array, Array, Array]:
    """The whole characterization study as one traced computation."""

    def at_point(t: Array, p: Array) -> Tuple[Array, Array, Array]:
        read = profiler.individual_min_timings(cells, t, p, window_s, consts)
        write = profiler.write_mode_min_timings(
            cells, t, p, window_s, consts, tras_mode=write_tras
        )
        joint = profiler.joint_min_timings(
            cells, t, 1.0, window_s, consts
        )
        # Joint mode is pattern-independent in the model but broadcast over
        # the pattern axis so all three stacks share one dense shape.
        return read, write, joint

    over_patterns = jax.vmap(at_point, in_axes=(None, 0))
    over_grid = jax.vmap(over_patterns, in_axes=(0, None))
    return over_grid(temps_c, patterns)


@partial(
    jax.jit,
    static_argnames=("window_s", "consts", "write_tras", "interpret"),
)
def _sweep_grid_pallas(
    cells: CellParams,
    temps_c: Array,
    patterns: Array,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
    interpret: bool,
) -> Tuple[Array, Array, Array]:
    """The characterization study routed through the fused charge-sweep
    kernel: read + write profiles of the ENTIRE (T, P, N) grid in ONE
    kernel pass (the kernel evaluates all searches per candidate cycle,
    carrying the per-cell charge-model invariants forward instead of
    re-deriving them per candidate). Joint mode has no grid search — it
    stays on the closed-form vmap path, bit-identical to `_sweep_grid`'s.
    """
    eff = charge.apply_pattern(
        CellParams(
            r=cells.r[None, None, :],
            c=cells.c[None, None, :],
            leak=cells.leak[None, None, :],
        ),
        patterns[None, :, None],
    )
    read, write = charge_sweep.sweep_min_timings(
        eff, temps_c[:, None, None], window_s, consts,
        impl="pallas", interpret=interpret,
    )
    if write_tras == "untested":
        write = jnp.concatenate(
            [
                write[..., :1],
                jnp.full_like(write[..., 1:2], profiler.WRITE_TRAS_UNTESTED_NS),
                write[..., 2:],
            ],
            axis=-1,
        )

    def at_point(t: Array, p: Array) -> Array:
        del p  # joint mode is pattern-independent; broadcast like _sweep_grid
        return profiler.joint_min_timings(cells, t, 1.0, window_s, consts)

    joint = jax.vmap(
        jax.vmap(at_point, in_axes=(None, 0)), in_axes=(0, None)
    )(temps_c, patterns)
    return read, write, joint


@partial(
    jax.jit, static_argnames=("window_s", "consts", "write_tras")
)
def _sweep_grid_regions(
    cells: CellParams,
    temps_c: Array,
    patterns: Array,
    region_fracs: Array,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
) -> Tuple[Array, Array]:
    """The rank-raised study — (T × P × R × N) — as one traced computation:
    the same pure profiler functions, vmapped over one more axis. This is
    the pure-jnp oracle the region-tiled kernel path is gated bit-exact
    against."""

    def at_point(t: Array, p: Array, f: Array) -> Tuple[Array, Array]:
        read = profiler.individual_min_timings(
            cells, t, p, window_s, consts, region_frac=f
        )
        write = profiler.write_mode_min_timings(
            cells, t, p, window_s, consts, tras_mode=write_tras, region_frac=f
        )
        return read, write

    over_regions = jax.vmap(at_point, in_axes=(None, None, 0))
    over_patterns = jax.vmap(over_regions, in_axes=(None, 0, None))
    over_grid = jax.vmap(over_patterns, in_axes=(0, None, None))
    return over_grid(temps_c, patterns, region_fracs)


@partial(
    jax.jit,
    static_argnames=("window_s", "consts", "write_tras", "interpret"),
)
def _sweep_grid_pallas_regions(
    cells: CellParams,
    temps_c: Array,
    patterns: Array,
    region_fracs: Array,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
    interpret: bool,
) -> Tuple[Array, Array]:
    """The region-axis study through the fused charge-sweep kernel: the
    region axis rides the kernel's arbitrary-leading-axes contract exactly
    like the pattern axis, so the ENTIRE (T, P, R, N) grid is still ONE
    kernel pass — the ops layer flattens the four leading axes into tiles
    and the kernel never knows a region axis exists."""
    eff = charge.apply_pattern(
        CellParams(
            r=cells.r[None, None, None, :],
            c=cells.c[None, None, None, :],
            leak=cells.leak[None, None, None, :],
        ),
        patterns[None, :, None, None],
    )
    eff = charge.apply_region(eff, region_fracs[None, None, :, None], consts)
    read, write = charge_sweep.sweep_min_timings(
        eff, temps_c[:, None, None, None], window_s, consts,
        impl="pallas", interpret=interpret,
    )
    if write_tras == "untested":
        write = jnp.concatenate(
            [
                write[..., :1],
                jnp.full_like(write[..., 1:2], profiler.WRITE_TRAS_UNTESTED_NS),
                write[..., 2:],
            ],
            axis=-1,
        )
    return read, write


@functools.lru_cache(maxsize=32)
def _sharded_region_sweep_runner(
    mesh,
    n_dimms: int,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
    impl: str,
    interpret: bool,
):
    """Cached (pad → shard_map → slice) wrapper for one region-sweep
    configuration; the DIMM axis sits at position 3 of the (T, P, R, N, 4)
    stacks. Called as ``run(cells, temps, patterns, region_fracs)``, the
    grid replicated, for the reason :func:`_sharded_sweep_runner` gives."""
    if impl == "pallas":

        def grid_fn(c: CellParams, t: Array, p: Array, f: Array):
            return _sweep_grid_pallas_regions(
                c, t, p, f, window_s, consts, write_tras, interpret
            )
    else:

        def grid_fn(c: CellParams, t: Array, p: Array, f: Array):
            return _sweep_grid_regions(c, t, p, f, window_s, consts, write_tras)

    return shard.sharded_dimm_map(
        grid_fn, mesh, in_axes=(0, None, None, None), out_axes=(3, 3),
        n_dimms=n_dimms,
    )


def sweep_regions(
    fleet: Fleet | CellParams,
    temps_c: Sequence[float] = DEFAULT_TEMPS_C,
    patterns: Sequence[float] = DEFAULT_PATTERNS,
    n_regions: int = 1,
    window_s: float = charge.REFRESH_WINDOW_S,
    consts: ChargeModelConstants = DEFAULT_CONSTANTS,
    write_tras: str = "profiled",
    impl: str = "pallas",
    interpret: bool | None = None,
    mesh=None,
) -> RegionSweepResult:
    """Characterize a fleet over the (DIMM × temp × pattern × region) grid
    in one jitted call — :func:`sweep` raised by one rank.

    Same contract as :func:`sweep` plus ``n_regions``: per-DIMM distance-
    from-sense-amp classes (:func:`repro.core.charge.region_fracs`). The
    result's ``read`` / ``write`` stacks are ``(T, P, R, N, 4)``; region
    index R-1 is the anchor (farthest) class, bitwise identical to the
    region-free profile, so ``n_regions=1`` reproduces :func:`sweep`'s
    stacks exactly. ``impl="pallas"`` keeps the one-kernel-pass structure
    (the region axis is tiled with the other leading axes); ``mesh=``
    shards the DIMM axis bit-exactly as in :func:`sweep`."""
    if write_tras not in profiler.WRITE_TRAS_MODES:
        raise ValueError(
            f"write_tras must be one of {profiler.WRITE_TRAS_MODES}, "
            f"got {write_tras!r}"
        )
    if impl not in charge_sweep.IMPLS:
        raise ValueError(
            f"impl must be one of {charge_sweep.IMPLS}, got {impl!r}"
        )
    cells = fleet.cells if isinstance(fleet, Fleet) else fleet
    interp = charge_sweep.default_interpret() if interpret is None else interpret
    t = jnp.asarray([float(x) for x in temps_c], jnp.float32)
    p = jnp.asarray([float(x) for x in patterns], jnp.float32)
    f = charge.region_fracs(int(n_regions))
    if mesh is None:
        if impl == "pallas":
            read, write = _sweep_grid_pallas_regions(
                cells, t, p, f, float(window_s), consts, write_tras, interp
            )
        else:
            read, write = _sweep_grid_regions(
                cells, t, p, f, float(window_s), consts, write_tras
            )
    else:
        run = _sharded_region_sweep_runner(
            mesh, int(cells.r.shape[0]), float(window_s), consts,
            write_tras, impl, interp,
        )
        read, write = run(cells, t, p, f)
    return RegionSweepResult(
        temps_c=t, patterns=p, region_fracs=f, read=read, write=write,
        temps_exact=tuple(float(x) for x in temps_c),
    )


@functools.lru_cache(maxsize=32)
def _sharded_sweep_runner(
    mesh,
    n_dimms: int,
    window_s: float,
    consts: ChargeModelConstants,
    write_tras: str,
    impl: str,
    interpret: bool,
):
    """Cached (pad → shard_map → slice) wrapper for one sweep
    configuration: repeated sharded sweeps of the same (mesh, fleet size)
    hit the jit cache instead of re-tracing the whole study.

    Called as ``run(cells, temps, patterns)``: the grid rides in as
    replicated arguments, exactly as in the unsharded call. Closed over,
    it would be a compile-time constant, and XLA would fold the
    temperature-dependent charge-model terms on the host, whose ``exp``
    is not the device's; on a TPU that moves the min-safe index of a cell
    that sits at a grid point's ``_EPS`` boundary."""
    if impl == "pallas":

        def grid_fn(c: CellParams, t: Array, p: Array):
            return _sweep_grid_pallas(
                c, t, p, window_s, consts, write_tras, interpret
            )
    else:

        def grid_fn(c: CellParams, t: Array, p: Array):
            return _sweep_grid(c, t, p, window_s, consts, write_tras)

    return shard.sharded_dimm_map(
        grid_fn, mesh, in_axes=(0, None, None), out_axes=(2, 2, 2),
        n_dimms=n_dimms,
    )


def sweep(
    fleet: Fleet | CellParams,
    temps_c: Sequence[float] = DEFAULT_TEMPS_C,
    patterns: Sequence[float] = DEFAULT_PATTERNS,
    window_s: float = charge.REFRESH_WINDOW_S,
    consts: ChargeModelConstants = DEFAULT_CONSTANTS,
    write_tras: str = "profiled",
    impl: str = "pallas",
    interpret: bool | None = None,
    mesh=None,
) -> SweepResult:
    """Characterize a whole fleet in one jitted (vmap × vmap) call.

    Equivalent to — and tested against — looping
    ``profiler.profile_individual`` / ``profile_write_mode`` /
    ``profile_joint`` over every (temperature, pattern) point, but with the
    entire grid fused into one XLA computation.

    Args / contract:

    * ``fleet`` — a :class:`Fleet` or bare :class:`CellParams`; every leaf
      is ``(n_dimms,)``, the DIMM axis.
    * ``temps_c`` / ``patterns`` — the ``(T,)`` / ``(P,)`` grid; the
      result's ``read`` / ``write`` / ``joint`` stacks are
      ``(T, P, n_dimms, 4)`` ns (``PARAM_NAMES`` order, cycle-quantized).
    * ``write_tras`` — passes through to
      :func:`repro.core.profiler.write_mode_min_timings` (``"untested"``
      fills the write tRAS column with the refused sentinel — for tests of
      the refusal path, never for real tables).
    * ``impl`` — ``"pallas"`` (default) runs the read/write grid searches
      through the fused charge-sweep kernel
      (:mod:`repro.kernels.charge_sweep`): one kernel pass for the whole
      (DIMM × temperature × pattern) grid, property-tested bit-exact
      against ``"ref"`` (the pure-jnp full-model search, kept reachable
      for oracle runs) and golden-gated against the committed benchmark
      baselines. ``interpret`` forces/disables the kernel's interpret mode
      (default: interpret everywhere but TPU).
    * ``mesh`` — optional 1-D device mesh carrying the ``"dimm"`` axis
      (:func:`repro.core.shard.fleet_mesh`). The DIMM axis is
      ``shard_map``-ped across the mesh — each device sweeps a contiguous
      block of modules with the very same jitted computation (the fused
      kernel runs *locally* per shard) — with edge-replication padding for
      fleet sizes that do not divide the device count (including
      ``n_dimms < n_devices``). Sharded results are BIT-EXACT vs
      ``mesh=None``.
    """
    if write_tras not in profiler.WRITE_TRAS_MODES:
        raise ValueError(
            f"write_tras must be one of {profiler.WRITE_TRAS_MODES}, "
            f"got {write_tras!r}"
        )
    if impl not in charge_sweep.IMPLS:
        raise ValueError(
            f"impl must be one of {charge_sweep.IMPLS}, got {impl!r}"
        )
    cells = fleet.cells if isinstance(fleet, Fleet) else fleet
    interp = charge_sweep.default_interpret() if interpret is None else interpret
    t = jnp.asarray([float(x) for x in temps_c], jnp.float32)
    p = jnp.asarray([float(x) for x in patterns], jnp.float32)
    if mesh is None:
        if impl == "pallas":
            read, write, joint = _sweep_grid_pallas(
                cells, t, p, float(window_s), consts, write_tras, interp
            )
        else:
            read, write, joint = _sweep_grid(
                cells, t, p, float(window_s), consts, write_tras
            )
    else:
        run = _sharded_sweep_runner(
            mesh, int(cells.r.shape[0]), float(window_s), consts,
            write_tras, impl, interp,
        )
        read, write, joint = run(cells, t, p)
    return SweepResult(
        temps_c=t, patterns=p, read=read, write=write, joint=joint,
        temps_exact=tuple(float(x) for x in temps_c),
    )


# ---------------------------------------------------------------------------
# Loop baseline (measurement reference only — what the seed pipeline did)
# ---------------------------------------------------------------------------
def sweep_loop_baseline(
    fleet: Fleet | CellParams,
    temps_c: Sequence[float] = DEFAULT_TEMPS_C,
    patterns: Sequence[float] = DEFAULT_PATTERNS,
    window_s: float = charge.REFRESH_WINDOW_S,
    consts: ChargeModelConstants = DEFAULT_CONSTANTS,
) -> SweepResult:
    """Per-DIMM Python-loop characterization: one ``profile_*`` call per
    (DIMM, temperature, pattern) point, results reassembled from dicts.

    This is the seed's execution model, kept as the wall-clock baseline for
    ``benchmarks/fleet_sweep.py`` and the equivalence tests. O(N·T·P)
    Python dispatches — do not use it for real fleets."""
    cells = fleet.cells if isinstance(fleet, Fleet) else fleet
    n = int(cells.r.shape[0])
    read, write, joint = [], [], []
    for t in temps_c:
        rt, wt, jt = [], [], []
        for p in patterns:
            rd, wd, jd = [], [], []
            for i in range(n):
                one = CellParams(
                    r=cells.r[i : i + 1], c=cells.c[i : i + 1], leak=cells.leak[i : i + 1]
                )
                r = profiler.profile_individual(one, t, window_s, consts, pattern=p)
                w = profiler.profile_write_mode(one, t, window_s, consts, pattern=p)
                j = profiler.profile_joint(one, t, window_s, consts)
                rd.append([float(r.timings[q][0]) for q in PARAM_NAMES])
                wd.append([float(w.timings[q][0]) for q in PARAM_NAMES])
                jd.append([float(j.timings[q][0]) for q in PARAM_NAMES])
            rt.append(rd)
            wt.append(wd)
            jt.append(jd)
        read.append(rt)
        write.append(wt)
        joint.append(jt)
    return SweepResult(
        temps_c=jnp.asarray(temps_c, jnp.float32),
        patterns=jnp.asarray(patterns, jnp.float32),
        read=jnp.asarray(read, jnp.float32),
        write=jnp.asarray(write, jnp.float32),
        joint=jnp.asarray(joint, jnp.float32),
        temps_exact=tuple(float(x) for x in temps_c),
    )
