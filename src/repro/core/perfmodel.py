"""Memory-system performance model — real-system evaluation analogue (§1.6).

The paper evaluates AL-DRAM on a real AMD system (software-controllable DRAM
timings) across 35 workloads, single- and multi-core, with the deployed
55 °C reductions tRCD/tRAS/tWR/tRP = 27/32/33/18 %. We reproduce that
evaluation with an analytic DRAM + core model:

* **Bank timing**: a request is a row-buffer *hit* (tCL), *empty-row miss*
  (tRCD+tCL) or *conflict* (tRP+tRCD+tCL, plus a tRAS residual when the row
  cycle is still open and a tWR recovery after writes) — the standard DDR3
  state machine parameterized by the four adapted timings.
* **Queueing / saturation**: banks are servers whose *miss* occupancy is
  row-cycle bound (tRC = tRAS+tRP and write recovery); effective bank count
  is derated by ``bank_balance`` (address-interleave imbalance). The data
  bus is a second server (tBURST per transfer). Under multi-core pressure
  the bank server saturates, so shortening the row cycle buys throughput —
  this is why the paper's multi-core gains exceed single-core, and why
  STREAM (bandwidth-bound, row-locality destroyed by multi-stream
  interleaving) gains the most.
* **Core**: IPC solves ``ipc = 1 / (cpi_exe + mpki·(lat+queue)·f/mlp)`` by
  bisection (the rhs is monotone decreasing in ipc through the queue term,
  so the fixed point is unique and bisection is robust even in deep
  saturation).

Workload parameters (MPKI, row-hit fraction under the evaluated system,
write fraction, MLP) follow standard SPEC CPU2006 / STREAM characterization
buckets; the handful of global constants are calibrated once against the
paper's aggregates — +14.0 % memory-intensive, +2.9 % non-intensive,
+10.5 % overall (multi-core) — giving 14.7 / 2.8 / 9.8 % (EXPERIMENTS.md
§Repro).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.timing import (
    ACCESS_TYPES,
    JEDEC_DDR3_1600,
    PARAM_NAMES,
    TBURST_NS,
    TCL_NS,
    TimingParams,
)

#: Deployed reductions from the paper's real-system evaluation (§1.6).
DEPLOYED_REDUCTIONS_55C: Dict[str, float] = {
    "trcd": 0.27,
    "tras": 0.32,
    "twr": 0.33,
    "trp": 0.18,
}


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    mpki: float           # last-level-cache misses per kilo-instruction
    row_hit: float        # row-buffer hit fraction (under this system)
    write_frac: float     # fraction of DRAM requests that are writes
    mlp: float            # memory-level parallelism (overlapped misses)
    category: str         # "stream" | "intensive" | "non-intensive"


# 35 workloads: 4 STREAM kernels + 17 memory-intensive + 14 non-intensive.
WORKLOADS: Tuple[Workload, ...] = (
    Workload("stream.copy", 70.0, 0.42, 0.45, 10.0, "stream"),
    Workload("stream.scale", 70.0, 0.42, 0.45, 10.0, "stream"),
    Workload("stream.add", 70.0, 0.42, 0.33, 10.0, "stream"),
    Workload("stream.triad", 70.0, 0.42, 0.33, 10.0, "stream"),
    Workload("mcf", 67.0, 0.38, 0.28, 6.0, "intensive"),
    Workload("lbm", 45.0, 0.52, 0.42, 7.0, "intensive"),
    Workload("libquantum", 50.0, 0.65, 0.20, 7.5, "intensive"),
    Workload("milc", 29.0, 0.48, 0.30, 5.0, "intensive"),
    Workload("soplex", 27.0, 0.45, 0.25, 4.5, "intensive"),
    Workload("GemsFDTD", 25.0, 0.50, 0.33, 5.0, "intensive"),
    Workload("omnetpp", 21.0, 0.30, 0.30, 3.0, "intensive"),
    Workload("leslie3d", 20.0, 0.52, 0.35, 4.5, "intensive"),
    Workload("bwaves", 18.0, 0.55, 0.30, 5.0, "intensive"),
    Workload("sphinx3", 13.0, 0.46, 0.15, 3.0, "intensive"),
    Workload("zeusmp", 12.0, 0.48, 0.35, 3.5, "intensive"),
    Workload("cactusADM", 11.0, 0.42, 0.35, 2.5, "intensive"),
    Workload("astar", 10.5, 0.35, 0.25, 2.0, "intensive"),
    Workload("wrf", 10.0, 0.50, 0.30, 3.0, "intensive"),
    Workload("xalancbmk", 10.0, 0.32, 0.25, 2.5, "intensive"),
    Workload("gcc", 10.2, 0.40, 0.30, 2.5, "intensive"),
    Workload("bzip2", 11.5, 0.42, 0.35, 2.5, "intensive"),
    Workload("perlbench", 2.7, 0.45, 0.30, 1.5, "non-intensive"),
    Workload("gobmk", 1.8, 0.40, 0.30, 1.5, "non-intensive"),
    Workload("sjeng", 1.5, 0.38, 0.30, 1.4, "non-intensive"),
    Workload("h264ref", 2.4, 0.50, 0.25, 1.8, "non-intensive"),
    Workload("hmmer", 2.1, 0.52, 0.30, 1.8, "non-intensive"),
    Workload("namd", 1.2, 0.50, 0.25, 1.5, "non-intensive"),
    Workload("povray", 0.45, 0.45, 0.25, 1.2, "non-intensive"),
    Workload("calculix", 1.05, 0.50, 0.28, 1.5, "non-intensive"),
    Workload("gamess", 0.6, 0.45, 0.25, 1.2, "non-intensive"),
    Workload("gromacs", 2.7, 0.50, 0.28, 1.8, "non-intensive"),
    Workload("tonto", 1.8, 0.48, 0.27, 1.5, "non-intensive"),
    Workload("dealII", 3.0, 0.50, 0.28, 1.8, "non-intensive"),
    Workload("sixtrack", 0.6, 0.45, 0.25, 1.2, "non-intensive"),
    Workload("wupwise", 3.6, 0.52, 0.30, 2.0, "non-intensive"),
)
assert len(WORKLOADS) == 35


@dataclasses.dataclass(frozen=True)
class SystemConfig:
    """Evaluated memory system (paper: 1 rank, 1 channel) + calibrated
    constants (benchmarks/calibrate.py; DESIGN.md §8)."""

    n_cores: int = 1
    n_banks: int = 8
    bank_balance: float = 0.55     # address-interleave bank derating
    cpu_ghz: float = 3.2
    cpi_exe: float = 0.5           # non-memory CPI
    ctrl_overhead_ns: float = 14.0  # controller + bus fixed latency
    empty_frac: float = 0.35       # misses landing on a precharged row
    ras_residual: float = 0.35     # conflict fraction still bound by tRAS
    wr_turnaround: float = 0.55    # conflict-after-write tWR exposure
    rho_max: float = 0.995
    bisect_iters: int = 60


#: The paper's two evaluated configurations.
SINGLE_CORE = SystemConfig(n_cores=1)
MULTI_CORE = SystemConfig(n_cores=4)


# Cached per workload tuple, as HOST numpy arrays: they are rebuilt once
# instead of on every evaluate() call, and — unlike jnp arrays — caching
# them is safe when the first call happens inside a jit trace (a cached
# jnp.array would be a leaked tracer there).
@functools.lru_cache(maxsize=8)
def _fields(ws: Tuple[Workload, ...]) -> Dict[str, "np.ndarray"]:
    return {
        "mpki": np.array([w.mpki for w in ws], np.float32),
        "row_hit": np.array([w.row_hit for w in ws], np.float32),
        "write_frac": np.array([w.write_frac for w in ws], np.float32),
        "mlp": np.array([w.mlp for w in ws], np.float32),
    }


def access_latency_ns(
    t: TimingParams,
    f: Dict[str, Array],
    cfg: SystemConfig,
    t_write: Optional[TimingParams] = None,
) -> Array:
    """Expected bank access latency (no queueing) per request.

    ``t`` is the *read* timing set; ``t_write`` the write set (defaults to
    ``t`` — the merged single-register-file behaviour, to which this
    reduces exactly when the sets coincide). Reads are bound by
    tRCD/tRAS/tRP of the read set; write requests take their tRCD/tRP from
    the write set, expose its tWR through the turnaround recovery, and —
    like :func:`miss_service_ns`'s ``occ_write`` — are bound by the WRITE
    set's tRAS residual when their row cycle is still open (the write
    set's restore-under-write tRAS, not the read set's)."""
    tw = t if t_write is None else t_write
    h = f["row_hit"]
    wf = f["write_frac"]
    miss = 1.0 - h
    empty = cfg.empty_frac * miss
    conflict = miss - empty
    trcd_eff = (1.0 - wf) * t.trcd + wf * tw.trcd
    trp_eff = (1.0 - wf) * t.trp + wf * tw.trp
    t_hit = TCL_NS + TBURST_NS
    t_empty = trcd_eff + TCL_NS + TBURST_NS
    ras_read = jnp.maximum(t.tras - (t.trcd + TCL_NS + TBURST_NS), 0.0)
    ras_write = jnp.maximum(tw.tras - (tw.trcd + TCL_NS + TBURST_NS), 0.0)
    ras_extra = cfg.ras_residual * ((1.0 - wf) * ras_read + wf * ras_write)
    wr_extra = cfg.wr_turnaround * wf * tw.twr
    t_conf = trp_eff + trcd_eff + TCL_NS + TBURST_NS + ras_extra + wr_extra
    return h * t_hit + empty * t_empty + conflict * t_conf + cfg.ctrl_overhead_ns


#: Read-to-precharge gate (DDR3 tRTP, ns): the bank may precharge this long
#: after the column access — the burst itself rides the data bus.
TRTP_NS: float = 7.5


def miss_service_ns(
    t: TimingParams,
    f: Dict[str, Array],
    cfg: SystemConfig,
    t_write: Optional[TimingParams] = None,
) -> Array:
    """Bank occupancy per *miss*: the row cycle. Precharge may start once
    both tRAS and read-to-precharge (tRCD+tRTP) are satisfied; writes add
    tWR recovery. With a distinct write set, write-conflict row cycles run
    at the write set's (shorter, restore-under-write) tRAS."""
    tw = t if t_write is None else t_write
    h = f["row_hit"]
    wf = f["write_frac"]
    miss = jnp.maximum(1.0 - h, 1e-9)
    empty = cfg.empty_frac * miss
    conflict = miss - empty
    trcd_eff = (1.0 - wf) * t.trcd + wf * tw.trcd
    wr_extra = cfg.wr_turnaround * wf * tw.twr
    occ_read = jnp.maximum(t.tras, t.trcd + TRTP_NS) + t.trp
    occ_write = jnp.maximum(tw.tras, tw.trcd + TRTP_NS) + tw.trp
    occ_conf = (1.0 - wf) * occ_read + wf * occ_write + wr_extra
    return (empty * (trcd_eff + TBURST_NS) + conflict * occ_conf) / miss


def evaluate(
    t: TimingParams,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...] = WORKLOADS,
    t_write: Optional[TimingParams] = None,
    refresh_occ: Array | float = 0.0,
    trfc_ns: Array | float = 0.0,
) -> Dict[str, Array]:
    """IPC per workload under timing set ``t`` (homogeneous multi-instance
    for the multi-core configuration, the paper's methodology).

    Pass ``t_write`` to evaluate a per-access-type register file: reads
    run at ``t``'s margins, writes at ``t_write``'s. Omitting it models a
    merged single set (the two coincide).

    ``refresh_occ`` is the rank's refresh occupancy — the fraction of
    time lost to REFRESH commands (``mult · tRFC / tREFI``, see
    :mod:`repro.core.refresh`). Refresh steals bandwidth from BOTH
    servers (during tRFC no bank can cycle and no data moves, so the
    effective capacity of the bank pool and the data bus shrinks by
    ``1 − occ``) and adds expected blocking latency (an arrival landing
    in an in-flight REFRESH waits ``tRFC / 2`` on average). At the
    defaults (0.0) every term reduces to exactly the refresh-free
    arithmetic (``x + 0`` and ``x / 1`` are exact in float32), so
    refresh-free callers are numerically unchanged.

    An occupancy given as a JAX array (the refresh-aware fleet path) runs
    the bisection as a ``fori_loop``; a Python scalar keeps it unrolled.
    The steps are the same operations in the same order either way (see
    ``_ipc_stack_refresh`` for why)."""
    f = _fields(workloads)
    # Expected refresh blocking per request: P(arrive during refresh) ×
    # mean residual refresh time. The SAME absolute penalty lands on
    # adapted and JEDEC timings alike, which is why refresh DILUTES the
    # relative gain (combined ≤ latency-only speedup).
    lat = access_latency_ns(t, f, cfg, t_write) + refresh_occ * trfc_ns * 0.5
    svc = miss_service_ns(t, f, cfg, t_write)
    miss = 1.0 - f["row_hit"]
    banks_eff = cfg.n_banks * cfg.bank_balance
    ghz = cfg.cpu_ghz
    avail = 1.0 - refresh_occ

    def cpi_of(ipc: Array) -> Array:
        rate = cfg.n_cores * ipc * ghz * f["mpki"] * 1e-3  # req/ns
        rho_bank = jnp.clip(
            rate * miss * svc / (banks_eff * avail), 0.0, cfg.rho_max
        )
        rho_bus = jnp.clip(rate * TBURST_NS / avail, 0.0, cfg.rho_max)
        queue = (
            rho_bank / (1.0 - rho_bank) * svc * 0.5
            + rho_bus / (1.0 - rho_bus) * TBURST_NS * 0.5
        )
        return cfg.cpi_exe + f["mpki"] * 1e-3 * (lat + queue) * ghz / f["mlp"]

    # Bisection on the unique fixed point ipc = 1/cpi(ipc).
    def halve(_, bounds: Tuple[Array, Array]) -> Tuple[Array, Array]:
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        go_up = 1.0 / cpi_of(mid) > mid
        return jnp.where(go_up, mid, lo), jnp.where(go_up, hi, mid)

    bounds = (jnp.full_like(lat, 1e-4), jnp.full_like(lat, 1.0 / cfg.cpi_exe))
    if isinstance(refresh_occ, jax.Array):
        bounds = jax.lax.fori_loop(0, cfg.bisect_iters, halve, bounds)
    else:
        for i in range(cfg.bisect_iters):
            bounds = halve(i, bounds)
    ipc = 0.5 * (bounds[0] + bounds[1])
    return {"ipc": ipc, "latency_ns": lat, "service_ns": svc}


def _geomean(x: Array) -> float:
    return float(jnp.exp(jnp.log(x).mean()))


def speedup_report(
    cfg: SystemConfig,
    reductions: Dict[str, float] = DEPLOYED_REDUCTIONS_55C,
    workloads: Tuple[Workload, ...] = WORKLOADS,
) -> Dict[str, float]:
    """Fig. 3 aggregates: per-category geometric-mean speedups of AL-DRAM
    (deployed 55 °C reductions) over JEDEC."""
    base = evaluate(JEDEC_DDR3_1600, cfg, workloads)["ipc"]
    fast = evaluate(JEDEC_DDR3_1600.reduced(reductions), cfg, workloads)["ipc"]
    speedup = fast / base
    cats = [w.category for w in workloads]

    def cat(catname: str) -> Array:
        idx = jnp.array([i for i, c in enumerate(cats) if c == catname])
        return speedup[idx]

    mem = jnp.concatenate([cat("stream"), cat("intensive")])
    return {
        "all_geomean": _geomean(speedup) - 1.0,
        "intensive_geomean": _geomean(mem) - 1.0,
        "nonintensive_geomean": _geomean(cat("non-intensive")) - 1.0,
        "stream_max": float(cat("stream").max()) - 1.0,
        "best": float(speedup.max()) - 1.0,
    }


# ---------------------------------------------------------------------------
# Fleet path: vmapped evaluation of per-DIMM timing stacks
# ---------------------------------------------------------------------------
def _with_access_axis(timings: Array, split: Optional[bool] = None) -> Array:
    """Normalize a timing stack to ``(..., 2, 4)`` (access-type axis).

    ``split=True`` asserts the stack already carries the access axis
    (read = 0, write = 1, the ``ACCESS_TYPES`` order); ``split=False``
    treats it as a merged set and duplicates it into both slots. With
    ``split=None`` an unambiguous shape decides: a trailing axis of
    extent != 2 is a merged stack. A trailing ``(2, 4)`` is AMBIGUOUS — it
    could be an access-type axis or a merged stack whose leading axis
    happens to have extent 2 (a 2-DIMM fleet, a 2-bin table) — and is
    REFUSED: callers must pass ``split`` explicitly rather than have this
    function guess. The fixed-rank entry points (``trace_score``,
    ``realized_latency_reductions``) decide by rank and always pass it."""
    timings = jnp.asarray(timings, jnp.float32)
    if timings.shape[-1] != len(PARAM_NAMES):
        raise ValueError(f"timing stack must end in a 4-axis, got {timings.shape}")
    if split is None:
        if timings.ndim >= 2 and timings.shape[-2] == len(ACCESS_TYPES):
            raise ValueError(
                f"ambiguous timing stack shape {timings.shape}: the trailing "
                "(2, 4) could be a (read, write) access-type axis or a merged "
                "stack with a leading axis of extent 2; pass split=True "
                "(access axis) or split=False (merged) explicitly"
            )
        split = False
    if split:
        if timings.ndim < 2 or timings.shape[-2] != len(ACCESS_TYPES):
            raise ValueError(
                f"expected an access-type axis (..., 2, 4), got {timings.shape}"
            )
        return timings
    return jnp.stack([timings, timings], axis=-2)


@functools.partial(jax.jit, static_argnames=("cfg", "workloads"))
def _ipc_stack(flat: Array, cfg: SystemConfig, workloads: Tuple[Workload, ...]) -> Array:
    def one(ts: Array) -> Array:
        tr = TimingParams(ts[0, 0], ts[0, 1], ts[0, 2], ts[0, 3])
        tw = TimingParams(ts[1, 0], ts[1, 1], ts[1, 2], ts[1, 3])
        return evaluate(tr, cfg, workloads, t_write=tw)["ipc"]

    return jax.vmap(one)(flat)


# A separate jitted program for the refresh-aware path: the refresh-free
# `_ipc_stack` keeps its exact operand signature (and therefore its
# compiled program), preserving the "identical compiled programs" bitwise
# guarantees of the refresh-free score paths.
#
# Its bisection is rolled, since `o` is an array. Traced into one program
# with the JEDEC baseline's (the sharded finalize runs `_score_figures`
# under shard_map), the two same-shape 60-step chains fuse into one
# XLA:CPU loop kernel, whose emitter splits it into subgraph functions
# that call each other once per use: past ~45 steps each step multiplied
# the run time by 7-23, and the sharded refresh score never returned. A
# `fori_loop` step is a kernel of its own, so nothing fuses across steps.
@functools.partial(jax.jit, static_argnames=("cfg", "workloads", "trfc_ns"))
def _ipc_stack_refresh(
    flat: Array,
    occ: Array,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...],
    trfc_ns: float,
) -> Array:
    def one(ts: Array, o: Array) -> Array:
        tr = TimingParams(ts[0, 0], ts[0, 1], ts[0, 2], ts[0, 3])
        tw = TimingParams(ts[1, 0], ts[1, 1], ts[1, 2], ts[1, 3])
        return evaluate(
            tr, cfg, workloads, t_write=tw, refresh_occ=o, trfc_ns=trfc_ns
        )["ipc"]

    return jax.vmap(one)(flat, occ)


def evaluate_stack(
    timings: Array,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...] = WORKLOADS,
    split: Optional[bool] = None,
    refresh_occ: Optional[Array] = None,
    trfc_ns: float = 0.0,
) -> Array:
    """IPC for a ``(..., 4)`` merged or ``(..., 2, 4)`` per-access-type
    timing stack (``PARAM_NAMES`` order, ns; see :func:`_with_access_axis`
    for the ``split`` disambiguation rule — pass it explicitly when a
    leading axis could legitimately have extent 2).

    Jitted and vmapped over all leading axes — the fleet engine feeds the
    sweep output straight in (eager dispatch of the unrolled bisection
    loop is ~300× slower). Returns IPC with shape
    ``(leading..., n_workloads)``.

    ``refresh_occ`` — optional per-entry refresh occupancy, broadcastable
    to the stack's leading axes (see :func:`evaluate`); ``None`` runs the
    refresh-free compiled program, untouched.
    """
    timings = _with_access_axis(timings, split)
    if refresh_occ is None:
        ipc = _ipc_stack(timings.reshape(-1, 2, 4), cfg, workloads)
    else:
        occ = jnp.broadcast_to(
            jnp.asarray(refresh_occ, jnp.float32), timings.shape[:-2]
        ).reshape(-1)
        ipc = _ipc_stack_refresh(
            timings.reshape(-1, 2, 4), occ, cfg, workloads, float(trfc_ns)
        )
    return ipc.reshape(*timings.shape[:-2], ipc.shape[-1])


def fleet_speedups(
    timings: Array,
    cfg: SystemConfig = MULTI_CORE,
    workloads: Tuple[Workload, ...] = WORKLOADS,
    split: Optional[bool] = None,
    refresh_occ: Optional[Array] = None,
    trfc_ns: float = 0.0,
) -> Array:
    """Per-entry geometric-mean speedup over JEDEC for a ``(..., 4)``
    merged or ``(..., 2, 4)`` per-access-type stack (``split`` as in
    :func:`evaluate_stack`).

    This is the per-DIMM "what do I gain from adapting this module" number
    of the paper's Fig. 3, computed for a whole fleet in one call.

    With ``refresh_occ`` (per-entry occupancy, broadcastable to the
    leading axes) the ratio becomes the COMBINED latency+refresh speedup:
    each entry's JEDEC baseline pays the SAME refresh occupancy — the
    refresh rate is set by temperature, which adapting timings does not
    change — so the ratio isolates what adaptation buys in a system that
    is refreshing either way."""
    jedec = jnp.asarray([list(JEDEC_DDR3_1600)], jnp.float32)
    if refresh_occ is None:
        base = evaluate_stack(jedec, cfg, workloads, split=False)[0]
        ipc = evaluate_stack(timings, cfg, workloads, split=split)
        ratio = ipc / jnp.broadcast_to(base, jnp.shape(ipc))
    else:
        timings = _with_access_axis(timings, split)
        jedec_rows = jnp.broadcast_to(
            jnp.stack([jedec[0], jedec[0]]), timings.shape
        )
        kw = dict(split=True, refresh_occ=refresh_occ, trfc_ns=trfc_ns)
        base = evaluate_stack(jedec_rows, cfg, workloads, **kw)
        ipc = evaluate_stack(timings, cfg, workloads, **kw)
        ratio = ipc / base
    return jnp.exp(jnp.log(ratio).mean(axis=-1))


# ---------------------------------------------------------------------------
# Trace scoring: what did the controller actually deliver over a day?
# ---------------------------------------------------------------------------
#: The paper's headline claim: 14 % average performance improvement for
#: memory-intensive workloads on the real system (§1.6). Trace scoring
#: reports realized speedup against this number.
PAPER_CLAIM_SPEEDUP: float = 0.14

#: The claim's cohort: STREAM + memory-intensive SPEC (the paper's
#: "memory-intensive" aggregate; non-intensive workloads gain ~3 %).
MEM_INTENSIVE_WORKLOADS: Tuple[Workload, ...] = tuple(
    w for w in WORKLOADS if w.category != "non-intensive"
)


def time_in_bin(bin_idx: Array, n_bins: int) -> Array:
    """Occupancy fractions per (DIMM, effective bin) over a replay.

    ``bin_idx`` is the ``(n_steps, n_dimms)`` effective-row trace from
    :class:`repro.core.controller.ReplayResult` (``n_bins`` = the JEDEC
    sentinel); returns ``(n_dimms, n_bins + 1)`` fractions summing to 1."""
    bins = jnp.arange(n_bins + 1)[None, None, :]
    return (bin_idx[:, :, None] == bins).mean(axis=0)


def realized_latency_reductions(timings: Array) -> Dict[str, Array]:
    """Per-DIMM mean read/write latency reduction vs JEDEC over a trace.

    ``timings`` is the ``(n_steps, n_dimms, 2, 4)`` realized per-access
    row stack from a replay (a legacy merged ``(n_steps, n_dimms, 4)``
    stack is also accepted and duplicated); the figures of merit are the
    paper's Fig. 2 sums, each computed from its own access-type set
    (read: tRCD+tRAS+tRP of the read set, write: tRCD+tWR+tRP of the
    write set). ``read_params`` / ``write_params`` give the ``(n_dimms,
    4)`` per-parameter realized reductions of each set."""
    timings = jnp.asarray(timings, jnp.float32)
    # Fixed-rank input: rank 4 carries the access axis, rank 3 is legacy
    # merged — no shape heuristic needed (a 2-DIMM fleet stays a fleet).
    timings = _with_access_axis(timings, split=(timings.ndim == 4))
    rs, ws = timings[..., 0, :], timings[..., 1, :]
    read = rs[..., 0] + rs[..., 1] + rs[..., 3]
    write = ws[..., 0] + ws[..., 2] + ws[..., 3]
    jedec = jnp.asarray(list(JEDEC_DDR3_1600), jnp.float32)
    return {
        "read": 1.0 - read.mean(axis=0) / JEDEC_DDR3_1600.read_sum,
        "write": 1.0 - write.mean(axis=0) / JEDEC_DDR3_1600.write_sum,
        "read_params": 1.0 - rs.mean(axis=0) / jedec[None, :],
        "write_params": 1.0 - ws.mean(axis=0) / jedec[None, :],
    }


class ScorePartials(NamedTuple):
    """Running trace-score accumulators over the step axis (a jax pytree).

    These are the mask-weighted per-DIMM partials every ``trace_score``
    path reduces — and the ONLY thing a streaming replay
    (:mod:`repro.core.stream`) has to carry to score a trace: no
    materialized ``(n_steps, ...)`` history is ever needed.

    * ``occupancy`` — ``(n_dimms, n_bins + 1)`` int32 step counts per
      effective bin (last column = the beyond-last-bin JEDEC sentinel).
      Integer, hence exact under any accumulation order.
    * ``switches`` — ``(n_dimms,)`` int32 timing-set switch counts. Exact.
    * ``timing_sums`` — ``(n_dimms, 2, 4)`` float32 sums of the realized
      per-access timing rows (ns; axes = ``ACCESS_TYPES`` ×
      ``PARAM_NAMES``). Realized timings are cycle-quantized — multiples
      of tCK = 1.25 ns, itself exact in float32 — so these sums are EXACT
      (independent of chunking / accumulation order) as long as
      ``n_steps · max_timing < 2²⁴ · 1.25 ns``, i.e. ~600k steps at JEDEC
      tRAS: a week of minute-cadence telemetry per accumulator. This
      exactness is what makes streamed scores bit-identical to
      materialized ones.
    * ``n_steps`` — ``()`` int32 observations absorbed so far.
    """

    occupancy: Array    # (N, B+1) int32
    switches: Array     # (N,) int32
    timing_sums: Array  # (N, 2, 4) float32 ns
    n_steps: Array      # () int32


@functools.partial(jax.jit, static_argnames=("n_dimms", "n_bins"))
def trace_score_init(n_dimms: int, n_bins: int) -> ScorePartials:
    """Zeroed accumulators for an ``n_dimms``-DIMM, ``n_bins``-bin fleet.

    Jitted (both args static): zero-filling is then a compile-time
    constant, so re-initializing partials inside a strict
    ``transfer_guard`` scope stays legal once warm."""
    return ScorePartials(
        occupancy=jnp.zeros((n_dimms, n_bins + 1), jnp.int32),
        switches=jnp.zeros((n_dimms,), jnp.int32),
        timing_sums=jnp.zeros(
            (n_dimms, len(ACCESS_TYPES), len(PARAM_NAMES)), jnp.float32
        ),
        n_steps=jnp.zeros((), jnp.int32),
    )


def trace_score_accumulate(
    partials: ScorePartials,
    timings: Array,
    bin_idx: Array,
    switched: Array,
    impl: str = "ref",
    interpret: Optional[bool] = None,
) -> ScorePartials:
    """Absorb a ``(chunk, n_dimms, 2, 4)`` block of replay outputs
    (legacy merged ``(chunk, n_dimms, 4)`` rows are duplicated).

    Pure and jit/scan-safe: the streaming replay calls this inside its
    ``lax.scan`` carry with ``chunk = 1`` slices, chunked callers once per
    chunk, and the materialized :func:`trace_score` once with the whole
    trace — by the exactness notes on :class:`ScorePartials`, all
    chunkings produce bit-identical partials.

    ``impl="pallas"`` folds the block through the fused accumulate kernel
    (:func:`repro.kernels.replay_step.ops.accumulate_chunk`) — one
    VMEM-resident pass per DIMM tile instead of three reductions; equal
    to the ref under the same quantization exactness the chunk-invariance
    contract already relies on (int accumulators exact outright).
    ``interpret=None`` auto-enables interpret mode off-TPU."""
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be one of ('ref', 'pallas'), got {impl!r}")
    if impl == "pallas":
        from repro.kernels.replay_step import ops as replay_ops

        return replay_ops.accumulate_chunk(
            partials, timings, bin_idx, switched, interpret
        )
    timings = jnp.asarray(timings, jnp.float32)
    timings = _with_access_axis(timings, split=(timings.ndim == 4))
    n_bins1 = partials.occupancy.shape[-1]
    occ = (bin_idx[:, :, None] == jnp.arange(n_bins1)[None, None, :]).sum(axis=0)
    return ScorePartials(
        occupancy=partials.occupancy + occ.astype(jnp.int32),
        switches=partials.switches + switched.sum(axis=0).astype(jnp.int32),
        timing_sums=partials.timing_sums + timings.sum(axis=0),
        n_steps=partials.n_steps + timings.shape[0],
    )


def _score_figures(
    partials: ScorePartials,
    stack: Array,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...],
    refresh=None,
):
    """Per-DIMM score figures from partials — the shared core of every
    ``trace_score`` path (single-device, shard-local, streamed finalize).

    Returns ``(occ (N, B+1) fractions, red dict, realized (N,),
    realized_mem (N,), tras_flags (N,), extra)``. IPC is evaluated once
    per unique (DIMM, bin) register block and weighted by time-in-bin, so
    a 10⁷-transition day costs the same as a minute.

    ``refresh`` — optional :class:`repro.core.refresh.BinRefresh`
    (per-effective-bin occupancies + tRFC). ``extra`` is then a dict of
    per-DIMM refresh figures (``combined``/``combined_mem`` realized
    combined speedups, ``refresh_occ`` time-weighted occupancy), else
    ``None``. Because the occupancy is a function of the SELECTED BIN,
    the existing time-in-bin partials already carry everything this
    needs: refresh enters at finalize only, and streamed ≡ materialized
    stays bit-exact with refresh enabled for free."""
    n_steps = partials.n_steps.astype(jnp.float32)
    occ = partials.occupancy.astype(jnp.float32) / n_steps       # (N, B+1)
    sums = partials.timing_sums                                  # (N, 2, 4)
    mean_rows = sums / n_steps
    rs, ws = mean_rows[:, 0, :], mean_rows[:, 1, :]
    read_mean = (sums[:, 0, 0] + sums[:, 0, 1] + sums[:, 0, 3]) / n_steps
    write_mean = (sums[:, 1, 0] + sums[:, 1, 2] + sums[:, 1, 3]) / n_steps
    jedec = jnp.asarray(list(JEDEC_DDR3_1600), jnp.float32)
    red = {
        "read": 1.0 - read_mean / JEDEC_DDR3_1600.read_sum,
        "write": 1.0 - write_mean / JEDEC_DDR3_1600.write_sum,
        "read_params": 1.0 - rs / jedec[None, :],
        "write_params": 1.0 - ws / jedec[None, :],
    }
    jedec_rows = jnp.broadcast_to(jedec, (stack.shape[0], 1, 2, 4))
    rows = jnp.concatenate([stack, jedec_rows], axis=1)          # (N, B+1, 2, 4)
    sp = fleet_speedups(rows, cfg, workloads, split=True)        # (N, B+1)
    sp_mem = fleet_speedups(rows, cfg, MEM_INTENSIVE_WORKLOADS, split=True)
    realized = (occ * sp).sum(axis=-1)                           # (N,)
    realized_mem = (occ * sp_mem).sum(axis=-1)
    # Fraction of DIMMs whose *programmed* read-set tRAS sits below JEDEC
    # in the coolest bin — 1.0 unless a merge bug reappears.
    tras_flags = (
        stack[:, 0, 0, 1] < JEDEC_DDR3_1600.tras - 1e-6
    ).astype(jnp.float32)
    extra = None
    if refresh is not None:
        if len(refresh.occupancy) != occ.shape[-1]:
            raise ValueError(
                f"refresh carries {len(refresh.occupancy)} per-bin "
                f"occupancies for {occ.shape[-1]} effective bins"
            )
        occ_bins = jnp.asarray(refresh.occupancy, jnp.float32)   # (B+1,)
        kw = dict(split=True, refresh_occ=occ_bins[None, :],
                  trfc_ns=refresh.trfc_ns)
        sp_c = fleet_speedups(rows, cfg, workloads, **kw)        # (N, B+1)
        sp_c_mem = fleet_speedups(rows, cfg, MEM_INTENSIVE_WORKLOADS, **kw)
        extra = {
            "combined": (occ * sp_c).sum(axis=-1),               # (N,)
            "combined_mem": (occ * sp_c_mem).sum(axis=-1),
            "refresh_occ": (occ * occ_bins[None, :]).sum(axis=-1),
        }
    return occ, red, realized, realized_mem, tras_flags, extra


def trace_score_finalize(
    partials: ScorePartials,
    stack: Array,
    cfg: SystemConfig = MULTI_CORE,
    claim: float = PAPER_CLAIM_SPEEDUP,
    workloads: Tuple[Workload, ...] = WORKLOADS,
    mesh=None,
    refresh=None,
) -> Dict[str, float]:
    """Final score dict from accumulated partials + the table's registers.

    Produces exactly the :func:`trace_score` dict — ``trace_score`` is
    ``init → accumulate(whole trace) → finalize``, and a streamed replay's
    chunk-wise partials are bit-identical (see :class:`ScorePartials`), so
    streamed and materialized scores agree bitwise. ``mesh`` runs the
    per-DIMM finalize work gather-free over the ``"dimm"`` axis with
    mask-weighted psums, composing with a streamed ``replay_stream(mesh=)``
    whose partials stayed device-sharded.

    ``refresh`` — optional :class:`repro.core.refresh.BinRefresh`
    (hashable, so it keys the cached sharded runners): adds the combined
    latency+refresh figures (``refresh_occupancy_mean``,
    ``speedup_combined_*``) on top of the latency-only ones. The partials
    are refresh-agnostic — occupancy is a function of the selected bin —
    so the same accumulated partials score with or without refresh."""
    stack = jnp.asarray(stack, jnp.float32)
    stack = _with_access_axis(stack, split=(stack.ndim == 4))    # (N, B, 2, 4)
    n_dimms, n_bins = stack.shape[0], stack.shape[1]
    if partials.occupancy.shape != (n_dimms, n_bins + 1):
        raise ValueError(
            f"partials occupancy shape {partials.occupancy.shape} does not "
            f"match a {n_dimms}-DIMM, {n_bins}-bin table"
        )
    n_steps = int(partials.n_steps)
    if n_steps <= 0:
        raise ValueError("cannot finalize a score over zero observations")
    if mesh is not None:
        from repro.core import shard

        mask = shard.dimm_mask(
            n_dimms, shard.padded_size(n_dimms, shard.n_shards(mesh))
        )
        run = _sharded_finalize_runner(
            mesh, n_dimms, n_bins, cfg, workloads, refresh
        )
        sums = run(partials.occupancy, partials.switches,
                   partials.timing_sums, partials.n_steps, stack, mask)
        return _score_dict_from_sums(sums, n_dimms, n_steps, claim, refresh)
    occ, red, realized, realized_mem, tras_flags, extra = _score_figures(
        partials, stack, cfg, workloads, refresh
    )
    out = {
        "read_reduction_mean": float(red["read"].mean()),
        "write_reduction_mean": float(red["write"].mean()),
        "speedup_realized_mean": float(realized.mean() - 1.0),
        "speedup_realized_min": float(realized.min() - 1.0),
        "speedup_realized_intensive_mean": float(realized_mem.mean() - 1.0),
        # Degradation vs the paper's headline, on the claim's own cohort.
        "speedup_vs_claim": float(realized_mem.mean() - 1.0) - claim,
        "switches_total": float(partials.switches.sum()),
        "switches_per_dimm_mean": float(partials.switches.mean()),
        "switches_per_kstep": float(partials.switches.sum())
        / (n_steps * n_dimms / 1000.0),
        "time_at_jedec_frac": float(occ[:, n_bins].mean()),
        "time_in_coolest_bin_frac": float(occ[:, 0].mean()),
        "tras_below_jedec_coolest_frac": float(tras_flags.mean()),
    }
    if extra is not None:
        out.update({
            "refresh_occupancy_mean": float(extra["refresh_occ"].mean()),
            "speedup_combined_mean": float(extra["combined"].mean() - 1.0),
            "speedup_combined_min": float(extra["combined"].min() - 1.0),
            "speedup_combined_intensive_mean": float(
                extra["combined_mem"].mean() - 1.0
            ),
            "speedup_combined_vs_claim": float(
                extra["combined_mem"].mean() - 1.0
            ) - claim,
        })
    for access in ACCESS_TYPES:
        per = red[f"{access}_params"]                            # (N, 4)
        for pi, param in enumerate(PARAM_NAMES):
            out[f"{access}_{param}_reduction_mean"] = float(per[:, pi].mean())
    return out


def trace_score(
    stack: Array,
    replay,
    cfg: SystemConfig = MULTI_CORE,
    claim: float = PAPER_CLAIM_SPEEDUP,
    workloads: Tuple[Workload, ...] = WORKLOADS,
    mesh=None,
    refresh=None,
) -> Dict[str, float]:
    """Score a controller replay: realized latency/performance gains,
    switching activity, and degradation vs the paper's 14 % claim.

    ``stack`` is the table's ``(n_dimms, n_bins, 2, 4)`` per-access-type
    timing registers (a legacy merged ``(n_dimms, n_bins, 4)`` stack is
    duplicated); ``replay`` a :class:`repro.core.controller.ReplayResult`
    (duck-typed: ``timings``, ``bin_idx``, ``switched``). Internally this
    is the partial-accumulate/finalize split — :func:`trace_score_init` →
    :func:`trace_score_accumulate` (the whole trace as one chunk) →
    :func:`trace_score_finalize` — the same accumulators a streaming
    replay carries, so streamed scores match this bitwise. Alongside the
    Fig. 2 sum reductions, the per-parameter realized reductions of each
    access-type set are reported as ``{access}_{param}_reduction_mean``
    (the per-access-type register sets are the whole point — tRAS must
    show up reduced in the read set, not pinned at JEDEC by a merge).

    ``mesh`` — optional 1-D ``"dimm"`` mesh
    (:func:`repro.core.shard.fleet_mesh`): scoring then runs GATHER-FREE.
    Stack and replay outputs stay partitioned over the DIMM axis (pass the
    arrays of a ``replay(mesh=...)`` straight in); each shard accumulates
    its block's :class:`ScorePartials` locally and contributes
    mask-weighted partial sums combined with ``psum`` / ``pmin``, so no
    per-DIMM array is ever gathered to one device. Counts and
    integer-valued sums are exact; float means can differ from
    ``mesh=None`` only by cross-shard summation order (tested to ~1e-5
    relative).

    ``refresh`` — optional :class:`repro.core.refresh.BinRefresh`
    (typically ``table.bin_refresh()``): adds the combined
    latency+refresh figures; see :func:`trace_score_finalize`."""
    stack = jnp.asarray(stack, jnp.float32)
    # Fixed-rank input: rank 4 = (N, B, 2, 4) split registers, rank 3 =
    # legacy merged (N, B, 4) — decided by rank, never by axis extent.
    stack = _with_access_axis(stack, split=(stack.ndim == 4))    # (N, B, 2, 4)
    if mesh is not None:
        return _trace_score_sharded(
            stack, replay, cfg, claim, workloads, mesh, refresh
        )
    n_dimms, n_bins = stack.shape[0], stack.shape[1]
    partials = trace_score_accumulate(
        trace_score_init(n_dimms, n_bins),
        replay.timings,
        jnp.asarray(replay.bin_idx),
        jnp.asarray(replay.switched),
    )
    return trace_score_finalize(
        partials, stack, cfg, claim, workloads, refresh=refresh
    )


def _psum_score_partials(
    partials: ScorePartials,
    stack_l: Array,
    mask_l: Array,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...],
    refresh=None,
) -> Tuple:
    """Shard-local score figures → mask-weighted cross-device sums (the
    body both sharded entry points run under ``shard_map``). With
    ``refresh``, four more sums ride along (combined/combined-mem totals,
    combined pmin, occupancy total) — 15 instead of 11."""
    from repro.core import shard

    n_bins = stack_l.shape[1]
    m = mask_l.astype(jnp.float32)
    occ, red, realized, realized_mem, tras_flags, extra = _score_figures(
        partials, stack_l, cfg, workloads, refresh
    )

    def tot(x):
        return shard.psum(jnp.sum(x * m))

    per_access = tuple(
        shard.psum(jnp.sum(red[f"{a}_params"] * m[:, None], axis=0))
        for a in ACCESS_TYPES
    )
    refresh_sums = () if extra is None else (
        tot(extra["combined"]),
        tot(extra["combined_mem"]),
        shard.pmin(jnp.min(jnp.where(mask_l, extra["combined"], jnp.inf))),
        tot(extra["refresh_occ"]),
    )
    return (
        tot(red["read"]),
        tot(red["write"]),
        tot(realized),
        tot(realized_mem),
        shard.pmin(jnp.min(jnp.where(mask_l, realized, jnp.inf))),
        # Switch COUNT stays integer through the psum: a float32
        # accumulator would lose exactness above 2^24 switches, i.e.
        # exactly at the fleet scales this layer exists for.
        shard.psum(jnp.sum(jnp.where(mask_l, partials.switches, 0))),
        tot(occ[:, n_bins]),
        tot(occ[:, 0]),
        tot(tras_flags),
    ) + per_access + refresh_sums


def _score_dict_from_sums(
    sums: Tuple, n_dimms: int, n_steps: int, claim: float, refresh=None
) -> Dict[str, float]:
    """Assemble the score dict from the psum'd cross-shard sums (11
    refresh-free, 15 with refresh)."""
    (s_read, s_write, s_real, s_real_mem, real_min, s_switch,
     s_jedec, s_cool, s_tras, s_read_params, s_write_params) = sums[:11]
    n = float(n_dimms)
    out = {
        "read_reduction_mean": float(s_read) / n,
        "write_reduction_mean": float(s_write) / n,
        "speedup_realized_mean": float(s_real) / n - 1.0,
        "speedup_realized_min": float(real_min) - 1.0,
        "speedup_realized_intensive_mean": float(s_real_mem) / n - 1.0,
        "speedup_vs_claim": (float(s_real_mem) / n - 1.0) - claim,
        "switches_total": float(s_switch),
        "switches_per_dimm_mean": float(s_switch) / n,
        "switches_per_kstep": float(s_switch) / (n_steps * n / 1000.0),
        "time_at_jedec_frac": float(s_jedec) / n,
        "time_in_coolest_bin_frac": float(s_cool) / n,
        "tras_below_jedec_coolest_frac": float(s_tras) / n,
    }
    if refresh is not None:
        s_comb, s_comb_mem, comb_min, s_ref_occ = sums[11:]
        out.update({
            "refresh_occupancy_mean": float(s_ref_occ) / n,
            "speedup_combined_mean": float(s_comb) / n - 1.0,
            "speedup_combined_min": float(comb_min) - 1.0,
            "speedup_combined_intensive_mean": float(s_comb_mem) / n - 1.0,
            "speedup_combined_vs_claim": (float(s_comb_mem) / n - 1.0) - claim,
        })
    for access, sums_a in zip(ACCESS_TYPES, (s_read_params, s_write_params)):
        arr = np.asarray(sums_a)
        for pi, param in enumerate(PARAM_NAMES):
            out[f"{access}_{param}_reduction_mean"] = float(arr[pi]) / n
    return out


def _trace_score_sharded(
    stack: Array,
    replay,
    cfg: SystemConfig,
    claim: float,
    workloads: Tuple[Workload, ...],
    mesh,
    refresh=None,
) -> Dict[str, float]:
    """Gather-free :func:`trace_score`: each shard accumulates its block's
    :class:`ScorePartials` locally (full step axis, its slice of DIMMs),
    then the SAME sharded finalize the streamed path uses
    (:func:`trace_score_finalize` with ``mesh=``) masks out padding lanes
    and combines mask-weighted partial sums (and a ``pmin`` for the fleet
    minimum). Only O(1) scalars cross devices — and because accumulate and
    finalize are the identical compiled programs a chunked
    :func:`repro.core.stream.replay_stream` runs, streamed and
    materialized sharded scores agree BITWISE (not just to tolerance)."""
    n_dimms, n_bins = stack.shape[0], stack.shape[1]
    timings = jnp.asarray(replay.timings, jnp.float32)
    timings = _with_access_axis(timings, split=(timings.ndim == 4))
    run = _sharded_accumulate_runner(mesh, n_dimms, n_bins)
    partials = ScorePartials(*run(
        timings, jnp.asarray(replay.bin_idx), jnp.asarray(replay.switched)
    ))
    return trace_score_finalize(
        partials, stack, cfg, claim, workloads, mesh=mesh, refresh=refresh
    )


@functools.lru_cache(maxsize=16)
def _sharded_accumulate_runner(mesh, n_dimms: int, n_bins: int):
    """Cached sharded whole-trace partial accumulation: each shard sums its
    DIMM block's replay outputs into :class:`ScorePartials` leaves (the
    per-shard sums are exact — see the class notes — so chunking and
    sharding both commute with accumulation)."""
    from repro.core import shard

    def local(timings_l, bin_l, switched_l):
        return tuple(trace_score_accumulate(
            trace_score_init(timings_l.shape[1], n_bins),
            timings_l, bin_l, switched_l,
        ))

    return shard.sharded_dimm_map(
        local, mesh, in_axes=(1, 1, 1), out_axes=(0, 0, 0, None),
        n_dimms=n_dimms,
    )


@functools.lru_cache(maxsize=16)
def _sharded_finalize_runner(
    mesh,
    n_dimms: int,
    n_bins: int,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...],
    refresh=None,
):
    """Cached gather-free finalize for already-accumulated partials (the
    streamed path: :func:`trace_score_finalize` with ``mesh=``). Same
    shard-local body as the materialized sharded scorer, so a streamed
    score over the same mesh is bit-identical to the materialized one.
    ``refresh`` (a hashable :class:`repro.core.refresh.BinRefresh` or
    ``None``) keys the cache — refresh-on and refresh-off runners are
    distinct compiled programs with 15 vs 11 output sums."""
    from repro.core import shard

    def local(occ_l, switches_l, timing_sums_l, n_steps, stack_l, mask_l):
        partials = ScorePartials(occ_l, switches_l, timing_sums_l, n_steps)
        return _psum_score_partials(
            partials, stack_l, mask_l, cfg, workloads, refresh
        )

    n_out = 11 if refresh is None else 15
    return shard.sharded_dimm_map(
        local, mesh, in_axes=(0, 0, 0, None, 0, 0), out_axes=(None,) * n_out,
        n_dimms=n_dimms,
    )


# ---------------------------------------------------------------------------
# Region-resolved scoring (design-induced variation, schema-v5 tables)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_dimms", "n_bins", "n_regions"))
def region_counts_init(n_dimms: int, n_bins: int, n_regions: int) -> Array:
    """Zeroed ``(n_dimms, n_bins + 1, n_regions)`` int32 region-access
    counters (last bin column = the beyond-last-bin JEDEC sentinel).

    The region analogue of :func:`trace_score_init`: integer counts are
    exact under ANY accumulation order, so chunked/streamed accumulation
    is bit-identical to one materialized pass by construction."""
    return jnp.zeros((n_dimms, n_bins + 1, n_regions), jnp.int32)


@jax.jit
def region_counts_accumulate(
    counts: Array, bin_idx: Array, region_mix: Array
) -> Array:
    """Absorb a chunk of per-step region-access mixes at each step's
    effective bin.

    ``bin_idx`` is the ``(chunk, n_dimms)`` effective-bin trace (``n_bins``
    = the JEDEC sentinel, exactly what
    :class:`repro.core.controller.ReplayResult` records); ``region_mix``
    the ``(chunk, n_dimms, n_regions)`` int32 access counts per
    distance-from-sense-amp class. Each step's mix row lands in its
    effective bin's counter — the integer scatter that makes region
    scoring exact under any chunking or sharding."""
    n_bins1 = counts.shape[1]
    onehot = (
        bin_idx[:, :, None] == jnp.arange(n_bins1)[None, None, :]
    ).astype(jnp.int32)                                          # (S, N, B+1)
    mix = jnp.asarray(region_mix, jnp.int32)
    return counts + jnp.einsum("snb,snr->nbr", onehot, mix)


def _region_speedup_grids(
    region_stack: Array,
    cfg: SystemConfig,
    workloads: Tuple[Workload, ...],
) -> Tuple[Array, Array]:
    """Per-(DIMM, effective bin, region) speedups of a rank-5 register
    stack, plus the region-OBLIVIOUS per-(DIMM, effective bin) speedups of
    its max-over-regions rows. This is where the per-(DIMM, bin, region)
    timing lookup happens: each region's own profiled ``(2, 4)`` block is
    evaluated, not the worst-case merge."""
    n_dimms, _, n_regions = region_stack.shape[:3]
    jedec = jnp.asarray(list(JEDEC_DDR3_1600), jnp.float32)
    jedec_rows = jnp.broadcast_to(
        jedec, (n_dimms, 1, n_regions, len(ACCESS_TYPES), len(PARAM_NAMES))
    )
    rows = jnp.concatenate([region_stack, jedec_rows], axis=1)  # (N,B+1,R,2,4)
    sp = fleet_speedups(rows, cfg, workloads, split=True)       # (N, B+1, R)
    # The oblivious register set: the max over regions per (bin, access,
    # param) — the only single set safe for every region. Each region row
    # is elementwise <= this merge, and IPC is monotone non-increasing in
    # every timing parameter, so sp >= sp_obl[..., None] HOLDS ELEMENTWISE
    # — region-aware scores can only gain.
    sp_obl = fleet_speedups(
        rows.max(axis=2), cfg, workloads, split=True
    )                                                           # (N, B+1)
    return sp, sp_obl


def region_score_finalize(
    counts: Array,
    region_stack: Array,
    cfg: SystemConfig = MULTI_CORE,
    claim: float = PAPER_CLAIM_SPEEDUP,
    workloads: Tuple[Workload, ...] = WORKLOADS,
) -> Dict[str, float]:
    """Region-occupancy-weighted realized speedups from accumulated
    region-access counts + a region table's registers.

    ``counts`` — ``(n_dimms, n_bins + 1, n_regions)`` int32 accumulators
    (:func:`region_counts_init` → :func:`region_counts_accumulate`);
    ``region_stack`` — the table's rank-5 ``(n_dimms, n_bins, n_regions,
    2, 4)`` registers (:meth:`repro.core.controller.DimmTimingTable.region_stack`).

    Reports BOTH sides of the design-induced-variation argument:

    * ``speedup_region_aware_*`` — each access is served at ITS region's
      profiled timings (per-(DIMM, bin, region) lookup), weighted by the
      accumulated access counts.
    * ``speedup_region_oblivious_*`` — the same accesses all served at the
      max-over-regions register set (what a region-unaware controller must
      program).

    Aware >= oblivious holds unconditionally (elementwise speedup
    dominance, see :func:`_region_speedup_grids`); the GAP is what the
    region axis buys, and it grows with mix skew toward near regions."""
    region_stack = jnp.asarray(region_stack, jnp.float32)
    if region_stack.ndim != 5 or region_stack.shape[3:] != (
        len(ACCESS_TYPES), len(PARAM_NAMES)
    ):
        raise ValueError(
            f"region_stack must be (n_dimms, n_bins, n_regions, 2, 4), "
            f"got {region_stack.shape}"
        )
    n_dimms, n_bins, n_regions = region_stack.shape[:3]
    if counts.shape != (n_dimms, n_bins + 1, n_regions):
        raise ValueError(
            f"counts shape {counts.shape} does not match a {n_dimms}-DIMM, "
            f"{n_bins}-bin, {n_regions}-region table"
        )
    w = counts.astype(jnp.float32)                              # (N, B+1, R)
    total = w.sum(axis=(1, 2))                                  # (N,)
    if bool((total <= 0).any()):
        raise ValueError("cannot finalize a region score with zero accesses")
    sp, sp_obl = _region_speedup_grids(region_stack, cfg, workloads)
    sp_m, sp_obl_m = _region_speedup_grids(
        region_stack, cfg, MEM_INTENSIVE_WORKLOADS
    )
    aware = (w * sp).sum(axis=(1, 2)) / total                   # (N,)
    aware_m = (w * sp_m).sum(axis=(1, 2)) / total
    obl = (w.sum(axis=2) * sp_obl).sum(axis=1) / total
    obl_m = (w.sum(axis=2) * sp_obl_m).sum(axis=1) / total
    near_frac = w[:, :, 0].sum(axis=1) / total
    return {
        "n_regions": float(n_regions),
        "region_accesses_total": float(np.asarray(counts, np.int64).sum()),
        "nearest_region_access_frac": float(near_frac.mean()),
        "speedup_region_aware_mean": float(aware.mean() - 1.0),
        "speedup_region_aware_min": float(aware.min() - 1.0),
        "speedup_region_aware_intensive_mean": float(aware_m.mean() - 1.0),
        "speedup_region_oblivious_mean": float(obl.mean() - 1.0),
        "speedup_region_oblivious_intensive_mean": float(obl_m.mean() - 1.0),
        "region_aware_advantage_intensive": float((aware_m - obl_m).mean()),
        "speedup_region_aware_vs_claim": float(aware_m.mean() - 1.0) - claim,
    }


def region_trace_score(
    region_stack: Array,
    replay,
    region_mix: Array,
    cfg: SystemConfig = MULTI_CORE,
    claim: float = PAPER_CLAIM_SPEEDUP,
    workloads: Tuple[Workload, ...] = WORKLOADS,
) -> Dict[str, float]:
    """Score a materialized replay against a region table given the
    trace's per-step region-access mix.

    ``replay`` — a :class:`repro.core.controller.ReplayResult` (duck-typed:
    only ``bin_idx``, the effective-bin history, is consumed — bin
    dynamics depend only on temperature, so the SAME replay scores any
    region resolution); ``region_mix`` — ``(n_steps, n_dimms, n_regions)``
    int32 per-step access counts (:func:`repro.core.traces.region_access_mix`).
    Internally init → accumulate (whole trace) → finalize, the same
    integer accumulators a streamed replay carries chunk-wise
    (:func:`repro.core.stream.replay_stream` with ``region_mix=``), so
    streamed region scores match this bitwise."""
    region_stack = jnp.asarray(region_stack, jnp.float32)
    if region_stack.ndim != 5:
        raise ValueError(
            f"region_stack must be rank-5, got {region_stack.shape}; "
            "pass DimmTimingTable.region_stack()"
        )
    n_dimms, n_bins, n_regions = region_stack.shape[:3]
    counts = region_counts_accumulate(
        region_counts_init(n_dimms, n_bins, n_regions),
        jnp.asarray(replay.bin_idx),
        jnp.asarray(region_mix, jnp.int32),
    )
    return region_score_finalize(counts, region_stack, cfg, claim, workloads)


def per_workload_speedups(
    cfg: SystemConfig,
    reductions: Dict[str, float] = DEPLOYED_REDUCTIONS_55C,
    workloads: Tuple[Workload, ...] = WORKLOADS,
) -> List[Tuple[str, float]]:
    base = evaluate(JEDEC_DDR3_1600, cfg, workloads)["ipc"]
    fast = evaluate(JEDEC_DDR3_1600.reduced(reductions), cfg, workloads)["ipc"]
    sp = fast / base - 1.0
    return [(w.name, float(sp[i])) for i, w in enumerate(workloads)]
