"""The plain reference of the AL-DRAM fleet controller.

Written from the model's equations, with nothing imported from the program
and nothing taken from what the program made: the reference sweeps its own
timing table from the population, replays its own telemetry and scores its
own partials.

* :func:`table` — the boot characterization: for every DIMM and
  temperature bin, the smallest cycle-quantized tRCD/tRAS/tWR/tRP that
  passes the forward read- and write-correctness predicates of the charge
  model (paper §1.3, §1.5), one parameter at a time with the others at
  JEDEC DDR3-1600; the read set takes tWR from the write test. Run on the
  device in blocks of DIMMs.
* :func:`replay` — the runtime selector over the telemetry: error fuse,
  guard band, hysteresis (paper §5), with per-DIMM occupancy per effective
  bin and switch counts; the timing sums are occupancy times the bin's
  registers, which is exact because every timing is a multiple of tCK.
* :func:`score` — the realized-speedup score from partials, with the
  analytic memory-system model of the paper's §1.6 evaluation, in float64
  on the host, once per distinct timing row.

``dtype`` selects the arithmetic of the device parts: float32 is the
reference; bfloat16 is the control, the step below the program's float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --- DDR3-1600 ---------------------------------------------------------------
TCK = 1.25
#: JEDEC DDR3-1600 (trcd, tras, twr, trp), ns.
JEDEC = (13.75, 35.0, 15.0, 13.75)
TCL = 13.75
TBURST = 5.0
TRTP = 7.5

# --- charge model (calibrated constants, paper §1.3) ------------------------
WINDOW_S = 64e-3
T_WORST = 85.0
EXT_BOUNDARY = 85.0
EXT_FACTOR = 0.5
EPS = 1e-4
#: The reference's own rounding: a threshold moved by this share gives the
#: band of answers float32 arithmetic can give at a cell that sits on it.
#: Answers inside the band are the reference's; see :func:`table`. On the
#: TPU, whose float32 log errs by up to about 1e-4, this reference and the
#: program part at cells up to about 2e-6 of a threshold apart (one
#: register in 1.6e9 over 40 seeds of warehouse-1m past 1e-6, none past
#: 2e-6), so the band is five times that; the bfloat16 control still puts
#: about 2.4 million of a fleet's 40 million registers outside it.
SLACK = 1e-5
RET85 = 0.9282
LEAK_DOUBLING = 7.24
V_FULL = 0.975
V_SENSE = 0.75
CS_ALPHA = 0.20
C_MIN = 0.70
R_MAX = 1.45
OVH_RCD, OVH_RAS, OVH_WR, OVH_RP = 3.0, 6.0, 2.0, 3.5
V_RESTORE_START = 0.55
V_OVERDRIVE = 0.9830
MOBILITY_EXP = 1.418
DELTA_FLOOR = 0.010
PC_VAR = 1.011
PC_TEMP = 0.672
V_HALF = 0.50
WM_GAIN_RCD = 2.186
WM_TEMP = 1.26
WM_GAIN_RP = 2.709
DV_FLOOR = CS_ALPHA * C_MIN * V_FULL * RET85


def _taus():
    """Time constants anchored so the worst cell at 85 °C needs JEDEC."""
    t_sense = JEDEC[0] - OVH_RCD
    tau_sa = t_sense / (R_MAX * jnp.log(V_SENSE / DV_FLOOR))
    tau_restore = (JEDEC[1] - OVH_RAS - t_sense) / (
        R_MAX * jnp.log((1.0 - V_RESTORE_START) / (1.0 - V_FULL)))
    tau_write = (JEDEC[2] - OVH_WR) / (
        R_MAX * jnp.log(V_OVERDRIVE / (V_OVERDRIVE - V_FULL)))
    tau_bl = (JEDEC[3] - OVH_RP) / (
        R_MAX * jnp.log(V_HALF / DELTA_FLOOR))
    return tau_sa, tau_restore, tau_write, tau_bl


def _retention(leak, t):
    scale = 2.0 ** ((t - T_WORST) / LEAK_DOUBLING)
    window = jnp.where(t > EXT_BOUNDARY, EXT_FACTOR, 1.0).astype(t.dtype)
    return jnp.exp(jnp.log(RET85) * leak * scale * window)


def _sense_time(r, dv0, tau_sa):
    return r * tau_sa * jnp.log(V_SENSE / dv0)


def _restore_target(c, ret):
    v_needed = DV_FLOOR / (CS_ALPHA * c * ret)
    return jnp.clip(v_needed, V_RESTORE_START + 0.02, V_FULL)


def _residual(r, t):
    q = (R_MAX - r) / (R_MAX - 1.0)
    dt = (T_WORST - t) / 30.0
    return DELTA_FLOOR * jnp.exp(PC_VAR * q + PC_TEMP * dt)


def _wm_dv0(c, ret, t):
    dt = (T_WORST - t) / 30.0
    dv0 = CS_ALPHA * c * (V_FULL * ret)
    dv0 = dv0 * WM_GAIN_RCD * jnp.exp(WM_TEMP * dt)
    return jnp.minimum(dv0, V_SENSE * 0.95)


def _read_ok(r, c, leak, t, trcd, tras, trp, slack):
    tau_sa, tau_restore, _, tau_bl = _taus()
    ret = _retention(leak, t)
    dv0 = CS_ALPHA * c * (V_FULL * ret)
    dv_reached = dv0 * jnp.exp((trcd - OVH_RCD) / (r * tau_sa))
    sense = dv_reached >= V_SENSE * (1.0 - EPS - slack)
    avail = tras - OVH_RAS - _sense_time(r, dv0, tau_sa)
    v_reached = 1.0 - (1.0 - V_RESTORE_START) * jnp.exp(
        -jnp.maximum(avail, 0.0) / (r * tau_restore))
    restore = v_reached >= _restore_target(c, ret) * (1.0 - EPS - slack)
    delta = V_HALF * jnp.exp(-(trp - OVH_RP) / (r * tau_bl))
    ok = jnp.minimum(_residual(r, t), 0.4 * V_HALF)
    return sense & restore & (delta <= ok * (1.0 + EPS + slack))


def _write_ok(r, c, leak, t, trcd, tras, twr, trp, slack):
    tau_sa, _, tau_write, tau_bl = _taus()
    ret = _retention(leak, t)
    drive = ((t + 273.15) / (T_WORST + 273.15)) ** MOBILITY_EXP
    tau_wr = r * tau_write * drive
    v_tgt = _restore_target(c, ret)
    v_reached = V_OVERDRIVE * (1.0 - jnp.exp(-(twr - OVH_WR) / tau_wr))
    write = v_reached >= v_tgt * (1.0 - EPS - slack)
    dv0w = _wm_dv0(c, ret, t)
    avail = tras - OVH_RAS - _sense_time(r, dv0w, tau_sa)
    v_row = V_OVERDRIVE - (V_OVERDRIVE - V_RESTORE_START) * jnp.exp(
        -jnp.maximum(avail, 0.0) / tau_wr)
    restore = v_row >= v_tgt * (1.0 - EPS - slack)
    min_trcd = OVH_RCD + _sense_time(r, dv0w, tau_sa)
    delta = jnp.minimum(_residual(r, t) * WM_GAIN_RP, 0.4 * V_HALF)
    min_trp = OVH_RP + r * tau_bl * jnp.log(V_HALF / delta)
    return (write & restore & (trcd >= min_trcd * (1.0 - EPS - slack))
            & (trp >= min_trp * (1.0 - EPS - slack)))


def grid_size(jedec_ns: float) -> int:
    return int(round(jedec_ns / TCK + 0.5))


def _min_safe(ok_at, jedec_ns: float, dtype):
    """Smallest grid value (1 cycle .. JEDEC) for which ``ok_at`` holds;
    JEDEC's grid point when none does."""
    n = grid_size(jedec_ns)
    grid = jnp.arange(1, n + 1, dtype=dtype) * jnp.asarray(TCK, dtype)
    ok = jax.vmap(ok_at)(grid)
    idx = jnp.where(ok.any(axis=0), jnp.argmax(ok, axis=0), n - 1)
    return grid[idx]


@functools.partial(jax.jit, static_argnames=("dtype", "slack"))
def _table_block(r, c, leak, temps, patterns, dtype, slack):
    """(Nb, T, 2, 4) registers of one block of DIMMs at the worst-case
    (first) data pattern."""
    r, c, leak = (x.astype(dtype) for x in (r, c, leak))
    c = c * patterns[0].astype(dtype)
    jr, ja, jw, jp = (jnp.asarray(v, dtype) for v in JEDEC)

    def at(t):
        t = t.astype(dtype)
        rd = lambda trcd=jr, tras=ja, trp=jp: _read_ok(
            r, c, leak, t, trcd, tras, trp, slack)
        wr = lambda trcd=jr, tras=ja, twr=jw, trp=jp: _write_ok(
            r, c, leak, t, trcd, tras, twr, trp, slack)
        w_twr = _min_safe(lambda v: wr(twr=v), JEDEC[2], dtype)
        read = [_min_safe(lambda v: rd(trcd=v), JEDEC[0], dtype),
                _min_safe(lambda v: rd(tras=v), JEDEC[1], dtype),
                w_twr,
                _min_safe(lambda v: rd(trp=v), JEDEC[3], dtype)]
        write = [_min_safe(lambda v: wr(trcd=v), JEDEC[0], dtype),
                 _min_safe(lambda v: wr(tras=v), JEDEC[1], dtype),
                 w_twr,
                 _min_safe(lambda v: wr(trp=v), JEDEC[3], dtype)]
        return jnp.stack([jnp.stack(read, -1), jnp.stack(write, -1)], -2)

    return jnp.moveaxis(jax.lax.map(at, temps), 0, 1)      # (Nb, T, 2, 4)


def table(cells, temp_bins: Sequence[float], patterns: Sequence[float],
          dtype=jnp.float32, slack: float = 0.0,
          block: int = 1 << 17) -> np.ndarray:
    """(N, T, 2, 4) float32 registers on the host, swept on the device.

    ``slack`` moves every threshold by that share: ``+SLACK`` passes a
    cell a hair sooner (the low edge of the band), ``-SLACK`` a hair later
    (the high edge). A register between the two edges differs from the
    reference only by where float32 rounding put a cell that sits on a
    threshold."""
    temps = jnp.asarray(temp_bins, jnp.float32)
    pats = jnp.asarray(patterns, jnp.float32)
    n = int(cells.r.shape[0])
    out = np.empty((n, len(temp_bins), 2, 4), np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        got = _table_block(cells.r[lo:hi], cells.c[lo:hi], cells.leak[lo:hi],
                           temps, pats, dtype, float(slack))
        out[lo:hi] = np.asarray(got.astype(jnp.float32))
    return out


def band(cells, temp_bins, patterns, dtype=jnp.float32):
    """The reference table and its band's low and high edges."""
    return tuple(table(cells, temp_bins, patterns, dtype, s)
                 for s in (0.0, SLACK, -SLACK))


# --- the runtime selector ----------------------------------------------------
class State(NamedTuple):
    bin_idx: np.ndarray     # (N,) int32, n_bins = the JEDEC sentinel
    cool_streak: np.ndarray  # (N,) int32
    fused: np.ndarray       # (N,) bool


class Partials(NamedTuple):
    occupancy: np.ndarray    # (N, B + 1) int32 steps per effective bin
    switches: np.ndarray     # (N,) int32
    timing_sums: np.ndarray  # (N, 2, 4) float32 ns
    n_steps: int


@functools.partial(jax.jit, static_argnames=("hyst_steps", "dtype"))
def _replay_chunk(carry, temps, errors, n_valid, edges, guard, hyst,
                  hyst_steps, dtype):
    """Advance the fleet over the first ``n_valid`` steps of a chunk.
    Returns the carry and per-step (effective bin, switched)."""
    n_bins = edges.shape[0]
    edges = edges.astype(dtype)
    big = jnp.asarray(jnp.finfo(dtype).max, dtype)

    def body(carry, xs):
        b, streak, fused, occ, switches = carry
        s, temp, err = xs
        valid = s < n_valid
        fused_n = fused | err
        t_eff = temp.astype(dtype) + jnp.asarray(guard, dtype)
        target = jnp.sum(edges[None, :] < t_eff[:, None], axis=1,
                         dtype=jnp.int32)
        hotter = target > b
        cooler = target < b
        edge = jnp.where(target < n_bins,
                         edges[jnp.minimum(target, n_bins - 1)], big)
        calm = t_eff <= edge - jnp.asarray(hyst, dtype)
        streak_c = jnp.where(calm, streak + 1, 0)
        recover = cooler & (streak_c >= hyst_steps)
        move = hotter | recover
        b_n = jnp.where(fused_n, b, jnp.where(move, target, b))
        streak_n = jnp.where(fused_n, streak,
                             jnp.where(cooler & ~recover, streak_c, 0))
        switched = move & ~fused_n
        eff = jnp.where(fused_n, n_bins, b_n)
        occ_n = occ + (eff[:, None] == jnp.arange(n_bins + 1)[None, :])
        keep = lambda new, old: jnp.where(valid, new, old)
        carry = (keep(b_n, b), keep(streak_n, streak), keep(fused_n, fused),
                 keep(occ_n, occ), keep(switches + switched, switches))
        return carry, (eff.astype(jnp.int8), switched)

    steps = jnp.arange(temps.shape[0])
    return jax.lax.scan(body, carry, (steps, temps, errors))


def replay(table_: np.ndarray, temp_bins: Sequence[float], controller: dict,
           chunks: Iterable[Tuple[jax.Array, jax.Array, int]],
           keep_steps: Sequence[int] = (), dtype=jnp.float32):
    """Run the selector over ``chunks`` of ``(temps, errors, n_valid)``
    (device arrays, step-major; only the first ``n_valid`` steps count).

    Returns ``(state, partials, decisions)``; ``decisions`` maps each step
    in ``keep_steps`` to its ``(rows (N, 2, 4), eff (N,), switched (N,))``.
    """
    n, n_bins = table_.shape[0], table_.shape[1]
    carry = (jnp.full((n,), n_bins - 1, jnp.int32), jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), bool), jnp.zeros((n, n_bins + 1), jnp.int32),
             jnp.zeros((n,), jnp.int32))
    edges = jnp.asarray(temp_bins, jnp.float32)
    keep = sorted(set(int(k) for k in keep_steps))
    kept = {}
    step0 = 0
    for temps, errors, n_valid in chunks:
        carry, (eff, switched) = _replay_chunk(
            carry, temps, errors, jnp.int32(n_valid), edges,
            float(controller["guard_band_c"]), float(controller["hysteresis_c"]),
            int(controller["hysteresis_steps"]), dtype)
        for k in keep:
            if step0 <= k < step0 + n_valid:
                kept[k] = (np.asarray(eff[k - step0]).astype(np.int32),
                           np.asarray(switched[k - step0]))
        step0 += n_valid
    b, streak, fused, occ, switches = (np.asarray(x) for x in carry)
    full = with_jedec(table_)
    sums = timing_sums(occ, full, dtype)
    every = np.arange(n)
    decisions = {k: (full[every, e], e, s) for k, (e, s) in kept.items()}
    return (State(b, streak, fused), Partials(occ, switches, sums, step0),
            decisions)


def jedec_rows(dtype=np.float32) -> np.ndarray:
    return np.asarray([JEDEC, JEDEC], dtype)


def with_jedec(table_: np.ndarray) -> np.ndarray:
    """(N, B + 1, 2, 4): the table with the JEDEC sentinel bin appended."""
    jedec = np.broadcast_to(jedec_rows(), (table_.shape[0], 1, 2, 4))
    return np.concatenate([table_, jedec], axis=1)


def timing_sums(occ: np.ndarray, full: np.ndarray, dtype) -> np.ndarray:
    """Σ over effective bins of steps × registers. Exact in float32: the
    registers are multiples of tCK = 1.25 ns, so every product and partial
    sum is a multiple of 0.25 far below 2**22."""
    o = jnp.asarray(occ).astype(dtype)
    f = jnp.asarray(full).astype(dtype)
    out = jnp.zeros(full.shape[:1] + full.shape[2:], dtype)
    for b in range(full.shape[1]):
        out = out + o[:, b, None, None] * f[:, b]
    return np.asarray(out.astype(jnp.float32))


# --- the score (paper §1.6 memory-system model), float64 on the host --------
#: name, MPKI, row-hit fraction, write fraction, MLP, memory-intensive.
WORKLOADS = (
    ("stream.copy", 70.0, 0.42, 0.45, 10.0, True),
    ("stream.scale", 70.0, 0.42, 0.45, 10.0, True),
    ("stream.add", 70.0, 0.42, 0.33, 10.0, True),
    ("stream.triad", 70.0, 0.42, 0.33, 10.0, True),
    ("mcf", 67.0, 0.38, 0.28, 6.0, True),
    ("lbm", 45.0, 0.52, 0.42, 7.0, True),
    ("libquantum", 50.0, 0.65, 0.20, 7.5, True),
    ("milc", 29.0, 0.48, 0.30, 5.0, True),
    ("soplex", 27.0, 0.45, 0.25, 4.5, True),
    ("GemsFDTD", 25.0, 0.50, 0.33, 5.0, True),
    ("omnetpp", 21.0, 0.30, 0.30, 3.0, True),
    ("leslie3d", 20.0, 0.52, 0.35, 4.5, True),
    ("bwaves", 18.0, 0.55, 0.30, 5.0, True),
    ("sphinx3", 13.0, 0.46, 0.15, 3.0, True),
    ("zeusmp", 12.0, 0.48, 0.35, 3.5, True),
    ("cactusADM", 11.0, 0.42, 0.35, 2.5, True),
    ("astar", 10.5, 0.35, 0.25, 2.0, True),
    ("wrf", 10.0, 0.50, 0.30, 3.0, True),
    ("xalancbmk", 10.0, 0.32, 0.25, 2.5, True),
    ("gcc", 10.2, 0.40, 0.30, 2.5, True),
    ("bzip2", 11.5, 0.42, 0.35, 2.5, True),
    ("perlbench", 2.7, 0.45, 0.30, 1.5, False),
    ("gobmk", 1.8, 0.40, 0.30, 1.5, False),
    ("sjeng", 1.5, 0.38, 0.30, 1.4, False),
    ("h264ref", 2.4, 0.50, 0.25, 1.8, False),
    ("hmmer", 2.1, 0.52, 0.30, 1.8, False),
    ("namd", 1.2, 0.50, 0.25, 1.5, False),
    ("povray", 0.45, 0.45, 0.25, 1.2, False),
    ("calculix", 1.05, 0.50, 0.28, 1.5, False),
    ("gamess", 0.6, 0.45, 0.25, 1.2, False),
    ("gromacs", 2.7, 0.50, 0.28, 1.8, False),
    ("tonto", 1.8, 0.48, 0.27, 1.5, False),
    ("dealII", 3.0, 0.50, 0.28, 1.8, False),
    ("sixtrack", 0.6, 0.45, 0.25, 1.2, False),
    ("wupwise", 3.6, 0.52, 0.30, 2.0, False),
)
#: The evaluated four-core system (one rank, one channel) and its
#: calibrated constants.
SYSTEM = dict(n_cores=4, n_banks=8, bank_balance=0.55, cpu_ghz=3.2,
              cpi_exe=0.5, ctrl_overhead_ns=14.0, empty_frac=0.35,
              ras_residual=0.35, wr_turnaround=0.55, rho_max=0.995,
              bisect_iters=60)
#: The paper's headline: +14 % for memory-intensive workloads.
CLAIM = 0.14


def ipc(rows: np.ndarray) -> np.ndarray:
    """(K, W) IPC of each workload under each (2, 4) register set: the
    fixed point ipc = 1 / cpi(ipc), by bisection."""
    s = SYSTEM
    w = np.asarray([x[1:5] for x in WORKLOADS], np.float64)
    mpki, hit, wf, mlp = (w[:, i][None, :] for i in range(4))
    rd = [rows[:, 0, i][:, None] for i in range(4)]
    wr = [rows[:, 1, i][:, None] for i in range(4)]
    miss = 1.0 - hit
    empty = s["empty_frac"] * miss
    conflict = miss - empty
    trcd = (1.0 - wf) * rd[0] + wf * wr[0]
    trp = (1.0 - wf) * rd[3] + wf * wr[3]
    ras_extra = s["ras_residual"] * (
        (1.0 - wf) * np.maximum(rd[1] - (rd[0] + TCL + TBURST), 0.0)
        + wf * np.maximum(wr[1] - (wr[0] + TCL + TBURST), 0.0))
    wr_extra = s["wr_turnaround"] * wf * wr[2]
    lat = (hit * (TCL + TBURST) + empty * (trcd + TCL + TBURST)
           + conflict * (trp + trcd + TCL + TBURST + ras_extra + wr_extra)
           + s["ctrl_overhead_ns"])
    miss_c = np.maximum(miss, 1e-9)
    empty_c = s["empty_frac"] * miss_c
    occ_read = np.maximum(rd[1], rd[0] + TRTP) + rd[3]
    occ_write = np.maximum(wr[1], wr[0] + TRTP) + wr[3]
    svc = (empty_c * (trcd + TBURST) + (miss_c - empty_c) * (
        (1.0 - wf) * occ_read + wf * occ_write + wr_extra)) / miss_c
    banks = s["n_banks"] * s["bank_balance"]

    def cpi(x):
        rate = s["n_cores"] * x * s["cpu_ghz"] * mpki * 1e-3
        rho_b = np.clip(rate * miss * svc / banks, 0.0, s["rho_max"])
        rho_d = np.clip(rate * TBURST, 0.0, s["rho_max"])
        queue = (rho_b / (1.0 - rho_b) * svc * 0.5
                 + rho_d / (1.0 - rho_d) * TBURST * 0.5)
        return s["cpi_exe"] + mpki * 1e-3 * (lat + queue) * s["cpu_ghz"] / mlp

    lo = np.full(lat.shape, 1e-4)
    hi = np.full(lat.shape, 1.0 / s["cpi_exe"])
    for _ in range(s["bisect_iters"]):
        mid = 0.5 * (lo + hi)
        up = 1.0 / cpi(mid) > mid
        lo, hi = np.where(up, mid, lo), np.where(up, hi, mid)
    return 0.5 * (lo + hi)


def _unique_rows(flat: np.ndarray):
    """Distinct (2, 4) register sets and the index of each row's set.
    Registers are whole cycles below 64, so a set packs into one integer."""
    cycles = np.rint(flat / TCK)
    if np.array_equal(cycles * TCK, flat) and cycles.min() >= 0 and cycles.max() < 64:
        code = (cycles.reshape(len(flat), -1).astype(np.int64)
                << (6 * np.arange(8, dtype=np.int64))).sum(axis=1)
        _, first, inv = np.unique(code, return_index=True, return_inverse=True)
        return flat[first], inv
    return np.unique(flat, axis=0, return_inverse=True)


def speedups(rows: np.ndarray):
    """Geometric-mean speedup over JEDEC of each (..., 2, 4) register set,
    over all workloads and over the memory-intensive ones."""
    flat = rows.reshape(-1, 2, 4).astype(np.float64)
    uniq, inv = _unique_rows(flat)
    ratio = ipc(uniq) / ipc(jedec_rows(np.float64)[None])
    logr = np.log(ratio)
    mem = np.asarray([x[5] for x in WORKLOADS])
    sp = np.exp(logr.mean(axis=1))[inv.reshape(-1)]
    sp_mem = np.exp(logr[:, mem].mean(axis=1))[inv.reshape(-1)]
    return sp.reshape(rows.shape[:-2]), sp_mem.reshape(rows.shape[:-2])


PARAMS = ("trcd", "tras", "twr", "trp")


def score(partials: Partials, table_: np.ndarray) -> Dict[str, float]:
    """The running score over the replayed steps, float64."""
    n_steps = float(partials.n_steps)
    n, n_bins = table_.shape[0], table_.shape[1]
    occ = partials.occupancy.astype(np.float64) / n_steps
    sums = partials.timing_sums.astype(np.float64)
    mean = sums / n_steps
    jedec = np.asarray(JEDEC)
    read = 1.0 - (sums[:, 0, 0] + sums[:, 0, 1] + sums[:, 0, 3]) / n_steps / (
        JEDEC[0] + JEDEC[1] + JEDEC[3])
    write = 1.0 - (sums[:, 1, 0] + sums[:, 1, 2] + sums[:, 1, 3]) / n_steps / (
        JEDEC[0] + JEDEC[2] + JEDEC[3])
    sp, sp_mem = speedups(with_jedec(table_))
    realized = (occ * sp).sum(axis=1)
    realized_mem = (occ * sp_mem).sum(axis=1)
    switches = float(partials.switches.astype(np.int64).sum())
    out = {
        "read_reduction_mean": read.mean(),
        "write_reduction_mean": write.mean(),
        "speedup_realized_mean": realized.mean() - 1.0,
        "speedup_realized_min": realized.min() - 1.0,
        "speedup_realized_intensive_mean": realized_mem.mean() - 1.0,
        "speedup_vs_claim": realized_mem.mean() - 1.0 - CLAIM,
        "switches_total": switches,
        "switches_per_dimm_mean": switches / n,
        "switches_per_kstep": switches / (n_steps * n / 1000.0),
        "time_at_jedec_frac": occ[:, n_bins].mean(),
        "time_in_coolest_bin_frac": occ[:, 0].mean(),
        "tras_below_jedec_coolest_frac": (
            table_[:, 0, 0, 1] < JEDEC[1] - 1e-6).mean(),
    }
    for a, access in enumerate(("read", "write")):
        red = 1.0 - mean[:, a, :] / jedec[None, :]
        for p, param in enumerate(PARAMS):
            out[f"{access}_{param}_reduction_mean"] = red[:, p].mean()
    return {k: float(v) for k, v in out.items()}


#: What each score entry is offset from the mean behind it: a speedup
#: entry is a mean less 1 (less the claim, for ``speedup_vs_claim``).
SCORE_OFFSETS = {"speedup_realized_mean": 1.0, "speedup_realized_min": 1.0,
                 "speedup_realized_intensive_mean": 1.0,
                 "speedup_vs_claim": 1.0 + CLAIM}
#: Quantities smaller than this are compared by their absolute gap.
SCORE_FLOOR = 1e-2


def score_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Largest relative gap between the means behind two score dicts; a
    key that is missing on either side is a gap of infinity."""
    if set(got) != set(want):
        return float("inf")
    gap = 0.0
    for k, w in want.items():
        off = SCORE_OFFSETS.get(k, 0.0)
        g, w = float(got[k]) + off, float(w) + off
        if not np.isfinite(g):
            return float("inf")
        gap = max(gap, abs(g - w) / max(abs(w), SCORE_FLOOR))
    return gap
