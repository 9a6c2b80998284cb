"""Run the AL-DRAM fleet controller's main path once on a TPU and check it.

    python chip_smoke.py                # one chip: boot sweep + service
    python chip_smoke.py --four-chips   # four chips: the ("dimm",) mesh only

One chip. A fleet of 1,000,000 DIMMs (the warehouse fleet) is
characterized by ``fleet.sweep`` on the compiled ``charge_sweep`` kernel
and turned into its ``DimmTimingTable``. Two ``FleetControllerService``
instances, one on the fused ``replay_step`` kernel and one on the ref
chunk scan, then take the same four 256-step chunks of diurnal telemetry
with injected errors, one decision-emitting 8-step chunk, and
``running_score()``. Checked exactly: the kernel sweep against the ref
sweep; the kernel service against the ref service (state, score
partials, score dict, decisions); eight DIMMs against the plain
``ALDRAMController.observe`` loop.

Four chips. A fleet of 4 × 2**20 DIMMs is swept and served on the
``("dimm",)`` mesh and compared with single-device runs of each quarter
of the fleet on device 0: sweep, state and partials bitwise, the means
behind the fleet score within the cross-mesh tolerance (rtol 1e-5,
atol 1e-6).

Every kernel runs compiled (``interpret=False``) and each kernel program
is checked for its ``tpu_custom_call``. The script exits non-zero, and
prints no result, when JAX finds no TPU or any check fails. The last line
of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import controller, fleet, shard, traces  # noqa: E402
from repro.core.controller import (  # noqa: E402
    ALDRAMController,
    ControllerParams,
    ControllerState,
    DimmTimingTable,
)
from repro.core.perfmodel import PAPER_CLAIM_SPEEDUP, ScorePartials  # noqa: E402
from repro.core.timing import PARAM_NAMES  # noqa: E402
from repro.kernels.replay_step import pallas_chunk_scan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve_fleet import FleetControllerService  # noqa: E402

N_DIMMS = 1_000_000
CHUNK_STEPS = 256
N_CHUNKS = 4
DECISION_STEPS = 8
ERROR_RATE = 1e-5
N_OBSERVED = 8
MESH_CHIPS = 4
SHARD_DIMMS = 1 << 20
TEMPS_C = controller.DEFAULT_TEMP_BINS
PATTERNS = (1.0,)
#: Cross-mesh score tolerance: the sharded finalize sums per shard and
#: then across shards, so float means may differ in summation order only.
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-6


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(count: int) -> dict:
    """The device record for the result line; exits when the first device
    is not a TPU or fewer than ``count`` chips are visible."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r})"
        )
    if len(devices) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} TPU chips, JAX found {len(devices)}"
        )
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def check_kernel_program(what: str, lowered, interpret: bool) -> None:
    """A compiled kernel lowers to a ``tpu_custom_call``; an interpreted
    one does not. Refuses a program that is not what was asked for."""
    compiled = "tpu_custom_call" in lowered.as_text()
    if compiled == interpret:
        raise AssertionError(
            f"{what}: asked for interpret={interpret}, but the program "
            f"{'has' if compiled else 'has no'} tpu_custom_call"
        )


def assert_bitwise(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(
            f"{what}: {got.dtype}{got.shape} != {want.dtype}{want.shape}"
        )
    if got.tobytes() != want.tobytes():
        bad = got != want
        diff = np.abs(got.astype(np.float64) - want.astype(np.float64)).max()
        raise AssertionError(
            f"{what}: {int(bad.sum())} of {bad.size} entries differ, "
            f"largest difference {diff}"
        )


def assert_scores_bitwise(what: str, got: dict, want: dict) -> None:
    if set(got) != set(want):
        raise AssertionError(f"{what}: score keys differ")
    bad = {k: (got[k], want[k]) for k in want
           if np.float64(got[k]).tobytes() != np.float64(want[k]).tobytes()}
    if bad:
        raise AssertionError(f"{what}: score differs: {bad}")


def peak_bytes(device):
    """``peak_bytes_in_use`` where the backend reports it (TPU), else None."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def to_host(tree):
    return jax.tree.map(np.asarray, tree)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def boot_sweep(fl: fleet.Fleet, interpret: bool, mesh=None):
    """``fleet.sweep`` on the charge-sweep kernel (timed: first call with
    compile, then warm), checked exactly against the ref sweep unless a
    mesh is given. Returns the kernel's SweepResult."""

    def kernel_sweep(cells):
        # Single-device program: under a mesh each shard runs it as is.
        r = fleet.sweep(cells, TEMPS_C, PATTERNS, impl="pallas",
                        interpret=interpret)
        return r.read, r.write, r.joint

    check_kernel_program("charge_sweep", jax.jit(kernel_sweep).lower(fl.cells),
                         interpret)
    seconds = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = fleet.sweep(fl, TEMPS_C, PATTERNS, impl="pallas",
                          interpret=interpret, mesh=mesh)
        jax.block_until_ready((res.read, res.write, res.joint))
        seconds.append(time.perf_counter() - t0)
    log(f"sweep {fl.n_dimms:,} DIMMs x {len(TEMPS_C)} temps: "
        f"{seconds[0]:.3f} s first call (includes compile), "
        f"{seconds[1]:.3f} s warm")
    if mesh is None:
        ref = fleet.sweep(fl, TEMPS_C, PATTERNS, impl="ref")
        # ns stacks: grid values are strictly increasing, so equal ns is
        # equal min-safe indices.
        for name in ("read", "write", "joint"):
            assert_bitwise(f"sweep {name} kernel vs ref",
                           getattr(res, name), getattr(ref, name))
        log("sweep kernel == ref sweep (read, write, joint), bitwise")
    return res


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _telemetry_block(key, n_dimms: int, n_steps: int, error_rate: float):
    k_t, k_e = jax.random.split(key)
    return (traces.generate("diurnal", k_t, n_dimms, n_steps),
            traces.error_injections(k_e, n_steps, n_dimms, error_rate))


def telemetry(seed: int, n_dimms: int, n_steps: int, error_rate: float,
              block: int = 1 << 17):
    """Host ``(n_steps, n_dimms)`` diurnal temperatures and error flags,
    generated on the device one block of DIMMs at a time (the next block
    is dispatched before the last one is copied out)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    blocks = [(lo, min(lo + block, n_dimms)) for lo in range(0, n_dimms, block)]

    def launch(b):
        lo, hi = blocks[b]
        return _telemetry_block(jax.random.fold_in(key, b), hi - lo, n_steps,
                                error_rate)

    temps = np.empty((n_steps, n_dimms), np.float32)
    errors = np.empty((n_steps, n_dimms), bool)
    nxt = launch(0)
    for b, (lo, hi) in enumerate(blocks):
        cur, nxt = nxt, launch(b + 1) if b + 1 < len(blocks) else None
        temps[:, lo:hi] = np.asarray(cur[0])
        errors[:, lo:hi] = np.asarray(cur[1])
    return temps, errors


def serve(table: DimmTimingTable, temps, errors, impl: str, interpret: bool,
          chunk_steps: int, n_chunks: int, decision_steps: int, mesh=None):
    """Feed ``n_chunks`` chunks, then ``decision_steps`` decision-emitting
    steps, through ``FleetControllerService.submit``; return host copies
    of the state, partials, decisions and running score."""
    service = FleetControllerService(table, mesh=mesh, impl=impl,
                                     interpret=interpret)
    if impl == "pallas":
        run = pallas_chunk_scan(table.temp_bins, ControllerParams(), interpret)
        spec = jax.ShapeDtypeStruct
        check_kernel_program("replay_step", run.lower(
            spec(table.stack.shape, np.float32),
            spec((table.n_bins,), np.float32),
            ControllerParams(*(jnp.asarray(p) for p in ControllerParams())),
            service.engine.state, *service.engine.partials,
            spec((chunk_steps, table.n_dimms), np.float32),
            spec((chunk_steps, table.n_dimms), bool),
        ), interpret)
    seconds = []
    for c in range(n_chunks):
        rows = slice(c * chunk_steps, (c + 1) * chunk_steps)
        t0 = time.perf_counter()
        service.submit(temps[rows], errors[rows])
        jax.block_until_ready(service.engine.state)
        seconds.append(time.perf_counter() - t0)
    log(f"service impl={impl}: chunk seconds "
        + ", ".join(f"{s:.3f}" for s in seconds)
        + " (the first includes compile)")
    out = {}
    if decision_steps:
        rows = slice(n_chunks * chunk_steps,
                     n_chunks * chunk_steps + decision_steps)
        resp = service.submit(temps[rows], errors[rows], decisions=True)
        out["decisions"] = {k: np.asarray(resp[k])
                            for k in ("timings", "bin_idx", "switched")}
    t0 = time.perf_counter()
    out["score"] = service.running_score()
    log(f"service impl={impl}: running_score {time.perf_counter() - t0:.3f} s")
    out["state"] = ControllerState(*to_host(tuple(service.engine.state)))
    out["partials"] = ScorePartials(*to_host(tuple(service.engine.partials)))
    out["sharding"] = service.engine.state.bin_idx.sharding
    return out


def compare_services(got: dict, want: dict, what: str) -> None:
    for name, a, b in zip(ControllerState._fields, got["state"], want["state"]):
        assert_bitwise(f"{what} state.{name}", a, b)
    for name, a, b in zip(ScorePartials._fields, got["partials"],
                          want["partials"]):
        assert_bitwise(f"{what} partials.{name}", a, b)
    for name in ("timings", "bin_idx", "switched"):
        assert_bitwise(f"{what} decisions.{name}", got["decisions"][name],
                       want["decisions"][name])
    assert_scores_bitwise(f"{what} score", got["score"], want["score"])


def observed_dimms(errors, n: int = N_OBSERVED) -> np.ndarray:
    """Half the DIMMs that saw an injected error, the rest spread over
    the fleet."""
    n_dimms = errors.shape[1]
    hit = np.flatnonzero(errors.any(axis=0))[: n // 2]
    spread = np.linspace(0, n_dimms - 1, n, dtype=np.int64)
    picked = list(dict.fromkeys(list(hit) + list(spread)))
    return np.sort(np.asarray(picked[:n], np.int64))


def observe_check(table: DimmTimingTable, temps, errors, served: dict,
                  dimms: np.ndarray) -> None:
    """Replay the same steps through ``ALDRAMController.observe`` for
    ``dimms`` and require the service's registers, partials and decisions
    there to equal it exactly."""
    sub = DimmTimingTable(table.temp_bins, table.stack[dimms])
    ctl = ALDRAMController(sub)
    n_steps, k = temps.shape[0], len(dimms)
    dec = served["decisions"]
    first_dec = n_steps - dec["bin_idx"].shape[0]
    occ = np.zeros((k, sub.n_bins + 1), np.int32)
    switches = np.zeros((k,), np.int32)
    sums = np.zeros((k, 2, 4), np.float32)
    for s in range(n_steps):
        for j, d in enumerate(dimms):
            if errors[s, d]:
                ctl.report_error(j)
            before = ctl.switch_count
            t = ctl.observe(j, float(temps[s, d]))
            switches[j] += ctl.switch_count - before
            b = ctl.bin_of(j)
            eff = sub.n_bins if b is None else b
            occ[j, eff] += 1
            row = np.asarray([[getattr(t.read, p) for p in PARAM_NAMES],
                              [getattr(t.write, p) for p in PARAM_NAMES]],
                             np.float32)
            sums[j] += row
            if s >= first_dec:
                assert_bitwise(f"observe step {s} DIMM {d} row",
                               dec["timings"][s - first_dec, d], row)
                if int(dec["bin_idx"][s - first_dec, d]) != eff:
                    raise AssertionError(
                        f"observe step {s} DIMM {d}: bin "
                        f"{int(dec['bin_idx'][s - first_dec, d])} != {eff}")
    for name, a, b in zip(ControllerState._fields, ctl.state(),
                          served["state"]):
        assert_bitwise(f"observe state.{name}", np.asarray(a),
                       np.asarray(b)[dimms])
    p = served["partials"]
    assert_bitwise("observe occupancy", occ, p.occupancy[dimms])
    assert_bitwise("observe switches", switches, p.switches[dimms])
    assert_bitwise("observe timing sums", sums, p.timing_sums[dimms])
    log(f"{k} DIMMs {dimms.tolist()} == ALDRAMController.observe loop over "
        f"{n_steps} steps ({int(errors[:, dimms].any(axis=0).sum())} fused)")


def one_chip(n_dimms: int, chunk_steps: int, n_chunks: int,
             decision_steps: int, error_rate: float, seed: int,
             interpret: bool) -> None:
    """Boot sweep and service at ``n_dimms`` on the default device."""
    fl = fleet.synthesize(jax.random.PRNGKey(seed), n_dimms)
    table = boot_sweep(fl, interpret).to_table()
    n_steps = n_chunks * chunk_steps + decision_steps
    t0 = time.perf_counter()
    temps, errors = telemetry(seed, n_dimms, n_steps, error_rate)
    log(f"telemetry {n_steps} x {n_dimms:,} ({int(errors.sum())} errors) "
        f"generated in {time.perf_counter() - t0:.3f} s (set-up)")
    runs = {impl: serve(table, temps, errors, impl, interpret, chunk_steps,
                        n_chunks, decision_steps)
            for impl in ("pallas", "ref")}
    compare_services(runs["pallas"], runs["ref"], "service kernel vs ref")
    log("service kernel == ref service (state, partials, decisions, score), "
        "bitwise")
    observe_check(table, temps, errors, runs["ref"], observed_dimms(errors))
    score = runs["pallas"]["score"]
    log(f"running score: realized {score['speedup_realized_mean']:+.6f}, "
        f"time at JEDEC {score['time_at_jedec_frac']:.6f}, "
        f"switches {score['switches_total']:.0f}")


def score_means(score: dict) -> dict:
    """The fleet means behind a score dict. A ``speedup_*`` entry is a mean
    speedup less 1, and a ``*_vs_claim`` entry less the paper's claim too;
    the cross-mesh tolerance bounds the means' summation order, so it is
    applied to the means, before those constants cancel their leading
    digits."""
    out = {}
    for k, v in score.items():
        if k.startswith("speedup_"):
            v = v + 1.0 + (PAPER_CLAIM_SPEEDUP if k.endswith("_vs_claim")
                           else 0.0)
        out[k] = v
    return out


def combine_quarter_scores(scores) -> dict:
    """The fleet score from equal-sized quarters' score dicts: totals add,
    minima take the minimum, every other entry is a per-DIMM mean."""
    out = {}
    for k in scores[0]:
        vals = [s[k] for s in scores]
        if k.endswith("_min"):
            out[k] = min(vals)
        elif k == "switches_total":
            out[k] = sum(vals)
        else:
            out[k] = sum(vals) / len(vals)
    return out


def mesh_phase(n_shards: int, shard_dimms: int, chunk_steps: int,
               n_chunks: int, error_rate: float, seed: int,
               interpret: bool) -> None:
    """The ``("dimm",)`` mesh against single-device runs of its quarters."""
    mesh = shard.fleet_mesh(n_shards)
    devices = list(mesh.devices.flat)
    n_dimms = n_shards * shard_dimms
    fl = fleet.synthesize(jax.random.PRNGKey(seed), n_dimms)
    res = boot_sweep(fl, interpret, mesh=mesh)
    if len(res.read.sharding.device_set) != n_shards:
        raise AssertionError(
            f"sharded sweep output on {len(res.read.sharding.device_set)} "
            f"of {n_shards} devices")
    sweep = {k: np.asarray(getattr(res, k)) for k in ("read", "write", "joint")}
    table = res.to_table()
    del res
    temps, errors = telemetry(seed, n_dimms, n_chunks * chunk_steps,
                              error_rate)
    sharded = serve(table, temps, errors, "pallas", interpret, chunk_steps,
                    n_chunks, 0, mesh=mesh)
    on = sharded["sharding"].device_set
    if on != set(devices):
        raise AssertionError(f"service state on {len(on)} of {n_shards} devices")
    log("peak_bytes_in_use per device after the mesh run: "
        + ", ".join(f"{d.id}:{peak_bytes(d)}" for d in devices))
    quarter_scores = []
    for q in range(n_shards):
        cols = slice(q * shard_dimms, (q + 1) * shard_dimms)
        fq = jax.device_put(fl.take(cols), devices[0])
        rq = boot_sweep(fq, interpret)
        for name in ("read", "write", "joint"):
            assert_bitwise(f"quarter {q} sweep {name} vs mesh",
                           getattr(rq, name), sweep[name][:, :, cols])
        single = serve(rq.to_table(), temps[:, cols], errors[:, cols],
                       "pallas", interpret, chunk_steps, n_chunks, 0)
        for name, a, b in zip(ControllerState._fields, single["state"],
                              sharded["state"]):
            assert_bitwise(f"quarter {q} state.{name} vs mesh", a, b[cols])
        for name, a, b in zip(ScorePartials._fields, single["partials"],
                              sharded["partials"]):
            assert_bitwise(f"quarter {q} partials.{name} vs mesh", a,
                           b if name == "n_steps" else b[cols])
        quarter_scores.append(single["score"])
    want = score_means(combine_quarter_scores(quarter_scores))
    got = score_means(sharded["score"])
    if set(got) != set(want):
        raise AssertionError("mesh score keys differ from the quarters'")
    worst = 0.0
    for k in want:
        if not np.isclose(got[k], want[k], rtol=SCORE_RTOL, atol=SCORE_ATOL):
            raise AssertionError(f"mesh score mean behind {k}: {got[k]} vs "
                                 f"quarters {want[k]}")
        worst = max(worst, abs(got[k] - want[k]) / max(abs(want[k]), 1e-30))
    log(f"mesh of {n_shards}: sweep, state and partials == single-device "
        f"quarters bitwise; score means max relative difference {worst:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    chips = MESH_CHIPS if args.four_chips else 1
    device = require_tpu(chips)
    log(f"device_kind {device['kind']}, {device['count']} chip(s), "
        f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        mesh_phase(MESH_CHIPS, SHARD_DIMMS, CHUNK_STEPS, N_CHUNKS, ERROR_RATE,
                   args.seed, interpret=False)
    else:
        one_chip(N_DIMMS, CHUNK_STEPS, N_CHUNKS, DECISION_STEPS, ERROR_RATE,
                 args.seed, interpret=False)
    log(f"peak_bytes_in_use {peak_bytes(jax.devices()[0])} on device 0; "
        f"wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
