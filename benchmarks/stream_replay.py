"""Streaming million-DIMM replay: chunked-scan controller vs materialized.

The ROADMAP's serving north star — 10⁶ DIMMs × a day of minute-cadence
telemetry — cannot be replayed by :func:`repro.core.controller.replay`:
the materialized ``(n_steps, n_dimms, 2, 4)`` float32 timing history
alone is ~43 GiB, past any accelerator's device memory (and the history
is pure waste for scoring, which only needs the
:class:`~repro.core.perfmodel.ScorePartials`). This benchmark drives the
streaming path (:func:`repro.core.stream.replay_stream`) at exactly that
scale: telemetry is *generated chunkwise* (never materialized either),
each chunk is one jitted scan carrying only state + partials, and the
day is scored faster than real time.

  PYTHONPATH=src python benchmarks/stream_replay.py           # 10⁶ × 1440
  PYTHONPATH=src python benchmarks/stream_replay.py --tiny    # CI smoke
  PYTHONPATH=src python benchmarks/stream_replay.py --tiny --sharded \
      --chunk-sweep 24,96,512 --bench-json BENCH_replay.json

Parity gates (the run fails hard, CI goes red — never just logs):

* ``--tiny`` (64 × 512, error injections, a ragged last chunk): streamed
  final state, per-DIMM switch counts and the full score dict must equal
  the materialized ``replay`` + ``trace_score`` BITWISE (==0 max error)
  for chunk sizes {ragged, 1, n_steps}.
* the fused-kernel section repeats those gates with ``impl="pallas"``
  (the one-pass step + lookup + score-accumulate Pallas kernel,
  :mod:`repro.kernels.replay_step`) at the same chunkings, plus a
  partials-leaf bitwise gate vs the ref stream, and times kernel vs ref
  (``--chunk-sweep`` sweeps the step-tile size for both impls).
* full scale (where materialized replay cannot run): two different
  chunkings of the same stream — the scan carry is the only state, so
  re-chunking must reproduce state, partials and score bit-exactly
  (the chunk scan is chosen by platform, the fused kernel on TPU and the
  ref elsewhere; ``--impl`` forces one).
* ``--sharded``: the same gates with the DIMM axis shard_map-ped over
  every visible device; the streamed sharded score must match the
  materialized sharded score bitwise (they share the accumulate/finalize
  programs), the sharded PALLAS stream must match the same-mesh ref
  stream bitwise (partials, state, score), and the sharded score must
  match single-device to psum summation-order tolerance.

``--bench-json`` additionally writes the consolidated ``BENCH_replay.json``
throughput record (steps/sec, DIMM-steps/sec, peak-memory estimate, one
entry per impl) that CI uploads as an artifact.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

try:
    from benchmarks._sharded_env import ensure_host_devices
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _sharded_env import ensure_host_devices

ensure_host_devices()  # before jax initializes its backend

import jax
import numpy as np

from repro import analysis
from repro.core import controller, fleet, perfmodel, stream, traces
from repro.kernels.replay_step import default_interpret
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks._json_out import write_bench_replay_json, write_rows_json
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _json_out import write_bench_replay_json, write_rows_json

#: Reference accelerator HBM (GiB) for the cannot-hold-in-memory rows —
#: a generous single-device budget (A100-40G class has 40, v5e has 16).
DEVICE_MEM_GIB = 32.0

#: Bytes per transition of a materialized ReplayResult: (2, 4) float32
#: timings + int32 bin + 2 bools.
HISTORY_BYTES_PER_TRANSITION = 2 * 4 * 4 + 4 + 2


def stream_scenario(key, n_dimms, n_steps, gen_chunk, dt_s=traces.DEFAULT_DT_S,
                    error_rate=0.0):
    """Chunkwise diurnal-like telemetry source — O(n_dimms · gen_chunk)
    host memory, never a full trace.

    Every value is a pure function of ``(key, generation-chunk index,
    step)``: a per-DIMM base + daily sinusoid plus per-chunk Gaussian
    noise, rounded to the 0.25 °C sensor grid. Re-consuming the generator
    yields identical chunks, and because nothing carries across steps the
    *replay* chunking downstream is free to differ from the generation
    chunking (unlike :func:`traces.generate`'s diurnal scenario, whose
    cumulative drift clamp ties every step to the whole history)."""
    k_base, k_amp = jax.random.split(jax.random.fold_in(key, 0))
    base = np.asarray(
        jax.random.uniform(k_base, (n_dimms,), minval=28.0, maxval=40.0)
    )
    amp = np.asarray(jax.random.uniform(k_amp, (n_dimms,), minval=3.0, maxval=9.0))
    period = 86_400.0 / dt_s
    for ci, s0 in enumerate(range(0, n_steps, gen_chunk)):
        s = np.arange(s0, min(s0 + gen_chunk, n_steps))
        noise = 0.5 * np.asarray(
            jax.random.normal(jax.random.fold_in(key, 100 + ci), (len(s), n_dimms))
        )
        temps = base[None] + amp[None] * np.sin(2 * np.pi * s / period)[:, None]
        temps = np.round((temps + noise) * 4.0) / 4.0
        errs = None
        if error_rate > 0.0:
            errs = np.asarray(jax.random.bernoulli(
                jax.random.fold_in(key, 10_000 + ci), error_rate,
                (len(s), n_dimms),
            ))
        yield temps.astype(np.float32), errs


def _split_halves(chunks):
    """Re-chunk a stream by splitting every chunk in two — the adversarial
    alternative chunking for the full-scale parity gate."""
    for temps, errs in chunks:
        h = temps.shape[0] // 2
        if h == 0:
            yield temps, errs
            continue
        yield temps[:h], None if errs is None else errs[:h]
        yield temps[h:], None if errs is None else errs[h:]


def _assert_stream_equal(a, b, what):
    """Hard ==0 gate: two StreamResults must agree bitwise everywhere."""
    for name, la, lb in zip(("bin_idx", "cool_streak", "fused"), a.state, b.state):
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            raise AssertionError(f"{what}: final state.{name} diverged")
    for name, la, lb in zip(stream.ScorePartials._fields, a.partials, b.partials):
        err = float(np.abs(
            np.asarray(la, np.float64) - np.asarray(lb, np.float64)
        ).max())
        if err != 0.0:
            raise AssertionError(f"{what}: partials.{name} max|err|={err}")


def _assert_scores_equal(sa, sb, what, exact=True, rtol=1e-4):
    keys = set(sa)
    if keys != set(sb):
        raise AssertionError(f"{what}: score keys differ")
    if exact:
        bad = {k: (sa[k], sb[k]) for k in keys if sa[k] != sb[k]}
        if bad:
            raise AssertionError(f"{what}: score not bit-exact: {bad}")
        return 0.0
    err = max(abs(sa[k] - sb[k]) / max(abs(sb[k]), 1.0) for k in keys)
    if err > rtol:
        raise AssertionError(f"{what}: score max rel err {err:.2e} > {rtol}")
    return err


def _time_stream(table, trace, errors, chunk, impl, repeats=2):
    """Best-of-N steady-state wall seconds for one streamed replay (the
    first pass pays tracing/compile and is discarded)."""
    best = float("inf")
    for i in range(repeats + 1):
        t0 = time.perf_counter()
        res = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                                   impl=impl)
        jax.block_until_ready((res.state, tuple(res.partials)))
        if i > 0:
            best = min(best, time.perf_counter() - t0)
    return best


def _peak_mem_estimate(n_dimms, n_bins, chunk, impl):
    """Rough peak device-resident bytes for one streamed chunk scan:
    timing-register stack + carried state/partials + double-buffered
    observation chunks. The pallas path pads the DIMM axis up to whole
    1024-lane (8×128) tiles, so its footprint steps at tile boundaries."""
    n = n_dimms
    if impl == "pallas":
        n = -(-n_dimms // 1024) * 1024
    stack = n * n_bins * 2 * 4 * 4             # float32 timing registers
    state = n * 3 * 4                          # bin / streak / fused
    partials = n * ((n_bins + 1) + 1 + 2 * 4) * 4  # occ + switches + sums
    buffers = 2 * chunk * n * (4 + 1)          # double-buffered temps+errs
    return float(stack + state + partials + buffers)


def _kernel_section(table, trace, errors, chunk, n_steps, ref, score_ref,
                    sharded, chunk_sweep):
    """Fused Pallas replay kernel: hard ==0 parity gates + kernel-vs-ref
    timing. Parity at chunkings {ragged, 1, n_steps} vs the materialized
    replay, partials-leaf bitwise vs the ref stream, and (``--sharded``)
    bitwise vs the SAME-MESH ref stream. Throughput is reported, not
    gated: off-TPU the kernel runs in interpret mode and loses by
    construction; the speedup row says which regime produced it."""
    n_dimms = table.n_dimms
    for c in (chunk, 1, n_steps):
        res = stream.replay_stream(table, trace, errors, chunk_steps=c,
                                   impl="pallas")
        for name, la, lb in zip(("bin_idx", "cool_streak", "fused"),
                                res.state, ref.state):
            if not np.array_equal(np.asarray(la), np.asarray(lb)):
                raise AssertionError(
                    f"kernel chunk={c}: state.{name} != materialized"
                )
        if not np.array_equal(np.asarray(res.partials.switches),
                              np.asarray(ref.switch_counts)):
            raise AssertionError(f"kernel chunk={c}: switch counts diverged")
        _assert_scores_equal(res.score(), score_ref,
                             f"kernel chunk={c} score", exact=True)
    # Stronger than score equality: every partials leaf bitwise vs ref.
    _assert_stream_equal(
        stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                             impl="pallas"),
        stream.replay_stream(table, trace, errors, chunk_steps=chunk),
        "kernel vs ref stream",
    )
    interp = default_interpret()
    rows = [
        ("stream/kernel_parity_exact", 1.0, "==1 (hard gate)"),
        ("stream/kernel_interpret_mode", float(interp),
         "1 = no TPU, kernel interpreted"),
    ]
    bench = {}
    for impl in ("ref", "pallas"):
        dt = _time_stream(table, trace, errors, chunk, impl)
        steps = n_steps / dt
        bench[impl] = {
            "seconds": dt,
            "steps_per_sec": steps,
            "dimm_steps_per_sec": steps * n_dimms,
            "peak_memory_bytes_est":
                _peak_mem_estimate(n_dimms, table.n_bins, chunk, impl),
            "interpret_mode": bool(interp) and impl == "pallas",
        }
        rows.append((f"stream/{impl}_steps_per_sec", steps, ""))
    speedup = bench["pallas"]["steps_per_sec"] / bench["ref"]["steps_per_sec"]
    rows.append(("stream/kernel_vs_ref_speedup", speedup,
                 "interpret mode, not meaningful" if interp
                 else ">=1 (fused kernel)"))
    for c in chunk_sweep:
        for impl in ("ref", "pallas"):
            steps = n_steps / _time_stream(table, trace, errors, c, impl,
                                           repeats=1)
            bench[impl].setdefault("chunk_sweep", {})[str(c)] = steps
            rows.append((f"stream/{impl}_steps_per_sec_chunk{c}", steps,
                         "step-tile sweep"))
    if sharded:
        from repro.core import shard

        mesh = shard.fleet_mesh()
        ps = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                                  mesh=mesh, impl="pallas")
        rs = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                                  mesh=mesh)
        _assert_stream_equal(ps, rs, "sharded kernel vs sharded ref stream")
        _assert_scores_equal(ps.score(), rs.score(),
                             "sharded kernel vs sharded ref score",
                             exact=True)
        rows.append(("stream/kernel_sharded_parity_exact", 1.0,
                     "==1 (hard gate)"))
    return rows, bench


def run_tiny(chunk: int = 96, error_rate: float = 0.002, seed: int = 0,
             sharded: bool = False, verbose: bool = True, chunk_sweep=()):
    """CI smoke: small enough to ALSO run the materialized replay, so the
    streamed path is gated ==0 against the ground truth end to end."""
    n_dimms, n_steps = 64, 512
    key = jax.random.PRNGKey(seed)
    k_fleet, k_trace, k_err = jax.random.split(key, 3)
    fl = fleet.synthesize(k_fleet, n_dimms)
    table = fleet.sweep(fl, temps_c=controller.DEFAULT_TEMP_BINS,
                        patterns=(1.0,)).to_table()
    trace = np.asarray(traces.generate("diurnal", k_trace, n_dimms, n_steps))
    errors = np.asarray(traces.error_injections(k_err, n_steps, n_dimms,
                                                error_rate))

    ref = controller.replay(table, trace, errors)
    score_ref = perfmodel.trace_score(table.stack, ref)

    results = {}
    for c in (chunk, 1, n_steps):  # ragged last chunk, degenerate, one-shot
        res = stream.replay_stream(table, trace, errors, chunk_steps=c)
        for name, la, lb in zip(("bin_idx", "cool_streak", "fused"),
                                res.state, ref.state):
            if not np.array_equal(np.asarray(la), np.asarray(lb)):
                raise AssertionError(
                    f"chunk={c}: streamed state.{name} != materialized"
                )
        if not np.array_equal(np.asarray(res.partials.switches),
                              np.asarray(ref.switch_counts)):
            raise AssertionError(f"chunk={c}: streamed switch counts diverged")
        _assert_scores_equal(res.score(), score_ref,
                             f"chunk={c} streamed score", exact=True)
        results[c] = res

    # Timed steady-state streamed pass (compiled above) vs materialized,
    # under the runtime sanitizers with retrace accounting: every hot
    # runner must serve the timed pass from its compile cache — a
    # nonzero lint/retrace_* row below is a retrace storm starting.
    retrace = analysis.RetraceCounter()
    with analysis.sanitize(), retrace:
        t0 = time.perf_counter()
        res = stream.replay_stream(table, trace, errors, chunk_steps=chunk)
        jax.block_until_ready(res.state)
        t_stream = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref2 = controller.replay(table, trace, errors)
    jax.block_until_ready(ref2.timings)
    t_mat = time.perf_counter() - t0

    rows = [
        ("stream/n_dimms", float(n_dimms), ""),
        ("stream/n_steps", float(n_steps), ""),
        ("stream/chunk_steps", float(chunk), "ragged last chunk"),
        ("stream/n_chunks", float(results[chunk].n_chunks), ""),
        ("stream/parity_state_exact", 1.0, "==1 (hard gate)"),
        ("stream/parity_switches_exact", 1.0, "==1 (hard gate)"),
        ("stream/parity_score_max_abs_err", 0.0, "==0 (hard gate)"),
        ("stream/errors_injected", float(results[chunk].errors_total), ""),
        ("stream/stream_seconds", t_stream, ""),
        ("stream/materialized_seconds", t_mat, "history path, same steps"),
        ("stream/speedup_realized_intensive_mean",
         score_ref["speedup_realized_intensive_mean"],
         f"paper claim {perfmodel.PAPER_CLAIM_SPEEDUP}"),
    ]
    # Steady-state compile accounting (0 expected for every runner).
    rows += list(retrace.rows(expected={n: 0 for n in retrace.runners}))
    if retrace.total():
        raise AssertionError(
            f"steady-state retrace detected: {retrace.deltas}"
        )
    if sharded:
        rows += _sharded_section(table, trace, errors, chunk, score_ref)
    krows, bench = _kernel_section(table, trace, errors, chunk, n_steps,
                                   ref, score_ref, sharded, chunk_sweep)
    rows += krows
    if verbose:
        print(f"# tiny: {n_dimms} x {n_steps}, chunks {sorted(results)} all "
              f"bit-exact vs materialized (state, switches, score)")
        print(f"# streamed {t_stream*1e3:.1f} ms vs materialized "
              f"{t_mat*1e3:.1f} ms; {results[chunk].errors_total} errors "
              f"injected")
        print(f"# kernel (impl=pallas) bit-exact at all chunkings; "
              f"ref {bench['ref']['steps_per_sec']:,.0f} vs pallas "
              f"{bench['pallas']['steps_per_sec']:,.0f} steps/s"
              + (" [interpret mode]" if bench["pallas"]["interpret_mode"]
                 else ""))
    bench_cfg = {"n_dimms": n_dimms, "n_steps": n_steps, "chunk_steps": chunk,
                 "mode": "tiny"}
    return rows, (bench_cfg, bench)


def _sharded_section(table, trace, errors, chunk, score_single):
    """Mesh gates: streamed-sharded ≡ materialized-sharded bitwise, and
    sharded ≈ single-device to summation-order tolerance."""
    from repro.core import shard

    mesh = shard.fleet_mesh()
    n_dev = shard.n_shards(mesh)
    sref = controller.replay(table, trace, errors, mesh=mesh)
    score_sref = perfmodel.trace_score(table.stack, sref, mesh=mesh)
    res = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                               mesh=mesh)
    for name, la, lb in zip(("bin_idx", "cool_streak", "fused"),
                            res.state, sref.state):
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            raise AssertionError(f"sharded stream: state.{name} diverged")
    _assert_scores_equal(res.score(), score_sref,
                         "sharded streamed vs materialized-sharded score",
                         exact=True)
    rel = _assert_scores_equal(score_sref, score_single,
                               "sharded vs single-device score",
                               exact=False, rtol=1e-4)
    return [
        ("stream/sharded_n_devices", float(n_dev), ">=8 in CI"),
        ("stream/sharded_parity_exact", 1.0, "==1 (hard gate)"),
        ("stream/sharded_vs_single_score_rel_err", rel, "<=1e-4"),
    ]


def run_full(n_dimms: int = 1_000_000, n_steps: int = 1440,
             chunk: int = 96, error_rate: float = 1e-5,
             dt_s: float = traces.DEFAULT_DT_S, seed: int = 0,
             sharded: bool = False, verbose: bool = True,
             impl: Optional[str] = None):
    """The north-star point: a fleet × trace length whose materialized
    replay history cannot exist on a device. Telemetry is generated
    chunkwise, streamed once (timed), then re-streamed under a different
    chunking — the ==0 gate that scoring is chunking-invariant. The chunk
    scan is chosen by platform unless ``impl`` names one."""
    impl = stream.resolve_impl(impl)
    key = jax.random.PRNGKey(seed)
    if verbose:
        print(f"# profiling {n_dimms:,} DIMMs ...", flush=True)
    t0 = time.perf_counter()
    fl = fleet.synthesize(jax.random.fold_in(key, 7), n_dimms)
    table = fleet.sweep(fl, temps_c=controller.DEFAULT_TEMP_BINS,
                        patterns=(1.0,)).to_table()
    t_profile = time.perf_counter() - t0

    mesh = None
    if sharded:
        from repro.core import shard

        mesh = shard.fleet_mesh()

    k_scn = jax.random.fold_in(key, 11)
    source = lambda: stream_scenario(  # noqa: E731 — re-consumable stream
        k_scn, n_dimms, n_steps, gen_chunk=chunk, dt_s=dt_s,
        error_rate=error_rate,
    )
    if verbose:
        print(f"# streaming {n_dimms:,} x {n_steps} (chunk {chunk}) ...",
              flush=True)
    t0 = time.perf_counter()
    with analysis.sanitize():  # rank-promotion raise over the whole stream
        res = stream.replay_stream(table, source(), chunk_steps=chunk,
                                   mesh=mesh, impl=impl)
        jax.block_until_ready(res.state)
    t_stream = time.perf_counter() - t0
    t0 = time.perf_counter()
    score = res.score()
    t_score = time.perf_counter() - t0

    # The chunked reference: same stream, different chunking, ==0 gate.
    res2 = stream.replay_stream(table, _split_halves(source()),
                                chunk_steps=chunk, mesh=mesh, impl=impl)
    _assert_stream_equal(res, res2, "re-chunked stream")
    _assert_scores_equal(score, res2.score(), "re-chunked score", exact=True)

    transitions = float(n_dimms) * n_steps
    history_gib = transitions * HISTORY_BYTES_PER_TRANSITION / 2**30
    buffer_gib = 2 * chunk * n_dimms * 4 / 2**30  # double-buffered temps
    wall = t_stream + t_score
    realtime = n_steps * dt_s / wall
    rows = [
        ("stream/n_dimms", float(n_dimms), "north star 1e6"),
        ("stream/n_steps", float(n_steps), "a day at minute cadence"),
        ("stream/chunk_steps", float(chunk), ""),
        ("stream/transitions", transitions, ""),
        ("stream/profile_seconds", t_profile, "boot-time characterization"),
        ("stream/stream_seconds", t_stream, ""),
        ("stream/score_seconds", t_score, ""),
        ("stream/obs_per_second", transitions / t_stream, ""),
        ("stream/realtime_factor", realtime, ">=1 is faster than real time"),
        ("stream/materialized_history_gib", history_gib,
         f"does not fit {DEVICE_MEM_GIB} GiB device memory"),
        ("stream/streamed_buffer_gib", buffer_gib, "O(n_dimms * chunk)"),
        ("stream/history_vs_device_ratio", history_gib / DEVICE_MEM_GIB,
         ">1 = materialized replay cannot run"),
        ("stream/rechunk_parity_exact", 1.0, "==1 (hard gate)"),
        ("stream/errors_injected", float(res.errors_total), ""),
        ("stream/speedup_realized_mean", score["speedup_realized_mean"], ""),
        ("stream/speedup_realized_intensive_mean",
         score["speedup_realized_intensive_mean"],
         f"paper claim {perfmodel.PAPER_CLAIM_SPEEDUP}"),
        ("stream/switches_per_kstep", score["switches_per_kstep"], ""),
        ("stream/time_at_jedec_frac", score["time_at_jedec_frac"], ""),
    ]
    if sharded:
        from repro.core import shard

        rows.append(("stream/sharded_n_devices",
                     float(shard.n_shards(mesh)), ""))
    if verbose:
        print(f"# {transitions:,.0f} transitions in {t_stream:.2f} s stream "
              f"+ {t_score:.2f} s score = {realtime:,.0f}x real time")
        print(f"# materialized history would be {history_gib:.1f} GiB "
              f"({history_gib / DEVICE_MEM_GIB:.1f}x a {DEVICE_MEM_GIB:.0f} "
              f"GiB device); streamed buffers {buffer_gib:.2f} GiB")
        print(f"# realized +{score['speedup_realized_mean']*100:.1f}% all, "
              f"+{score['speedup_realized_intensive_mean']*100:.1f}% "
              f"mem-intensive; re-chunked replay bit-exact")
    bench_cfg = {"n_dimms": n_dimms, "n_steps": n_steps, "chunk_steps": chunk,
                 "mode": "full"}
    bench = {impl: {
        "seconds": t_stream,
        "steps_per_sec": n_steps / t_stream,
        "dimm_steps_per_sec": transitions / t_stream,
        "peak_memory_bytes_est":
            _peak_mem_estimate(n_dimms, table.n_bins, chunk, impl),
        "interpret_mode": impl == "pallas" and default_interpret(),
    }}
    return rows, (bench_cfg, bench)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-dimms", type=int, default=None,
                    help="fleet size (default 1,000,000)")
    ap.add_argument("--n-steps", type=int, default=None,
                    help="stream length in observations (default 1440)")
    ap.add_argument("--chunk", type=int, default=96,
                    help="step-axis chunk per jitted scan")
    ap.add_argument("--error-rate", type=float, default=None,
                    help="per-(step,DIMM) error-injection probability")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 64 x 512 with hard ==0 parity gates vs "
                         "the materialized replay")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the DIMM axis over all visible devices (on "
                         "CPU forces 8 host devices unless XLA_FLAGS pins "
                         "a count) and gate sharded parity")
    ap.add_argument("--impl", default=None, choices=("ref", "pallas"),
                    help="chunk-scan impl for the full-scale run (default: "
                         "chosen by platform, pallas on TPU, ref elsewhere; "
                         "the tiny kernel section always times both)")
    ap.add_argument("--chunk-sweep", type=str, default=None,
                    help="comma list of step-tile sizes to time both impls "
                         "at (tiny mode), e.g. 24,96,512")
    ap.add_argument("--json", type=str, default=None,
                    help="also write rows to this JSON artifact path")
    ap.add_argument("--bench-json", type=str, default=None,
                    help="write the consolidated BENCH_replay.json "
                         "throughput record (per-impl steps/sec, "
                         "DIMM-steps/sec, peak-memory estimate)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    sweep = tuple(
        int(c) for c in args.chunk_sweep.split(",")
    ) if args.chunk_sweep else ()

    # One sanitize() scope over the whole run: jit cache keys include the
    # guard config, so mixing sanitized and unsanitized regions would
    # recompile every program at the boundary (and trip the retrace gate).
    if args.tiny:
        conflicts = [name for name, val in (
            ("--n-dimms", args.n_dimms), ("--n-steps", args.n_steps),
        ) if val is not None]
        if conflicts:
            ap.error(f"--tiny fixes the configuration; remove {', '.join(conflicts)}")
        with analysis.sanitize():
            rows, (bench_cfg, bench) = run_tiny(
                chunk=args.chunk,
                error_rate=0.002 if args.error_rate is None else args.error_rate,
                seed=args.seed, sharded=args.sharded, chunk_sweep=sweep,
            )
    else:
        with analysis.sanitize():
            rows, (bench_cfg, bench) = run_full(
                n_dimms=1_000_000 if args.n_dimms is None else args.n_dimms,
                n_steps=1440 if args.n_steps is None else args.n_steps,
                chunk=args.chunk,
                error_rate=1e-5 if args.error_rate is None else args.error_rate,
                seed=args.seed, sharded=args.sharded, impl=args.impl,
            )
    for name, value, ref in rows:
        print(f"{name},{value:.6g},{ref}")
    meta = {"tiny": args.tiny, "sharded": args.sharded, "seed": args.seed}
    if args.json:
        write_rows_json(args.json, "stream_replay", rows, meta=meta)
    if args.bench_json:
        bench_cfg["device"] = jax.devices()[0].platform
        bench_cfg["device_kind"] = jax.devices()[0].device_kind
        bench_cfg["device_count"] = jax.device_count()
        write_bench_replay_json(args.bench_json, bench_cfg, bench, meta=meta)


if __name__ == "__main__":
    main()
