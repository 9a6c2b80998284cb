"""Trace-driven controller evaluation: a fleet-day in one jitted scan.

The ROADMAP's open item, closed: feed the AL-DRAM controller recorded
temperature traces (:mod:`repro.core.traces` scenarios) and score the
*realized* latency reductions, switching activity and performance gain
against the paper's claims (14 % average speedup, <0.1 °C/s drift, zero
errors). The controller is the pure scan state machine of
:mod:`repro.core.controller` — a 1,000-DIMM × 10,000-step day is ONE
compiled ``lax.scan`` — and the measured baseline is the per-observation
``ALDRAMController.observe`` Python loop it replaced.

  PYTHONPATH=src python benchmarks/trace_eval.py             # 1,000 × 10,000
  PYTHONPATH=src python benchmarks/trace_eval.py --tiny      # CI smoke run
  PYTHONPATH=src python benchmarks/trace_eval.py --scenario hvac_failure

The loop baseline is timed on a ``--baseline-dimms`` × ``--baseline-steps``
sub-grid (default 24 × 500) and extrapolated linearly to the full grid —
running 10⁷ Python observe calls would take tens of minutes, which is the
point. Equivalence with the scan is asserted bit-exactly on that sub-grid
(the run fails hard on divergence); the speedup is reported, not gated —
wall-clock on shared CI boxes is too noisy to assert.

Regression gate: ``--tiny`` (CI) loads the committed baseline JSON
(``benchmarks/baselines/trace_eval_tiny.json``) and fails hard if the
realized memory-intensive speedup drops below it, or if any DIMM's
programmed read-set tRAS fails to sit below JEDEC in the coolest bin —
the two observable symptoms of the old tRAS-at-JEDEC merge bug.

Refresh: the table carries the extended-temperature refresh policy by
default (``--refresh off`` disables it), so the score reports the
*combined* latency+refresh realized speedup next to the latency-only
one — the honest figure to hold against the paper's 14 % claim, since
hot bins pay slower timings AND doubled refresh occupancy at once.
``--tiny --scenario refresh_storm`` is gated against its own committed
baseline (``trace_eval_refresh_storm_tiny.json``): combined intensive
speedup floor plus a pinned time-weighted refresh occupancy, so the
2×-refresh penalty can neither silently vanish nor silently grow.
``--bench-json`` persists the refresh-on vs refresh-off speedup rows as
``BENCH_trace_eval.json`` for the CI artifact trail.

Regions: scenarios with a paired region-access mix (``design_skew``,
``hot_bank`` — see ``traces.SCENARIO_REGION_PROFILES``) additionally
profile ``--regions`` distance-from-sense-amp classes (default 4) and
score the SAME replay's bin history both ways: each access at ITS
region's registers (aware) vs everything at the max-over-regions set
(oblivious). ``--tiny --scenario design_skew`` is gated against its own
committed baseline (``trace_eval_design_skew_tiny.json``): a floor on
the region-aware intensive speedup plus the strict aware > oblivious
assertion, with the anchor contract (region table's oblivious set
bitwise-equal to the region-free profile) checked on every region run.

``--sharded`` adds the mesh section (``trace/sharded_*`` rows): the same
replay shard_map-ped over a 1-D DIMM mesh spanning every visible device
(hard-gated bit-exact vs the single-device scan) plus the gather-free
``trace_score(mesh=...)`` — local partials + psum, gated to match the
single-device score. On CPU it forces 8 host devices unless XLA_FLAGS
already pins a count.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

try:
    from benchmarks._sharded_env import ensure_host_devices
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _sharded_env import ensure_host_devices

ensure_host_devices()  # before jax initializes its backend

import jax
import numpy as np

from repro.core import controller, fleet, perfmodel, traces
from repro.core import refresh as rf
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks._json_out import write_rows_json
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _json_out import write_rows_json

#: Committed regression baseline for the --tiny CI configuration.
TINY_BASELINE_PATH = pathlib.Path(__file__).parent / "baselines" / "trace_eval_tiny.json"
#: Committed baseline for --tiny --scenario refresh_storm (refresh gate).
REFRESH_STORM_BASELINE_PATH = (
    pathlib.Path(__file__).parent / "baselines" / "trace_eval_refresh_storm_tiny.json"
)
#: Committed baseline for --tiny --scenario design_skew (region gate).
DESIGN_SKEW_BASELINE_PATH = (
    pathlib.Path(__file__).parent / "baselines" / "trace_eval_design_skew_tiny.json"
)

#: Regions profiled for the region-aware section (--regions; scenarios in
#: traces.SCENARIO_REGION_PROFILES enable it by default).
DEFAULT_N_REGIONS = 4

#: --refresh choices -> table refresh policy.
REFRESH_POLICIES = {
    "ddr3": rf.DDR3_EXTENDED,
    "ddr3_4x": rf.DDR3_EXTENDED_4X,
    "off": None,
}


def run(
    n_dimms: int = 1000,
    n_steps: int = 10_000,
    scenario: str = "diurnal",
    temp_bins=controller.DEFAULT_TEMP_BINS,
    dt_s: float = traces.DEFAULT_DT_S,
    error_rate: float = 0.0,
    baseline_dimms: int = 24,
    baseline_steps: int = 500,
    seed: int = 0,
    verbose: bool = True,
    regression_baseline: str | pathlib.Path | None = None,
    sharded: bool = False,
    refresh: str = "ddr3",
    regions: int | None = None,
):
    key = jax.random.PRNGKey(seed)
    k_fleet, k_trace, k_err = jax.random.split(key, 3)
    if regions is None:  # region scenarios carry a paired access mix
        regions = (DEFAULT_N_REGIONS
                   if scenario in traces.SCENARIO_REGION_PROFILES else 0)

    fl = fleet.synthesize(k_fleet, n_dimms)
    sweep = fleet.sweep(fl, temps_c=temp_bins, patterns=(1.0,))
    table = sweep.to_table()
    policy = REFRESH_POLICIES[refresh]
    if policy is not None:
        table = controller.DimmTimingTable(
            temp_bins=table.temp_bins, stack=table.stack, refresh=policy
        )

    trace_kw = {"vendor": fl.vendor} if scenario == "vendor_skew" else {}
    trace = traces.generate(scenario, k_trace, n_dimms, n_steps, dt_s, **trace_kw)
    errors = traces.error_injections(k_err, n_steps, n_dimms, error_rate)
    drift = traces.max_drift_rate(trace, dt_s)

    # -- scan replay: compile once, then time the steady state -------------
    res = controller.replay(table, trace, errors)
    jax.block_until_ready(res.timings)
    t0 = time.perf_counter()
    res = controller.replay(table, trace, errors)
    jax.block_until_ready(res.timings)
    t_scan = time.perf_counter() - t0

    # -- per-observation Python loop (the pre-refactor execution model) ----
    n_b = min(baseline_dimms, n_dimms)
    s_b = min(baseline_steps, n_steps)
    sub_table = controller.DimmTimingTable(
        temp_bins=table.temp_bins, stack=table.stack[:n_b]
    )
    ctl = controller.ALDRAMController(sub_table)
    sub_trace = np.asarray(trace[:s_b, :n_b])
    sub_err = np.asarray(errors[:s_b, :n_b])
    loop_rows = np.zeros((s_b, n_b, 2, 4), np.float32)
    t0 = time.perf_counter()
    for s in range(s_b):
        for d in range(n_b):
            if sub_err[s, d]:
                ctl.report_error(d)
            t = ctl.observe(d, float(sub_trace[s, d]))
            loop_rows[s, d, 0] = tuple(t.read)
            loop_rows[s, d, 1] = tuple(t.write)
    t_loop_measured = time.perf_counter() - t0
    t_loop = t_loop_measured * (n_dimms * n_steps) / (n_b * s_b)
    speedup = t_loop / t_scan

    # -- bit-exact equivalence on the measured sub-grid --------------------
    sub_res = controller.replay(sub_table, sub_trace, sub_err)
    exact = bool(np.array_equal(np.asarray(sub_res.timings), loop_rows))
    max_err = float(np.abs(np.asarray(sub_res.timings) - loop_rows).max())
    if not exact:  # the correctness gate: CI must go red, not just log
        raise AssertionError(
            f"scan replay diverged from the observe loop: "
            f"max|err| = {max_err} ns on the {n_b}x{s_b} sub-grid"
        )

    # -- scoring -----------------------------------------------------------
    # With a refresh policy the score dict carries BOTH the latency-only
    # figures (bitwise identical to a policy-less score) and the combined
    # latency+refresh ones.
    score = perfmodel.trace_score(table.stack, res, refresh=table.bin_refresh())

    # -- sharded section: replay + gather-free scoring over the mesh -------
    shard_rows = []
    if sharded:
        from repro.core import shard

        mesh = shard.fleet_mesh()
        n_dev = shard.n_shards(mesh)
        sres = controller.replay(table, trace, errors, mesh=mesh)
        jax.block_until_ready(sres.timings)
        t0 = time.perf_counter()
        sres = controller.replay(table, trace, errors, mesh=mesh)
        jax.block_until_ready(sres.timings)
        t_sharded = time.perf_counter() - t0
        shard_err = float(
            np.abs(np.asarray(sres.timings) - np.asarray(res.timings)).max()
        )
        replay_exact = shard_err == 0.0 and bool(
            np.array_equal(np.asarray(sres.bin_idx), np.asarray(res.bin_idx))
        ) and bool(
            np.array_equal(np.asarray(sres.switched), np.asarray(res.switched))
        )
        if not replay_exact:  # parity gate: CI must go red, not just log
            raise AssertionError(
                f"sharded replay diverged from single-device scan: "
                f"max|err| = {shard_err} ns on {n_dev} devices"
            )
        sscore = perfmodel.trace_score(
            table.stack, sres, mesh=mesh, refresh=table.bin_refresh()
        )
        score_err = max(
            abs(sscore[k] - score[k]) / max(abs(score[k]), 1.0)
            for k in score
        )
        if score_err > 1e-4:  # psum partials: summation-order noise only
            raise AssertionError(
                f"sharded trace_score diverged: max rel err {score_err:.2e}"
            )
        shard_rows = [
            ("trace/sharded_n_devices", float(n_dev), ">=8 in CI"),
            ("trace/sharded_replay_seconds", t_sharded, ""),
            ("trace/sharded_vs_single_device_ratio", t_scan / t_sharded,
             "scaling row; >1 = sharding wins"),
            ("trace/sharded_replay_parity_exact",
             1.0 if replay_exact else 0.0, "==1"),
            ("trace/sharded_score_max_rel_err", score_err, "<=1e-4"),
        ]

    # -- region section: per-region registers vs the oblivious set ---------
    # Profiles the SAME fleet with the region axis raised, then scores the
    # SAME replay's bin history against the rank-5 registers under the
    # scenario's paired region-access mix. The anchor property makes the
    # region table's oblivious set bitwise the replay table's registers,
    # so res.bin_idx is exactly the bin history a region-oblivious
    # controller would realize — no second replay.
    region_rows = []
    region_score = None
    if regions:
        rsweep = fleet.sweep_regions(
            fl, temps_c=temp_bins, patterns=(1.0,), n_regions=regions
        )
        rtable = rsweep.to_table()
        if not np.array_equal(rtable.oblivious_stack(), table.stack):
            raise AssertionError(
                "region table's max-over-regions registers diverged from "
                "the region-free profile — the anchor contract is broken"
            )
        profile = traces.SCENARIO_REGION_PROFILES.get(scenario, "uniform")
        mix = traces.region_access_mix(
            jax.random.fold_in(key, 4), n_steps, n_dimms, regions,
            profile=profile,
        )
        region_score = perfmodel.region_trace_score(
            rtable.region_stack(), res, mix
        )
        region_rows = [
            ("trace/region_n_regions", float(regions), ""),
            ("trace/region_mix_" + profile, 1.0, ""),
            ("trace/nearest_region_access_frac",
             region_score["nearest_region_access_frac"], ""),
            ("trace/speedup_region_aware_intensive_mean",
             region_score["speedup_region_aware_intensive_mean"],
             "per-(DIMM,bin,region) lookup"),
            ("trace/speedup_region_oblivious_intensive_mean",
             region_score["speedup_region_oblivious_intensive_mean"],
             "max-over-regions registers"),
            ("trace/region_aware_advantage_intensive",
             region_score["region_aware_advantage_intensive"],
             "> 0 on skewed mixes"),
        ]

    rows = [
        ("trace/scenario_" + scenario, 1.0, ""),
        ("trace/n_dimms", float(n_dimms), ""),
        ("trace/n_steps", float(n_steps), ""),
        ("trace/transitions", float(n_dimms) * n_steps, ""),
        ("trace/max_drift_c_per_s", drift,
         f"paper bound {traces.PAPER_MAX_DRIFT_C_PER_S}"),
        ("trace/scan_seconds", t_scan, ""),
        ("trace/loop_seconds_extrapolated", t_loop, ""),
        ("trace/speedup_vs_loop", speedup, ">=100"),
        ("trace/loop_equivalence_exact", float(exact), "==1"),
        ("trace/loop_max_abs_error_ns", max_err, "==0"),
        ("trace/read_reduction_mean", score["read_reduction_mean"], ""),
        ("trace/write_reduction_mean", score["write_reduction_mean"], ""),
        ("trace/read_tras_reduction_mean",
         score["read_tras_reduction_mean"], "> 0 (merge bug pinned this at 0)"),
        ("trace/write_tras_reduction_mean",
         score["write_tras_reduction_mean"], ""),
        ("trace/read_trcd_reduction_mean", score["read_trcd_reduction_mean"], ""),
        ("trace/write_twr_reduction_mean", score["write_twr_reduction_mean"], ""),
        ("trace/tras_below_jedec_coolest_frac",
         score["tras_below_jedec_coolest_frac"], "==1"),
        ("trace/speedup_realized_mean", score["speedup_realized_mean"], ""),
        ("trace/speedup_realized_intensive_mean",
         score["speedup_realized_intensive_mean"],
         f"paper claim {perfmodel.PAPER_CLAIM_SPEEDUP}"),
        ("trace/speedup_vs_claim", score["speedup_vs_claim"], ""),
        ("trace/switches_total", score["switches_total"], ""),
        ("trace/switches_per_kstep", score["switches_per_kstep"], ""),
        ("trace/time_at_jedec_frac", score["time_at_jedec_frac"], ""),
        ("trace/time_in_coolest_bin_frac", score["time_in_coolest_bin_frac"], ""),
        ("trace/fused_dimms", float(np.asarray(res.state.fused).sum()),
         "0 unless error injection"),
    ]
    if policy is not None:
        occ_1x = policy.occupancy_of(1.0)
        rows.extend([
            ("trace/refresh_occupancy_mean", score["refresh_occupancy_mean"],
             f"1x floor {occ_1x:.5f}"),
            ("trace/speedup_combined_mean", score["speedup_combined_mean"],
             "<= latency-only"),
            ("trace/speedup_combined_intensive_mean",
             score["speedup_combined_intensive_mean"],
             f"paper claim {perfmodel.PAPER_CLAIM_SPEEDUP} (latency+refresh)"),
            ("trace/speedup_combined_vs_claim",
             score["speedup_combined_vs_claim"], ""),
            ("trace/refresh_dilution_intensive",
             score["speedup_realized_intensive_mean"]
             - score["speedup_combined_intensive_mean"], ">= 0"),
        ])
    rows.extend(region_rows)
    rows.extend(shard_rows)

    # -- regression gate vs the committed baseline -------------------------
    if regression_baseline is not None:
        base = json.loads(pathlib.Path(regression_baseline).read_text())
        floor = base["speedup_realized_intensive_mean"] - base.get("tolerance", 0.005)
        got = score["speedup_realized_intensive_mean"]
        if got < floor:  # CI must go red on a realized-speedup regression
            raise AssertionError(
                f"realized memory-intensive speedup regressed: {got:.4f} < "
                f"baseline {base['speedup_realized_intensive_mean']:.4f} - "
                f"tolerance (see {regression_baseline})"
            )
        if score["tras_below_jedec_coolest_frac"] < 1.0:
            raise AssertionError(
                "tRAS-at-JEDEC merge bug symptom: some DIMM's coolest-bin "
                "read set does not reduce tRAS below JEDEC "
                f"(frac={score['tras_below_jedec_coolest_frac']:.3f})"
            )
        if "speedup_combined_intensive_mean" in base:
            # Refresh gate (refresh_storm tiny): the COMBINED speedup may
            # not regress, and the time-weighted refresh occupancy is
            # pinned both ways — the 2x extended-temperature penalty can
            # neither silently vanish nor silently grow.
            if policy is None:
                raise AssertionError(
                    f"baseline {regression_baseline} gates refresh figures "
                    "but the run was started with --refresh off"
                )
            floor_c = (base["speedup_combined_intensive_mean"]
                       - base.get("tolerance", 0.005))
            got_c = score["speedup_combined_intensive_mean"]
            if got_c < floor_c:
                raise AssertionError(
                    f"combined latency+refresh intensive speedup regressed: "
                    f"{got_c:.4f} < baseline "
                    f"{base['speedup_combined_intensive_mean']:.4f} - "
                    f"tolerance (see {regression_baseline})"
                )
            occ_tol = base.get("occupancy_tolerance", 1e-3)
            occ_got = score["refresh_occupancy_mean"]
            if abs(occ_got - base["refresh_occupancy_mean"]) > occ_tol:
                raise AssertionError(
                    f"time-weighted refresh occupancy moved: {occ_got:.5f} "
                    f"vs pinned {base['refresh_occupancy_mean']:.5f} "
                    f"(+/- {occ_tol}, see {regression_baseline})"
                )
        if "speedup_region_aware_intensive_mean" in base:
            # Region gate (design_skew tiny): the region-aware realized
            # speedup may not regress, and it must sit STRICTLY above
            # the region-oblivious figure — the whole point of carrying
            # per-region registers on a near-skewed mix.
            if region_score is None:
                raise AssertionError(
                    f"baseline {regression_baseline} gates region figures "
                    "but the run was started with --regions 0"
                )
            floor_r = (base["speedup_region_aware_intensive_mean"]
                       - base.get("tolerance", 0.005))
            got_r = region_score["speedup_region_aware_intensive_mean"]
            if got_r < floor_r:
                raise AssertionError(
                    f"region-aware intensive speedup regressed: {got_r:.4f}"
                    f" < baseline "
                    f"{base['speedup_region_aware_intensive_mean']:.4f} - "
                    f"tolerance (see {regression_baseline})"
                )
            if not (region_score["speedup_region_aware_intensive_mean"]
                    > region_score["speedup_region_oblivious_intensive_mean"]):
                raise AssertionError(
                    "region-aware realized speedup is not strictly above "
                    "the region-oblivious figure on the "
                    f"{scenario} mix — the region axis bought nothing"
                )
        rows.append(("trace/regression_gate_pass", 1.0,
                     f">= {floor:.4f} intensive"))

    if verbose:
        print(f"# {scenario}: {n_dimms} DIMMs x {n_steps} steps = "
              f"{n_dimms * n_steps:,} transitions "
              f"(max drift {drift:.3f} C/s)")
        print(f"# scan replay: {t_scan*1e3:.1f} ms | observe loop: "
              f"{t_loop_measured:.2f} s for {n_b}x{s_b} -> "
              f"{t_loop:.1f} s extrapolated | speedup {speedup:,.0f}x")
        print(f"# loop equivalence: exact={exact} max|err|={max_err:.2e} ns")
        print(f"# per-access tRAS: read -{score['read_tras_reduction_mean']*100:.1f}% "
              f"write -{score['write_tras_reduction_mean']*100:.1f}% "
              f"(coolest-bin below-JEDEC frac "
              f"{score['tras_below_jedec_coolest_frac']:.2f})")
        print(f"# realized: read -{score['read_reduction_mean']*100:.1f}% "
              f"write -{score['write_reduction_mean']*100:.1f}% | "
              f"perf +{score['speedup_realized_mean']*100:.1f}% all, "
              f"+{score['speedup_realized_intensive_mean']*100:.1f}% "
              f"mem-intensive (paper claims "
              f"+{perfmodel.PAPER_CLAIM_SPEEDUP*100:.0f}%) | "
              f"{score['switches_total']:.0f} switches")
        if policy is not None:
            print(f"# refresh ({refresh}): occupancy "
                  f"{score['refresh_occupancy_mean']*100:.2f}% of tREFI | "
                  f"combined +{score['speedup_combined_mean']*100:.1f}% all, "
                  f"+{score['speedup_combined_intensive_mean']*100:.1f}% "
                  f"mem-intensive")
        if region_score is not None:
            print(f"# regions ({regions}): aware "
                  f"+{region_score['speedup_region_aware_intensive_mean']*100:.1f}% "
                  f"vs oblivious "
                  f"+{region_score['speedup_region_oblivious_intensive_mean']*100:.1f}% "
                  f"mem-intensive (advantage "
                  f"+{region_score['region_aware_advantage_intensive']*100:.2f} pp)")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-dimms", type=int, default=None,
                    help="fleet size (default 1000)")
    ap.add_argument("--n-steps", type=int, default=None,
                    help="trace length in observations (default 10000)")
    ap.add_argument("--scenario", choices=sorted(traces.SCENARIOS),
                    default="diurnal")
    ap.add_argument("--dt-s", type=float, default=traces.DEFAULT_DT_S,
                    help="seconds per observation (default 60)")
    ap.add_argument("--error-rate", type=float, default=0.0,
                    help="per-(step,DIMM) error-injection probability")
    ap.add_argument("--baseline-dimms", type=int, default=None,
                    help="DIMMs actually timed in the observe loop (default 24)")
    ap.add_argument("--baseline-steps", type=int, default=None,
                    help="steps actually timed in the observe loop (default 500)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 64 DIMMs x 512 steps, gated against the "
                         "committed regression baseline")
    ap.add_argument("--sharded", action="store_true",
                    help="add the trace/sharded_* section: replay + "
                         "gather-free scoring over all visible devices, "
                         "gated vs single-device (on CPU this forces 8 "
                         "host devices unless XLA_FLAGS pins a count)")
    ap.add_argument("--refresh", choices=sorted(REFRESH_POLICIES),
                    default="ddr3",
                    help="refresh policy the table carries (default ddr3: "
                         "1x/2x extended-temperature; ddr3_4x adds a 4x "
                         "step; off scores latency only)")
    ap.add_argument("--regions", type=int, default=None,
                    help="profile this many distance-from-sense-amp "
                         "regions and add the region-aware vs -oblivious "
                         "rows (default: 4 for scenarios with a paired "
                         "region mix — design_skew, hot_bank — else off; "
                         "0 disables)")
    ap.add_argument("--regression-baseline", type=str, default=None,
                    help="baseline JSON for the realized-speedup gate "
                         "(default: the committed tiny baseline when --tiny, "
                         "per scenario)")
    ap.add_argument("--json", type=str, default=None,
                    help="also write rows to this JSON artifact path")
    ap.add_argument("--bench-json", type=str, default=None,
                    help="write the refresh-on vs refresh-off speedup "
                         "comparison rows to this path (BENCH_trace_eval.json)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.tiny:
        conflicts = [name for name, val in (
            ("--n-dimms", args.n_dimms), ("--n-steps", args.n_steps),
            ("--baseline-dimms", args.baseline_dimms),
            ("--baseline-steps", args.baseline_steps),
        ) if val is not None]
        if conflicts:
            ap.error(f"--tiny fixes the configuration; remove {', '.join(conflicts)}")
        gate = args.regression_baseline
        if gate is None and args.seed == 0:  # committed configs the baselines pin
            if args.scenario == "diurnal" and TINY_BASELINE_PATH.exists():
                gate = TINY_BASELINE_PATH
            elif args.scenario == "refresh_storm" and args.refresh != "off" \
                    and REFRESH_STORM_BASELINE_PATH.exists():
                gate = REFRESH_STORM_BASELINE_PATH
            elif args.scenario == "design_skew" and args.regions != 0 \
                    and DESIGN_SKEW_BASELINE_PATH.exists():
                gate = DESIGN_SKEW_BASELINE_PATH
        rows = run(n_dimms=64, n_steps=512, scenario=args.scenario,
                   dt_s=args.dt_s, error_rate=args.error_rate,
                   baseline_dimms=8, baseline_steps=128, seed=args.seed,
                   regression_baseline=gate, sharded=args.sharded,
                   refresh=args.refresh, regions=args.regions)
    else:
        rows = run(
            n_dimms=1000 if args.n_dimms is None else args.n_dimms,
            n_steps=10_000 if args.n_steps is None else args.n_steps,
            scenario=args.scenario,
            dt_s=args.dt_s,
            error_rate=args.error_rate,
            baseline_dimms=24 if args.baseline_dimms is None else args.baseline_dimms,
            baseline_steps=500 if args.baseline_steps is None else args.baseline_steps,
            seed=args.seed,
            regression_baseline=args.regression_baseline,
            sharded=args.sharded,
            refresh=args.refresh,
            regions=args.regions,
        )
    for name, value, ref in rows:
        print(f"{name},{value:.6g},{ref}")
    meta = {"scenario": args.scenario, "tiny": args.tiny, "seed": args.seed,
            "refresh": args.refresh}
    if args.json:
        write_rows_json(args.json, "trace_eval", rows, meta=meta)
    if args.bench_json:
        # The BENCH artifact: just the refresh-on vs refresh-off speedup
        # comparison (latency-only "realized" rows vs combined rows), so
        # the refresh penalty's trajectory is machine-readable across PRs.
        bench_names = {
            "trace/speedup_realized_mean",
            "trace/speedup_realized_intensive_mean",
            "trace/speedup_vs_claim",
            "trace/refresh_occupancy_mean",
            "trace/speedup_combined_mean",
            "trace/speedup_combined_intensive_mean",
            "trace/speedup_combined_vs_claim",
            "trace/refresh_dilution_intensive",
            "trace/time_at_jedec_frac",
        }
        write_rows_json(args.bench_json, "trace_eval",
                        [r for r in rows if r[0] in bench_names], meta=meta)


if __name__ == "__main__":
    main()
