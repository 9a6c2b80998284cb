"""Fleet-scale characterization: 1,000+ synthetic DIMMs in one jitted sweep.

Reproduces the paper's Fig. 2 / §1.5 population study — per-parameter
min/mean/max timing reductions across a module population per temperature —
at fleet scale, and measures the wall-clock speedup of the batched engine
(:mod:`repro.core.fleet`) over the seed's per-DIMM Python loop.

  PYTHONPATH=src python benchmarks/fleet_sweep.py            # 1,152 DIMMs
  PYTHONPATH=src python benchmarks/fleet_sweep.py --tiny     # CI smoke run
  PYTHONPATH=src python benchmarks/fleet_sweep.py --tiny --sharded  # 8 devices

The loop baseline is timed on ``--baseline-dimms`` modules (default 24) and
extrapolated linearly to the full fleet — running the seed pipeline on the
whole fleet would take minutes-to-hours, which is the point. Pass
``--full-baseline`` to actually loop over every module.

``--sharded`` adds the mesh section (``fleet/sharded_*`` rows): the same
sweep shard_map-ped over a 1-D DIMM mesh spanning every visible device,
hard-gated bit-exact against the single-device result. On CPU it forces
``--xla_force_host_platform_device_count=8`` (unless XLA_FLAGS already
pins a device count), so CI and laptops measure a real 8-way mesh.
"""

from __future__ import annotations

import argparse
import time

try:
    from benchmarks._sharded_env import ensure_host_devices
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _sharded_env import ensure_host_devices

ensure_host_devices()  # before jax initializes its backend

import jax
import numpy as np

from repro.core import fleet, perfmodel, profiler
from repro.core.timing import PARAM_NAMES
from repro.kernels.charge_sweep import ops as charge_sweep
from repro.launch.compile_cache import enable_compile_cache

try:
    from benchmarks._json_out import write_rows_json
except ImportError:  # direct-script execution: benchmarks/ is sys.path[0]
    from _json_out import write_rows_json

#: Paper §1.5 headline band at 55 °C: per-parameter average reductions
#: range from 17.3 % (tRCD) to 54.8 % (tWR).
PAPER_55C_MIN = 0.173
PAPER_55C_MAX = 0.548


def run(
    n_dimms: int = 1152,
    temps_c=(45.0, 55.0, 85.0),
    patterns=(1.0, 1.03, 1.08),
    baseline_dimms: int = 24,
    full_baseline: bool = False,
    seed: int = 0,
    verbose: bool = True,
    sharded: bool = False,
    regions: int = 0,
):
    key = jax.random.PRNGKey(seed)
    fl = fleet.synthesize(key, n_dimms)
    grid_points = n_dimms * len(temps_c) * len(patterns)

    # -- batched engine (pure-jnp ref impl): compile, then steady state ----
    res = fleet.sweep(fl, temps_c, patterns, impl="ref")
    jax.block_until_ready(res.read)
    t0 = time.perf_counter()
    res = fleet.sweep(fl, temps_c, patterns, impl="ref")
    jax.block_until_ready(res.read)
    t_fleet = time.perf_counter() - t0

    # -- fused charge-sweep kernel: the DEFAULT impl since PR 5 ------------
    # Off-TPU this runs the kernel in interpret mode (the parity
    # configuration CI gates on), so the timing shows interpreter overhead
    # rather than fused-kernel wall-clock; on a TPU backend it compiles for
    # real. Either way the result must be bit-exact vs the ref sweep.
    kres = fleet.sweep(fl, temps_c, patterns)
    jax.block_until_ready(kres.read)
    t0 = time.perf_counter()
    kres = fleet.sweep(fl, temps_c, patterns)
    jax.block_until_ready(kres.read)
    t_kernel = time.perf_counter() - t0
    kernel_err = max(
        float(np.abs(np.asarray(kres.read) - np.asarray(res.read)).max()),
        float(np.abs(np.asarray(kres.write) - np.asarray(res.write)).max()),
        float(np.abs(np.asarray(kres.joint) - np.asarray(res.joint)).max()),
    )
    if kernel_err != 0.0:  # parity is the gate: CI must go red, not just log
        raise AssertionError(
            f"charge-sweep kernel diverged from the ref sweep: "
            f"max|err| = {kernel_err} ns"
        )

    # -- loop baseline: the seed's per-DIMM per-point execution model ------
    n_base = n_dimms if full_baseline else min(baseline_dimms, n_dimms)
    sub = fl.take(slice(0, n_base))
    t0 = time.perf_counter()
    base_res = fleet.sweep_loop_baseline(sub, temps_c, patterns)
    t_loop_measured = time.perf_counter() - t0
    t_loop = t_loop_measured * (n_dimms / n_base)
    speedup = t_loop / t_fleet

    # -- equivalence on the measured subset --------------------------------
    idx = slice(0, n_base)
    err = max(
        float(np.abs(np.asarray(res.read[:, :, idx]) - np.asarray(base_res.read)).max()),
        float(np.abs(np.asarray(res.write[:, :, idx]) - np.asarray(base_res.write)).max()),
        float(np.abs(np.asarray(res.joint[:, :, idx]) - np.asarray(base_res.joint)).max()),
    )

    # -- sharded section: DIMM axis shard_map-ped over every device --------
    # The scaling row the ROADMAP's million-module target needs: the same
    # default-impl sweep, distributed. Parity is the gate (bit-exact);
    # wall-clock scaling is reported, not asserted (CI boxes oversubscribe
    # host devices onto few cores, so speedup there is not meaningful).
    shard_rows = []
    if sharded:
        from repro.core import shard

        mesh = shard.fleet_mesh()
        n_dev = shard.n_shards(mesh)
        sres = fleet.sweep(fl, temps_c, patterns, mesh=mesh)
        jax.block_until_ready(sres.read)
        t0 = time.perf_counter()
        sres = fleet.sweep(fl, temps_c, patterns, mesh=mesh)
        jax.block_until_ready(sres.read)
        t_sharded = time.perf_counter() - t0
        shard_err = max(
            float(np.abs(np.asarray(sres.read) - np.asarray(kres.read)).max()),
            float(np.abs(np.asarray(sres.write) - np.asarray(kres.write)).max()),
            float(np.abs(np.asarray(sres.joint) - np.asarray(kres.joint)).max()),
        )
        if shard_err != 0.0:  # parity gate: CI must go red, not just log
            raise AssertionError(
                f"sharded sweep diverged from single-device: "
                f"max|err| = {shard_err} ns on {n_dev} devices"
            )
        shard_rows = [
            ("fleet/sharded_n_devices", float(n_dev), ">=8 in CI"),
            ("fleet/sharded_sweep_seconds", t_sharded, ""),
            ("fleet/sharded_vs_single_device_ratio", t_kernel / t_sharded,
             "scaling row; >1 = sharding wins"),
            ("fleet/sharded_max_abs_error_vs_single_ns", shard_err, "==0"),
            ("fleet/sharded_parity_exact",
             1.0 if shard_err == 0.0 else 0.0, "==1"),
        ]

    # -- region section: the (DIMM x temp x pattern x region) grid ---------
    # The sweep raised by one rank (distance-from-sense-amp classes).
    # Two hard gates ride along: the Pallas region sweep must be bit-exact
    # vs the ref region sweep (the region axis tiles through the same
    # kernel), and the anchor region (index R-1, region_factor exactly
    # 1.0) must reproduce the region-free sweep bitwise — the contract
    # that makes n_regions=1 invisible end to end. The throughput rows
    # are the BENCH_region_sweep artifact: grid points per second as the
    # region axis multiplies the work.
    region_rows = []
    if regions:
        rres = fleet.sweep_regions(fl, temps_c, patterns,
                                   n_regions=regions, impl="ref")
        jax.block_until_ready(rres.read)
        t0 = time.perf_counter()
        rres = fleet.sweep_regions(fl, temps_c, patterns,
                                   n_regions=regions, impl="ref")
        jax.block_until_ready(rres.read)
        t_region = time.perf_counter() - t0

        krres = fleet.sweep_regions(fl, temps_c, patterns, n_regions=regions)
        jax.block_until_ready(krres.read)
        t0 = time.perf_counter()
        krres = fleet.sweep_regions(fl, temps_c, patterns, n_regions=regions)
        jax.block_until_ready(krres.read)
        t_region_kernel = time.perf_counter() - t0

        region_kernel_err = max(
            float(np.abs(np.asarray(krres.read) - np.asarray(rres.read)).max()),
            float(np.abs(np.asarray(krres.write) - np.asarray(rres.write)).max()),
        )
        if region_kernel_err != 0.0:  # parity gate: CI goes red, not logs
            raise AssertionError(
                f"region sweep kernel diverged from the ref region sweep: "
                f"max|err| = {region_kernel_err} ns"
            )
        anchor_err = max(
            float(np.abs(np.asarray(rres.read[:, :, -1]) - np.asarray(res.read)).max()),
            float(np.abs(np.asarray(rres.write[:, :, -1]) - np.asarray(res.write)).max()),
        )
        if anchor_err != 0.0:  # anchor contract gate
            raise AssertionError(
                f"anchor region diverged from the region-free sweep: "
                f"max|err| = {anchor_err} ns (region_factor(1.0) must be 1)"
            )
        region_points = grid_points * regions
        region_rows = [
            ("fleet/region_n_regions", float(regions), ""),
            ("fleet/region_grid_points", float(region_points), ""),
            ("fleet/region_sweep_seconds", t_region, ""),
            ("fleet/region_points_per_second", region_points / t_region, ""),
            ("fleet/region_vs_base_time_ratio", t_region / t_fleet,
             f"~{regions}x the work; <{regions} = the rank-raise amortizes"),
            ("fleet/region_kernel_sweep_seconds", t_region_kernel,
             "interpret mode" if charge_sweep.default_interpret()
             else "compiled"),
            ("fleet/region_kernel_parity_exact",
             1.0 if region_kernel_err == 0.0 else 0.0, "==1"),
            ("fleet/region_anchor_exact",
             1.0 if anchor_err == 0.0 else 0.0, "==1"),
        ]

    interp = charge_sweep.default_interpret()
    rows = [
        ("fleet/n_dimms", float(n_dimms), ""),
        ("fleet/grid_points", float(grid_points), ""),
        ("fleet/sweep_seconds", t_fleet, ""),
        ("fleet/loop_seconds_extrapolated", t_loop, ""),
        ("fleet/speedup_vs_loop", speedup, ">=10"),
        ("fleet/max_abs_error_vs_loop_ns", err, "<=1e-5"),
        # Kernel-vs-ref section: the fused charge-sweep kernel against the
        # pure-jnp grid search, same fleet, same grid, bit-exact by gate.
        ("fleet/kernel_sweep_seconds", t_kernel,
         "interpret mode" if interp else "compiled"),
        ("fleet/kernel_vs_ref_time_ratio", t_kernel / t_fleet,
         "interpreter overhead dominates off-TPU" if interp else ""),
        ("fleet/kernel_max_abs_error_vs_ref_ns", kernel_err, "==0"),
        ("fleet/kernel_parity_exact", 1.0 if kernel_err == 0.0 else 0.0, "==1"),
    ]
    rows.extend(region_rows)
    rows.extend(shard_rows)

    summary = res.summary()
    for t, per_param in sorted(summary.items()):
        for p in PARAM_NAMES:
            mn, mean, mx = per_param[p]
            ref = ""
            if t == 55.0:
                ref = f"paper band {PAPER_55C_MIN:.3f}..{PAPER_55C_MAX:.3f}"
            rows.append((f"fleet/{t:g}C/{p}_reduction_mean", mean, ref))
            rows.append((f"fleet/{t:g}C/{p}_reduction_min", mn, ""))
            rows.append((f"fleet/{t:g}C/{p}_reduction_max", mx, ""))

    # -- per-DIMM performance yield (Fig. 3 at fleet scale) ----------------
    p_worst = res.worst_pattern_idx()
    ti = list(temps_c).index(55.0) if 55.0 in temps_c else 0
    t_label = f"{temps_c[ti]:g}C"
    # (N, 4) merged joint stack: say so explicitly, or a 2-DIMM run would
    # be misread as an access-type axis.
    sp = perfmodel.fleet_speedups(res.joint[ti, p_worst], split=False)
    rows.append((f"fleet/{t_label}/perf_speedup_mean", float(sp.mean() - 1.0), ""))
    rows.append((f"fleet/{t_label}/perf_speedup_min", float(sp.min() - 1.0), ""))
    rows.append((f"fleet/{t_label}/perf_speedup_max", float(sp.max() - 1.0), ""))

    if verbose:
        print(f"# fleet: {n_dimms} DIMMs x {len(temps_c)} temps x "
              f"{len(patterns)} patterns = {grid_points} grid points")
        print(f"# batched sweep: {t_fleet*1e3:.1f} ms | loop baseline: "
              f"{t_loop_measured:.2f} s for {n_base} DIMMs -> "
              f"{t_loop:.1f} s extrapolated | speedup {speedup:,.0f}x")
        print(f"# max |fleet - loop| = {err:.2e} ns")
        print(f"# charge-sweep kernel ({'interpret' if interp else 'compiled'}): "
              f"{t_kernel*1e3:.1f} ms, {t_kernel/t_fleet:.1f}x ref wall-clock, "
              f"max |kernel - ref| = {kernel_err:.2e} ns (bit-exact gate)")
        if region_rows:
            print(f"# region sweep ({regions} regions): "
                  f"{region_rows[2][1]*1e3:.1f} ms ref for "
                  f"{region_rows[1][1]:.0f} grid points "
                  f"({region_rows[4][1]:.2f}x base sweep), kernel parity + "
                  f"anchor bit-exact")
        if shard_rows:
            print(f"# sharded sweep ({shard_rows[0][1]:.0f} devices): "
                  f"{shard_rows[1][1]*1e3:.1f} ms, "
                  f"{shard_rows[2][1]:.2f}x single-device, bit-exact")
        for t, per_param in sorted(summary.items()):
            cells = ", ".join(
                f"{p} {per_param[p][0]*100:.1f}/{per_param[p][1]*100:.1f}/"
                f"{per_param[p][2]*100:.1f}%" for p in PARAM_NAMES
            )
            print(f"# {t:g} C min/mean/max: {cells}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-dimms", type=int, default=None,
                    help="fleet size (default 1152)")
    ap.add_argument("--temps", type=str, default=None,
                    help="comma-separated temperatures in C (default 45,55,85)")
    ap.add_argument("--patterns", type=str, default=None,
                    help="comma-separated data-pattern margin factors "
                         "(default 1.0,1.03,1.08)")
    ap.add_argument("--baseline-dimms", type=int, default=None,
                    help="modules to actually time in the loop baseline "
                         "(default 24)")
    ap.add_argument("--full-baseline", action="store_true",
                    help="loop over every module instead of extrapolating")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 48 DIMMs, 3 temps, worst pattern only")
    ap.add_argument("--sharded", action="store_true",
                    help="add the fleet/sharded_* section: the sweep "
                         "shard_map-ped over all visible devices, gated "
                         "bit-exact vs single-device (on CPU this forces "
                         "8 host devices unless XLA_FLAGS pins a count)")
    ap.add_argument("--regions", type=int, default=0,
                    help="add the fleet/region_* section: the sweep over "
                         "this many distance-from-sense-amp classes per "
                         "DIMM, gated bit-exact kernel-vs-ref and "
                         "anchor-vs-region-free (0 disables)")
    ap.add_argument("--json", type=str, default=None,
                    help="also write rows to this JSON artifact path")
    ap.add_argument("--bench-json", type=str, default=None,
                    help="write the fleet/region_* throughput rows to this "
                         "path (BENCH_region_sweep.json); requires --regions")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.bench_json and not args.regions:
        ap.error("--bench-json records the region sweep; add --regions N")
    if args.tiny:
        conflicts = [name for name, val in (
            ("--n-dimms", args.n_dimms), ("--temps", args.temps),
            ("--patterns", args.patterns),
            ("--baseline-dimms", args.baseline_dimms),
        ) if val is not None]
        if args.full_baseline:
            conflicts.append("--full-baseline")
        if conflicts:
            ap.error(f"--tiny fixes the configuration; remove {', '.join(conflicts)}")
        rows = run(n_dimms=48, temps_c=(45.0, 55.0, 85.0), patterns=(1.0,),
                   baseline_dimms=8, seed=args.seed, sharded=args.sharded,
                   regions=args.regions)
    else:
        n_dimms = 1152 if args.n_dimms is None else args.n_dimms
        if n_dimms < 1:
            ap.error("--n-dimms must be >= 1")
        temps = tuple(float(t) for t in (args.temps or "45,55,85").split(",")
                      if t.strip())
        pats = tuple(float(p) for p in (args.patterns or "1.0,1.03,1.08").split(",")
                     if p.strip())
        if not temps or not pats:
            ap.error("--temps/--patterns need at least one value")
        rows = run(
            n_dimms=n_dimms,
            temps_c=temps,
            patterns=pats,
            baseline_dimms=24 if args.baseline_dimms is None else args.baseline_dimms,
            full_baseline=args.full_baseline,
            seed=args.seed,
            sharded=args.sharded,
            regions=args.regions,
        )
    for name, value, ref in rows:
        print(f"{name},{value:.6g},{ref}")
    meta = {"tiny": args.tiny, "seed": args.seed, "regions": args.regions}
    if args.json:
        write_rows_json(args.json, "fleet_sweep", rows, meta=meta)
    if args.bench_json:
        # The BENCH artifact: just the region-axis sweep throughput and
        # parity rows, so the rank-raised sweep's cost trajectory is
        # machine-readable across PRs.
        write_rows_json(args.bench_json, "fleet_sweep",
                        [r for r in rows if r[0].startswith("fleet/region_")],
                        meta=meta)


if __name__ == "__main__":
    main()
