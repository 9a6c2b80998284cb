"""Benchmark driver: one section per paper table/figure + the framework's
own roofline/margin benches. Prints ``name,value,reference`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--skip-altune]
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-altune", action="store_true",
                    help="skip interpret-mode kernel profiling (slow)")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (
        crossval,
        fig2_profiling,
        fig3_performance,
        fleet_sweep,
        paper_extras,
        roofline,
        trace_eval,
    )

    sections = [
        ("fig2 (115-DIMM profiling)", fig2_profiling.run),
        ("fleet sweep (batched characterization)",
         lambda: fleet_sweep.run(n_dimms=256, baseline_dimms=8, verbose=False)),
        ("trace eval (controller replay)",
         lambda: trace_eval.run(n_dimms=128, n_steps=1000, baseline_dimms=8,
                                baseline_steps=100, verbose=False)),
        ("fig3 (real-system performance)", fig3_performance.run),
        ("paper extras (§1.7)", paper_extras.run),
        ("roofline (dry-run cells)", roofline.run),
        ("analytic-vs-HLO crossval", crossval.run),
    ]
    if not args.skip_altune:
        from benchmarks import altune_margin
        sections.append(("altune margin (TPU embodiment)", altune_margin.run))

    print("name,value,reference")
    failures = 0
    for title, fn in sections:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"# SECTION FAILED: {title}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failures += 1
            continue
        print(f"# --- {title} ({time.time()-t0:.1f}s) ---")
        for name, value, ref in rows:
            v = f"{value:.4f}" if isinstance(value, float) else value
            print(f"{name},{v},{ref}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
