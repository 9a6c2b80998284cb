"""Fleet-controller service: batched observation chunks in, timing
decisions + running score out.

The serving shape the ROADMAP's north star calls for: a long-lived
AL-DRAM controller process that holds the fleet's timing registers
(:class:`~repro.core.controller.DimmTimingTable`) and per-DIMM state,
accepts batched temperature/error observation chunks as they arrive from
telemetry, and answers with the realized per-access timing sets / bin
decisions to program plus the running realized-speedup score. Backed by
:class:`repro.core.stream.StreamingController`, so the service retains
only O(n_dimms) state + score partials no matter how long it runs, every
chunk is one jitted scan (double-buffered host→device ingestion), and the
running score is bit-exact vs materializing the whole history. Composes
with the ``"dimm"`` device mesh (:mod:`repro.core.shard`) for fleets
bigger than one device, and with ``impl="pallas"`` for the fused
replay-step kernel (:mod:`repro.kernels.replay_step`): non-decision
chunks then run step + timing lookup + score accumulation in one
VMEM-resident kernel pass, bit-exact vs the ref scan.

Usage (demo driver feeding a synthetic scenario through the service):
  PYTHONPATH=src python -m repro.launch.serve_fleet \
      --n-dimms 512 --n-steps 1440 --chunk 256 --scenario diurnal \
      --impl pallas
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional

import jax
import numpy as np

from repro.core import fleet, stream, traces
from repro.core.controller import ControllerParams, DimmTimingTable
from repro.core.timing import ACCESS_TYPES, PARAM_NAMES
from repro.launch.compile_cache import enable_compile_cache

#: Bins profiled by the demo bootstrap (the paper's evaluation points).
DEFAULT_TEMP_BINS = (45.0, 55.0, 70.0, 85.0)


class FleetControllerService:
    """The request/response face of the streaming fleet controller.

    One instance per fleet. :meth:`submit` absorbs a batched observation
    chunk and returns a status dict — with ``decisions=True`` it also
    carries the realized ``(chunk, n_dimms, 2, 4)`` timing rows (read and
    write register sets, ns), the effective bin per step (``n_bins`` =
    the JEDEC fallback sentinel) and the switch flags, which is exactly
    what a hardware-programming agent consumes. :meth:`running_score`
    finalizes the accumulated partials at any time without disturbing the
    stream. ``interpret`` passes through to the fused kernel
    (``None``: interpret mode everywhere but TPU)."""

    def __init__(
        self,
        table: DimmTimingTable,
        params: ControllerParams = ControllerParams(),
        mesh=None,
        impl: str = "ref",
        interpret: Optional[bool] = None,
    ):
        self.engine = stream.StreamingController(
            table, params=params, mesh=mesh, impl=impl, interpret=interpret
        )

    @property
    def table(self) -> DimmTimingTable:
        return self.engine.table

    def submit(self, temps, errors=None, decisions: bool = False) -> Dict:
        """Ingest one ``(chunk_steps, n_dimms)`` observation chunk."""
        out = self.engine.ingest(temps, errors, return_decisions=decisions)
        resp = {
            "n_steps": self.engine.n_steps,
            "n_chunks": self.engine.n_chunks,
            "total_switches": self.engine.total_switches,
            "errors_total": self.engine.errors_total,
        }
        if decisions:
            rows, bin_idx, switched = out
            resp.update(timings=rows, bin_idx=bin_idx, switched=switched)
        return resp

    def running_score(self) -> Dict[str, float]:
        """The bit-exact ``trace_score`` dict over everything submitted."""
        return self.engine.score()


def bootstrap_table(
    key: jax.Array, n_dimms: int, temp_bins=DEFAULT_TEMP_BINS
) -> DimmTimingTable:
    """Profile a synthetic fleet into the controller's timing registers
    (the boot-time characterization pass a real deployment runs once)."""
    fl = fleet.synthesize(key, n_dimms)
    return fleet.sweep(fl, tuple(temp_bins), (1.0,)).to_table()


def serve(
    n_dimms: int = 512,
    n_steps: int = 1440,
    chunk: int = stream.DEFAULT_CHUNK_STEPS,
    scenario: str = "diurnal",
    error_rate: float = 0.0,
    dt_s: float = traces.DEFAULT_DT_S,
    decisions: bool = False,
    sharded: bool = False,
    seed: int = 0,
    table: Optional[DimmTimingTable] = None,
    impl: str = "ref",
) -> Dict[str, float]:
    """Demo driver: boot the service, stream a synthetic scenario through
    it chunk by chunk, report throughput + the running score."""
    key = jax.random.PRNGKey(seed)
    if table is None:
        table = bootstrap_table(key, n_dimms)
    mesh = None
    if sharded:
        from repro.core import shard

        mesh = shard.fleet_mesh()
    service = FleetControllerService(table, mesh=mesh, impl=impl)

    k_t, k_e = jax.random.split(jax.random.fold_in(key, 1))
    trace = np.asarray(traces.generate(scenario, k_t, n_dimms, n_steps, dt_s=dt_s))
    errors = (
        np.asarray(traces.error_injections(k_e, n_steps, n_dimms, error_rate))
        if error_rate > 0.0
        else None
    )

    t0 = time.perf_counter()
    resp: Dict = {}
    for temps_c, errs_c in stream.iter_chunks(trace, errors, chunk):
        resp = service.submit(temps_c, errs_c, decisions=decisions)
    jax.block_until_ready(service.engine.state)
    wall = time.perf_counter() - t0
    score = service.running_score()

    realtime = n_steps * dt_s / max(wall, 1e-9)
    print(
        f"[serve_fleet] {scenario}: {n_dimms} DIMMs × {n_steps} steps "
        f"(chunk {chunk}, impl {impl}{', sharded' if sharded else ''}"
        f"{', decisions' if decisions else ''}) | "
        f"{resp.get('n_chunks', 0)} chunks in {wall:.2f} s "
        f"({n_steps * n_dimms / max(wall, 1e-9):,.0f} obs/s, "
        f"{realtime:,.0f}× real time)"
    )
    print(
        f"[serve_fleet] running score: realized "
        f"{score['speedup_realized_mean'] * 100:+.2f} % "
        f"(intensive {score['speedup_realized_intensive_mean'] * 100:+.2f} %), "
        f"switches {resp.get('total_switches', 0)}, "
        f"time at JEDEC {score['time_at_jedec_frac'] * 100:.1f} %"
    )
    if decisions:
        rows = np.asarray(resp["timings"])
        bins = np.asarray(resp["bin_idx"])
        for a, ai in (("read", 0), ("write", 1)):
            last = ", ".join(
                f"{p}={rows[-1, 0, ai, pi]:.2f}"
                for pi, p in enumerate(PARAM_NAMES)
            )
            print(f"[serve_fleet] DIMM 0 last {a} set (ns): {last}")
        print(
            f"[serve_fleet] DIMM 0 last bin: {int(bins[-1, 0])} "
            f"(JEDEC sentinel = {table.n_bins}); access order {ACCESS_TYPES}"
        )
    return score


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-dimms", type=int, default=512)
    ap.add_argument("--n-steps", type=int, default=1440)
    ap.add_argument("--chunk", type=int, default=stream.DEFAULT_CHUNK_STEPS)
    ap.add_argument("--scenario", default="diurnal",
                    choices=sorted(traces.SCENARIOS))
    ap.add_argument("--error-rate", type=float, default=0.0)
    ap.add_argument("--decisions", action="store_true",
                    help="return per-chunk timing rows / bin decisions")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the DIMM axis over the fleet mesh")
    ap.add_argument("--impl", default="ref", choices=("ref", "pallas"),
                    help="chunk-scan implementation (pallas = fused kernel)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    serve(
        n_dimms=args.n_dimms, n_steps=args.n_steps, chunk=args.chunk,
        scenario=args.scenario, error_rate=args.error_rate,
        decisions=args.decisions, sharded=args.sharded, seed=args.seed,
        impl=args.impl,
    )


if __name__ == "__main__":
    main()
