"""The benchmark harness: one cell, one run, one result line.

Everything a cell is made of is found by name: the cell's entry in
``BENCHMARK.json``, its deployment in ``bench/configs/<config>.json``, its
traffic mix in ``bench/traffic/<traffic>.json``, the limits of its
correctness checks in ``bench/limits/<cell>.json`` and each metric's reader
in ``bench/metrics/<metric>.py``. A new cell, mix, configuration or metric
is a new file; no file here needs an edit for it.

A run: make the inputs from the seed, set the program up as a deployment
does (its own defaults pick every implementation), warm every call the mix
makes, then drive the mix in a closed loop for ``--seconds`` and until the
repeating unit in flight completes. After the window: read the device's
peak memory, free the program's state, run the plain reference over the
same inputs and compare.

A traffic mix's repeating unit is, in order: ``boots`` characterizations
of the fleet (``fleet.sweep`` then ``to_table``, ended when the table is on
the host), ``submits`` telemetry chunks of ``chunk_steps`` steps through
``FleetControllerService.submit`` (with ``decisions``, the timing rows, bins
and switch flags are copied to the host inside the call's time), and
``scores`` reads of ``running_score``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

#: Persistent compile cache, at a fixed path inside the checkout: the path
#: is part of the cache's key, so a directory that moved would never hit.
CACHE_DIR = ".jax_cache"
#: Where a traced run writes its profile (removed once read).
TRACE_DIR = ".bench_trace"


class Refused(SystemExit):
    """A run that must end without a result line."""


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: Path

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"bench: {path} is missing")


def load_cell(root: Path, workload: str) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    entries = [w for w in spec["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise Refused(f"bench: no workload named {workload!r}")
    entry = entries[0]
    configs = [c for c in spec["configs"] if c["name"] == entry["config"]]
    if len(configs) != 1:
        raise Refused(f"bench: no configuration named {entry['config']!r}")
    bench = root / spec["paths"][0]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return Cell(
        name=workload, entry=entry,
        config=_json(root / configs[0]["file"]),
        traffic=_json(bench / "traffic" / f"{entry['traffic']}.json"),
        limits=_json(bench / "limits" / f"{workload}.json"),
        end_to_end=e2e, per_layer=per_layer, bench=bench,
    )


def metric_reader(bench: Path, name: str) -> Callable:
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    if spec is None or not path.exists():
        raise Refused(f"bench: no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------
def require_chips(n: int):
    """The first ``n`` TPU devices; refuses any other platform or fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"bench: JAX found no TPU (platform "
                      f"{devices[0].platform!r}); this benchmark runs on the chip")
    if len(devices) < n:
        raise Refused(f"bench: the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peaks_for(bench: Path, kind: str) -> dict:
    table = _json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise Refused(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_cache(root: Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    # Cache every program, the small eager ones of the score's finalize too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs compiled, or read from the persistent cache, while
    ``active``: the window should do neither."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if self.active and event.endswith("compilation_cache/cache_hits"):
            self.count += 1


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Call:
    kind: str        # "boot" | "submit" | "score"
    t0: float
    t1: float
    steps: int = 0   # telemetry steps absorbed (submit)
    decisions: bool = False


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    n_dimms: int
    n_bins: int
    chips: int
    peaks: dict
    setup_s: float
    window_s: float
    calls: List[Call]
    trace: Optional[object] = None   # xplane.Trace of the traced window

    def calls_of(self, kind: str, decisions: Optional[bool] = None) -> List[Call]:
        return [c for c in self.calls if c.kind == kind
                and (decisions is None or c.decisions == decisions)]

    def trace_window(self):
        return self.trace.window()

    def idle_share(self) -> Optional[float]:
        """Per cent of the traced window in which no operation ran, mean
        over the cell's chips."""
        if self.trace is None:
            return None
        lo, hi = self.trace_window()
        return 100.0 * (1.0 - self.trace.mean_busy(lo, hi) / (hi - lo))

    def busy_ms_in(self, span: str) -> Optional[List[float]]:
        """Device-busy milliseconds inside each host span ``span``."""
        if self.trace is None:
            return None
        got = self.trace.busy_in_spans(span)
        return [b / 1e6 for b in got] or None


# ---------------------------------------------------------------------------
# The program, set up and driven as a deployment does
# ---------------------------------------------------------------------------
class Driver:
    """Set-up and the closed loop of one caller."""

    def __init__(self, cell: Cell, seed: int, devices):
        import jax
        import inputs
        from repro.core import fleet, shard
        from repro.core.charge import CellParams
        from repro.core.controller import ControllerParams
        from repro.launch.serve_fleet import FleetControllerService

        self.fleet = fleet
        cfg, tr = cell.config, cell.traffic
        self.unit = tr["unit"]
        self.n = int(cfg["n_dimms"])
        self.temp_bins = tuple(float(t) for t in cfg["temp_bins"])
        self.patterns = tuple(float(p) for p in cfg["patterns"])
        if int(cfg["n_regions"]) != 1:
            raise Refused("bench: region sweeps are not driven yet")
        mesh_devices = int(cfg["mesh"]["devices"]) if cfg.get("mesh") else 0
        self.mesh = shard.fleet_mesh(mesh_devices) if mesh_devices else None
        with jax.default_device(devices[0]):
            self.cells = inputs.population(
                inputs.fleet_key(seed), inputs.vendor_split(
                    self.n, cfg["vendor_split"]))
        self.fl = fleet.Fleet(
            cells=CellParams(self.cells.r, self.cells.c, self.cells.leak),
            vendor=self.cells.vendor)
        self.service = None
        self.pool = []
        self.steps = 0                 # telemetry steps absorbed
        self.kept_decisions: Dict[int, tuple] = {}
        self.kept_tables: List[np.ndarray] = []
        self.last_score = None
        self.score_steps = 0
        self.boots = 0
        rng = np.random.default_rng(seed)
        self.keep_every = int(tr.get("keep_every", 0))
        self.keep_offset = int(rng.integers(self.keep_every)) if self.keep_every else 0
        self.boot_keep = int(rng.integers(1, 1 + int(tr.get("keep_boot_within", 1))))
        if self.unit["submits"]:
            chunk = int(tr["pool_chunk_steps"])
            self.chunk_steps = int(self.unit["chunk_steps"])
            self.pool_chunk = chunk
            self.pool_steps = int(tr["pool_chunks"]) * chunk
            if self.pool_steps % self.chunk_steps:
                raise Refused("bench: the pool must hold whole submits")
            self.pool = inputs.pool(seed, cfg, int(tr["pool_chunks"]), chunk,
                                    device=devices[0])
            table = fleet.sweep(self.fl, self.temp_bins, self.patterns,
                                mesh=self.mesh).to_table()
            c = cfg["controller"]
            params = ControllerParams(float(c["guard_band_c"]),
                                      float(c["hysteresis_c"]),
                                      int(c["hysteresis_steps"]))
            self.service = FleetControllerService(table, params, mesh=self.mesh)

    # -- one call each --------------------------------------------------
    def _telemetry(self, steps: int):
        k = self.steps % self.pool_steps
        temps, errors = self.pool[k // self.pool_chunk]
        lo = k % self.pool_chunk
        if steps == 1:
            return temps[lo], errors[lo]
        return temps[lo:lo + steps], errors[lo:lo + steps]

    def boot(self, calls: List[Call]) -> None:
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("bench.boot"):
            with TraceAnnotation("bench.sweep"):
                res = self.fleet.sweep(self.fl, self.temp_bins, self.patterns,
                                       mesh=self.mesh)
            with TraceAnnotation("bench.to_table"):
                table = res.to_table()
        calls.append(Call("boot", t0, time.perf_counter()))
        self.boots += 1
        if self.boots == self.boot_keep:
            self.kept_tables.append((table.temp_bins, table.stack))
        self.last_table = table

    def submit(self, calls: List[Call]) -> None:
        from jax.profiler import TraceAnnotation

        steps = self.chunk_steps
        decide = bool(self.unit["decisions"])
        temps, errors = self._telemetry(steps)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.submit"):
            resp = self.service.submit(temps, errors, decisions=decide)
            if decide:
                with TraceAnnotation("bench.to_host"):
                    got = (np.asarray(resp["timings"]), np.asarray(resp["bin_idx"]),
                           np.asarray(resp["switched"]))
        calls.append(Call("submit", t0, time.perf_counter(), steps, decide))
        if decide:
            for i in range(steps):
                k = self.steps + i
                if self.keep_every and k % self.keep_every == self.keep_offset:
                    self.kept_decisions[k] = tuple(a[i] for a in got)
            self.last_decisions = (self.steps + steps - 1,
                                   tuple(a[-1] for a in got))
        self.steps += steps

    def score(self, calls: List[Call]) -> None:
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("bench.running_score"):
            self.last_score = self.service.running_score()
        calls.append(Call("score", t0, time.perf_counter()))
        self.score_steps = self.steps

    def run_unit(self, calls: List[Call]) -> None:
        for _ in range(int(self.unit["boots"])):
            self.boot(calls)
        for _ in range(int(self.unit["submits"])):
            self.submit(calls)
        for _ in range(int(self.unit["scores"])):
            self.score(calls)

    def warm_up(self) -> None:
        """One call of each kind the mix makes: every program the window
        runs is compiled (or read from the cache) here."""
        sink: List[Call] = []
        if self.unit["boots"]:
            self.boot(sink)
            self.boots = 0
            self.kept_tables.clear()
        if self.unit["submits"]:
            self.submit(sink)
            if self.mesh is not None:
                # The first chunk finds the service's fresh state on one
                # device, every later one finds it sharded over the mesh:
                # a second program.
                self.submit(sink)
        if self.unit["scores"]:
            self.score(sink)
        self.kept_decisions.clear()

    def produced(self) -> dict:
        """What the window made, on the host, for the comparison."""
        out: dict = {"steps": self.steps}
        if self.service is not None:
            eng = self.service.engine
            out["state"] = tuple(np.asarray(x) for x in eng.state)
            p = eng.partials
            out["partials"] = (np.asarray(p.occupancy), np.asarray(p.switches),
                               np.asarray(p.timing_sums), int(p.n_steps))
        if self.last_score is not None:
            out["score"] = (self.score_steps, dict(self.last_score))
        if self.unit["submits"] and self.unit["decisions"]:
            kept = dict(self.kept_decisions)
            k, rows = self.last_decisions
            kept[k] = rows
            out["decisions"] = kept
        if self.unit["boots"]:
            out["tables"] = list(self.kept_tables)
            out["tables"].append((self.last_table.temp_bins,
                                  self.last_table.stack))
        return out

    def free(self) -> None:
        self.service = None
        self.pool = []
        self.last_table = None
        self.fl = None
        gc.collect()


# ---------------------------------------------------------------------------
# The comparison with the plain reference
# ---------------------------------------------------------------------------
def reference_outputs(cell: Cell, seed: int, produced: dict, cells,
                      device, dtype=None) -> dict:
    """Run the reference over the inputs of the run, all of them made again
    from the seed, one block of DIMMs at a time (the DIMMs are independent),
    and return what it produces: its answers, and for each register-valued
    answer the low and high edge of its float32 band (``reference.band``)."""
    import jax
    import jax.numpy as jnp
    import inputs
    import reference

    dtype = jnp.float32 if dtype is None else dtype
    cfg, tr = cell.config, cell.traffic
    n = int(cfg["n_dimms"])
    steps = int(produced["steps"])
    replayed = "state" in produced
    keep = sorted(produced.get("decisions") or {})
    key = inputs.telemetry_key(seed)
    tel = inputs.telemetry_params(cfg)
    rate = float(cfg["error_rate_per_dimm_step"])
    parts = []
    with jax.default_device(device):
        for j, (lo, hi) in enumerate(inputs.blocks(n)):
            sub = type(cells)(*(x[lo:hi] for x in cells))
            tables = reference.band(sub, cfg["temp_bins"], cfg["patterns"], dtype)
            if not replayed:
                parts.append((tables,))
                continue
            chunk = int(tr["pool_chunk_steps"])

            def chunks():
                for c in range(math.ceil(steps / chunk)):
                    t, e = inputs.telemetry_block(
                        key, c % int(tr["pool_chunks"]), j, hi - lo, chunk,
                        tel, rate)
                    yield t, e, min(chunk, steps - c * chunk)

            parts.append((tables,) + reference.replay(
                tables[0], cfg["temp_bins"], cfg["controller"], chunks(), keep,
                dtype))
    cat = lambda xs: np.concatenate(list(xs))
    bands = [cat(p[0][i] for p in parts) for i in range(3)]
    out: dict = {"table": bands[0], "table_band": bands[1:],
                 "temp_bins": tuple(float(t) for t in cfg["temp_bins"])}
    if replayed:
        out["state"] = reference.State(*(cat(p[1][i] for p in parts)
                                         for i in range(3)))
        out["partials"] = reference.Partials(
            *(cat(p[2][i] for p in parts) for i in range(3)), parts[0][2].n_steps)
        out["decisions"] = {k: tuple(cat(p[3][k][i] for p in parts)
                                     for i in range(3)) for k in keep}
        full = [reference.with_jedec(b) for b in bands[1:]]
        occ = out["partials"].occupancy
        out["sums_band"] = [reference.timing_sums(occ, f, dtype) for f in full]
        every = np.arange(n)
        out["rows_band"] = {k: [f[every, out["decisions"][k][1]] for f in full]
                            for k in keep}
        if "score" in produced:
            out["score"] = reference.score(out["partials"], out["table"])
    return out


def _outside(got: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Per leading index: does any entry lie outside ``[lo, hi]``?"""
    bad = (got < lo) | (got > hi)
    return bad.reshape(len(bad), -1).any(axis=1)


def compare(produced: dict, ref: dict) -> Dict[str, float]:
    """Each number compared, by its short name.

    ``dimm_mismatch`` counts DIMMs whose controller state (bin, cool
    streak, fuse), occupancy per effective bin or switch count differ from
    the reference's, or whose 2 x 4 timing sums lie outside the reference's
    band; plus, in decision cells, each DIMM of each kept decision row
    whose effective bin or switch flag differs or whose timing row lies
    outside the band. A different number of steps absorbed counts every
    DIMM. ``score_gap`` is the largest relative gap between the means
    behind the last running score and the reference's. ``table_mismatch``
    counts the registers of the kept boot tables outside the band. The
    band is a single value wherever no cell sits within float32 rounding
    of a threshold, so these are exact comparisons but ``score_gap``."""
    import reference

    got: Dict[str, float] = {}
    if "state" in produced:
        occ, sw, sums, n_steps = produced["partials"]
        rp = ref["partials"]
        bad = (occ != rp.occupancy).any(axis=1) | (sw != rp.switches)
        bad |= _outside(sums, *ref["sums_band"])
        for p, r in zip(produced["state"], ref["state"]):
            bad |= np.asarray(p) != np.asarray(r)
        count = int(bad.sum())
        if n_steps != rp.n_steps:
            count += len(sw)
        for k, (rows, eff, flags) in produced.get("decisions", {}).items():
            _, re, rs = ref["decisions"][k]
            wrong = (eff != re) | (flags != rs) | _outside(rows, *ref["rows_band"][k])
            count += int(wrong.sum())
        got["dimm_mismatch"] = float(count)
    if "score" in produced:
        steps, score = produced["score"]
        got["score_gap"] = (reference.score_gap(score, ref["score"])
                            if steps == ref["partials"].n_steps else math.inf)
    if "tables" in produced:
        lo, hi = ref["table_band"]
        bad = 0
        for bins, stack in produced["tables"]:
            if tuple(bins) != ref["temp_bins"] or stack.shape != lo.shape:
                bad += lo.size
            else:
                bad += int(((stack < lo) | (stack > hi)).sum())
        got["table_mismatch"] = float(bad)
    return got


def judge(got: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    return {k: {"value": v, "limit": limits.get(k)} for k, v in got.items()}


def correct(checks: Dict[str, dict]) -> bool:
    return bool(checks) and all(
        c["limit"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def run(root: Path, workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, chips=require_chips) -> dict:
    """One run of one cell; returns the result line's object."""
    cell = load_cell(root, workload)
    devices = chips(cell.chips)
    import jax

    enable_cache(root)
    compiles = CompileCounter()
    peaks = peaks_for(cell.bench, devices[0].device_kind)
    for m in cell.end_to_end + cell.per_layer:
        metric_reader(cell.bench, m["name"])        # every reader is there
    drv = Driver(cell, seed, devices)
    drv.warm_up()
    setup_s = time.perf_counter() - t_start

    trace_dir = root / TRACE_DIR / f"{workload}-{seed}"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans are the benchmark's own
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    calls: List[Call] = []
    compiles.active = True
    w0 = time.perf_counter()
    while True:
        drv.run_unit(calls)
        if calls[-1].t1 - w0 >= seconds:
            break
    window_s = calls[-1].t1 - w0
    compiles.active = False
    trace = None
    if traced:
        jax.profiler.stop_trace()
        import xplane

        trace = xplane.load(str(trace_dir),
                            devices=[d.id for d in devices])
        shutil.rmtree(root / TRACE_DIR, ignore_errors=True)
    device = device_record(devices)

    produced = drv.produced()
    cells = drv.cells
    drv.free()
    ref = reference_outputs(cell, seed, produced, cells, devices[0])
    checks = judge(compare(produced, ref), cell.limits)

    rec = Run(cell=cell, n_dimms=drv.n, n_bins=len(drv.temp_bins),
              chips=len(devices), peaks=peaks, setup_s=setup_s,
              window_s=window_s, calls=calls, trace=trace)
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = metric_reader(cell.bench, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    ok = correct(checks)
    result = {"correct": ok, "attempted": len(calls),
              "failed": 0 if ok else len(calls),
              "metrics": metrics, "device": device,
              "compiles_in_window": compiles.count}
    if traced:
        lo, hi = trace.window()
        result["device"]["busy_s"] = trace.mean_busy(lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        idle = sorted(trace.idle_by_span(lo, hi).items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[n, s / 1e9] for n, s in trace.top_ops(lo, hi)],
            "idle_gaps": [[n, s / 1e9] for n, s in idle[:10]],
        }
    result["checks"] = checks
    return result


def main(argv: List[str], root: Path, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(root, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    except Refused as e:
        print(e.code, file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
