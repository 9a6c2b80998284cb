"""A run's ``correct`` against faults planted under the timed path.

Each test drives a whole tiny run through the harness, chip check stepped
over, with the program broken underneath in one way a later change could
break it, and sees ``correct`` come out false; the sound run and the
control (the reference one precision down) are here too.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, make_tiny

CELLS_1 = ["warehouse-1m.backfill", "warehouse-1m.live", "warehouse-1m.boot"]


def checks_of(result):
    return {k: (c["value"], c["limit"]) for k, c in result["checks"].items()}


@pytest.mark.parametrize("workload", CELLS_1)
def test_sound_run_is_correct(run_cell, workload):
    result = run_cell(workload)
    assert result["correct"], checks_of(result)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compiles_in_window"] == 0
    assert "setup_s" in result["metrics"]


@pytest.mark.parametrize("workload", CELLS_1)
def test_sound_run_over_several_telemetry_blocks(run_cell, monkeypatch, workload):
    """The reference runs block by block of DIMMs; with blocks smaller than
    the fleet, its pieces still line up with the program's whole fleet."""
    import inputs

    monkeypatch.setattr(inputs, "BLOCK", 384)
    result = run_cell(workload, seed=2**31 + 9)
    assert result["correct"], checks_of(result)


@pytest.mark.parametrize("workload", ["warehouse-1m.backfill", "warehouse-1m.live"])
def test_state_returned_unchanged(run_cell, monkeypatch, workload):
    from repro.core import stream

    ingest = stream.StreamingController.ingest

    def stale(self, *a, **k):
        before = self._state
        out = ingest(self, *a, **k)
        self._state = before
        return out

    monkeypatch.setattr(stream.StreamingController, "ingest", stale)
    result = run_cell(workload)
    assert not result["correct"]
    assert result["checks"]["dimm_mismatch"]["value"] > 0


@pytest.mark.parametrize("workload", ["warehouse-1m.backfill", "warehouse-1m.live"])
def test_half_the_fleet_left_out(run_cell, monkeypatch, workload):
    import jax

    from repro.core import stream

    ingest = stream.StreamingController.ingest

    def half(self, *a, **k):
        keep_state, keep_partials = self._state, self._partials
        out = ingest(self, *a, **k)
        cut = self.table.n_dimms // 2

        def mix(new, old):
            if getattr(new, "ndim", 0) == 0:
                return new
            return new.at[cut:].set(old[cut:])

        self._state = jax.tree.map(mix, self._state, keep_state)
        self._partials = jax.tree.map(mix, self._partials, keep_partials)
        return out

    monkeypatch.setattr(stream.StreamingController, "ingest", half)
    result = run_cell(workload)
    assert not result["correct"]
    assert result["checks"]["dimm_mismatch"]["value"] > 0


def test_decision_altered(run_cell, monkeypatch):
    from repro.launch import serve_fleet

    submit = serve_fleet.FleetControllerService.submit

    def altered(self, *a, **k):
        resp = submit(self, *a, **k)
        if "bin_idx" in resp:
            resp["bin_idx"] = np.asarray(resp["bin_idx"]).copy()
            resp["bin_idx"][-1, 7] += 1
        return resp

    monkeypatch.setattr(serve_fleet.FleetControllerService, "submit", altered)
    result = run_cell("warehouse-1m.live")
    assert not result["correct"]
    assert result["checks"]["dimm_mismatch"]["value"] >= 1


def test_score_altered(run_cell, monkeypatch):
    from repro.launch import serve_fleet

    score = serve_fleet.FleetControllerService.running_score

    def altered(self):
        out = dict(score(self))
        out["speedup_realized_intensive_mean"] *= 1.001
        return out

    monkeypatch.setattr(serve_fleet.FleetControllerService, "running_score",
                        altered)
    result = run_cell("warehouse-1m.backfill")
    assert not result["correct"]
    assert result["checks"]["score_gap"]["value"] > result["checks"]["score_gap"]["limit"]


def test_table_register_altered(run_cell, monkeypatch):
    from repro.core import fleet

    to_table = fleet.SweepResult.to_table

    def altered(self):
        table = to_table(self)
        table.stack = table.stack.copy()
        table.stack[3, 0, 1, 2] += 1.25
        return table

    monkeypatch.setattr(fleet.SweepResult, "to_table", altered)
    result = run_cell("warehouse-1m.boot")
    assert not result["correct"]
    assert result["checks"]["table_mismatch"]["value"] >= 1


@pytest.mark.parametrize("workload,steps", [
    ("warehouse-1m.backfill", 48), ("warehouse-1m.live", 30),
    ("warehouse-1m.boot", 0)])
def test_control_fails(tiny_root, workload, steps):
    import jax

    import control
    import harness

    cell = harness.load_cell(tiny_root, workload)
    got = control.readings(cell, 11, steps, jax.devices()[0])
    assert any(v > cell.limits[k] for k, v in got.items()), got


MESH_RUN = r"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax, harness
from repro.core import shard
if sys.argv[4] == "no_exchange":
    shard.psum = lambda x: x
harness.peaks_for = lambda bench, kind: {"hbm_bytes_per_s": 819e9}
res = harness.run(Path(sys.argv[3]), "warehouse-4m.backfill", 5, 0.2, False,
                  time.perf_counter(), chips=lambda n: jax.devices()[:n])
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "compiles_in_window": res["compiles_in_window"]}))
"""


def add_four_chip_cell(root):
    """The four-chip backfill cell, whose configuration, mix and limits are
    files of the benchmark, entered in this copy's BENCHMARK.json where the
    benchmark does not list it yet."""
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    if any(w["name"] == "warehouse-4m.backfill" for w in spec["workloads"]):
        return
    spec["configs"].append({"name": "warehouse-4m", "source": "https://arxiv.org/abs/1603.08454",
                            "file": "bench/configs/warehouse-4m.json", "reduced": [],
                            "why": "4 x 2^20 DIMMs on a (dimm,) mesh of four chips"})
    spec["workloads"].append({"name": "warehouse-4m.backfill", "config": "warehouse-4m",
                              "traffic": "backfill", "chips": 4, "why": "the mesh"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "warehouse-1m.backfill" in m.get("workloads", []):
            m["workloads"].append("warehouse-4m.backfill")
    path.write_text(json.dumps(spec))


@pytest.fixture(scope="module")
def on_four_cpu_devices(tmp_path_factory):
    """``on_four_cpu_devices(fault)``: the four-chip backfill cell run tiny
    on four CPU devices in a process of its own; one run per fault."""
    got = {}

    def run(fault):
        if fault not in got:
            root = make_tiny(tmp_path_factory.mktemp("mesh"))
            add_four_chip_cell(root)
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS="--xla_force_host_platform_device_count=4")
            out = subprocess.run(
                [sys.executable, "-c", MESH_RUN, str(BENCH),
                 str(BENCH.parent / "src"), str(root), fault],
                env=env, capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, out.stderr[-3000:]
            got[fault] = json.loads(out.stdout.strip().splitlines()[-1])
        return got[fault]

    return run


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_mesh_exchange_left_out(on_four_cpu_devices, fault):
    """The four-chip backfill cell on four CPU devices: sound, and with the
    all-reduce of the sharded finalize left out."""
    got = on_four_cpu_devices(fault)
    assert got["correct"] == (fault == "none"), got


def test_mesh_window_compiles_nothing(on_four_cpu_devices):
    """On the mesh the second chunk runs another program than the first
    (its state arrives sharded); warm-up has to run it too."""
    got = on_four_cpu_devices("none")
    assert got["compiles_in_window"] == 0, got
