"""Region-axis physics and plumbing invariants (design-induced variation).

The region axis models distance-from-sense-amp classes inside one module
(Lee et al., design-induced latency variation): near regions have less
bitline/wordline RC to drive, so they charge faster and tolerate tighter
timings. Region index R−1 is the ANCHOR — the farthest class, whose
``region_factor`` is exactly 1.0 — so every region-free profile is the
anchor's, and ``n_regions=1`` must reproduce the legacy model bitwise.

Pinned here:

* physics — min-safe timings monotone non-decreasing in region index at
  fixed (temperature, pattern); the anchor bitwise-equal to the
  region-free profile; region sweep ref ≡ pallas bitwise;
* persistence — v1–v4 region-broadcast JSON loads bitwise-equal to an
  explicit n_regions=1 v5 table; v5 rank-5 roundtrip;
* scoring — region-aware ≥ region-oblivious realized speedup on EVERY
  access mix (elementwise speedup dominance), with the gap growing with
  near-skew and collapsing on far-skew;
* streaming — streamed region counts and the finalized score dict
  bitwise-equal to the materialized accumulation at every chunking;
* traces — the ``hot_bank`` / ``design_skew`` scenarios respect the
  paper's <0.1 °C/s drift bound, and region access mixes are exact
  integer allocations.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import charge, dimm, fleet, profiler, traces
from repro.core.charge import DEFAULT_CONSTANTS
from repro.core.controller import DimmTimingTable, replay
from repro.core.perfmodel import region_trace_score
from repro.core.stream import replay_stream
from repro.core.timing import ACCESS_TYPES, PARAM_NAMES

KEY = jax.random.PRNGKey(0)


def small_cells(n: int = 4):
    cells, _ = dimm.sample_population(jax.random.PRNGKey(0))
    return type(cells)(r=cells.r[:n], c=cells.c[:n], leak=cells.leak[:n])


def region_table(n_regions: int = 3, n: int = 4):
    return DimmTimingTable.profile(
        small_cells(n), temp_bins=(55.0, 70.0, 85.0), n_regions=n_regions
    )


# ---------------------------------------------------------------- physics

def test_region_factor_anchored_and_monotone():
    fracs = charge.region_fracs(5)
    assert fracs.shape == (5,)
    # The farthest class IS the module's worst case: factor exactly 1.0,
    # so its profile is bitwise the region-free one.
    assert float(charge.region_factor(fracs[-1], DEFAULT_CONSTANTS)) == 1.0
    factors = np.asarray(charge.region_factor(fracs, DEFAULT_CONSTANTS))
    assert (np.diff(factors) > 0).all()          # nearer → smaller factor
    assert (factors > 0).all()
    # n_regions=1 degenerates to the anchor alone.
    assert float(charge.region_fracs(1)[0]) == 1.0


@settings(max_examples=8, deadline=None)
@given(st.floats(30.0, 85.0), st.floats(0.5, 1.0))
def test_min_safe_timings_monotone_in_region(temp_c, pattern):
    # At ANY fixed (temperature, pattern): farther regions (larger frac,
    # more RC) must never need less time, for every parameter and both
    # access types — the ordering the per-region register sets rely on.
    cells = small_cells()
    fracs = charge.region_fracs(4)
    reads = np.stack([
        np.asarray(profiler.individual_min_timings(
            cells, temp_c, pattern, impl="ref", region_frac=f))
        for f in fracs
    ])                                           # (R, N, 4)
    writes = np.stack([
        np.asarray(profiler.write_mode_min_timings(
            cells, temp_c, pattern, impl="ref", region_frac=f))
        for f in fracs
    ])
    assert (np.diff(reads, axis=0) >= 0).all()
    assert (np.diff(writes, axis=0) >= 0).all()


def test_region_sweep_anchor_equals_legacy_sweep_bitwise():
    cells = small_cells()
    temps, patterns = (45.0, 85.0), (0.8, 1.0)
    legacy = fleet.sweep(cells, temps_c=temps, patterns=patterns, impl="ref")
    regions = fleet.sweep_regions(
        cells, temps_c=temps, patterns=patterns, n_regions=3, impl="ref"
    )
    # Anchor region (last index) ≡ the region-free sweep, bitwise.
    np.testing.assert_array_equal(
        np.asarray(regions.read[:, :, -1]), np.asarray(legacy.read))
    np.testing.assert_array_equal(
        np.asarray(regions.write[:, :, -1]), np.asarray(legacy.write))
    # And the single-region sweep is the legacy sweep with a unit axis.
    one = fleet.sweep_regions(
        cells, temps_c=temps, patterns=patterns, n_regions=1, impl="ref"
    )
    np.testing.assert_array_equal(
        np.asarray(one.read[:, :, 0]), np.asarray(legacy.read))
    np.testing.assert_array_equal(
        np.asarray(one.write[:, :, 0]), np.asarray(legacy.write))


def test_region_sweep_ref_matches_pallas_bitwise():
    cells = small_cells()
    kw = dict(temps_c=(45.0, 85.0), patterns=(0.8, 1.0), n_regions=4)
    ref_r = fleet.sweep_regions(cells, impl="ref", **kw)
    pal_r = fleet.sweep_regions(cells, impl="pallas", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(ref_r.read),
                                  np.asarray(pal_r.read))
    np.testing.assert_array_equal(np.asarray(ref_r.write),
                                  np.asarray(pal_r.write))


def test_region_table_monotone_and_oblivious_is_anchor():
    table = region_table(n_regions=3)
    rs = table.region_stack()                    # (N, B, R, 2, 4)
    assert (np.diff(rs, axis=2) >= 0).all()
    # For monotone profiles the max-over-regions register set IS the
    # farthest region's — what a region-unaware controller programs.
    np.testing.assert_array_equal(table.oblivious_stack(), rs[:, :, -1])
    # Per-region row lookup reads the rank-5 registers.
    row = table.row(1, 0, region=0)
    assert row.read.trcd == float(rs[1, 0, 0, 0, 0])
    assert row.write.tras <= table.row(1, 0, region=2).write.tras + 1e-6
    with pytest.raises(IndexError, match="region"):
        table.row(0, 0, region=3)


# ------------------------------------------------------------ persistence

def test_v3_json_loads_bitwise_equal_to_explicit_v5_r1():
    table = DimmTimingTable.profile(small_cells(),
                                    temp_bins=(55.0, 70.0, 85.0))
    v3 = json.dumps({
        "schema_version": 3,
        "params": list(PARAM_NAMES),
        "access_types": list(ACCESS_TYPES),
        "temp_bins": list(table.temp_bins),
        "stack": table.stack.tolist(),
    })
    v5 = json.dumps({
        "schema_version": 5,
        "params": list(PARAM_NAMES),
        "access_types": list(ACCESS_TYPES),
        "temp_bins": list(table.temp_bins),
        "n_regions": 1,
        "refresh": None,
        "stack": table.stack[:, :, None].tolist(),   # explicit rank-5, R=1
    })
    a, b = DimmTimingTable.from_json(v3), DimmTimingTable.from_json(v5)
    assert a == b == table
    # Both collapse to the canonical rank-4 representation.
    assert a.stack.ndim == b.stack.ndim == 4
    assert a.n_regions == b.n_regions == 1


def test_v5_region_table_roundtrip_bitwise():
    table = region_table(n_regions=3)
    assert table.n_regions == 3
    obj = json.loads(table.to_json())
    assert obj["schema_version"] == 5 and obj["n_regions"] == 3
    again = DimmTimingTable.from_json(table.to_json())
    assert again == table
    np.testing.assert_array_equal(again.region_stack(), table.region_stack())
    assert again.n_regions == 3


def test_region_free_table_profiles_bitwise_vs_r1():
    # profile(n_regions=1) must be the legacy profile, bitwise — the
    # degenerate region axis is invisible end to end.
    free = DimmTimingTable.profile(small_cells(), temp_bins=(55.0, 85.0))
    r1 = DimmTimingTable.profile(small_cells(), temp_bins=(55.0, 85.0),
                                 n_regions=1)
    assert free == r1
    np.testing.assert_array_equal(free.stack, r1.stack)


# ---------------------------------------------------------------- scoring

def _scored(profile: str, n_regions: int = 3):
    table = region_table(n_regions=n_regions, n=6)
    tr = traces.generate("diurnal", KEY, 6, 128)
    rep = replay(table, tr)
    mix = traces.region_access_mix(
        jax.random.PRNGKey(7), 128, 6, n_regions, profile=profile
    )
    return region_trace_score(table.region_stack(), rep, mix), table, tr, mix


@pytest.mark.parametrize("profile", traces.REGION_MIX_PROFILES)
def test_region_aware_never_below_oblivious(profile):
    # Elementwise dominance: each region's registers are ≤ the oblivious
    # (max-over-regions) set, and IPC is monotone in every timing
    # parameter — so the weighted speedup dominates on ANY mix.
    score, *_ = _scored(profile)
    assert (score["speedup_region_aware_mean"]
            >= score["speedup_region_oblivious_mean"] - 1e-9)
    assert (score["speedup_region_aware_intensive_mean"]
            >= score["speedup_region_oblivious_intensive_mean"] - 1e-9)
    assert score["region_aware_advantage_intensive"] >= -1e-9


def test_region_advantage_grows_with_near_skew():
    near, *_ = _scored("near")
    far, *_ = _scored("far")
    uniform, *_ = _scored("uniform")
    # Near-skewed placement is where design-induced variation pays;
    # far-skew concentrates on the anchor whose timings the oblivious
    # set already programs, so the gap collapses toward zero.
    assert (near["region_aware_advantage_intensive"]
            > uniform["region_aware_advantage_intensive"]
            > far["region_aware_advantage_intensive"] >= 0.0)
    assert near["region_aware_advantage_intensive"] > 0.005


@pytest.mark.parametrize("chunk_steps", [1, 17, 128])
def test_streamed_region_score_bitwise_vs_materialized(chunk_steps):
    score, table, tr, mix = _scored("hot_bank")
    out = replay_stream(table, tr, chunk_steps=chunk_steps, region_mix=mix)
    # Integer accumulators: the streamed counts — and therefore every
    # figure finalized from them — are EQUAL, not just close.
    rep = replay(table, tr)
    from repro.core.perfmodel import (
        region_counts_accumulate,
        region_counts_init,
    )
    want = region_counts_accumulate(
        region_counts_init(table.n_dimms, table.n_bins, table.n_regions),
        rep.bin_idx, jnp.asarray(mix))
    np.testing.assert_array_equal(np.asarray(out.region_counts),
                                  np.asarray(want))
    assert out.region_score() == score


def test_streamed_region_mix_stays_on_ref_on_tpu(monkeypatch):
    """The region-resolved scan has no kernel: on a TPU backend a stream
    with a region mix still runs the ref scan (here on the CPU, where a
    compiled TPU kernel could not run), and only an explicit
    ``impl="pallas"`` is refused."""
    score, table, tr, mix = _scored("hot_bank")
    want = replay_stream(table, tr, chunk_steps=32, region_mix=mix,
                         impl="ref")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    out = replay_stream(table, tr, chunk_steps=32, region_mix=mix)
    np.testing.assert_array_equal(np.asarray(out.region_counts),
                                  np.asarray(want.region_counts))
    for a, b in zip(out.partials, want.partials):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert out.region_score() == score
    with pytest.raises(ValueError, match="region_mix"):
        replay_stream(table, tr, chunk_steps=32, region_mix=mix,
                      impl="pallas")


def test_stream_without_mix_has_no_region_counts():
    table = region_table(n_regions=2)
    tr = traces.generate("diurnal", KEY, 4, 32)
    out = replay_stream(table, tr, chunk_steps=16)
    assert out.region_counts is None
    with pytest.raises(ValueError, match="region_mix"):
        out.region_score()


# ----------------------------------------------------------------- traces

@pytest.mark.parametrize("name", ["hot_bank", "design_skew"])
def test_region_scenarios_respect_drift_bound(name):
    tr = traces.generate(name, KEY, 12, 600)
    assert tr.shape == (600, 12)
    assert (traces.max_drift_rate(tr, traces.DEFAULT_DT_S)
            <= traces.PAPER_MAX_DRIFT_C_PER_S + 1e-6)


def test_scenario_region_profiles_are_registered():
    for name, profile in traces.SCENARIO_REGION_PROFILES.items():
        assert name in traces.SCENARIOS
        assert profile in traces.REGION_MIX_PROFILES


@pytest.mark.parametrize("profile", traces.REGION_MIX_PROFILES)
def test_region_access_mix_exact_integer_rows(profile):
    mix = traces.region_access_mix(
        jax.random.PRNGKey(3), 16, 5, 4, profile=profile,
        accesses_per_step=57,
    )
    assert mix.shape == (16, 5, 4) and mix.dtype == jnp.int32
    assert (np.asarray(mix) >= 0).all()
    # Largest-remainder allocation: every (step, DIMM) row sums EXACTLY.
    np.testing.assert_array_equal(np.asarray(mix).sum(axis=-1), 57)
    again = traces.region_access_mix(
        jax.random.PRNGKey(3), 16, 5, 4, profile=profile,
        accesses_per_step=57,
    )
    np.testing.assert_array_equal(np.asarray(mix), np.asarray(again))


def test_near_and_far_mixes_mirror_each_other():
    near = np.asarray(traces.region_access_mix(KEY, 1, 1, 5, profile="near"))
    far = np.asarray(traces.region_access_mix(KEY, 1, 1, 5, profile="far"))
    assert (np.diff(near[0, 0]) <= 0).all()      # mass toward region 0
    assert (np.diff(far[0, 0]) >= 0).all()       # mass toward the anchor
    np.testing.assert_array_equal(near[0, 0], far[0, 0][::-1])
