"""Fused replay-step kernel ≡ ref chunk scan, bit for bit.

The Pallas replay kernel (:mod:`repro.kernels.replay_step`) fuses
controller step + timing-table lookup + ScorePartials accumulation into
one VMEM-resident pass per DIMM tile. Its contract is UNCONDITIONAL
bit-exactness vs the ref scan — the kernel performs the same f32 adds in
the same per-step order, so parity does not even lean on the
cycle-quantization envelope:

* ``replay_stream(impl="pallas")`` reproduces the materialized
  ``replay`` + ``trace_score`` results exactly (state, switch counts,
  exact score-dict equality) at chunkings {1, ragged, n_steps}, with and
  without error injections — the same gate the ref streaming layer holds
  (tests/test_stream.py);
* under a mesh the kernel composes BELOW the shard_map (local per-shard
  tiles): same-mesh pallas partials/state/score ≡ same-mesh ref bitwise;
* ``controller.step(impl="pallas")`` and
  ``perfmodel.trace_score_accumulate(impl="pallas")`` match their refs
  elementwise, including controller-boundary temperatures (exact bin
  edges, guard-band and hysteresis-margin corners) where one misrounded
  comparison would flip a transition;
* the decision-EMITTING serving path stays on the ref and mixes freely
  with fused chunks (the carried partials are bit-identical);
* with no ``impl`` given, the chunk scan is chosen by platform: the
  kernel on TPU, the ref elsewhere.

Runs tier-1 on one device in interpret mode (the same kernel body that
compiles for TPU); the CI multidevice job re-runs this module on an
8-device host mesh where padding and psums are non-trivial.
"""

import functools

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import controller, fleet, perfmodel, shard, stream, traces
from repro.kernels.replay_step import ops as replay_ops
from repro.kernels.replay_step import ref as replay_ref
from repro.kernels.replay_step.kernel import CHUNK_STEP_BLOCK
from repro.launch.serve_fleet import FleetControllerService

TEMPS = (45.0, 55.0, 85.0)
N_MAX = 11
N_STEPS = 72

#: Fleet sizes: degenerate (1024-lane padding dominates), below CI device
#: counts, the boundary, a prime.
SIZES = (1, 3, 5, 8, 11)


# Module-level lazy singletons (not pytest fixtures: the hypothesis
# fallback's @given produces a zero-arg wrapper, so property tests cannot
# take fixture arguments).
@functools.lru_cache(maxsize=None)
def _mesh():
    return shard.fleet_mesh()


@functools.lru_cache(maxsize=None)
def _table_full():
    fl = fleet.synthesize(jax.random.PRNGKey(0), N_MAX)
    return fleet.sweep(fl, TEMPS, (1.0,)).to_table()


def _sub_table(n):
    t = _table_full()
    return controller.DimmTimingTable(temp_bins=t.temp_bins, stack=t.stack[:n])


@functools.lru_cache(maxsize=None)
def _trace(n, error_rate):
    k_t, k_e = jax.random.split(jax.random.PRNGKey(29 * n + int(error_rate * 1e3)))
    trace = np.asarray(traces.generate("diurnal", k_t, n, N_STEPS))
    errors = np.asarray(traces.error_injections(k_e, N_STEPS, n, error_rate))
    return trace, errors


@functools.lru_cache(maxsize=None)
def _materialized(n, error_rate):
    trace, errors = _trace(n, error_rate)
    res = controller.replay(_sub_table(n), trace, errors)
    return res, perfmodel.trace_score(_sub_table(n).stack, res)


def _assert_state_equal(a, b):
    for name, la, lb in zip(("bin_idx", "cool_streak", "fused"), a, b):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=f"state.{name}"
        )


def _assert_partials_equal(a, b):
    for name, la, lb in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=f"partials.{name}"
        )


# ---------------------------------------------------------------------------
# Streamed replay through the fused kernel vs the materialized truth
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(st.sampled_from(SIZES), st.sampled_from([1, 17, N_STEPS]),
       st.sampled_from([0.0, 0.02]))
def test_pallas_stream_bit_exact_vs_materialized(n, chunk, error_rate):
    """impl="pallas" at chunk sizes {1, ragged (17 ∤ 72), n_steps} ×
    error rates {0, 0.02}: exact state/switch/score equality."""
    table = _sub_table(n)
    trace, errors = _trace(n, error_rate)
    ref, score_ref = _materialized(n, error_rate)
    res = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                               impl="pallas")
    _assert_state_equal(res.state, ref.state)
    np.testing.assert_array_equal(
        np.asarray(res.partials.switches), np.asarray(ref.switch_counts)
    )
    assert res.total_switches == ref.total_switches
    assert res.n_steps == N_STEPS
    assert res.score() == score_ref  # bitwise: every key, exact equality


@settings(max_examples=6, deadline=None)
@given(st.sampled_from(SIZES), st.sampled_from([0.0, 0.02]))
def test_pallas_stream_partials_bitwise_vs_ref(n, error_rate):
    """The fused kernel's raw partials — occupancy, switches, f32 timing
    sums — equal the ref chunk scan's leaf for leaf (the unconditional
    accumulation-order contract, stronger than score equality)."""
    table = _sub_table(n)
    trace, errors = _trace(n, error_rate)
    r = stream.replay_stream(table, trace, errors, chunk_steps=17)
    p = stream.replay_stream(table, trace, errors, chunk_steps=17,
                             impl="pallas")
    _assert_state_equal(p.state, r.state)
    _assert_partials_equal(p.partials, r.partials)


@pytest.mark.parametrize("chunk", [2 * CHUNK_STEP_BLOCK, 600, 1440])
def test_pallas_chunk_longer_than_a_step_block(chunk):
    """A chunk longer than one :data:`CHUNK_STEP_BLOCK` streams through
    the kernel block by block (whole blocks, then a shorter remainder for
    600 and 1440, a day at minute cadence): state and partials still equal
    the ref chunk scan's bit for bit, and the materialized replay's."""
    n = N_MAX
    table = _sub_table(n)
    k_t, k_e = jax.random.split(jax.random.PRNGKey(chunk))
    trace = np.asarray(traces.generate("diurnal", k_t, n, chunk))
    errors = np.asarray(traces.error_injections(k_e, chunk, n, 0.002))
    r = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                             impl="ref")
    p = stream.replay_stream(table, trace, errors, chunk_steps=chunk,
                             impl="pallas")
    _assert_state_equal(p.state, r.state)
    _assert_partials_equal(p.partials, r.partials)
    _assert_state_equal(p.state, controller.replay(table, trace, errors).state)
    assert p.n_steps == chunk


# ---------------------------------------------------------------------------
# Mesh composition: kernel local per shard, bitwise same-mesh parity
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(st.sampled_from(SIZES), st.sampled_from([0.0, 0.02]))
def test_pallas_sharded_bitwise(n, error_rate):
    """Same-mesh pallas stream ≡ same-mesh ref stream in partials, state
    AND finalized score (bitwise); state also bit-exact vs unsharded
    materialized replay."""
    table = _sub_table(n)
    trace, errors = _trace(n, error_rate)
    ref, _ = _materialized(n, error_rate)
    r = stream.replay_stream(table, trace, errors, chunk_steps=17,
                             mesh=_mesh())
    p = stream.replay_stream(table, trace, errors, chunk_steps=17,
                             mesh=_mesh(), impl="pallas")
    _assert_state_equal(p.state, ref.state)
    _assert_partials_equal(p.partials, r.partials)
    assert p.score() == r.score()


# ---------------------------------------------------------------------------
# One fused observation: controller.step(impl="pallas")
# ---------------------------------------------------------------------------
def test_step_pallas_parity_over_sequence():
    """step(impl="pallas") tracks the ref step for a whole stateful
    sequence — rows, switch flags, effective bins and carried state all
    elementwise equal (the chunk-1 kernel launch contract)."""
    n = 7
    table = _sub_table(min(n, N_MAX))
    trace, errors = _trace(min(n, N_MAX), 0.02)
    stack = controller.jnp.asarray(table.stack)
    edges = controller.jnp.asarray(table.temp_bins, controller.jnp.float32)
    params = controller.ControllerParams()
    st_r = st_p = controller.init_state(table.n_dimms, table.n_bins)
    for s in range(0, N_STEPS, 9):
        st_r, rows_r, sw_r, eff_r = controller.step(
            stack, edges, params, st_r, trace[s], errors[s]
        )
        st_p, rows_p, sw_p, eff_p = controller.step(
            stack, edges, params, st_p, trace[s], errors[s], impl="pallas"
        )
        np.testing.assert_array_equal(np.asarray(rows_p), np.asarray(rows_r))
        np.testing.assert_array_equal(np.asarray(sw_p), np.asarray(sw_r))
        np.testing.assert_array_equal(np.asarray(eff_p), np.asarray(eff_r))
        _assert_state_equal(st_p, st_r)


def test_boundary_temperatures_parity():
    """Controller-boundary corners: temperatures landing EXACTLY on a bin
    edge, on edge − guard band (searchsorted equality case) and on
    edge − guard − hysteresis margin (the calm boundary) must transition
    identically — one misrounded kernel comparison flips these."""
    table = _sub_table(4)
    params = controller.ControllerParams()
    corners = []
    for e in table.temp_bins:
        corners += [
            e, e - params.guard_band_c,
            e - params.guard_band_c - params.hysteresis_c,
            np.nextafter(np.float32(e - params.guard_band_c),
                         np.float32(-np.inf)),
        ]
    # Each step feeds one corner value to every DIMM; repeat the cooling
    # ladder enough times to trip hysteresis recoveries.
    trace = np.tile(
        np.asarray(sorted(corners, reverse=True), np.float32)[:, None],
        (3, table.n_dimms),
    )
    errors = np.zeros(trace.shape, bool)
    ref = controller.replay(table, trace, errors)
    res = stream.replay_stream(table, trace, errors, chunk_steps=5,
                               impl="pallas")
    _assert_state_equal(res.state, ref.state)
    np.testing.assert_array_equal(
        np.asarray(res.partials.switches), np.asarray(ref.switch_counts)
    )
    assert res.score() == perfmodel.trace_score(table.stack, ref)


# ---------------------------------------------------------------------------
# Fused partials accumulation: perfmodel.trace_score_accumulate
# ---------------------------------------------------------------------------
def test_accumulate_pallas_parity():
    """trace_score_accumulate(impl="pallas") over a materialized decision
    block — whole-trace, chained ragged chunks, and the legacy merged
    rank-3 timing layout — matches the ref leaf for leaf."""
    n = 5
    ref, _ = _materialized(n, 0.02)
    init = perfmodel.trace_score_init(n, _sub_table(n).n_bins)
    r = perfmodel.trace_score_accumulate(
        init, ref.timings, ref.bin_idx, ref.switched
    )
    p = perfmodel.trace_score_accumulate(
        init, ref.timings, ref.bin_idx, ref.switched, impl="pallas"
    )
    _assert_partials_equal(p, r)
    # Chained ragged chunks through the kernel reproduce the one-shot.
    acc = init
    for s in range(0, N_STEPS, 31):
        acc = perfmodel.trace_score_accumulate(
            acc, ref.timings[s:s + 31], ref.bin_idx[s:s + 31],
            ref.switched[s:s + 31], impl="pallas",
        )
    _assert_partials_equal(acc, r)
    # Legacy merged (chunk, N, 4) rows are duplicated in both impls.
    merged = np.asarray(ref.timings)[:, :, 0, :]
    rm = perfmodel.trace_score_accumulate(init, merged, ref.bin_idx, ref.switched)
    pm = perfmodel.trace_score_accumulate(init, merged, ref.bin_idx,
                                          ref.switched, impl="pallas")
    _assert_partials_equal(pm, rm)


# ---------------------------------------------------------------------------
# Serving engine: fused chunks mix with decision-emitting ref chunks
# ---------------------------------------------------------------------------
def test_streaming_controller_pallas_mixed_emit():
    """A pallas StreamingController whose middle chunk requests decisions
    (served by the ref scan) still lands bit-exact — the partials carried
    across the impl switch are identical."""
    n = 5
    table = _sub_table(n)
    trace, errors = _trace(n, 0.02)
    ref, score_ref = _materialized(n, 0.02)
    eng = stream.StreamingController(table, impl="pallas")
    for i, (t, e) in enumerate(stream.iter_chunks(trace, errors, 25)):
        out = eng.ingest(t, e, return_decisions=(i == 1))
        if i == 1:
            rows, bins, switched = out
            np.testing.assert_array_equal(
                np.asarray(rows), np.asarray(ref.timings)[25:50]
            )
    assert eng.score() == score_ref
    _assert_state_equal(eng.state, ref.state)
    assert eng.total_switches == ref.total_switches


# ---------------------------------------------------------------------------
# The chunk scan is chosen by platform
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "backend, impl, emit, sharded, want",
    [
        ("tpu", None, False, False, "kernel"),
        ("cpu", None, False, False, "ref"),
        ("gpu", None, False, False, "ref"),
        ("tpu", None, True, False, "ref"),
        ("tpu", "ref", False, False, "ref"),
        ("cpu", "pallas", False, False, "kernel"),
        ("cpu", "pallas", True, False, "ref"),
        ("tpu", None, False, True, "kernel"),
        ("tpu", None, True, True, "ref"),
        ("cpu", None, False, True, "ref"),
    ],
)
def test_chunk_runner_chooses_by_platform(monkeypatch, backend, impl, emit,
                                          sharded, want):
    """``impl=None`` resolves from ``jax.default_backend()``: the fused
    kernel, compiled (not interpreted), on TPU; the ref scan elsewhere.
    The decision-emitting scan stays on the ref on every platform, an
    explicit ``impl`` overrides the platform, and under a mesh the same
    choice is made for each shard."""
    table = _table_full()
    params = controller.ControllerParams()
    mesh = _mesh() if sharded else None
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    got = stream._chunk_runner(mesh, table.n_dimms, table.temp_bins, params,
                               emit=emit, impl=impl)
    if want == "kernel":
        key = (tuple(table.temp_bins), replay_ops.canonical_params(params),
               backend != "tpu")
        fn = replay_ops.pallas_chunk_scan(*key)
    else:
        key = None
        fn = replay_ref.chunk_scan_emit if emit else replay_ref.chunk_scan
    if sharded:
        impl_used = "pallas" if want == "kernel" else "ref"
        fn = stream._sharded_chunk_runner(mesh, table.n_dimms, emit,
                                          impl_used, key)
    assert got is fn


@pytest.mark.parametrize(
    "backend, impl, want",
    [("tpu", None, "pallas"), ("cpu", None, "ref"), ("tpu", "ref", "ref"),
     ("cpu", "pallas", "pallas")],
)
def test_engine_reports_chosen_impl(monkeypatch, backend, impl, want):
    """The streaming engine, and the service over it, name the chunk scan
    they run, never ``None``."""
    table = _sub_table(3)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert stream.StreamingController(table, impl=impl).impl == want
    assert FleetControllerService(table, impl=impl).engine.impl == want


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------
def test_impl_validation():
    table = _sub_table(3)
    trace, _ = _trace(3, 0.0)
    with pytest.raises(ValueError, match="impl"):
        stream.replay_stream(table, trace, impl="fast")
    with pytest.raises(ValueError, match="impl"):
        stream.StreamingController(table, impl="fast")
    with pytest.raises(ValueError, match="impl"):
        controller.step(table.stack, np.asarray(table.temp_bins),
                        controller.ControllerParams(),
                        controller.init_state(3, table.n_bins),
                        trace[0], impl="fast")
    with pytest.raises(ValueError, match="impl"):
        perfmodel.trace_score_accumulate(
            perfmodel.trace_score_init(3, table.n_bins),
            np.zeros((1, 3, 2, 4), np.float32),
            np.zeros((1, 3), np.int32), np.zeros((1, 3), bool), impl="fast",
        )
    # replay's dense history is what the kernel avoids — pointed error.
    with pytest.raises(ValueError, match="replay_stream"):
        controller.replay(table, trace, impl="pallas")
    assert replay_ops.IMPLS == ("ref", "pallas")


def test_scalars_roundtrip_exact():
    """The kernel's static policy scalars round-trip f64→f32 exactly —
    the precondition for in-kernel f32 arithmetic matching the ref's
    traced scalars bit for bit."""
    scal = replay_ops.replay_scalars(
        _sub_table(3).temp_bins, controller.ControllerParams()
    )
    for e, orig in zip(scal.edges, _sub_table(3).temp_bins):
        assert np.float32(e) == np.float32(orig)
        assert float(np.float32(e)) == e
    assert len(scal.jedec) == 8
    np.testing.assert_array_equal(
        np.asarray(scal.jedec, np.float32).reshape(2, 4),
        controller._JEDEC_ROWS,
    )
