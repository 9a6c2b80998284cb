"""Streaming (chunked-scan) controller replay — constant device memory.

:func:`repro.core.controller.replay` materializes the full
``(n_steps, n_dimms, 2, 4)`` timing history, which is the right shape for
property tests and day-scale benchmarks but collapses at the ROADMAP's
serving north star: 10⁶ DIMMs × a day of minute-cadence telemetry is a
~46 GB history per replica even before the mesh only shards the DIMM
axis. AL-DRAM's controller is a *runtime* service over an unbounded
observation stream (paper §5; Chang et al. frame latency adaptation the
same way), so this module is the streaming embodiment of the exact same
state machine:

* :func:`replay_stream` — an outer Python loop over step-axis chunks,
  each chunk one jitted ``lax.scan`` whose carry is ONLY the
  :class:`~repro.core.controller.ControllerState` pytree plus the running
  :class:`~repro.core.perfmodel.ScorePartials` (occupancy per
  (DIMM, bin), switch counts, realized-timing sums). No step-indexed
  array is ever materialized: peak device memory is
  O(n_dimms · chunk_steps) — the telemetry chunk in flight — independent
  of trace length.
* **Bit-exact by construction**: realized timings are cycle-quantized
  (multiples of tCK = 1.25 ns), so the float32 partial sums are exact
  under ANY chunking (see :class:`~repro.core.perfmodel.ScorePartials`),
  and :func:`~repro.core.perfmodel.trace_score_finalize` is the same
  finalize the materialized scorer runs — streamed final state, switch
  totals and score dict equal materialized ``replay`` + ``trace_score``
  bitwise (property-tested in tests/test_stream.py).
* **Double-buffered ingestion**: jax dispatch is asynchronous, so each
  iteration first dispatches the current chunk's scan, then stages the
  NEXT chunk's host→device transfer (``jax.device_put``, with a
  ``NamedSharding`` over the ``"dimm"`` axis when a mesh is given) while
  the device is still scanning.
* **Mesh composition**: ``mesh=`` runs every chunk scan under the same
  (pad → ``shard_map`` → slice) machinery as the materialized sharded
  replay (:mod:`repro.core.shard`); state and partials stay partitioned
  over the DIMM axis between chunks, and the finalized score can stay
  gather-free via ``trace_score_finalize(mesh=...)``.
* :class:`StreamingController` — the stateful engine behind the fleet
  service (:mod:`repro.launch.serve_fleet`): ``ingest`` batched
  observation chunks (optionally returning the realized timings / bin
  decisions for programming hardware), ``score`` the stream so far.
* **Implementation chosen by platform**: on a TPU backend every
  non-emitting chunk runs the fused replay-step kernel
  (:mod:`repro.kernels.replay_step`) — step + timing lookup + partials
  accumulation in one VMEM-resident pass per DIMM tile, bit-exact vs the
  ref scan (same adds, same order); elsewhere, where the kernel would
  only run in the Pallas interpreter, the ref ``lax.scan``. An explicit
  ``impl="ref"|"pallas"`` overrides the choice. The chunk-scan
  *semantics* live in :mod:`repro.kernels.replay_step.ref`; this module
  aliases them.

Chunk-size guidance: every distinct chunk length compiles its own scan,
so feed uniform chunks (one trailing ragged chunk costs exactly one extra
compile). Larger chunks amortize dispatch overhead; smaller chunks bound
the in-flight telemetry buffer — :data:`DEFAULT_CHUNK_STEPS` (256) keeps
a 10⁶-DIMM chunk at ~1 GB while leaving dispatch overhead negligible.
"""

from __future__ import annotations

import functools
from typing import Iterable, Iterator, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import shard
from repro.core.controller import (
    ControllerParams,
    ControllerState,
    DimmTimingTable,
    init_state,
)
from repro.core.perfmodel import (
    MULTI_CORE,
    PAPER_CLAIM_SPEEDUP,
    WORKLOADS,
    ScorePartials,
    region_counts_init,
    region_score_finalize,
    trace_score_finalize,
    trace_score_init,
)
from repro.kernels.replay_step import ops as replay_ops
from repro.kernels.replay_step import ref as _replay_ref

#: Default step-axis chunk length. 256 minute-cadence observations ≈ 4 h
#: of telemetry per dispatch; a 10⁶-DIMM float32 chunk is ~1 GB.
DEFAULT_CHUNK_STEPS: int = 256


# ---------------------------------------------------------------------------
# The jitted chunk scans (carry = state + partials, never a history)
# ---------------------------------------------------------------------------
# The chunk-scan semantics moved to kernels/replay_step/ref.py when the
# fused Pallas path landed (the kernel convention keeps ref + kernel side
# by side); these aliases keep the SAME module-level jitted function
# objects every streamed caller compiled against — program identity is
# what the bitwise same-mesh parity gates rely on.
_chunk_body = _replay_ref.chunk_body
_chunk_scan = _replay_ref.chunk_scan
_chunk_scan_emit = _replay_ref.chunk_scan_emit
_region_chunk_scan = _replay_ref.region_chunk_scan


@functools.lru_cache(maxsize=16)
def _sharded_region_runner(mesh, n_dimms: int):
    """Cached sharded wrapper for the region-resolved chunk scan: the
    int32 region-count carry shards over the DIMM axis like every other
    per-DIMM accumulator, and integer adds make the sharded counts
    bitwise-equal to single-device ones (padding lanes are sliced off)."""
    in_axes = (0, None, None, 0, 0, 0, 0, None, 0, 1, 1, 1)
    out_axes = (0, 0, 0, 0, None, 0)
    return shard.sharded_dimm_map(
        _region_chunk_scan, mesh, in_axes, out_axes, n_dimms
    )


@functools.lru_cache(maxsize=32)
def _sharded_chunk_runner(mesh, n_dimms: int, emit: bool, impl: str = "ref",
                          key=None):
    """Cached (pad → shard_map → slice) wrapper around the chunk scan:
    state and partials re-enter every chunk along the DIMM axis, so the
    same runner carries them across the whole stream without gathers
    (padding lanes accumulate edge-replica partials that the final slice
    discards). ``impl="pallas"`` composes the fused kernel BELOW the
    mesh — each shard tiles and scans its own DIMM block locally, exactly
    like the charge-sweep kernel — with ``key = (temp_bins, params,
    interpret)`` identifying the kernel's static policy."""
    if impl == "pallas":
        fn = replay_ops.pallas_chunk_scan(*key)
    else:
        fn = _chunk_scan_emit if emit else _chunk_scan
    in_axes = (0, None, None, 0, 0, 0, 0, None, 1, 1)
    out_axes = (0, 0, 0, 0, None) + ((1, 1, 1) if emit else ())
    return shard.sharded_dimm_map(fn, mesh, in_axes, out_axes, n_dimms)


def resolve_impl(impl: Optional[str]) -> str:
    """The chunk-scan implementation ``impl`` names, or for ``None`` the
    platform's: the fused kernel on a TPU backend, the ref scan
    elsewhere (off-TPU the kernel only runs in the Pallas interpreter)."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in replay_ops.IMPLS:
        raise ValueError(
            f"impl must be one of {replay_ops.IMPLS}, got {impl!r}"
        )
    return impl


def _chunk_runner(mesh, n_dimms: int, temp_bins, params: ControllerParams,
                  emit: bool = False, impl: Optional[str] = None,
                  interpret: Optional[bool] = None):
    """THE dispatch point for every chunk-scan call site (replay_stream
    and StreamingController.ingest both route here).

    ``impl="pallas"`` selects the fused replay-step kernel
    (:mod:`repro.kernels.replay_step`) — bit-exact vs the ref by the
    kernel's accumulation-order contract; ``impl=None`` chooses by
    platform (:func:`resolve_impl`). The decision-EMITTING path stays
    on the ref: materializing the per-step rows is precisely what the
    kernel exists to avoid, and the partials it carries are bit-identical
    either way."""
    impl = resolve_impl(impl)
    if emit or impl == "ref":
        fn, key = (_chunk_scan_emit if emit else _chunk_scan), None
        impl = "ref"
    else:
        key = (
            tuple(float(e) for e in temp_bins),
            replay_ops.canonical_params(params),
            replay_ops.default_interpret() if interpret is None else bool(interpret),
        )
        fn = replay_ops.pallas_chunk_scan(*key)
    if mesh is None:
        return fn
    return _sharded_chunk_runner(mesh, n_dimms, emit, impl, key)


# ---------------------------------------------------------------------------
# Chunk sources + double-buffered ingestion
# ---------------------------------------------------------------------------
def iter_chunks(
    traces: Array,
    errors: Optional[Array] = None,
    chunk_steps: int = DEFAULT_CHUNK_STEPS,
) -> Iterator[Tuple[np.ndarray, Optional[np.ndarray]]]:
    """Slice a materialized ``(n_steps, n_dimms)`` trace into
    ``(temps_chunk, errors_chunk)`` pairs (the last chunk may be ragged).
    The streaming entry points accept any iterable yielding such pairs —
    this is just the adapter for traces that DO fit in host memory."""
    if chunk_steps < 1:
        raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
    n_steps = traces.shape[0]
    for s in range(0, n_steps, chunk_steps):
        e = None if errors is None else errors[s : s + chunk_steps]
        yield traces[s : s + chunk_steps], e


class _Ingestor:
    """Double-buffered host→device chunk feeder.

    ``stage`` transfers a chunk toward the device(s) and returns device
    handles WITHOUT blocking; the driver loop stages chunk k+1 right
    after dispatching chunk k's scan, overlapping the H2D copy with
    compute (jax dispatch is asynchronous). With a mesh, chunks are
    edge-replication-padded on host and placed with a
    ``NamedSharding(mesh, P(None, "dimm"))`` so each device receives only
    its DIMM block."""

    def __init__(self, n_dimms: int, mesh=None):
        self.n_dimms = n_dimms
        self.errors_seen = 0
        self._sharding = None
        self._mix_sharding = None
        self._padded = n_dimms
        if mesh is not None:
            self._padded = shard.padded_size(n_dimms, shard.n_shards(mesh))
            self._sharding = NamedSharding(mesh, P(None, shard.DIMM_AXIS))
            self._mix_sharding = NamedSharding(
                mesh, P(None, shard.DIMM_AXIS, None)
            )

    def _pad(self, a: np.ndarray) -> np.ndarray:
        pad = self._padded - a.shape[1]
        if pad == 0:
            return a
        return np.concatenate([a, np.repeat(a[:, -1:], pad, axis=1)], axis=1)

    def stage_mix(self, mix) -> Array:
        """Stage a ``(chunk_steps, n_dimms, n_regions)`` region-access-mix
        chunk (edge-replication-padded over the DIMM axis like the
        temperature chunk; padding lanes' counts are sliced off with the
        rest of the padded carry)."""
        mix = np.asarray(mix, np.int32)
        if mix.ndim != 3 or mix.shape[1] != self.n_dimms:
            raise ValueError(
                f"region mix chunk must be (chunk_steps, {self.n_dimms}, "
                f"n_regions), got {mix.shape}"
            )
        mix = self._pad(mix)
        if self._mix_sharding is None:
            return jax.device_put(mix)
        return jax.device_put(mix, self._mix_sharding)

    def stage(self, temps, errors) -> Tuple[Array, Array]:
        temps = np.asarray(temps, np.float32)
        if temps.ndim != 2 or temps.shape[1] != self.n_dimms:
            raise ValueError(
                f"chunk must be (chunk_steps, {self.n_dimms}), got {temps.shape}"
            )
        if errors is None:
            errors = np.zeros(temps.shape, bool)
        else:
            errors = np.asarray(errors, bool)
            if errors.shape != temps.shape:
                raise ValueError(
                    f"errors chunk shape {errors.shape} != temps {temps.shape}"
                )
            self.errors_seen += int(errors.sum())
        temps, errors = self._pad(temps), self._pad(errors)
        if self._sharding is None:
            return jax.device_put(temps), jax.device_put(errors)
        return (
            jax.device_put(temps, self._sharding),
            jax.device_put(errors, self._sharding),
        )


# ---------------------------------------------------------------------------
# The streamed replay
# ---------------------------------------------------------------------------
class StreamResult(NamedTuple):
    """Outcome of a streamed replay: the final controller registers and the
    accumulated score partials — everything a materialized
    :class:`~repro.core.controller.ReplayResult` + ``trace_score`` pair
    provides except the per-step history (which streaming exists to avoid).
    """

    state: ControllerState
    partials: ScorePartials
    table: DimmTimingTable
    n_chunks: int
    errors_total: int
    mesh: object = None
    #: (n_dimms, n_bins + 1, n_regions) int32 region-access counts, only
    #: when the stream carried a region mix (``replay_stream(region_mix=)``).
    region_counts: Optional[Array] = None

    @property
    def n_steps(self) -> int:
        return int(self.partials.n_steps)

    @property
    def switch_counts(self) -> Array:
        """(N,) per-DIMM timing-set switches over the stream."""
        return self.partials.switches

    @property
    def total_switches(self) -> int:
        return int(np.asarray(self.partials.switches, np.int64).sum())

    def score(
        self,
        cfg=MULTI_CORE,
        claim: float = PAPER_CLAIM_SPEEDUP,
        workloads=WORKLOADS,
        mesh=None,
    ):
        """Finalize the running partials into the :func:`trace_score` dict
        — bit-identical to scoring the materialized replay. ``mesh``
        defaults to the stream's own mesh (pass ``mesh=None`` explicitly
        via :func:`~repro.core.perfmodel.trace_score_finalize` to force a
        single-device finalize). A table carrying a refresh policy scores
        the combined latency+refresh figures too — the partials are
        refresh-agnostic (occupancy is a function of the selected bin),
        so refresh enters at this finalize only."""
        return trace_score_finalize(
            self.partials, self.table.oblivious_stack(), cfg, claim,
            workloads, mesh=self.mesh if mesh is None else mesh,
            refresh=self.table.bin_refresh(),
        )

    def region_score(
        self,
        cfg=MULTI_CORE,
        claim: float = PAPER_CLAIM_SPEEDUP,
        workloads=WORKLOADS,
    ):
        """Region-occupancy-weighted realized speedups from the streamed
        region-access counts + the table's rank-5 registers — bitwise
        equal to the materialized
        :func:`~repro.core.perfmodel.region_trace_score` at every
        chunking (the counts are integers; see
        :func:`~repro.core.perfmodel.region_counts_accumulate`)."""
        if self.region_counts is None:
            raise ValueError(
                "this stream carried no region mix; pass region_mix= to "
                "replay_stream"
            )
        return region_score_finalize(
            self.region_counts, self.table.region_stack(), cfg, claim,
            workloads,
        )


def replay_stream(
    table: DimmTimingTable,
    traces: Union[Array, Iterable[Tuple[Array, Optional[Array]]]],
    errors: Optional[Array] = None,
    params: ControllerParams = ControllerParams(),
    state: Optional[ControllerState] = None,
    chunk_steps: int = DEFAULT_CHUNK_STEPS,
    mesh=None,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
    region_mix: Optional[Array] = None,
) -> StreamResult:
    """Replay a temperature stream in step-axis chunks, carrying only the
    controller state and the running score partials — O(n_dimms ·
    chunk_steps) peak device memory, independent of stream length.

    ``traces`` is either a materialized ``(n_steps, n_dimms)`` array
    (chunked internally via :func:`iter_chunks`; ``errors`` may then be a
    matching array) or any iterable yielding ``(temps_chunk,
    errors_chunk-or-None)`` pairs — e.g. a generator reading telemetry
    shards off disk — in which case ``errors`` must be ``None``. Chunks
    may be ragged; each distinct chunk length compiles once.

    Bit-exact vs materialized :func:`~repro.core.controller.replay`: the
    final :class:`ControllerState`, per-DIMM switch counts and the
    finalized score dict are identical bitwise for every chunking,
    because the transition kernel is the same jitted :func:`step` and the
    partials' sums are exact under reordering (cycle-quantized values —
    see :class:`~repro.core.perfmodel.ScorePartials`).

    ``mesh`` — optional 1-D ``"dimm"`` mesh: every chunk scan runs
    sharded, state/partials stay partitioned between chunks, and incoming
    chunks are device_put pre-sharded (double-buffered against the
    in-flight scan).

    ``impl`` — chosen by platform when ``None``: the fused replay-step
    kernel (:mod:`repro.kernels.replay_step`: step + timing lookup +
    partials in one VMEM-resident pass, bit-exact vs the ref) on TPU,
    the ref (jitted scan of separate XLA ops) elsewhere. ``"ref"`` or
    ``"pallas"`` forces one. ``interpret=None`` auto-enables kernel
    interpret mode off-TPU. Under a mesh the kernel runs locally per
    shard.

    ``region_mix`` — optional ``(n_steps, n_dimms, n_regions)`` int32
    per-step region-access counts (region tables, schema v5): each chunk
    then runs the region-resolved scan
    (:func:`repro.kernels.replay_step.ref.region_chunk_scan`), carrying
    int32 per-(DIMM, effective bin, region) counters alongside the
    partials — ``StreamResult.region_counts`` /
    :meth:`StreamResult.region_score`. Integer accumulation keeps
    streamed counts bitwise-equal to a materialized accumulation at
    every chunking and same-mesh sharding. Requires a materialized
    ``traces`` array and stays on the ref scan on every platform (the
    precedent of the decision-emitting path); the carried
    :class:`ScorePartials` are bit-identical to a mix-free stream of the
    same trace."""
    if state is None:
        state = init_state(table.n_dimms, table.n_bins)
    region_counts = None
    if region_mix is not None:
        if impl not in (None, "ref"):
            raise ValueError(
                "region_mix streaming runs the ref chunk scan; drop "
                f"impl={impl!r}"
            )
        if not (hasattr(traces, "ndim") or hasattr(traces, "shape")):
            raise ValueError(
                "region_mix requires a materialized (n_steps, n_dimms) "
                "traces array (chunked in lockstep with the mix)"
            )
        region_mix = np.asarray(region_mix, np.int32)
        if region_mix.ndim != 3 or region_mix.shape[2] != table.n_regions:
            raise ValueError(
                f"region_mix must be (n_steps, n_dimms, "
                f"{table.n_regions}), got {region_mix.shape}"
            )
        region_counts = region_counts_init(
            table.n_dimms, table.n_bins, table.n_regions
        )
    if hasattr(traces, "ndim") or hasattr(traces, "shape"):
        traces = np.asarray(traces)
        if traces.ndim != 2:
            raise ValueError(
                f"traces must be (n_steps, n_dimms), got {traces.shape}"
            )
        if traces.shape[1] != table.n_dimms:
            raise ValueError(
                f"trace has {traces.shape[1]} DIMMs, table has {table.n_dimms}"
            )
        if errors is not None and np.asarray(errors).shape != traces.shape:
            raise ValueError(
                f"errors shape {np.asarray(errors).shape} != traces shape "
                f"{traces.shape}"
            )
        chunks = iter_chunks(traces, errors, chunk_steps)
    else:
        if errors is not None:
            raise ValueError(
                "pass per-chunk errors through the chunk iterable, not the "
                "errors= argument"
            )
        chunks = iter(traces)

    n = table.n_dimms
    mix_chunks = None
    if region_counts is not None:
        if region_mix.shape[:2] != traces.shape:
            raise ValueError(
                f"region_mix leading shape {region_mix.shape[:2]} != "
                f"traces shape {traces.shape}"
            )
        mix_chunks = (
            region_mix[s : s + chunk_steps]
            for s in range(0, traces.shape[0], chunk_steps)
        )
    partials = trace_score_init(n, table.n_bins)
    # Explicit staging: these host tables cross to the device exactly once
    # per stream, and device_put keeps that legal under
    # jax.transfer_guard("disallow") scopes (implicit jnp.asarray
    # transfers are what the guard exists to catch). Region tables stream
    # on their region-OBLIVIOUS registers (bin dynamics depend only on
    # temperature); for rank-4 tables oblivious_stack() IS table.stack.
    stack = jax.device_put(np.asarray(table.oblivious_stack()))
    edges = jax.device_put(np.asarray(table.temp_bins, np.float32))
    jparams = ControllerParams(*(jax.device_put(p) for p in params))
    if mix_chunks is None:
        run = _chunk_runner(mesh, n, table.temp_bins, params,
                            emit=False, impl=impl, interpret=interpret)
    else:
        run = (
            _region_chunk_scan if mesh is None
            else _sharded_region_runner(mesh, n)
        )

    ingest = _Ingestor(n, mesh)
    n_chunks = 0

    def stage_next():
        nxt = next(chunks, None)
        if nxt is None:
            return None
        staged = ingest.stage(*nxt)
        if mix_chunks is not None:
            staged += (ingest.stage_mix(next(mix_chunks)),)
        return staged

    staged = stage_next()
    while staged is not None:
        # Dispatch the scan (asynchronous), THEN stage the next chunk's
        # host→device transfer so the copy overlaps the running scan.
        if mix_chunks is None:
            temps_d, errors_d = staged
            out = run(stack, edges, jparams, state,
                      partials.occupancy, partials.switches,
                      partials.timing_sums, partials.n_steps,
                      temps_d, errors_d)
        else:
            temps_d, errors_d, mix_d = staged
            out = run(stack, edges, jparams, state,
                      partials.occupancy, partials.switches,
                      partials.timing_sums, partials.n_steps,
                      region_counts, temps_d, errors_d, mix_d)
            region_counts = out[5]
        state = out[0]
        partials = ScorePartials(*out[1:5])
        n_chunks += 1
        staged = stage_next()
    return StreamResult(
        state=state, partials=partials, table=table, n_chunks=n_chunks,
        errors_total=ingest.errors_seen, mesh=mesh,
        region_counts=region_counts,
    )


# ---------------------------------------------------------------------------
# The serving engine (launch/serve_fleet.py wraps this)
# ---------------------------------------------------------------------------
class StreamingController:
    """Stateful fleet-controller engine over an observation stream.

    The serving-shaped face of :func:`replay_stream`: hold one of these
    per fleet, feed it batched observation chunks as they arrive
    (:meth:`ingest`), and read the running score at any point
    (:meth:`score`). Decisions can be returned per chunk for programming
    hardware (``return_decisions=True``); either way the engine itself
    retains only the O(n_dimms) state + partials. State/counter
    absorption is identical to
    :meth:`~repro.core.controller.ALDRAMController.replay` — the two
    wrappers are interchangeable step for step.

    The chunk-scan implementation is chosen by platform (``impl=None``):
    on TPU every non-decision-emitting chunk runs the fused replay-step
    kernel (bit-exact vs ``"ref"``), elsewhere the ref scan;
    ``impl="ref"|"pallas"`` forces one, and :attr:`impl` reports the
    one chosen. ``return_decisions=True`` chunks always take the ref
    scan, which is safe to mix freely — the carried partials are
    bit-identical."""

    def __init__(
        self,
        table: DimmTimingTable,
        params: ControllerParams = ControllerParams(),
        state: Optional[ControllerState] = None,
        mesh=None,
        impl: Optional[str] = None,
        interpret: Optional[bool] = None,
    ):
        self.table = table
        self.params = params
        self.mesh = mesh
        self.impl = resolve_impl(impl)
        self.interpret = interpret
        self._stack = jnp.asarray(table.oblivious_stack())
        self._edges = jnp.asarray(table.temp_bins, jnp.float32)
        self._jparams = ControllerParams(*(jnp.asarray(p) for p in params))
        self._state = (
            init_state(table.n_dimms, table.n_bins) if state is None else state
        )
        self._partials = trace_score_init(table.n_dimms, table.n_bins)
        self._ingest = _Ingestor(table.n_dimms, mesh)
        self.n_chunks = 0

    # -- introspection ----------------------------------------------------
    @property
    def state(self) -> ControllerState:
        return self._state

    @property
    def partials(self) -> ScorePartials:
        return self._partials

    @property
    def n_steps(self) -> int:
        return int(self._partials.n_steps)

    @property
    def total_switches(self) -> int:
        return int(np.asarray(self._partials.switches, np.int64).sum())

    @property
    def errors_total(self) -> int:
        return self._ingest.errors_seen

    # -- the stream -------------------------------------------------------
    def ingest(
        self,
        temps,
        errors=None,
        return_decisions: bool = False,
    ):
        """Absorb one ``(chunk_steps, n_dimms)`` observation chunk (a 1-D
        ``(n_dimms,)`` row is treated as a single step).

        With ``return_decisions=True`` returns ``(timings, bin_idx,
        switched)`` — the realized per-access timing rows ``(chunk, N, 2,
        4)``, effective bin per step (``n_bins`` = the JEDEC sentinel) and
        switch flags — for callers that program hardware; otherwise
        returns ``None`` and nothing step-indexed is materialized."""
        temps = np.asarray(temps, np.float32)
        if temps.ndim == 1:
            temps = temps[None]
            if errors is not None:
                errors = np.asarray(errors, bool)[None]
        temps_d, errors_d = self._ingest.stage(temps, errors)
        run = _chunk_runner(
            self.mesh, self.table.n_dimms, self.table.temp_bins, self.params,
            emit=return_decisions, impl=self.impl, interpret=self.interpret,
        )
        out = run(self._stack, self._edges, self._jparams, self._state,
                  self._partials.occupancy, self._partials.switches,
                  self._partials.timing_sums, self._partials.n_steps,
                  temps_d, errors_d)
        self._state = out[0]
        self._partials = ScorePartials(*out[1:5])
        self.n_chunks += 1
        if not return_decisions:
            return None
        rows, switched, eff = out[5], out[6], out[7]
        return rows, eff, switched

    def score(
        self,
        cfg=MULTI_CORE,
        claim: float = PAPER_CLAIM_SPEEDUP,
        workloads=WORKLOADS,
    ):
        """The running :func:`trace_score` dict over everything ingested so
        far — bit-identical to materializing and scoring the same steps
        (combined latency+refresh figures included when the table carries
        a refresh policy)."""
        return trace_score_finalize(
            self._partials, self.table.oblivious_stack(), cfg, claim,
            workloads, mesh=self.mesh, refresh=self.table.bin_refresh(),
        )

    def result(self) -> StreamResult:
        """Snapshot as a :class:`StreamResult` (shares the live arrays)."""
        return StreamResult(
            state=self._state, partials=self._partials, table=self.table,
            n_chunks=self.n_chunks, errors_total=self._ingest.errors_seen,
            mesh=self.mesh,
        )
