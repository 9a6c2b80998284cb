"""Tier-1 suite configuration.

Tests exercise correctness, not codegen quality: XLA's expensive
optimization passes roughly double compile-bound test wall-clock on CPU
without changing what the tests verify, so they are disabled for the whole
suite (set before any test module imports jax). Equivalence-style tests
compare programs compiled under the same flags, so relative numerics are
unaffected. Unset JAX_DISABLE_MOST_OPTIMIZATIONS to measure real codegen.

Persistent compilation cache: the suite is compile-bound (every property
test traces dozens of (shape, chunk) program variants), so re-running it
recompiles identical XLA programs from scratch. JAX's own environment
variables turn on its persistent cache; zero the thresholds so that the
sub-second compiles of these tiny test shapes are cached too. CI points
the directory at an actions/cache-restored path; local example::

    JAX_COMPILATION_CACHE_DIR=~/.cache/repro-jax \
    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0 \
    JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=-1 \
    PYTHONPATH=src pytest -x -q

Measured on the full tier-1 suite (CPU run, one container): cold 348 s
(populating ~8.5k cache entries), warm re-run 216 s. The cache skips the
XLA backend compile; tracing, which it cannot skip, is most of the rest.

Per-test time limit: every test runs under :class:`_time_limit.TimeLimit`,
so a hung test fails on its own and the rest of the run still ends.
"""

import os

import pytest

from _time_limit import TimeLimit

os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

#: Wall-clock limit per test (setup + call + teardown). The slowest tests
#: are compile-bound parity gates of up to ~86 s on a shared 8-core CPU
#: box under 6 xdist workers; 300 s is ~3.5x that, and well inside the
#: whole run's budget, so only a hang reaches it.
TEST_TIME_LIMIT_S = 300.0


def pytest_configure(config):
    config.pluginmanager.register(TimeLimit(TEST_TIME_LIMIT_S), "time-limit")


@pytest.fixture(autouse=True)
def _sanitized():
    """Every tier-1 test runs under ``repro.analysis.sanitize()``.

    Defaults (overridable via REPRO_SANITIZE / REPRO_TRANSFER_GUARD /
    REPRO_RANK_PROMOTION / REPRO_DEBUG_NANS — see repro.analysis):
    rank promotion raises, transfer guard allows (the strict "disallow"
    mode rejects compile-time constant transfers, so it is only usable
    around pre-compiled regions — tests/test_sanitizers.py exercises it
    that way), NaN debugging off.
    """
    from repro import analysis

    with analysis.sanitize() as cfg:
        yield cfg
