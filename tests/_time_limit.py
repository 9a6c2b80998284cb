"""Per-test wall-clock limit, built from the stdlib and pytest alone.

A test that hangs inside a compiled program never returns to the
interpreter, so neither ``SIGALRM`` nor an exception can stop it. This
plugin arms ``faulthandler.dump_traceback_later(limit, exit=True)``
around each test's setup, call and teardown: past the limit a C watchdog
thread dumps every thread's stack to stderr and ends the process.

Without xdist that ends the whole run, with the hung test's stack on
stderr. Under ``pytest-xdist`` the controller reports the dead worker's
test as failed and starts a replacement worker. The stock ``loadfile``
scheduler then re-queues every file the dead worker was ever given, its
finished tests and the hung test included: the replacement is handed an
empty batch first and the run stalls, or it runs the hung test again and
dies in turn. So under ``--dist loadfile`` this plugin supplies the
scheduler, one that re-queues only the tests the dead worker had not
started.

The tier-1 ``conftest.py`` registers the plugin with its limit::

    config.pluginmanager.register(TimeLimit(300.0), "time-limit")

``-o faulthandler_timeout=...`` shares the same process-wide watchdog
and overrides this limit for the run it is given to.
"""

from __future__ import annotations

import faulthandler
import os
import sys

import pytest


class TimeLimit:
    """pytest plugin that ends any test running past ``seconds``."""

    def __init__(self, seconds: float):
        if seconds <= 0:
            raise ValueError(f"time limit must be positive, got {seconds}")
        self.seconds = float(seconds)
        self._stderr = -1

    def pytest_configure(self, config: pytest.Config) -> None:
        # Capture is suspended while plugins configure, so fd 2 is still
        # the real stderr: keep a copy for the watchdog's stack dump.
        self._stderr = os.dup(sys.__stderr__.fileno())

    def pytest_unconfigure(self, config: pytest.Config) -> None:
        faulthandler.cancel_dump_traceback_later()
        if self._stderr >= 0:
            os.close(self._stderr)
            self._stderr = -1

    @pytest.hookimpl(wrapper=True, tryfirst=True)
    def pytest_runtest_protocol(self, item: pytest.Item):
        faulthandler.dump_traceback_later(
            self.seconds, file=self._stderr, exit=True
        )
        try:
            return (yield)
        finally:
            faulthandler.cancel_dump_traceback_later()

    @pytest.hookimpl(optionalhook=True, tryfirst=True)
    def pytest_xdist_make_scheduler(self, config: pytest.Config, log):
        if config.getoption("dist") != "loadfile":
            return None
        return _resuming_loadfile(config, log)


def _resuming_loadfile(config: pytest.Config, log):
    """xdist's ``loadfile`` scheduler, resuming a dead worker's files
    with the tests it had not started (see the module docstring)."""
    from xdist.scheduler import LoadFileScheduling

    class ResumingLoadFile(LoadFileScheduling):
        def remove_node(self, node) -> str | None:
            workload = self.assigned_work.pop(node)
            left = [
                (scope, [t for t, done in unit.items() if not done])
                for scope, unit in workload.items()
            ]
            left = [(scope, tests) for scope, tests in left if tests]
            if not left:
                return None
            # A worker runs its batches in order: the first test it had
            # not finished is the one it died in, reported by the caller.
            crashed = left[0][1].pop(0)
            for scope, tests in left:
                if tests:
                    self.workqueue[scope] = dict.fromkeys(tests, False)
            for other in self.assigned_work:
                self._reschedule(other)
            return crashed

        def _reschedule(self, node) -> None:
            # A replacement still collecting is given work once its
            # collection is in (``schedule`` runs again then).
            if node not in self.registered_collections:
                return
            # A worker runs a test only once it holds the next one or is
            # told to stop, so never leave it holding a single test: a
            # resumed file may be one test long.
            super()._reschedule(node)
            while (not node.shutting_down
                   and self._pending_of(self.assigned_work[node]) < 2):
                super()._reschedule(node)

    return ResumingLoadFile(config, log)
