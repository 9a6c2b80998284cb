"""The per-test time limit (``tests/_time_limit.py``) under xdist.

Planted tests sleep past a short limit in a throwaway suite run the way
tier-1 runs (``-p xdist --dist loadfile``). The run must still end, each
sleeper must fail once, and every other test must pass, the quick test
queued after each sleeper in its file among them. Each sleeper's file
is queued after files its worker has already finished. With one
worker, its replacement resumes a one-test remainder and a whole file;
with two, both workers die at about the same time. xdist's stock
``loadfile`` scheduler stalls or ends the run with an internal error in
these cases.
"""

import os
import subprocess
import sys
import textwrap
import xml.etree.ElementTree as ET

import pytest

pytest.importorskip("xdist")

LIMIT_S = 2.0
SLEEP_S = 60.0


def _write(path, text):
    path.write_text(textwrap.dedent(text))


@pytest.mark.parametrize("workers", [1, 2])
def test_overrun_fails_alone_and_run_reaches_its_end(tmp_path, workers):
    _write(tmp_path / "pytest.ini", "[pytest]\n")
    _write(tmp_path / "conftest.py", f"""
        from _time_limit import TimeLimit

        def pytest_configure(config):
            config.pluginmanager.register(TimeLimit({LIMIT_S}), "time-limit")
    """)
    # xdist queues files with more tests first: these three-test files
    # run before the two-test planted ones.
    for name in "abc":
        _write(tmp_path / f"test_{name}.py", "".join(
            f"def test_{name}{i}():\n    pass\n\n" for i in range(3)
        ))
    for name in ("sleepy", "drowsy"):
        _write(tmp_path / f"test_{name}.py", f"""
            import time

            def test_{name}_sleeps_past_limit():
                time.sleep({SLEEP_S})

            def test_{name}_quick_after():
                pass
        """)
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (here, env.get("PYTHONPATH")) if p
    )
    junit = tmp_path / "out.xml"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "xdist", "-n", str(workers),
         "--dist", "loadfile",
         f"--junitxml={junit}", str(tmp_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=SLEEP_S * 1.5,
    )
    log = out.stdout + out.stderr
    assert out.returncode == 1, log
    outcomes = {}  # test name -> passed?, once per report
    for case in ET.parse(junit).iter("testcase"):
        passed = case.find("failure") is None and case.find("error") is None
        outcomes.setdefault(case.get("name"), []).append(passed)
    sleepers = {f"test_{n}_sleeps_past_limit" for n in ("sleepy", "drowsy")}
    assert {k: v for k, v in outcomes.items() if k in sleepers} == {
        k: [False] for k in sleepers
    }, log
    others = {k: v for k, v in outcomes.items() if k not in sleepers}
    assert len(others) == 11 and all(v == [True] for v in others.values()), log
    # The watchdog's stack dump names each hung test's frame.
    assert log.count("Timeout (0:00:02)!") == 2, log
    assert all(s in log for s in sleepers), log
