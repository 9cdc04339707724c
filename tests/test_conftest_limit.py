"""The suite's per-test time limit (``tests/conftest.py``): a test body or
a fixture that hangs fails alone, names the line it hung on, and leaves
no timer and no handler behind it.

Each case runs in a pytest subprocess, under a conftest that loads the
suite's own hooks with the limit set to 2 s."""

import json
import os
import subprocess
import sys
import time
import xml.etree.ElementTree as ET

import pytest

LIMIT_S = 2

_SUITE_CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")

_CONFTEST = f"""\
import importlib.util
import json
import signal

_spec = importlib.util.spec_from_file_location("suite_conftest", {_SUITE_CONFTEST!r})
suite = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(suite)
suite.LIMIT_S = {LIMIT_S}
pytest_runtest_setup = suite.pytest_runtest_setup
pytest_runtest_call = suite.pytest_runtest_call
pytest_runtest_teardown = suite.pytest_runtest_teardown


def pytest_runtest_logfinish(nodeid, location):
    with open("after.jsonl", "a") as f:
        f.write(json.dumps({{
            "test": nodeid.split("::")[-1],
            "timer": signal.getitimer(signal.ITIMER_REAL),
            "handler_is_default": signal.getsignal(signal.SIGALRM) is signal.SIG_DFL,
        }}) + "\\n")
"""

_TESTS = """\
import threading

import pytest


def test_loops():
    while True: pass


def test_waits():
    threading.Event().wait()


def test_swallows_exceptions():
    try:
        threading.Event().wait()
    except Exception:
        threading.Event().wait()


def test_is_quick():
    assert 1 + 1 == 2


@pytest.fixture
def stuck():
    threading.Semaphore(0).acquire()
    yield


def test_with_a_stuck_fixture(stuck):
    pass


@pytest.fixture
def stuck_at_teardown():
    yield
    waiter = threading.Thread(target=threading.Event().wait, daemon=True)
    waiter.start()
    waiter.join()


def test_with_a_stuck_teardown(stuck_at_teardown):
    pass
"""


# Where each hang sits in the generated file.
_HUNG_AT = {
    "test_loops": "    while True: pass",
    "test_waits": "    threading.Event().wait()",
    "stuck": "    threading.Semaphore(0).acquire()",
    "stuck_at_teardown": "    waiter.join()",
}


def _hung_at(name):
    line = _TESTS.splitlines().index(_HUNG_AT[name]) + 1
    return f'test_hangs.py", line {line} in {name}'


# Runs side by side, so the file takes about one start-up and one limit;
# the quick test runs after a hang in the same process.
_RUNS = (
    ("test_loops", "test_is_quick"),
    ("test_waits",),
    ("test_swallows_exceptions",),
    ("test_with_a_stuck_fixture",),
    ("test_with_a_stuck_teardown",),
)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The generated file's runs: their junit cases by name, their
    output, the timer's state after each test, and their wall time."""
    root = tmp_path_factory.mktemp("limit")
    (root / "conftest.py").write_text(_CONFTEST)
    (root / "test_hangs.py").write_text(_TESTS)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTEST_DISABLE_PLUGIN_AUTOLOAD"] = "1"
    t0 = time.monotonic()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "pytest", "-p", "no:cacheprovider", "-q",
             f"--junitxml=cases{i}.xml", *(f"test_hangs.py::{t}" for t in tests)],
            cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i, tests in enumerate(_RUNS)
    ]
    try:
        output = "".join(p.communicate(timeout=60)[0] for p in procs)
    finally:
        for p in procs:
            p.kill()
            p.wait()
    seconds = time.monotonic() - t0
    cases = {
        c.get("name"): c
        for i in range(len(procs))
        for c in ET.parse(root / f"cases{i}.xml").iter("testcase")
    }
    after = {}
    for line in (root / "after.jsonl").read_text().splitlines():
        rec = json.loads(line)
        after[rec["test"]] = rec
    return cases, output, after, seconds


@pytest.mark.parametrize("test", ["test_loops", "test_waits"])
def test_a_hanging_test_fails_at_the_limit_and_names_its_line(run, test):
    cases, output, after, _ = run
    failure = cases[test].find("failure")
    assert failure is not None
    assert f"over its {LIMIT_S} s limit" in failure.get("message")
    assert float(cases[test].get("time")) < 2 * LIMIT_S
    assert _hung_at(test) in output
    assert after[test]["timer"] == [0.0, 0.0]
    assert after[test]["handler_is_default"]


def test_a_tests_own_except_exception_cannot_swallow_the_limit(run):
    cases, _, _, _ = run
    failure = cases["test_swallows_exceptions"].find("failure")
    assert failure is not None
    assert f"over its {LIMIT_S} s limit" in failure.get("message")


def test_a_quick_test_after_a_hang_passes_and_leaves_no_timer(run):
    cases, _, after, _ = run
    case = cases["test_is_quick"]
    assert case.find("failure") is None and case.find("error") is None
    assert after["test_is_quick"]["timer"] == [0.0, 0.0]
    assert after["test_is_quick"]["handler_is_default"]


@pytest.mark.parametrize(
    "test, fixture, phase",
    [
        ("test_with_a_stuck_fixture", "stuck", "setup"),
        ("test_with_a_stuck_teardown", "stuck_at_teardown", "teardown"),
    ],
)
def test_a_hanging_fixture_fails_its_phase(run, test, fixture, phase):
    cases, output, after, _ = run
    error = cases[test].find("error")
    assert error is not None
    assert f"failed on {phase}" in error.get("message")
    assert f"over its {LIMIT_S} s limit" in error.get("message")
    assert _hung_at(fixture) in output
    assert after[test]["timer"] == [0.0, 0.0]
    assert after[test]["handler_is_default"]


def test_every_case_reports_and_the_runs_end(run):
    cases, _, _, seconds = run
    assert sorted(cases) == sorted(t for tests in _RUNS for t in tests)
    assert seconds < LIMIT_S + 20
