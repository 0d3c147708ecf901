"""The per-test wall-clock limit tests/conftest.py enforces: a test that
runs past its `timeout` marker fails with its name, and a test inside
its limit passes. Each runs the conftest in a fresh pytest process."""
import pathlib
import time

import pytest

pytest_plugins = ["pytester"]

_INNER = """
import time

import pytest


@pytest.mark.timeout(1)
def test_sleeps_past_its_limit():
    time.sleep(60)


@pytest.mark.timeout(60)
def test_finishes_inside_its_limit():
    time.sleep(0.1)
"""


@pytest.fixture
def limited(pytester):
    pytester.makeconftest(
        pathlib.Path(__file__).with_name("conftest.py").read_text())
    pytester.makeini("[pytest]\nmarkers =\n    timeout: wall-clock limit\n")
    pytester.makepyfile(test_inner=_INNER)
    return pytester


def test_limit_fails_a_test_with_its_name(limited):
    start = time.monotonic()
    res = limited.runpytest_subprocess("-p", "no:cacheprovider", "-k",
                                       "past_its_limit")
    assert time.monotonic() - start < 50     # cut at 1 s, not slept out
    res.assert_outcomes(failed=1)
    res.stdout.fnmatch_lines(
        ["*test_inner.py::test_sleeps_past_its_limit exceeded its 1 s limit*"])


def test_test_inside_its_limit_passes(limited):
    res = limited.runpytest_subprocess("-p", "no:cacheprovider", "-k",
                                       "inside_its_limit")
    res.assert_outcomes(passed=1)
