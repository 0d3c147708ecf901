"""Runs the distributed correctness suite in a subprocess with 8 virtual
devices (XLA device count must be set before jax initializes)."""
import pytest

pytestmark = pytest.mark.slow


@pytest.mark.timeout(430)
def test_distributed_checks(run_check_script):
    run_check_script("dist_checks.py", "ALL DISTRIBUTED CHECKS PASSED",
                     timeout=400)
