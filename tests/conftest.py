import faulthandler
import os
import signal
import subprocess
import sys
import threading

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 itself, in its own
# process). Distributed tests spawn subprocesses with their own XLA_FLAGS.
TESTS_DIR = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(TESTS_DIR, "..", "src"))
sys.path.insert(0, TESTS_DIR)

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402
from hypothesis import HealthCheck, settings  # noqa: E402

# Property tests draw the same examples on every run: the work (and the
# programs compiled for each drawn shape) is fixed per tree.
settings.register_profile("repro", derandomize=True, deadline=None,
                          suppress_health_check=[HealthCheck.too_slow])
settings.load_profile("repro")

#: XLA:CPU maps memory for every program it compiles; one worker that
#: keeps thousands of them alive reaches the kernel's per-process map
#: limit (vm.max_map_count, 65530 by default) and segfaults inside the
#: compiler. Past this many mappings the caches are dropped.
_MAX_MAPS = 30000


@pytest.fixture(autouse=True)
def _bound_compiled_programs():
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:                  # no procfs: nothing to bound
        return
    if n_maps > _MAX_MAPS:
        jax.clear_caches()
        gc.collect()


# ---------------------------------------------------------------------------
# per-test wall-clock limits (pytest-timeout enforces them when installed)
# ---------------------------------------------------------------------------

#: seconds a test may take (setup, call and teardown) without a marker
DEFAULT_TIMEOUT = 180

try:
    import pytest_timeout  # noqa: F401
except ImportError:
    pytest_timeout = None


def _limit(item):
    mark = item.get_closest_marker("timeout")
    if mark is None:
        return DEFAULT_TIMEOUT
    return mark.args[0] if mark.args else mark.kwargs["timeout"]


if pytest_timeout is None and hasattr(signal, "SIGALRM"):
    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_protocol(item, nextitem):
        if threading.current_thread() is not threading.main_thread():
            yield                    # SIGALRM reaches the main thread only
            return
        limit = _limit(item)

        def expire(signum, frame):
            pytest.fail(f"{item.nodeid} exceeded its {limit} s limit",
                        pytrace=False)

        previous = signal.signal(signal.SIGALRM, expire)
        signal.alarm(limit)
        faulthandler.dump_traceback_later(limit, file=sys.stderr)
        try:
            yield
        finally:
            faulthandler.cancel_dump_traceback_later()
            signal.alarm(0)
            signal.signal(signal.SIGALRM, previous)


# ---------------------------------------------------------------------------
# check scripts that need their own virtual device count
# ---------------------------------------------------------------------------

@pytest.fixture
def run_check_script():
    """Run tests/<script> in a fresh interpreter (it sets the XLA device
    count before jax starts), echo the tail of its output, and assert it
    exits 0 after printing `sentinel`. `timeout` kills it if it hangs."""
    def run(script, sentinel, timeout):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        res = subprocess.run([sys.executable,
                              os.path.join(TESTS_DIR, script)],
                             capture_output=True, text=True, env=env,
                             timeout=timeout)
        sys.stdout.write(res.stdout[-4000:])
        sys.stderr.write(res.stderr[-4000:])
        assert res.returncode == 0, f"{script} exited {res.returncode}"
        assert sentinel in res.stdout, f"{script} did not print {sentinel}"
    return run
