import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 itself, in its own
# process). Distributed tests spawn subprocesses with their own XLA_FLAGS.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import gc  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

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
