"""Test config: force JAX onto a virtual 8-device CPU platform.

(SURVEY.md §4: CPU-backend jit tests + 8 simulated devices for mesh tests.)
The forcing recipe lives in jepsen_etcd_demo_tpu.utils.platform (shared with
__graft_entry__.dryrun_multichip).
"""

from jepsen_etcd_demo_tpu.utils.platform import force_virtual_cpu

force_virtual_cpu(8)

import os  # noqa: E402
import random  # noqa: E402
import tempfile  # noqa: E402

# The program keeps its compile cache (and the tuned profile beside it)
# where JAX_COMPILATION_CACHE_DIR says; a test worker's own temp dir
# keeps CPU-compiled entries out of the checkout's .xla-cache.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="jt-xla-cache-"))

import pytest  # noqa: E402


@pytest.fixture
def rng():
    return random.Random(0xE7CD)
