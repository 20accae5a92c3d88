"""Repo-level pytest bootstrap.

Tests exercise the multi-chip code paths on a virtualized 8-device CPU
"mesh" (the TPU-native answer to testing multi-node without a pod, see
SURVEY.md §4): XLA is forced onto the host platform and told to expose 8
devices through the environment, before jax is imported. Set
PMDT_TEST_ON_TPU=1 to run the suite against real chips instead (note:
multi-device tests assume 8 devices; on smaller real topologies they
will skip/fail by design).
"""

import atexit
import os
import shutil
import sys
import tempfile

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if not os.environ.get("PMDT_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

# One persistent compile cache for the session, inherited by the CLI
# subprocesses the suite starts: the suite compiles the same tiny
# programs hundreds of times (every engine instance its decode step),
# and tier-1 runs against a fixed time window. A fresh directory per
# session, removed at exit — an XLA:CPU executable is only safe to
# reload on the machine that built it.
_cache = tempfile.mkdtemp(prefix="pmdt_test_jax_cache_")
atexit.register(shutil.rmtree, _cache, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.1"

sys.path.insert(0, os.path.dirname(__file__))

# runtime jit-hygiene sentinels as suite-wide fixtures
# (transfer_sentinel / recompile_sentinel — tests/test_sentinels.py
# pins them on the train step, generate() and the serving engine)
pytest_plugins = (
    "pytorch_multiprocessing_distributed_tpu.analysis.sentinels",
)
