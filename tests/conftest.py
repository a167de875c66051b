"""Test harness: 8 virtual CPU devices so the 2-tier HiPS mesh (2 parties x
4 workers, or 4 x 2) runs multi-"chip" on one host — the same trick as the
reference's pseudo-distributed localhost scripts
(scripts/cpu/run_vanilla_hips.sh runs 12 processes on 127.0.0.1)."""

import faulthandler
import os
import signal
import sys

# the checkout on the path: `benchmark/` and `tools/` for the tests that
# import them, `geomx_tpu` for a `pytest` started without `python -m`
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu logs out of /tmp
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the suite's wall time is dominated by
# CPU compiles of the training-step programs, and the programs are stable
# across runs, so warm reruns cut minutes.  Keyed by the program's hash,
# scope names and source lines included: tests read scopes from compiled
# programs (Trainer.step_layers), and without the metadata in the key a
# directory filled by an earlier build answers with that build's names
# (utils/compile_cache.py).  The directory is JAX_COMPILATION_CACHE_DIR
# where set, else <checkout>/.geomx_compile_cache (utils/compile_cache.py);
# GEOMX_TEST_COMPILE_CACHE=0 disables.
if os.environ.get("GEOMX_TEST_COMPILE_CACHE") != "0":
    import jax
    from geomx_tpu.utils import enable_compile_cache
    enable_compile_cache(min_compile_seconds=0.7)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from geomx_tpu.topology import HiPSTopology  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tier2: long-running convergence/e2e tests whose semantics a "
        "faster tier-1 sibling also covers; skipped by default so the "
        "tier-1 run stays inside its limit — run them with "
        "GEOMX_TEST_TIER=full or -m tier2")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("GEOMX_TEST_TIER") == "full":
        return
    if "tier2" in config.getoption("markexpr", ""):
        return  # an explicit -m tier2 expression picks its own tests
    # any OTHER -m expression (the tier-1 command runs -m 'not slow')
    # keeps the default tier2 skip: before the shard_map fix these
    # convergence tests failed in ~1s each, so 'not slow' accidentally
    # admitting them never showed; actually running them blows the
    # tier-1 time budget this skip exists to protect
    # naming a test by node id ("file.py::test_x") overrides the tier:
    # a developer running one slow test must get the test, not a skip
    explicit = {a.split("::", 1)[1] for a in config.args if "::" in a}
    skip = pytest.mark.skip(
        reason="tier2 (GEOMX_TEST_TIER=full or -m tier2 to run)")
    for item in items:
        if "tier2" not in item.keywords:
            continue
        name = item.nodeid.split("::", 1)[-1]
        if any(name.startswith(e) for e in explicit):
            continue
        item.add_marker(skip)


@pytest.fixture(scope="module", autouse=True)
def _collected_garbage_between_files():
    """A file's tests start without the cyclic garbage of the files that
    ran before them in this worker: trainers and their device arrays that
    only the collector frees, at a moment of its choosing, which a test
    that counts live device bytes
    (benchmark/test_benchmark_reference.py) reads as its own."""
    import gc
    gc.collect()


TEST_LIMIT_S = 240


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item):
    """One test's limit, set-up (the module's fixtures it is the first to
    ask for included) to tear-down: a test that waits for ever on a socket,
    a thread or a compile costs `TEST_LIMIT_S` and fails under its own
    name, not the whole run its time limit.  The alarm's handler writes
    every thread's stack to stderr and raises in the worker's main thread,
    where a test runs; a test stuck where no Python runs never sees it, so
    half a minute later `faulthandler`'s own thread writes the stacks and
    ends the worker, and xdist names the test it died in."""
    def overrun(_signum, _frame):
        faulthandler.dump_traceback(file=sys.stderr)
        pytest.fail(f"{item.nodeid} ran past the {TEST_LIMIT_S} s every "
                    f"test is given (tests/conftest.py)", pytrace=False)
    was = signal.signal(signal.SIGALRM, overrun)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_LIMIT_S + 30, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, was)


@pytest.fixture(scope="module")
def chip():
    """One described v5e device for the `test_tpu_compile_*.py` files, with
    the persistent compile cache off: an executable compiled for an
    unattached chip is written to the cache but cannot be read back
    without one."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="session")
def topo2x4():
    return HiPSTopology(num_parties=2, workers_per_party=4)


@pytest.fixture(scope="session")
def topo4x2():
    return HiPSTopology(num_parties=4, workers_per_party=2)


@pytest.fixture(scope="session")
def mesh2x4(topo2x4):
    return topo2x4.build_mesh()


@pytest.fixture()
def rng():
    return np.random.RandomState(0)
