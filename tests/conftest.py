"""Test fixtures (reference pattern: python/ray/tests/conftest.py).

JAX is forced onto a virtual 8-device CPU mesh so all parallelism logic runs
on CPU CI (the analogue of the reference's `_fake_gpus`), per SURVEY.md §4.
"""

import os
import sys

# Must happen before jax initializes a backend anywhere in the test process.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# The suite runs on the CPU backend wherever it is run, a machine with a
# chip included: the driver process and (by inheritance) every worker.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the model tests are compile-bound, and
# repeat runs hit the cache. One directory for the driver and the workers,
# the one the runtime itself uses (a value given from outside wins).
from ray_tpu._private.compile_cache import export_compile_cache_dir  # noqa: E402

export_compile_cache_dir()
os.environ["RAY_TPU_HEARTBEAT_INTERVAL_S"] = "0.2"
os.environ["RAY_TPU_NODE_DEATH_TIMEOUT_S"] = "2.0"

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Per-test timeout (reference: pytest.ini `timeout = 180` via pytest-timeout,
# which is not in this image — hand-rolled with SIGALRM, the same mechanism
# as pytest-timeout's "signal" method). One wedged test must not stall the
# whole suite/driver. Override per test with @pytest.mark.timeout(N).
# ---------------------------------------------------------------------------
_DEFAULT_TEST_TIMEOUT = float(os.environ.get("RAY_TPU_TEST_TIMEOUT", "180"))


def pytest_configure(config):
    # xdist's loadfile hands the files out in the order collected (see
    # `heavy_first`) and not, its default since 3.7, those with the most
    # tests first: a cell's whole step is a file of two or three. Set where
    # the scheduler lives, in the process that collects nothing.
    config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit "
        f"(default {_DEFAULT_TEST_TIMEOUT:.0f}s)")
    # Killed runs leak plasma arenas (/dev/shm/rtpu_<pid>_*) — 4.3 GB
    # piled up in one session and degraded a later full-suite run —
    # and compiled-DAG ring channels (rtch_<pid>_*, same name scheme).
    # Reap segments whose creator pid is gone before this run starts.
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        names = []
    for name in names:
        if not name.startswith(("rtpu_", "rtch_")):
            continue
        try:
            pid = int(name.split("_")[1])
        except (IndexError, ValueError):
            continue
        if not os.path.exists(f"/proc/{pid}"):
            try:
                os.unlink(os.path.join("/dev/shm", name))
            except OSError:
                pass  # raced with a concurrent reaper / foreign owner


# ---------------------------------------------------------------------------
# The heavy files first. Under `--dist loadfile` a worker is handed the next
# file when it has two tests left, so the files that start last decide how
# long the other workers stand idle at the end: they should be the light
# ones. What makes a file heavy is read off what was collected, so a new
# family's two files are placed without a list to edit.
# ---------------------------------------------------------------------------

def heavy_first(files):
    """files: {file: (whether its module has a FAMILY, the fixtures its tests
    take)} -> the files in the order they run: the families' files, one that
    compiles a cell's whole step for the described chip (`cell_step`) and
    one of the others in turn (the whole steps take ~4 cores each and five
    of them stand under the 180 s ceiling: taken in a row they would run
    four at a time), then whatever else compiles for the described chip
    (`v5e`), then what runs JAX on the CPU (`jax_cpu`), then the rest; by
    name within each, so that every xdist worker collects one order."""
    kind = {file: (0 if "cell_step" in fixtures else 1 if has_family
                   else 2 if "v5e" in fixtures else 3 if "jax_cpu" in fixtures
                   else 4) for file, (has_family, fixtures) in files.items()}
    turn = {}       # a family's file -> its place among those of its kind
    for heavy in (0, 1):
        turn.update((file, at) for at, file in enumerate(sorted(
            file for file in files if kind[file] == heavy)))
    return sorted(files, key=lambda file: (
        max(kind[file], 1), turn.get(file, 0), kind[file], file))


def pytest_collection_modifyitems(items):
    """Whole files change places; a file's tests keep their order."""
    def file_of(item):
        return item.nodeid.split("::", 1)[0]
    files = {}
    for item in items:
        _has_family, fixtures = files.setdefault(file_of(item), (
            hasattr(getattr(item, "module", None), "FAMILY"), set()))
        fixtures.update(getattr(item, "fixturenames", ()))
    place = {file: at for at, file in enumerate(heavy_first(files))}
    items.sort(key=lambda item: place[file_of(item)])


# ---------------------------------------------------------------------------
# gen-2 GC relief for the pytest DRIVER process (the analogue of PR 10's
# forkserver gc.freeze() fix, applied to the suite itself). Collection
# imports every test module — pulling ray_tpu + jax + models into a heap
# that only grows as the session ages; every gen-2 collection then
# re-traverses all of it. Freezing moves the accumulated survivors into
# the permanent generation (never traversed again; a gen-2 collect
# measured 15ms -> 0 post-freeze); re-freezing at each module boundary
# folds in whatever the previous module loaded lazily. gc.collect()
# first so garbage cycles aren't immortalized. Measured at the 870s
# tier-1 cap: 425 dots (80%) at HEAD -> 497 dots (88%) with this change
# — while collecting ~45 MORE tests (the static-analysis suite) and
# with the same 7 pre-existing failures.
# ---------------------------------------------------------------------------


def pytest_collection_finish(session):
    import gc
    gc.collect()
    gc.freeze()


@pytest.fixture(autouse=True, scope="module")
def _gc_freeze_accumulated_heap():
    import gc
    gc.collect()
    gc.freeze()
    yield


def pytest_generate_tests(metafunc):
    """A family's file (tests/helpers/families.py) gives the shared checks
    it imports their cases: FAMILY.cases[argument] -> values or
    pytest.params. A test that parametrizes the argument itself keeps its
    own."""
    cases = getattr(getattr(metafunc.module, "FAMILY", None), "cases", {})
    own = {name.strip() for mark in metafunc.definition.iter_markers(
        "parametrize") for name in (
            mark.args[0].split(",") if isinstance(mark.args[0], str)
            else mark.args[0])}
    for name, values in cases.items():
        if name in metafunc.fixturenames and name not in own:
            metafunc.parametrize(name, values)


class _TestTimeout(Exception):
    pass


def _timeout_for(item) -> float:
    m = item.get_closest_marker("timeout")
    if m and m.args:
        return float(m.args[0])
    return _DEFAULT_TEST_TIMEOUT


def _run_with_alarm(item, seconds: float):
    import faulthandler
    import signal

    if seconds <= 0 or os.name != "posix":
        yield
        return

    def _on_alarm(signum, frame):
        # Dump every thread first (the hang is usually NOT in the main
        # thread on this codebase — core loop / worker pool / pump tasks).
        faulthandler.dump_traceback(file=sys.stderr)
        raise _TestTimeout(
            f"test exceeded {seconds:.0f}s wall-clock limit")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _phase_wrapper(item):
    """Arm the alarm around one runtest phase (setup/call/teardown get a
    full budget each): the raise lands inside the test/fixture code, so
    the single test fails and the session lives on."""
    gen = _run_with_alarm(item, _timeout_for(item))
    next(gen)
    try:
        yield
    finally:
        try:
            next(gen)
        except StopIteration:
            pass


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _phase_wrapper(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _phase_wrapper(item)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    yield from _phase_wrapper(item)


def _force_cpu_jax():
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass


@pytest.fixture(scope="session")
def jax_cpu():
    _force_cpu_jax()
    import jax
    assert jax.default_backend() == "cpu"
    return jax


@pytest.fixture
def ray_start(request):
    """Single-node cluster, 4 CPUs, fresh per test."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=0,
                 system_config={"task_max_retries_default": 0})
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_shared(request):
    """Single-node cluster shared across a test module (faster)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_cluster():
    """Multi-raylet fake cluster (reference: ray_start_cluster fixture)."""
    from ray_tpu.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()
