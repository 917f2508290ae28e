"""Test harness configuration.

Mirrors the reference's CPU-only test strategy (SURVEY.md §4): all tests run
on a virtual 8-device CPU platform so multi-chip sharding is exercised without
TPU hardware. Must set env vars BEFORE jax is imported anywhere.
"""

import contextlib
import fcntl
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import compile_chain  # noqa: E402 — tests/ is on the path under pytest


@pytest.fixture(autouse=True)
def _seed_everything():
    from areal_tpu.base import seeding

    seeding.set_random_seed(1)
    np.random.seed(1)
    yield


@pytest.fixture(autouse=True)
def _no_label_left_by_another_test():
    """``compile_watch.label`` is a thread-local that lives until the next
    store: an engine that dispatched ``infer_forward`` in one test leaves
    its grid's label for whatever test the worker runs next, and
    ``test_compile_watch``'s fed-in ``infer_forward`` compile then reads
    it (seen once under six workers, PR 58: which tests share a worker
    moves with every file added). A test starts with none."""
    from areal_tpu.base import compile_watch

    vars(compile_watch._LABEL).pop("value", None)
    yield


@pytest.fixture(scope="module", autouse=True)
def _forget_a_parity_files_programs(request):
    """Every executable XLA's CPU compiler has made keeps a few memory
    mappings, a process may hold 65,530 (``vm.max_map_count``), and the
    parity files compile by the thousand (each eager op of a gradient is
    a program): a worker that ran several of them died inside the compiler
    ("LLVM compilation error: Cannot allocate memory", then a segmentation
    fault: ROADMAP D12). ``jax.clear_caches()`` gives the mappings back, so
    a parity file leaves its worker as light as it found it."""
    yield
    if request.module.__name__.endswith("_parity"):
        import jax

        jax.clear_caches()


@pytest.fixture()
def tmp_name_resolve(tmp_path):
    from areal_tpu.base import name_resolve

    old = name_resolve.DEFAULT_REPO
    name_resolve.DEFAULT_REPO = name_resolve.NfsNameRecordRepo(str(tmp_path / "nr"))
    yield name_resolve.DEFAULT_REPO
    name_resolve.DEFAULT_REPO = old


@pytest.fixture(scope="session")
def shared_run_dir(tmp_path_factory):
    """One temp directory per test RUN, shared by every xdist worker."""
    base = tmp_path_factory.getbasetemp()
    return base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base


@pytest.fixture(scope="session", autouse=True)
def _one_compile_cache_a_run(shared_run_dir):
    """The workers of a run share ONE persistent compile cache, new with
    the run. Most of a test's time here is XLA compiling tiny programs
    (188 of them, 6.4 of 10.5 s, in ONE forward of a parity file's model
    and its reference), and six workers compile the same ones: with the
    cache a program is compiled by the first worker that needs it and read
    by the rest (the nine largest parity and kernel files: 2,132 -> 1,539 s
    of case time, ROADMAP D12). A directory of the run's own, so that no
    run depends on what another left behind; a half-written entry reads as
    a warning and a compile (jax's own fallback). Child processes keep
    their own cache (``compile_watch.enable_compilation_cache``)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir",
                      str(shared_run_dir / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def libtpu_lock(shared_run_dir):
    """libtpu admits ONE process at a time (``/tmp/libtpu_lockfile``; a
    second one aborts). Tests whose child processes load it — to compile
    for a described TPU, or just to find no chip — hold this run-wide file
    lock meanwhile: ``with libtpu_lock(): ...``. The compile chain below
    holds it from the run's start to its own end, so
    :func:`pytest_collection_modifyitems` puts such tests last."""
    @contextlib.contextmanager
    def hold():
        with open(shared_run_dir / compile_chain.LOCK, "a") as f:
            fcntl.flock(f, fcntl.LOCK_EX)
            yield

    return hold


# The compiles for a described v5e (tests/compile_chain.py): fixture name ->
# (the child's command, its time limit in seconds). A test that takes the
# fixture gets the JSON object its child printed last; the children of a run
# are started at the run's start as one chain, in this order, and their
# readers are collected last, in this order. A new child is one entry here.
_TPU_COMPILE = [sys.executable,
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "test_tpu_compile.py")]
DESCRIBED_CHIP_CHILDREN = {
    "compiled": (_TPU_COMPILE, 1100),
    "compiled_lfm2": (_TPU_COMPILE + ["lfm2"], 600),
    "compiled_glm": (_TPU_COMPILE + ["glm"], 600),
    "compiled_kimi": (_TPU_COMPILE + ["kimi"], 600),
    "compiled_keye": (_TPU_COMPILE + ["keye"], 600),
}
_CHAIN = pytest.StashKey[dict]()


def readers_last(items, children=DESCRIBED_CHIP_CHILDREN):
    """``items`` with everything that waits for the chain behind everything
    that does not: the readers of each child in the chain's order, then the
    holders of ``libtpu_lock`` (they wait for the whole chain). Otherwise
    the order is kept, so every xdist worker computes the same."""
    waits_for = [*children, "libtpu_lock"]

    def rank(item):
        return min((waits_for.index(n) + 1 for n in item.fixturenames
                    if n in waits_for), default=0)

    return sorted(items, key=rank)


@pytest.hookimpl(trylast=True)  # behind -k / -m: what is deselected is gone
def pytest_collection_modifyitems(config, items):
    items[:] = readers_last(items)
    config.stash[_CHAIN] = {
        name: child for name, child in DESCRIBED_CHIP_CHILDREN.items()
        if any(name in item.fixturenames for item in items)}


def _start_chain(config, run_dir):
    # under xdist the run's one lasting process is the workers' parent
    owner = os.getppid() if os.environ.get("PYTEST_XDIST_WORKER") \
        else os.getpid()
    compile_chain.start(run_dir, config.stash.get(_CHAIN, {}), owner)


@pytest.fixture(scope="session", autouse=True)
def _described_chip_compiles_start_with_the_run(request, shared_run_dir):
    """Before a worker's first test: the chain of every child that the
    collection holds a reader of, unless another worker was first. A run
    that reads none starts nothing."""
    _start_chain(request.config, shared_run_dir)


def _reader(name):
    @pytest.fixture(scope="session", name=name)
    def read(request, shared_run_dir):
        _start_chain(request.config, shared_run_dir)  # if nothing has yet
        results = compile_chain.read(shared_run_dir, name)
        if "skip" in results:
            pytest.skip(results["skip"])
        return results

    read.__doc__ = (f"What the child ``{name}`` of the run's compile chain "
                    "printed (``DESCRIBED_CHIP_CHILDREN``), once it is there.")
    return read


for _name in DESCRIBED_CHIP_CHILDREN:
    globals()[_name] = _reader(_name)
