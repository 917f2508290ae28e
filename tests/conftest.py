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


@pytest.fixture(scope="session")
def libtpu_lock(shared_run_dir):
    """libtpu admits ONE process at a time (``/tmp/libtpu_lockfile``; a
    second one aborts). Tests whose child processes load it — to compile
    for a described TPU, or just to find no chip — hold this run-wide file
    lock meanwhile: ``with libtpu_lock(): ...``."""
    @contextlib.contextmanager
    def hold():
        with open(shared_run_dir / "libtpu.lock", "w") as f:
            fcntl.flock(f, fcntl.LOCK_EX)
            yield

    return hold

