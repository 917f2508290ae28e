"""The compile chain itself (``tests/compile_chain.py`` and conftest's
ordering hook), driven with fake children: no libtpu is loaded here."""

import json
import os
import sys
import threading
import time
import types

import pytest

import compile_chain
import conftest


def _child(body):
    """A child's command whose Python ``body`` runs in a process of its
    own."""
    return [sys.executable, "-c", body]


def _counts_its_starts(tmp_path, sleep_s=0.0, result='{"ok": 1}'):
    """A child that appends a line to ``starts`` before it prints."""
    return _child(
        f"import time; open({str(tmp_path / 'starts')!r}, 'a').write('x\\n');"
        f" time.sleep({sleep_s}); print('noise'); print({result!r})")


def _wait_for_the_chain_to_go(tmp_path, budget_s=20.0):
    """Until the chain's lock is free (its process is gone)."""
    import fcntl

    end = time.monotonic() + budget_s
    while time.monotonic() < end:
        with open(tmp_path / compile_chain.LOCK, "a") as f:
            try:
                fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
                return
            except BlockingIOError:
                time.sleep(0.05)
    raise AssertionError("the chain still holds its lock")


def test_concurrent_starters_start_one_chain(tmp_path):
    """Eight workers reach ``start`` at once: one of them starts the
    chain, and each child runs once."""
    children = {"a": (_counts_its_starts(tmp_path, 0.3), 30),
                "b": (_counts_its_starts(tmp_path), 30)}
    go, started = threading.Barrier(8), []

    def worker():
        go.wait()
        started.append(
            compile_chain.start(tmp_path, children, os.getpid()))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(started) == [False] * 7 + [True]
    assert compile_chain.read(tmp_path, "b") == {"ok": 1}
    # ... and a latecomer, after the chain is gone, starts no second one
    _wait_for_the_chain_to_go(tmp_path)
    assert not compile_chain.start(tmp_path, children, os.getpid())
    assert (tmp_path / "starts").read_text() == "x\nx\n"


def test_an_early_reader_waits_and_reads_the_whole_result(tmp_path):
    """A reader that arrives while its child still runs gets the whole
    object, every other child's too, and each result says what it took."""
    big = {"rows": list(range(20000)), "last": "end"}
    children = {
        "slow": (_counts_its_starts(tmp_path, 1.0, json.dumps(big)), 30),
        "next": (_counts_its_starts(tmp_path), 30)}
    assert compile_chain.start(tmp_path, children, os.getpid())
    began = time.monotonic()
    assert not (tmp_path / "slow.json").exists()
    assert compile_chain.read(tmp_path, "slow") == big
    assert time.monotonic() - began >= 0.9
    assert compile_chain.read(tmp_path, "next") == {"ok": 1}
    took = json.loads((tmp_path / "slow.json").read_text())
    assert took["returncode"] == 0 and took["seconds"] >= 1.0
    # nothing but whole results under their names: the rename's temp is gone
    assert sorted(p.name for p in tmp_path.glob("*.json")) == [
        "next.json", "slow.json"]


@pytest.mark.parametrize("how", ["exits_non_zero", "runs_out_of_time",
                                 "prints_no_json", "was_never_started"])
def test_a_child_that_fails_makes_its_readers_fail_with_its_stderr(
        tmp_path, how):
    """A failed child is a FAILED reader that shows the child's stderr,
    never a skip and never a wait without end; the child behind it still
    runs."""
    body = {"exits_non_zero":
            "import sys; sys.stderr.write('Mosaic said no'); sys.exit(3)",
            "runs_out_of_time":
            "import sys, time; sys.stderr.write('Mosaic is slow');"
            " sys.stderr.flush(); time.sleep(60)",
            "prints_no_json": "print('half a li')",
            "was_never_started": "print('{}')"}[how]
    limit_s = 0.5 if how == "runs_out_of_time" else 30
    children = {"bad": (_child(body), limit_s),
                "good": (_counts_its_starts(tmp_path), 30)}
    if how == "was_never_started":
        del children["bad"]
    assert compile_chain.start(tmp_path, children, os.getpid())
    began = time.monotonic()
    with pytest.raises(AssertionError) as failure:
        compile_chain.read(tmp_path, "bad")
    assert time.monotonic() - began < 20
    assert {"exits_non_zero": "exited 3",
            "runs_out_of_time": "ran out of its 0.5 s",
            "prints_no_json": "is no JSON",
            "was_never_started": "left no bad.json"}[how] in str(
                failure.value)
    if how in ("exits_non_zero", "runs_out_of_time"):
        assert "Mosaic" in str(failure.value)
    assert compile_chain.read(tmp_path, "good") == {"ok": 1}


def test_the_chain_does_not_outlive_its_run(tmp_path):
    """The chain of a run that is gone (an interrupted pytest) stops its
    child and starts no other."""
    import subprocess

    owner = subprocess.Popen(_child("import time; time.sleep(60)"))
    children = {"long": (_counts_its_starts(tmp_path, 60), 120),
                "never": (_counts_its_starts(tmp_path), 30)}
    assert compile_chain.start(tmp_path, children, owner.pid)
    while not (tmp_path / "starts").exists():
        time.sleep(0.05)
    owner.kill()
    owner.wait()
    _wait_for_the_chain_to_go(tmp_path)
    with pytest.raises(AssertionError, match="lost the test run"):
        compile_chain.read(tmp_path, "long")
    assert (tmp_path / "starts").read_text() == "x\n"
    assert not (tmp_path / "never.json").exists()


def _items(*fixture_sets):
    return [types.SimpleNamespace(nodeid=f"t{i}", fixturenames=list(names))
            for i, names in enumerate(fixture_sets)]


def test_the_ordering_hook_puts_every_reader_behind_every_other_item():
    """Readers go last in the chain's order, lock holders behind them, the
    rest keeps its order — and twice gives the same (xdist compares the
    workers' collections)."""
    children = dict.fromkeys(["first", "second"])
    items = _items(["second"], ["tmp_path"], ["libtpu_lock"], ["first", "x"],
                   [], ["second", "first"], ["request"], ["first"])
    order = [i.nodeid for i in conftest.readers_last(items, children)]
    assert order == ["t1", "t4", "t6", "t3", "t5", "t7", "t0", "t2"]
    again = conftest.readers_last(list(reversed(items)), children)
    assert [i.nodeid for i in conftest.readers_last(again, children)] == [
        i.nodeid for i in again]


def test_this_runs_readers_are_behind_everything_else(request):
    """The hook ran on THIS collection: behind the first item that waits
    for the chain there is none that does not."""
    waits_for = {*conftest.DESCRIBED_CHIP_CHILDREN, "libtpu_lock"}
    waiting = [bool(waits_for & set(item.fixturenames))
               for item in request.session.items]
    assert waiting == sorted(waiting)
