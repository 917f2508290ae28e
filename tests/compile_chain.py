"""The compiles for a described chip of one test run: ONE chain of child
processes, started with the run, that no test waits behind.

libtpu admits one process at a time, and a compile of the real programs for
a described v5e takes minutes. So the children of a run (``conftest.py``'s
``DESCRIBED_CHIP_CHILDREN``) run one behind another in ONE detached
background process — this file, executed as a script — that holds the run's
``libtpu.lock`` for its length, while the workers run other tests:

- :func:`start` is called by every worker at its session start. The first
  to take the lock makes an exclusive-create marker in the run's shared
  directory (so exactly one worker of a run gets through) and hands the
  locked descriptor to the chain: the lock is the chain's from before any
  test runs until its process is gone, however it goes.
- The chain runs each child under a time limit of its own and writes
  ``<name>.json`` by rename (a reader never sees half a file): the child's
  return code, the tail of its stderr, its seconds, and on success the JSON
  object of its last stdout line under ``results``.
- :func:`read` waits for that file. A child that failed or ran out of time
  makes its readers FAIL with its stderr; so does a chain that is gone
  without the file (its lock is free then).

The chain ends with the run: it watches the pytest process that owns it and
stops its child when that process is gone, so an interrupted run leaves no
compile behind to hold ``/tmp/libtpu_lockfile`` against the next one.
"""

import fcntl
import json
import os
import subprocess
import sys
import time

MARKER = "compile_chain.started"
LOCK = "libtpu.lock"
POLL_S = 0.2


def start(run_dir, children, owner_pid):
    """Start the chain for ``children`` ({name: (argv, time limit in s)})
    unless a worker of this run already has; returns whether THIS call did.
    ``owner_pid`` is the process the chain must not outlive."""
    if not children:
        return False
    with open(os.path.join(run_dir, LOCK), "a") as lock:
        try:
            fcntl.flock(lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BlockingIOError:
            return False  # the starter's, or already its chain's
        try:
            # made UNDER the lock: who holds the lock later and finds the
            # marker knows that the chain has come and gone
            os.close(os.open(os.path.join(run_dir, MARKER),
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY, 0o644))
        except FileExistsError:
            return False
        # flock belongs to the open file description, which the chain
        # inherits: the lock outlives this ``with`` and ends with the chain
        with open(os.path.join(run_dir, "compile_chain.log"), "w") as log:
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), str(run_dir),
                 str(owner_pid), json.dumps(children)],
                stdin=subprocess.DEVNULL, stdout=log, stderr=log,
                pass_fds=[lock.fileno()], start_new_session=True)
    return True


def read(run_dir, name):
    """The ``results`` of child ``name``, waiting for them if need be."""
    path = os.path.join(run_dir, name + ".json")
    while not os.path.exists(path):
        with open(os.path.join(run_dir, LOCK), "a") as lock:
            try:
                fcntl.flock(lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except BlockingIOError:
                pass  # the chain (or another holder) is at work
            else:
                if not os.path.exists(path):
                    raise AssertionError(
                        f"the compile chain is gone and left no {name}.json"
                        f" in {run_dir}: was {name!r} among the children it"
                        " was started with?\n" + _tail(
                            os.path.join(run_dir, "compile_chain.log")))
        time.sleep(POLL_S)
    with open(path) as f:
        got = json.load(f)
    if got["returncode"] != 0:
        raise AssertionError(
            f"child {name!r} {got['ended']} after {got['seconds']:.0f} s:\n"
            + got["stderr"])
    return got["results"]


def _tail(path, n=3000):
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def _alive(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        pass
    return True


def _run_child(run_dir, name, argv, limit_s, owner_pid):
    """One child to its end, its time limit or its owner's; its result."""
    out, err = (os.path.join(run_dir, f"{name}.{ext}")
                for ext in ("stdout", "stderr"))
    t0 = time.monotonic()
    with open(out, "w") as o, open(err, "w") as e:
        child = subprocess.Popen(argv, stdin=subprocess.DEVNULL, stdout=o,
                                 stderr=e)
        ended = None
        while child.poll() is None:
            if time.monotonic() - t0 > limit_s:
                ended = f"ran out of its {limit_s:g} s"
            elif not _alive(owner_pid):
                ended = "lost the test run it belonged to"
            if ended:
                child.kill()
                child.wait()
                break
            time.sleep(POLL_S)
    got = {"returncode": child.returncode if ended is None else None,
           "ended": ended or f"exited {child.returncode}",
           "seconds": round(time.monotonic() - t0, 3),
           "stderr": _tail(err)}
    if got["returncode"] == 0:
        try:
            with open(out) as o:
                got["results"] = json.loads(o.read().splitlines()[-1])
        except (IndexError, ValueError) as err:
            got.update(returncode=None, ended="exited 0 but its last line "
                       f"of output is no JSON ({err})")
    return got


def run(run_dir, owner_pid, children):
    for name, (argv, limit_s) in children.items():
        got = _run_child(run_dir, name, argv, limit_s, owner_pid)
        tmp = os.path.join(run_dir, f".{name}.json.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(got, f)
        os.replace(tmp, os.path.join(run_dir, name + ".json"))
        print(f"{name}: {got['ended']} after {got['seconds']} s", flush=True)
        if not _alive(owner_pid):
            return


if __name__ == "__main__":
    run(sys.argv[1], int(sys.argv[2]), json.loads(sys.argv[3]))
