"""The per-item time limit of tier-1 (tests/_timelimit.py), at small
scale: the soft limit ends the waits a lost wake-up leaves a test in and
says where it waited; it leaves nothing armed; the hard limit ends a
process whose main thread no signal reaches."""
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

import pytest

import _timelimit

# limited() does not nest: a block here replaces, and on leaving disarms,
# the limit conftest.py put around the item.  These items end in seconds.


def _sleep():
    time.sleep(30)  # WAITS-HERE


def _lock():
    mu = threading.Lock()
    mu.acquire()
    mu.acquire()  # WAITS-HERE


def _join():
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, daemon=True)
    t.start()
    try:
        t.join()  # WAITS-HERE
    finally:
        stop.set()


@pytest.mark.parametrize("wait", [_sleep, _lock, _join])
def test_soft_limit_ends_a_wait_and_names_its_line(wait, capfd):
    t0 = time.monotonic()
    with pytest.raises(_timelimit.TimeLimitExceeded) as ei:
        with _timelimit.limited(0.3, wait.__name__):
            wait()
    assert time.monotonic() - t0 < 5.0
    assert wait.__name__ in str(ei.value)
    tb = "".join(traceback.format_tb(ei.tb))
    assert "WAITS-HERE" in tb, tb
    # every thread's stack went to stderr first
    assert "most recent call first" in capfd.readouterr().err


def test_nothing_left_armed_after_a_block_that_finished():
    before = signal.getsignal(signal.SIGALRM)
    with _timelimit.limited(5.0, "quick"):
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 5.0
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is before


def test_hard_limit_ends_a_process_no_signal_reaches():
    # the child blocks SIGALRM, so only the watchdog thread can end it
    code = (
        "import signal, sys, time\n"
        f"sys.path.insert(0, {os.path.dirname(__file__)!r})\n"
        "import _timelimit\n"
        "signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})\n"
        "with _timelimit.limited(0.5, 'stuck', hard_extra=0.5):\n"
        "    time.sleep(30)\n")
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=20)
    assert time.monotonic() - t0 < 5.0
    assert p.returncode != 0
    assert "most recent call first" in p.stderr, p.stderr


def test_an_item_that_ended_a_worker_is_not_run_again(tmp_path, monkeypatch):
    # what conftest.py asks before an item's set-up under xdist
    monkeypatch.setattr(_timelimit.tempfile, "tempdir", str(tmp_path))
    dead = _timelimit.Running("run1", "gw0")
    never_left = dead.item("tests/x.py::test_stuck")
    never_left.__enter__()
    live = _timelimit.Running("run1", "gw6")
    with live.item("tests/x.py::test_stuck"):
        assert live.ended_a_worker("tests/x.py::test_stuck")
        assert not live.ended_a_worker("tests/x.py::test_other")
    assert not _timelimit.Running("run2", "gw0").ended_a_worker(
        "tests/x.py::test_stuck")  # another run's files are not read
    with live.item("tests/x.py::test_fine"):
        pass
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "tinysql-t1-run1-gw0"]  # a healthy item leaves nothing behind
    never_left.__exit__(None, None, None)
