"""A time limit for every tier-1 item, from the standard library alone
(`pytest-timeout` is not installed here and nothing may be downloaded).

Two mechanisms, armed together by `limited()`:

- soft: `SIGALRM` after `seconds`.  The handler writes every thread's
  stack to fd 2 (pytest's capture attaches it to the item's report) and
  to the process's own stderr (the run's log), and raises
  `TimeLimitExceeded` into the main thread, where pytest — and
  every xdist worker — runs its tests.  `Lock.acquire`, `Event.wait`,
  `Thread.join`, `socket.recv` and `time.sleep` are all interrupted, so
  the item FAILS with the line it waited at and the run goes on.  The
  alarm repeats every `REPEAT_S` until the block is left, so a tear-down
  that waits for the same lost thread is ended too.
- hard: `faulthandler.dump_traceback_later(seconds + HARD_EXTRA_S,
  exit=True)` for a main thread stuck where no Python signal handler can
  run (a held GIL, a C call that does not return): a watchdog thread in
  C writes all stacks to the real stderr and ends the process; xdist
  reports the node down, fails the item and replaces the worker.

One limit for all items.  A test that needs more is marked `slow`.

Under `--dist loadfile` xdist hands a crashed worker's file, the item
that ended it included, to the next worker, which it would end in turn
until the restarts run out.  So a worker names the item it runs in a file
(`Running`), and an item that a dead worker's file names is failed in
set-up and not run again: a hard limit costs one worker and one item.
"""
import contextlib
import faulthandler
import glob
import os
import signal
import sys
import tempfile
import time

LIMIT_S = 120.0       # the longest healthy item is under 60 s (PERF.md §8)
HARD_EXTRA_S = 60.0   # the hard limit's distance behind the soft one
REPEAT_S = 10.0       # the soft alarm's period after its first firing


class TimeLimitExceeded(BaseException):
    """Not an `Exception`: a retry loop's `except Exception` must not
    swallow the end of its own test."""


@contextlib.contextmanager
def limited(seconds, what, hard_extra=HARD_EXTRA_S, log=2):
    """Run the block under both limits; neither is left armed after it.
    `log` (an fd) is the process's own stderr where fd 2 is captured: the
    stacks go to both, since what the handler raises can be swallowed (a
    finalizer's wait, an `except BaseException`) and the item pass.
    Yields the list of the alarm's firings."""
    fired = []

    def on_alarm(signum, frame):
        if not fired:
            for fd in {2, log}:
                os.write(fd, f"\n{what}: still running after {seconds:g} s\n"
                         .encode())
                faulthandler.dump_traceback(file=fd, all_threads=True)
        fired.append(signum)
        raise TimeLimitExceeded(
            f"{what}: still running after {seconds:g} s "
            "(every thread's stack is on stderr)")

    old = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(seconds + hard_extra, exit=True,
                                      file=log)
    signal.setitimer(signal.ITIMER_REAL, seconds, REPEAT_S)
    try:
        yield fired
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, old)


class Running:
    """One xdist worker's record of the item it is running: a file in
    `$TMPDIR` named for the run and the worker, there while an item runs
    and left behind only by a worker that died."""

    def __init__(self, run_id, worker):
        self.prefix = os.path.join(tempfile.gettempdir(),
                                   f"tinysql-t1-{run_id}-")
        self.own = self.prefix + worker

    @contextlib.contextmanager
    def item(self, nodeid):
        with open(self.own, "w") as f:
            f.write(nodeid)
        try:
            yield
        finally:
            os.unlink(self.own)

    def ended_a_worker(self, nodeid):
        for path in glob.glob(glob.escape(self.prefix) + "*"):
            if path == self.own:
                continue
            try:
                with open(path) as f:
                    if f.read() == nodeid:
                        return True
            except FileNotFoundError:
                pass  # a live worker's: its item ended since the listing
        return False


def join(thread, timeout=30.0):
    """`Thread.join` that cannot wait for ever, and says when it gave up
    (`join` returns None either way)."""
    thread.join(timeout)
    assert not thread.is_alive(), \
        f"thread {thread.name} still running after {timeout:g} s"


def until(pred, what, timeout=10.0):
    """Poll for the state a test waits for, in place of a fixed sleep
    that stands for it: sooner on an idle machine, later on a busy one,
    and a failure that names the state when it never comes."""
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, \
            f"not within {timeout:g} s: {what}"
        time.sleep(0.002)


def hit(point):
    """Wait until some thread is INSIDE failpoint `point` (a hit is
    counted before the point's action runs): "the statement is
    mid-flight", "the worker is wedged".  `fail.reset_hits()` first."""
    from tinysql_tpu import fail
    until(lambda: fail.hits().get(point), f"failpoint {point} reached")


def in_queue(pool, n=1):
    """Wait until `n` statements wait in the pool's admission queue."""
    until(lambda: pool.snapshot()["queued"] >= n,
          f"{n} statement(s) in the admission queue")


def in_wait(thread):
    """Wait until `thread` blocks in a `threading` wait (Event,
    Condition): its live leaf frame is that wait's."""
    def leaf_is_wait():
        frame = sys._current_frames().get(thread.ident)
        return frame is not None and frame.f_code.co_name == "wait"
    until(leaf_is_wait, f"thread {thread.name} parked in a wait")
