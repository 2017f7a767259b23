"""A time limit for one test phase, with the stacks of what it waits on.

``time_limit(seconds)`` arms ``SIGALRM`` for ``seconds``.  On expiry the
handler writes every thread's stack and the stack of every pending task
of the running asyncio loop, then raises :class:`TimeLimitExceeded` in
the main thread: out of ``selectors.select`` when the loop idles on a
future that never resolves, or into whatever Python code runs.

A test whose clean-up swallows that failure (``asyncio.run`` cancelling
tasks that never finish, an executor waiting on a stuck thread) is
ended by a backstop, ``faulthandler.dump_traceback_later(seconds +
GRACE, exit=True)``: the process exits with the stacks written, so a
pytest-xdist worker is replaced and the rest of the suite goes on.

Limits nest: leaving an inner one re-arms the outer one's remainder.
Only the main thread may take a limit; elsewhere it is a no-op.
"""

from __future__ import annotations

import asyncio
import contextlib
import faulthandler
import signal
import sys
import threading
import time

import pytest

GRACE = 60.0
"""Seconds past the limit before the backstop ends the process."""


class TimeLimitExceeded(pytest.fail.Exception):
    """A test phase outlived its time limit."""


_armed: list[tuple[float, object]] = []  # (monotonic deadline, file)


def dump_stacks(file) -> None:
    """Write every thread's stack, then every pending task's, to ``file``."""
    file.flush()
    faulthandler.dump_traceback(file=file, all_threads=True)
    loop = asyncio._get_running_loop()
    if loop is None:
        return
    tasks = [t for t in asyncio.all_tasks(loop) if not t.done()]
    print(f"\n{len(tasks)} pending asyncio task(s), outermost call "
          "first:", file=file)
    for task in tasks:
        print(f"-- {task!r}", file=file)
        for line in await_chain(task.get_coro()):
            print(line, file=file)
    file.flush()


def await_chain(coro) -> list[str]:
    """Each frame of a suspended coroutine's chain of awaits, outermost
    first (``Task.print_stack`` shows only the outermost one); the
    task's repr names the future the innermost frame waits on."""
    lines = []
    while coro is not None:
        frame = getattr(coro, "cr_frame", None) or getattr(
            coro, "gi_frame", None)
        if frame is None:  # a future's iterator, or a finished coroutine
            break
        code = frame.f_code
        lines.append(f'  File "{code.co_filename}", line {frame.f_lineno}, '
                     f"in {code.co_qualname}")
        coro = getattr(coro, "cr_await", None) or getattr(
            coro, "gi_yieldfrom", None)
    return lines


def _arm_backstop(remaining: float, file) -> None:
    faulthandler.dump_traceback_later(remaining + GRACE, exit=True, file=file)


@contextlib.contextmanager
def time_limit(seconds: float, file=None):
    """Fail the enclosed code if it runs longer than ``seconds``.

    The stacks go to ``file`` (``sys.stderr`` by default; it needs a
    real file descriptor, for faulthandler).
    """
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    out = sys.stderr if file is None else file
    deadline = time.monotonic() + seconds

    def on_alarm(signum, frame):
        print(f"\n*** time limit of {seconds:g} s exceeded; stacks:",
              file=out)
        dump_stacks(out)
        raise TimeLimitExceeded(
            f"test exceeded its time limit of {seconds:g} s"
        )

    previous = signal.signal(signal.SIGALRM, on_alarm)
    entry = (deadline, out)
    _armed.append(entry)
    _arm_backstop(seconds, out)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        _armed.remove(entry)
        signal.signal(signal.SIGALRM,
                      signal.SIG_DFL if previous is None else previous)
        if _armed:
            # an outer limit is still armed: give it back its remainder
            outer_deadline, outer_out = min(_armed, key=lambda e: e[0])
            remaining = max(outer_deadline - time.monotonic(), 1e-3)
            _arm_backstop(remaining, outer_out)
            signal.setitimer(signal.ITIMER_REAL, remaining)
