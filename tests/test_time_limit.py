"""The per-test time limit (tests/_time_limit.py) that conftest.py arms
around every test phase: a stalled test fails at its limit with every
thread's stack and every pending asyncio task's await chain, and the
suite goes on."""

import asyncio
import signal
import time

import pytest

from tests._time_limit import TimeLimitExceeded, time_limit


async def _never_answered():
    await asyncio.get_running_loop().create_future()


def _stall(seconds: float, out) -> float:
    """Run a coroutine that never finishes under a limit; the seconds
    it took to fail."""

    async def main():
        await asyncio.gather(_never_answered(), asyncio.sleep(60))

    t0 = time.monotonic()
    with pytest.raises(TimeLimitExceeded, match="time limit of 0.5 s"):
        with time_limit(seconds, file=out):
            asyncio.run(main())
    return time.monotonic() - t0


def test_a_stalled_coroutine_fails_within_the_limit(tmp_path):
    with open(tmp_path / "stacks.txt", "w") as out:
        took = _stall(0.5, out)
    assert 0.5 <= took < 1.5


def test_the_dump_names_the_pending_task_and_its_await(tmp_path):
    path = tmp_path / "stacks.txt"
    with open(path, "w") as out:
        _stall(0.5, out)
    dump = path.read_text()
    assert "time limit of 0.5 s exceeded" in dump
    assert "Current thread" in dump  # faulthandler's thread stacks
    assert "pending asyncio task" in dump
    assert "coro=<_never_answered()" in dump
    assert "in _never_answered" in dump  # the frame that awaits


def test_the_previous_sigalrm_handler_is_restored(tmp_path):
    def mine(signum, frame):
        pass

    before = signal.signal(signal.SIGALRM, mine)
    try:
        with open(tmp_path / "stacks.txt", "w") as out:
            with time_limit(5.0, file=out):
                assert signal.getsignal(signal.SIGALRM) is not mine
        assert signal.getsignal(signal.SIGALRM) is mine
    finally:
        signal.signal(signal.SIGALRM, before)
