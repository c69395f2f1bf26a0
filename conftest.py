"""Suite-wide hooks for ``tests/`` and ``bench/tests/``.

The per-test time limit is the ``timeout`` key of ``pytest.ini``. Where
the pytest-timeout plugin is installed it reads that key and this file
adds nothing. Without the plugin, the hooks below give the same key its
meaning: a test still running at the limit fails with a clear message
(SIGALRM, delivered to the main thread, where pytest and each xdist
worker run their tests) and the stacks of every thread are dumped. A test
blocked inside native code fails as soon as control returns to Python;
its stacks are dumped at the limit either way.
"""
import faulthandler
import signal
import sys

import pytest

try:
    import pytest_timeout  # noqa: F401
except ImportError:
    pytest_timeout = None


if pytest_timeout is None:
    def pytest_addoption(parser):
        parser.addini("timeout", "per-test time limit in seconds (0: none)",
                      default="0")

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        limit = float(item.config.getini("timeout") or 0)
        if limit <= 0 or not hasattr(signal, "SIGALRM"):
            yield
            return

        def expire(signum, frame):
            pytest.fail(f"Timeout: the test ran past its {limit:g} s limit "
                        f"(pytest.ini 'timeout')")

        previous = signal.signal(signal.SIGALRM, expire)
        faulthandler.dump_traceback_later(limit, file=sys.__stderr__)
        signal.setitimer(signal.ITIMER_REAL, limit)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            faulthandler.cancel_dump_traceback_later()
            signal.signal(signal.SIGALRM, previous)
