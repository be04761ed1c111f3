"""Per-test time limit: a pytest plugin (loaded by tests/conftest.py).

Every test runs under :data:`DEFAULT_LIMIT_S` seconds, covering its
setup, call and teardown; ``@pytest.mark.time_limit(seconds)`` sets
another limit for one test.  The limit is a ``faulthandler`` timer, so it
fires even while the test sits inside a C++ call that never returns to
Python (an XLA compile, a blocking socket read): the process prints every
thread's stack to the real stderr and exits.  Under xdist the controller
reports the running test as failed by name ("worker 'gwN' crashed while
running ...") and starts a fresh worker, so the run goes on.  Without
xdist the limit ends the whole run, with the same stack printed.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os

import pytest

DEFAULT_LIMIT_S = 240.0


def _real_stderr(config):
    """A file on the process's real stderr: while a test runs under
    pytest's fd capture, fd 2 is a temp file, and a stack dump written
    there dies with the process unread."""
    capman = config.pluginmanager.getplugin("capturemanager")
    with capman.global_and_fixture_disabled() if capman else contextlib.nullcontext():
        return os.fdopen(os.dup(2), "w")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else DEFAULT_LIMIT_S
    with _real_stderr(item.config) as out:
        faulthandler.dump_traceback_later(limit, exit=True, file=out)
        try:
            yield
        finally:
            faulthandler.cancel_dump_traceback_later()
