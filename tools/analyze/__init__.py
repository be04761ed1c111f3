"""Repo-native static-analysis & sanitizer suite (``python -m tools.analyze``).

Eight passes, one exit code:

- ``lock`` — AST lock-discipline checker (``# guarded-by:`` annotations,
  the ``with``-block rule, the ``_locked``/def-line helper conventions,
  the externally-serialized-class registry).  tools/analyze/lockcheck.py
- ``wfq`` — exactly one virtual-clock WFQ implementation
  (utils/wfq.py); floor-init / tie-break reimplementations anywhere else
  fail the build.  tools/analyze/wfqcheck.py
- ``contracts`` — frozen-reference golden vectors: wire codecs, hash
  values, CLI stdout.  tools/analyze/contracts.py
- ``trace`` — JAX trace-safety lint over ops/ and parallel/ (concretize /
  branch-on-tracer / wall-clock / RNG / unhashable-static bug class).
  tools/analyze/tracecheck.py
- ``sanitize`` — the runtime race sanitizer's machinery self-test (the
  BMT_SANITIZE=1 leg lives in the test suites).  tools/analyze/sanitcheck.py
- ``metrics`` — every counter/histogram/gauge name emitted anywhere must
  appear in the documented registry block in utils/metrics.py, and vice
  versa (documented-but-never-emitted fails).  tools/analyze/metriccheck.py
- ``loop`` — asyncio loop-discipline lint: blocking primitives in
  coroutines / ``# on-loop:`` code, sync locks on the loop, off-thread
  writes bypassing the ``call_soon_threadsafe`` hop.
  tools/analyze/loopcheck.py
- ``thread`` — thread-lifecycle sanitizer: every ``threading.Thread``
  construction joined on its class's close()/stop()/shutdown() path or
  annotated ``# thread-owner:``.  tools/analyze/threadcheck.py

Grandfathered findings live in tools/analyze/ratchet.json and may only
shrink.  See README "Static analysis & sanitizers".
"""

from __future__ import annotations

from .common import Finding, apply_ratchet, load_ratchet, save_ratchet  # noqa: F401
from . import (  # noqa: F401
    contracts,
    lockcheck,
    loopcheck,
    metriccheck,
    sanitcheck,
    threadcheck,
    tracecheck,
    wfqcheck,
)

PASSES = {
    "lock": lockcheck.run,
    "wfq": wfqcheck.run,
    "contracts": contracts.run,
    "trace": tracecheck.run,
    "sanitize": sanitcheck.run,
    "metrics": metriccheck.run,
    "loop": loopcheck.run,
    "thread": threadcheck.run,
}
