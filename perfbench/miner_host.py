"""The one process of a run that touches jax: it runs the miner under test
(``bitcoin_miner_tpu.apps.miner.main``) in its main thread, so that it alone
holds the chip and can trace it.

    python perfbench/miner_host.py <hostport> [miner flags...]

While the miner runs, a thread reads commands on stdin and answers each
with one JSON line on stdout:

    mark              -> {"t", "lanes", "compiles"}
    trace_start DIR   -> the same, once ``jax.profiler`` traces into DIR
    trace_stop        -> the same, once the trace is written

``lanes`` is the miner's own ``sweep.device_lanes`` counter and
``compiles`` the number of backend compiles so far.  SIGTERM makes the
miner drain and return; the host then prints ``{"exit": ...,
"memory_peak_bytes": ...}`` and exits, freeing the chip.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

_compiles = [0]


def _on_event(event: str, duration: float, **_kw) -> None:
    if "backend_compile" in event:
        _compiles[0] += 1


def _state() -> dict:
    from bitcoin_miner_tpu.utils.metrics import METRICS

    return {
        "t": time.monotonic(),
        "lanes": METRICS.get("sweep.device_lanes"),
        "compiles": _compiles[0],
    }


def _reply(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _commands() -> None:
    import jax

    for line in sys.stdin:
        cmd, _, arg = line.strip().partition(" ")
        try:
            if cmd == "mark":
                _reply(_state())
            elif cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                st = _state()  # the slice starts here, as the trace does
                jax.profiler.start_trace(arg, profiler_options=opts)
                _reply(st)
            elif cmd == "trace_stop":
                st = _state()
                jax.profiler.stop_trace()
                st["t_written"] = time.monotonic()
                _reply(st)
            else:
                _reply({"error": f"unknown command {cmd!r}"})
        except Exception as e:  # reported to the harness, which fails the run
            _reply({"error": f"{cmd}: {e!r}"})


def _faulty(fault: str):
    """Wrap the miner's search so that the timed path is broken: used only
    by the benchmark's own tests, to see ``correct`` come out false."""
    from concurrent.futures import Future

    from bitcoin_miner_tpu.apps import miner

    make = miner.make_async_search

    class Faulty:
        def __init__(self, inner) -> None:
            self._inner = inner
            self.tier = getattr(inner, "tier", "cpu")
            self.devices = getattr(inner, "devices", None)

        def submit(self, data, lo, hi):
            if fault == "half":  # half of each chunk left out
                return self._inner.submit(data, lo, lo + (hi - lo) // 2)
            out = Future()  # "alter": a valid nonce of the chunk, not its min
            inner = self._inner.submit(data, lo, hi)

            def done(f):
                import hashlib

                n = lo if f.result()[1] != lo else hi
                d = hashlib.sha256(f"{data} {n}".encode()).digest()
                out.set_result((int.from_bytes(d[:8], "big"), n))

            inner.add_done_callback(done)
            return out

        def close(self) -> None:
            self._inner.close()

    miner.make_async_search = lambda *a, **k: Faulty(make(*a, **k))


def main(argv) -> int:
    import jax
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    fault = os.environ.get("PERFBENCH_FAULT")
    if fault:
        _faulty(fault)
    from bitcoin_miner_tpu.apps import miner

    threading.Thread(target=_commands, daemon=True).start()
    rc = miner.main(["miner", *argv])
    peak = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    _reply({"exit": rc, "memory_peak_bytes": peak, **_state()})
    return rc

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
