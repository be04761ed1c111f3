"""The miner worker binary: Join, then Request→sweep→Result forever.

CLI parity with the reference stub (``bitcoin/miner/miner.go:18-24``):
``miner <hostport>``; the reference's intended loop (SURVEY §3.6) is
implemented with the hash search running on one of three backends:

- ``pallas``  — the VMEM-resident TPU kernel (default on TPU)
- ``xla``     — fused jnp tier (default elsewhere; also runs on CPU/GPU)
- ``cpu``     — single-process CPU loop, bit-identical to the Go reference
  miner's hot loop; compiled C++ w/ SHA-NI when available (native/),
  hashlib otherwise.  Exists so heterogeneous fleets (Go-like CPU miners +
  TPU miners) exercise the same scheduler path (BASELINE.json config 3)

``--devices N`` spans the sweep over an N-chip mesh via shard_map +
collective min (parallel/sweep.py); the process still presents one worker
to the scheduler — multi-chip is invisible at the protocol boundary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Callable, Optional, Tuple

from .. import lsp
from ..bitcoin.hash import min_hash_range
from ..bitcoin.message import Message, MsgType
from ..utils import trace
from ..utils.metrics import METRICS

SearchFn = Callable[[str, int, int], Tuple[int, int]]  # -> (hash, nonce)


def _is_default(workload) -> bool:
    """True when ``workload`` is the frozen mining default (or unset) —
    those ride the original, byte-identical factory code below; every
    other registered workload builds from its own tier factories.  The
    contract itself lives in workloads.resolve_nondefault (lazy import:
    the default path must not pull the registry in at module import)."""
    if workload is None:
        return True
    from ..workloads import resolve_nondefault

    return resolve_nondefault(workload) is None


def _resolve_tier(backend: str, workload, devices: Optional[int] = None) -> str:
    """Map the miner's ``--backend`` vocabulary onto a workload's tier
    ladder: ``auto`` picks the strongest tier this host can actually run
    (pallas only on TPU; a CPU mesh test rig gets the sharded xla tier),
    a named tier must exist on the ladder."""
    tiers = workload.tiers
    if backend == "auto":
        from ..utils.platform import is_tpu

        if is_tpu() and "pallas" in tiers:
            return "pallas"
        if devices is not None and devices != 1 and "xla" in tiers:
            return "xla"  # CPU mesh (tests): sharded xla pipeline
        return "cpu" if "cpu" in tiers else tiers[-1]
    if backend in tiers:
        return backend
    raise ValueError(
        f"workload {workload.name!r} has no {backend!r} tier "
        f"(ladder: {'->'.join(tiers)})"
    )


def _time_chunk(fut, lo: int, hi: int) -> None:
    """Attach miner-side chunk timing to a search future: submit→solve
    wall time into ``hist.miner_chunk_s`` plus a trace event when armed —
    the miner half of the per-request timeline (the scheduler only sees
    the round trip including the wire)."""
    import time as _time

    t0 = _time.monotonic()

    def _done(f) -> None:
        if f.cancelled() or f.exception() is not None:
            return
        dt = _time.monotonic() - t0
        METRICS.observe("hist.miner_chunk_s", dt)
        if trace.enabled():
            trace.emit(
                None, "miner", "chunk_done", lo=lo, hi=hi, dt=round(dt, 6)
            )

    fut.add_done_callback(_done)


def make_search(
    backend: str = "auto", devices: Optional[int] = None, workload=None
) -> SearchFn:
    """Build the (data, lower, upper) -> (min_hash, nonce) search function.

    ``workload`` (ISSUE 9) selects a registered range-fold workload; the
    search is then built from that workload's own tier factories.  None
    (or the frozen default) keeps the pre-registry code path
    byte-identical."""
    if workload is not None and not _is_default(workload):
        tier = _resolve_tier(backend, workload, devices)
        return workload.make_search(tier, devices)
    if backend == "cpu":
        if devices is not None and devices != 1:
            raise ValueError(
                "--devices requires a JAX backend (xla/pallas); "
                "--backend cpu is the single-process CPU loop"
            )
        from .. import native

        # Compiled C++ sweep (SHA-NI when the CPU has it, all cores) — the
        # analogue of the Go reference riding stdlib assembly SHA-256;
        # hashlib fallback.
        if native.available():
            return native.min_hash_range_native
        return min_hash_range
    if backend == "auto":
        if devices in (None, 1):
            # Best single-device tier: pallas on TPU; on a CPU-only host the
            # compiled multi-core sweep beats jnp-on-CPU by ~25x.
            from ..utils.platform import is_tpu

            if not is_tpu():
                return make_search("cpu")
        backend = None  # let the ops layer pick pallas-on-TPU / xla elsewhere

    # JAX tiers: persistent compile cache so miner restarts skip the first
    # compile per shape class.
    from ..utils.platform import enable_compile_cache

    enable_compile_cache()
    if devices is not None and devices != 1:
        if devices < 1:
            raise ValueError(f"--devices must be >= 1, got {devices}")
        from ..parallel import default_mesh, sweep_min_hash_sharded

        mesh = default_mesh(devices)

        def search(data: str, lower: int, upper: int) -> Tuple[int, int]:
            r = sweep_min_hash_sharded(data, lower, upper, mesh=mesh, backend=backend)
            return r.hash, r.nonce

        return search

    from ..ops.sweep import sweep_min_hash

    def search(data: str, lower: int, upper: int) -> Tuple[int, int]:
        r = sweep_min_hash(data, lower, upper, backend=backend)
        return r.hash, r.nonce

    return search


class _PoolSearch:
    """Async facade over a blocking search fn: one worker thread, so
    completion order == submission order (the scheduler matches FIFO).
    Used for the cpu/native tier, the sharded mesh search, and plain
    callables handed to :func:`run_miner` by tests.  ``tier`` names the
    host tier ``fn`` runs (the miner's startup line reports it)."""

    def __init__(self, fn: SearchFn, tier: str = "cpu") -> None:
        from concurrent.futures import ThreadPoolExecutor

        self.tier = tier
        self._fn = fn
        self._pool = ThreadPoolExecutor(max_workers=1)

    def submit(self, data: str, lower: int, upper: int):
        return self._pool.submit(self._fn, data, lower, upper)

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


class _PipelineSearch:
    """Async facade over :class:`ops.sweep.SweepPipeline` (the JAX tiers):
    dispatches of the NEXT chunk enqueue on the device while the current
    chunk computes, so back-to-back Requests cost zero device idle."""

    def __init__(
        self,
        backend: Optional[str],
        devices: Optional[int] = None,
        workload=None,
    ) -> None:
        from concurrent.futures import Future

        from ..ops.sweep import SweepPipeline

        mesh = None
        if devices is not None and devices != 1:
            from ..parallel import default_mesh

            mesh = default_mesh(devices)
        self._Future = Future
        self._p = SweepPipeline(backend=backend, mesh=mesh, workload=workload)
        self.tier = self._p.backend
        self.devices = self._p.devices

    def submit(self, data: str, lower: int, upper: int):
        out = self._Future()

        def _done(src) -> None:
            e = src.exception()
            if e is not None:
                out.set_exception(e)
            else:
                r = src.result()
                out.set_result((r.hash, r.nonce))

        self._p.submit(data, lower, upper).add_done_callback(_done)
        return out

    def prewarm(self, data: str, upper: int) -> None:
        """Speculatively warm the digit class one past this assignment's
        upper bound so crossing a digit boundary never stalls the sweep
        on a class's first use in the process (SweepPipeline.prewarm_async
        has the measured costs)."""
        self._p.prewarm_async(data, len(str(upper)) + 1)

    def close(self) -> None:
        self._p.close()


def make_async_search(
    backend: str = "auto", devices: Optional[int] = None, workload=None,
):
    """Build the async (submit -> Future of (hash, nonce)) search the miner
    serves Requests with.  JAX tiers get the cross-request SweepPipeline —
    single-device or mesh-sharded (a multi-chip miner must not idle its
    whole mesh between chunks); only the cpu tier runs behind a
    single-worker pool (FIFO, compute-bound anyway).  ``workload``: see
    :func:`make_search`."""
    if workload is not None and not _is_default(workload):
        tier = _resolve_tier(backend, workload, devices)
        return workload.make_async_search(tier, devices)
    multi = devices is not None and devices != 1
    if devices is not None and devices < 1:
        raise ValueError(f"--devices must be >= 1, got {devices}")
    if backend == "cpu":
        # make_search owns the cpu+mesh rejection (single-sourced message).
        return _PoolSearch(make_search("cpu", devices))
    if backend == "auto":
        from ..utils.platform import is_tpu

        if not is_tpu():
            if not multi:
                return _PoolSearch(make_search("cpu"))
            backend = "xla"  # CPU mesh (tests): sharded xla pipeline
        else:
            backend = None  # ops layer picks pallas-on-TPU
    from ..utils.platform import enable_compile_cache

    enable_compile_cache()
    return _PipelineSearch(backend, devices=devices)


def run_miner(
    client: "lsp.Client",
    search,
    close_search: bool = True,
    drain: Optional["threading.Event"] = None,
) -> bool:
    """Join and serve Requests until the server connection dies (the
    reference miner's intended lifetime: exit on server loss).
    ``close_search=False`` keeps an externally-owned async search alive
    across calls — the reconnect loop (:func:`run_miner_resilient`) reuses
    one search (and its warm compiles) over many connections.
    Returns True if the exit was a (reconnect-worthy) connection loss,
    False if the search backend itself failed — a broken backend must stop
    the miner, not send it into a join/fail/reconnect churn.

    ``drain`` (ISSUE 18, the autoscaler's clean scale-down): once set,
    the loop finishes every chunk ALREADY RECEIVED, writes their Results,
    and returns — nothing accepted is abandoned, so the only chunks the
    scheduler re-assigns are ones this miner never delivered, and a
    resumed job sweeps strictly fewer nonces than after a kill.  The
    miner binary arms this from its SIGTERM handler.

    ``search`` is either a plain ``(data, lo, hi) -> (hash, nonce)``
    callable (wrapped in a one-worker pool) or an async object with
    ``submit(data, lo, hi) -> Future`` (see :func:`make_async_search`).
    Requests are read by a dedicated thread and submitted immediately;
    Results are written in submission (FIFO) order, matching the
    scheduler's pipelined FIFO accounting.  Why: every synchronous sweep
    pays a dispatch+fetch latency, so with the scheduler's 2-deep
    assignment window the NEXT chunk's dispatches must enqueue while the
    current chunk computes — a serialized request loop capped the fleet at
    ~25% of kernel rate (an older remote-runtime run, before PR 1; not
    comparable with a locally attached chip).
    """
    import queue as _queue
    import threading

    owned = not hasattr(search, "submit")
    asearch = _PoolSearch(search) if owned else search
    client.write(Message.join().marshal())
    inflight: "_queue.Queue" = _queue.Queue()
    _SEARCH_FAILED = object()  # dispatch-time backend failure sentinel

    def reader() -> None:
        while True:
            try:
                payload = client.read()
            except lsp.LspError:
                inflight.put(None)  # server lost/closed → drain and exit
                return
            msg = Message.unmarshal(payload)
            if msg is None or msg.type != MsgType.REQUEST:
                continue
            try:
                fut = asearch.submit(msg.data, msg.lower, msg.upper)
                _time_chunk(fut, msg.lower, msg.upper)
                inflight.put((fut, msg))
                prewarm = getattr(asearch, "prewarm", None)
                if prewarm is not None:
                    prewarm(msg.data, msg.upper)
            except Exception as e:
                # Dispatch-time backend failure (or the search closing
                # under a shutdown race): surface it as a SEARCH failure,
                # not a conn loss — the resilient loop must not reconnect-
                # churn a live server over a broken backend.
                inflight.put((_SEARCH_FAILED, e))
                return

    t = threading.Thread(target=reader, name="miner-reader", daemon=True)
    t.start()
    try:
        while True:
            if drain is None:
                item = inflight.get()
            elif drain.is_set():
                try:
                    # Drain mode: serve out whatever the reader already
                    # queued; an EMPTY queue means every received chunk's
                    # Result is written — exit, leaving the reader (daemon,
                    # parked in read()) to die with the conn/process.
                    item = inflight.get_nowait()
                except _queue.Empty:
                    trace.emit(None, "miner", "drained")
                    return True
            else:
                try:
                    # Armed but not signalled: poll so a SIGTERM between
                    # chunks is noticed without a Request arriving.
                    item = inflight.get(timeout=0.25)
                except _queue.Empty:
                    continue
            if item is None:
                return True
            fut, msg = item
            if fut is _SEARCH_FAILED:
                print(f"miner: search failed: {msg!r}", file=sys.stderr)
                return False
            try:
                h, n = fut.result()
            except Exception as e:
                # A broken backend (e.g. pallas without a TPU) must not dump
                # a traceback mid-protocol; exit cleanly so the server
                # reassigns.
                print(f"miner: search failed: {e!r}", file=sys.stderr)
                return False
            METRICS.inc("miner.nonces", msg.upper - msg.lower + 1)
            try:
                client.write(Message.result(h, n).marshal())
            except lsp.LspError:
                return True
    finally:
        # Don't block on an in-flight sweep (it may be wedged — that's why
        # we're exiting); daemon threads are reaped with the process.
        if owned or close_search:
            asearch.close()


def run_miner_resilient(
    host: str,
    port: int,
    search,
    params: Optional["lsp.Params"] = None,
    *,
    max_retries: int = 5,
    backoff_base: float = 0.25,
    backoff_cap: float = 8.0,
    label: Optional[str] = None,
    first_client: Optional["lsp.Client"] = None,
    stop: Optional["threading.Event"] = None,
    drain: Optional["threading.Event"] = None,
    sleep=None,
) -> None:
    """Self-healing miner lifetime: Join/serve until the server connection
    dies, then reconnect with exponential backoff and re-Join on a fresh
    conn, abandoning any stale in-flight chunk (the scheduler's dead-miner
    reassignment already re-queued it server-side; our late Result would be
    FIFO-mismatched on a new conn anyway, so it is simply never written).

    ``max_retries`` bounds *consecutive* failed connect attempts — any
    successful reconnect resets the budget, so a miner rides out repeated
    transient partitions but still exits once the server is gone for good.
    ``stop`` (an Event) ends the lifetime at the next reconnect decision —
    harnesses use it so torn-down fleets don't leave reconnect loops
    dialing a dead port.  ``drain`` is the clean scale-down signal
    forwarded into :func:`run_miner` — once set, the current connection
    finishes its received chunks and the lifetime ends (no reconnect).
    One async ``search`` (and its warm kernel compiles) is reused across
    connections; plain callables are wrapped once.
    """
    import time as _time

    from ..utils.retry import backoff_delay

    sleep = _time.sleep if sleep is None else sleep
    asearch = _PoolSearch(search) if not hasattr(search, "submit") else search
    client = first_client
    connected_before = client is not None
    failures = 0

    def pause(delay: float) -> bool:
        """Back off; True if a stop was requested meanwhile."""
        if stop is not None:
            return stop.wait(delay)
        sleep(delay)
        return False

    try:
        while not (stop is not None and stop.is_set()):
            if client is None:
                try:
                    client = lsp.Client(host, port, params, label=label)
                except (lsp.LspError, OSError):
                    failures += 1
                    if failures > max_retries:
                        trace.emit(
                            None, "miner", "gave_up",
                            label=label, attempts=failures,
                        )
                        print(
                            f"miner: giving up after {max_retries} reconnect "
                            "attempts", file=sys.stderr,
                        )
                        return
                    if pause(backoff_delay(failures, backoff_base, backoff_cap)):
                        return
                    continue
                if connected_before:
                    METRICS.inc("miner.reconnects")
                    trace.emit(
                        None, "miner", "reconnect",
                        label=label, attempts=failures,
                    )
                failures = 0
            connected_before = True
            conn_lost = False
            try:
                conn_lost = run_miner(
                    client, asearch, close_search=False, drain=drain
                )
            finally:
                try:
                    client.close()
                except lsp.LspError:
                    pass
                client = None
            if drain is not None and drain.is_set():
                return  # clean drain: received work delivered; don't rejoin
            if not conn_lost:
                # The search backend failed, not the network: reconnecting
                # would just churn join/fail forever against a live server.
                return
            # Conn lost (or server closed us): retry after a beat — a dead
            # server fails the next connect and enters the backoff ladder.
            failures += 1
            if failures > max_retries:
                return
            if pause(backoff_delay(failures, backoff_base, backoff_cap)):
                return
    finally:
        asearch.close()


class _TieredSearch:
    """Watchdog-guarded fallback chain over kernel tiers.

    A wedged accelerator runtime (the failure the scheduler's straggler
    tick sees from the *outside*) hangs the miner's search future forever;
    this wrapper notices from the *inside* — any chunk exceeding the
    tier's wedge budget, or raising — abandons that tier and re-runs the
    chunk on the next one (Pallas → XLA → cpu/hashlib), so the miner
    degrades instead of stalling.  The budget escalates ``wedge_growth``×
    per downgrade: a chunk sized for a TPU tier honestly takes orders of
    magnitude longer on the fallback, and a flat budget would misread
    slow-but-healthy as wedged and cascade straight off the bottom of the
    chain.  Chunks are served FIFO by one dispatcher thread (which
    serializes tiers' sweeps — the price of wedge detection; production
    TPU fleets that want pipelining run without ``--watchdog``).
    """

    _SHUTDOWN = object()

    def __init__(
        self, tiers, wedge_seconds: float = 30.0, wedge_growth: float = 8.0
    ) -> None:
        import queue as _queue
        import threading

        from concurrent.futures import Future

        self._Future = Future
        self._chain = list(tiers)  # [(name, factory_returning_search)]
        self._idx = 0
        self._active = None
        self._active_name: Optional[str] = None
        self._wedge = wedge_seconds
        self._growth = wedge_growth
        self._downgrades = 0  # real downgrades only — build-time skips of
        # unavailable tiers must not inflate the first working tier's budget
        self._closing = False
        self._jobs: "_queue.Queue" = _queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, name="tiered-search", daemon=True
        )  # thread-owner: process — close() must NOT block behind a
        # wedged tier's in-flight job; the daemon drains the shutdown
        # sentinel when the tier unwedges, or dies with the process
        self._thread.start()

    def submit(self, data: str, lower: int, upper: int):
        out = self._Future()
        self._jobs.put((data, lower, upper, out))
        return out

    def close(self) -> None:
        # Flag first: the dispatcher must see closing before the active
        # tier's futures start failing, or it would "downgrade" to a fresh
        # tier it then never closes.
        self._closing = True
        self._jobs.put(self._SHUTDOWN)
        if self._active is not None:
            try:
                self._active.close()
            except Exception:
                pass

    # ------------------------------------------------------------- internals

    @property
    def active_tier(self) -> Optional[str]:
        return self._active_name

    @property
    def tier(self) -> str:
        """The tier serving now, or the one the next chunk will build."""
        if self._active_name is not None:
            return self._active_name
        return self._chain[min(self._idx, len(self._chain) - 1)][0]

    def _tier(self):
        while self._active is None and self._idx < len(self._chain):
            name, factory = self._chain[self._idx]
            try:
                built = factory()
                if not hasattr(built, "submit"):
                    built = _PoolSearch(built)
                self._active, self._active_name = built, name
            except Exception as e:
                print(
                    f"miner: tier {name!r} unavailable ({e!r}); skipping",
                    file=sys.stderr,
                )
                self._idx += 1
        return self._active

    def _downgrade(self, why: str) -> None:
        import threading

        METRICS.inc("miner.tier_downgrades")
        self._downgrades += 1
        # Trace the WHY (ISSUE 6): a chaos soak's trace shows which tier
        # was abandoned and for what reason, not just a counter bump.
        trace.emit(
            None, "miner", "tier_downgrade",
            tier=self._active_name, why=why, downgrades=self._downgrades,
        )
        print(
            f"miner: tier {self._active_name!r} {why}; downgrading",
            file=sys.stderr,
        )
        dead = self._active
        self._active, self._active_name = None, None
        self._idx += 1
        if dead is not None:
            # close() may block on the wedged runtime — do it off to the side.
            threading.Thread(
                target=lambda: _swallow(dead.close), daemon=True
            ).start()

    def _loop(self) -> None:
        from concurrent.futures import TimeoutError as _FutTimeout

        while True:
            item = self._jobs.get()
            if item is self._SHUTDOWN:
                return
            data, lo, hi, out = item
            while True:
                if self._closing:
                    out.set_exception(RuntimeError("search closed"))
                    break
                tier = self._tier()
                if tier is None:
                    out.set_exception(
                        RuntimeError("all search tiers wedged or failed")
                    )
                    break
                budget = self._wedge * (self._growth ** self._downgrades)
                try:
                    res = tier.submit(data, lo, hi).result(timeout=budget)
                    out.set_result(res)
                    break
                except _FutTimeout:
                    if self._closing:
                        out.set_exception(RuntimeError("search closed"))
                        break
                    trace.emit(
                        None, "miner", "wedge_detected",
                        tier=self._active_name, budget_s=budget,
                        lo=lo, hi=hi,
                    )
                    self._downgrade(f"wedged (> {budget:g}s/chunk)")
                except Exception as e:
                    if self._closing:
                        out.set_exception(RuntimeError("search closed"))
                        break
                    self._downgrade(f"failed ({e!r})")


def _swallow(fn) -> None:
    try:
        fn()
    except Exception:
        pass


def make_tiered_search(
    backend: str = "auto",
    devices: Optional[int] = None,
    wedge_seconds: float = 30.0,
    workload=None,
) -> _TieredSearch:
    """The self-healing search: the requested tier first, every strictly
    weaker tier behind it, hashlib last (pure Python cannot wedge).

    The chain is the workload's OWN tier ladder (ISSUE 9): a workload
    with no device kernels still downgrades sanely (e.g. blake2b64's
    cpu → hashlib), and a SHA-256-template workload rides the full
    pallas → xla → cpu → hashlib ladder like the frozen default."""
    if workload is not None and not _is_default(workload):
        tiers = list(workload.tiers)
        backend = _resolve_tier(backend, workload, devices)
        chain = [
            (
                t,
                lambda t=t: workload.make_async_search(
                    t, devices if t in ("pallas", "xla") else None
                ),
            )
            for t in tiers[tiers.index(backend):]
        ]
        return _TieredSearch(chain, wedge_seconds=wedge_seconds)
    from ..bitcoin.hash import min_hash_range as _oracle

    if backend == "auto":
        from ..utils.platform import is_tpu

        backend = "pallas" if is_tpu() else "cpu"
    chain = []
    if backend == "pallas":
        chain.append(("pallas", lambda: make_async_search("pallas", devices)))
    if backend in ("pallas", "xla"):
        chain.append(("xla", lambda: make_async_search("xla", devices)))
    chain.append(("cpu", lambda: _PoolSearch(make_search("cpu"))))
    chain.append(("hashlib", lambda: _PoolSearch(_oracle, "hashlib")))
    return _TieredSearch(chain, wedge_seconds=wedge_seconds)


def resolved(search, devices: Optional[int] = None) -> dict:
    """What the miner resolved: its tier and the devices that tier runs on.

    A host tier (cpu, hashlib) touches no jax backend, so on a chip host it
    leaves the chip to the process that needs it; it reports no device."""
    tier = getattr(search, "tier", "cpu")
    if tier in ("cpu", "hashlib"):
        return {
            "tier": tier, "platform": "host", "device_kind": None,
            "device_count": 0, "mesh": 0, "mesh_device_ids": [],
        }
    import jax

    everything = jax.devices()
    used = getattr(search, "devices", None) or everything[: devices or 1]
    return {
        "tier": tier,
        "platform": used[0].platform,
        "device_kind": used[0].device_kind,
        "device_count": len(everything),
        "mesh": len(used),
        "mesh_device_ids": [d.id for d in used],
    }


def serve_multihost(client, sweep: SearchFn, broadcast) -> None:
    """The primary/secondary Request loop of a multi-host logical miner.

    ``client`` is the primary host's LSP connection (None on secondaries);
    ``sweep(data, lower, upper) -> (hash, nonce)`` is the collective sweep
    every host executes in lockstep; ``broadcast(buf) -> buf`` is the
    host-0-to-all collective.  Factored out of :func:`run_miner_multihost`
    (which supplies the real jax.distributed wiring) so the protocol logic
    is unit-testable on one host.
    """
    from ..parallel.multihost import (
        decode_request,
        encode_request,
        encode_shutdown,
    )

    while True:
        # host 0 reads the next Request; everyone gets it via broadcast.
        buf = encode_shutdown()
        if client is not None:
            msg = None
            while msg is None or msg.type != MsgType.REQUEST:
                try:
                    msg = Message.unmarshal(client.read())
                except lsp.LspError:
                    msg = None
                    break
            if msg is not None:
                try:
                    buf = encode_request(msg.data, msg.lower, msg.upper)
                except ValueError as e:
                    # Un-broadcastable Request (e.g. oversize data): refuse
                    # loudly — a truncated sweep would return a plausible
                    # but WRONG Result.  Shut the whole logical miner down;
                    # the dropped conn makes the scheduler reassign.
                    print(f"miner: rejecting request: {e}", file=sys.stderr)
        req = decode_request(broadcast(buf))
        if req is None:
            return  # scheduler gone / fatal request: all hosts exit together
        data, lower, upper = req
        h, n = sweep(data, lower, upper)
        if client is not None:
            METRICS.inc("miner.nonces", upper - lower + 1)
            try:
                client.write(Message.result(h, n).marshal())
            except lsp.LspError:
                return


def run_miner_multihost(
    hostport: str, coordinator: str, num_hosts: int, host_id: int
) -> None:
    """One logical miner spanning all hosts of a TPU pod (DCN scaling).

    Every process executes the same sharded sweep over the global mesh
    (multi-controller SPMD); only host 0 talks LSP to the scheduler and
    broadcasts each Request's parameters to the other hosts.  See
    parallel/multihost.py for when to prefer this over plain per-process
    miners.
    """
    import numpy as np
    from jax.experimental import multihost_utils

    from ..parallel import sweep_min_hash_sharded
    from ..parallel.multihost import global_mesh, initialize, is_primary

    initialize(coordinator, num_hosts, host_id)
    mesh = global_mesh()
    client = None
    if is_primary():
        host, _, port = hostport.rpartition(":")
        client = lsp.Client(host or "127.0.0.1", int(port))
        client.write(Message.join().marshal())

    def sweep(data: str, lower: int, upper: int) -> Tuple[int, int]:
        r = sweep_min_hash_sharded(data, lower, upper, mesh=mesh)
        return r.hash, r.nonce

    def broadcast(buf):
        return np.asarray(multihost_utils.broadcast_one_to_all(buf))

    serve_multihost(client, sweep, broadcast)


def main(argv=None) -> int:
    argv = sys.argv if argv is None else argv
    if len(argv) < 2:
        print(f"Usage: ./{argv[0]} <hostport>", end="")
        return 0
    parser = argparse.ArgumentParser(prog=argv[0], add_help=False)
    parser.add_argument("hostport")
    parser.add_argument(
        "--backend", choices=["auto", "pallas", "xla", "cpu"], default="auto"
    )
    parser.add_argument("--devices", type=int, default=None)
    # Self-healing knobs: --reconnect N bounds consecutive failed re-Join
    # attempts after a lost server conn (0 restores the reference's
    # exit-on-loss lifetime); --watchdog SECONDS wraps the search in the
    # kernel-tier fallback chain (pallas→xla→cpu→hashlib) with a per-chunk
    # wedge timeout.
    parser.add_argument("--reconnect", type=int, default=5)
    parser.add_argument("--watchdog", type=float, default=None)
    # Paced-capacity mode (ISSUE 18): sweep at a FIXED nonces/s (sleep-
    # dominated, not CPU-bound), so N workers on one box model N units of
    # capacity — the substrate the autoscale bench's open-loop overload
    # leg needs (tools/fleet_bench.py --autoscale stamps the pace into
    # its JSON line).  BMT_MINER_THROTTLE_NPS is the env spelling.
    parser.add_argument(
        "--throttle-nps", type=float,
        default=float(os.environ.get("BMT_MINER_THROTTLE_NPS", "0") or 0),
    )
    # Registered range-fold workload (ISSUE 9): the hash family this
    # miner sweeps.  Must match the server's --workload (the wire never
    # names workloads); BMT_WORKLOAD is the env spelling for subprocess
    # benches.  Default: the frozen mining contract.
    parser.add_argument(
        "--workload", default=os.environ.get("BMT_WORKLOAD") or None
    )
    # Telemetry sidecar (ISSUE 7): ship periodic metric snapshots to the
    # server's --telemetry-port over a SECOND LSP connection.  Entirely
    # off the sweep path (a daemon timer thread with its own conn and
    # backoff); BMT_TELEMETRY is the env spelling for subprocess benches.
    parser.add_argument(
        "--telemetry", metavar="HOSTPORT",
        default=os.environ.get("BMT_TELEMETRY") or None,
    )
    parser.add_argument("--telemetry-interval", type=float, default=2.0)
    parser.add_argument(
        "--source", default=None,
        help="telemetry source name (default miner-<pid>)",
    )
    parser.add_argument("--multihost", action="store_true")
    parser.add_argument("--coordinator", default=None)
    parser.add_argument("--num-hosts", type=int, default=None)
    parser.add_argument("--host-id", type=int, default=None)
    args = parser.parse_args(argv[1:])
    # Hermetic CPU-mesh override for driving the --devices CLI without N
    # real chips (same mechanism as dryrun_multichip).
    force_n = os.environ.get("BMT_FORCE_CPU_DEVICES")
    if force_n:
        from ..utils.platform import force_virtual_cpu

        force_virtual_cpu(int(force_n))
    if args.multihost:
        if None in (args.coordinator, args.num_hosts, args.host_id):
            print("--multihost requires --coordinator, --num-hosts, --host-id")
            return 0
        from ..workloads import resolve_nondefault

        try:
            nondefault = resolve_nondefault(args.workload)
        except ValueError as e:
            print("Invalid miner configuration:", e)
            return 0
        if nondefault is not None:
            # Lockstep pod sweep: frozen default only (for now).
            print("Invalid miner configuration:",
                  "--multihost supports the default workload only")
            return 0
        run_miner_multihost(
            args.hostport, args.coordinator, args.num_hosts, args.host_id
        )
        return 0
    try:
        from ..workloads import resolve as resolve_workload

        workload = resolve_workload(args.workload)
        if args.watchdog is not None:
            search = make_tiered_search(
                args.backend, args.devices, wedge_seconds=args.watchdog,
                workload=workload,
            )
        else:
            search = make_async_search(
                args.backend, args.devices, workload=workload
            )
    except ValueError as e:
        print("Invalid miner configuration:", e)
        return 0
    # One line that says what this process will sweep on (chip_smoke.py
    # reads it): a CPU fallback must never pass for a chip.
    print(
        "miner: resolved " + json.dumps(resolved(search, args.devices)),
        file=sys.stderr, flush=True,
    )
    import time as _time

    if args.throttle_nps and args.throttle_nps > 0:
        _paced = search
        _rate = float(args.throttle_nps)

        class _PacedSearch:
            # The sleep rides the reader thread's submit call, pacing the
            # whole pipeline at ``_rate`` without holding a core.
            def submit(self, d, lo, hi):
                _time.sleep((hi - lo + 1) / _rate)
                return _paced.submit(d, lo, hi)

            def close(self):
                _paced.close()

        search = _PacedSearch()
    if os.environ.get("BMT_MINER_LOG"):
        # Operator observability: per-chunk submit/resolve timing on stderr
        # (used by tools/fleet_bench.py --miner-log to audit fleet cadence).
        _t0 = _time.monotonic()
        _inner = search

        class _LoggedSearch:
            def submit(self, d, lo, hi):
                t = _time.monotonic() - _t0
                print(
                    f"{t:9.3f} submit [{lo},{hi}] size={hi - lo + 1:.3e}",
                    file=sys.stderr,
                    flush=True,
                )
                f = _inner.submit(d, lo, hi)
                f.add_done_callback(
                    lambda _s, lo=lo, hi=hi, t=t: print(
                        f"{_time.monotonic() - _t0:9.3f} done   [{lo},{hi}] "
                        f"dt={_time.monotonic() - _t0 - t:.3f} lanes device "
                        f"{METRICS.get('sweep.device_lanes')} host fold "
                        f"{METRICS.get('sweep.host_fold_lanes')}",
                        file=sys.stderr,
                        flush=True,
                    )
                )
                return f

            def close(self):
                _inner.close()

        search = _LoggedSearch()
    exporter = None
    if args.telemetry:
        from ..utils.telemetry import TelemetryExporter

        thost, _, tport = args.telemetry.rpartition(":")
        try:
            exporter = TelemetryExporter(
                thost or "127.0.0.1", int(tport),
                args.source or f"miner-{os.getpid()}",
                interval=args.telemetry_interval,
            ).start()
        except ValueError as e:
            print("Invalid miner configuration:", e)
            return 0
    host, _, port = args.hostport.rpartition(":")
    try:
        client = lsp.Client(host or "127.0.0.1", int(port))
    except (lsp.LspError, OSError, ValueError) as e:
        print("Failed to join with server:", e)
        return 0
    import signal
    import threading
    import time

    # Clean-drain signal (ISSUE 18): the autoscaler retires a worker with
    # SIGTERM; the handler only sets an Event — the serve loop finishes
    # every chunk already received, writes their Results, and exits 0,
    # so a drained worker's job resumes with strictly fewer nonces left
    # than after a kill.  Best-effort: installing a handler needs the
    # main thread (tests drive main() elsewhere — they keep the default).
    drain_evt = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda _s, _f: drain_evt.set())
    except ValueError:
        pass

    t0 = time.monotonic()
    try:
        if args.reconnect > 0:
            run_miner_resilient(
                host or "127.0.0.1", int(port), search,
                max_retries=args.reconnect, first_client=client,
                stop=drain_evt, drain=drain_evt,
            )
        else:
            run_miner(client, search, drain=drain_evt)
    finally:
        if exporter is not None:
            exporter.stop()
        try:
            client.close()
        except lsp.LspError:
            pass
        swept = METRICS.get("miner.nonces")
        dt = max(time.monotonic() - t0, 1e-9)
        print(
            f"miner: {swept} nonces swept ({swept / dt:,.0f}/s lifetime); "
            f"lanes on device {METRICS.get('sweep.device_lanes')}, "
            f"host fold {METRICS.get('sweep.host_fold_lanes')} in "
            f"{METRICS.get('sweep.host_fold_s'):.3f} s; kernel "
            f"export hits {METRICS.get('sweep.kernel_export_hits')}, "
            f"misses {METRICS.get('sweep.kernel_export_misses')}, build "
            f"{METRICS.gauge('sweep.kernel_build_s'):.3f} s; grid groups "
            f"{METRICS.get('sweep.grid_groups')}, skipped "
            f"{METRICS.get('sweep.grid_groups_skipped')}",
            file=sys.stderr, flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
