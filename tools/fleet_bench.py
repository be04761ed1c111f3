"""Fleet-level throughput: what the mining *application* delivers end-to-end.

`bench.py` measures the bare kernel; the capability this framework rebuilds
is the fleet (SURVEY §3.6): client → server/scheduler → LSP → miner →
kernel → min-fold → Result.  This tool stands up the real binaries on
loopback — `apps.server` and `apps.miner` as subprocesses, an in-process
LSP client — runs a big job, and reports **delivered nonces/s** next to the
kernel rate, so scheduler/transport overhead is a measured number instead
of a guess.

Jobs run in order:

- a **warm-up job** (default 4e9 nonces) that pays the one-time costs —
  TPU runtime init, the dynamic kernel's one build (persistent-cached
  across runs), and the scheduler's EWMA rate ramp from `min_chunk` to
  full-size chunks;
- **class warm-ups**: one tiny job per digit class the timed job touches
  beyond the warm-up range (same contract as bench.py: compiles precede
  the measurement window);
- the **timed job** (default 2e10 nonces), whose delivered rate is the
  steady-state fleet number the JSON line reports.  The warm-up wall time
  is reported alongside so cold-start cost stays visible;
- optionally the **kill drill** (`--kill-drill`): the same fresh-range job
  clean and with a mid-job miner SIGKILL+respawn, asserting identical
  `(hash, nonce)` — the scheduler's reassignment invariant on hardware.

`--cpu-miners N` adds N native C++ workers to the fleet (heterogeneous
scheduling under one scheduler; liveness-checked).

Fault tolerance IS the harness: a miner process that wedges at init
would hang the job forever.  The miner runs with
``BMT_MINER_LOG`` chunk-timing on; a monitor watches that log and the
process, and a miner that dies or stalls past ``--stall`` seconds is
killed and respawned — the scheduler's dead-conn reassignment then
carries the job, which is the framework's own recovery path doing the
work (miner restarts are counted in the JSON line).

Usage: python tools/fleet_bench.py [--nonces N] [--warmup N] [--backend B]
       [--kernel-rate R] [--miner-log FILE]   (prints one JSON line)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # repo root

from bitcoin_miner_tpu import workloads as workloads_mod  # noqa: E402
from bitcoin_miner_tpu.bitcoin.message import Message, MsgType  # noqa: E402
from bitcoin_miner_tpu.utils.metrics import Histogram  # noqa: E402

REPO = Path(__file__).resolve().parents[1]

#: The resolved range-fold workload (ISSUE 9).  main() resolves it
#: AFTER argparse — ``--workload`` first, env BMT_WORKLOAD second — and
#: exports the env so the server/miner/federation subprocesses serve
#: the same hash family this tool's oracle validates against.  Import
#: time pins the default only: a stale BMT_WORKLOAD must not kill
#: ``--help`` (or a valid flag) before the parser ever runs.
WORKLOAD = workloads_mod.resolve(None)

#: Request→result latency of every job this bench ran (warm-ups, class
#: warms, timed, drills) — p50/p95/p99 land in the BENCH JSON line so the
#: perf trajectory has a latency axis next to nonces/s (ISSUE 6).
LATENCY = Histogram()


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _read_last_fleet_state(path: str) -> dict:
    """Last decodable row of the server's fleet log (torn tails from a
    concurrent append are skipped)."""
    last = None
    try:
        with open(path) as f:
            for line in f:
                if not line.endswith("\n"):
                    break
                try:
                    last = json.loads(line)
                except ValueError:
                    continue
    except OSError:
        return None
    return last


def _wait_listening(proc: subprocess.Popen, timeout: float) -> None:
    import select

    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        # select before readline: a server that wedges without printing
        # anything must trip the deadline, not block this tool forever.
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if ready:
            line = proc.stdout.readline()
            if "Server listening" in line:
                return
        if proc.poll() is not None:
            break
    raise RuntimeError(f"server did not come up (last: {line!r})")


class MinerKeeper:
    """Owns the miner subprocess: spawns it, watches its chunk-timing log
    for liveness, kills + respawns on wedge/death.  ``telemetry`` is the
    server's sidecar hostport (ISSUE 7): a respawned miner re-arms its
    exporter too, so the fleet view keeps seeing the replacement."""

    def __init__(
        self, port: int, backend: str, log_path: str,
        telemetry: str = None,
    ) -> None:
        self.port = port
        self.backend = backend
        self.log_path = log_path
        self.telemetry = telemetry
        self.restarts = 0
        self.proc: subprocess.Popen = None
        self.spawn()

    def spawn(self) -> None:
        self._log_f = open(self.log_path, "ab", buffering=0)
        argv = [
            sys.executable,
            "-m",
            "bitcoin_miner_tpu.apps.miner",
            f"127.0.0.1:{self.port}",
            "--backend",
            self.backend,
        ]
        if self.telemetry:
            argv += [
                "--telemetry", self.telemetry,
                "--telemetry-interval", "1.0",
                "--source", "tpu-miner",
            ]
        self.proc = subprocess.Popen(
            argv,
            cwd=str(REPO),
            env={**os.environ, "BMT_MINER_LOG": "1"},
            stdout=subprocess.DEVNULL,
            stderr=self._log_f,
        )
        self._progress_size = -1
        self._progress_at = time.monotonic()

    def progressing(self, stall_timeout: float) -> bool:
        """True while the miner looks alive: process up and log growing
        within stall_timeout."""
        try:
            size = os.stat(self.log_path).st_size
        except OSError:
            size = 0
        now = time.monotonic()
        if size != self._progress_size:
            self._progress_size = size
            self._progress_at = now
        if self.proc.poll() is not None:
            return False
        return (now - self._progress_at) < stall_timeout

    def restart(self, reason: str = "wedged/dead") -> None:
        self.restarts += 1
        log(f"miner {reason}; restart #{self.restarts}")
        self.kill()  # reaped: its chip is free for the respawn
        self.spawn()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
        self._log_f.close()


def run_job(
    client, keeper: MinerKeeper, data: str, max_nonce: int, deadline: float,
    stall: float, lower: int = 0, kill_after: float = 0.0,
) -> dict:
    """Submit one Request; wait for the Result with the keeper watching the
    miner.  Validates the Result against the hashlib per-nonce oracle.

    ``kill_after`` > 0: SIGKILL the miner that many seconds into the job
    and respawn it — the fault-injection leg of the kill drill; the
    scheduler's dead-conn reassignment must carry the job to the same
    answer."""
    t0 = time.monotonic()
    client.write(Message.request(data, lower, max_nonce).marshal())
    box: list = []

    def _read() -> None:
        try:
            box.append(client.read())
        except BaseException as e:
            box.append(e)

    reader = threading.Thread(target=_read, daemon=True)
    reader.start()
    kill_fired = False
    while reader.is_alive():
        armed = kill_after > 0.0 and not kill_fired
        reader.join(timeout=0.5 if armed else 5.0)
        if (
            armed
            and reader.is_alive()  # a post-Result kill proves nothing
            and time.monotonic() - t0 >= kill_after
        ):
            log(f"kill drill: SIGKILL miner at t+{kill_after:.1f}s")
            keeper.restart(reason="kill drill")  # scheduler must reassign
            kill_fired = True
        if reader.is_alive():
            if time.monotonic() - t0 > deadline:
                raise RuntimeError(f"job exceeded {deadline:.0f}s deadline")
            if not keeper.progressing(stall):
                # The scheduler reassigns the dead conn's chunks once the
                # replacement joins — the job continues where it left off.
                keeper.restart()
    out = box[0]
    if isinstance(out, BaseException):
        raise out
    dt = time.monotonic() - t0
    LATENCY.observe(dt)
    msg = Message.unmarshal(out)
    assert msg is not None and msg.type == MsgType.RESULT, out
    # Full-argmin verification of a 2e10 job is beyond any CPU oracle; the
    # scheduler already hashlib-validates every chunk Result, and the
    # kernel tiers are oracle-tested.  Assert the returned pair is at
    # least a real in-range hash of the job.
    assert lower <= msg.nonce <= max_nonce, (msg.nonce, lower, max_nonce)
    assert WORKLOAD.hash_nonce(data, msg.nonce) == msg.hash, (
        msg.hash, msg.nonce, WORKLOAD.name,
    )
    return {
        "wall_s": dt,
        "hash": msg.hash,
        "nonce": msg.nonce,
        "kill_fired": kill_fired,
    }


def _wait_replica(proc: subprocess.Popen, timeout: float) -> None:
    import select

    deadline = time.monotonic() + timeout
    line = ""
    while time.monotonic() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [], 1.0)
        if ready:
            line = proc.stdout.readline()
            if "listening on port" in line:
                return
        if proc.poll() is not None:
            break
    raise RuntimeError(f"replica did not come up (last: {line!r})")


class _FedCell:
    """One subprocess replica + its cpu-miner subprocess."""

    def __init__(self, name, port, fed_port, peers_spec, tmp):
        self.name, self.port, self.fed_port = name, port, fed_port
        argv = [
            sys.executable, "-m", "bitcoin_miner_tpu.apps.federation",
            str(port), f"--cell={name}", f"--fed-port={fed_port}",
            "--gossip-interval=0.3",
        ]
        if peers_spec:
            argv.append(f"--peers={peers_spec}")
        self.proc = subprocess.Popen(
            argv,
            cwd=tmp,
            env={**os.environ, "PYTHONPATH": str(REPO)},
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        _wait_replica(self.proc, 30)
        self._mlog = open(os.path.join(tmp, f"miner.{name}.log"), "wb")
        self.miner = subprocess.Popen(
            [
                sys.executable, "-m", "bitcoin_miner_tpu.apps.miner",
                f"127.0.0.1:{port}", "--backend", "cpu",
            ],
            cwd=str(REPO),
            stdout=subprocess.DEVNULL,
            stderr=self._mlog,
        )

    def alive(self):
        return self.proc.poll() is None

    def kill(self, miner_only=False):
        if self.miner.poll() is None:
            self.miner.send_signal(signal.SIGKILL)
        if not miner_only and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)


def _fed_request_once(port, data, lo, hi, deadline_s=30.0):
    """One wire request with a hard deadline (a minerless cell that would
    have to sweep hangs instead of answering — the black-box zero-work
    discriminator the probes rely on)."""
    from bitcoin_miner_tpu import lsp

    try:
        c = lsp.Client("127.0.0.1", port)
    except (lsp.LspError, OSError):
        return None
    got = None
    try:
        c.write(Message.request(data, lo, hi).marshal())
        box: list = []

        def _read() -> None:
            try:
                box.append(c.read())
            except BaseException as e:
                box.append(e)

        rt = threading.Thread(target=_read, daemon=True)
        rt.start()
        rt.join(timeout=deadline_s)
        if box and not isinstance(box[0], BaseException):
            m = Message.unmarshal(box[0])
            if m is not None and m.type == MsgType.RESULT:
                got = (m.hash, m.nonce)
    finally:
        try:
            c.close()
        except lsp.LspError:
            pass
    return got


def _fed_batch(cells, jobs, oracle, clients=6, deadline_s=120.0,
               on_index=None):
    """Spray the jobs across the cells' public ports from ``clients``
    worker threads (round-robin start + failover to the next live cell),
    validating every Result against the oracle.  Returns wall seconds."""
    errors: list = []
    cursor = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                if cursor[0] >= len(jobs) or errors:
                    return
                i = cursor[0]
                cursor[0] += 1
            if on_index is not None:
                on_index(i)
            data, lo, hi = jobs[i]
            got = None
            live = [c for c in cells if c.alive()]
            for k in range(len(live)):
                cell = live[(i + k) % len(live)]
                got = _fed_request_once(cell.port, data, lo, hi, deadline_s)
                if got is not None:
                    break
            if got != oracle[(data, lo, hi)]:
                errors.append(f"job {i} ({data},{lo},{hi}): got {got}")

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=deadline_s * len(jobs))
    wall = time.monotonic() - t0
    if errors:
        raise RuntimeError("federation batch failed: " + "; ".join(errors[:5]))
    return wall


def run_federation_bench(args) -> int:
    """The federation leg (ISSUE 8), all real subprocesses — N
    ``apps.federation`` replicas each with its own cpu-miner process, so
    (unlike an in-process harness) the cells genuinely compute in
    parallel:

    1. a timed sweep-bound duplicate-heavy batch across N replicas, then
       the SAME batch on a fresh 1-replica federation — the 1→N jobs/s
       scaling number (BENCH_pr8.json);
    2. an untimed drill batch with one whole cell SIGKILLed mid-batch:
       every Result still oracle-bit-exact through the survivors;
    3. black-box zero-work probes: every miner is killed, then a repeat
       of a solved signature must answer at EVERY live replica (cache via
       routing) and a never-issued covered sub-range must answer at a
       NON-home replica's federation port (gossiped spans) — a cell that
       had to sweep would hang its minerless scheduler past the deadline.

    Prints one JSON line."""
    import random

    from bitcoin_miner_tpu.federation.drill import run_all as run_fed_drills
    from bitcoin_miner_tpu.federation.ring import Ring

    min_hash_range = WORKLOAD.min_range

    # Resilience legs first (ISSUE 12), in-process so counters are
    # readable: shed-storm (zero false-death markings), drain-handoff
    # (successor resumes from stashed progress, strictly fewer nonces
    # swept than from-scratch), death-detect (SIGKILL-shaped silence
    # declared dead with no forward-path connect timeout spent), and
    # ack-retransmit (partition heals via ack-gap retransmit before any
    # full sync).  Each failing drill replays from its seed with
    # `python tools/chaos_replay.py --fed-drill NAME --seed N`.
    resilience = run_fed_drills(seed=args.chaos_seed)
    for rep in resilience:
        log(f"resilience drill {rep['name']}: "
            f"{'ok' if rep['ok'] else 'FAILED'} {rep}")
    bad = [rep["name"] for rep in resilience if not rep["ok"]]
    if bad:
        raise RuntimeError(
            f"federation resilience drill(s) failed: {', '.join(bad)} "
            f"(replay: python tools/chaos_replay.py --fed-drill <name> "
            f"--seed {args.chaos_seed})"
        )

    n = max(2, args.federation)
    names = [f"r{i}" for i in range(n)]
    base = args.port or 3000 + (os.getpid() * 7919) % 40000
    tmp = tempfile.mkdtemp(prefix="fed_bench_")
    rng = random.Random(7)
    # Sweep-bound duplicate-heavy jobs: ~1e6-nonce ranges keep each cpu
    # miner busy seconds per distinct signature, so the scaling number
    # measures the cells' parallel sweep capacity, not client overhead.
    n_jobs, max_nonce = 36, 1_000_000
    issued: list = []
    jobs: list = []
    for _ in range(n_jobs):
        if issued and rng.random() < 0.35:
            jobs.append(rng.choice(issued))
        else:
            sig = (f"fed{len(issued)}", 0,
                   rng.randint(max_nonce // 2, max_nonce))
            issued.append(sig)
            jobs.append(sig)
    oracle = {s: min_hash_range(s[0], s[1], s[2]) for s in set(jobs)}
    log(f"workload: {len(jobs)} jobs, {len(issued)} distinct, "
        f"max_nonce {max_nonce}")

    cells: list = []
    single: list = []
    try:
        for i, name in enumerate(names):
            peers = ",".join(
                f"{o}=127.0.0.1:{base + 2 * j + 1}"
                for j, o in enumerate(names) if o != name
            )
            cells.append(
                _FedCell(name, base + 2 * i, base + 2 * i + 1, peers, tmp)
            )
        log(f"federation up: {n} replicas on {[c.port for c in cells]}")
        wall_n = _fed_batch(cells, jobs, oracle)
        rate_n = len(jobs) / wall_n
        log(f"{n}-replica leg: {rate_n:.2f} jobs/s over {wall_n:.2f}s")

        # The 1-replica comparison: a fresh single cell, fresh data keys
        # (same shapes) so nothing is pre-solved.
        sjobs = [(f"s{d[3:]}" if d.startswith("fed") else d, lo, hi)
                 for d, lo, hi in jobs]
        soracle = {s: min_hash_range(s[0], s[1], s[2]) for s in set(sjobs)}
        single.append(_FedCell("solo", base + 100, base + 101, "", tmp))
        wall_1 = _fed_batch(single, sjobs, soracle)
        rate_1 = len(sjobs) / wall_1
        log(f"1-replica leg: {rate_1:.2f} jobs/s over {wall_1:.2f}s "
            f"(scaling {rate_n / rate_1:.2f}x)")

        # Cell-kill drill: fresh keys, one whole cell SIGKILLed mid-batch.
        ring = Ring(names)
        wide = max(jobs, key=lambda s: s[2] - s[1])
        probe_name = next(nm for nm in names if nm != ring.home(wide[0]))
        victim = next(nm for nm in names if nm != probe_name)
        vcell = next(c for c in cells if c.name == victim)
        djobs = [(f"k{i}", 0, 400_000) for i in range(6)]
        doracle = {s: min_hash_range(s[0], s[1], s[2]) for s in set(djobs)}
        kill_at = len(djobs) // 2
        fired = [False]

        def maybe_kill(i):
            if i >= kill_at and not fired[0]:
                fired[0] = True
                log(f"cell-kill drill: SIGKILL cell {victim} mid-batch")
                vcell.kill()

        _fed_batch(cells, djobs, doracle, on_index=maybe_kill)
        log(f"cell-kill drill: all {len(djobs)} Results bit-exact "
            f"through the survivors")

        # Zero-work probes: no miner anywhere — an answer now can only
        # come from caches/spans; a sweep would hang past the deadline.
        time.sleep(2.0)  # let gossip full-sync the batch's spans
        for c in cells:
            c.kill(miner_only=True)
        time.sleep(0.5)
        repeat_ok = True
        # A signature homed on a LIVE cell: the probe proves the routing +
        # cache path, not dead-home failover (the drill above covered
        # that); a victim-homed key would burn the deadline on connect
        # timeouts to the killed cell.
        data, lo, hi = next(
            s for s in jobs if ring.home(s[0]) != victim
        )
        for c in cells:
            if not c.alive():
                continue
            got = _fed_request_once(c.port, data, lo, hi, deadline_s=10.0)
            ok = got == oracle[(data, lo, hi)]
            log(f"repeat probe at {c.name} (minerless): {ok}")
            repeat_ok = repeat_ok and ok
        h_star, n_star = oracle[wide]
        probe_cell = next(c for c in cells if c.name == probe_name)
        gossip_ok = None
        if n_star > wide[1] and probe_cell.alive():
            want = min_hash_range(wide[0], n_star, wide[2])
            got = _fed_request_once(
                probe_cell.fed_port, wide[0], n_star, wide[2],
                deadline_s=10.0,
            )
            gossip_ok = got == want
            log(f"gossip probe at {probe_name}'s fed port (minerless): "
                f"got {got}, want {want} -> {gossip_ok}")
        if not repeat_ok or gossip_ok is False:
            raise RuntimeError(
                f"zero-work probes failed: repeat={repeat_ok} "
                f"gossip={gossip_ok}"
            )
        # SIGTERM-drain leg (ISSUE 12): the subprocess handler end to
        # end — a live cell SIGTERM'd must announce the drain (DRAINING
        # broadcast + successor handoff happen inside) and exit 0, not
        # die mid-flight like the SIGKILL drill above.
        sigterm_ok = None
        tcell = next((c for c in cells if c.alive()), None)
        if tcell is not None:
            log(f"SIGTERM-drain leg: draining cell {tcell.name}")
            tcell.proc.send_signal(signal.SIGTERM)
            try:
                tcell.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                sigterm_ok = False
            else:
                out = tcell.proc.stdout.read() or ""
                sigterm_ok = (
                    tcell.proc.returncode == 0 and "draining" in out
                )
            log(f"SIGTERM-drain leg: exit={tcell.proc.returncode} "
                f"ok={sigterm_ok}")
            if not sigterm_ok:
                raise RuntimeError(
                    f"SIGTERM drain failed on {tcell.name}: "
                    f"exit={tcell.proc.returncode}"
                )
        print(
            json.dumps(
                {
                    "metric": "federation_fleet_jobs_per_sec",
                    "value": round(rate_n, 3),
                    "unit": "jobs/s",
                    "workload": WORKLOAD.name,
                    "replicas": n,
                    # Scaling is bounded by the host: N cells can only
                    # compute in parallel up to the core count.
                    "host_cpus": os.cpu_count(),
                    "jobs": len(jobs),
                    "distinct_signatures": len(issued),
                    "max_nonce": max_nonce,
                    "wall_s": round(wall_n, 3),
                    "single_jobs_per_sec": round(rate_1, 3),
                    "single_wall_s": round(wall_1, 3),
                    "scaling_vs_single": round(rate_n / rate_1, 3),
                    "cell_killed_mid_batch": victim,
                    "kill_drill_bit_exact": True,
                    "repeat_zero_work_all_replicas": repeat_ok,
                    "cross_replica_zero_work_probe": gossip_ok,
                    "sigterm_drain_exit0": sigterm_ok,
                    "resilience": {
                        rep["name"]: {
                            k: v for k, v in rep.items() if k != "name"
                        }
                        for rep in resilience
                    },
                }
            ),
            flush=True,
        )
        return 0
    finally:
        for c in cells + single:
            c.kill()


def run_dispatch_compare(args) -> int:
    """The adaptive-dispatch leg (ISSUE 10): static fixed-size chunking vs
    the 10^k ladder + straggler-tail stealing (+ speculative span prefill)
    on the SAME seeded chaos weather and the same induced straggler.

    An in-process loopback fleet (the chaos-drill substrate): one serve
    loop over a Gateway, ``--dc-miners`` hashlib miner threads of which
    miner-0 is the induced straggler — it computes at ``--dc-slow-rate``
    nonces/s and flat-out wedges every third chunk for ``--dc-wedge-s``
    seconds (the live-but-hung regime the steal scan exists for).  Each
    leg runs the same job batch through 2 client workers, sampling
    ``fleet.utilization`` (busy/live miners) under the event lock; every
    Result is validated against the hashlib oracle.  The adaptive leg
    then runs the zero-chunk probes: an exact repeat (cache), a solved
    sub-range (spans), and — after the idle fleet speculatively extends
    the hot key — an overlapping query past the originally requested
    range (prefill).  Prints one JSON line (the BENCH_pr10 artifact)."""
    import threading

    from bitcoin_miner_tpu import lsp
    from bitcoin_miner_tpu.apps import client as client_mod
    from bitcoin_miner_tpu.apps import miner as miner_mod
    from bitcoin_miner_tpu.apps import server as server_mod
    from bitcoin_miner_tpu.apps.scheduler import Scheduler
    from bitcoin_miner_tpu.gateway import Gateway, SpanStore
    from bitcoin_miner_tpu.lspnet.chaos import CHAOS, standard_scenarios
    from bitcoin_miner_tpu.utils import sanitize
    from bitcoin_miner_tpu.utils.metrics import METRICS

    min_hash_range = WORKLOAD.min_range
    # epoch_limit 10: burst loss must degrade the wire, not roll dice on
    # WHICH miner gets disconnected — a leg that happens to lose its
    # straggler for half the batch measures luck, not dispatch policy.
    params = lsp.Params(10, 100, 5)
    n_jobs, job_nonces = args.dc_jobs, args.dc_nonces
    n_miners = args.dc_miners

    def leg(tag: str, adaptive: bool) -> dict:
        CHAOS.reset()
        if args.chaos:
            CHAOS.seed(args.chaos_seed)
            CHAOS.run(
                standard_scenarios(params.epoch_seconds)[args.chaos],
                loop_every=args.chaos_loop,
            )
        before = METRICS.snapshot()
        server = lsp.Server(0, params, label="server")
        # Both legs share the straggler-re-queue policy (factor 4, floor
        # 1 s) and the same upper chunk envelope: what differs is ONLY the
        # dispatch plane under test.  The rate-based re-queue deadline is
        # exactly as tight as chunk sizing lets it be — a right-sized
        # 0.2 s straggler chunk times out in ~1 s, a fixed 4 s chunk not
        # for 16 s — which is the point of the ladder.
        if adaptive:
            sched = Scheduler(
                min_chunk=500,
                max_chunk=args.dc_static_chunk,
                target_chunk_seconds=args.dc_target_s,
                straggler_min_seconds=1.0,
                steal_factor=2.0,
                steal_min_seconds=0.6,
            )
        else:
            sched = Scheduler(
                min_chunk=args.dc_static_chunk,
                max_chunk=args.dc_static_chunk,
                adaptive_chunks=False,
                steal_factor=0.0,
                straggler_min_seconds=1.0,
            )
        gw = Gateway(
            sched, rate=None, spans=SpanStore(),
            prefill=args.dc_prefill if adaptive else 0,
            # Speculate only after a full second of continuous idleness:
            # inter-job gaps in the sequential batch are not idleness.
            prefill_idle_s=1.0,
        )
        lock = sanitize.make_lock(f"dispatch-compare.{tag}")
        threading.Thread(
            target=server_mod.serve,
            args=(server, gw),
            kwargs={"tick_interval": 0.1, "lock": lock},
            daemon=True,
        ).start()
        stop = threading.Event()

        def make_search(slow: bool):
            # The induced straggler sweeps at dc_slow_rate nonces/s and,
            # every dc_wedge_every_s seconds of wall time, its NEXT chunk
            # wedges flat for dc_wedge_s (a stuck-runtime episode).  Time-
            # based cadence: a per-chunk cadence would wedge more often
            # the smaller its chunks, punishing the leg that sizes a slow
            # miner down — the opposite of how real runtimes fail.  The
            # cadence clock starts at the FIRST SERVED CHUNK, not at
            # process setup: the straggler must first complete an honest
            # slow chunk so the scheduler learns its rate — the regime
            # under test is a known-slow miner whose fixed-size chunk
            # rides under the rate-aware re-queue deadline (4x expected),
            # not a cold miner the 1 s floor quarantines instantly.
            state = {"wedge_at": None}

            def search(d, lo, hi):
                if slow:
                    now = time.monotonic()
                    if state["wedge_at"] is None:
                        state["wedge_at"] = now + args.dc_wedge_every_s
                    if now >= state["wedge_at"]:
                        state["wedge_at"] = now + args.dc_wedge_every_s
                        time.sleep(args.dc_wedge_s)  # live-but-hung chunk
                    else:
                        time.sleep((hi - lo + 1) / args.dc_slow_rate)
                return min_hash_range(d, lo, hi)

            return search

        for i in range(n_miners):
            threading.Thread(
                target=miner_mod.run_miner_resilient,
                args=("127.0.0.1", server.port, make_search(i == 0)),
                kwargs={"params": params, "max_retries": 12,
                        "backoff_base": 0.05, "backoff_cap": 0.5,
                        "label": f"miner-{i}", "stop": stop},
                daemon=True,
            ).start()
        util: list = []
        sampling = threading.Event()

        def sampler() -> None:
            while not stop.is_set():
                if sampling.is_set():
                    with lock:
                        st = gw.stats()
                    # Only while a real request is in flight: inter-job
                    # wire gaps would otherwise penalize the FASTER leg
                    # (same wall-clock gap over a shorter wall).
                    if st["miners"] and st["gw_inflight"]:
                        util.append(
                            (st["miners"] - st["idle_miners"]) / st["miners"]
                        )
                time.sleep(0.05)

        threading.Thread(target=sampler, daemon=True).start()
        try:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with lock:
                    if gw.stats()["miners"] == n_miners:
                        break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"{tag}: miners never joined")

            jobs = [(f"dc-{tag}-{i}", job_nonces - 1) for i in range(n_jobs)]
            results: dict = {}
            cursor = [0]
            qlock = threading.Lock()

            def worker(w: int) -> None:
                while True:
                    with qlock:
                        if cursor[0] >= len(jobs):
                            return
                        i = cursor[0]
                        cursor[0] += 1
                    data, mx = jobs[i]
                    results[data] = client_mod.request_with_retry(
                        "127.0.0.1", server.port, data, mx,
                        retries=8, backoff_base=0.1, params=params,
                        label=f"client-{tag}-{w}",
                    )

            sampling.set()
            t0 = time.monotonic()
            workers = [
                threading.Thread(target=worker, args=(w,), daemon=True)
                for w in range(max(1, args.dc_clients))
            ]
            for t in workers:
                t.start()
            for t in workers:
                t.join(timeout=args.dc_deadline)
            wall = time.monotonic() - t0
            sampling.clear()
            if any(t.is_alive() for t in workers):
                raise RuntimeError(f"{tag}: batch exceeded {args.dc_deadline}s")
            for data, mx in jobs:
                want = min_hash_range(data, 0, mx)
                if results.get(data) != want:
                    raise RuntimeError(
                        f"{tag}: {data} got {results.get(data)}, want {want}"
                    )
            out = {
                "wall_s": round(wall, 3),
                "jobs_per_sec": round(n_jobs / wall, 3),
                "utilization_mean": round(
                    sum(util) / len(util), 3) if util else None,
            }

            if adaptive:
                out.update(_dispatch_probes(
                    gw, lock, server.port, params, jobs[0][0], job_nonces,
                    args, min_hash_range,
                ))
        finally:
            stop.set()
            CHAOS.reset()
            server.close()
        after = METRICS.snapshot()
        for k in ("sched.steals", "sched.chunk_size_adapt",
                  "sched.prefill_chunks", "sched.chunks_straggler_requeued",
                  "gateway.prefill_jobs", "gateway.prefill_preempted"):
            delta = after.get(k, 0) - before.get(k, 0)
            if delta or adaptive:
                out[k] = delta
        return out

    def _dispatch_probes(
        gw, lock, port, params, hot_data, job_nonces, args, min_hash_range
    ) -> dict:
        """Zero-chunk probes on the adaptive leg's live fleet: exact
        repeat (cache), solved sub-range (spans — also marks the key hot),
        then a query overlapping the speculative extension the idle fleet
        prefilled past the hot span."""
        probes: dict = {}

        def zero_chunk_request(mx: int):
            # Real (non-speculative) chunks only: the idle fleet may keep
            # prefilling between probes, and those chunks are exactly the
            # point — they must not read as the probe having swept.
            def real_chunks() -> int:
                return (
                    METRICS.get("sched.chunks_assigned")
                    - METRICS.get("sched.prefill_chunks")
                )

            before = real_chunks()
            got = client_mod.request_with_retry(
                "127.0.0.1", port, hot_data, mx,
                retries=5, backoff_base=0.1, params=params,
                label="client-probe",
            )
            return real_chunks() == before, got

        zero, got = zero_chunk_request(job_nonces - 1)
        probes["repeat_zero_chunks"] = (
            zero and got == min_hash_range(hot_data, 0, job_nonces - 1)
        )
        sub_hi = job_nonces // 2 - 1
        zero, got = zero_chunk_request(sub_hi)
        probes["subrange_zero_chunks"] = (
            zero and got == min_hash_range(hot_data, 0, sub_hi)
        )
        # Idle fleet: the serve ticker's gateway tick speculates past the
        # hot span.  Wait until the extension's sweep lands in the store.
        deadline = time.monotonic() + args.dc_deadline
        ext_hi = job_nonces + args.dc_prefill // 2 - 1
        covered = False
        while time.monotonic() < deadline:
            with lock:
                _best, gaps = gw.spans.cover(hot_data, 0, ext_hi)
            if not gaps:
                covered = True
                break
            time.sleep(0.1)
        probes["prefill_covered"] = covered
        if covered:
            zero, got = zero_chunk_request(ext_hi)
            probes["prefill_zero_chunks"] = (
                zero and got == min_hash_range(hot_data, 0, ext_hi)
            )
        else:
            probes["prefill_zero_chunks"] = False
        return probes

    static = leg("static", adaptive=False)
    adaptive = leg("adaptive", adaptive=True)
    speedup = (
        adaptive["jobs_per_sec"] / static["jobs_per_sec"]
        if static["jobs_per_sec"] else None
    )
    log(f"static:   {static}")
    log(f"adaptive: {adaptive}")
    log(f"speedup: {speedup:.2f}x")
    print(
        json.dumps(
            {
                "metric": "dispatch_adaptive_speedup",
                "value": round(speedup, 3),
                "unit": "x vs static chunking",
                "workload": WORKLOAD.name,
                "jobs": n_jobs,
                "job_nonces": job_nonces,
                "miners": n_miners,
                "induced_straggler": {
                    "slow_rate_nps": args.dc_slow_rate,
                    "wedge_every_s": args.dc_wedge_every_s,
                    "wedge_s": args.dc_wedge_s,
                },
                **(
                    {
                        "chaos": {
                            "scenario": args.chaos,
                            "seed": args.chaos_seed,
                            "loop_s": args.chaos_loop,
                        }
                    }
                    if args.chaos
                    else {}
                ),
                "static": static,
                "adaptive": adaptive,
                "utilization_gain": (
                    round(
                        adaptive["utilization_mean"]
                        - static["utilization_mean"], 3,
                    )
                    if adaptive.get("utilization_mean") is not None
                    and static.get("utilization_mean") is not None
                    else None
                ),
            }
        ),
        flush=True,
    )
    return 0


def run_depth_compare(args) -> int:
    """The --adaptive-depth arbitration leg (ISSUE 15 satellite, ROADMAP
    PR-14 follow-on d): the SAME job batch through the same in-process
    fleet twice — static 2-deep assignment windows vs ``adaptive_depth``
    re-sizing off the observed ``hist.device_dispatch_s`` p50 — with the
    miners on a SIEVE-ENABLED jax pipeline (``SweepPipeline(backend=
    "xla", sieve=True)``), since threshold freshness under shallow
    windows is the effect being arbitrated.  Prints one JSON line with
    the same-seed pair; the default only flips if the adaptive leg wins
    it."""
    import threading

    from bitcoin_miner_tpu import lsp
    from bitcoin_miner_tpu.apps import client as client_mod
    from bitcoin_miner_tpu.apps import miner as miner_mod
    from bitcoin_miner_tpu.apps import server as server_mod
    from bitcoin_miner_tpu.apps.scheduler import Scheduler
    from bitcoin_miner_tpu.gateway import Gateway, SpanStore
    from bitcoin_miner_tpu.utils import sanitize
    from bitcoin_miner_tpu.utils.metrics import METRICS

    min_hash_range = WORKLOAD.min_range
    params = lsp.Params(10, 200, 5)

    class _SievePipelineSearch:
        """The miner's async search on the sieve-enabled jax tier: the
        depth window under test gates how stale each dispatch's enqueued
        sieve threshold is."""

        def __init__(self) -> None:
            from concurrent.futures import Future

            from bitcoin_miner_tpu.ops.sweep import SweepPipeline

            self._Future = Future
            self._p = SweepPipeline(backend="xla", sieve=True)

        def submit(self, data, lower, upper):
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

        def close(self) -> None:
            self._p.close()

    def leg(tag: str, adaptive: bool) -> dict:
        # Fresh registry per leg: hist.device_dispatch_s is cumulative
        # (histograms have no delta view), and the adaptive leg must make
        # its depth decisions from ITS OWN cold-start samples — a warm
        # cross-leg p50 is evidence a cold production server never gets,
        # and the stamped per-leg dispatch_p50_s would otherwise mix legs.
        METRICS.reset()
        before = METRICS.snapshot()
        server = lsp.Server(0, params, label="server")
        sched = Scheduler(adaptive_depth=adaptive)
        gw = Gateway(sched, rate=None, spans=SpanStore())
        lock = sanitize.make_lock(f"depth-compare.{tag}")
        threading.Thread(
            target=server_mod.serve,
            args=(server, gw),
            kwargs={"tick_interval": 0.1, "lock": lock},
            daemon=True,
        ).start()
        searches = [_SievePipelineSearch() for _ in range(args.dp_miners)]
        for i, s in enumerate(searches):
            mc = lsp.Client("127.0.0.1", server.port, params,
                            label=f"miner-{i}")
            threading.Thread(
                target=miner_mod.run_miner, args=(mc, s), daemon=True
            ).start()
        try:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                with lock:
                    if gw.stats()["miners"] == args.dp_miners:
                        break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"{tag}: miners never joined")
            jobs = [
                (f"depth-{tag}-{i}", args.dp_nonces - 1)
                for i in range(args.dp_jobs)
            ]
            results: dict = {}
            cursor = [0]
            qlock = threading.Lock()

            def worker(w: int) -> None:
                while True:
                    with qlock:
                        if cursor[0] >= len(jobs):
                            return
                        i = cursor[0]
                        cursor[0] += 1
                    data, mx = jobs[i]
                    results[data] = client_mod.request_with_retry(
                        "127.0.0.1", server.port, data, mx,
                        retries=4, backoff_base=0.1, params=params,
                        label=f"client-{tag}-{w}",
                    )

            t0 = time.monotonic()
            workers = [
                threading.Thread(target=worker, args=(w,), daemon=True)
                for w in range(max(1, args.dp_clients))
            ]
            for t in workers:
                t.start()
            for t in workers:
                t.join(timeout=args.dp_deadline)
            wall = time.monotonic() - t0
            if any(t.is_alive() for t in workers):
                raise RuntimeError(f"{tag}: batch exceeded {args.dp_deadline}s")
            for data, mx in jobs:
                want = min_hash_range(data, 0, mx)
                if results.get(data) != want:
                    raise RuntimeError(
                        f"{tag}: {data} got {results.get(data)}, want {want}"
                    )
        finally:
            server.close()
            # Tear the leg's device pipelines down HERE, not on the
            # miners' epoch-loss schedule (~2 s after the close): the
            # next leg must not share wall time with this leg's pipeline
            # worker threads (the same cross-leg hygiene as the METRICS
            # reset above).  SweepPipeline.close is idempotent with the
            # miner loop's own close-on-exit.
            for s in searches:
                try:
                    s.close()
                except Exception:
                    pass
            time.sleep(2.5)  # epoch-loss window: miner threads fully exit
        after = METRICS.snapshot()
        h = METRICS.histogram("hist.device_dispatch_s")
        snap = h.snapshot() if h is not None else {}
        return {
            "wall_s": round(wall, 3),
            "jobs_per_sec": round(args.dp_jobs / wall, 3),
            "depth_adapts": after.get("sched.depth_adapt", 0)
            - before.get("sched.depth_adapt", 0),
            "dispatch_p50_s": round(snap.get("p50", 0.0), 6),
        }

    static = leg("static", adaptive=False)
    adaptive = leg("adaptive", adaptive=True)
    speedup = (
        round(adaptive["jobs_per_sec"] / static["jobs_per_sec"], 3)
        if static["jobs_per_sec"] else None
    )
    log(f"static:   {static}")
    log(f"adaptive: {adaptive}")
    log(f"speedup: {speedup}x")
    print(
        json.dumps(
            {
                "metric": "adaptive_depth_speedup",
                "value": speedup,
                "unit": "x vs static 2-deep windows",
                "workload": WORKLOAD.name,
                "backend": "xla",
                "sieve": True,
                "jobs": args.dp_jobs,
                "job_nonces": args.dp_nonces,
                "miners": args.dp_miners,
                "static": static,
                "adaptive": adaptive,
            }
        ),
        flush=True,
    )
    return 0


def run_mixed_fleet(args) -> int:
    """The --mixed-fleet heterogeneous-tier proving leg (ISSUE 20).

    One in-process fleet, one job stream, miners on DIFFERENT kernel
    tiers of the same workload's ladder — the device (xla) rung next to
    the cpu and hashlib host rungs, the shape a real mixed fleet has
    when only some hosts carry accelerators.  Defaults to the
    ``blake2b64`` workload (the second kernel family this leg proves;
    any workload whose ladder spans a jax tier + host tiers works via
    ``--workload``).

    What it proves, all stamped into one JSON line:

    - **bit-exact**: a small job is checked against the workload's
      hashlib oracle exactly, and the big timed job against the device
      kernel's own sweep — heterogeneous min-folding changes nothing;
    - **chunk sizes diverge**: the scheduler's per-miner EWMA chunking
      sizes each tier's chunks to its measured rate — the device rung's
      ``mean_chunk_nonces`` strictly above every host rung's;
    - **no slow-rung drag**: the per-tier split of the miner-side chunk
      wall time (the per-tier view of ``hist.miner_chunk_s``) stays in
      one band across tiers — a hashlib rung 5-6x slower per nonce gets
      proportionally smaller chunks, not proportionally longer stalls,
      so the fleet's chunk p50 is not set by its slowest rung.
    """
    import statistics
    import threading

    from bitcoin_miner_tpu import lsp
    from bitcoin_miner_tpu.apps import client as client_mod
    from bitcoin_miner_tpu.apps import miner as miner_mod
    from bitcoin_miner_tpu.apps import server as server_mod
    from bitcoin_miner_tpu.apps.scheduler import Scheduler
    from bitcoin_miner_tpu.gateway import Gateway, SpanStore
    from bitcoin_miner_tpu.ops.sweep import sweep_min_hash
    from bitcoin_miner_tpu.utils import sanitize

    if args.workload or os.environ.get("BMT_WORKLOAD"):
        wl = WORKLOAD
    else:
        wl = workloads_mod.resolve("blake2b64")
        log("mixed-fleet: defaulting to the blake2b64 workload ladder")
    tiers = [t for t in ("xla", "cpu", "hashlib") if t in wl.tiers]
    if len(tiers) < 2 or "xla" not in tiers:
        raise SystemExit(
            f"--mixed-fleet needs a workload whose ladder spans the xla "
            f"tier and a host tier; {wl.name!r} has {'->'.join(wl.tiers)}"
        )
    target_s = 0.3

    class _TierTimer:
        """Per-tier chunk accounting around an async search: the same
        submit→resolve wall time the miner observes into
        ``hist.miner_chunk_s``, split by tier (and with the chunk SIZE
        kept, which the process-global histogram cannot carry)."""

        def __init__(self, inner, rec) -> None:
            self._inner, self._rec = inner, rec

        def submit(self, data, lower, upper):
            t0 = time.monotonic()
            fut = self._inner.submit(data, lower, upper)

            def _done(f) -> None:
                if not f.cancelled() and f.exception() is None:
                    self._rec.append(
                        (upper - lower + 1, time.monotonic() - t0)
                    )

            fut.add_done_callback(_done)
            return fut

        def prewarm(self, data, upper) -> None:
            p = getattr(self._inner, "prewarm", None)
            if p is not None:
                p(data, upper)

        def close(self) -> None:
            self._inner.close()

    params = lsp.Params(10, 200, 5)
    server = lsp.Server(0, params, label="server")
    sched = Scheduler(
        workload=workloads_mod.resolve_nondefault(wl),
        target_chunk_seconds=target_s,
    )
    gw = Gateway(sched, rate=None, spans=SpanStore())
    lock = sanitize.make_lock("mixed-fleet")
    threading.Thread(
        target=server_mod.serve,
        args=(server, gw),
        kwargs={"tick_interval": 0.1, "lock": lock},
        daemon=True,
    ).start()
    recs = {t: [] for t in tiers}
    searches = [_TierTimer(wl.make_async_search(t), recs[t]) for t in tiers]
    try:
        for i, (t, s) in enumerate(zip(tiers, searches)):
            mc = lsp.Client(
                "127.0.0.1", server.port, params, label=f"miner-{t}"
            )
            threading.Thread(
                target=miner_mod.run_miner, args=(mc, s),
                kwargs={"close_search": False}, daemon=True,
            ).start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with lock:
                if gw.stats()["miners"] == len(tiers):
                    break
            time.sleep(0.05)
        else:
            raise RuntimeError("mixed-fleet: miners never joined")

        def job(data: str, mx: int):
            return client_mod.request_with_retry(
                "127.0.0.1", server.port, data, mx,
                retries=4, backoff_base=0.1, params=params,
                label="client-mixed",
            )

        # Distinct data per job — the gateway's span store prefills
        # overlapping ranges of SAME-data jobs from completed spans
        # (correct serving behavior, but here it would quietly shrink
        # the swept portion of the proving job) — at the SAME length,
        # since device kernels are compiled per message length and the
        # warm-up must pay the timed job's compiles.
        data, data_warm, data_oracle = (
            "mixed-fleet/0", "mixed-fleet/1", "mixed-fleet/2",
        )
        # Compile every digit class the jobs touch BEFORE any job runs:
        # kernel factories are lru_cached process-wide and the miners
        # run in this process, so these compiles are exactly the ones
        # the xla miner would otherwise pay mid-job (same contract as
        # the subprocess fleet's class warm-up jobs).  Default
        # host_lane_budget mirrors the pipeline: tiny classes host-route
        # and compile nothing.
        top = args.mf_nonces - 1
        for d in range(6, len(str(top)) + 1):
            hi = min(10**d - 1, top)
            sweep_min_hash(
                data, max(10 ** (d - 1), hi - 50_000 + 1), hi,
                backend="xla", workload=wl,
            )
        # Oracle job: small enough to sweep with the pure-Python oracle,
        # large enough that every tier serves chunks of it.
        oracle_n = args.mf_oracle_nonces
        got = job(data_oracle, oracle_n - 1)
        want = wl.min_range(data_oracle, 0, oracle_n - 1)
        if tuple(got) != want:
            raise RuntimeError(
                f"mixed-fleet oracle job mismatch: {got} vs {want}"
            )
        log(f"oracle job OK over [0,{oracle_n - 1}]: {got}")
        # Warm-up job: device compiles + per-miner EWMA ramp out of the
        # way before the proving job is timed.
        job(data_warm, args.mf_warmup - 1)
        for rec in recs.values():
            rec.clear()
        log(f"timed job: {args.mf_nonces:.1e} nonces across {tiers}")
        t0 = time.monotonic()
        got = job(data, args.mf_nonces - 1)
        wall = time.monotonic() - t0
        # The device kernel's own single-process sweep (oracle-gated at
        # tier-1 and in the oracle job above) arbitrates the big range.
        r = sweep_min_hash(
            data, 0, args.mf_nonces - 1, backend="xla", workload=wl
        )
        if tuple(got) != (r.hash, r.nonce):
            raise RuntimeError(
                f"mixed-fleet timed job mismatch: {got} vs "
                f"{(r.hash, r.nonce)}"
            )
    finally:
        server.close()
        for s in searches:
            try:
                s.close()
            except Exception:
                pass
        time.sleep(2.5)  # epoch-loss window: miner threads fully exit

    per_tier = {}
    for t in tiers:
        rec = recs[t]
        if not rec:
            raise RuntimeError(
                f"mixed-fleet: the {t} tier served no chunks of the timed "
                "job — nothing heterogeneous was proven"
            )
        sizes = [s for s, _ in rec]
        dts = [dt for _, dt in rec]
        per_tier[t] = {
            "chunks": len(rec),
            "nonces": sum(sizes),
            "mean_chunk_nonces": round(sum(sizes) / len(rec)),
            "miner_chunk_p50_s": round(statistics.median(dts), 4),
        }
        log(f"tier {t}: {per_tier[t]}")
    # Chunk sizes must DIVERGE: the EWMA sized the device rung's chunks
    # strictly larger than the oracle rung's.  The scheduler's size
    # ladder is decade-quantized, so adjacent tiers within ~3x of each
    # other (cpu vs either neighbor, under this process's GIL
    # contention) may legitimately share a rung — the robust
    # heterogeneous claim is the ladder's two ENDS a decade apart,
    # asserted strictly, with the middle rung weakly ordered above the
    # bottom; all three means are stamped regardless.
    means = {t: per_tier[t]["mean_chunk_nonces"] for t in tiers}
    bottom = tiers[-1]
    diverged = means["xla"] > means[bottom] and all(
        means[t] >= means[bottom] for t in tiers
    )
    if not diverged:
        raise RuntimeError(
            f"mixed-fleet: chunk sizes did not diverge down the ladder: "
            f"{means}"
        )
    # No slow-rung drag: every rung's chunk p50 sits in one band — the
    # slow tiers trade chunk SIZE, not chunk LATENCY.  4x the adaptive
    # target (plus ramp slack) is the same slack factor the scheduler's
    # own straggler detector uses.
    p50s = {t: per_tier[t]["miner_chunk_p50_s"] for t in tiers}
    drag = max(p50s.values()) > 4.0 * target_s
    if drag:
        raise RuntimeError(
            f"mixed-fleet: slow-rung drag — per-tier chunk p50 {p50s} "
            f"vs target {target_s}s"
        )
    rate = args.mf_nonces / wall
    print(
        json.dumps(
            {
                "metric": "mixed_fleet_nonces_per_sec",
                "value": round(rate),
                "unit": "nonces/s",
                "workload": wl.name,
                "tiers": tiers,
                "nonces": args.mf_nonces,
                "wall_s": round(wall, 3),
                "oracle_job_nonces": oracle_n,
                "bitexact": True,
                "chunk_sizes_diverged": True,
                "slow_rung_drag": False,
                "target_chunk_seconds": target_s,
                "per_tier": per_tier,
            }
        ),
        flush=True,
    )
    return 0


def run_autoscale_bench(args) -> int:
    """The self-scaling capacity plane leg (ISSUE 18): the SAME seeded
    open-loop Poisson arrival schedule — a warm phase, then a ramp past
    one worker's capacity — against a fixed 1-worker fleet and against
    the autoscale controller closing the loop from SLO burn alerts to
    worker spawns.  Asserts the whole causal chain on the autoscaled
    leg: the request-latency burn alert FIRES, the controller SCALES UP
    (within its hold/cooldown discipline), p99 recovers vs the fixed
    leg, and after the ramp the controller CLEAN-DRAINS back to the
    floor — every retired worker exits 0 (SIGTERM drain, not SIGKILL)
    and every answer is bit-exact on the oracle.

    Workers are real ``apps.miner`` subprocesses spawned/retired by the
    controller's own :class:`ProcessActuator`, throttled to
    ``--as-throttle-nps`` (BMT_MINER_THROTTLE_NPS) so each worker is one
    deterministic unit of capacity — the box has one core, so UNPACED
    cpu workers would all share it and scale-up would add no throughput;
    the pace is stamped into the JSON line (same honesty contract as the
    dispatch leg's induced straggler).  Prints one JSON line (the
    BENCH_pr18 artifact)."""
    import random
    import threading

    from bitcoin_miner_tpu import lsp
    from bitcoin_miner_tpu.apps import client as client_mod
    from bitcoin_miner_tpu.apps import server as server_mod
    from bitcoin_miner_tpu.apps.scheduler import Scheduler
    from bitcoin_miner_tpu.autoscale import (
        AutoscaleConfig, AutoscaleController, ControllerPump,
        ProcessActuator,
    )
    from bitcoin_miner_tpu.gateway import Gateway, SpanStore
    from bitcoin_miner_tpu.utils import sanitize
    from bitcoin_miner_tpu.utils.metrics import METRICS
    from bitcoin_miner_tpu.utils.slo import SloEngine, default_slos
    from bitcoin_miner_tpu.utils.telemetry import TelemetryHub

    min_hash_range = WORKLOAD.min_range
    # Miner-binary default params: the workers are REAL subprocesses the
    # actuator spawns with the frozen CLI, so the in-process server must
    # speak the params they default to.
    params = lsp.Params()
    nonces = args.as_nonces
    throttle = args.as_throttle_nps
    service_s = nonces / throttle  # one job, one worker, no queue
    slo_threshold_s = args.as_slo_threshold_s or round(1.5 * service_s, 3)

    # ONE seeded arrival schedule, shared by both legs: open-loop Poisson
    # at warm_x of one worker's capacity for warm_s seconds, then
    # overload_x (past one worker, under max_workers) for overload_s.
    rng = random.Random(args.as_seed)
    arrivals: list = []
    t = 0.0
    for rate_x, until in (
        (args.as_warm_x, args.as_warm_s),
        (args.as_overload_x, args.as_warm_s + args.as_overload_s),
    ):
        lam = rate_x * throttle / nonces  # jobs/s
        while True:
            t += rng.expovariate(lam)
            if t >= until:
                t = until  # phase boundary: unused tail draw
                break
            arrivals.append(t)
    if len(arrivals) < 4:
        raise RuntimeError(f"degenerate schedule: {len(arrivals)} arrivals")

    def _pct(xs: list, q: float):
        if not xs:
            return None
        s = sorted(xs)
        return round(s[min(len(s) - 1, int(round(q * (len(s) - 1))))], 3)

    tmp = tempfile.mkdtemp(prefix="autoscale_bench_")

    def leg(tag: str, autoscaled: bool) -> dict:
        METRICS.reset()
        server = lsp.Server(0, params, label="server")
        # Chunks a few tenths long so one queued job range-splits across
        # every live worker — scale-up must shorten the in-flight job's
        # tail, not just drain the backlog behind it.
        sched = Scheduler(
            min_chunk=5_000, max_chunk=nonces, target_chunk_seconds=0.4,
        )
        gw = Gateway(sched, rate=None, spans=SpanStore())
        lock = sanitize.make_lock(f"autoscale-bench.{tag}")
        # Burn evidence: the serve ticker drives the hub each beat; the
        # gateway observes hist.request_s in-process, so the request-p95
        # SLO needs no miner exporters.  Windows sized to the leg (6/15 s)
        # with a low burn threshold: the ramp must fire the alert in
        # seconds, not the production default's minutes.
        slo = SloEngine([
            s for s in default_slos(
                request_threshold_s=slo_threshold_s, objective=0.9,
                fast_window_s=6.0, slow_window_s=15.0,
                burn_threshold=2.0, min_events=3,
            ) if s.name == "request-p95"
        ])
        hub = TelemetryHub(0, params=params, slo=slo,
                           publish_interval=0.5).start()
        threading.Thread(
            target=server_mod.serve,
            args=(server, gw),
            kwargs={"tick_interval": 0.1, "lock": lock, "telemetry": hub},
            daemon=True,
        ).start()
        workers = ProcessActuator(
            server.port, backend="cpu", log_dir=tmp,
            extra_env={"BMT_MINER_THROTTLE_NPS": str(throttle)},
        )
        pump = None
        alerts_seen: set = set()
        timeline: list = []
        mon_stop = threading.Event()
        try:
            workers.spawn(1)  # the floor worker both legs start from
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with lock:
                    if gw.stats()["miners"] >= 1:
                        break
                time.sleep(0.05)
            else:
                raise RuntimeError(f"{tag}: floor worker never joined")

            controller = None
            if autoscaled:
                cfg = AutoscaleConfig(
                    min_workers=1, max_workers=args.as_max_workers, step=1,
                    hold_ticks=args.as_hold,
                    up_cooldown_s=args.as_up_cooldown,
                    down_cooldown_s=args.as_down_cooldown, util_low=0.5,
                )

                def burn():
                    st = hub.last_state() or {}
                    alerts = (st.get("slo") or {}).get("alerts") or None
                    if alerts:
                        alerts_seen.update(alerts)
                    return alerts

                controller = AutoscaleController(
                    workers, burn=burn,
                    utilization=lambda: METRICS.gauges().get(
                        "fleet.utilization"),
                    config=cfg,
                )
                pump = ControllerPump(
                    controller, interval=args.as_interval).start()

            t0 = time.monotonic()

            def monitor() -> None:
                while not mon_stop.wait(1.0):
                    row = {
                        "t": round(time.monotonic() - t0, 1),
                        "live": workers.live(),
                    }
                    if controller is not None:
                        st = controller.status()
                        row["state"] = st["state"]
                        row["target"] = st["target"]
                    timeline.append(row)

            threading.Thread(target=monitor, daemon=True).start()

            latencies: dict = {}
            results: dict = {}
            rec = threading.Lock()

            def fire(i: int, t_arr: float) -> None:
                delay = t0 + t_arr - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                data = f"as-{tag}-{i}"
                start = time.monotonic()
                got = client_mod.request_with_retry(
                    "127.0.0.1", server.port, data, nonces - 1,
                    retries=8, backoff_base=0.1, params=params,
                    label=f"client-{tag}-{i}",
                )
                with rec:
                    latencies[i] = time.monotonic() - start
                    results[i] = got

            clients = [
                threading.Thread(target=fire, args=(i, t_arr), daemon=True)
                for i, t_arr in enumerate(arrivals)
            ]
            for c in clients:
                c.start()
            batch_deadline = t0 + args.as_deadline
            for c in clients:
                c.join(timeout=max(0.1, batch_deadline - time.monotonic()))
            if any(c.is_alive() for c in clients):
                raise RuntimeError(f"{tag}: batch exceeded "
                                   f"{args.as_deadline}s open-loop deadline")
            wall = time.monotonic() - t0
            for i in range(len(arrivals)):
                want = min_hash_range(f"as-{tag}-{i}", 0, nonces - 1)
                if results.get(i) != want:
                    raise RuntimeError(
                        f"{tag}: job {i} got {results.get(i)}, want {want}")

            out = {
                "wall_s": round(wall, 3),
                "jobs": len(arrivals),
                "p50_s": _pct(list(latencies.values()), 0.50),
                "p95_s": _pct(list(latencies.values()), 0.95),
                "p99_s": _pct(list(latencies.values()), 0.99),
                "workers_peak": max(
                    (r["live"] for r in timeline), default=1),
            }
            if autoscaled:
                # The ramp is over: the controller must now walk the
                # fleet back down to the floor through clean drains.
                drain_deadline = time.monotonic() + (
                    args.as_max_workers
                    * (args.as_down_cooldown + args.as_interval * args.as_hold)
                    + 30.0
                )
                while time.monotonic() < drain_deadline:
                    # live() drops at SIGTERM; the None codes clear when
                    # the drained workers finish their in-flight chunks
                    # and actually exit — wait for both.
                    if (workers.live() == 1
                            and None not in workers.exit_codes()):
                        break
                    time.sleep(0.2)
                else:
                    raise RuntimeError(
                        f"{tag}: never drained back to the floor "
                        f"(live={workers.live()}, "
                        f"codes={workers.exit_codes()})")
                codes = workers.exit_codes()
                if any(c != 0 for c in codes):
                    raise RuntimeError(
                        f"{tag}: non-clean worker exits {codes} "
                        "(0 = SIGTERM drain finished its chunks)")
                if not alerts_seen:
                    raise RuntimeError(f"{tag}: burn alert never fired")
                scale_ups = METRICS.get("autoscale.scale_ups")
                scale_downs = METRICS.get("autoscale.scale_downs")
                if not scale_ups or not scale_downs:
                    raise RuntimeError(
                        f"{tag}: controller never closed the loop "
                        f"(ups={scale_ups} downs={scale_downs})")
                out.update({
                    "alerts_fired": sorted(alerts_seen),
                    "scale_ups": scale_ups,
                    "scale_downs": scale_downs,
                    "actions_suppressed": METRICS.get(
                        "autoscale.actions_suppressed"),
                    "reweights": METRICS.get("autoscale.reweights"),
                    "actuator_failures": METRICS.get(
                        "autoscale.actuator_failures"),
                    "drained_exit_codes": codes,
                    "end_live": workers.live(),
                    "timeline": timeline,
                })
            return out
        finally:
            mon_stop.set()
            if pump is not None:
                pump.stop()
            workers.stop_all()
            hub.close()
            server.close()

    fixed = leg("fixed", autoscaled=False)
    autoscaled = leg("auto", autoscaled=True)
    if autoscaled["p99_s"] >= fixed["p99_s"]:
        raise RuntimeError(
            f"autoscaling did not recover p99: {autoscaled['p99_s']}s vs "
            f"fixed {fixed['p99_s']}s")
    speedup = round(fixed["p99_s"] / autoscaled["p99_s"], 3)
    log(f"fixed:      {fixed}")
    log(f"autoscaled: {autoscaled}")
    log(f"p99 speedup: {speedup}x")
    print(
        json.dumps(
            {
                "metric": "autoscale_p99_speedup",
                "value": speedup,
                "unit": "x p99 latency vs fixed 1-worker fleet, same "
                        "seeded arrival schedule",
                "workload": WORKLOAD.name,
                "job_nonces": nonces,
                "worker_throttle_nps": throttle,
                "slo_threshold_s": slo_threshold_s,
                "schedule": {
                    "seed": args.as_seed,
                    "warm_s": args.as_warm_s,
                    "warm_x": args.as_warm_x,
                    "overload_s": args.as_overload_s,
                    "overload_x": args.as_overload_x,
                    "arrivals": len(arrivals),
                },
                "controller": {
                    "min_workers": 1,
                    "max_workers": args.as_max_workers,
                    "step": 1,
                    "hold_ticks": args.as_hold,
                    "up_cooldown_s": args.as_up_cooldown,
                    "down_cooldown_s": args.as_down_cooldown,
                    "util_low": 0.5,
                    "interval_s": args.as_interval,
                },
                "fixed": fixed,
                "autoscaled": autoscaled,
            }
        ),
        flush=True,
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nonces", type=int, default=2 * 10**10)
    ap.add_argument("--warmup", type=int, default=4 * 10**9)
    ap.add_argument(
        "--backend", default="auto", choices=["auto", "pallas", "xla", "cpu"]
    )
    ap.add_argument(
        "--kernel-rate",
        type=float,
        default=1.947e9,
        help="single-chip kernel rate to compare against",
    )
    ap.add_argument(
        "--cpu-miners",
        type=int,
        default=0,
        help="spawn this many additional native C++ (--backend cpu) miners "
        "alongside the main miner — the heterogeneous fleet of "
        "BASELINE.json:9 on real hardware; the scheduler range-splits "
        "across all workers and min-folds their Results",
    )
    ap.add_argument(
        "--kill-drill",
        action="store_true",
        help="after the timed job, run one job clean and the same job with "
        "a mid-job miner SIGKILL+respawn; assert both return the identical "
        "(hash, nonce) — the scheduler's reassignment invariant on the "
        "real fleet",
    )
    ap.add_argument("--drill-nonces", type=int, default=6 * 10**9)
    ap.add_argument(
        "--chaos",
        metavar="SCENARIO",
        default=None,
        help="apply this named seeded lspnet.chaos schedule in the SERVER "
        "process for the whole run (looped every --chaos-loop seconds so "
        "it stays active through the timed job) and report degraded-"
        "network throughput; names: lspnet.standard_scenarios()",
    )
    ap.add_argument("--chaos-seed", type=int, default=1)
    ap.add_argument(
        "--chaos-loop",
        type=float,
        default=10.0,
        help="replay period for the --chaos scenario (seconds)",
    )
    ap.add_argument(
        "--workload",
        default=None,
        metavar="NAME",
        help="registered range-fold workload to bench (ISSUE 9); exported "
        "as BMT_WORKLOAD to the server/miner/federation subprocesses so "
        "the whole fleet serves one hash family; default: the frozen "
        "sha256d contract",
    )
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument(
        "--stall",
        type=float,
        default=90.0,
        help="restart the miner if its chunk log stalls this many seconds",
    )
    ap.add_argument(
        "--miner-log",
        metavar="FILE",
        default=None,
        help="path for the miner's chunk-timing stderr log (default: temp)",
    )
    ap.add_argument(
        "--trace",
        metavar="FILE",
        default=None,
        help="arm the server's structured event log (BMT_TRACE) and write "
        "it here; analyze with python -m tools.trace",
    )
    ap.add_argument(
        "--telemetry",
        action="store_true",
        help="arm the fleet metrics plane (ISSUE 7): the server opens a "
        "telemetry sidecar port + SLO engine, every miner exports "
        "snapshots to it, and the fleet-merged histograms + SLO verdicts "
        "are stamped into the JSON line (watch live: python -m tools.dash "
        "--connect)",
    )
    ap.add_argument(
        "--dispatch-compare",
        action="store_true",
        help="adaptive-dispatch leg (ISSUE 10): static fixed chunking vs "
        "the 10^k ladder + straggler-tail stealing + span prefill on an "
        "in-process loopback fleet with an induced straggler (combine "
        "with --chaos SCENARIO for the degraded-network artifact); "
        "prints its own JSON line and exits",
    )
    ap.add_argument("--dc-jobs", type=int, default=12)
    ap.add_argument("--dc-nonces", type=int, default=80_000,
                    help="nonces per dispatch-compare job")
    ap.add_argument("--dc-miners", type=int, default=3)
    ap.add_argument("--dc-static-chunk", type=int, default=20_000,
                    help="fixed chunk size of the static comparison leg")
    ap.add_argument("--dc-target-s", type=float, default=0.1,
                    help="adaptive leg per-chunk service-time target")
    ap.add_argument("--dc-slow-rate", type=float, default=10_000.0,
                    help="induced straggler's sweep rate (nonces/s)")
    ap.add_argument("--dc-wedge-s", type=float, default=2.0,
                    help="induced straggler's stuck-runtime episode length (s)")
    ap.add_argument("--dc-wedge-every-s", type=float, default=1.0,
                    help="seconds between the straggler's wedge episodes")
    ap.add_argument("--dc-prefill", type=int, default=20_000,
                    help="speculative prefill job size (adaptive leg)")
    ap.add_argument("--dc-clients", type=int, default=1,
                    help="concurrent client workers; 1 = sequential jobs, "
                    "the regime where a straggler-held tail idles the "
                    "healthy miners")
    ap.add_argument("--dc-deadline", type=float, default=120.0)
    ap.add_argument(
        "--depth-compare",
        action="store_true",
        help="adaptive pipeline-depth arbitration (ISSUE 15 satellite): "
        "static 2-deep vs --adaptive-depth windows on a sieve-enabled "
        "xla fleet, same job batch; prints its own JSON line and exits",
    )
    ap.add_argument("--dp-jobs", type=int, default=6,
                    help="jobs per depth-compare leg")
    ap.add_argument("--dp-nonces", type=int, default=2_000_000,
                    help="nonces per depth-compare job")
    ap.add_argument("--dp-miners", type=int, default=2)
    ap.add_argument("--dp-clients", type=int, default=2)
    ap.add_argument("--dp-deadline", type=float, default=300.0)
    ap.add_argument(
        "--autoscale",
        action="store_true",
        help="self-scaling capacity plane leg (ISSUE 18): the same seeded "
        "open-loop arrival ramp against a fixed 1-worker fleet and "
        "against the SLO-burn-driven controller (spawn on burn, clean "
        "drain after); asserts alert->scale-up->p99-recovery->drain and "
        "bit-exact answers; prints its own JSON line and exits",
    )
    ap.add_argument("--as-nonces", type=int, default=40_000,
                    help="nonces per autoscale-leg job (short jobs: burn "
                    "evidence arrives at completion, so the completion "
                    "rate is the controller's evidence rate)")
    ap.add_argument("--as-throttle-nps", type=float, default=50_000.0,
                    help="per-worker pace (BMT_MINER_THROTTLE_NPS): one "
                    "deterministic unit of capacity per worker on a "
                    "1-core box")
    ap.add_argument("--as-warm-s", type=float, default=6.0,
                    help="seconds of in-capacity warm arrivals")
    ap.add_argument("--as-warm-x", type=float, default=0.25,
                    help="warm arrival rate as a multiple of one "
                    "worker's capacity")
    ap.add_argument("--as-overload-s", type=float, default=20.0,
                    help="seconds of past-capacity ramp arrivals")
    ap.add_argument("--as-overload-x", type=float, default=2.2,
                    help="ramp arrival rate as a multiple of one worker's "
                    "capacity (must exceed 1, stay under --as-max-workers)")
    ap.add_argument("--as-max-workers", type=int, default=3)
    ap.add_argument("--as-interval", type=float, default=0.25,
                    help="controller tick interval (s)")
    ap.add_argument("--as-hold", type=int, default=3,
                    help="consecutive burning/quiet ticks before acting")
    ap.add_argument("--as-up-cooldown", type=float, default=3.0)
    ap.add_argument("--as-down-cooldown", type=float, default=6.0)
    ap.add_argument("--as-slo-threshold-s", type=float, default=None,
                    help="request-p95 SLO latency threshold "
                    "(default: 1.5x one job's unqueued service time)")
    ap.add_argument("--as-deadline", type=float, default=120.0,
                    help="open-loop batch deadline per leg (s)")
    ap.add_argument("--as-seed", type=int, default=1,
                    help="arrival-schedule seed (both legs share it)")
    ap.add_argument(
        "--mixed-fleet",
        action="store_true",
        help="heterogeneous-tier leg (ISSUE 20): one in-process fleet "
        "with one miner per kernel tier of the workload's ladder "
        "(xla + cpu + hashlib; default workload blake2b64) on one job — "
        "asserts the answer is bit-exact, per-tier chunk sizes diverge "
        "with measured rates, and no slow rung drags the chunk p50; "
        "prints its own JSON line and exits",
    )
    ap.add_argument("--mf-nonces", type=int, default=24_000_000,
                    help="nonces in the timed mixed-fleet job")
    ap.add_argument("--mf-warmup", type=int, default=4_000_000,
                    help="nonces in the mixed-fleet warm-up job")
    ap.add_argument("--mf-oracle-nonces", type=int, default=120_000,
                    help="nonces in the oracle-checked mixed-fleet job")
    ap.add_argument(
        "--federation",
        type=int,
        default=0,
        metavar="N",
        help="federation leg (ISSUE 8): N real apps.federation replica "
        "subprocesses, duplicate-heavy batch with a mid-batch cell "
        "SIGKILL and a minerless cross-replica gossip probe; prints its "
        "own JSON line and exits",
    )
    args = ap.parse_args()

    global WORKLOAD
    try:
        WORKLOAD = workloads_mod.resolve(
            args.workload or os.environ.get("BMT_WORKLOAD") or None
        )
    except ValueError as e:
        ap.error(str(e))
    if args.workload:
        # Subprocess fleets (MinerKeeper, server, federation cells) all
        # spawn with {**os.environ}: one export reaches every process.
        os.environ["BMT_WORKLOAD"] = WORKLOAD.name

    if args.dispatch_compare:
        if args.chaos:
            from bitcoin_miner_tpu.lspnet.chaos import standard_scenarios

            if args.chaos not in standard_scenarios():
                raise SystemExit(
                    f"unknown --chaos scenario {args.chaos!r}; valid: "
                    f"{sorted(standard_scenarios())}"
                )
        return run_dispatch_compare(args)

    if args.depth_compare:
        return run_depth_compare(args)

    if args.mixed_fleet:
        return run_mixed_fleet(args)

    if args.autoscale:
        return run_autoscale_bench(args)

    if args.federation:
        return run_federation_bench(args)

    port = args.port or 3000 + (os.getpid() * 7919) % 50000
    data = "cmu440"
    tmp = tempfile.mkdtemp(prefix="fleet_bench_")
    miner_log = args.miner_log or os.path.join(tmp, "miner.log")
    server = None
    keeper = None
    client = None
    cpu_miners: list = []
    try:
        server_env = {**os.environ, "PYTHONPATH": str(REPO)}
        tele_addr = None
        fleet_log = None
        if args.telemetry:
            # The sidecar port rides next to the serving port; the server
            # appends the merged view to a fleet log this tool reads back
            # for the JSON stamp (and tools.dash can tail live).
            tport = port + 1
            tele_addr = f"127.0.0.1:{tport}"
            fleet_log = os.path.join(tmp, "fleet.jsonl")
            server_env.update(
                BMT_TELEMETRY_PORT=str(tport),
                BMT_FLEET_LOG=fleet_log,
                BMT_SLO="1",
            )
            log(f"telemetry: sidecar on :{tport}, fleet log -> {fleet_log}")
        if args.trace:
            # The server process owns the gateway/scheduler events; its
            # ticker drains them to the file (apps/server.main reads
            # BMT_TRACE, the env spelling of --trace=FILE).
            server_env["BMT_TRACE"] = os.path.abspath(args.trace)
            log(f"trace: server event log -> {args.trace}")
        if args.chaos:
            from bitcoin_miner_tpu.lspnet.chaos import standard_scenarios

            # Validate HERE: the server subprocess's "unknown scenario"
            # warning goes to a devnulled stderr, and a typoed name would
            # otherwise stamp a chaos config onto a clean-network number.
            if args.chaos not in standard_scenarios():
                raise SystemExit(
                    f"unknown --chaos scenario {args.chaos!r}; valid: "
                    f"{sorted(standard_scenarios())}"
                )
            # The server arms the schedule at startup (apps/server.main);
            # its tx shapes both the chunk stream to miners and the Result
            # stream to clients — the degraded-network leg of the bench.
            server_env.update(
                BMT_CHAOS_SCENARIO=args.chaos,
                BMT_CHAOS_LOOP=str(args.chaos_loop),
                LSPNET_CHAOS_SEED=str(args.chaos_seed),
            )
            log(f"chaos: {args.chaos} (seed {args.chaos_seed}, "
                f"looped every {args.chaos_loop:.1f}s) armed in the server")
        server = subprocess.Popen(
            [sys.executable, "-m", "bitcoin_miner_tpu.apps.server", str(port)],
            cwd=tmp,  # server writes ./log.txt (reference parity)
            env=server_env,
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
        )
        _wait_listening(server, 30)
        log(f"server up on :{port}; miner log -> {miner_log}")
        keeper = MinerKeeper(port, args.backend, miner_log, telemetry=tele_addr)
        for i in range(args.cpu_miners):
            cpu_log = open(os.path.join(tmp, f"cpu_miner_{i}.log"), "wb")
            cpu_argv = [
                sys.executable,
                "-m",
                "bitcoin_miner_tpu.apps.miner",
                f"127.0.0.1:{port}",
                "--backend",
                "cpu",
            ]
            if tele_addr:
                cpu_argv += [
                    "--telemetry", tele_addr,
                    "--telemetry-interval", "1.0",
                    "--source", f"cpu-miner-{i}",
                ]
            cpu_miners.append(
                subprocess.Popen(
                    cpu_argv,
                    cwd=str(REPO),
                    stdout=subprocess.DEVNULL,
                    stderr=cpu_log,
                )
            )
        if cpu_miners:
            log(f"spawned {len(cpu_miners)} native cpu miners (logs in {tmp})")

        from bitcoin_miner_tpu import lsp

        client = lsp.Client("127.0.0.1", port)
        log(f"warm-up job: {args.warmup:.1e} nonces (compiles + EWMA ramp)")
        warm = run_job(
            client, keeper, data, args.warmup - 1, args.timeout, args.stall
        )
        log(
            f"warm-up done in {warm['wall_s']:.2f}s "
            f"({args.warmup / warm['wall_s'] / 1e9:.3f}e9 n/s incl. ramp)"
        )
        # Class warm: every digit class the timed job will touch must be
        # built before timing starts (same contract as bench.py, which
        # compiles before its measurement window) — a class's first use
        # costs ~9 s of tracing + ~5 s of executable load per process even
        # on a persistent-cache hit, and the main warm-up job only covers
        # the classes below `--warmup`.  A tiny job per uncovered digit
        # class pays that cost here instead of mid-measurement.  The
        # mid-job path is still covered: the miner prewarms one class
        # ahead of each assignment (SweepPipeline.prewarm_async).
        # The drill range sits beyond the timed job; its digit classes must
        # be warm too, or the "clean" drill leg absorbs a first-use build.
        top = args.nonces - 1
        if args.kill_drill:
            top = args.nonces + args.drill_nonces - 1
        for d in range(len(str(args.warmup - 1)) + 1, len(str(top)) + 1):
            t0 = time.monotonic()
            hi = min(10**d - 1, top)
            run_job(
                client, keeper, data, hi, args.timeout, args.stall,
                lower=max(0, hi - 10**6 + 1),
            )
            log(f"class d={d} warm-up done in {time.monotonic() - t0:.2f}s")
        log(f"timed job: {args.nonces:.1e} nonces")
        timed = run_job(
            client, keeper, data, args.nonces - 1, args.timeout, args.stall
        )
        rate = args.nonces / timed["wall_s"]
        log(
            f"fleet delivered {rate / 1e9:.3f}e9 n/s over {timed['wall_s']:.2f}s "
            f"({rate / args.kernel_rate:.1%} of the {args.kernel_rate / 1e9:.3f}e9 kernel rate)"
        )
        # A cpu miner that died mid-bench would make the "heterogeneous
        # fleet" artifact describe a fleet that never ran — refuse.
        dead = [i for i, m in enumerate(cpu_miners) if m.poll() is not None]
        if dead:
            raise RuntimeError(
                f"cpu miner(s) {dead} died during the bench; see "
                f"{tmp}/cpu_miner_*.log"
            )
        drill = None
        if args.kill_drill:
            # Same range, clean vs mid-job miner SIGKILL: the argmin over a
            # fixed range is deterministic, so any correct execution —
            # including one the scheduler had to reassemble from a dead
            # miner's reassigned chunks — must return the identical pair.
            d_lo = args.nonces  # fresh range, beyond the timed job
            d_hi = d_lo + args.drill_nonces - 1
            log(f"kill drill: clean job over [{d_lo},{d_hi}]")
            clean = run_job(
                client, keeper, data, d_hi, args.timeout, args.stall,
                lower=d_lo,
            )
            kill_at = max(1.0, 0.4 * clean["wall_s"])
            log(f"kill drill: same job, SIGKILL at t+{kill_at:.1f}s")
            killed = run_job(
                client, keeper, data, d_hi, args.timeout, args.stall,
                lower=d_lo, kill_after=kill_at,
            )
            if not killed["kill_fired"]:
                # A Result that lands before the kill makes the drill a
                # second clean run — no fault-tolerance evidence at all.
                raise RuntimeError(
                    "kill drill: Result arrived before the SIGKILL fired; "
                    "raise --drill-nonces"
                )
            match = (clean["hash"], clean["nonce"]) == (
                killed["hash"], killed["nonce"],
            )
            drill = {
                "match": match,
                "hash": clean["hash"],
                "nonce": clean["nonce"],
                "clean_wall_s": round(clean["wall_s"], 3),
                "killed_wall_s": round(killed["wall_s"], 3),
                # Exactly one deliberate kill per drill (kill_fired was
                # asserted above); any further restarts in the drill window
                # were involuntary wedge recoveries and stay in
                # miner_restarts.
                "deliberate_kills": 1,
            }
            log(f"kill drill: match={match} ({clean} vs {killed})")
            if not match:
                raise RuntimeError(f"kill drill mismatch: {clean} vs {killed}")
        # Fleet-plane stamp (ISSUE 7): the merged view + SLO verdicts the
        # server's hub last published, read back while it is still up.
        fleet_stamp = _read_last_fleet_state(fleet_log) if fleet_log else None
        if args.telemetry and fleet_stamp is None:
            log("warning: --telemetry armed but no fleet state was published")
        print(
            json.dumps(
                {
                    "metric": "fleet_nonces_per_sec",
                    "value": round(rate),
                    "unit": "nonces/s",
                    "workload": WORKLOAD.name,
                    "vs_baseline": round(rate / 1e9, 4),
                    "kernel_rate": round(args.kernel_rate),
                    "vs_kernel": round(rate / args.kernel_rate, 4),
                    "nonces": args.nonces,
                    "wall_s": round(timed["wall_s"], 3),
                    "warmup_nonces": args.warmup,
                    "warmup_wall_s": round(warm["wall_s"], 3),
                    "latency_s": {
                        k: round(v, 4)
                        for k, v in LATENCY.snapshot().items()
                        if k in ("p50", "p95", "p99")
                    } | {"count": LATENCY.count()},
                    # Involuntary (wedge/death) recoveries only; the
                    # drill's deliberate kill is counted in kill_drill.
                    "miner_restarts": keeper.restarts
                    - (drill["deliberate_kills"] if drill else 0),
                    "backend": args.backend,
                    **(
                        {
                            "chaos": {
                                "scenario": args.chaos,
                                "seed": args.chaos_seed,
                                "loop_s": args.chaos_loop,
                            }
                        }
                        if args.chaos
                        else {}
                    ),
                    **(
                        {"cpu_miners": args.cpu_miners}
                        if args.cpu_miners
                        else {}
                    ),
                    **({"kill_drill": drill} if drill is not None else {}),
                    **(
                        {
                            "fleet": {
                                "sources": fleet_stamp["sources"],
                                "stale_sources": fleet_stamp["stale_sources"],
                                "hists": fleet_stamp["hists"],
                                "stragglers": [
                                    s["source"]
                                    for s in fleet_stamp.get("stragglers", [])
                                ],
                            },
                            "slo": {
                                s["name"]: {
                                    "ok": s["ok"],
                                    "burn_fast": s["burn_fast"],
                                    "burn_slow": s["burn_slow"],
                                }
                                for s in fleet_stamp.get("slo", {}).get(
                                    "slos", []
                                )
                            },
                        }
                        if fleet_stamp is not None
                        else {}
                    ),
                }
            ),
            flush=True,
        )
        return 0
    finally:
        if client is not None:
            try:
                client.close()
            except Exception:
                pass
        if keeper is not None:
            keeper.kill()
        for m in cpu_miners:
            if m.poll() is None:
                m.send_signal(signal.SIGKILL)
        if server is not None and server.poll() is None:
            server.send_signal(signal.SIGTERM)
            try:
                server.wait(timeout=5)
            except subprocess.TimeoutExpired:
                server.kill()


if __name__ == "__main__":
    sys.exit(main())
