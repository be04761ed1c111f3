"""End-to-end mining integration tests (B8, BASELINE configs 1/3/5).

Real loopback UDP through the full LSP stack: a server thread running the
scheduler loop, miner threads on the CPU-oracle backend (byte-identical to
the Go reference's hot loop), and client threads using the frozen
request/response path.  Mirrors the reference test style (SURVEY §4):
everything in one process, epoch-denominated timeouts, the lspnet seam for
fault injection.
"""

import threading
import time

import pytest

from bitcoin_miner_tpu import lsp, lspnet
from bitcoin_miner_tpu.apps import client as client_mod
from bitcoin_miner_tpu.apps import miner as miner_mod
from bitcoin_miner_tpu.apps import server as server_mod
from bitcoin_miner_tpu.apps.scheduler import Scheduler
from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
from bitcoin_miner_tpu.bitcoin.message import Message


PARAMS = lsp.Params(epoch_limit=5, epoch_millis=200, window_size=5)


@pytest.fixture(autouse=True)
def _clean_network():
    lspnet.reset_faults()
    yield
    lspnet.reset_faults()


class MiningSystem:
    """In-process cluster: scheduler server + N miner threads."""

    def __init__(self, n_miners: int = 2, min_chunk: int = 500):
        self.server = lsp.Server(0, PARAMS)
        self.port = self.server.port
        self.scheduler = Scheduler(min_chunk=min_chunk)
        self.server_thread = threading.Thread(
            target=server_mod.serve, args=(self.server, self.scheduler), daemon=True
        )
        self.server_thread.start()
        self.miner_clients = []
        self.miner_threads = []
        for _ in range(n_miners):
            self.add_miner()

    def add_miner(self, search=None):
        c = lsp.Client("127.0.0.1", self.port, PARAMS)
        t = threading.Thread(
            target=miner_mod.run_miner,
            args=(c, search or miner_mod.make_search("cpu")),
            daemon=True,
        )
        t.start()
        self.miner_clients.append(c)
        self.miner_threads.append(t)
        return c

    def request(self, data: str, max_nonce: int):
        c = lsp.Client("127.0.0.1", self.port, PARAMS)
        try:
            return client_mod.request_once(c, data, max_nonce)
        finally:
            c.close()

    def close(self):
        self.server.close()


def test_single_miner_correct_result():
    sys_ = MiningSystem(n_miners=1)
    try:
        res = sys_.request("cmu440", 4999)
        assert res == min_hash_range("cmu440", 0, 4999)
    finally:
        sys_.close()


def test_multi_miner_range_split_correct():
    sys_ = MiningSystem(n_miners=4, min_chunk=300)
    try:
        res = sys_.request("distributed", 7999)
        assert res == min_hash_range("distributed", 0, 7999)
    finally:
        sys_.close()


def test_concurrent_clients():
    sys_ = MiningSystem(n_miners=3, min_chunk=400)
    results = {}

    def one(job):
        data, mx = job
        results[job] = sys_.request(data, mx)

    jobs = [("alpha", 3000), ("beta", 4000), ("gamma", 2500)]
    try:
        threads = [threading.Thread(target=one, args=(j,)) for j in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "client timed out"
        for data, mx in jobs:
            assert results[(data, mx)] == min_hash_range(data, 0, mx)
    finally:
        sys_.close()


def test_heterogeneous_backends():
    """A fast+slow fleet (10x rate skew) still min-folds correctly — the
    BASELINE config-3 shape (CPU + TPU mix) with the skew simulated."""

    def slow_search(data, lower, upper):
        time.sleep(0.05)
        return min_hash_range(data, lower, upper)

    sys_ = MiningSystem(n_miners=0, min_chunk=500)
    try:
        sys_.add_miner(miner_mod.make_search("cpu"))
        sys_.add_miner(slow_search)
        res = sys_.request("hetero", 6000)
        assert res == min_hash_range("hetero", 0, 6000)
    finally:
        sys_.close()


def test_miner_killed_mid_job_range_reassigned():
    """BASELINE config 5: kill a miner mid-job; the server must reassign its
    outstanding chunk and the final result must be unchanged."""
    block = threading.Event()
    killed = threading.Event()

    def stalling_search(data, lower, upper):
        if not killed.is_set():
            killed.set()
            block.wait(timeout=30)  # hold the chunk until we are killed
        return min_hash_range(data, lower, upper)

    sys_ = MiningSystem(n_miners=0, min_chunk=500)
    try:
        victim = sys_.add_miner(stalling_search)
        sys_.add_miner()

        out = {}

        def run_client():
            out["res"] = sys_.request("faulty", 4000)

        t = threading.Thread(target=run_client, daemon=True)
        t.start()
        assert killed.wait(timeout=30), "victim never got a chunk"
        victim.close()  # miner process dies; epochs declare it lost
        t.join(timeout=60)
        assert not t.is_alive(), "client never got a result"
        assert out["res"] == min_hash_range("faulty", 0, 4000)
    finally:
        block.set()
        sys_.close()


def test_client_death_cancels_job_and_server_survives():
    sys_ = MiningSystem(n_miners=1, min_chunk=200)
    try:
        c = lsp.Client("127.0.0.1", sys_.port, PARAMS)
        c.write(Message.request("doomed", 0, 10**7).marshal())
        time.sleep(0.3)  # let the job get scheduled
        c.close()
        deadline = time.time() + PARAMS.epoch_limit * PARAMS.epoch_seconds + 5
        while time.time() < deadline and sys_.scheduler.jobs:
            time.sleep(0.1)
        assert sys_.scheduler.jobs == {}, "job not cancelled after client death"
        # Server still serves new work afterwards.
        res = sys_.request("alive", 1500)
        assert res == min_hash_range("alive", 0, 1500)
    finally:
        sys_.close()


def test_mining_under_packet_loss():
    """Request/Result survive 20% write drop both ways (lsp retransmits)."""
    sys_ = MiningSystem(n_miners=2, min_chunk=500)
    try:
        lspnet.set_write_drop_percent(20)
        res = sys_.request("lossy", 3000)
        assert res == min_hash_range("lossy", 0, 3000)
    finally:
        lspnet.reset_faults()
        sys_.close()


def test_xla_backend_fleet():
    """The Request→sweep→Result glue through the JAX tier — the backend a
    real TPU miner runs (here on the virtual CPU mesh).  Round 1 only ever
    exercised the fleet with the cpu oracle; this pins the apps/miner.py
    routing of Request fields into sweep_min_hash."""
    sys_ = MiningSystem(n_miners=0, min_chunk=400)
    try:
        sys_.add_miner(miner_mod.make_search("xla"))
        sys_.add_miner()  # heterogeneous: xla + cpu oracle in one fleet
        res = sys_.request("xlatier", 2500)
        assert res == min_hash_range("xlatier", 0, 2500)
    finally:
        sys_.close()


def test_many_one_shot_clients_on_reused_ports(monkeypatch):
    """The CMU client's many-user shape on the served path: 8 concurrent
    users, each job on a fresh connection, ranges that cross the miner's
    host/device split (d = 7 folded on the host, d = 8 on the xla tier),
    and half the users' second job bound to the local port their first
    job had just closed, while the server's old connection lingers."""
    from bitcoin_miner_tpu.ops.sweep import auto_host_lane_budget
    from lsp_harness import endpoint_on_port

    assert auto_host_lane_budget() == 10**7  # xla: d <= 7 on the host
    connect_lock = threading.Lock()
    want_port = [0]
    monkeypatch.setattr(
        lspnet, "create_client_endpoint", endpoint_on_port(lambda: want_port[0]))
    from bitcoin_miner_tpu.utils.metrics import METRICS

    rebound0 = METRICS.get("lsp.connects_rebound")
    fold0 = METRICS.get("sweep.host_fold_lanes"), METRICS.get("sweep.host_fold_s")
    sys_ = MiningSystem(n_miners=0, min_chunk=2000)
    sys_.add_miner(miner_mod.make_async_search("xla"))
    answers, errors = {}, []

    def job(c, j, port):
        data = f"u{c}j{j}x"
        lo = 9_990_000 + 1_000 * c
        hi = 10_000_000 + 9_000 + 2_000 * c + 500 * j
        with connect_lock:
            want_port[0] = port
            try:
                client = lsp.Client("127.0.0.1", sys_.port, PARAMS)
            finally:
                want_port[0] = 0
        used = client._c._endpoint.local_addr[1]
        try:
            client.write(Message.request(data, lo, hi).marshal())
            msg = Message.unmarshal(client.read(timeout=90))
            answers[(data, lo, hi)] = (msg.hash, msg.nonce)
        finally:
            client.close()
        return used

    def user(c):
        try:
            port = job(c, 0, 0)
            job(c, 1, port if c % 2 == 0 else 0)
        except Exception as e:  # reported below, with the user
            errors.append((c, repr(e)))

    try:
        threads = [threading.Thread(target=user, args=(c,)) for c in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=200)
            assert not t.is_alive(), "a user timed out"
        assert not errors, errors
        assert len(answers) == 16
        # Each reused port met its old connection still lingering.
        assert METRICS.get("lsp.connects_rebound") - rebound0 == 4
        assert METRICS.get("sweep.host_fold_lanes") > fold0[0]
        assert METRICS.get("sweep.host_fold_s") > fold0[1]
        for (data, lo, hi), got in answers.items():
            assert got == min_hash_range(data, lo, hi), (data, lo, hi)
    finally:
        sys_.close()


def test_multichip_mesh_miner_fleet():
    """One miner process spanning the full 8-device virtual mesh via
    --devices (shard_map + pmin cascade), serving a real fleet job — the
    apps/miner.py glue over parallel/sweep.py (BASELINE's single ultra-fast
    worker shape)."""
    sys_ = MiningSystem(n_miners=0, min_chunk=500)
    try:
        sys_.add_miner(miner_mod.make_search("xla", devices=8))
        res = sys_.request("meshminer", 2500)
        assert res == min_hash_range("meshminer", 0, 2500)
    finally:
        sys_.close()


def test_checkpoint_resume_fleet_restart(tmp_path):
    """Kill the whole fleet mid-job; a restarted server resumes from the
    checkpoint file and completes WITHOUT re-sweeping finished sub-ranges
    (scheduler checkpoint/resume, SURVEY §5 beyond-parity)."""
    ckpt = str(tmp_path / "ckpt.json")
    data, mx = "resumable", 9999
    first_done = threading.Event()
    hold = threading.Event()

    def first_then_hang(d, lo, hi):
        r = min_hash_range(d, lo, hi)
        if first_done.is_set():
            hold.wait(timeout=30)  # freeze the fleet after one chunk lands
        first_done.set()
        return r

    # --- fleet 1: completes exactly one chunk, then is killed ------------
    server1 = lsp.Server(0, PARAMS)
    sched1 = Scheduler(min_chunk=2000, straggler_min_seconds=60.0)
    t1 = threading.Thread(
        target=server_mod.serve,
        args=(server1, sched1),
        kwargs={"tick_interval": 0.05, "checkpoint_path": ckpt},
        daemon=True,
    )
    t1.start()
    m1 = lsp.Client("127.0.0.1", server1.port, PARAMS)
    threading.Thread(
        target=miner_mod.run_miner, args=(m1, first_then_hang), daemon=True
    ).start()
    c1 = lsp.Client("127.0.0.1", server1.port, PARAMS)
    c1.write(Message.request(data, 0, mx).marshal())
    assert first_done.wait(timeout=30), "first chunk never completed"
    # Wait for a checkpoint that has folded the first chunk's result.
    deadline = time.time() + 10
    state = None
    while time.time() < deadline:
        state = server_mod.load_checkpoint(ckpt)
        if state and state["jobs"] and state["jobs"][0]["best"] is not None:
            break
        time.sleep(0.05)
    assert state and state["jobs"][0]["best"] is not None, "no checkpoint"
    server1.close()  # fleet dies mid-job
    hold.set()

    # --- fleet 2: resumes from the file -----------------------------------
    [jobdict] = state["jobs"]
    completed_upper = min(lo for lo, _ in jobdict["remaining"]) - 1
    assert completed_upper >= 0, "nothing was actually completed"

    swept = []

    def recording_search(d, lo, hi):
        swept.append((lo, hi))
        return min_hash_range(d, lo, hi)

    server2 = lsp.Server(0, PARAMS)
    sched2 = Scheduler(
        min_chunk=2000, resume_state=server_mod.load_checkpoint(ckpt)
    )
    threading.Thread(
        target=server_mod.serve, args=(server2, sched2), daemon=True
    ).start()
    m2 = lsp.Client("127.0.0.1", server2.port, PARAMS)
    threading.Thread(
        target=miner_mod.run_miner, args=(m2, recording_search), daemon=True
    ).start()
    try:
        c2 = lsp.Client("127.0.0.1", server2.port, PARAMS)
        try:
            res = client_mod.request_once(c2, data, mx)
        finally:
            c2.close()
        assert res == min_hash_range(data, 0, mx)
        assert swept, "resumed fleet did no work"
        # Nothing below the completed prefix may have been re-swept.
        assert min(lo for lo, _ in swept) > completed_upper
    finally:
        server2.close()


def test_client_disconnected_output():
    """Frozen stdout contract: server dies -> client prints Disconnected."""
    import io

    sys_ = MiningSystem(n_miners=0)
    port = sys_.port
    out = io.StringIO()

    def run():
        client_mod.main(["client", f"127.0.0.1:{port}", "x", "100000"], out=out)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(0.5)  # request reaches the (miner-less) scheduler
    sys_.close()
    t.join(timeout=30)
    assert not t.is_alive()
    assert out.getvalue() == "Disconnected\n"


def test_ticker_survives_checkpoint_write_failure():
    """An unwritable checkpoint path must not kill the ticker thread (and
    with it straggler recovery) — the serve loop logs and keeps going."""
    server = lsp.Server(0, PARAMS)
    sched = Scheduler(min_chunk=500)
    t = threading.Thread(
        target=server_mod.serve,
        args=(server, sched),
        kwargs={
            "tick_interval": 0.05,
            "checkpoint_path": "/nonexistent-dir/ckpt.json",
        },
        daemon=True,
    )
    t.start()
    try:
        m = lsp.Client("127.0.0.1", server.port, PARAMS)
        threading.Thread(
            target=miner_mod.run_miner,
            args=(m, miner_mod.make_search("cpu")),
            daemon=True,
        ).start()
        time.sleep(0.5)  # several failing ticks elapse
        c = lsp.Client("127.0.0.1", server.port, PARAMS)
        try:
            res = client_mod.request_once(c, "tickerok", 3000)
        finally:
            c.close()
        assert res == min_hash_range("tickerok", 0, 3000)
    finally:
        server.close()


def test_adversarial_fleet_soak():
    """Everything at once, live: 20% packet loss, a permanently hung miner
    (straggler tick reclaims), a lying miner (validation evicts), a slow
    miner, and concurrent jobs — every client still gets the bit-exact
    min (BASELINE configs 3+5 combined, plus this framework's guards)."""
    server = lsp.Server(0, PARAMS)
    sched = Scheduler(min_chunk=400, straggler_min_seconds=4.0)
    threading.Thread(
        target=server_mod.serve,
        args=(server, sched),
        kwargs={"tick_interval": 0.2},
        daemon=True,
    ).start()

    def add(search):
        c = lsp.Client("127.0.0.1", server.port, PARAMS)
        threading.Thread(
            target=miner_mod.run_miner, args=(c, search), daemon=True
        ).start()
        return c

    hold = threading.Event()
    try:
        for _ in range(5):
            add(miner_mod.make_search("cpu"))
        add(lambda d, lo, hi: hold.wait(3600))  # hung: straggler path
        add(lambda d, lo, hi: (12345, lo))  # liar: validation path

        def slow(d, lo, hi):
            time.sleep(0.2)
            return min_hash_range(d, lo, hi)

        add(slow)

        lspnet.set_write_drop_percent(20)
        jobs = [(f"soak{i}", 3000 + 500 * i) for i in range(4)]
        results = {}

        def run_job(data, mx):
            c = lsp.Client("127.0.0.1", server.port, PARAMS)
            try:
                results[data] = client_mod.request_once(c, data, mx)
            finally:
                c.close()

        ths = [
            threading.Thread(target=run_job, args=j, daemon=True) for j in jobs
        ]
        for t in ths:
            t.start()
        for t in ths:
            # Generous: this host exposes one CPU core, and a full-suite
            # run adds contention on top of the 20% loss + 8-miner fleet.
            t.join(timeout=240)
            assert not t.is_alive(), "client starved"
        for data, mx in jobs:
            assert results[data] == min_hash_range(data, 0, mx), data
    finally:
        hold.set()
        lspnet.reset_faults()
        server.close()


def test_u64_edge_fleet_e2e():
    """A job at the top of the uint64 nonce space — [2^64 − 3·10^6, 2^64 − 1],
    20-digit decimal templates — through the FULL fleet (scheduler → LSP →
    heterogeneous native + xla miners → min-fold), checked bit-exact against
    the hashlib oracle.  Pins the `Lower, Upper uint64` wire contract
    (reference bitcoin/message.go:21) end-to-end, not just at the ops tier."""
    U64 = (1 << 64) - 1
    lo = U64 - 3_000_000 + 1
    sys_ = MiningSystem(n_miners=1)  # one native/cpu-tier miner...
    try:
        sys_.add_miner(miner_mod.make_search("xla"))  # ...plus one xla-tier
        c = lsp.Client("127.0.0.1", sys_.port, PARAMS)
        try:
            c.write(Message.request("cmu440", lo, U64).marshal())
            msg = Message.unmarshal(c.read())
        finally:
            c.close()
        assert (msg.hash, msg.nonce) == min_hash_range("cmu440", lo, U64)
    finally:
        sys_.close()


def test_server_logs_health(caplog):
    """The server shell periodically logs scheduler stats + recovery
    counters (the observability surface the reference's LOGF scaffold
    implies, bitcoin/server/server.go:26-39)."""
    import logging

    logger = logging.getLogger("test.health")
    server = lsp.Server(0, PARAMS)
    sched = Scheduler(min_chunk=500)
    threading.Thread(
        target=server_mod.serve,
        args=(server, sched),
        kwargs={
            "log": logger,
            "tick_interval": 0.05,
            "health_interval": 0.2,
        },
        daemon=True,
    ).start()
    try:
        with caplog.at_level(logging.INFO, logger="test.health"):
            c = lsp.Client("127.0.0.1", server.port, PARAMS)
            mc = lsp.Client("127.0.0.1", server.port, PARAMS)
            threading.Thread(
                target=miner_mod.run_miner,
                args=(mc, miner_mod.make_search("cpu")),
                daemon=True,
            ).start()
            try:
                assert client_mod.request_once(c, "health", 2000) == (
                    min_hash_range("health", 0, 2000)
                )
                # Wait for a health line that has SEEN the fleet: the first
                # line can beat the miner's Join (ticker t=0.2 vs conn
                # handshake), and repeats are deduped, so polling for the
                # bare prefix races the join on a fast box.
                deadline = time.monotonic() + 5.0
                while (
                    time.monotonic() < deadline
                    and "'miners': 1" not in caplog.text
                ):
                    time.sleep(0.1)
            finally:
                c.close()
        assert "health {" in caplog.text
        assert "'miners': 1" in caplog.text
        assert "chunks_assigned" in caplog.text
        assert "jobs_completed" in caplog.text
    finally:
        server.close()


@pytest.mark.slow
def test_mesh_miner_cli_subprocess_fleet():
    """The --devices CLI path as real subprocesses: server + an 8-virtual-
    CPU-device mesh miner (BMT_FORCE_CPU_DEVICES — env vars alone can't
    override the boot platform here) + client, oracle-exact Result.
    Covers the pipelined sharded search behind the actual binary."""
    import os
    import subprocess
    import sys
    import time
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    port = 3000 + (os.getpid() * 6151) % 50000
    env = {
        **os.environ,
        "PYTHONPATH": str(repo) + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    server = subprocess.Popen(
        [sys.executable, "-m", "bitcoin_miner_tpu.apps.server", str(port)],
        cwd=str(repo), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )
    miner = None
    try:
        import select

        deadline = time.monotonic() + 30
        up = False
        while not up:
            assert time.monotonic() < deadline, "server did not come up"
            assert server.poll() is None, "server died at startup"
            ready, _, _ = select.select([server.stdout], [], [], 1.0)
            if ready:
                up = "listening" in (server.stdout.readline() or "")
        miner = subprocess.Popen(
            [sys.executable, "-m", "bitcoin_miner_tpu.apps.miner",
             f"127.0.0.1:{port}", "--devices", "8"],
            cwd=str(repo),
            env={**env, "BMT_FORCE_CPU_DEVICES": "8", "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        out = subprocess.run(
            [sys.executable, "-m", "bitcoin_miner_tpu.apps.client",
             f"127.0.0.1:{port}", "meshcli", "300000"],
            cwd=str(repo), env=env, capture_output=True, text=True,
            timeout=180,
        )
        h, n = min_hash_range("meshcli", 0, 300000)
        assert out.stdout.strip() == f"Result {h} {n}", out.stdout
    finally:
        for p in (miner, server):
            if p is not None and p.poll() is None:
                p.kill()
