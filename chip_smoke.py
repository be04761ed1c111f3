"""Chip smoke: the served mining path on a TPU, end to end, in one command.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh miner against the one-chip miner

It drives the path a user runs, with the flags a user gets:
``apps.client -> apps.server (scheduler) -> LSP -> apps.miner -> the pallas
kernel``, at the reference deployment's job shape (BASELINE.json configs 2,
3 and 5: data ``cmu440``, up to 2e10 nonces).  Phases on one chip:

1. oracle: maxNonce 1.2e8.  The d=8 and d=9 digit classes exceed the host
   fold budget (1e7 lanes) and run on the device; the answer must equal the
   native C++ oracle's, itself cross-checked against hashlib on 1e6 nonces.
2. long: maxNonce 2e10.  The answer must hash to itself, lie in range, and
   be the minimum of two 1e7-nonce sub-ranges the native oracle sweeps.
3. restart (config 5): maxNonce 6e9, run once cleanly; then again with the
   miner SIGKILLed mid-job and a new one started once the chip is free.
   The answer must equal the clean run's.

With ``--chips 4`` it runs only ``apps.miner --devices 4`` and the one-chip
miner on phases 1 and 2; their answers must agree bit for bit and with the
oracle, the mesh miner must deliver at least ``MESH_MIN_SPEEDUP`` times the
one-chip miner's rate on the long job, and the mesh must span four devices
with the operands sharded over all of them.

One process per chip: only the miners touch a JAX backend.  The server, the
clients and this process stay off JAX until the last miner has exited; then
this process reads the device for its last line, which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero without it.  Speeds printed here are smoke
readings, not benchmarks; the mesh/one-chip ratio is the one that is held
to a floor.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
DATA = "cmu440"
ORACLE_MAX = 120_000_000
LONG_MAX = 20_000_000_000
RESTART_MAX = 6_000_000_000
HOST_FOLD_LANES = 10**7  # nonces 0..9,999,999: the d <= 7 classes
SUB_RANGE = 10**7
XCHECK_RANGE = 10**6
SECOND_SUB_LO = 15_000_000_000
STARTUP_S = 300.0  # spawn -> the miner's resolved line (JAX init, no compile)
FIRST_JOB_S = 600.0  # includes the kernel's compile
JOB_S = 300.0
# Four chips against one on the same long job: near-linear scaling minus
# the job's fixed costs (host fold, chunk ramp), well above the 1.0x of a
# mesh whose dispatches put every row on one device.
MESH_MIN_SPEEDUP = 3.0
# The miner's per-chunk line (BMT_MINER_LOG): the chunk, and its running
# counts of lanes swept on the device and min-folded on the host.
DONE_RX = r"\bdone\s+\[(\d+),(\d+)\].* lanes device (\d+) host fold (\d+)"
# What every miner must resolve with the default flags (no MINER_FLAGS).
EXPECT_PLATFORM, EXPECT_TIER = "tpu", "pallas"
MINER_FLAGS: tuple = ()


class Failed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


class Child:
    """One subprocess of the path; a reader thread per stream keeps its
    lines with the time each arrived."""

    def __init__(self, name: str, module: str, *args, env=None) -> None:
        self.name = name
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module, *map(str, args)],
            cwd=REPO, env=env, stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        self.lines: list = []
        self._cv = threading.Condition()
        self._readers = [
            threading.Thread(target=self._read, args=(s,), daemon=True)
            for s in (self.proc.stdout, self.proc.stderr)
        ]
        for t in self._readers:
            t.start()

    def _read(self, stream) -> None:
        for line in stream:
            with self._cv:
                self.lines.append((time.monotonic(), line.rstrip("\n")))
                self._cv.notify_all()
        with self._cv:
            self._cv.notify_all()

    def wait_line(self, pattern: str, timeout: float, start: int = 0):
        """The first line at index >= ``start`` matching ``pattern``:
        ``(index, arrival time, match)``."""
        rx = re.compile(pattern)
        end = time.monotonic() + timeout
        i = start
        with self._cv:
            while True:
                while i < len(self.lines):
                    m = rx.search(self.lines[i][1])
                    if m:
                        return i, self.lines[i][0], m
                    i += 1
                if self.proc.poll() is not None and not any(
                    t.is_alive() for t in self._readers
                ):
                    raise Failed(
                        f"{self.name} exited ({self.proc.returncode}) before "
                        f"/{pattern}/\n{self.tail()}"
                    )
                left = end - time.monotonic()
                if left <= 0:
                    raise Failed(
                        f"{self.name}: no /{pattern}/ in {timeout:.0f} s\n"
                        f"{self.tail()}"
                    )
                self._cv.wait(min(left, 1.0))

    def tail(self, n: int = 40) -> str:
        with self._cv:
            return "\n".join(f"  {self.name}| {ln}" for _, ln in self.lines[-n:])

    def stop(self, sig=signal.SIGTERM, timeout: float = 60.0) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for t in self._readers:
            t.join(5)
        return self.proc.returncode


class Fleet:
    """Every process the smoke starts; :meth:`close` stops them all."""

    def __init__(self) -> None:
        self.children: list = []
        self.port = None

    def start(self, name, module, *args, env=None) -> Child:
        c = Child(name, module, *args, env=env)
        self.children.append(c)
        return c

    def server(self) -> Child:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        srv = self.start("server", "bitcoin_miner_tpu.apps.server", port)
        srv.wait_line(r"^Server listening on port \d+$", 60)
        self.port = port
        return srv

    def miner(self, name: str, *flags):
        """Start a miner with default flags (plus ``flags``); return it and
        the tier and devices it resolved, which must be pallas on tpu."""
        env = dict(os.environ, BMT_MINER_LOG="1")  # per-chunk done lines
        m = self.start(
            name, "bitcoin_miner_tpu.apps.miner", f"127.0.0.1:{self.port}",
            *MINER_FLAGS, *flags, env=env,
        )
        _, t, mt = m.wait_line(r"^miner: resolved (\{.*\})$", STARTUP_S)
        info = json.loads(mt.group(1))
        say(f"{name}: resolved {json.dumps(info)} ({t - m.t0:.2f} s after spawn)")
        check(
            info["platform"] == EXPECT_PLATFORM,
            f"{name} resolved platform {info['platform']!r}, not {EXPECT_PLATFORM}",
        )
        check(
            info["tier"] == EXPECT_TIER,
            f"{name} resolved tier {info['tier']!r}, not {EXPECT_TIER}",
        )
        m.ready_at = t
        return m, info

    def request(self, max_nonce: int) -> Child:
        return self.start(
            "client", "bitcoin_miner_tpu.apps.client",
            f"127.0.0.1:{self.port}", DATA, max_nonce,
        )

    def close(self) -> None:
        for c in self.children:
            c.stop(signal.SIGKILL, timeout=30)

    def dump(self, out: Path) -> None:
        """Every child's lines, stamped in seconds since it was spawned."""
        out.mkdir(parents=True, exist_ok=True)
        for i, c in enumerate(self.children):
            with open(out / f"{i:02d}-{c.name}.log", "w") as f:
                for t, ln in c.lines:
                    f.write(f"{t - c.t0:10.3f} {ln}\n")


def result_of(client: Child, timeout: float):
    """The client's answer ``(hash, nonce)`` and when it arrived."""
    _, t, m = client.wait_line(r"^(Result (\d+) (\d+)|Disconnected)$", timeout)
    check(m.group(2) is not None, "client printed Disconnected")
    client.stop()
    return (int(m.group(2)), int(m.group(3))), t


def device_holders() -> dict:
    """pid -> cmdline of every process holding a TPU device file open."""
    held = {}
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if re.fullmatch(r"/dev/(accel\d+|vfio/\d+)", target):
            pid = fd.split("/")[2]
            try:
                cmd = Path(f"/proc/{pid}/cmdline").read_bytes()
            except OSError:
                continue
            held[int(pid)] = cmd.replace(b"\0", b" ").decode(errors="replace")
    return held


def stop_miner(m: Child, sig=signal.SIGTERM) -> float:
    """Stop a miner, reap it, and wait until no process holds the chip's
    device files: the next miner must find the chip free.  Returns the
    seconds from the signal until the chip was free."""
    t = time.monotonic()
    rc = m.stop(sig)
    if sig == signal.SIGTERM:
        check(rc == 0, f"{m.name} exited {rc} on SIGTERM\n{m.tail()}")
    while True:
        held = device_holders()
        if not held:
            return time.monotonic() - t
        if time.monotonic() - t > 60:
            say(f"chip still held 60 s after {m.name} stopped: {held}")
            return time.monotonic() - t
        time.sleep(0.05)


def exit_lanes(m: Child):
    """(device lanes, host-fold lanes) from a stopped miner's last line."""
    _, _, mt = m.wait_line(r"lanes on device (\d+), host fold (\d+)", 0)
    return int(mt.group(1)), int(mt.group(2))


class Oracle:
    """The native C++ sweep (built from native/sha256_sweep.cc) and hashlib,
    both on this host: the reference every device answer is held to."""

    def __init__(self) -> None:
        from bitcoin_miner_tpu import native
        from bitcoin_miner_tpu.bitcoin.hash import hash_nonce, min_hash_range

        check(native.available(), "the native oracle did not build")
        self.native = native.min_hash_range_native
        self.hash_nonce = hash_nonce
        lo = ORACLE_MAX - XCHECK_RANGE + 1
        got, ref = self.native(DATA, lo, ORACLE_MAX), min_hash_range(
            DATA, lo, ORACLE_MAX
        )
        check(got == ref, f"native {got} != hashlib {ref} on [{lo}, {ORACLE_MAX}]")
        t = time.monotonic()
        self.oracle_job = self.native(DATA, 0, ORACLE_MAX)
        say(
            f"oracle: native min over [0, {ORACLE_MAX}] = {self.oracle_job} "
            f"in {time.monotonic() - t:.2f} s; matches hashlib on "
            f"[{lo}, {ORACLE_MAX}]"
        )

    def check_min(self, ans, max_nonce: int, what: str) -> None:
        """``ans`` hashes to itself, lies in range, and is the minimum of a
        sub-range around it and of a fixed one elsewhere."""
        h, n = ans
        check(0 <= n <= max_nonce, f"{what}: nonce {n} outside [0, {max_nonce}]")
        check(self.hash_nonce(DATA, n) == h, f"{what}: {ans} is not hash_nonce")
        lo = max(0, min(n - SUB_RANGE // 2, max_nonce - SUB_RANGE + 1))
        near = self.native(DATA, lo, lo + SUB_RANGE - 1)
        check(near == ans, f"{what}: {ans} but [{lo}, +1e7) has min {near}")
        far_lo = min(SECOND_SUB_LO, max_nonce - SUB_RANGE + 1)
        far = self.native(DATA, far_lo, far_lo + SUB_RANGE - 1)
        check(far >= ans, f"{what}: {ans} but [{far_lo}, +1e7) has min {far}")


class Job:
    """One client job served by one miner, read from the miner's lines."""

    def __init__(self, fleet: Fleet, miner: Child, max_nonce: int, timeout: float):
        mark = len(miner.lines)
        t = time.monotonic()
        self.ans, t_res = result_of(fleet.request(max_nonce), timeout)
        self.wall = t_res - t
        # The first chunk Result that ran on the device (d >= 8), and the
        # lane counts once the job's last chunk (the one holding maxNonce)
        # is done; both counts are the miner's running totals.
        i = mark
        self.t_first_device = None
        while True:
            i, t_done, m = miner.wait_line(DONE_RX, 30, start=i)
            lo, hi, dev, host = map(int, m.groups())
            if self.t_first_device is None and hi >= HOST_FOLD_LANES:
                self.t_first_device = t_done
            if hi == max_nonce:
                self.lanes = (dev, host)
                break
            i += 1


def first_device_result(name: str, m: Child, job: Job) -> str:
    return (
        f"{name}'s first device Result came {job.t_first_device - m.t0:.3f} s "
        f"after spawn, {job.t_first_device - m.ready_at:.3f} s after its "
        "resolved line (kernel trace, compile or cache load included)"
    )


def check_lanes(name: str, what: str, lanes, max_nonce: int) -> None:
    """Every nonce of a job was swept: the d <= 7 classes in the host fold,
    the rest on the device.  More is legal (the scheduler may re-issue a
    slow chunk) and is printed."""
    want = (max_nonce + 1 - HOST_FOLD_LANES, HOST_FOLD_LANES)
    extra = sum(lanes) - sum(want)
    say(
        f"{name} {what}: {lanes[0]} lanes on the device, {lanes[1]} in the "
        f"host fold (each nonce once: {want[0]} and {want[1]}; "
        f"{extra} lanes swept twice)"
    )
    check(
        lanes[0] >= want[0] and lanes[1] >= want[1],
        f"{name} {what}: lanes {lanes} < {want}",
    )


def run_oracle_and_long(fleet: Fleet, oracle: Oracle, name: str, *flags):
    """Phases 1 and 2 on one freshly started miner; returns both answers,
    what the miner resolved, and its delivered rate on the long job."""
    m, info = fleet.miner(name, *flags)
    job = Job(fleet, m, ORACLE_MAX, FIRST_JOB_S)
    check(
        job.ans == oracle.oracle_job,
        f"{name} oracle job: {job.ans} != native oracle {oracle.oracle_job}",
    )
    say(
        f"{name} oracle job [0, {ORACLE_MAX}]: Result {job.ans} bit-exact vs "
        f"the native oracle; wall {job.wall:.3f} s; "
        + first_device_result(name, m, job)
    )
    check_lanes(name, "oracle job", job.lanes, ORACLE_MAX)
    long = Job(fleet, m, LONG_MAX, JOB_S)
    oracle.check_min(long.ans, LONG_MAX, f"{name} long job")
    rate = (LONG_MAX + 1) / long.wall
    say(
        f"{name} long job [0, {LONG_MAX}]: Result {long.ans} checked; wall "
        f"{long.wall:.3f} s; delivered {rate:,.0f} nonces/s (smoke reading, "
        "not a benchmark)"
    )
    lanes = tuple(b - a for a, b in zip(job.lanes, long.lanes))
    check_lanes(name, "long job", lanes, LONG_MAX)
    freed = stop_miner(m)
    total = exit_lanes(m)
    check(
        all(a >= b for a, b in zip(total, long.lanes)),
        f"{name}: exit line lanes {total} < {long.lanes}",
    )
    say(f"{name} stopped; chip free {freed:.3f} s after SIGTERM")
    return job.ans, long.ans, info, rate


def run_restart(fleet: Fleet, oracle: Oracle) -> None:
    """Phase 3: a clean run, then the same job with the miner killed."""
    m, _ = fleet.miner("miner-b")
    job = Job(fleet, m, RESTART_MAX, JOB_S)
    clean = job.ans
    oracle.check_min(clean, RESTART_MAX, "restart job, clean run")
    say(
        f"restart job [0, {RESTART_MAX}] clean run: Result {clean}; wall "
        f"{job.wall:.3f} s; " + first_device_result("miner-b", m, job)
    )
    mark = len(m.lines)
    t_sub = time.monotonic()
    client = fleet.request(RESTART_MAX)
    m.wait_line(DONE_RX, JOB_S, start=mark)
    t_kill = time.monotonic()
    freed = stop_miner(m, signal.SIGKILL)
    say(
        f"miner-b SIGKILLed {t_kill - t_sub:.3f} s into the job, after its "
        f"first chunk Result; chip free {freed:.3f} s after the kill"
    )
    m2, _ = fleet.miner("miner-c")
    ans, t_res = result_of(client, JOB_S)
    check(ans == clean, f"restart job after the kill: {ans} != clean run {clean}")
    say(
        f"restart job after the kill: Result {ans} equals the clean run; "
        f"new miner resolved {m2.ready_at - t_kill:.3f} s after the kill, "
        f"job answered {t_res - t_kill:.3f} s after the kill"
    )
    stop_miner(m2)


def last_device(chips: int) -> dict:
    """Read the device in this process (every miner has exited)."""
    import jax

    devs = jax.devices()
    check(
        devs[0].platform == EXPECT_PLATFORM,
        f"jax found {devs[0].platform}, not {EXPECT_PLATFORM}",
    )
    check(len(devs) >= chips, f"{len(devs)} devices < --chips {chips}")
    if chips > 1:
        # The mesh miner's own placement code, on this host's chips: the
        # mesh spans distinct devices and a dispatch's rows land on all.
        import numpy as np

        from bitcoin_miner_tpu.parallel import default_mesh
        from bitcoin_miner_tpu.parallel.sweep import shard_operands

        mesh = default_mesh(chips)
        ids = {d.id for d in mesh.devices.flat}
        check(len(ids) == chips, f"mesh devices {sorted(ids)}")
        rows = chips * 1024
        _, tail, bounds = shard_operands(
            np.zeros(8, np.uint32), np.zeros((rows, 16), np.uint32),
            np.zeros((rows, 2), np.int32), mesh, "miners",
        )
        for arr in (tail, bounds):
            shards = arr.addressable_shards
            check(
                {s.device.id for s in shards} == ids
                and all(s.data.shape[0] == rows // chips for s in shards),
                f"operands not sharded over {sorted(ids)}: "
                f"{[(s.device.id, s.data.shape) for s in shards]}",
            )
        say(
            f"mesh: {chips} distinct devices {sorted(ids)}; a dispatch's "
            f"operand rows split {rows // chips} per device"
        )
    dev = devs[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: the mesh miner (--devices 4) against the one-chip miner only",
    )
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    fleet = Fleet()
    try:
        oracle = Oracle()
        fleet.server()
        if args.chips == 1:
            run_oracle_and_long(fleet, oracle, "miner-a")
            run_restart(fleet, oracle)
        else:
            mesh = run_oracle_and_long(fleet, oracle, "miner-mesh", "--devices", "4")
            check(
                mesh[2]["mesh"] == 4 and len(set(mesh[2]["mesh_device_ids"])) == 4,
                f"mesh miner resolved {mesh[2]}",
            )
            one = run_oracle_and_long(fleet, oracle, "miner-one")
            check(
                mesh[:2] == one[:2],
                f"mesh miner {mesh[:2]} != one-chip miner {one[:2]}",
            )
            say(f"mesh and one-chip miners agree bit for bit: {one[:2]}")
            speedup = mesh[3] / one[3]
            say(
                f"mesh/one-chip delivered rate on the long job: {speedup:.3f}x "
                f"(floor {MESH_MIN_SPEEDUP}x)"
            )
            check(
                speedup >= MESH_MIN_SPEEDUP,
                f"mesh miner delivered {speedup:.3f}x the one-chip miner's "
                f"rate, under {MESH_MIN_SPEEDUP}x",
            )
        fleet.close()
        device = last_device(args.chips)
    except Failed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        fleet.close()
        fleet.dump(REPO / "chiprun_out" / "chip_smoke")
    say(f"chip_smoke: all phases passed in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
