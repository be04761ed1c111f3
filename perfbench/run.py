"""The benchmark of the served miner: one cell, one seed, one run.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the server's and the miner's flags) and a traffic mix
(``traffic/<name>.json``: a generator from ``generators/<kind>.py`` and its
parameters).  A run:

1. starts ``bitcoin_miner_tpu.apps.server`` and one miner, the latter in
   ``miner_host.py``, the only process that touches jax;
2. warms up: waits for the miner's ``resolved`` line (a TPU and the pallas
   tier, or the run fails), then sends the traffic's warm-up jobs on keys
   the window never uses;
3. drives the traffic for ``--seconds`` from the seed, as clients of the
   server's public port, and times every job from the client's side;
4. stops the miner, reads the chip's memory peak, and checks the answers
   against the plain reference (``reference.py``) on the freed chip;
5. prints one JSON line: with ``--trace 0`` the cell's end-to-end metrics
   (``e2e/<name>.py``), with ``--trace 1`` its per-layer metrics
   (``metrics/<name>.py``), read from a profiler trace of a slice of the
   window and from the server's fleet log over the same slice.

``--cpu-rehearsal`` runs the same steps on the CPU at the traffic's
``rehearsal`` sizes, for the benchmark's own tests; it never reports a TPU.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(os.path.abspath(__file__)).parent  # a symlinked checkout stays itself
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT)]

import procs  # noqa: E402
from procs import Failed  # noqa: E402

RESOLVE_S = 300.0  # miner spawn -> resolved line
WARM_JOB_S = 900.0  # a warm-up job, the first compile included
AFTER_CLOSE_S = 60.0  # how long past the window's close an answer may come


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and the
    metrics that apply to it."""

    def __init__(self, name: str, rehearsal: bool) -> None:
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Failed(f"no workload {name!r} in BENCHMARK.json")
        self.entry = cells[name]
        self.name = name
        self.config = load_json(HERE / "configs" / f"{self.entry['config']}.json")
        self.traffic = load_json(HERE / "traffic" / f"{self.entry['traffic']}.json")
        if rehearsal:
            self.traffic.update(self.traffic.get("rehearsal", {}))
            self.config.update(self.config.get("rehearsal", {}))

        def applies(m):
            return name in m.get("workloads", [name])

        self.e2e = [m for m in bench["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m)]


def sample_jobs(spec: dict, seed: int, jobs: list) -> list:
    """The jobs whose minimum the reference recomputes, drawn from the
    seed: ``spec["jobs"]`` jobs, or every job of ``spec["keys"]`` data
    strings drawn by request; the longest job is always in."""
    answered = [j for j in jobs if j.answer is not None]
    if not answered:
        return []
    rng = random.Random(f"{seed}/check")
    longest = max(answered, key=lambda j: j.nonces)
    if "jobs" in spec:
        rest = [j for j in answered if j is not longest]
        return [longest] + rng.sample(rest, min(len(rest), spec["jobs"] - 1))
    datas = {longest.data}
    pool = [j.data for j in answered]
    while len(datas) < min(spec["keys"], len(set(pool))):
        datas.add(rng.choice(pool))
    return [j for j in answered if j.data in datas]


class Run:
    def __init__(self, args) -> None:
        self.args = args
        self.cell = Cell(args.workload, args.cpu_rehearsal)
        self.dir = HERE / ".runs" / f"{args.workload}-{args.seed}-{args.trace}"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        self.children = []
        self.port = procs.free_port()
        self.tport = procs.free_port() if args.trace else None
        self.trace_marks = {}
        self.fleet_lines = []  # (arrival time, state)

    # -- processes ---------------------------------------------------------

    def start(self, name, argv, env, stdin=False):
        c = procs.Child(name, argv, env, self.dir, stdin=stdin)
        self.children.append(c)
        return c

    def start_server(self) -> None:
        flags = list(self.cell.config["server_flags"])
        if self.tport:
            flags += [f"--telemetry-port={self.tport}",
                      f"--fleet-log={self.dir / 'fleet.jsonl'}"]
        self.server = self.start(
            "server",
            [sys.executable, "-m", "bitcoin_miner_tpu.apps.server", str(self.port), *flags],
            procs.child_env(),
        )
        self.server.wait_line(r"^Server listening on port \d+$", 60)

    def start_miner(self) -> dict:
        flags = list(self.cell.config["miner_flags"])
        if self.tport:
            flags += ["--telemetry", f"127.0.0.1:{self.tport}",
                      "--telemetry-interval", "0.5"]
        extra = {}
        if self.args.cpu_rehearsal:
            extra = {"JAX_PLATFORMS": "cpu"}
            if self.args.fault:
                extra["PERFBENCH_FAULT"] = self.args.fault
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        self.miner = self.start(
            "miner",
            [sys.executable, str(HERE / "miner_host.py"), f"127.0.0.1:{self.port}", *flags],
            procs.child_env(extra), stdin=True,
        )
        _, _, m = self.miner.wait_line(r"^miner: resolved (\{.*\})$", RESOLVE_S)
        info = json.loads(m.group(1))
        want = ("cpu", "xla") if self.args.cpu_rehearsal else ("tpu", "pallas")
        if (info["platform"], info["tier"]) != want:
            raise Failed(f"the miner resolved {info}, not platform {want[0]} "
                         f"with tier {want[1]}")
        if info["device_count"] < self.cell.entry["chips"]:
            raise Failed(f"{info['device_count']} devices, the cell needs "
                         f"{self.cell.entry['chips']}")
        say(f"miner resolved {json.dumps(info)}")
        return info

    def stop_all(self) -> None:
        for c in reversed(self.children):
            c.stop(signal.SIGKILL, timeout=30)
            c.dump(self.dir / f"{c.name}.log")

    # -- traffic -----------------------------------------------------------

    async def warm_up(self) -> None:
        import keys
        from lspjobs import Job, run_job

        for i, (lo, hi) in enumerate(self.cell.traffic["warmup"]):
            job = Job(keys.warm_key(i, self.cell.traffic["data_len"]), lo, hi)
            t = time.monotonic()
            await run_job(self.port, job, t + WARM_JOB_S)
            if job.answer is None:
                raise Failed(f"warm-up job [{lo}, {hi}] got no answer\n{self.miner.tail()}")
            say(f"warm-up job [{lo}, {hi}] answered in {job.done - t:.3f} s")

    def trace_start(self) -> None:
        st = self.miner.command(f"trace_start {self.dir / 'trace'}")
        if "error" in st:
            raise Failed(st["error"])
        self.trace_marks["start"] = st
        say(f"trace started at +{st['t'] - self.t0:.3f} s")

    def trace_stop(self) -> None:
        if "start" not in self.trace_marks or "stop" in self.trace_marks:
            return
        st = self.miner.command("trace_stop", timeout=300)
        if "error" in st:
            raise Failed(st["error"])
        self.trace_marks["stop"] = st
        say(f"trace stopped at +{st['t'] - self.t0:.3f} s "
            f"(written {st['t_written'] - st['t']:.3f} s later)")

    def window(self) -> list:
        gen = load_module(HERE / "generators" / f"{self.cell.traffic['generator']}.py")
        traffic = self.cell.traffic
        timers = []

        def window_start(t0: float) -> None:
            self.t0 = t0
            self.mark0 = self.miner.command("mark")
            if self.args.trace and "trace_from_s" in traffic:
                for key, fn in (("trace_from_s", self.trace_start),
                                ("trace_to_s", self.trace_stop)):
                    tm = threading.Timer(max(0.0, t0 + traffic[key] - time.monotonic()), fn)
                    tm.start()
                    timers.append(tm)

        def boundary(i: int, done: list) -> None:
            """Between two jobs of a closed loop: trace whole jobs."""
            if not self.args.trace or "trace_jobs_from" not in traffic:
                return
            if i == traffic["trace_jobs_from"]:
                self.trace_start()
            elif ("start" in self.trace_marks
                  and time.monotonic() - self.trace_marks["start"]["t"]
                  >= traffic["trace_seconds"]):
                self.trace_stop()

        ctx = types.SimpleNamespace(
            port=self.port, seed=self.args.seed, seconds=self.args.seconds,
            traffic=traffic, job_timeout=AFTER_CLOSE_S + self.args.seconds,
            window_start=window_start, boundary=boundary,
        )
        jobs = asyncio.run(gen.drive(ctx))
        for tm in timers:
            tm.join()
        self.mark1 = self.miner.command("mark")
        self.trace_stop()  # a closed loop may end its window while tracing
        return jobs

    def tail_fleet_log(self, stop: threading.Event) -> None:
        path = self.dir / "fleet.jsonl"
        pos = 0
        while not stop.is_set():
            if path.exists():
                with open(path) as f:
                    f.seek(pos)
                    while True:
                        line = f.readline()
                        if not line.endswith("\n"):
                            break
                        pos = f.tell()
                        self.fleet_lines.append((time.monotonic(), json.loads(line)))
            stop.wait(0.1)

    # -- the whole run -----------------------------------------------------

    def go(self) -> dict:
        stop_tail = threading.Event()
        tail = threading.Thread(target=self.tail_fleet_log, args=(stop_tail,), daemon=True)
        try:
            self.start_server()
            info = self.start_miner()
            asyncio.run(self.warm_up())
            if self.tport:
                tail.start()
            jobs = self.window()
            setup_s = self.t0 - T_START
            if self.tport:
                time.sleep(2.5)  # one more fleet-log beat after the slice
            stop_tail.set()
            self.miner.proc.send_signal(signal.SIGTERM)
            _, _, m = self.miner.wait_line(r'^(\{"exit".*\})$', 120, stream="out")
            exit_state = json.loads(m.group(1))
            if self.miner.proc.wait(60) != 0:
                raise Failed(f"miner host exited {self.miner.proc.returncode}")
            self.server.stop(signal.SIGTERM, timeout=10)
            if not self.args.cpu_rehearsal:
                procs.wait_chip_free()
            checks = self.check(jobs)
        finally:
            stop_tail.set()
            self.stop_all()
        return self.result(info, jobs, setup_s, exit_state, checks)

    # -- correctness -------------------------------------------------------

    def check(self, jobs: list) -> dict:
        """Every answer hashes to itself and lies in range; a sample drawn
        from the seed is the exact minimum by the plain reference, run in
        this process once the miner has freed the chip."""
        import reference

        unanswered = [j for j in jobs if j.answer is None]
        bad = [j for j in jobs if j.answer is not None
               and not reference.answer_ok(j.data, j.lower, j.upper, j.answer)]
        sample = sample_jobs(self.cell.traffic["check"], self.args.seed, jobs)
        by_data = {}
        for j in sample:
            by_data.setdefault(j.data, set()).add((j.lower, j.upper))
        t = time.monotonic()
        reference.use_device(ROOT)
        exact = {}
        for data, bounds in by_data.items():
            for (lo, hi), (e, _) in reference.sweep_min(data, sorted(bounds)).items():
                exact[(data, lo, hi)] = e
        not_min = [j for j in sample if j.answer != exact[(j.data, j.lower, j.upper)]]
        say(f"checked {len(sample)} of {len(jobs)} jobs against the reference "
            f"({sum(j.nonces for j in sample):.4g} nonces) in "
            f"{time.monotonic() - t:.3f} s")
        for j in (unanswered + bad + not_min)[:5]:
            say(f"  wrong or missing: {j.data} [{j.lower}, {j.upper}] -> {j.answer}"
                f" (reference {exact.get((j.data, j.lower, j.upper))})")
        return {
            "unanswered": {"value": len(unanswered), "limit": 0},
            "bad_hash_or_range": {"value": len(bad), "limit": 0},
            "not_the_minimum": {"value": len(not_min), "limit": 0},
            "reference_checked": len(sample),
        }

    # -- the result line ---------------------------------------------------

    def result(self, info, jobs, setup_s, exit_state, checks) -> dict:
        ctx = types.SimpleNamespace(
            jobs=jobs, t0=self.t0, seconds=self.args.seconds, setup_s=setup_s,
            traffic=self.cell.traffic, config=self.cell.config,
        )
        lateness = sorted(j.sent - j.due for j in jobs)
        say(f"window: {len(jobs)} jobs, generator late by at most "
            f"{lateness[-1] * 1e3:.3f} ms (median {lateness[len(lateness) // 2] * 1e3:.3f} ms)")
        say(f"compiles in the window: {self.mark1['compiles'] - self.mark0['compiles']}")
        device = {
            "platform": info["platform"], "kind": info["device_kind"],
            "count": info["device_count"],
            "memory_peak_bytes": exit_state["memory_peak_bytes"],
        }
        out = {"correct": None, "attempted": len(jobs),
               "failed": checks["unanswered"]["value"], "metrics": {},
               "device": device}
        if self.args.trace:
            self.per_layer(ctx, out)
            metrics = self.cell.per_layer
            folder = "metrics"
        else:
            metrics = self.cell.e2e
            folder = "e2e"
        for m in metrics:
            value = load_module(HERE / folder / f"{m['name']}.py").read(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        numbers = {k: v for k, v in checks.items() if isinstance(v, dict)}
        out["correct"] = all(v["value"] <= v["limit"] for v in numbers.values())
        (self.dir / "jobs.json").write_text(json.dumps([j.as_dict(self.t0) for j in jobs]))
        for k, v in numbers.items():
            say(f"check {k} {v['value']} limit {v['limit']}")
        out["checks"] = numbers
        return out

    def per_layer(self, ctx, out) -> None:
        import opcount
        import trace_reduce

        a, b = self.trace_marks["start"], self.trace_marks["stop"]
        ctx.slice_s = b["t"] - a["t"]
        ctx.lanes = b["lanes"] - a["lanes"]
        ctx.ops_per_nonce = min(
            opcount.ops_per_nonce(self.cell.traffic["data_len"], d)
            for d in self.cell.traffic["device_classes"]
        )
        peaks = load_json(HERE / "peaks.json")
        kind = out["device"]["kind"]
        ctx.peak = peaks.get(kind)
        if ctx.peak is None and not self.args.cpu_rehearsal:
            raise Failed(f"no peak for device kind {kind!r} in peaks.json")
        files = sorted((self.dir / "trace").rglob("*.xplane.pb"))
        if not files:
            raise Failed("the profiler wrote no trace")
        ctx.trace = trace_reduce.reduce_trace(str(files[-1]), (0, int(ctx.slice_s * 1e9)))
        ctx.fleet = self.fleet_delta(a["t"], b["t"])
        out["device"]["busy_s"] = ctx.trace.get("busy_s", 0.0)
        out["device"]["window_s"] = ctx.slice_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.trace.get("top_ops", [])],
            "idle_gaps": [[n, s] for n, s in ctx.trace.get("top_gaps", [])],
        }
        say(f"traced slice {ctx.slice_s:.3f} s: device busy {out['device']['busy_s']:.6f} s, "
            f"{ctx.lanes} device lanes, {ctx.ops_per_nonce} ops per nonce")

    def fleet_delta(self, t_a: float, t_b: float):
        """Counters and histogram sums of the fleet log, first line after
        ``t_b`` minus first line after ``t_a``."""
        after = lambda t: next((s for at, s in self.fleet_lines if at >= t), None)  # noqa: E731
        a, b = after(t_a), after(t_b)
        if a is None or b is None or a is b:
            return None
        counters = {k: b["counters"].get(k, 0) - a["counters"].get(k, 0)
                    for k in b["counters"]}
        hists = {}
        for k, h in b["hists"].items():
            h0 = a["hists"].get(k, {"count": 0.0, "mean": 0.0})
            n = h["count"] - h0["count"]
            hists[k] = (n, h["count"] * h["mean"] - h0["count"] * h0["mean"])
        return {"counters": counters, "hists": hists}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the benchmark's own tests: run on the CPU at tiny sizes")
    ap.add_argument("--fault", choices=("half", "alter"),
                    help="with --cpu-rehearsal: break the miner's answers")
    args = ap.parse_args(argv)
    if args.fault and not args.cpu_rehearsal:
        ap.error("--fault needs --cpu-rehearsal")
    try:
        out = Run(args).go()
    except Failed as e:
        say(f"benchmark FAILED: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
