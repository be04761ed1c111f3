"""Headline benchmark: hash-search throughput on one chip.

Measures the flagship workload — the BASELINE config-1/2 job shape
(``data='cmu440'``), swept with the fastest available tier (Pallas on TPU,
fused-jnp elsewhere) — and always prints exactly ONE JSON line on stdout::

    {"metric": "nonces_per_sec_per_chip", "value": N, "unit": "nonces/s",
     "vs_baseline": N / 1e9, "platform": ..., "device_kind": ...,
     "backend": ...}

``vs_baseline`` is the ratio to the north-star target of 1e9 nonces/sec/chip
(BASELINE.json:5; the reference itself publishes no numbers — BASELINE.md).

Device: without ``--cpu`` the benchmark runs on the TPU or not at all —
when jax finds no TPU (or its backend fails to start, or hangs past the
watchdog), the one JSON line carries ``{"error": ...}`` and the exit code
is non-zero; a CPU number is never reported under a TPU name.  ``--cpu``
benches the CPU backend explicitly (``--devices N --cpu``: N virtual CPU
devices).  Diagnostics go to stderr; stdout carries only the JSON line.

Before timing, the run bit-exactness-checks the kernel against the hashlib
oracle on a digit-boundary-crossing range; a mismatch aborts the benchmark.
Correctness contract: ``Hash = BigEndian.Uint64(SHA256("<data> <nonce>")
[:8])`` per the reference ``bitcoin/hash.go:13-17``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class Watchdog:
    """Guard against in-process hangs: a wedged backend init or compile
    never raises — without this the bench would die with no JSON line.

    Heartbeat-based: the monitor thread hard-exits with an error JSON line
    if ``beat()`` hasn't been called for ``timeout`` seconds.  ``os._exit``
    because a wedged PJRT client cannot be unwound by exceptions.
    """

    def __init__(self, timeout: float, stage: str = "backend init") -> None:
        self.timeout = timeout
        self.stage = stage
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def beat(self, stage: str = None) -> None:
        self._last = time.monotonic()
        if stage is not None:
            self.stage = stage

    def disarm(self) -> None:
        self._stop.set()

    def _run(self) -> None:
        while not self._stop.wait(min(self.timeout / 4, 5.0)):
            idle = time.monotonic() - self._last
            if idle > self.timeout:
                log(f"WATCHDOG: '{self.stage}' hung {idle:.0f}s; aborting")
                # Single os.write (atomic for short writes), NOT print():
                # if the main thread is mid-emit on a slow pipe, interleaved
                # writes would break the one-valid-JSON-line contract.  The
                # leading newline terminates any partial main-thread line.
                err = json.dumps(
                    {"error": f"{self.stage} hung >{self.timeout:.0f}s"}
                )
                os.write(sys.stdout.fileno(), f"\n{err}\n".encode())
                sys.stderr.flush()
                os._exit(2)


def run_sharded(args, watchdog) -> int:
    """--devices N: bench the multi-chip sharded sweep (parallel/sweep.py)
    over an N-device mesh: N TPU chips, or with ``--cpu`` N virtual CPU
    devices, which exercise the sharding path itself."""
    n = args.devices
    import jax

    from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
    from bitcoin_miner_tpu.parallel import default_mesh, sweep_min_hash_sharded
    from bitcoin_miner_tpu.utils.platform import (
        enable_compile_cache,
        pallas_platform,
    )

    enable_compile_cache()
    watchdog.beat("mesh init")
    devs = jax.devices()
    if len(devs) < n:
        emit({"error": f"{len(devs)} devices < requested {n}"})
        return 1
    platform = devs[0].platform
    mesh = default_mesh(n)
    log(f"sharded bench: mesh of {n} x {platform}")

    def run(lo, hi, stats=None):
        return sweep_min_hash_sharded(
            "cmu440", lo, hi, mesh=mesh, stats=stats
        )

    # Correctness gate (digit-boundary-crossing, same as single-chip).
    watchdog.beat("sharded correctness gate (first compile)")
    r = run(95, 1205)
    expect = min_hash_range("cmu440", 95, 1205)
    if (r.hash, r.nonce) != expect:
        emit({"error": "sharded correctness gate failed", "devices": n})
        return 1
    log(f"correctness OK: hash={r.hash} nonce={r.nonce}")

    base = 10**9
    run(base, base + 10**5 - 1)  # compile the timed shape class

    def timed(count, stats=None):
        watchdog.beat(f"sharded sweep of {count} nonces")
        t0 = time.perf_counter()
        r = run(base, base + count - 1, stats)
        dt = time.perf_counter() - t0
        assert r.lanes_swept == count
        watchdog.beat()
        return dt

    # stats resets on every sweep entry, so the last iteration's numbers
    # are the ones reported — no extra stats-only sweep needed.
    stats: dict = {}
    count = 10**6 if platform == "cpu" else 10**8
    dt = timed(count, stats)
    while dt < 4.0 and count < 4 * 10**9:
        count = min(count * max(2, int(4.0 / max(dt, 1e-3))), 4 * 10**9)
        dt = timed(count, stats)
    watchdog.disarm()
    rate = count / dt
    log(
        f"swept {count} nonces on {n} devices in {dt:.3f}s -> "
        f"{rate:,.0f} nonces/s total, {rate / n:,.0f}/device; "
        f"{stats['dispatches']} dispatches, "
        f"fetch wait {stats['fetch_wait_seconds']:.3f}s"
    )
    emit(
        {
            "metric": "nonces_per_sec_total_sharded",
            "value": round(rate),
            "unit": "nonces/s",
            "vs_baseline": round(rate / 1e9, 4),
            "platform": platform,
            "devices": n,
            "per_device": round(rate / n),
            "dispatches": stats["dispatches"],
            "fetch_wait_seconds": round(stats["fetch_wait_seconds"], 3),
            "backend": "pallas" if platform == "tpu" else "xla",
            "pallas_platform": pallas_platform(),
        }
    )
    return 0


def run_sieve_compare(args, watchdog) -> int:
    """--sieve-compare: same-seed sieve-vs-baseline kernel legs (ISSUE 13).

    Runs the SAME data + nonce range through the baseline kernel and the
    two-stage sieve kernel of the resolved jax tier and emits one JSON
    line with both rates — the BENCH_pr13 artifact.  Both legs are
    bit-exactness-gated against the hashlib oracle first (including the
    sieve's conservative-tie contract on a digit-boundary-crossing
    range); ``--fast`` swaps the timed windows for tiny tier-1-sized ones
    and adds an interpret-mode pallas sieve leg, so the correctness half
    runs on every PR without the full-speed legs' wall-clock.

    Honesty contract: ``auto_tune_sieve`` records which kernel
    :func:`bitcoin_miner_tpu.ops.sweep.auto_tune` actually picks for this
    backend — if the sieve loses here, the default demonstrably keeps the
    baseline kernel and both numbers still land in the JSON.
    """
    import jax

    from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
    from bitcoin_miner_tpu.ops.sweep import auto_tune, sweep_min_hash
    from bitcoin_miner_tpu.utils.platform import (
        enable_compile_cache,
        is_tpu,
        pallas_platform,
    )

    enable_compile_cache()
    # Own-benchmark mode: the single-chip headline knobs don't apply —
    # say so instead of silently dropping them (same contract as the
    # --devices branch).
    for flag, val in (("--autotune", args.autotune), ("--profile", args.profile)):
        if val:
            log(f"WARNING: {flag} is ignored in --sieve-compare mode")
    watchdog.beat("device init (jax.devices)")
    dev = jax.devices()[0]
    platform = dev.platform
    if args.backend in ("pallas", "xla"):
        backend = args.backend
    elif args.backend == "native":
        emit({"error": "--sieve-compare applies to the jax tiers only"})
        return 1
    else:
        backend = "pallas" if is_tpu() else "xla"
    data = "cmu440"  # the flagship BASELINE shape

    # -- correctness gates: both kernels, digit-boundary-crossing range --
    lo, hi = 95, 1205
    expect = min_hash_range(data, lo, hi)
    watchdog.beat("sieve-compare correctness gates (first compiles)")
    for sieve in (False, True):
        r = sweep_min_hash(data, lo, hi, backend=backend, max_k=2, sieve=sieve)
        if (r.hash, r.nonce) != expect:
            emit(
                {
                    "error": "sieve-compare correctness gate failed",
                    "sieve": sieve,
                    "kernel": [r.hash, r.nonce],
                    "oracle": list(expect),
                    "backend": backend,
                }
            )
            return 1
    interp_ok = None
    if args.fast:
        # Tier-1 also covers the REAL prize path in interpreter mode: the
        # pallas sieve kernel (SMEM threshold scratch, survivor-only
        # pass 2) bit-exact across a digit boundary.
        watchdog.beat("interpret-mode pallas sieve gate")
        ri = sweep_min_hash(
            data, 985, 1040, backend="pallas", interpret=True,
            batch=2, max_k=2, sieve=True,
        )
        interp_ok = (ri.hash, ri.nonce) == min_hash_range(data, 985, 1040)
        if not interp_ok:
            emit({"error": "interpret-mode pallas sieve gate failed"})
            return 1
    log("correctness OK: baseline and sieve match the oracle")

    # -- same-seed timed legs ------------------------------------------------
    base = 10**9

    def timed(n: int, sieve: bool) -> float:
        watchdog.beat(f"timed {'sieve' if sieve else 'baseline'} sweep of {n}")
        t0 = time.perf_counter()
        r = sweep_min_hash(
            data, base, base + n - 1, backend=backend, sieve=sieve
        )
        dt = time.perf_counter() - t0
        assert r.lanes_swept == n
        watchdog.beat()
        return dt

    warm = 10**5 if args.fast else 10**6
    timed(warm, False)  # compile both shape classes
    timed(warm, True)
    if args.fast:
        n = 2 * 10**5
    else:
        n = 4 * 10**6
        dt = timed(n, False)
        while dt < 4.0 and n < 16 * 10**9:
            n = min(n * max(2, int(4.0 / max(dt, 1e-3))), 16 * 10**9)
            dt = timed(n, False)
    # Interleave two rounds per leg and keep each leg's best: this 2-core
    # box's wall clock swings run-to-run (ROADMAP), and the PAIR on the
    # same seed is the honest comparison.
    dt_base = min(timed(n, False), timed(n, False))
    dt_sieve = min(timed(n, True), timed(n, True))
    watchdog.disarm()
    r_base = n / dt_base
    r_sieve = n / dt_sieve
    _, _, _, tuned_sieve, _, _ = auto_tune(backend, None, None)
    log(
        f"swept {n} nonces twice: baseline {r_base:,.0f} n/s, sieve "
        f"{r_sieve:,.0f} n/s (ratio {r_sieve / r_base:.3f}); auto_tune "
        f"keeps the {'sieve' if tuned_sieve else 'baseline'} kernel for "
        f"backend={backend}"
    )
    out = {
        "metric": "sieve_compare",
        "unit": "nonces/s",
        "data": data,
        "count": n,
        "baseline_nps": round(r_base),
        "sieve_nps": round(r_sieve),
        "ratio": round(r_sieve / r_base, 4),
        "auto_tune_sieve": bool(tuned_sieve),
        "kept_kernel": "sieve" if tuned_sieve else "baseline",
        "platform": platform,
        "pallas_platform": pallas_platform(),
        "backend": backend,
        "bitexact": True,
        "fast": bool(args.fast),
    }
    if interp_ok is not None:
        out["interpret_pallas_sieve_bitexact"] = bool(interp_ok)
    emit(out)
    return 0


def run_factor_compare(args, watchdog) -> int:
    """--factor-compare: same-seed factored-vs-baseline kernel legs
    (ISSUE 14).

    Runs the SAME data + nonce range through the unfactored kernel and
    the outer/inner digit-factored kernel of the resolved jax tier —
    both legs at the backend's default sieve rung, so the pair isolates
    the factoring — and emits one JSON line with both rates (the
    BENCH_pr14 artifact).  Both legs are bit-exactness-gated against the
    hashlib oracle first on a digit-boundary-crossing range; ``--fast``
    swaps the timed windows for tiny tier-1-sized ones and adds
    interpret-mode pallas factored gates (plain AND composed with the
    sieve), so the correctness half runs on every PR.

    Honesty contract: ``auto_tune_factored`` records which kernel
    :func:`bitcoin_miner_tpu.ops.sweep.auto_tune` actually picks for
    this backend — if the factored leg loses here, the default
    demonstrably keeps the baseline kernel and both numbers still land.
    """
    import jax

    from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
    from bitcoin_miner_tpu.ops.sweep import auto_tune, sweep_min_hash
    from bitcoin_miner_tpu.utils.platform import (
        enable_compile_cache,
        is_tpu,
        pallas_platform,
    )

    enable_compile_cache()
    for flag, val in (("--autotune", args.autotune), ("--profile", args.profile)):
        if val:
            log(f"WARNING: {flag} is ignored in --factor-compare mode")
    watchdog.beat("device init (jax.devices)")
    dev = jax.devices()[0]
    platform = dev.platform
    if args.backend in ("pallas", "xla"):
        backend = args.backend
    elif args.backend == "native":
        emit({"error": "--factor-compare applies to the jax tiers only"})
        return 1
    else:
        backend = "pallas" if is_tpu() else "xla"
    data = "cmu440"  # the flagship BASELINE shape

    # -- correctness gates: both kernels, digit-boundary-crossing range --
    lo, hi = 95, 1205
    expect = min_hash_range(data, lo, hi)
    watchdog.beat("factor-compare correctness gates (first compiles)")
    for factored in (False, True):
        r = sweep_min_hash(
            data, lo, hi, backend=backend, max_k=2, factored=factored
        )
        if (r.hash, r.nonce) != expect:
            emit(
                {
                    "error": "factor-compare correctness gate failed",
                    "factored": factored,
                    "kernel": [r.hash, r.nonce],
                    "oracle": list(expect),
                    "backend": backend,
                }
            )
            return 1
    interp_ok = None
    if args.fast:
        # Tier-1 also covers the REAL prize path in interpreter mode: the
        # pallas factored kernel (outer grid axis, per-group scalar round
        # prefix) bit-exact across a digit boundary — plain and composed
        # with the PR-13 sieve (group-prefix reuse in both passes).
        watchdog.beat("interpret-mode pallas factored gates")
        expect_i = min_hash_range(data, 985, 1040)
        interp_ok = True
        for sieve in (False, True):
            ri = sweep_min_hash(
                data, 985, 1040, backend="pallas", interpret=True,
                batch=2, max_k=2, factored=True, sieve=sieve,
            )
            interp_ok = interp_ok and (ri.hash, ri.nonce) == expect_i
        if not interp_ok:
            emit({"error": "interpret-mode pallas factored gate failed"})
            return 1
    log("correctness OK: baseline and factored match the oracle")

    # -- same-seed timed legs ------------------------------------------------
    base = 10**9

    def timed(n: int, factored: bool) -> float:
        watchdog.beat(
            f"timed {'factored' if factored else 'baseline'} sweep of {n}"
        )
        t0 = time.perf_counter()
        r = sweep_min_hash(
            data, base, base + n - 1, backend=backend, factored=factored
        )
        dt = time.perf_counter() - t0
        assert r.lanes_swept == n
        watchdog.beat()
        return dt

    warm = 10**5 if args.fast else 10**6
    timed(warm, False)  # compile both shape classes
    timed(warm, True)
    if args.fast:
        n = 2 * 10**5
    else:
        n = 4 * 10**6
        dt = timed(n, False)
        while dt < 4.0 and n < 16 * 10**9:
            n = min(n * max(2, int(4.0 / max(dt, 1e-3))), 16 * 10**9)
            dt = timed(n, False)
    # Interleaved best-of-2 per leg: same-seed PAIR, not single numbers
    # (this box's wall clock swings run-to-run — ROADMAP).
    dt_base = min(timed(n, False), timed(n, False))
    dt_fact = min(timed(n, True), timed(n, True))
    watchdog.disarm()
    r_base = n / dt_base
    r_fact = n / dt_fact
    _, _, _, _, tuned_factored, _ = auto_tune(backend, None, None)
    log(
        f"swept {n} nonces twice: baseline {r_base:,.0f} n/s, factored "
        f"{r_fact:,.0f} n/s (ratio {r_fact / r_base:.3f}); auto_tune "
        f"keeps the {'factored' if tuned_factored else 'baseline'} kernel "
        f"for backend={backend}"
    )
    out = {
        "metric": "factor_compare",
        "unit": "nonces/s",
        "data": data,
        "count": n,
        "baseline_nps": round(r_base),
        "factored_nps": round(r_fact),
        "ratio": round(r_fact / r_base, 4),
        "auto_tune_factored": bool(tuned_factored),
        "kept_kernel": "factored" if tuned_factored else "baseline",
        "platform": platform,
        "pallas_platform": pallas_platform(),
        "backend": backend,
        "bitexact": True,
        "fast": bool(args.fast),
    }
    if interp_ok is not None:
        out["interpret_pallas_factored_bitexact"] = bool(interp_ok)
    emit(out)
    return 0


def run_tier_compare(args, watchdog) -> int:
    """--tier-compare: same-seed device-vs-host tier legs (ISSUE 20).

    Runs the SAME data + nonce range through the workload's strongest
    jax tier and its cpu tier — the heterogeneous-fleet arbitration
    number: the ratio is what a mixed fleet gains by putting this
    workload's chunks on the device rung — and emits one JSON line with
    both rates (the BENCH_pr20 artifact).  Both legs are
    bit-exactness-gated against the workload's hashlib oracle first on
    a digit-boundary-crossing range (device leg forced onto the kernel
    with ``host_lane_budget=0`` so tiny classes can't silently route to
    the host fold); ``--fast`` swaps the timed windows for
    tier-1-sized ones.

    Two payload shapes land in one line (``--workload blake2b64`` is
    the flagship): the LONG payload — data_len of form ``128n + 6``,
    where the device kernel's midstate folding compresses the whole
    constant prefix once per sweep while the cpu tier re-hashes it per
    nonce (the realistic block-header-sized shape the exchange-benchmark
    paper prices) — and the 6-byte flagship-short shape as the honesty
    secondary: midstate folding is most of the long-payload win, and
    stamping both ratios says so instead of letting the headline imply
    a pure ALU win.

    Honesty contract: ``auto_tune_*`` fields record the rungs
    :func:`bitcoin_miner_tpu.ops.sweep.auto_tune` actually resolves for
    this workload's family — the timed device leg runs exactly those
    defaults, so the JSON's kept_kernel is what a fleet miner ships.
    """
    import jax

    from bitcoin_miner_tpu import workloads as registry
    from bitcoin_miner_tpu.ops.sweep import auto_tune, sweep_min_hash
    from bitcoin_miner_tpu.utils.platform import (
        enable_compile_cache,
        pallas_platform,
    )

    enable_compile_cache()
    for flag, val in (("--autotune", args.autotune), ("--profile", args.profile)):
        if val:
            log(f"WARNING: {flag} is ignored in --tier-compare mode")
    watchdog.beat("device init (jax.devices)")
    dev = jax.devices()[0]
    platform = dev.platform
    wl = registry.resolve(args.workload)
    jax_tiers = [t for t in wl.tiers if t in ("pallas", "xla")]
    if not jax_tiers or "cpu" not in wl.tiers:
        emit(
            {
                "error": "--tier-compare needs a workload with both a jax "
                "tier and a cpu tier",
                "workload": wl.name,
                "tiers": list(wl.tiers),
            }
        )
        return 1
    if args.backend in ("pallas", "xla"):
        if args.backend not in jax_tiers:
            emit(
                {
                    "error": f"workload {wl.name!r} has no "
                    f"{args.backend!r} tier",
                    "tiers": list(wl.tiers),
                }
            )
            return 1
        backend = args.backend
    elif args.backend == "native":
        emit({"error": "--tier-compare times the jax tier against the cpu "
              "tier; --backend native names no jax tier"})
        return 1
    else:
        # Strongest jax tier this host actually lowers: pallas only under
        # Mosaic (the Triton rung is unpriced — utils/platform.py).
        backend = (
            "pallas"
            if "pallas" in jax_tiers and pallas_platform() == "mosaic"
            else jax_tiers[-1]
        )
    cpu_search = wl.make_search("cpu")

    # LONG payload: data_len = 128n + 6 puts the constant/digit split at
    # the same tail offsets as the 6-byte flagship (c_len % 128 == 7)
    # while handing the device kernel n whole prefix blocks to fold into
    # the midstate ONCE — the shape where per-nonce host hashing pays
    # full freight.  Deterministic filler, no RNG.
    data_long = ("tier-compare/" * 32)[:390]
    data_short = "cmu440"

    # -- correctness gates: both tiers, digit-boundary-crossing range ------
    lo, hi = 95, 1205
    watchdog.beat("tier-compare correctness gates (first compiles)")
    for data in (data_long, data_short):
        expect = wl.min_range(data, lo, hi)
        r = sweep_min_hash(
            data, lo, hi, backend=backend, max_k=2, workload=wl,
            host_lane_budget=0,
        )
        if (r.hash, r.nonce) != expect:
            emit(
                {
                    "error": "tier-compare device correctness gate failed",
                    "workload": wl.name,
                    "data_len": len(data),
                    "kernel": [r.hash, r.nonce],
                    "oracle": list(expect),
                    "backend": backend,
                }
            )
            return 1
        if tuple(cpu_search(data, lo, hi)) != expect:
            emit(
                {
                    "error": "tier-compare cpu correctness gate failed",
                    "workload": wl.name,
                    "data_len": len(data),
                }
            )
            return 1
    log("correctness OK: device and cpu tiers match the oracle")

    # -- same-seed timed legs ----------------------------------------------
    base = 10**9

    def timed(data: str, n: int, tier: str) -> float:
        watchdog.beat(f"timed {tier} sweep of {n} (data_len {len(data)})")
        t0 = time.perf_counter()
        if tier == "cpu":
            cpu_search(data, base, base + n - 1)
        else:
            r = sweep_min_hash(
                data, base, base + n - 1, backend=backend, workload=wl
            )
            assert r.lanes_swept == n
        dt = time.perf_counter() - t0
        watchdog.beat()
        return dt

    warm = 10**5 if args.fast else 10**6
    timed(data_long, warm, backend)  # compile both payload shape classes
    timed(data_short, warm, backend)
    if args.fast:
        n = 2 * 10**5
    else:
        n = 10**6
        dt = timed(data_long, n, backend)
        # Size the window on the DEVICE leg (~2s is solid on this host);
        # the cpu leg then pays ~ratio× that, which caps the full-mode
        # wall clock near a minute for the expected mid-single-digit
        # ratios.
        while dt < 2.0 and n < 10**9:
            n = min(n * max(2, int(2.0 / max(dt, 1e-3))), 10**9)
            dt = timed(data_long, n, backend)
    # Interleaved best-of-2 per leg: same-seed PAIR, not single numbers
    # (this box's wall clock swings run-to-run — ROADMAP).
    rates = {}
    for data, key in ((data_long, "long"), (data_short, "short")):
        dt_dev = min(timed(data, n, backend), timed(data, n, backend))
        dt_cpu = min(timed(data, n, "cpu"), timed(data, n, "cpu"))
        rates[key] = (n / dt_dev, n / dt_cpu)
    watchdog.disarm()
    (r_dev, r_cpu), (rs_dev, rs_cpu) = rates["long"], rates["short"]
    tuned = auto_tune(backend, None, None, family=wl.kernel_family)
    t_backend, t_batch, _t_max_k, t_sieve, t_factored, t_hot = tuned
    kept = "factored" if t_factored else "baseline"
    if t_sieve:
        kept += "+sieve"
    if t_hot:
        kept += "+hot"
    log(
        f"workload={wl.name} data_len={len(data_long)}: {backend} "
        f"{r_dev:,.0f} n/s vs cpu {r_cpu:,.0f} n/s (ratio "
        f"{r_dev / r_cpu:.3f}); short data_len={len(data_short)}: "
        f"{rs_dev:,.0f} vs {rs_cpu:,.0f} (ratio {rs_dev / rs_cpu:.3f}); "
        f"auto_tune keeps the {kept} kernel for family={wl.kernel_family}"
    )
    emit(
        {
            "metric": "tier_compare",
            "unit": "nonces/s",
            "workload": wl.name,
            "data_len": len(data_long),
            "count": n,
            "device_tier": backend,
            "device_nps": round(r_dev),
            "cpu_nps": round(r_cpu),
            "ratio": round(r_dev / r_cpu, 4),
            "short_data_len": len(data_short),
            "short_device_nps": round(rs_dev),
            "short_cpu_nps": round(rs_cpu),
            "short_ratio": round(rs_dev / rs_cpu, 4),
            "auto_tune_backend": t_backend,
            "auto_tune_batch": t_batch,
            "auto_tune_sieve": bool(t_sieve),
            "auto_tune_factored": bool(t_factored),
            "auto_tune_hot": bool(t_hot),
            "kept_kernel": kept,
            "platform": platform,
            "pallas_platform": pallas_platform(),
            "backend": backend,
            "bitexact": True,
            "fast": bool(args.fast),
        }
    )
    return 0


def run_hot_compare(args, watchdog) -> int:
    """--hot-compare: same-seed persistent-vs-per-chunk dispatch legs
    (ISSUE 16).

    Runs the SAME data + nonce range through the per-chunk dispatch path
    and the always-hot plane (donated running-min carry + device
    descriptor ring) of the resolved jax tier — both legs at the
    backend's default sieve/factored rungs, so the pair isolates the
    dispatch discipline — and emits one JSON line with both rates (the
    BENCH_pr16 artifact).  Both legs are bit-exactness-gated against the
    hashlib oracle first on a digit-boundary-crossing range; ``--fast``
    swaps the timed windows for tiny tier-1-sized ones and adds
    interpret-mode pallas hot gates (plain AND composed with the sieve's
    device-carried threshold), so the correctness half runs on every PR.

    Honesty contract: ``auto_tune_hot`` records which dispatch
    discipline :func:`bitcoin_miner_tpu.ops.sweep.auto_tune` actually
    picks for this backend — if the hot leg loses here, the default
    demonstrably keeps the per-chunk path and both numbers still land.
    """
    import jax

    from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
    from bitcoin_miner_tpu.ops.sweep import auto_tune, sweep_min_hash
    from bitcoin_miner_tpu.utils.platform import (
        enable_compile_cache,
        is_tpu,
        pallas_platform,
    )

    enable_compile_cache()
    for flag, val in (("--autotune", args.autotune), ("--profile", args.profile)):
        if val:
            log(f"WARNING: {flag} is ignored in --hot-compare mode")
    watchdog.beat("device init (jax.devices)")
    dev = jax.devices()[0]
    platform = dev.platform
    if args.backend in ("pallas", "xla"):
        backend = args.backend
    elif args.backend == "native":
        emit({"error": "--hot-compare applies to the jax tiers only"})
        return 1
    else:
        backend = "pallas" if is_tpu() else "xla"
    data = "cmu440"  # the flagship BASELINE shape

    # -- correctness gates: both disciplines, digit-boundary range -----------
    lo, hi = 95, 1205
    expect = min_hash_range(data, lo, hi)
    watchdog.beat("hot-compare correctness gates (first compiles)")
    for hot in (False, True):
        r = sweep_min_hash(data, lo, hi, backend=backend, max_k=2, hot=hot)
        if (r.hash, r.nonce) != expect:
            emit(
                {
                    "error": "hot-compare correctness gate failed",
                    "hot": hot,
                    "kernel": [r.hash, r.nonce],
                    "oracle": list(expect),
                    "backend": backend,
                }
            )
            return 1
    interp_ok = None
    if args.fast:
        # Tier-1 also covers the REAL prize path in interpreter mode: the
        # pallas hot plane (donated carry threaded through the flipped
        # scalar-prefetch threshold) bit-exact across a digit boundary —
        # plain and composed with the PR-13 sieve, whose threshold is now
        # the device-carried running min.
        watchdog.beat("interpret-mode pallas hot gates")
        expect_i = min_hash_range(data, 985, 1040)
        interp_ok = True
        for sieve in (False, True):
            ri = sweep_min_hash(
                data, 985, 1040, backend="pallas", interpret=True,
                batch=2, max_k=2, hot=True, sieve=sieve,
            )
            interp_ok = interp_ok and (ri.hash, ri.nonce) == expect_i
        if not interp_ok:
            emit({"error": "interpret-mode pallas hot gate failed"})
            return 1
    log("correctness OK: per-chunk and hot dispatch match the oracle")

    # -- same-seed timed legs ------------------------------------------------
    base = 10**9

    def timed(n: int, hot: bool) -> float:
        watchdog.beat(
            f"timed {'hot' if hot else 'per-chunk'} sweep of {n}"
        )
        t0 = time.perf_counter()
        r = sweep_min_hash(data, base, base + n - 1, backend=backend, hot=hot)
        dt = time.perf_counter() - t0
        assert r.lanes_swept == n
        watchdog.beat()
        return dt

    warm = 10**5 if args.fast else 10**6
    timed(warm, False)  # compile both dispatch disciplines
    timed(warm, True)
    if args.fast:
        n = 2 * 10**5
    else:
        n = 4 * 10**6
        dt = timed(n, False)
        while dt < 4.0 and n < 16 * 10**9:
            n = min(n * max(2, int(4.0 / max(dt, 1e-3))), 16 * 10**9)
            dt = timed(n, False)
    # Interleaved best-of-2 per leg: same-seed PAIR, not single numbers
    # (this box's wall clock swings run-to-run — ROADMAP).
    dt_chunk = min(timed(n, False), timed(n, False))
    dt_hot = min(timed(n, True), timed(n, True))
    watchdog.disarm()
    r_chunk = n / dt_chunk
    r_hot = n / dt_hot
    _, _, _, _, _, tuned_hot = auto_tune(backend, None, None)
    log(
        f"swept {n} nonces twice: per-chunk {r_chunk:,.0f} n/s, hot "
        f"{r_hot:,.0f} n/s (ratio {r_hot / r_chunk:.3f}); auto_tune "
        f"keeps the {'hot' if tuned_hot else 'per-chunk'} dispatch "
        f"for backend={backend}"
    )
    out = {
        "metric": "hot_compare",
        "unit": "nonces/s",
        "data": data,
        "count": n,
        "perchunk_nps": round(r_chunk),
        "hot_nps": round(r_hot),
        "ratio": round(r_hot / r_chunk, 4),
        "auto_tune_hot": bool(tuned_hot),
        "kept_kernel": "hot" if tuned_hot else "per-chunk",
        "platform": platform,
        "pallas_platform": pallas_platform(),
        "backend": backend,
        "bitexact": True,
        "fast": bool(args.fast),
    }
    if interp_ok is not None:
        out["interpret_pallas_hot_bitexact"] = bool(interp_ok)
    emit(out)
    return 0


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="capture a JAX profiler trace of the timed sweep into DIR "
        "(view with tensorboard / xprof)",
    )
    ap.add_argument(
        "--cpu",
        action="store_true",
        help="bench the CPU backend (with --devices N: N virtual CPU "
        "devices); without it the bench runs on the TPU or fails",
    )
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="sweep dispatch batch sizes for the JAX tier and report each "
        "rate to stderr before benchmarking with the best",
    )
    ap.add_argument(
        "--backend",
        choices=["auto", "pallas", "xla", "native"],
        default="auto",
        help="force a tier instead of picking by platform",
    )
    ap.add_argument(
        "--sieve-compare",
        action="store_true",
        help="same-seed sieve-vs-baseline kernel legs on the resolved jax "
        "tier; emits the BENCH_pr13 sieve_compare JSON line",
    )
    ap.add_argument(
        "--factor-compare",
        action="store_true",
        help="same-seed factored-vs-baseline kernel legs on the resolved "
        "jax tier (ISSUE 14); emits the BENCH_pr14 factor_compare JSON line",
    )
    ap.add_argument(
        "--hot-compare",
        action="store_true",
        help="same-seed persistent-vs-per-chunk dispatch legs on the "
        "resolved jax tier (ISSUE 16); emits the BENCH_pr16 hot_compare "
        "JSON line",
    )
    ap.add_argument(
        "--tier-compare",
        action="store_true",
        help="same-seed device-tier-vs-cpu-tier legs for --workload "
        "(ISSUE 20); emits the BENCH_pr20 tier_compare JSON line",
    )
    ap.add_argument(
        "--workload",
        default=None,
        metavar="NAME",
        help="registered workload for --tier-compare (default: the frozen "
        "sha256d mining default); e.g. blake2b64",
    )
    ap.add_argument(
        "--fast",
        action="store_true",
        help="with --sieve-compare / --factor-compare / --hot-compare / "
        "--tier-compare: tiny tier-1-sized timed windows plus "
        "interpret-mode pallas correctness legs",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        metavar="N",
        help="bench the sharded multi-chip sweep over an N-device mesh "
        "(parallel/sweep.py)",
    )
    args = ap.parse_args()

    # Everything from here (device init, compiles, timed runs) beats this
    # watchdog; a hang still lands a JSON line.
    watchdog = Watchdog(
        float(os.environ.get("BENCH_WATCHDOG_SECS", "300")), "jax import"
    )
    if os.environ.get("BENCH_SIMULATE_WEDGE"):  # test hook (test_bench.py)
        time.sleep(float(os.environ["BENCH_SIMULATE_WEDGE"]))

    if args.devices is not None and args.devices < 1:
        emit({"error": f"--devices must be >= 1, got {args.devices}"})
        return 1
    import jax

    from bitcoin_miner_tpu.utils.platform import (
        device_desc,
        enable_compile_cache,
        force_virtual_cpu,
        is_tpu,
        pallas_platform,
    )

    if args.cpu:
        force_virtual_cpu(args.devices or 1)
    watchdog.beat("device init (jax.devices)")
    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        emit(
            {
                "error": f"no TPU: jax found {device_desc(dev)} "
                "(pass --cpu to bench the CPU backend)",
                "platform": dev.platform,
            }
        )
        return 1

    if args.devices is not None:
        # Sharded mode is its own benchmark: the single-chip-only knobs
        # don't apply there — say so instead of silently dropping them.
        for flag, val in (
            ("--autotune", args.autotune),
            ("--profile", args.profile),
            ("--sieve-compare", args.sieve_compare),
            ("--factor-compare", args.factor_compare),
            ("--hot-compare", args.hot_compare),
            ("--tier-compare", args.tier_compare),
            ("--fast", args.fast),
        ):
            if val:
                log(f"WARNING: {flag} is ignored in --devices sharded mode")
        if args.backend != "auto":
            log("WARNING: --backend is ignored in --devices sharded mode")
        return run_sharded(args, watchdog)

    enable_compile_cache()

    if sum(
        (
            args.sieve_compare,
            args.factor_compare,
            args.hot_compare,
            args.tier_compare,
        )
    ) > 1:
        emit(
            {
                "error": "--sieve-compare, --factor-compare, --hot-compare "
                "and --tier-compare are exclusive"
            }
        )
        return 1
    if args.workload is not None and not args.tier_compare:
        emit({"error": "--workload applies to --tier-compare only"})
        return 1
    if args.sieve_compare:
        return run_sieve_compare(args, watchdog)
    if args.factor_compare:
        return run_factor_compare(args, watchdog)
    if args.hot_compare:
        return run_hot_compare(args, watchdog)
    if args.tier_compare:
        return run_tier_compare(args, watchdog)
    if args.fast:
        log(
            "WARNING: --fast only applies to --sieve-compare/"
            "--factor-compare/--hot-compare/--tier-compare; ignored"
        )

    from bitcoin_miner_tpu import native
    from bitcoin_miner_tpu.bitcoin.hash import min_hash_range
    from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

    platform = dev.platform
    device_kind = getattr(dev, "device_kind", "") or ""
    if args.backend != "auto":
        backend = args.backend
    elif is_tpu():
        backend = "pallas"
    elif native.available():
        # Best CPU tier: the compiled multi-threaded SHA-NI sweep (what a
        # real --backend cpu miner runs), not the jnp-on-CPU path.
        backend = "native"
    else:
        backend = "xla"
    log(
        f"platform={platform} device={device_desc(dev)} "
        f"devices={len(jax.devices())} backend={backend}"
    )

    tuned_batch = None  # None = the tier's default chunks-per-dispatch
    tuned_tile = None  # None = the pallas tier's default lanes-per-program
    tuned_cpb = None  # None = the pallas tier's default chunk rows/program

    def run(d: str, lo: int, hi: int, max_k=None):
        if backend == "native":
            h, n = native.min_hash_range_native(d, lo, hi)
            return h, n, hi - lo + 1
        r = sweep_min_hash(
            d, lo, hi, backend=backend, max_k=max_k,
            batch=tuned_batch, tile=tuned_tile, cpb=tuned_cpb,
        )
        return r.hash, r.nonce, r.lanes_swept

    # -- correctness gate ---------------------------------------------------
    data = "cmu440"
    lo, hi = 95, 1205  # crosses 2->3->4 digit boundaries
    watchdog.beat("correctness gate (first compile)")
    h, n, _ = run(data, lo, hi, max_k=2)
    expect = min_hash_range(data, lo, hi)
    if (h, n) != expect:
        log(f"CORRECTNESS FAILURE: kernel {(h, n)} oracle {expect}")
        emit(
            {
                "error": "correctness gate failed",
                "kernel": [h, n],
                "oracle": list(expect),
                "platform": platform,
                "backend": backend,
            }
        )
        return 1
    log(f"correctness OK: hash={h} nonce={n}")

    # -- throughput ---------------------------------------------------------
    # Steady-state rate on one digit bucket (d=10): warm up the exact shape
    # class first so the timed run hits the compiled kernel, then scale the
    # swept range until it takes >= ~4s of device time.
    base = 10**9

    def timed(n: int) -> float:
        watchdog.beat(f"timed sweep of {n} nonces")
        t0 = time.perf_counter()
        _h, _n, swept = run(data, base, base + n - 1)
        dt = time.perf_counter() - t0
        assert swept == n
        watchdog.beat()
        return dt

    warm = 10**6
    timed(warm)  # compile

    if args.autotune and backend != "native":
        # Dispatch-shape sweep: the pallas superbatch trades per-dispatch
        # latency against per-call memory, and tile sets the VMEM blocking
        # per grid program.  The probe workload must span >= 2 FULL
        # dispatches per candidate — a sub-dispatch probe measures
        # dispatch latency, not the kernel, and ranks candidates by
        # overhead.
        # Candidates that fail to compile are skipped (batch 2048 needs the
        # flattened SMEM chunk table; the int32 argmin guard caps larger).
        if backend == "pallas":
            candidates = [
                (b, t, c)
                for b in (1024, 2048)
                for t in (2048, 4096, 8192)
                for c in (4, 8)
            ]
        else:
            candidates = [(b, None, None) for b in (4, 8, 16, 32)]
        from bitcoin_miner_tpu.ops.sweep import auto_tune

        # Lanes-per-chunk from the tier's own max_k default, so the
        # two-full-dispatches probe sizing can't drift out of sync with it.
        lanes = 10 ** auto_tune(backend, None, None)[2]
        best = None
        best_rate = 0.0
        for cand in candidates:
            tuned_batch, tuned_tile, tuned_cpb = cand
            probe_n = 2 * cand[0] * lanes
            try:
                timed(min(probe_n, 10**6))  # compile this shape class
                dt = timed(probe_n)
            except Exception as e:
                log(f"autotune {cand}: failed ({type(e).__name__}), skipped")
                continue
            rate = probe_n / dt
            log(
                f"autotune batch={cand[0]} tile={cand[1]} cpb={cand[2]}: "
                f"{rate:,.0f} nonces/s"
            )
            if rate > best_rate:
                best_rate, best = rate, cand
        if best is None:
            emit({"error": "autotune: every candidate failed", "backend": backend})
            return 1
        tuned_batch, tuned_tile, tuned_cpb = best
        log(
            f"autotune picked batch={tuned_batch} tile={tuned_tile} "
            f"cpb={tuned_cpb}"
        )

    n = 4 * 10**6
    dt = timed(n)
    # Grow until the measurement window is solid (caps at ~1.6e10 nonces):
    # a fixed lead-in + trailing fetch per window weighs less the longer
    # the window.
    while dt < 7.5 and n < 16 * 10**9:
        n = min(n * max(2, int(7.5 / max(dt, 1e-3))), 16 * 10**9)
        dt = timed(n)
    if args.profile:
        with jax.profiler.trace(args.profile):
            timed(n)
        log(f"profiler trace written to {args.profile}")
    watchdog.disarm()
    rate = n / dt
    log(f"swept {n} nonces in {dt:.3f}s -> {rate:,.0f} nonces/s")

    out = {
        "metric": "nonces_per_sec_per_chip",
        "value": round(rate),
        "unit": "nonces/s",
        "vs_baseline": round(rate / 1e9, 4),
        "platform": platform,
        "pallas_platform": pallas_platform(),
        "device_kind": device_kind,
        "backend": backend,
    }
    if tuned_batch is not None:
        out["batch"] = tuned_batch
    if tuned_tile is not None:
        out["tile"] = tuned_tile
    if tuned_cpb is not None:
        out["cpb"] = tuned_cpb
    emit(out)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # last-ditch: never exit without a JSON line
        import traceback

        traceback.print_exc()
        emit({"error": f"{type(e).__name__}: {e}"})
        sys.exit(1)
