"""The pluggable range-fold workload registry (ISSUE 9).

Four layers of coverage:

- **registry semantics** — resolution by name, default-first listing,
  registration invariants (golden vectors mandatory, ladders end at the
  un-wedgeable hashlib tier), and the frozen default staying
  byte-identical to the reference ``bitcoin/hash`` contract;
- **oracle bit-exactness per workload** — every registered workload's
  golden vectors recompute, its cpu tier matches its hashlib oracle
  across digit-class boundaries, and the families are genuinely
  distinct hash functions;
- **tier ladders** — per-workload kernel factories: the
  separator-parameterized SHA-256 template runs the preimage workload
  bit-exact on the real XLA tier, host-only workloads refuse device
  tiers loudly, and the watchdog downgrade drill passes on a
  NON-default workload (the ISSUE 9 acceptance bar);
- **serving stack e2e** — a gateway+interval-store loadgen leg runs
  end-to-end bit-exact against each NEW workload's own hashlib oracle,
  and per-workload state stamps keep checkpoints/caches/span files from
  leaking across hash families.

The gateway cache/span/coalesce slice and the seeded chaos drill are
parameterized over the registry in tests/test_gateway.py and
tests/test_chaos_soak.py (same ``workloads`` marker).
"""

import json
import threading

import pytest

from bitcoin_miner_tpu import lsp, workloads
from bitcoin_miner_tpu.apps import client as client_mod
from bitcoin_miner_tpu.apps import miner as miner_mod
from bitcoin_miner_tpu.apps import server as server_mod
from bitcoin_miner_tpu.apps.scheduler import Scheduler
from bitcoin_miner_tpu.bitcoin.hash import hash_nonce, min_hash_range
from bitcoin_miner_tpu.gateway import ResultCache, SpanStore
from bitcoin_miner_tpu.utils.metrics import METRICS
from bitcoin_miner_tpu.workloads import Sha256Workload, Workload

pytestmark = pytest.mark.workloads

PARAMS = lsp.Params(epoch_limit=5, epoch_millis=100, window_size=5)

ALL = workloads.names()
NON_DEFAULT = [n for n in ALL if n != workloads.DEFAULT_WORKLOAD]

#: Workloads whose own device kernel family (not a shared SHA-256 kernel)
#: must reproduce their golden vectors, and the tier that runs it.
WORKLOAD_DEVICE_TIERS = {"blake2b64": "xla"}


# ------------------------------------------------------------------ registry


class TestRegistry:
    def test_default_first_and_expected_members(self):
        assert ALL[0] == workloads.DEFAULT_WORKLOAD == "sha256d"
        assert {"sha256d", "preimage", "blake2b64"} <= set(ALL)

    def test_resolve_contract(self):
        d = workloads.resolve(None)
        assert d.name == "sha256d"
        assert workloads.resolve("") is d
        p = workloads.get("preimage")
        assert workloads.resolve("preimage") is p
        assert workloads.resolve(p) is p

    def test_unknown_name_lists_valid_ones(self):
        with pytest.raises(ValueError) as ei:
            workloads.get("nope")
        for name in ALL:
            assert name in str(ei.value)

    def test_register_invariants(self):
        golden = (("x", 1, 123),)
        with pytest.raises(ValueError, match="golden"):
            workloads.register(Sha256Workload("wl-nogold"))
        with pytest.raises(ValueError, match="already registered"):
            workloads.register(
                Sha256Workload("sha256d", golden=golden)
            )

        class NoHashlibLast(Workload):
            tiers = ("hashlib", "cpu")

        bad = NoHashlibLast()
        bad.name, bad.golden = "wl-ladder", golden
        with pytest.raises(ValueError, match="hashlib"):
            workloads.register(bad)

        # native_ok is proven at register time: the sweep drivers trust it
        # to route host lanes through the default-format native/compiled
        # path, so a non-default family claiming it must be refused.
        with pytest.raises(ValueError, match="native_ok"):
            workloads.register(
                Sha256Workload("wl-native-lie", sep=":", native_ok=True,
                               golden=golden)
            )

    def test_default_is_the_frozen_reference_contract(self):
        w = workloads.resolve(None)
        for data, nonce in (("hello", 0), ("cmu440", 987654321), ("", 7)):
            assert w.hash_nonce(data, nonce) == hash_nonce(data, nonce)
        assert w.min_range("frozen", 0, 300) == min_hash_range("frozen", 0, 300)


# ------------------------------------------------------------------- oracles


@pytest.mark.parametrize("wname", ALL)
class TestOracles:
    def test_golden_vectors_recompute(self, wname):
        w = workloads.get(wname)
        assert len(w.golden) >= 3
        for data, nonce, frozen in w.golden:
            assert w.hash_nonce(data, nonce) == frozen, (wname, data, nonce)

    def test_cpu_tier_matches_oracle_across_digit_boundaries(self, wname):
        w = workloads.get(wname)
        cpu = w.make_search("cpu")
        # Digit-class boundaries are where template machinery breaks
        # first; the cpu tier must agree with the naive oracle loop.
        for lo, hi in ((0, 25), (7, 13), (95, 112), (998, 1005), (40, 400)):
            assert cpu("wl", lo, hi) == w.min_range("wl", lo, hi), (wname, lo, hi)

    def test_min_range_rejects_empty(self, wname):
        w = workloads.get(wname)
        with pytest.raises(ValueError):
            w.min_range("x", 5, 4)


def test_families_are_distinct_functions():
    probes = [("dist", 3), ("dist", 41), ("", 999)]
    seen = {}
    for name in ALL:
        w = workloads.get(name)
        sig = tuple(w.hash_nonce(d, n) for d, n in probes)
        assert sig not in seen.values(), (name, "collides with", seen)
        seen[name] = sig


# -------------------------------------------------------------- tier ladders


class TestTierLadders:
    def test_ladder_shapes(self):
        assert workloads.get("sha256d").tiers == (
            "pallas", "xla", "cpu", "hashlib")
        assert workloads.get("preimage").tiers == (
            "pallas", "xla", "cpu", "hashlib")
        # ISSUE 20: blake2b64 grew a real device tier (ops/blake2b.py);
        # no pallas rung — the family has no Mosaic lowering yet.
        assert workloads.get("blake2b64").tiers == ("xla", "cpu", "hashlib")

    def test_blake2b_refuses_pallas_tier(self):
        b = workloads.get("blake2b64")
        with pytest.raises(ValueError, match="no 'pallas' tier"):
            b.make_search("pallas")
        with pytest.raises(ValueError, match="no 'pallas' tier"):
            miner_mod.make_search("pallas", workload=b)

    def test_blake2b_xla_tier_bit_exact(self):
        """ISSUE 20's device half: the u32-pair blake2b kernel runs the
        workload bit-exact vs its own hashlib oracle across a digit-class
        boundary, and genuinely hashes the blake2b message (differs from
        the sha256 families on the same range)."""
        w = workloads.get("blake2b64")
        search = w.make_search("xla")
        assert search("b2dev", 95, 320) == w.min_range("b2dev", 95, 320)
        assert search("b2dev", 95, 320) != min_hash_range("b2dev", 95, 320)

    @pytest.mark.parametrize(
        "wname,tier,data,nonce,frozen",
        [
            (wname, tier, *vec)
            for wname, tier in WORKLOAD_DEVICE_TIERS.items()
            for vec in workloads.get(wname).golden
        ],
    )
    def test_device_tier_reproduces_golden(self, wname, tier, data, nonce, frozen):
        """A from-scratch device kernel can drift while the hashlib oracle
        stays green, and every sweep would then serve wrong minima
        whenever that tier wins the ladder.  A single-nonce sweep
        (``[n, n]``; ``sweep_min_hash`` routes nothing to the host) puts
        each golden vector through the whole device path: layout build,
        midstate fold, device compression, min-fold epilogue."""
        from bitcoin_miner_tpu.ops.sweep import sweep_min_hash

        w = workloads.get(wname)
        assert tier in w.tiers
        r = sweep_min_hash(data, nonce, nonce, backend=tier, workload=w)
        assert (r.hash, r.nonce) == (frozen, nonce)

    def test_preimage_xla_tier_bit_exact(self):
        """The tentpole's device half: the separator-parameterized layout
        drives the real (rolled, XLA:CPU-compiled) kernel for a
        non-default workload, bit-exact vs its own hashlib oracle across
        digit classes."""
        w = workloads.get("preimage")
        search = w.make_search("xla")
        assert search("pw0", 0, 300) == w.min_range("pw0", 0, 300)
        # And differs from the default family on the same range — the
        # kernel really hashed "<data>:<nonce>", not "<data> <nonce>".
        assert search("pw0", 0, 300) != min_hash_range("pw0", 0, 300)

    def test_async_search_cpu_pool(self):
        for name in NON_DEFAULT:
            w = workloads.get(name)
            s = w.make_async_search("cpu")
            try:
                assert s.submit("async", 0, 200).result(timeout=30) == (
                    w.min_range("async", 0, 200)
                )
            finally:
                s.close()

    def test_tiered_chain_is_the_workloads_ladder(self):
        # auto on a CPU host resolves to the cpu rung; the chain is the
        # suffix of the workload's own ladder from there.
        ts = miner_mod.make_tiered_search(
            "auto", workload=workloads.get("blake2b64")
        )
        try:
            assert [t for t, _ in ts._chain] == ["cpu", "hashlib"]
        finally:
            ts.close()
        ts = miner_mod.make_tiered_search(
            "xla", workload=workloads.get("preimage")
        )
        try:
            assert [t for t, _ in ts._chain] == ["xla", "cpu", "hashlib"]
        finally:
            ts.close()
        # ISSUE 20: the blake2b64 device rung heads the 3-rung watchdog
        # chain when asked for explicitly.
        ts = miner_mod.make_tiered_search(
            "xla", workload=workloads.get("blake2b64")
        )
        try:
            assert [t for t, _ in ts._chain] == ["xla", "cpu", "hashlib"]
        finally:
            ts.close()
        with pytest.raises(ValueError, match="no 'pallas' tier"):
            miner_mod.make_tiered_search(
                "pallas", workload=workloads.get("blake2b64")
            )


def test_watchdog_downgrade_drill_non_default_workload():
    """The ISSUE 9 acceptance drill: the watchdog ladder works
    per-workload — a wedged top tier is abandoned and the chunk re-runs
    bit-exact on the NON-default workload's own next tier."""
    w = workloads.get("preimage")
    downgrades0 = METRICS.get("miner.tier_downgrades")
    hold = threading.Event()

    def wedged(d, lo, hi):
        hold.wait(timeout=30)
        return (0, 0)

    ts = miner_mod._TieredSearch(
        [
            ("wedged", lambda: wedged),
            ("cpu", lambda: w.make_async_search("cpu")),
            ("hashlib", lambda: w.min_range),
        ],
        wedge_seconds=0.4,
    )
    try:
        got = ts.submit("wl-wedge", 0, 600).result(timeout=30)
        assert got == w.min_range("wl-wedge", 0, 600)
        assert METRICS.get("miner.tier_downgrades") - downgrades0 == 1
        assert ts.active_tier == "cpu"
    finally:
        hold.set()
        ts.close()


def test_watchdog_fleet_serves_non_default_workload_after_downgrade():
    """Fleet shape of the same drill: a server scheduling the preimage
    workload, whose only miner starts on a wedging tier, still answers
    the client bit-exact — run_miner never notices the tier swap."""
    w = workloads.get("preimage")
    hold = threading.Event()

    def wedged(d, lo, hi):
        hold.wait(timeout=30)
        return (0, 0)

    server = lsp.Server(0, PARAMS)
    threading.Thread(
        target=server_mod.serve,
        args=(server, Scheduler(min_chunk=500, workload=w)),
        daemon=True,
    ).start()
    ts = miner_mod._TieredSearch(
        [("wedged", lambda: wedged),
         ("cpu", lambda: w.make_async_search("cpu"))],
        wedge_seconds=0.5,
    )
    mc = lsp.Client("127.0.0.1", server.port, PARAMS)
    threading.Thread(
        target=miner_mod.run_miner, args=(mc, ts), daemon=True
    ).start()
    try:
        c = lsp.Client("127.0.0.1", server.port, PARAMS)
        try:
            res = client_mod.request_once(c, "wlfleet", 2000)
        finally:
            c.close()
        assert res == w.min_range("wlfleet", 0, 2000)
    finally:
        hold.set()
        server.close()


# --------------------------------------------------------- serving-stack e2e


@pytest.mark.parametrize("wname", NON_DEFAULT)
def test_loadgen_gateway_interval_leg_per_new_workload(wname, capsys):
    """The ISSUE 9 acceptance bar: each NEW workload runs the
    gateway+interval-store loadgen leg end-to-end — overlap-heavy
    traffic, every Result validated against that workload's own hashlib
    oracle, the repeat and covered-sub-range probes answering with zero
    chunks assigned."""
    import tools.loadgen as loadgen

    rc = loadgen.main([
        "--fast", "--overlap", "--workload", wname,
        "--jobs", "14", "--clients", "4", "--max-nonce", "2500",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == wname
    assert out["repeat_zero_chunks"] is True
    assert out["subrange_zero_chunks"] is True
    assert out["swept_reduction"] is None or out["swept_reduction"] >= 0


class TestWorkloadStateStamps:
    """Per-workload state files refuse to load across hash families —
    resuming another function's minima would silently corrupt answers."""

    def test_scheduler_checkpoint_stamp(self):
        s = Scheduler(workload=workloads.get("preimage"))
        state = s.checkpoint()
        assert state["workload"] == "preimage"
        # Non-default state nests its payload under version 2: a
        # pre-registry reader (which gates on neither version nor stamp
        # and reads top-level "jobs" directly) must find NOTHING, not
        # another hash family's minima.
        assert state["version"] == 2 and "jobs" not in state
        jobs = [{
            "data": "x", "lower": 0, "upper": 99,
            "best": [5, 3], "remaining": [[10, 99]],
        }]
        state["state"]["jobs"] = jobs
        other = Scheduler()  # default workload
        other.load_checkpoint(state)
        assert other._resume == {}
        same = Scheduler(workload=workloads.get("preimage"))
        same.load_checkpoint(state)
        assert ("x", 0, 99) in same._resume
        # Pre-registry (unstamped, flat v1) checkpoints belong to the
        # default — and the default still WRITES that frozen flat shape.
        legacy = Scheduler()
        legacy.load_checkpoint({"version": 1, "jobs": jobs})
        assert ("x", 0, 99) in legacy._resume
        default_state = Scheduler().checkpoint()
        assert default_state["version"] == 1 and "jobs" in default_state

    def test_result_cache_stamp(self, tmp_path):
        path = str(tmp_path / "cache.json")
        c = ResultCache(path=path, workload="blake2b64")
        c.put(("d", 0, 9), 1, 2)
        c.save(path)
        # Nested non-default shape: no top-level "entries" for a
        # pre-registry reader to misread (see workloads.stamp_state).
        on_disk = json.loads((tmp_path / "cache.json").read_text())
        assert on_disk["version"] == 2 and "entries" not in on_disk
        assert ResultCache(path=path, workload="blake2b64").get(("d", 0, 9)) == (1, 2)
        assert ResultCache(path=path).get(("d", 0, 9)) is None
        assert ResultCache(path=path, workload="preimage").get(("d", 0, 9)) is None

    def test_span_store_stamp(self, tmp_path):
        path = str(tmp_path / "spans.json")
        s = SpanStore(path=path, workload="preimage")
        s.add("d", 0, 99, 7, 42)
        s.save(path)
        on_disk = json.loads((tmp_path / "spans.json").read_text())
        assert on_disk["version"] == 2 and "data" not in on_disk
        assert len(SpanStore(path=path, workload="preimage")) == 1
        assert len(SpanStore(path=path)) == 0
