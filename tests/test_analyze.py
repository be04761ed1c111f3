"""The repo-native analysis suite, both directions (ISSUE 4).

- The live repo passes every pass clean (``python -m tools.analyze``
  exits 0) — this is the tier-1 gate every future PR runs.
- Every rule FIRES on its seeded fixture violation
  (tests/fixtures_analyze): an analyzer that cannot detect certifies
  nothing.
- The runtime race sanitizer's primitives (TrackedLock ownership,
  acquisition-order graph, Monitor discipline) unit-tested directly, and
  the only-shrink ratchet mechanics.

The BMT_SANITIZE=1 integration legs live with the suites they harden:
tests/test_chaos_soak.py (sanitized fast drill) and tests/test_gateway.py
(sanitized duplicate-heavy fleet).
"""

import importlib.util
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.analysis

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures_analyze"

if str(REPO) not in sys.path:  # make `tools.analyze` importable in-process
    sys.path.insert(0, str(REPO))

from tools.analyze import PASSES, apply_ratchet, load_ratchet, save_ratchet
from tools.analyze import contracts as contracts_pass
from tools.analyze.common import DEFAULT_SCAN_DIRS, Finding
from tools.analyze.tracecheck import TRACE_SCAN_DIRS

from bitcoin_miner_tpu.utils import sanitize


def _pass_findings(name, root, scan=None):
    return PASSES[name](root, scan)


# --------------------------------------------------------------------------
# 1. The live repo is clean
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name",
    [
        "lock", "wfq", "trace", "contracts", "sanitize", "metrics",
        "loop", "thread",
    ],
)
def test_repo_is_clean(name):
    scan = {"trace": TRACE_SCAN_DIRS}.get(name, DEFAULT_SCAN_DIRS)
    findings = _pass_findings(name, REPO, scan)
    ratchet = load_ratchet(REPO / "tools" / "analyze" / "ratchet.json")
    new, stale = apply_ratchet(findings, ratchet)
    assert not new, "\n".join(f.render() for f in new)
    assert not stale, stale


def test_cli_repo_mode_exits_zero():
    """The command every future PR runs — fast, CPU-safe, no network."""
    res = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "-q"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert res.returncode == 0, res.stdout + res.stderr


def test_cli_fixture_mode_exits_nonzero():
    res = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--root", str(FIXTURES)],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    # Every pass contributed at least one finding to the output.
    for tag in ("[lock/", "[wfq/", "[contracts/", "[trace/", "[sanitize/",
                "[metrics/", "[loop/", "[thread/"):
        assert tag in res.stdout, f"{tag} never fired:\n{res.stdout}"


# --------------------------------------------------------------------------
# 2. Every rule fires on its seeded fixture
# --------------------------------------------------------------------------


def _rules(findings):
    return {f.rule for f in findings}


def test_lock_rules_fire_on_fixture():
    rules = _rules(_pass_findings("lock", FIXTURES))
    assert {"field-off-lock", "helper-off-lock", "local-off-lock"} <= rules


def test_lock_pass_understands_acquire_release_pairs():
    """Explicit acquire()/release() pairing (ISSUE 5): access between the
    calls (the try/finally idiom) is LEGAL; access after the release
    fires.  Both directions checked by line, for fields and for
    serve-loop locals."""
    src = (FIXTURES / "bad_lock.py").read_text().splitlines()

    def line_of(marker):
        return next(i + 1 for i, text in enumerate(src) if marker in text)

    findings = _pass_findings("lock", FIXTURES)
    flagged = {(f.symbol, f.line) for f in findings}
    # The seeded post-release violations fire...
    assert ("PairedCounter._n", line_of("post-release read")) in flagged
    assert (
        "serve_like_paired:state",
        line_of("local read after paired release"),
    ) in flagged
    # ...and the legal between-acquire/release accesses do NOT.
    legal_lines = {
        i + 1
        for i, text in enumerate(src)
        if "legal: between acquire/release" in text
    }
    assert len(legal_lines) == 2  # one field access, one serve-loop local
    assert not {(s, ln) for s, ln in flagged if ln in legal_lines}


def test_wfq_rules_fire_on_fixture():
    rules = _rules(_pass_findings("wfq", FIXTURES))
    assert {"floor-init-reimplemented", "tiebreak-reimplemented"} <= rules


def test_trace_rules_fire_on_fixture():
    rules = _rules(_pass_findings("trace", FIXTURES))
    assert {
        "trace-branch",
        "trace-concretize",
        "trace-wallclock",
        "trace-rng",
        "trace-unhashable-static",
    } <= rules


def test_contract_rules_fire_on_drifted_codec():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bad_contract", FIXTURES / "bad_contract.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    findings = contracts_pass.run(
        FIXTURES, None, modules={"bitcoin_message": mod, "hash": mod}
    )
    rules = _rules(findings)
    assert {"codec-marshal", "codec-roundtrip", "hash-vector"} <= rules


def test_sanitize_pass_fires_on_fixture():
    findings = _pass_findings("sanitize", FIXTURES)
    provoked = {f.symbol for f in findings}
    assert {
        "provoke_unsynchronized_access",
        "provoke_lock_order_inversion",
    } <= provoked


def test_metrics_rules_fire_on_fixture():
    """Every metric-registry rule fires on bad_metric.py: an emitted-but-
    undocumented name, a documented-but-never-emitted name, a histogram
    name emitted via inc(), and a computed (unverifiable) name."""
    findings = _pass_findings("metrics", FIXTURES)
    rules = _rules(findings)
    assert {
        "metric-undocumented",
        "metric-unused",
        "metric-kind-mismatch",
        "metric-dynamic-name",
    } <= rules
    symbols = {f.symbol for f in findings}
    assert "fixture.never_documented" in symbols
    assert "fixture.documented_only" in symbols
    assert "hist.fixture_latency" in symbols
    # fleet.* names are gauge-kind (ISSUE 7): inc() on one must fire.
    assert ("metric-kind-mismatch", "fleet.fixture_sources") in {
        (f.rule, f.symbol) for f in findings
    }
    # fed.peer_state.* is the membership gauge family (ISSUE 12): inc()
    # on one must fire too, while the rest of fed.* stays counter-kind.
    assert ("metric-kind-mismatch", "fed.peer_state.fixture") in {
        (f.rule, f.symbol) for f in findings
    }
    # gw.conns_live is the ingress live-conn gauge (ISSUE 15) — the one
    # gauge-kind name under gw.* — and the ingress.* counter family rides
    # the same registry cross-check.
    assert ("metric-kind-mismatch", "gw.conns_live") in {
        (f.rule, f.symbol) for f in findings
    }
    assert ("metric-unused", "ingress.fixture_events") in {
        (f.rule, f.symbol) for f in findings
    }
    # The sweep.* counter family rides the same registry cross-check
    # (inc-kind).
    assert ("metric-unused", "sweep.fixture_refills") in {
        (f.rule, f.symbol) for f in findings
    }
    # autoscale.target_workers is the capacity plane's fleet-size gauge
    # and fed.conns_live the federation transport's shared-loop conn
    # gauge (ISSUE 18); the rest of autoscale.* counts controller
    # actions and stays inc-kind, pinned by the unused-row cross-check.
    assert ("metric-kind-mismatch", "autoscale.target_workers") in {
        (f.rule, f.symbol) for f in findings
    }
    assert ("metric-kind-mismatch", "fed.conns_live") in {
        (f.rule, f.symbol) for f in findings
    }
    assert ("metric-unused", "autoscale.fixture_actions") in {
        (f.rule, f.symbol) for f in findings
    }
    # sanitize.* is the sanitizer trip-counter family (ISSUE 19) — stays
    # inc-kind, pinned by the unused-row cross-check.
    assert ("metric-unused", "sanitize.fixture_trips") in {
        (f.rule, f.symbol) for f in findings
    }


def test_loop_rules_fire_on_fixture():
    """Every loop-discipline rule fires on bad_loop.py — and none of the
    legal idioms (awaited calls, async-with locks, the identity fast
    path, the threadsafe hop, `# loop-ok:` suppressions) fire."""
    findings = _pass_findings("loop", FIXTURES)
    assert {
        "loop-blocking-call",
        "loop-lock",
        "loop-off-thread-write",
    } <= _rules(findings)
    rules_syms = {(f.rule, f.symbol) for f in findings}
    # The off-thread write on the annotated loop-owned field...
    assert ("loop-off-thread-write", "BadBridge.write") in rules_syms
    # ...the sync sleep / file open / Future wait inside coroutines...
    assert ("loop-blocking-call", "handler") in rules_syms
    assert ("loop-blocking-call", "locked_handler") in rules_syms
    assert ("loop-lock", "locked_handler") in rules_syms
    # ...and a PLAIN def pulled into scope by its `# on-loop:` header.
    assert ("loop-blocking-call", "on_loop_callback") in rules_syms
    # The clean idioms never appear at all.
    symbols = {f.symbol for f in findings}
    for clean in (
        "BadBridge.write_hopped",  # identity fast path + threadsafe hop
        "BadBridge.snapshot",      # trailing # loop-ok:
        "clean_handler",           # awaited read / async with
        "suppressed_handler",      # trailing # loop-ok:
        "BadBridge.__init__",      # the annotation site itself
    ):
        assert clean not in symbols, (clean, symbols)


def test_thread_rules_fire_on_fixture():
    """thread-unjoined fires on both ownership shapes — the class-owned
    thread whose close() never joins it (daemon does NOT exempt) and the
    fire-and-forget non-daemon local — while the reaper joins (direct
    and for-loop-over-list), the wait-for-workers local join, daemon
    locals, and `# thread-owner:` abandons stay clean."""
    findings = _pass_findings("thread", FIXTURES)
    assert "thread-unjoined" in _rules(findings)
    symbols = {f.symbol for f in findings}
    assert "LeakyWorker.__init__" in symbols
    assert "leaky_local" in symbols
    for clean in (
        "CleanWorker.__init__",       # joined in stop(), both spellings
        "AbandonedByDesign.__init__",  # trailing # thread-owner:
        "clean_local_join",
        "clean_local_daemon",
        "annotated_local",
    ):
        assert clean not in symbols, (clean, symbols)


def test_metrics_pass_honors_metric_ok_declaration(tmp_path):
    """A dynamic emit with `# metric-ok: prefix.*` is legal and marks the
    documented prefix as emitted (the chaos layer's one dynamic site);
    declaring an unknown name still fails."""
    good = tmp_path / "dyn_ok.py"
    good.write_text(
        "class Metrics:\n"
        "    def inc(self, name):\n"
        "        pass\n"
        "\n"
        "#: registry block\n"
        "#:   dyn.alpha   covered by the declared glob\n"
        "#:   dyn.beta    covered by the declared glob\n"
        "METRICS = Metrics()\n"
        "\n"
        "def emit(what):\n"
        "    METRICS.inc('dyn.' + what)  # metric-ok: dyn.*\n"
    )
    assert _pass_findings("metrics", tmp_path) == []
    bad = tmp_path / "dyn_ok.py"
    bad.write_text(
        bad.read_text().replace("# metric-ok: dyn.*",
                                "# metric-ok: dyn.alpha dyn.gamma")
    )
    findings = _pass_findings("metrics", tmp_path)
    rules_syms = {(f.rule, f.symbol) for f in findings}
    assert ("metric-undocumented", "dyn.gamma") in rules_syms  # bad token
    assert ("metric-unused", "dyn.beta") in rules_syms  # no longer covered


def test_trace_pass_does_not_flag_static_branches(tmp_path):
    """The taint heuristic must not cry wolf on the repo's real kernel
    idioms: static Python loops/branches and dict-membership over static
    keys inside a kernel factory."""
    clean = tmp_path / "clean_kernel.py"
    clean.write_text(
        "import jax\nimport jax.numpy as jnp\n\n"
        "def make_kernel(n_blocks, k):\n"
        "    def kernel(midstate, bounds):\n"
        "        i = jnp.arange(10 ** k)\n"
        "        contrib = {}\n"
        "        for b in range(n_blocks):\n"
        "            contrib[b] = i + b\n"
        "        w = []\n"
        "        for widx in range(16):\n"
        "            if widx in contrib:\n"
        "                w.append(contrib[widx])\n"
        "        if n_blocks > 1:\n"
        "            w.append(jnp.min(i))\n"
        "        return w\n"
        "    return jax.jit(kernel)\n"
    )
    assert _pass_findings("trace", tmp_path) == []


def test_trace_pass_collects_sieve_kernel_bodies():
    """ISSUE 13 coverage meta-test: the trace-safety lint must SEE the
    two-stage sieve kernel paths — both passes, both backends — exactly
    like the baseline kernels.  The sieve bodies live inside the factory
    convention (``make_kernel_body`` / ``_build_call`` /
    ``make_pallas_minhash*``), so _collect_kernel_bodies must return
    them; if a refactor ever moves them outside the convention, this
    test (not silence) is what fails."""
    import ast

    from tools.analyze.common import file_comments
    from tools.analyze.tracecheck import FACTORY_RE, _collect_kernel_bodies

    # The sieve factory naming is part of the convention now.
    assert FACTORY_RE.search("make_pallas_sieve")
    collected = {}
    for mod in ("ops/sweep.py", "ops/pallas_sha256.py"):
        src = (REPO / "bitcoin_miner_tpu" / mod).read_text()
        tree = ast.parse(src)
        names = [
            fn.name
            for fn in _collect_kernel_bodies(tree, file_comments(src))
        ]
        collected[mod] = names
    # ops/sweep.py: the xla tier's baseline AND sieve kernel bodies (two
    # defs named `kernel`) plus the shared assemble/hash/fold helpers
    # pass 1 and pass 2 run through.
    assert collected["ops/sweep.py"].count("kernel") >= 2
    for helper in ("_assemble", "_hash", "_fold"):
        assert helper in collected["ops/sweep.py"]
    # ops/pallas_sha256.py: the pallas kernel body (pass 1 + pass 2 in
    # one def) and the jit wrappers of both factories.
    assert "kernel" in collected["ops/pallas_sha256.py"]
    assert collected["ops/pallas_sha256.py"].count("minhash") >= 2


def test_trace_pass_collects_factored_kernel_bodies():
    """ISSUE 14 coverage meta-test: the trace-safety lint must SEE the
    factored kernel paths on both backends — the outer-group assembly /
    scalar-prefix / resumed-hash helpers of the xla tier's factored
    branch (inside ``make_kernel_body``) and the factored pallas body
    (inside ``_build_factored_call`` / ``make_pallas_minhash_factored``).
    If a refactor moves them outside the factory convention, this test
    (not silence) fails."""
    import ast

    from tools.analyze.common import file_comments
    from tools.analyze.tracecheck import FACTORY_RE, _collect_kernel_bodies

    # The factored factory naming is part of the convention now.
    assert FACTORY_RE.search("make_factored_kernel")
    assert FACTORY_RE.search("_build_factored_call")
    assert FACTORY_RE.search("make_pallas_minhash_factored")
    collected = {}
    for mod in ("ops/sweep.py", "ops/pallas_sha256.py"):
        src = (REPO / "bitcoin_miner_tpu" / mod).read_text()
        tree = ast.parse(src)
        names = [
            fn.name
            for fn in _collect_kernel_bodies(tree, file_comments(src))
        ]
        collected[mod] = names
    # ops/sweep.py: the factored branch's kernel defs push the `kernel`
    # count past the baseline+sieve pair, and its helpers are visible.
    assert collected["ops/sweep.py"].count("kernel") >= 4
    for helper in ("_assemble_group", "_group_prefix", "_hash_resumed"):
        assert helper in collected["ops/sweep.py"]
    # ops/pallas_sha256.py: the factored call's kernel body and the
    # factored jit wrapper join the static + dyn ones.
    assert collected["ops/pallas_sha256.py"].count("kernel") >= 2
    assert collected["ops/pallas_sha256.py"].count("minhash") >= 3


def test_trace_pass_collects_blake2b_kernel_bodies():
    """ISSUE 20 coverage meta-test: the trace-safety lint must SEE the
    second kernel family's bodies — the blake2b compression sweep body
    nested in ``make_blake2b_kernel_body`` (and the sharded wrapper's, in
    parallel/sweep.py) via the grown ``|blake2b`` factory convention, and
    the module-level u32-pair device primitives via their explicit
    ``# jit-kernel`` marks.  If a refactor renames a factory outside the
    convention or drops a mark, this test (not silence) fails."""
    import ast

    from tools.analyze.common import file_comments
    from tools.analyze.tracecheck import FACTORY_RE, _collect_kernel_bodies

    # The blake2b factory naming is part of the convention now.
    assert FACTORY_RE.search("make_blake2b_kernel_body")
    assert FACTORY_RE.search("_make_blake2b_kernel")
    assert FACTORY_RE.search("_make_sharded_blake2b_kernel")
    collected = {}
    for mod in ("ops/blake2b.py", "parallel/sweep.py"):
        src = (REPO / "bitcoin_miner_tpu" / mod).read_text()
        names = [
            fn.name
            for fn in _collect_kernel_bodies(ast.parse(src), file_comments(src))
        ]
        collected[mod] = names
    # The factory-nested compression sweep body...
    assert "kernel" in collected["ops/blake2b.py"]
    # ...the marked module-level device primitives the body calls into
    # (they sit outside any factory, so only the marks admit them)...
    for helper in ("_addm", "_rotr64", "_G", "_compress_pairs", "_bswap32"):
        assert helper in collected["ops/blake2b.py"]
    # ...and the mesh plane's traced bodies the sharded blake2b factory
    # composes: the per-shard `local` body and the collective-cascade
    # `shard_fn` wrapper (the blake2b body itself is built in
    # ops/blake2b.py and collected there as `kernel`).
    assert {"local", "shard_fn"} <= set(collected["parallel/sweep.py"])


# --------------------------------------------------------------------------
# 2b. lockcheck --fix: the mechanical lock fixer (ISSUE 12 carry-over)
# --------------------------------------------------------------------------


_FIXABLE = """\
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def bump(self):
        self._n += 1

    def read(self):
        return self._n
"""

_UNFIXABLE = """\
import threading


class Scanner:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0  # guarded-by: _lock

    def spin(self):
        while self._n < 10:
            pass
"""


def _run_lockfix(root, *extra):
    return subprocess.run(
        [sys.executable, "-m", "tools.analyze", "lockcheck", "--fix",
         "--root", str(root), *extra],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )


def test_lockfix_wraps_safe_findings_and_recheck_is_clean(tmp_path):
    """Direction 1: simple-statement findings are mechanically wrapped in
    `with self._lock:` and the lock pass then finds nothing."""
    (tmp_path / "fixme.py").write_text(_FIXABLE)
    res = _run_lockfix(tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    fixed = (tmp_path / "fixme.py").read_text()
    assert fixed.count("with self._lock:") == 2
    assert "with self._lock:\n            self._n += 1" in fixed
    assert "with self._lock:\n            return self._n" in fixed
    assert _pass_findings("lock", tmp_path) == []  # idempotent + clean
    res2 = _run_lockfix(tmp_path)
    assert res2.returncode == 0
    assert (tmp_path / "fixme.py").read_text() == fixed  # nothing to redo


def test_lockfix_refuses_compound_headers_and_emits_review_diff(tmp_path):
    """Direction 2: an access in a loop header cannot be wrapped without
    changing control flow — the file stays byte-identical and the
    annotated context block names the spot for review."""
    (tmp_path / "scanner.py").write_text(_UNFIXABLE)
    res = _run_lockfix(tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert (tmp_path / "scanner.py").read_text() == _UNFIXABLE
    assert "NOT auto-fixable" in res.stdout
    assert "scanner.py" in res.stdout and "Scanner._n" in res.stdout
    assert "while self._n < 10:" in res.stdout  # the annotated context


def test_lockfix_dry_run_touches_nothing(tmp_path):
    (tmp_path / "fixme.py").write_text(_FIXABLE)
    res = _run_lockfix(tmp_path, "--dry-run")
    assert (tmp_path / "fixme.py").read_text() == _FIXABLE
    assert "proposed (dry run)" in res.stdout
    assert "+        with self._lock:" in res.stdout


def test_lockfix_handles_serve_loop_locals(tmp_path):
    """The function-local `# guarded-by: lock` vocabulary wraps with the
    bare lock name, not `self.`."""
    (tmp_path / "serveish.py").write_text(
        "import threading\n"
        "\n"
        "\n"
        "def serve_like(lock):\n"
        "    state = {}  # guarded-by: lock\n"
        "    with lock:\n"
        "        state['a'] = 1\n"
        "    state['b'] = 2\n"
    )
    res = _run_lockfix(tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    fixed = (tmp_path / "serveish.py").read_text()
    assert "    with lock:\n        state['b'] = 2" in fixed
    assert _pass_findings("lock", tmp_path) == []


_HOPPABLE = """\
class Bridge:
    def __init__(self, server, loop):
        self.srv = server  # on-loop: lp
        self.lp = loop

    def poke(self, conn_id, payload):
        self.srv.write(conn_id, payload)
"""

_UNHOPPABLE = """\
class Bridge:
    def __init__(self, server, loop):
        self.srv = server  # on-loop: lp
        self.lp = loop

    def query(self, conn_id):
        n = self.srv.pending(conn_id)
        return n
"""


def test_lockfix_hops_simple_off_loop_writes(tmp_path):
    """ISSUE 19: a bare fire-and-forget call on a loop-owned field is
    mechanically rewritten to the call_soon_threadsafe hop the finding
    message spells, the loop pass then finds nothing, and a second run
    has nothing to do."""
    (tmp_path / "bridge.py").write_text(_HOPPABLE)
    res = _run_lockfix(tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    fixed = (tmp_path / "bridge.py").read_text()
    assert (
        "self.lp.call_soon_threadsafe(self.srv.write, conn_id, payload)"
        in fixed
    )
    assert _pass_findings("loop", tmp_path) == []  # recheck is clean
    res2 = _run_lockfix(tmp_path)
    assert res2.returncode == 0
    assert (tmp_path / "bridge.py").read_text() == fixed  # idempotent


def test_lockfix_refuses_hops_that_need_the_return_value(tmp_path):
    """A write whose result is bound cannot become a fire-and-forget
    hop — the file stays byte-identical and the review block names the
    spot."""
    (tmp_path / "bridge.py").write_text(_UNHOPPABLE)
    res = _run_lockfix(tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert (tmp_path / "bridge.py").read_text() == _UNHOPPABLE
    assert "NOT auto-hoppable" in res.stdout
    assert "Bridge.query" in res.stdout
    assert "n = self.srv.pending(conn_id)" in res.stdout  # the context


def test_lockfix_hop_dry_run_touches_nothing(tmp_path):
    (tmp_path / "bridge.py").write_text(_HOPPABLE)
    res = _run_lockfix(tmp_path, "--dry-run")
    assert (tmp_path / "bridge.py").read_text() == _HOPPABLE
    assert "proposed (dry run)" in res.stdout
    assert "+        self.lp.call_soon_threadsafe(self.srv.write" in res.stdout


def test_lockfix_repo_mode_is_a_noop_on_a_clean_repo():
    """The repo carries no findings, so --fix must change nothing (and
    exit 0) — the tier-1-safe property."""
    res = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "lockcheck", "--fix",
         "--dry-run"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 finding(s) wrapped" in res.stdout


# --------------------------------------------------------------------------
# 3. Ratchet mechanics: the grandfather list may only shrink
# --------------------------------------------------------------------------


def _finding(rule="r", path="p.py", symbol="s"):
    return Finding("lock", rule, path, 1, symbol, "msg")


def test_ratchet_grandfathers_up_to_count_and_flags_excess():
    ratchet = {_finding().key: 1}
    new, stale = apply_ratchet([_finding(), _finding()], ratchet)
    assert len(new) == 1 and not stale  # one allowed, one new


def test_ratchet_stale_entry_must_shrink():
    ratchet = {_finding().key: 2}
    new, stale = apply_ratchet([_finding()], ratchet)
    assert not new
    assert stale == [_finding().key]  # fired 1 < recorded 2: shrink the file


def test_ratchet_save_load_roundtrip(tmp_path):
    path = tmp_path / "ratchet.json"
    save_ratchet(path, [_finding(), _finding(), _finding(rule="other")])
    loaded = load_ratchet(path)
    assert loaded[_finding().key] == 2
    assert loaded[_finding(rule="other").key] == 1
    assert "only shrink" in json.loads(path.read_text())["comment"]


def test_checked_in_ratchet_is_empty():
    """The repo carries no grandfathered debt today; if a future PR must
    add some, it does so explicitly — and the file can then only shrink."""
    assert load_ratchet(REPO / "tools" / "analyze" / "ratchet.json") == {}


# --------------------------------------------------------------------------
# 4. ruff + mypy (configured in pyproject.toml; the image may not ship the
#    tools — skip, don't fail, so tier-1 stays hermetic)
# --------------------------------------------------------------------------


def _have(tool: str) -> bool:
    return importlib.util.find_spec(tool) is not None


@pytest.mark.skipif(not _have("ruff"), reason="ruff not installed in this image")
def test_ruff_clean():
    res = subprocess.run(
        [sys.executable, "-m", "ruff", "check", "bitcoin_miner_tpu", "tools", "tests"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stdout + res.stderr


@pytest.mark.skipif(not _have("mypy"), reason="mypy not installed in this image")
def test_mypy_clean():
    res = subprocess.run(
        [sys.executable, "-m", "mypy", "--no-error-summary"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, res.stdout + res.stderr


# --------------------------------------------------------------------------
# 5. Race-sanitizer primitives
# --------------------------------------------------------------------------


@pytest.fixture
def sanitizer():
    sanitize.force(True)
    sanitize.reset_order_graph()
    yield sanitize
    sanitize.force(None)
    sanitize.reset_order_graph()


def test_tracked_lock_ownership(sanitizer):
    lock = sanitize.TrackedLock("t.own")
    assert not lock.held()
    with lock:
        assert lock.held()
        box = {}

        def peek():
            box["other"] = lock.held()

        t = threading.Thread(target=peek)
        t.start()
        t.join()
        assert box["other"] is False  # held() is per-thread, not per-lock
    assert not lock.held()


def test_lock_order_graph_is_transitive(sanitizer):
    a, b, c = (sanitize.TrackedLock(n) for n in ("g.A", "g.B", "g.C"))
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with pytest.raises(sanitize.LockOrderError):
        with c:
            with a:  # A->B->C->A: caught via transitivity, not direct edge
                pass


def test_monitor_allows_thread_confined_use(sanitizer):
    lock = sanitize.TrackedLock("t.confined")
    obj = sanitize.guard({"n": 1}, lock, "conf")
    assert obj.keys() is not None  # single-threaded, off-lock: the setup window


def test_monitor_raises_once_shared(sanitizer):
    lock = sanitize.TrackedLock("t.shared")
    obj = sanitize.guard({"n": 1}, lock, "shared")

    def locked_touch():
        with lock:
            obj.keys()

    t = threading.Thread(target=locked_touch)
    t.start()
    t.join()
    with pytest.raises(sanitize.RaceError):
        obj.keys()
    with lock:
        obj.keys()  # disciplined access still fine


def test_guard_is_identity_when_disabled():
    sanitize.force(False)
    try:
        lock = sanitize.make_lock("t.off")
        assert isinstance(lock, type(threading.Lock()))
        obj = {"n": 1}
        assert sanitize.guard(obj, lock, "x") is obj
    finally:
        sanitize.force(None)


def test_loop_thread_self_call_raises_race_error(sanitizer):
    """ISSUE 12 carry-over: calling a blocking _LoopThread proxy FROM its
    own loop thread is a guaranteed deadlock (the Future can never
    resolve while its loop blocks on it) — refused outright."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    lt = _LoopThread("san-selfcall")
    try:
        box = {}

        def from_loop():
            try:
                lt.call(lambda: None)
            except BaseException as e:
                return e
            return None

        box["err"] = lt.call(lambda: from_loop())
        # from_loop ran ON the loop thread; its nested call() must raise.
        assert isinstance(box["err"], sanitize.RaceError), box["err"]
    finally:
        lt.stop()


def test_loop_thread_joins_lock_order_graph(sanitizer):
    """The Future-spelled ABBA: a loop whose callback takes the event
    lock, and a caller that blocks on the loop WHILE HOLDING that lock,
    is a deadlock-in-waiting — the order graph catches it
    deterministically, whichever side runs first."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    event = sanitize.TrackedLock("san.loop.event")
    lt = _LoopThread("san-order")
    try:
        # Leg 1: a loop callback acquires the event lock -> loop->event.
        def takes_event():
            with event:
                pass

        lt.call(takes_event)
        # Leg 2: blocking on the loop while holding the event lock adds
        # event->loop, closing the cycle.
        with pytest.raises(sanitize.LockOrderError):
            with event:
                lt.call(lambda: None)
    finally:
        lt.stop()


def test_loop_thread_clean_order_is_silent(sanitizer):
    """The repo's real discipline — locks taken outside loop waits, loop
    callbacks lock-free — records edges but never a cycle."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    event = sanitize.TrackedLock("san.loop.clean")
    lt = _LoopThread("san-clean")
    try:
        with event:
            lt.call(lambda: None)  # event->loop only: fine
        lt.call(lambda: None)
        with event:
            pass
    finally:
        lt.stop()


def test_serve_loop_discipline_clean_under_monitor(sanitizer):
    """The exact shape serve() runs: scheduler behind a Monitor, read loop
    + ticker threads, all access under the event lock — silent."""
    from bitcoin_miner_tpu.apps.scheduler import Scheduler

    lock = sanitize.make_lock("t.serve")
    sched = sanitize.guard(Scheduler(), lock, "scheduler")
    errors = []

    def actor(event_fn):
        try:
            for i in range(100):
                with lock:
                    event_fn(i)
        except BaseException as e:
            errors.append(e)

    threads = [
        threading.Thread(target=actor, args=(lambda i: sched.tick(float(i)),)),
        threading.Thread(target=actor, args=(lambda i: sched.stats(),)),
        threading.Thread(target=actor, args=(lambda i: sched.drain_evictions(),)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


# --------------------------------------------------------------------------
# 5. Loop-discipline runtime (ISSUE 19): the dynamic half of the `loop`
#    pass — blocking() declarations, the graph-based lock-on-loop check,
#    and the always-on thread census the flat-thread legs ride.
# --------------------------------------------------------------------------


def _returning_exc(fn):
    """Run ``fn``, returning the exception it raised (or None)."""
    try:
        fn()
    except BaseException as e:
        return e
    return None


def test_blocking_raises_only_on_registered_loop_threads(sanitizer):
    """sanitize.blocking() is free on a plain thread and a hard
    LoopBlockedError on a registered loop thread — the runtime spelling
    of loopcheck's loop-blocking-call rule."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    sanitize.blocking("test.plain_thread")  # plain thread: free
    lt = _LoopThread("san-blocking")
    try:
        err = lt.call(
            lambda: _returning_exc(lambda: sanitize.blocking("test.on_loop"))
        )
        assert isinstance(err, sanitize.LoopBlockedError), err
    finally:
        lt.stop()
    sanitize.blocking("test.after_stop")  # still free off-loop


def test_cross_loop_facade_wait_raises_loop_blocked(sanitizer):
    """A loop thread blocking on ANOTHER loop's proxy Future is the trip
    the sync facades now declare via sanitize.blocking: the nested call
    raises instead of stalling every conn riding the outer loop."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    a = _LoopThread("san-cross-a")
    b = _LoopThread("san-cross-b")
    try:
        err = a.call(
            lambda: _returning_exc(lambda: b.call(lambda: None))
        )
        assert isinstance(err, sanitize.LoopBlockedError), err
    finally:
        a.stop()
        b.stop()


def test_tracked_lock_on_loop_thread_uses_the_block_edge(sanitizer):
    """Taking a tracked lock ON a loop thread is legal in itself (the
    event plane does it every event) — it only becomes a refusal once
    some thread has BLOCKED on that loop while holding the same lock,
    because the next on-loop acquisition then closes a deadlock cycle."""
    from bitcoin_miner_tpu.lsp.sync import _LoopThread

    def take(lock):
        return _returning_exc(lambda: lock.acquire()) or lock.release()

    free = sanitize.TrackedLock("san.loopedge.free")
    event = sanitize.TrackedLock("san.loopedge.event")
    lt = _LoopThread("san-loopedge")
    try:
        # No block edge: an on-loop acquisition is silent.
        assert lt.call(lambda: take(free)) in (None, False)
        # Record event->loop: a thread blocks on the loop holding event.
        with event:
            lt.call(lambda: None)
        # Now the same lock ON the loop thread is the deadlock cycle.
        err = lt.call(lambda: _returning_exc(event.acquire))
        assert isinstance(err, sanitize.LoopBlockedError), err
    finally:
        lt.stop()


def test_thread_census_and_leak_check():
    """The always-on runtime half of the `thread` pass: the census
    baselines by name, threads_leaked names offenders (and feeds the
    sanitize.threads_leaked counter), and a reaped fleet drains clean."""
    from bitcoin_miner_tpu.utils.metrics import METRICS

    base = sanitize.thread_census()
    before = METRICS.get("sanitize.threads_leaked")
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="census-probe")
    t.start()
    try:
        leaked = sanitize.threads_leaked(base)
        assert leaked.count("census-probe") == 1, leaked
        assert METRICS.get("sanitize.threads_leaked") >= before + 1
    finally:
        stop.set()
        t.join()
    assert sanitize.threads_leaked(base, settle_s=5.0) == []


# --------------------------------------------------------------------------
# 6. Incremental mode: --changed (ISSUE 19), the pre-commit-hook shape
# --------------------------------------------------------------------------


def test_cli_changed_mode_agrees_with_full_run_and_is_fast():
    """--changed must reach the same verdict as the full run (scoping
    may skip work, never flip the exit code) AND clear the pre-commit
    bar: a warm scoped run over a small diff in well under five seconds
    (a full run pays the whole-repo parse; the scoped run must not).
    The full run doubles as the cache warmer for the timed leg."""
    full = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "-q"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    probe = REPO / "bitcoin_miner_tpu" / "_changed_probe.py"
    probe.write_text(
        '"""Untracked --changed timing probe (created and removed by '
        'tests/test_analyze.py)."""\n'
    )
    try:
        t0 = time.monotonic()
        inc = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--changed", "-q"],
            cwd=REPO, capture_output=True, text=True, timeout=60,
        )
        dt = time.monotonic() - t0
    finally:
        probe.unlink()
    assert inc.returncode == full.returncode, (
        full.stdout + full.stderr + inc.stdout + inc.stderr
    )
    assert dt < 5.0, f"--changed took {dt:.2f}s on a small diff"


def test_cli_changed_rejects_incompatible_flags():
    """--changed scopes the LIVE repo against git: combining it with an
    alternate --root or with --update-ratchet is a usage error."""
    for extra in (["--root", str(FIXTURES)], ["--update-ratchet"]):
        res = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--changed", *extra],
            cwd=REPO, capture_output=True, text=True, timeout=60,
        )
        assert res.returncode == 2, (extra, res.stdout, res.stderr)
