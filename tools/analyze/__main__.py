"""CLI for the analysis suite: ``python -m tools.analyze``.

Exit 0 when every pass is clean (modulo the only-shrink ratchet),
non-zero on any new finding or stale ratchet entry.  Tier-1 runs this on
every PR (tests/test_analyze.py), so the passes stay fast,
``JAX_PLATFORMS=cpu``-safe, and network-free.

    python -m tools.analyze                      # all passes, repo mode
    python -m tools.analyze --pass lock,wfq      # a subset
    python -m tools.analyze --root tests/fixtures_analyze   # fixture tree
    python -m tools.analyze --update-ratchet     # after FIXING findings
    python -m tools.analyze --changed            # files changed vs HEAD
    python -m tools.analyze --changed main       # ... vs a ref
"""

from __future__ import annotations

import argparse
import subprocess
import sys
from pathlib import Path
from typing import List, Optional, Tuple

from . import PASSES
from .common import (
    DEFAULT_SCAN_DIRS,
    REPO_ROOT,
    Finding,
    apply_ratchet,
    load_ratchet,
    save_ratchet,
)
from .donatecheck import DONATE_SCAN_DIRS
from .tracecheck import TRACE_SCAN_DIRS

DEFAULT_RATCHET = Path(__file__).resolve().parent / "ratchet.json"

#: Per-file passes can run on exactly the changed files.  The rest
#: (contracts, sanitize, metrics) are whole-repo cross-checks: metrics
#: must re-balance emitters against the registry after ANY change, while
#: contracts/sanitize self-tests only depend on their trigger dirs.
_PER_FILE_PASSES = frozenset({"lock", "wfq", "trace", "loop", "donate", "thread"})
_WHOLE_PASS_TRIGGERS = {
    # contracts: workloads are in the trigger set for the registry's
    # golden-vector pass.
    "contracts": ("bitcoin_miner_tpu/bitcoin", "bitcoin_miner_tpu/lsp",
                  "bitcoin_miner_tpu/apps", "bitcoin_miner_tpu/workloads",
                  "tools/analyze"),
    "sanitize": ("bitcoin_miner_tpu/utils", "bitcoin_miner_tpu/apps",
                 "tools/analyze"),
    "metrics": DEFAULT_SCAN_DIRS,
}


def _scan_dirs_for(name: str) -> Tuple[str, ...]:
    if name == "trace":
        return TRACE_SCAN_DIRS
    if name == "donate":
        return DONATE_SCAN_DIRS
    return DEFAULT_SCAN_DIRS


def _changed_files(root: Path, ref: str) -> Optional[List[str]]:
    """Repo-relative .py paths changed vs ``ref`` (committed diff, index,
    worktree, plus untracked), or None when git cannot answer."""
    out: List[str] = []
    for cmd in (
        ["git", "diff", "--name-only", ref, "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            proc = subprocess.run(
                cmd, cwd=root, capture_output=True, text=True, timeout=30
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        out.extend(line.strip() for line in proc.stdout.splitlines() if line.strip())
    return sorted(
        {p for p in out if p.endswith(".py") and (root / p).exists()}
    )


def main(argv: List[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.analyze")
    ap.add_argument(
        "mode",
        nargs="?",
        default=None,
        choices=["lockcheck"],
        help="subcommand: `lockcheck --fix` mechanically wraps safe "
        "unguarded accesses in `with <lock>:` and prints annotated "
        "diffs for the rest (ISSUE 12 carry-over)",
    )
    ap.add_argument(
        "--fix",
        action="store_true",
        help="with `lockcheck`: rewrite safe findings in place",
    )
    ap.add_argument(
        "--dry-run",
        action="store_true",
        help="with `lockcheck --fix`: print the would-be diffs, touch nothing",
    )
    ap.add_argument(
        "--pass",
        dest="passes",
        default="all",
        help=f"comma-separated subset of: {','.join(PASSES)} (default all)",
    )
    ap.add_argument(
        "--root",
        default=None,
        help="scan this tree instead of the repo (fixture mode: contracts/"
        "sanitize pick up bad_contract.py / bad_race.py found under it)",
    )
    ap.add_argument(
        "--ratchet",
        default=None,
        help="grandfather file (default tools/analyze/ratchet.json in repo "
        "mode, none in --root mode)",
    )
    ap.add_argument(
        "--update-ratchet",
        action="store_true",
        help="rewrite the ratchet from current findings (only for locking "
        "in FIXES — never to admit new findings)",
    )
    ap.add_argument(
        "--changed",
        nargs="?",
        const="HEAD",
        default=None,
        metavar="REF",
        help="incremental mode: per-file passes run only on files changed "
        "vs REF (default HEAD, incl. uncommitted + untracked); whole-repo "
        "passes run fully when a trigger dir changed, else skip.  Same "
        "exit codes — cheap enough for a pre-commit hook (see README)",
    )
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.mode == "lockcheck":
        if not args.fix:
            ap.error("lockcheck mode needs --fix (plain checking is "
                     "`--pass lock`)")
        from .lockfix import fix as lockfix_fix

        repo_mode = args.root is None
        root = REPO_ROOT if repo_mode else Path(args.root).resolve()
        scan = DEFAULT_SCAN_DIRS if repo_mode else None
        fixed, reviews = lockfix_fix(root, scan, write=not args.dry_run)
        for entry in reviews:
            print(entry)
        if not args.quiet:
            print(
                f"tools.analyze lockcheck --fix: {fixed} finding(s) "
                f"wrapped, {len(reviews)} left for review"
            )
        return 1 if reviews else 0

    names = (
        list(PASSES) if args.passes == "all" else [p.strip() for p in args.passes.split(",")]
    )
    unknown = [n for n in names if n not in PASSES]
    if unknown:
        print(f"unknown pass(es): {', '.join(unknown)}", file=sys.stderr)
        return 2

    repo_mode = args.root is None
    root = REPO_ROOT if repo_mode else Path(args.root).resolve()

    changed: Optional[List[str]] = None
    if args.changed is not None:
        if not repo_mode:
            print("--changed only applies in repo mode", file=sys.stderr)
            return 2
        if args.update_ratchet:
            print("--changed cannot update the ratchet (a partial scan "
                  "would erase unscanned grandfathers)", file=sys.stderr)
            return 2
        changed = _changed_files(root, args.changed)
        if changed is None:
            print(
                "--changed: git unavailable, running the full suite",
                file=sys.stderr,
            )

    findings: List[Finding] = []
    # pass name -> scanned-paths set (per-file) or None (ran fully);
    # passes skipped by --changed are absent, and their ratchet keys are
    # out of scope for the stale check this run.
    ran_scope: dict = {}
    for name in names:
        run = PASSES[name]
        if repo_mode:
            scan = _scan_dirs_for(name)
            if changed is not None:
                if name in _PER_FILE_PASSES:
                    scoped = tuple(
                        p for p in changed
                        if any(p == d or p.startswith(d + "/") for d in scan)
                    )
                    if not scoped:
                        continue
                    scan = scoped
                    ran_scope[name] = set(scoped)
                else:
                    triggers = _WHOLE_PASS_TRIGGERS.get(name, DEFAULT_SCAN_DIRS)
                    if not any(
                        p == d or p.startswith(d + "/")
                        for p in changed
                        for d in triggers
                    ):
                        continue
                    ran_scope[name] = None
            else:
                ran_scope[name] = None
        else:
            scan = None  # the whole fixture tree
            ran_scope[name] = None
        if name == "contracts" and not repo_mode:
            bad = list(root.rglob("bad_contract.py"))
            if not bad:
                continue  # nothing to check against in this tree
            import importlib.util

            spec = importlib.util.spec_from_file_location("bad_contract", bad[0])
            assert spec is not None and spec.loader is not None
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            findings.extend(
                run(root, None, modules={
                    "bitcoin_message": mod,
                    "hash": mod,
                })
            )
            continue
        findings.extend(run(root, scan))

    ratchet_path = (
        Path(args.ratchet)
        if args.ratchet
        else (DEFAULT_RATCHET if repo_mode else None)
    )
    if args.update_ratchet:
        if ratchet_path is None:
            print("--update-ratchet needs a ratchet path", file=sys.stderr)
            return 2
        save_ratchet(ratchet_path, findings)
        print(f"ratchet rewritten: {len(findings)} grandfathered finding(s)")
        return 0

    ratchet = load_ratchet(ratchet_path) if ratchet_path else {}
    if changed is not None:
        # An incremental run only sees the changed files' findings, so
        # only the matching ratchet slice participates — otherwise every
        # unscanned grandfather would read as stale.
        def _in_scope(key: str) -> bool:
            pass_name, path = key.split(":", 2)[:2]
            if pass_name not in ran_scope:
                return False
            scope = ran_scope[pass_name]
            return scope is None or path in scope

        ratchet = {k: v for k, v in ratchet.items() if _in_scope(k)}
    new, stale = apply_ratchet(findings, ratchet)
    grandfathered = len(findings) - len(new)

    for f in new:
        print(f.render())
    for key in stale:
        print(
            f"stale ratchet entry: {key} no longer fires at its recorded "
            f"count — shrink tools/analyze/ratchet.json (the only-shrink "
            f"contract: fixed findings stay fixed)"
        )
    if not args.quiet:
        print(
            f"tools.analyze: {len(names)} pass(es), {len(new)} new finding(s), "
            f"{grandfathered} grandfathered, {len(stale)} stale ratchet "
            f"entr{'y' if len(stale) == 1 else 'ies'}"
        )
    return 1 if new or stale else 0


if __name__ == "__main__":
    sys.exit(main())
