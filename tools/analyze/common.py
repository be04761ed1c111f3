"""Shared plumbing for the repo-native analysis suite.

Findings, source-file iteration, comment-annotation parsing (the
``# guarded-by:`` / ``# unguarded:`` / ``# trace-ok:`` vocabulary — see
README "Static analysis & sanitizers"), and the only-shrink ratchet.
"""

from __future__ import annotations

import ast
import json
import re
import tokenize
from collections import Counter
from dataclasses import dataclass
from io import StringIO
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

REPO_ROOT = Path(__file__).resolve().parents[2]

#: Directories the repo-mode passes scan (relative to the repo root).
DEFAULT_SCAN_DIRS = ("bitcoin_miner_tpu", "tools")


@dataclass(frozen=True)
class Finding:
    pass_name: str  # lock | wfq | contracts | trace | sanitize
    rule: str
    path: str  # repo-relative (or fixture-relative) posix path
    line: int
    symbol: str
    message: str

    @property
    def key(self) -> str:
        """Ratchet identity: line numbers excluded so unrelated edits to a
        file do not churn the grandfather list."""
        return f"{self.pass_name}:{self.path}:{self.rule}:{self.symbol}"

    def render(self) -> str:
        return (
            f"{self.path}:{self.line}: [{self.pass_name}/{self.rule}] "
            f"{self.symbol}: {self.message}"
        )


def iter_py_files(root: Path, scan_dirs: Optional[Tuple[str, ...]] = None) -> Iterator[Path]:
    """Every .py file under ``root`` (restricted to ``scan_dirs`` when
    given), skipping caches and the analyzer's own fixture trees unless
    they are the scan root itself."""
    roots = (
        [root]
        if scan_dirs is None
        else [root / d for d in scan_dirs if (root / d).exists()]
    )
    for r in roots:
        if r.is_file():
            yield r
            continue
        for p in sorted(r.rglob("*.py")):
            if "__pycache__" in p.parts:
                continue
            yield p


def walk_shallow(fn: ast.AST) -> Iterator[ast.AST]:
    """``ast.walk`` over one function's OWN body: nested function/class
    defs (and lambdas) are yielded but not descended into, so a nested
    def's statements are attributed to the nested symbol, never double-
    reported under the enclosing one."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)
        ):
            stack.extend(ast.iter_child_nodes(node))


def rel(path: Path, root: Path) -> str:
    try:
        return path.resolve().relative_to(root.resolve()).as_posix()
    except ValueError:
        return path.as_posix()


# --------------------------------------------------------------------------
# Comment annotations
# --------------------------------------------------------------------------

GUARDED_BY_RE = re.compile(r"guarded-by:\s*([A-Za-z_][A-Za-z0-9_.]*)")
UNGUARDED_RE = re.compile(r"unguarded:")
TRACE_OK_RE = re.compile(r"trace-ok:")
JIT_KERNEL_RE = re.compile(r"jit-kernel\b")
#: Concurrency-plane vocabulary (ISSUE 19).  ``# on-loop:`` on a ``def``
#: declares the body runs on an event-loop thread (loopcheck lints it
#: like a coroutine); on a ``self.<field> = ...`` assignment it declares
#: the field loop-owned, with the optional argument naming the loop
#: attribute off-thread writers must hop through
#: (``# on-loop: _loop`` -> ``self._loop.call_soon_threadsafe``).
ON_LOOP_RE = re.compile(r"(?<![\w-])on-loop:?\s*([A-Za-z_][A-Za-z0-9_]*)?")
LOOP_OK_RE = re.compile(r"loop-ok:")
THREAD_OWNER_RE = re.compile(r"thread-owner:\s*(\S+)")


def file_comments(source: str) -> Dict[int, str]:
    """line number -> comment text (without the #) for one source file."""
    out: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string.lstrip("#").strip()
    except tokenize.TokenError:
        pass  # a truncated file still analyzes as far as it parses
    return out


def comment_in_span(
    comments: Dict[int, str], lineno: int, end_lineno: Optional[int], pattern: re.Pattern
) -> Optional[re.Match]:
    """First match of ``pattern`` in any comment on the statement's
    physical lines (trailing comments land on the last line of a
    multi-line statement)."""
    for ln in range(lineno, (end_lineno or lineno) + 1):
        text = comments.get(ln)
        if text:
            m = pattern.search(text)
            if m:
                return m
    return None


# --------------------------------------------------------------------------
# Ratchet: grandfathered findings, allowed only to shrink
# --------------------------------------------------------------------------


def load_ratchet(path: Path) -> Dict[str, int]:
    if not path.exists():
        return {}
    data = json.loads(path.read_text())
    return {str(k): int(v) for k, v in data.get("grandfathered", {}).items()}


def save_ratchet(path: Path, findings: List[Finding]) -> None:
    counts = Counter(f.key for f in findings)
    path.write_text(
        json.dumps(
            {
                "comment": (
                    "Grandfathered analysis findings — this file may only "
                    "shrink.  Regenerate with python -m tools.analyze "
                    "--update-ratchet after FIXING findings, never to admit "
                    "new ones."
                ),
                "grandfathered": dict(sorted(counts.items())),
            },
            indent=2,
        )
        + "\n"
    )


def apply_ratchet(
    findings: List[Finding], ratchet: Dict[str, int]
) -> Tuple[List[Finding], List[str]]:
    """Split findings into (new, stale-ratchet-keys).

    A finding key is grandfathered up to its ratchet count; any excess is
    new.  A ratchet entry whose key now fires FEWER times than recorded is
    stale — the ratchet must be shrunk to match (that is the only-shrink
    contract: progress is locked in the moment it happens).
    """
    counts = Counter(f.key for f in findings)
    budget = dict(ratchet)
    new: List[Finding] = []
    for f in findings:
        if budget.get(f.key, 0) > 0:
            budget[f.key] -= 1
        else:
            new.append(f)
    stale = sorted(
        k for k, allowed in ratchet.items() if counts.get(k, 0) < allowed
    )
    return new, stale
