"""Pass 4: the JAX trace-safety lint over ``ops/`` and ``parallel/``.

The recompile/tracer-leak bug class: code that runs fine the first time a
kernel traces, then either crashes on the second shape ("concretization
of a traced value") or silently retraces every call (a 9-20 s stall per
chunk on the TPU).  The lint finds its textual signatures
inside **kernel bodies** — functions it identifies as jit-traced:

- decorated with ``@jax.jit`` / ``@partial(jax.jit, ...)``,
- passed by name to ``jax.jit(...)`` in the same module,
- defined (at any nesting depth) inside a kernel factory — a function
  whose name matches ``(make|build).*(kernel|minhash|sieve|factored|
  call|blake2b)``, the repo's factory convention (``make_kernel_body``,
  ``_build_call``, ``_make_sharded_kernel``, the ISSUE 13 sieve
  factories — both of the two-stage sieve's passes live inside these
  bodies on both backends, so the race/contract checks gate them like
  the old code — and the ISSUE 14 factored factories),
- or explicitly marked with ``# jit-kernel`` on its def line.

Rules (suppress a deliberate line with ``# trace-ok: <reason>``):

- ``trace-concretize``: ``int()``/``float()``/``bool()`` over a tainted
  (tracer-reaching) expression, or any ``.item()``/``.tolist()`` call —
  host concretization inside the traced body.
- ``trace-branch``: Python ``if``/``while`` whose test is tainted —
  data-dependent control flow must go through ``jnp.where``/``lax.cond``.
- ``trace-wallclock``: ``time.*()`` / ``datetime.now`` inside a traced
  body — traces once, freezes forever (and breaks retrace caching).
- ``trace-rng``: stateful host RNG (``random.*``, ``np.random.*``)
  inside a traced body — not reproducible, not shardable; thread
  ``jax.random`` keys instead.
- ``trace-unhashable-static``: an ``lru_cache``/``cache``-decorated
  function (the kernel-factory memo idiom) or a jit with
  ``static_argnums``/``static_argnames`` whose parameters carry
  list/dict/set defaults — unhashable statics are a fresh compile per
  call at best, a TypeError at worst.

Taint is a per-function over-approximation: parameters and results of
``jnp.``/``jax.``/``lax.`` calls are tracers; names assigned from tainted
expressions are tainted; ``.shape``/``.dtype``/``.ndim``, ``len()`` and
``range()`` launder (static under trace).  Heuristic by design — the
suppression comment is the escape hatch, and the frozen fixtures in
tests/fixtures_analyze pin what must keep firing.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from .common import (
    JIT_KERNEL_RE,
    TRACE_OK_RE,
    Finding,
    comment_in_span,
    file_comments,
    iter_py_files,
    rel,
)

PASS = "trace"

#: Kernel-factory naming convention the lint keys on; ``blake2b``
#: (ISSUE 20) admits the second kernel family's factories
#: (``make_blake2b_kernel_body`` / ``_make_blake2b_kernel`` /
#: ``build_kernel_for`` in ops/blake2b.py and the sharded wrapper in
#: parallel/sweep.py) so the u32-pair compression bodies are gated like
#: the sha256 plane's — its module-level device primitives (``_G``,
#: ``_compress_pairs``, ...) carry explicit ``# jit-kernel`` marks since
#: they sit outside any factory.
FACTORY_RE = re.compile(
    r"(make|build).*(kernel|minhash|sieve|factored|call|blake2b)"
)

#: Default scan scope in repo mode: the accelerator layers.
TRACE_SCAN_DIRS = (
    "bitcoin_miner_tpu/ops",
    "bitcoin_miner_tpu/parallel",
    # Workload kernel factories (ISSUE 9): any jit/factory-pattern kernel
    # body a registered workload ships is linted like ops/ and parallel/.
    "bitcoin_miner_tpu/workloads",
)

_TRACED_MODULES = ("jnp", "lax")
_LAUNDER_ATTRS = {"shape", "dtype", "ndim", "size"}
_LAUNDER_CALLS = {"len", "range", "isinstance", "getattr", "type"}
_CONCRETIZERS = {"int", "float", "bool"}
_WALLCLOCK = {
    ("time", "time"),
    ("time", "monotonic"),
    ("time", "perf_counter"),
    ("time", "process_time"),
    ("datetime", "now"),
    ("datetime", "utcnow"),
}


def _dotted(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """('jax', 'jit') for jax.jit, ('jit',) for bare jit."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """jax.jit / jit / partial(jax.jit, ...) as a decorator or callee."""
    if isinstance(node, ast.Call):
        d = _dotted(node.func)
        if d and d[-1] == "partial" and node.args:
            return _is_jit_expr(node.args[0])
        return _is_jit_expr(node.func)
    d = _dotted(node)
    return d is not None and d[-1] == "jit"


class _Taint:
    """Per-function tracer-taint over-approximation."""

    def __init__(self, params: Set[str]) -> None:
        self.names: Set[str] = set(params)

    def tainted(self, node: ast.AST) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in self.names:
                if not self._laundered(node, n):
                    return True
            if isinstance(n, ast.Call):
                d = _dotted(n.func)
                if d and d[0] in _TRACED_MODULES:
                    return True
        return False

    def _laundered(self, root: ast.AST, name: ast.Name) -> bool:
        """True if every path from ``root`` to ``name`` passes through a
        shape/dtype/len() laundering node.  Approximated: check the
        direct parent chain via a containment scan (cheap, good enough
        for lint granularity)."""
        for n in ast.walk(root):
            if isinstance(n, ast.Attribute) and n.attr in _LAUNDER_ATTRS:
                if name in ast.walk(n):
                    return True
            if isinstance(n, ast.Call):
                d = _dotted(n.func)
                if d and d[-1] in _LAUNDER_CALLS and name in list(ast.walk(n)):
                    return True
        return False


class _KernelBodyChecker:
    def __init__(
        self, path: str, comments: Dict[int, str], findings: List[Finding]
    ) -> None:
        self.path = path
        self.comments = comments
        self.findings = findings

    def _ok(self, stmt: ast.stmt) -> bool:
        return (
            comment_in_span(
                self.comments,
                stmt.lineno,
                getattr(stmt, "end_lineno", None),
                TRACE_OK_RE,
            )
            is not None
        )

    def check(self, fn: ast.FunctionDef) -> None:
        taint = _Taint({a.arg for a in fn.args.args if a.arg != "self"})
        self._walk(fn.body, fn.name, taint)

    def _emit(self, rule: str, node: ast.AST, symbol: str, msg: str) -> None:
        self.findings.append(
            Finding(PASS, rule, self.path, node.lineno, symbol, msg)
        )

    def _walk(self, body: List[ast.stmt], fname: str, taint: _Taint) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # Nested defs inside a kernel body are kernel code too
                # (pl.when closures); they share the enclosing taint.
                inner = _Taint(taint.names | {a.arg for a in stmt.args.args})
                self._walk(stmt.body, f"{fname}.{stmt.name}", inner)
                continue
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                value = stmt.value
                if value is not None and taint.tainted(value):
                    targets = (
                        stmt.targets
                        if isinstance(stmt, ast.Assign)
                        else [stmt.target]
                    )
                    for t in targets:
                        # Whole-name (and tuple-unpack) bindings only: a
                        # subscript store (`d[k] = tracer`) must not taint
                        # the container name — `if k in d:` over static
                        # keys is legal and common in kernel factories.
                        elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                        for n in elts:
                            if isinstance(n, ast.Name):
                                taint.names.add(n.id)
            if isinstance(stmt, (ast.If, ast.While)) and not self._ok(stmt):
                if taint.tainted(stmt.test):
                    self._emit(
                        "trace-branch",
                        stmt,
                        fname,
                        "Python branch on a traced value inside a kernel "
                        "body — use jnp.where / lax.cond / lax.while_loop",
                    )
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_exprs(stmt, fname, taint)
            for field_name in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field_name, None)
                if sub:
                    self._walk(sub, fname, taint)
            for handler in getattr(stmt, "handlers", ()) or ():
                self._walk(handler.body, fname, taint)

    def _scan_exprs(self, stmt: ast.stmt, fname: str, taint: _Taint) -> None:
        if self._ok(stmt):
            return
        # Only the statement's own expressions; nested suites re-enter via
        # _walk so their statements get their own suppression checks.
        roots: List[ast.AST] = []
        if isinstance(stmt, (ast.If, ast.While)):
            roots = [stmt.test]
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            roots = [stmt.iter]
        elif isinstance(stmt, ast.With):
            roots = [i.context_expr for i in stmt.items]
        elif isinstance(stmt, ast.Try):
            roots = []
        else:
            roots = [stmt]
        for root in roots:
            for node in ast.walk(root):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in _CONCRETIZERS
                    and node.args
                    and taint.tainted(node.args[0])
                ):
                    self._emit(
                        "trace-concretize",
                        node,
                        fname,
                        f"{node.func.id}() over a traced value inside a "
                        "kernel body — concretization error / silent "
                        "retrace",
                    )
                if isinstance(node.func, ast.Attribute) and node.func.attr in (
                    "item",
                    "tolist",
                ):
                    self._emit(
                        "trace-concretize",
                        node,
                        fname,
                        f".{node.func.attr}() inside a kernel body fetches "
                        "to host mid-trace",
                    )
                if d is not None and len(d) >= 2 and (d[-2], d[-1]) in _WALLCLOCK:
                    self._emit(
                        "trace-wallclock",
                        node,
                        fname,
                        f"{'.'.join(d)}() inside a traced body freezes one "
                        "timestamp into the compiled kernel",
                    )
                if d is not None and (
                    d[0] == "random"
                    or (len(d) >= 2 and d[0] in ("np", "numpy") and d[1] == "random")
                ):
                    self._emit(
                        "trace-rng",
                        node,
                        fname,
                        f"stateful host RNG {'.'.join(d)}() inside a traced "
                        "body — thread jax.random keys instead",
                    )


def _mutable_default_params(fn: ast.FunctionDef) -> List[str]:
    out = []
    args = fn.args
    defaults = list(args.defaults)
    params = args.args[len(args.args) - len(defaults):] if defaults else []
    for p, d in zip(params, defaults):
        if isinstance(d, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp)):
            out.append(p.arg)
    for p, d in zip(args.kwonlyargs, args.kw_defaults):
        if isinstance(d, (ast.List, ast.Dict, ast.Set)):
            out.append(p.arg)
    return out


def _check_static_hashability(
    path: str, tree: ast.Module, findings: List[Finding]
) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        memoized = any(
            (d_ := _dotted(dec.func if isinstance(dec, ast.Call) else dec))
            and d_[-1] in ("lru_cache", "cache")
            for dec in node.decorator_list
        )
        jit_static = any(
            isinstance(dec, ast.Call)
            and _is_jit_expr(dec)
            and any(
                kw.arg in ("static_argnums", "static_argnames")
                for kw in dec.keywords
            )
            for dec in node.decorator_list
        )
        if not (memoized or jit_static):
            continue
        bad = _mutable_default_params(node)
        if bad:
            findings.append(
                Finding(
                    PASS,
                    "trace-unhashable-static",
                    path,
                    node.lineno,
                    node.name,
                    f"memoized/static-arg function has unhashable default "
                    f"for {', '.join(bad)} — every call is a cache miss "
                    f"(or a TypeError); use tuples",
                )
            )


def _collect_kernel_bodies(
    tree: ast.Module, comments: Dict[int, str]
) -> List[ast.FunctionDef]:
    """See module docstring for the four identification routes."""
    kernels: List[ast.FunctionDef] = []
    jitted_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node.func):
            for a in node.args:
                if isinstance(a, ast.Name):
                    jitted_names.add(a.id)

    def visit(node: ast.AST, in_factory: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                marked = (
                    comments.get(child.lineno) is not None
                    and JIT_KERNEL_RE.search(comments[child.lineno]) is not None
                )
                decorated = any(
                    _is_jit_expr(d) for d in child.decorator_list
                )
                is_kernel = (
                    in_factory
                    or marked
                    or decorated
                    or child.name in jitted_names
                )
                if is_kernel and isinstance(child, ast.FunctionDef):
                    kernels.append(child)
                    continue  # its nested defs are checked by the body walk
                visit(child, in_factory or FACTORY_RE.search(child.name) is not None)
            else:
                visit(child, in_factory)

    visit(tree, False)
    return kernels


def run(root: Path, scan_dirs: Optional[Tuple[str, ...]] = None) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(root, scan_dirs):
        try:
            source = path.read_text()
            tree = ast.parse(source)
        except (SyntaxError, UnicodeDecodeError):
            continue
        comments = file_comments(source)
        rpath = rel(path, root)
        checker = _KernelBodyChecker(rpath, comments, findings)
        for fn in _collect_kernel_bodies(tree, comments):
            checker.check(fn)
        _check_static_hashability(rpath, tree, findings)
    return findings
