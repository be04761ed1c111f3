"""Pass 6: the metric-registry cross-check.

``utils/metrics.py`` carries a documented registry block (the contiguous
``#:`` comment lines directly above the ``METRICS = Metrics()``
assignment).  Before this pass, that block was free-form documentation —
nothing stopped a new ``METRICS.inc("gatway.requets")`` typo from minting
a silently-uncounted counter, or a refactor from leaving a documented
name that nothing increments (both happened: ``lsp.dropped_horizon`` and
the whole ``gateway.span_*`` family shipped undocumented).

Rules:

- ``metric-undocumented`` — a name passed to an emitter anywhere in the
  scan tree does not appear in the registry block.
- ``metric-unused`` — a registry name no emitter anywhere ever emits
  (documented-but-never-incremented: dead doc or a dropped call site).
- ``metric-kind-mismatch`` — the emitter does not match the name's kind:
  ``hist.*`` names take ``observe``, ``gauge.*`` AND ``fleet.*`` names
  take ``set_gauge`` (the merged fleet-view levels the telemetry hub
  publishes, ISSUE 7), everything else takes ``inc``.
- ``metric-dynamic-name`` — an emitter whose name argument is not a
  string literal (a computed name can never be registry-checked; read
  paths like ``METRICS.get(f"sched.{k}")`` are exempt — only emitters
  mint names).  A ``# metric-ok: <names...>`` comment on the statement
  declares which documented names the dynamic emit covers (``chaos.*``
  glob form marks a whole documented prefix) — the declared names count
  as emitted and the finding is suppressed.

Emitters are calls on the process-wide registry object: a ``METRICS``
receiver with method ``inc`` / ``observe`` / ``set_gauge``.  Local
``Metrics()`` instances (unit tests, fixtures) are out of scope in repo
mode because tests are outside the scan dirs.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from .common import Finding, comment_in_span, file_comments, iter_py_files, rel

PASS = "metrics"

#: Emitter method -> the name-prefix kind it must be used with.
EMITTERS = {"inc": "counter", "observe": "hist", "set_gauge": "gauge"}

#: ``# metric-ok: name [name...]`` — declares the documented names a
#: dynamic emit covers (``prefix.*`` marks every documented name under
#: that prefix).
METRIC_OK_RE = re.compile(r"metric-ok:\s*([A-Za-z0-9_.*,\s]+)")

#: A registry line: ``#:``, >= 2 spaces, a dotted lowercase name, then a
#: description.  Header/prose lines (one space, capitalised, no dotted
#: name) never match.
_REGISTRY_LINE = re.compile(r"^#:\s{2,}([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)\s+\S")

_METRICS_ASSIGN = re.compile(r"^METRICS\s*=", re.MULTILINE)


def _name_kind(name: str) -> str:
    if name.startswith("hist."):
        return "hist"
    if name.startswith(
        (
            "gauge.", "fleet.", "fed.peer_state", "fed.conns_live",
            "gw.conns_live", "autoscale.target_workers",
            "sweep.kernel_build_s",
        )
    ):
        # fed.peer_state[.<peer>] is the per-peer membership gauge family
        # (ISSUE 12) and fed.conns_live the federation transport's
        # live-conn level (ISSUE 18); the rest of fed.* stays
        # counter-kind.  gw.conns_live is the ingress live-conn gauge
        # (ISSUE 15) — the only gauge-kind name under gw.*.  sweep.*
        # stays counter-kind but for sweep.kernel_build_s, a stored
        # kernel's first-call seconds.  autoscale.target_workers is
        # the controller's worker-target level (ISSUE 18); the other
        # autoscale.* names count actions and stay counters.
        return "gauge"
    return "counter"


def _parse_registry(source: str) -> Optional[Dict[str, int]]:
    """name -> line number, from the contiguous ``#:`` block directly
    above the module-level ``METRICS = ...`` assignment; None if the file
    defines no registry."""
    lines = source.splitlines()
    assign_at = None
    for i, line in enumerate(lines):
        if _METRICS_ASSIGN.match(line):
            assign_at = i
            break
    if assign_at is None:
        return None
    out: Dict[str, int] = {}
    j = assign_at - 1
    while j >= 0 and lines[j].startswith("#:"):
        m = _REGISTRY_LINE.match(lines[j])
        if m:
            out[m.group(1)] = j + 1
        j -= 1
    return out


def _emitter_calls(
    tree: ast.Module, comments: Dict[int, str]
) -> List[Tuple[str, Optional[str], int, Optional[str]]]:
    """Every ``METRICS.<emitter>(...)`` call: (method, literal name or
    None when dynamic, line, metric-ok declaration text or None)."""
    out: List[Tuple[str, Optional[str], int, Optional[str]]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (
            isinstance(f, ast.Attribute)
            and f.attr in EMITTERS
            and isinstance(f.value, ast.Name)
            and f.value.id == "METRICS"
        ):
            continue
        name: Optional[str] = None
        if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
            node.args[0].value, str
        ):
            name = node.args[0].value
        ok = comment_in_span(
            comments, node.lineno, getattr(node, "end_lineno", None),
            METRIC_OK_RE,
        )
        out.append((f.attr, name, node.lineno, ok.group(1) if ok else None))
    return out


def run(root: Path, scan_dirs: Optional[Tuple[str, ...]] = None) -> List[Finding]:
    findings: List[Finding] = []
    registry: Dict[str, int] = {}
    registry_path: Optional[str] = None
    uses: List[Tuple[str, str, Optional[str], int, Optional[str]]] = []
    for path in iter_py_files(root, scan_dirs):
        try:
            source = path.read_text()
            tree = ast.parse(source)
        except (SyntaxError, UnicodeDecodeError):
            continue  # the lock pass reports parse errors once
        rpath = rel(path, root)
        reg = _parse_registry(source)
        if reg is not None:
            # One registry per scan tree (utils/metrics.py in repo mode,
            # bad_metric.py in fixture mode); a second one merges so the
            # cross-check still covers every documented name.
            registry.update(reg)
            registry_path = registry_path or rpath
        for method, name, line, ok in _emitter_calls(tree, file_comments(source)):
            uses.append((rpath, method, name, line, ok))
    if registry_path is None:
        return findings  # no registry in this tree: nothing to check against

    emitted: Set[str] = set()
    for rpath, method, name, line, ok in uses:
        if ok is not None:
            # Declared coverage of a dynamic (or literal) emit: each
            # token is marked emitted; ``prefix.*`` covers the whole
            # documented prefix.  Unknown literal tokens still fail.
            for token in re.split(r"[,\s]+", ok.strip()):
                if not token:
                    continue
                if token.endswith(".*"):
                    prefix = token[:-1]  # keep the trailing dot
                    emitted.update(
                        n for n in registry if n.startswith(prefix)
                    )
                elif token in registry:
                    emitted.add(token)
                else:
                    findings.append(
                        Finding(
                            PASS,
                            "metric-undocumented",
                            rpath,
                            line,
                            token,
                            "metric-ok declares a name that is not in the "
                            "documented registry block",
                        )
                    )
            if name is None:
                continue  # dynamic emit, coverage declared: done
        if name is None:
            findings.append(
                Finding(
                    PASS,
                    "metric-dynamic-name",
                    rpath,
                    line,
                    f"METRICS.{method}",
                    "metric name is not a string literal — computed names "
                    "cannot be registry-checked; emit a documented literal "
                    "or declare coverage with `# metric-ok: <names>`",
                )
            )
            continue
        emitted.add(name)
        if name not in registry:
            findings.append(
                Finding(
                    PASS,
                    "metric-undocumented",
                    rpath,
                    line,
                    name,
                    "name is not in the documented registry block in "
                    "utils/metrics.py — add it (or fix the typo)",
                )
            )
        elif EMITTERS[method] != _name_kind(name):
            findings.append(
                Finding(
                    PASS,
                    "metric-kind-mismatch",
                    rpath,
                    line,
                    name,
                    f"emitted via {method}() but the name's prefix says "
                    f"{_name_kind(name)} (hist.* -> observe, gauge.*/"
                    f"fleet.* -> set_gauge, else inc)",
                )
            )
    for name, line in sorted(registry.items()):
        if name not in emitted:
            findings.append(
                Finding(
                    PASS,
                    "metric-unused",
                    registry_path,
                    line,
                    name,
                    "documented in the registry but never emitted anywhere "
                    "in the scan tree — dead doc, or its call site was "
                    "dropped",
                )
            )
    return findings
