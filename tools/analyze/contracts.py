"""Pass 3: the frozen-contract checker.

The reference implementation froze three surfaces (SURVEY §3.6) that any
perf PR could silently drift — exactly the AsicBoost lesson from
PAPERS.md: aggressive pipeline optimization is only safe when the
contract surface is pinned by machinery.  Golden vectors here were
generated from the frozen implementations and hard-coded; the pass
recomputes and compares, no network, no device:

- **bitcoin/message**: Go-JSON byte-exact ``marshal`` for Join / Request /
  Result (field order, separators, u64 masking) and ``unmarshal``
  round-trips including the poison-rejection rules.
- **lsp/message**: byte-exact codec incl. base64 payloads and the
  ``null`` nil-payload convention.
- **bitcoin/hash**: ``Hash(msg, nonce)`` vectors (single SHA-256 over
  ``"<msg> <nonce>"``, big-endian first 8 bytes).
- **workload registry** (ISSUE 9): every registered workload's own
  golden vectors are recomputed through its ``hash_nonce`` — the same
  pin the reference contract gets, so no workload's hash family can
  drift silently; the DEFAULT workload must additionally agree with the
  reference ``bitcoin/hash`` vectors byte-for-byte (the sha256d path is
  the frozen contract, registry or not).  The device kernels' own
  recompute of those vectors is a test (tests/test_workloads.py), not
  part of this static pass.
- **CLI stdout**: the usage strings (driven through ``main()`` with a
  wrong argc) and the literal ``Result``/``Disconnected``/``Server
  listening`` prints, pinned at source level.

``modules`` overrides exist so the seeded-violation fixtures
(tests/fixtures_analyze) can demonstrate every rule firing against a
deliberately broken codec.
"""

from __future__ import annotations

import io
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from .common import REPO_ROOT, Finding

PASS = "contracts"

#: Hash(msg, nonce) golden vectors — frozen from bitcoin/hash.py, which is
#: itself pinned to the reference bitcoin/hash.go:13-17.
HASH_VECTORS = (
    ("hello", 0, 13593802692011500125),
    ("hello", 12345, 6725106177369798965),
    ("bitcoin", 999999999999, 12216901194327863447),
    ("", 1, 16224919167884709661),
    ("chaos", 4000, 9384656945151152569),
)

#: (constructor name, args, frozen bytes) for the mining wire protocol.
BITCOIN_VECTORS = (
    ("join", (), b'{"Type":0,"Data":"","Lower":0,"Upper":0,"Hash":0,"Nonce":0}'),
    (
        "request",
        ("abc", 0, 100),
        b'{"Type":1,"Data":"abc","Lower":0,"Upper":100,"Hash":0,"Nonce":0}',
    ),
    (
        "result",
        ((1 << 64) - 1, 42),
        b'{"Type":2,"Data":"","Lower":0,"Upper":0,"Hash":18446744073709551615,"Nonce":42}',
    ),
)

#: (constructor name, args, frozen bytes) for the LSP transport codec.
LSP_VECTORS = (
    ("connect", (), b'{"Type":0,"ConnID":0,"SeqNum":0,"Size":0,"Payload":null}'),
    (
        "data",
        (7, 3, 2, b"hi"),
        b'{"Type":1,"ConnID":7,"SeqNum":3,"Size":2,"Payload":"aGk="}',
    ),
    ("ack", (7, 3), b'{"Type":2,"ConnID":7,"SeqNum":3,"Size":0,"Payload":null}'),
)

#: Junk each codec must reject with None, never an exception.  Per-codec:
#: the mining codec validates u64 range/type on its own fields; the LSP
#: codec (like Go's) ignores unknown fields, so its poison set is only
#: structural junk.
BITCOIN_POISON = (
    b"",
    b"not json",
    b"[1,2]",
    b'{"Type":1,"Lower":-1}',
    b'{"Type":1,"Lower":true}',
    b'{"Type":1,"Data":7}',
)
LSP_POISON = (b"", b"not json", b"[1,2]", b'{"Type":"x"}', b'{"Payload":"%%%"}')

#: (relative file, required literal) — the frozen stdout prints, pinned at
#: source level so a refactor cannot rewrite them unnoticed.
SOURCE_PINS = (
    (
        "bitcoin_miner_tpu/apps/client.py",
        'print("Result", result[0], result[1], file=out)',
    ),
    ("bitcoin_miner_tpu/apps/client.py", 'print("Disconnected", file=out)'),
    (
        "bitcoin_miner_tpu/apps/server.py",
        'print("Server listening on port", port)',
    ),
)

#: Usage lines printed on wrong argc (argv shapes frozen by the reference).
USAGE = (
    ("client", "Usage: ./client <hostport> <message> <maxNonce>"),
    ("server", "Usage: ./server <port> [--checkpoint=FILE]"),
    ("miner", "Usage: ./miner <hostport>"),
)


def _default_modules() -> Dict[str, Any]:
    from bitcoin_miner_tpu.apps import client as client_mod
    from bitcoin_miner_tpu.apps import miner as miner_mod
    from bitcoin_miner_tpu.apps import server as server_mod
    from bitcoin_miner_tpu.bitcoin import hash as hash_mod
    from bitcoin_miner_tpu.bitcoin import message as bmsg
    from bitcoin_miner_tpu.lsp import message as lmsg

    return {
        "bitcoin_message": bmsg,
        "lsp_message": lmsg,
        "hash": hash_mod,
        "client": client_mod,
        "server": server_mod,
        "miner": miner_mod,
    }


def _check_codec(
    name: str,
    mod: Any,
    vectors: tuple,
    poison: tuple,
    findings: List[Finding],
    path: str,
) -> None:
    Message = getattr(mod, "Message", None)
    if Message is None:
        findings.append(
            Finding(PASS, "codec-missing", path, 1, name, "no Message class")
        )
        return
    for ctor, args, frozen in vectors:
        try:
            got = getattr(Message, ctor)(*args).marshal()
        except Exception as e:  # a crash IS a contract break
            findings.append(
                Finding(
                    PASS, "codec-marshal", path, 1, f"{name}.{ctor}",
                    f"marshal raised {e!r}",
                )
            )
            continue
        if got != frozen:
            findings.append(
                Finding(
                    PASS,
                    "codec-marshal",
                    path,
                    1,
                    f"{name}.{ctor}",
                    f"marshal drifted from the frozen wire bytes: "
                    f"{got!r} != {frozen!r}",
                )
            )
        back = Message.unmarshal(frozen)
        if back is None or back.marshal() != frozen:
            findings.append(
                Finding(
                    PASS,
                    "codec-roundtrip",
                    path,
                    1,
                    f"{name}.{ctor}",
                    f"unmarshal(frozen) does not round-trip: {back!r}",
                )
            )
    for junk in poison:
        try:
            if Message.unmarshal(junk) is not None and junk != b"":
                findings.append(
                    Finding(
                        PASS,
                        "codec-poison",
                        path,
                        1,
                        name,
                        f"unmarshal accepted poison {junk!r} (Go's decoder "
                        f"rejects it; a poison Request crashes miners)",
                    )
                )
        except Exception as e:
            findings.append(
                Finding(
                    PASS, "codec-poison", path, 1, name,
                    f"unmarshal raised {e!r} on junk instead of returning None",
                )
            )


#: Every registered workload must pin at least this many golden vectors.
WORKLOAD_MIN_GOLDEN = 3

#: The registry's frozen default must agree with the reference hash
#: contract — the rest of the checker pins that name's behavior.
WORKLOAD_DEFAULT_NAME = "sha256d"

_WORKLOADS_PATH = "bitcoin_miner_tpu/workloads/__init__.py"


def _check_workloads(findings: List[Finding]) -> None:
    """The per-workload golden-vector pass (ISSUE 9): recompute every
    registered workload's pinned vectors, require a minimum pin count,
    and hold the default to the reference contract."""
    from bitcoin_miner_tpu import workloads

    if workloads.DEFAULT_WORKLOAD != WORKLOAD_DEFAULT_NAME:
        findings.append(
            Finding(
                PASS, "workload-default", _WORKLOADS_PATH, 1,
                workloads.DEFAULT_WORKLOAD,
                f"registry default drifted from the frozen "
                f"{WORKLOAD_DEFAULT_NAME!r}",
            )
        )
    for name in workloads.names():
        w = workloads.get(name)
        if len(w.golden) < WORKLOAD_MIN_GOLDEN:
            findings.append(
                Finding(
                    PASS, "workload-golden-missing", _WORKLOADS_PATH, 1, name,
                    f"workload pins only {len(w.golden)} golden vectors "
                    f"(need >= {WORKLOAD_MIN_GOLDEN}) — an unpinned hash "
                    "family can drift silently",
                )
            )
        for data, nonce, frozen in w.golden:
            try:
                got = w.hash_nonce(data, nonce)
            except Exception as e:  # a crash IS a contract break
                findings.append(
                    Finding(
                        PASS, "workload-vector", _WORKLOADS_PATH, 1,
                        f"{name}({data!r},{nonce})", f"hash_nonce raised {e!r}",
                    )
                )
                continue
            if got != frozen:
                findings.append(
                    Finding(
                        PASS, "workload-vector", _WORKLOADS_PATH, 1,
                        f"{name}({data!r},{nonce})",
                        f"drifted: {got} != frozen {frozen}",
                    )
                )
    # The default's oracle must equal the reference contract itself.
    try:
        w = workloads.get(WORKLOAD_DEFAULT_NAME)
    except ValueError:
        findings.append(
            Finding(
                PASS, "workload-default", _WORKLOADS_PATH, 1,
                WORKLOAD_DEFAULT_NAME, "frozen default not registered",
            )
        )
        return
    for msg, nonce, frozen in HASH_VECTORS:
        if w.hash_nonce(msg, nonce) != frozen:
            findings.append(
                Finding(
                    PASS, "workload-default", _WORKLOADS_PATH, 1,
                    f"{WORKLOAD_DEFAULT_NAME}({msg!r},{nonce})",
                    "default workload disagrees with the reference "
                    "bitcoin/hash contract vectors",
                )
            )


def run(
    root: Path,
    scan_dirs: Any = None,
    modules: Optional[Dict[str, Any]] = None,
) -> List[Finding]:
    findings: List[Finding] = []
    fixture_mode = modules is not None
    mods = modules if modules is not None else _default_modules()

    if "bitcoin_message" in mods:
        _check_codec(
            "bitcoin.Message",
            mods["bitcoin_message"],
            BITCOIN_VECTORS,
            BITCOIN_POISON,
            findings,
            "bitcoin_miner_tpu/bitcoin/message.py" if not fixture_mode else "bad_contract.py",
        )
    if "lsp_message" in mods:
        _check_codec(
            "lsp.Message",
            mods["lsp_message"],
            LSP_VECTORS,
            LSP_POISON,
            findings,
            "bitcoin_miner_tpu/lsp/message.py" if not fixture_mode else "bad_contract.py",
        )
    if "hash" in mods:
        hash_nonce: Callable = mods["hash"].hash_nonce
        for msg, nonce, frozen in HASH_VECTORS:
            got = hash_nonce(msg, nonce)
            if got != frozen:
                findings.append(
                    Finding(
                        PASS,
                        "hash-vector",
                        "bitcoin_miner_tpu/bitcoin/hash.py" if not fixture_mode else "bad_contract.py",
                        1,
                        f"Hash({msg!r},{nonce})",
                        f"drifted: {got} != frozen {frozen}",
                    )
                )

    if not fixture_mode:
        _check_workloads(findings)

    for binary, frozen in USAGE:
        mod = mods.get(binary)
        if mod is None:
            continue
        out = io.StringIO()
        try:
            if binary == "client":
                mod.main([binary], out=out)
                got = out.getvalue()
            else:
                # server/miner mains print to real stdout; capture it.
                import contextlib

                with contextlib.redirect_stdout(out):
                    mod.main([binary])
                got = out.getvalue()
        except SystemExit:
            got = out.getvalue()
        except Exception as e:
            got = f"<raised {e!r}>"
        if got != frozen:
            findings.append(
                Finding(
                    PASS,
                    "cli-usage",
                    f"bitcoin_miner_tpu/apps/{binary}.py",
                    1,
                    binary,
                    f"usage stdout drifted: {got!r} != frozen {frozen!r}",
                )
            )

    if not fixture_mode:
        for relpath, literal in SOURCE_PINS:
            src_path = REPO_ROOT / relpath
            try:
                src = src_path.read_text()
            except OSError:
                src = ""
            if literal not in src:
                findings.append(
                    Finding(
                        PASS,
                        "stdout-pin",
                        relpath,
                        1,
                        literal.split("(")[0],
                        f"frozen print literal missing from source: "
                        f"{literal!r} (reference stdout contract)",
                    )
                )
    return findings
