"""Multi-host (DCN) scaling for a single miner worker.

Two distinct scaling axes exist in this framework (SURVEY §2.3):

1. **Process parallelism over LSP** — the reference's model: every miner
   process is an independent worker; the scheduler splits ranges across
   them.  This is the default and right answer for scaling out, because
   the workload is embarrassingly parallel and the min-fold is tiny.
2. **One logical worker spanning hosts** — this module: all hosts of a
   TPU pod join one `jax.distributed` job, build a global mesh over every
   chip, and run the sharded sweep (parallel/sweep.py) with its pmin
   cascade riding ICI within a slice and DCN across hosts.  XLA owns the
   transport — there is no hand-rolled NCCL/MPI analogue to port, by
   design.

Use (2) when one job must appear as a single ultra-fast miner to the
scheduler (e.g. BASELINE's v5e-8+ sweeps driven by one Request); use (1)
otherwise.  Run the same CLI on every host::

    python -m bitcoin_miner_tpu.apps.miner host:port --multihost \
        --coordinator <host0>:1234 --num-hosts N --host-id I

Only host 0 opens the LSP connection to the scheduler; the others run the
same jitted computation via XLA's SPMD launch (standard multi-controller
JAX: every process executes the same program on its local devices).

The full wiring — `jax.distributed.initialize` over a loopback
coordinator, the cross-process global mesh, the host-0 broadcast, and the
sharded sweep across processes — executes in
tests/test_multihost_distributed.py as a real two-process CPU job; on TPU
pods only the device type changes.

Why this loop is deliberately lockstep (not pipelined like the
single-host miner's SweepPipeline): the inter-chunk gap here is one
result fetch + one broadcast + template fill.  On a real pod the fetch is
device-local (~ms against ~0.5 s chunks, <1% idle), and the scheduler's
2-deep window means the next Request is already queued in LSP when the
sweep lands.  The ~0.2 s fetch cost that forced the single-host pipeline
was measured on an older remote runtime (before PR 1), which multihost
pods don't use.  Pipelining across the request broadcast would also serialize on the
device queue anyway (the broadcast is a collective enqueued behind the
sweep's dispatches), so the added complexity buys ~nothing where this
mode actually runs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from .mesh import MINER_AXIS
from jax.sharding import Mesh

#: Max UTF-8-encoded job-data bytes in one broadcast buffer.  Chosen to fit
#: an LSP datagram (MaxMessageSize=1000, lsp/util.go:16) alongside the other
#: Request fields — data the scheduler could never have delivered anyway.
MAX_DATA = 960

_HDR = 6  # [alive, lower_hi, lower_lo, upper_hi, upper_lo, dlen]


def encode_request(data: str, lower: int, upper: int) -> np.ndarray:
    """Pack a Request into the fixed-shape u32 broadcast buffer.

    u32 halves because the broadcast rides a jax collective (no u64 on all
    paths).  Raises ``ValueError`` on oversize data rather than truncating:
    a silently shortened message would mine the wrong string and return a
    plausible-but-incorrect Result.
    """
    raw = data.encode("utf-8")
    if len(raw) > MAX_DATA:
        raise ValueError(
            f"job data is {len(raw)} UTF-8 bytes; multihost broadcast caps "
            f"at {MAX_DATA}"
        )
    if not 0 <= lower < 1 << 64 or not 0 <= upper < 1 << 64:
        raise ValueError(f"nonce bounds out of u64 range: [{lower}, {upper}]")
    buf = np.zeros(_HDR + MAX_DATA, dtype=np.uint32)
    buf[0] = 1
    buf[1], buf[2] = lower >> 32, lower & 0xFFFFFFFF
    buf[3], buf[4] = upper >> 32, upper & 0xFFFFFFFF
    buf[5] = len(raw)
    buf[_HDR : _HDR + len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return buf


def encode_shutdown() -> np.ndarray:
    """The all-hosts-exit sentinel (alive flag 0)."""
    return np.zeros(_HDR + MAX_DATA, dtype=np.uint32)


def decode_request(buf: np.ndarray) -> Optional[Tuple[str, int, int]]:
    """Inverse of :func:`encode_request`; ``None`` means shutdown."""
    buf = np.asarray(buf)
    if buf[0] == 0:
        return None
    lower = (int(buf[1]) << 32) | int(buf[2])
    upper = (int(buf[3]) << 32) | int(buf[4])
    dlen = int(buf[5])
    data = bytes(buf[_HDR : _HDR + dlen].astype(np.uint8)).decode("utf-8")
    return data, lower, upper


def initialize(
    coordinator: str, num_hosts: int, host_id: int
) -> None:
    """Join this process to the multi-host JAX job (idempotent)."""
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )


def global_mesh(axis_name: str = MINER_AXIS) -> Mesh:
    """A 1-D mesh over every chip of every host in the job.

    The sweep's chunk batch shards contiguously across it exactly as on a
    single host — XLA places the pmin cascade's reduction tree so the
    intra-host stages ride ICI and only the final stage crosses DCN.
    """
    return Mesh(list(jax.devices()), (axis_name,))


def is_primary() -> bool:
    """True on the host that should own the LSP connection."""
    return jax.process_index() == 0
