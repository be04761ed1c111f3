"""The range-fold workload contract (ISSUE 9).

Every sweep consumer in this repo — scheduler validation, miner kernel
tiers, gateway/interval-store planning, federation routing, loadgen
oracles — is generic over one shape of problem:

    associatively fold ``f(data, nonce)`` over the inclusive nonce range
    ``[lower, upper]`` and return the argmin, lowest-nonce ties.

A :class:`Workload` names one concrete ``f`` and bundles everything a
process needs to serve it:

- the **bit-exact Python oracle** (:meth:`hash_nonce` /
  :meth:`min_range`) — the trusted slow tier the scheduler validates
  Results against and tests compare every faster tier to;
- the **per-tier kernel factories** (:meth:`make_search` /
  :meth:`make_async_search`) over the tier ladder in :attr:`tiers`,
  strongest first — the watchdog's downgrade chain
  (pallas → xla → cpu → hashlib) is built from exactly this list, so a
  workload with no device kernel still degrades sanely to its oracle;
- the **frozen golden vectors** (:attr:`golden`) — literal
  ``(data, nonce, hash)`` triples pinned in source; the analyzer's
  frozen-contract pass recomputes every registered workload's vectors on
  every run, so no workload's hash function can drift silently (the same
  machinery that pins the default's reference contract).

Workload objects are pure, stateless policy (no locks, no threads —
enforced by the analyzer registry): one instance is shared read-only by
every thread of a process.  Device-tier machinery is imported lazily so
importing the registry costs hashlib only.
"""

from __future__ import annotations

from typing import Optional, Tuple

#: (data, nonce, expected 64-bit hash) — the frozen-vector row shape.
GoldenVector = Tuple[str, int, int]

#: The full tier ladder, strongest first.  A workload's :attr:`tiers` is
#: an ordered subset; "hashlib" (the pure-Python oracle) must be last —
#: it is the one tier that cannot wedge.
TIER_LADDER = ("pallas", "xla", "cpu", "hashlib")


class Workload:
    """One registered range-fold workload (see module docstring).

    Subclasses implement :meth:`hash_nonce` and may override the tier
    factories; the base class provides the oracle sweep and the
    hashlib-tier factory, so a minimal workload is just a hash function
    plus golden vectors.
    """

    #: Registry key; sweep consumers resolve workloads by this name.
    name: str = ""
    #: One-line description for ``--workload`` listings and the README.
    description: str = ""
    #: Ordered strongest-first subset of :data:`TIER_LADDER`.
    tiers: Tuple[str, ...] = ("hashlib",)
    #: Frozen golden vectors, pinned literal in source (the analyzer's
    #: contract pass recomputes these for every registered workload).
    golden: Tuple[GoldenVector, ...] = ()
    #: ASCII byte(s) between ``data`` and the decimal nonce, for
    #: workloads the device message-template kernels can serve
    #: (ops/sweep reads this to build message layouts); None = no
    #: device tier.
    sep: Optional[bytes] = None
    #: Which device kernel family serves this workload's message format
    #: ("sha256" or "blake2b" — ISSUE 20): picks the layout builder and
    #: jitted kernel the sweep drivers compile.  Meaningful only with
    #: :attr:`sep` set.
    kernel_family: str = "sha256"
    #: Whether the compiled C++ SHA-NI sweep (native/) computes this
    #: workload — true only for the frozen default's message format.
    native_ok: bool = False

    # ------------------------------------------------------------- oracle

    def hash_nonce(self, data: str, nonce: int) -> int:
        """The workload's ``f(data, nonce) -> uint64`` — the bit-exact
        reference every other tier must match."""
        raise NotImplementedError

    def min_range(self, data: str, lower: int, upper: int) -> Tuple[int, int]:
        """Oracle sweep of inclusive ``[lower, upper]``: ``(min hash,
        argmin nonce)``, lowest-nonce ties — the same contract as
        ``bitcoin.hash.min_hash_range``."""
        if lower > upper:
            raise ValueError(f"empty nonce range [{lower}, {upper}]")
        best_hash = 1 << 64
        best_nonce = lower
        hash_nonce = self.hash_nonce
        for n in range(lower, upper + 1):
            h = hash_nonce(data, n)
            if h < best_hash:
                best_hash, best_nonce = h, n
        return best_hash, best_nonce

    # ------------------------------------------------------ tier factories

    def _check_tier(self, tier: str) -> None:
        if tier not in self.tiers:
            raise ValueError(
                f"workload {self.name!r} has no {tier!r} tier "
                f"(ladder: {'->'.join(self.tiers)})"
            )

    def make_search(self, tier: str, devices: Optional[int] = None):
        """A synchronous ``(data, lower, upper) -> (hash, nonce)`` search
        on ``tier``.  Device tiers exist only for workloads a device
        kernel family serves (:attr:`sep` set — the family is
        :attr:`kernel_family`); ``devices`` spans the jax tiers over an
        N-chip mesh."""
        self._check_tier(tier)
        if tier in ("hashlib", "cpu") and devices is not None and devices != 1:
            raise ValueError(
                "--devices requires a JAX backend (xla/pallas); "
                f"the {tier!r} tier is a single-process host loop"
            )
        if tier == "hashlib":
            return self.min_range
        if tier == "cpu":
            return self._cpu_search()
        if self.sep is None:
            raise ValueError(
                f"workload {self.name!r} declares device tier {tier!r} "
                "but no message template (sep)"
            )
        if tier == "pallas" and self.kernel_family != "sha256":
            raise ValueError(
                f"workload {self.name!r}: the {self.kernel_family!r} "
                "kernel family has no pallas lowering"
            )
        if devices is not None and devices != 1:
            if devices < 1:
                raise ValueError(f"--devices must be >= 1, got {devices}")
            from ..parallel import default_mesh, sweep_min_hash_sharded

            mesh = default_mesh(devices)

            def sharded(data: str, lower: int, upper: int) -> Tuple[int, int]:
                r = sweep_min_hash_sharded(
                    data, lower, upper, mesh=mesh, backend=tier, workload=self
                )
                return r.hash, r.nonce

            return sharded
        from ..ops.sweep import sweep_min_hash

        def search(data: str, lower: int, upper: int) -> Tuple[int, int]:
            r = sweep_min_hash(data, lower, upper, backend=tier, workload=self)
            return r.hash, r.nonce

        return search

    def make_async_search(self, tier: str, devices: Optional[int] = None):
        """An async search (``submit(data, lo, hi) -> Future``) on
        ``tier`` — the shape ``apps.miner.run_miner`` serves Requests
        with.  Jax tiers ride the cross-request
        :class:`~bitcoin_miner_tpu.ops.sweep.SweepPipeline`; host tiers
        run behind a one-worker FIFO pool."""
        self._check_tier(tier)
        from ..apps import miner as miner_mod

        if tier in ("pallas", "xla") and self.sep is not None:
            from ..utils.platform import enable_compile_cache

            enable_compile_cache()
            return miner_mod._PipelineSearch(tier, devices=devices, workload=self)
        return miner_mod._PoolSearch(self.make_search(tier, devices), tier)

    def _cpu_search(self):
        """The cpu-tier search: the compiled native sweep when this
        workload's format is the one it computes, else the oracle loop
        (subclasses override with faster prefix-folded host loops)."""
        native = self._native_search()
        return native if native is not None else self.min_range

    def _native_search(self):
        """The compiled C++ sweep if it computes this workload and is
        buildable here, else None."""
        if not self.native_ok:
            return None
        try:
            from .. import native

            if native.available():
                return native.min_hash_range_native
        except Exception:
            pass
        return None

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"<Workload {self.name!r} tiers={'->'.join(self.tiers)}>"
