"""DeviceStore: the accelerator twin of PimStore.

PRs 2-4 gave the *simulated* DRAM path residency - operands live in device
rows, chains never cross the channel, and the ledger measures only real
transfers. The performance backends ("jnp"/"pallas") still ferried every
operand host->device->host on each eval: exactly the traffic Ambit (and
Buddy-RAM's row-resident operand model) exists to elide. This module
closes that gap:

  * ``DeviceBitVector`` / ``DeviceStore`` - bitvectors ``put`` once live
    as jax device arrays behind the SAME handle API as PimStore
    (put/get/free/pin, dirty tracking, LRU spill to host under a
    ``capacity_bytes`` budget). ``OpStats.bytes_touched`` is zero for
    resident operands; only faulted-in / spilled bytes are charged, so
    the ledger is honest for the fast path the same way PR 2 made it
    honest for ambit_sim.

  * ``DevicePlanner`` - the QueryPlanner analogue: one whole expression
    tree evaluates as ONE fused dispatch over resident device arrays
    (jitted-callable LRU in core.engine mirroring ``_compile_cached``),
    results stay resident (dirty: no host read-back until ``get``), and
    ``out=``-style rebinds donate the destination's buffer to XLA
    (``jax.jit(..., donate_argnums=...)``) so chained queries update
    storage in place without allocation churn.

  * epoch-stacked execution - ``execute_epoch`` dispatches a whole
    scheduler epoch of shape-compatible queries as ONE stacked
    ``pallas_call`` (operand tiles stacked along a query axis), one
    kernel launch per epoch instead of one per query.

The DRAM-model fields of the ledger (ns / energy / AAPs) stay zero here:
the accelerator path measures *traffic*, the ambit_sim path measures the
paper's device physics. Both share OpStats so apps and benchmarks compare
them directly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import expr as E
from ..core.bitvector import BitVector
from ..core.engine import (OpStats, _device_compiled,
                           _device_compiled_stacked)
from ..core.simulator import AmbitError
from ..obs import (PLANNER_LAUNCH, PLANNER_OPERAND_BYTES, PLANNER_STACK,
                   PLANNER_STACK_BYTES, STORE_POPCOUNT, STORE_POPCOUNT_FLAT,
                   STORE_POPCOUNT_WAIT, host_span)
from .store import LruSpillBase


@dataclasses.dataclass(eq=False)
class DeviceBitVector:
    """Handle to a bitvector resident on the accelerator as a packed
    uint32 device array. Compares (and hashes) by identity.

    ``spilled`` handles hold no device buffer (LRU-evicted under the
    capacity budget) but stay fully usable: the host copy is current,
    ``get`` is free, and ``ensure_resident`` re-uploads on demand.
    ``pinned`` handles are never chosen as eviction victims."""

    store: "DeviceStore"
    n_bits: int
    shape: Tuple[int, ...]       # leading (batch) dims of the host layout
    words32: int                 # packed uint32 words per logical row
    _dev: Optional[jnp.ndarray] = None   # shape + (words32,) uint32
    dirty: bool = False
    pinned: bool = False
    spilled: bool = False
    name: Optional[str] = None
    _host: Optional[BitVector] = None
    # True when the store created _dev itself (planner results): only
    # such buffers may be donated to XLA - a put() buffer is shared with
    # the caller's BitVector, and donating it would invalidate memory
    # the caller still references.
    _private: bool = False

    @property
    def device_bytes(self) -> int:
        n_rows = int(np.prod(self.shape)) if self.shape else 1
        return n_rows * self.words32 * 4

    @property
    def slots(self) -> list:
        """Placement-API compatibility: accelerator arrays have no row
        homes, so apps' ``near=handle.slots`` chains degrade to None."""
        return []

    @property
    def freed(self) -> bool:
        return self._dev is None and not self.spilled

    def get(self) -> BitVector:
        return self.store.get(self)

    def free(self) -> None:
        self.store.free(self)

    def __repr__(self):
        nm = f" {self.name!r}" if self.name else ""
        flags = (" pinned" if self.pinned else "") + \
            (" spilled" if self.spilled else "")
        return (f"<DeviceBitVector{nm} n_bits={self.n_bits} "
                f"bytes={self.device_bytes} dirty={self.dirty}{flags}>")


class DeviceStore(LruSpillBase):
    """put/get/free lifecycle for bitvectors resident on one accelerator.

    Mirrors PimStore's ledger contract: ``bytes_to_device`` /
    ``bytes_from_device`` count only genuine host<->accelerator
    transfers (uploads at put/fault-in, read-backs of dirty data), and
    the LRU spills the coldest unpinned handle when ``capacity_bytes``
    would be exceeded - clean victims for free, dirty ones read back
    through the ledger first."""

    _handle_desc = "device bitvector"
    _obs_name = "device_store"

    def __init__(self, backend: str = "jnp",
                 capacity_bytes: Optional[int] = None):
        if backend not in ("jnp", "pallas"):
            raise ValueError(
                f"DeviceStore backends are 'jnp'/'pallas', got {backend!r} "
                "(the DRAM model path is PimStore)")
        self.backend = backend
        self.capacity_bytes = capacity_bytes
        self.resident_bytes = 0
        self.host_writes = 0
        self.host_reads = 0
        self.bytes_to_device = 0
        self.bytes_from_device = 0
        self._lru_init()

    # -- LruSpillBase hooks ---------------------------------------------------

    def _owner_of(self, rbv: DeviceBitVector):
        return rbv.store

    def _resident_storage(self, rbv: DeviceBitVector) -> bool:
        return rbv._dev is not None

    def _release_rows(self, rbv: DeviceBitVector) -> None:
        if rbv._dev is not None:
            self.resident_bytes -= rbv.device_bytes
        rbv._dev = None

    def _move_storage(self, out: DeviceBitVector,
                      res: DeviceBitVector) -> None:
        out._dev, res._dev = res._dev, None   # byte count rides along
        out._private = res._private

    def _read_back(self, rbv: DeviceBitVector) -> BitVector:
        # Materialize on the host (np.asarray forces the D2H transfer):
        # wrapping the device array itself would keep accelerator memory
        # alive past spill, silently breaking the capacity budget.
        out = BitVector(np.asarray(rbv._dev), rbv.n_bits)
        rbv._host = out
        rbv.dirty = False
        self._charge_io("from_device", self._io_cause or "read_back",
                        rbv.device_bytes)
        return out

    def spill(self, rbv: DeviceBitVector, _force_held: bool = False) -> None:
        super().spill(rbv, _force_held=_force_held)
        # Clean victims skip _read_back, but their host copy may still
        # wrap a device array (put() shares the caller's buffer): pin the
        # copy to host memory so the spill really releases the device.
        if isinstance(rbv._host.data, jnp.ndarray):
            rbv._host = BitVector(np.asarray(rbv._host.data), rbv.n_bits)

    # -- capacity -------------------------------------------------------------

    def _make_room(self, nbytes: int,
                   protect: Iterable[DeviceBitVector] = ()) -> None:
        if self.capacity_bytes is None:
            return
        while self.resident_bytes + nbytes > self.capacity_bytes:
            if not self._evict_lru(protect):
                raise AmbitError(
                    f"device capacity full ({self.resident_bytes}/"
                    f"{self.capacity_bytes} B resident) and every device "
                    f"bitvector is pinned or in use")

    def adopt(self, rbv: DeviceBitVector) -> DeviceBitVector:
        """Track an externally built handle (planner results) in the LRU
        and the capacity ledger, like any put() handle."""
        self.resident_bytes += rbv.device_bytes
        self._register(rbv)
        return rbv

    # -- lifecycle ------------------------------------------------------------

    def put(self, bv: BitVector, policy=None, near=None,
            name: Optional[str] = None,
            pin: bool = False) -> DeviceBitVector:
        """Upload a host BitVector (``near``/``policy`` are accepted for
        PimStore API compatibility; an accelerator has no row placement)."""
        del policy, near
        data = jnp.asarray(bv.data, jnp.uint32)
        rbv = DeviceBitVector(
            store=self, n_bits=bv.n_bits, shape=tuple(data.shape[:-1]),
            words32=int(data.shape[-1]), _dev=None, dirty=False,
            name=name, _host=bv)
        self._make_room(rbv.device_bytes)
        rbv._dev = data
        self.adopt(rbv)
        self._charge_io("to_device", "upload", rbv.device_bytes)
        if pin:
            try:
                self.pin(rbv)
            except AmbitError:          # over budget: undo the upload
                self.free(rbv)
                raise
        return rbv

    def ensure_resident(self, rbv: DeviceBitVector,
                        protect: Iterable[DeviceBitVector] = ()
                        ) -> DeviceBitVector:
        """Fault a spilled handle back onto the accelerator (charged as a
        fresh upload). Live handles just refresh recency."""
        self._check_handle(rbv)
        if not rbv.spilled:
            self._touch(rbv)
            return rbv
        self._make_room(rbv.device_bytes, protect=(rbv, *protect))
        rbv._dev = jnp.asarray(rbv._host.data, jnp.uint32)
        rbv._private = False        # conservatively non-donatable again
        rbv.spilled = False
        rbv.dirty = False
        self.adopt(rbv)
        self._charge_io("to_device", "fault_in", rbv.device_bytes)
        self._invalidate(rbv)   # placement changed: generation bumps
        return rbv

    # -- device-side reduction -------------------------------------------------

    def popcount(self, rbv: DeviceBitVector) -> int:
        """Count set bits WITHOUT reading the bitvector back: the
        reduction runs on the accelerator and only one int32 count per
        row crosses to the host - 4 ledger bytes per row instead of the
        whole array. On the pallas backend a live handle costs one
        dispatch of the jitted ``popcount_rows`` on its buffer as it
        lies (a one-row handle's kernel reads it flat and counts in
        ``store_popcount_flat``), then one blocking read; the jnp
        backend reduces with ``lax.population_count``. Rows sum on the
        host in int64, and a handle with 2^31 or more bits per row is
        refused rather than wrapping its int32 count. Device arrays are
        tail-masked by construction (put data comes from packed
        BitVectors; planner results are masked in
        ``_device_compiled``), so the full-array count is exact.
        Spilled handles count their current host copy for free."""
        with host_span(STORE_POPCOUNT):
            self._check_handle(rbv)
            if rbv.words32 * 32 >= 2 ** 31:
                raise AmbitError(
                    f"popcount of {rbv!r}: {rbv.words32 * 32} bits per row "
                    "reach 2^31 and would wrap the int32 per-row count")
            if rbv.spilled:
                return int(np.asarray(rbv._host.popcount(), np.int64).sum())
            self._touch(rbv)
            if self.backend == "pallas":
                from ..kernels import bitwise, ops as kops
                if bitwise.reads_flat(rbv._dev.shape):
                    self.metrics.counter(STORE_POPCOUNT_FLAT).inc(1)
                counts = kops.popcount(rbv._dev)
            else:
                counts = jax.lax.population_count(
                    rbv._dev.reshape(-1, rbv.words32)).astype(
                    jnp.int32).sum(-1)
            with host_span(STORE_POPCOUNT_WAIT):
                counts = np.asarray(counts, np.int64)
            self._charge_io("from_device", "popcount", 4 * counts.size)
            return int(counts.sum())


@dataclasses.dataclass
class DeviceReport:
    """What one accelerator planner execution (or epoch) did. ``per_bank``
    stays empty - an accelerator dispatch has no per-bank DRAM ledger -
    and exists so the async scheduler's accounting path is uniform."""

    queries: int = 0
    donated: int = 0                # out= buffers donated to XLA
    per_bank: Dict[Tuple[int, int], OpStats] = dataclasses.field(
        default_factory=dict)
    stats: OpStats = dataclasses.field(default_factory=OpStats)


class DevicePlanner:
    """Whole-Expr execution over DeviceStore handles: the accelerator
    analogue of QueryPlanner, sharing its ``execute`` / ``footprint`` /
    ``last_report`` surface so AmbitRuntime and AsyncScheduler drive
    either interchangeably."""

    def __init__(self, store: DeviceStore):
        self.store = store
        self.backend = store.backend
        self.last_report: Optional[DeviceReport] = None

    # -- scheduler hooks ------------------------------------------------------

    def footprint(self, env: Dict[str, DeviceBitVector]) -> frozenset:
        """An accelerator epoch is one fused launch, not a set of banks:
        queries never contend for (device, bank) resources, so epoch
        admission is governed purely by data hazards and the stack key."""
        return frozenset()

    def stack_key(self, expression: E.Expr, env: Dict[str, object]):
        """Queries sharing this key stack into ONE kernel launch: same
        expression DAG, operand names, and operand geometry. Ticket
        operands (results of earlier queries) inherit the geometry of
        their producers, so any concrete handle in the DAG decides."""
        handle = self._any_handle(env)
        if handle is None:
            return (expression, tuple(sorted(env)))
        return (expression, tuple(sorted(env)), handle.n_bits,
                handle.shape, handle.words32)

    def _any_handle(self, env: Dict[str, object]):
        for nm in sorted(env):
            v = env[nm]
            if isinstance(v, DeviceBitVector):
                return v
            sub = getattr(v, "env", None)   # a Ticket: recurse
            if sub is not None:
                h = self._any_handle(sub)
                if h is not None:
                    return h
        return None

    # -- execution ------------------------------------------------------------

    def _validate(self, env: Dict[str, DeviceBitVector]):
        if not env:
            raise ValueError("planner needs at least one operand")
        names = sorted(env)
        first = env[names[0]]
        for nm in names:
            rbv = env[nm]
            self.store._check_live(rbv)
            if (rbv.n_bits, rbv.shape, rbv.words32) != (
                    first.n_bits, first.shape, first.words32):
                raise ValueError(
                    "bbop operands must be row-aligned and equal-sized "
                    "(Section 5.3)")
            self.store._touch(rbv)
        return names, first

    def execute(self, expression: E.Expr,
                env: Dict[str, DeviceBitVector],
                out_name: Optional[str] = None,
                donate_to: Optional[DeviceBitVector] = None
                ) -> DeviceBitVector:
        """One fused dispatch over resident operands; the result stays
        resident (dirty). ``donate_to`` - the handle an ``out=`` rebind
        will overwrite - donates its buffer to XLA when it is exactly one
        of the operands, so the chained update reuses its storage."""
        names, first = self._validate(env)
        donate_idx = None
        if donate_to is not None and donate_to._private:
            # only store-created buffers donate (a put() buffer is shared
            # with the caller's BitVector); aliased twice is also unsafe
            matches = [k for k, nm in enumerate(names)
                       if env[nm] is donate_to]
            if len(matches) == 1:
                donate_idx = matches[0]
        fn = _device_compiled(expression, tuple(names), self.backend,
                              first.n_bits, donate_idx)
        self._count_operand_bytes(1, len(names), first)
        with host_span(PLANNER_LAUNCH):
            out_dev = fn(*[env[nm]._dev for nm in names])
        # Budget the result AFTER the dispatch consumed the operand
        # buffers: cold operands are now legal spill victims, so an
        # exact-fit capacity still runs arbitrarily long chains. A
        # donated destination must survive until the rebind.
        self.store._make_room(
            first.device_bytes,
            protect=() if donate_idx is None else (donate_to,))
        res = DeviceBitVector(
            store=self.store, n_bits=first.n_bits, shape=first.shape,
            words32=first.words32, _dev=out_dev, dirty=True, name=out_name,
            _private=True)
        self.store.adopt(res)
        self.last_report = DeviceReport(
            queries=1, donated=0 if donate_idx is None else 1,
            stats=OpStats())
        self._record_dispatch(queries=1,
                              donated=0 if donate_idx is None else 1)
        return res

    def _count_operand_bytes(self, queries: int, operands: int,
                             first: DeviceBitVector) -> None:
        """Add a launch's operand bytes as its fused program receives
        them: padded to whole kernel tiles on the pallas backend, as
        stored on jnp."""
        per = first.device_bytes
        if self.backend == "pallas":
            from ..kernels import ops as kops
            rows = int(np.prod(first.shape)) if first.shape else 1
            per = kops.fused_operand_bytes(rows, first.words32)
        self.store.metrics.counter(PLANNER_OPERAND_BYTES).inc(
            queries * operands * per)

    def _record_dispatch(self, queries: int, donated: int = 0) -> None:
        m = self.store.metrics
        m.counter("fused_dispatches").inc(1)
        m.counter("fused_queries").inc(queries)
        if donated:
            m.counter("donated_buffers").inc(donated)
        tr = self.store.tracer
        if tr.enabled:
            tr.instant(("device_store", "dispatch"), "fused_dispatch",
                       "dispatch", args={"queries": queries,
                                         "backend": self.backend,
                                         "donated": donated})

    def execute_epoch(self, jobs: Sequence[tuple]) -> List[DeviceBitVector]:
        """Dispatch one scheduler epoch - ``(expression, env, out_name,
        out_handle)`` jobs sharing a stack key - as ONE stacked kernel
        launch. Singleton epochs take the unstacked path so ``out=``
        chains keep their buffer donation.

        The host only gathers the operands' stored buffers, job-major;
        the epoch program stacks them, runs the kernel and hands back one
        result per job in its stored shape, so no eager stack, slice or
        reshape runs outside the program."""
        if len(jobs) == 1:
            expression, env, out_name, out = jobs[0]
            donate = out if out is not None and \
                any(v is out for v in env.values()) else None
            res = self.execute(expression, env, out_name=out_name,
                               donate_to=donate)
            return [res]
        expression, env0, _, _ = jobs[0]
        names, first = self._validate(env0)
        for _, env, _, _ in jobs[1:]:
            jnames, jfirst = self._validate(env)
            if jnames != names or (jfirst.n_bits, jfirst.shape) != (
                    first.n_bits, first.shape):
                raise AmbitError(
                    "epoch jobs must share (expression, names, shape) - "
                    "the scheduler's stack key guarantees this")
        fn = _device_compiled_stacked(expression, tuple(names),
                                      self.backend, first.n_bits)
        with host_span(PLANNER_STACK, operands=len(names)):
            operands = [job[1][nm]._dev for job in jobs for nm in names]
        self.store.metrics.counter(PLANNER_STACK_BYTES).inc(
            len(jobs) * len(names) * first.device_bytes)
        self._count_operand_bytes(len(jobs), len(names), first)
        with host_span(PLANNER_LAUNCH):
            outs = fn(*operands)        # one per job, shape + (words32,)
        self.store._make_room(len(jobs) * first.device_bytes)
        results = []
        for out_dev, (_, _, out_name, _) in zip(outs, jobs):
            res = DeviceBitVector(
                store=self.store, n_bits=first.n_bits, shape=first.shape,
                words32=first.words32, _dev=out_dev,
                dirty=True, name=out_name, _private=True)
            self.store.adopt(res)
            results.append(res)
        self.last_report = DeviceReport(queries=len(jobs), stats=OpStats())
        self._record_dispatch(queries=len(jobs))
        return results
