"""High-level Python API over the native core.

Reference parity: python/framework/pccl/_pccl.py of the reference —
Communicator, MasterNode, TensorInfo (from_numpy/from_torch, plus from_jax
here), SharedState, AsyncReduceHandle, ReduceOperandDescriptor — with the
same fault-tolerance contract: collective ops raise PcclError subclasses on
peer churn and the caller retries after update_topology() (reference
README.md:90-130 loop).

TPU note: jax.Array buffers are immutable and may live in HBM; TensorInfo
.from_jax stages to a pinned host copy, and jax_value() returns the synced
content as a fresh device array. The hierarchical ICI+WAN path lives in
pccl_tpu.parallel.hierarchical.
"""

from __future__ import annotations

import ctypes
import enum
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np

from . import _native


class Result(enum.IntEnum):
    SUCCESS = 0
    INVALID_ARGUMENT = 1
    NOT_CONNECTED = 2
    CONNECTION_LOST = 3
    OPERATION_ABORTED = 4
    TOO_FEW_PEERS = 5
    DUPLICATE_TAG = 6
    KICKED = 7
    MASTER_UNREACHABLE = 8
    INTERNAL_ERROR = 9
    CONTENT_MISMATCH = 10
    PENDING_ASYNC_OPS = 11
    INVALID_USAGE = 12


class DataType(enum.IntEnum):
    UINT8 = 0
    INT8 = 1
    UINT16 = 2
    INT16 = 3
    UINT32 = 4
    INT32 = 5
    UINT64 = 6
    INT64 = 7
    FLOAT16 = 8
    BFLOAT16 = 9
    FLOAT32 = 10
    FLOAT64 = 11


class DeviceType(enum.IntEnum):
    HOST = 0
    TPU = 1


class ReduceOp(enum.IntEnum):
    SUM = 0
    AVG = 1
    PROD = 2
    MAX = 3
    MIN = 4


class QuantizationAlgorithm(enum.IntEnum):
    NONE = 0
    MIN_MAX = 1
    ZERO_POINT_SCALE = 2


class SharedStateSyncStrategy(enum.IntEnum):
    ENFORCE_POPULAR = 0
    RECEIVE_ONLY = 1
    SEND_ONLY = 2


class Attribute(enum.IntEnum):
    GLOBAL_WORLD_SIZE = 0
    PEER_GROUP_WORLD_SIZE = 1
    NUM_DISTINCT_PEER_GROUPS = 2
    LARGEST_PEER_GROUP_WORLD_SIZE = 3
    # master HA (docs/10_high_availability.md)
    MASTER_EPOCH = 4
    RECONNECT_COUNT = 5
    SHARED_STATE_REVISION = 6


_NP_TO_DTYPE = {
    np.dtype(np.uint8): DataType.UINT8,
    np.dtype(np.int8): DataType.INT8,
    np.dtype(np.uint16): DataType.UINT16,
    np.dtype(np.int16): DataType.INT16,
    np.dtype(np.uint32): DataType.UINT32,
    np.dtype(np.int32): DataType.INT32,
    np.dtype(np.uint64): DataType.UINT64,
    np.dtype(np.int64): DataType.INT64,
    np.dtype(np.float16): DataType.FLOAT16,
    np.dtype(np.float32): DataType.FLOAT32,
    np.dtype(np.float64): DataType.FLOAT64,
}


def _np_dtype_of(arr: np.ndarray) -> DataType:
    # ml_dtypes.bfloat16 arrays (jax host staging) are not in the static map
    if arr.dtype.name == "bfloat16":
        return DataType.BFLOAT16
    try:
        return _NP_TO_DTYPE[arr.dtype]
    except KeyError:
        raise ValueError(f"unsupported dtype {arr.dtype}") from None


_DTYPE_ITEMSIZE = {
    DataType.UINT8: 1, DataType.INT8: 1,
    DataType.UINT16: 2, DataType.INT16: 2,
    DataType.UINT32: 4, DataType.INT32: 4,
    DataType.UINT64: 8, DataType.INT64: 8,
    DataType.FLOAT16: 2, DataType.BFLOAT16: 2,
    DataType.FLOAT32: 4, DataType.FLOAT64: 8,
}


# ---------------------------------------------------------------- exceptions

class PcclError(RuntimeError):
    """Base error; .result carries the native status code."""

    def __init__(self, result: Result, what: str = ""):
        self.result = Result(result)
        super().__init__(f"{self.result.name}{': ' + what if what else ''}")


class ConnectionLostError(PcclError):
    """A peer died mid-op; re-establish with update_topology() and retry."""


class OperationAbortedError(PcclError):
    """The op was aborted group-wide; retry after update_topology()."""


class TooFewPeersError(PcclError):
    """world < 2 — wait for peers to join, then retry."""


class KickedError(PcclError):
    """The master kicked this peer (protocol violation or state mismatch)."""


class MasterUnreachableError(PcclError):
    pass


def _check(code: int, what: str = "") -> None:
    if code == Result.SUCCESS:
        return
    r = Result(code)
    cls = {
        Result.CONNECTION_LOST: ConnectionLostError,
        Result.OPERATION_ABORTED: OperationAbortedError,
        Result.TOO_FEW_PEERS: TooFewPeersError,
        Result.KICKED: KickedError,
        Result.MASTER_UNREACHABLE: MasterUnreachableError,
    }.get(r, PcclError)
    raise cls(r, what)


# ------------------------------------------------- registered shm buffers

def shm_ndarray(shape, dtype=np.float32) -> np.ndarray:
    """Allocate a numpy array in a REGISTERED shared-memory region
    (pccltShmAlloc). Collectives whose payload lives in a registered region
    take the same-host zero-copy path: local peers map the region and reduce
    straight out of it, skipping even the one-copy CMA pull. Use for
    communication-heavy staging tensors (DiLoCo outer-step flats, bench
    buffers); ordinary arrays work with every op regardless.

    The region is freed when the returned array (and all its views) are
    garbage collected. pcclt extension — the reference (jundi69/pccl) always
    streams payloads over TCP and has no registered-buffer concept.
    """
    import weakref

    lib = _native.load()
    shape = tuple(np.atleast_1d(np.asarray(shape, dtype=np.int64)).tolist()) \
        if not isinstance(shape, (tuple, list)) else tuple(int(s) for s in shape)
    nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    ptr = ctypes.c_void_p()
    _check(lib.pccltShmAlloc(max(1, nbytes), ctypes.byref(ptr)), "shm alloc")
    buf = (ctypes.c_uint8 * max(1, nbytes)).from_address(ptr.value)
    weakref.finalize(buf, lib.pccltShmFree, ctypes.c_void_p(ptr.value))
    return np.ndarray(shape, dtype=dtype, buffer=buf)

# ---------------------------------------------------- flight-recorder trace

def trace_enable(on: bool = True) -> None:
    """Toggle the native flight recorder's event capture at runtime
    (process-global; see docs/09_observability.md). Counters —
    ``Communicator.stats()`` — are always on; this gates only the event
    ring feeding ``trace_events()`` / ``trace_dump()``. ``PCCLT_TRACE=path``
    in the environment enables capture at load and dumps at process exit."""
    lib = _native.load()
    _check(lib.pccltTraceEnable(1 if on else 0), "trace enable")


def trace_clear() -> None:
    """Drop every captured event (isolates multi-phase runs sharing one
    process, e.g. consecutive bench legs)."""
    lib = _native.load()
    _check(lib.pccltTraceClear(), "trace clear")


def trace_dump(path: str) -> None:
    """Write the recorder's event ring as Chrome trace-event JSON (load in
    chrome://tracing or ui.perfetto.dev). Timestamps are CLOCK_MONOTONIC
    microseconds — merge with Python profiler sections via
    Profiler.export_chrome_trace(..., native_events=...)."""
    lib = _native.load()
    _check(lib.pccltTraceDump(path.encode()), "trace dump")


def netem_inject(endpoint: str, spec: str) -> None:
    """Arm a time-scripted chaos fault schedule on the wire-emulation edge
    toward ``endpoint`` ("ip:port"), offsets relative to NOW — e.g.
    ``"degrade@t=0s:40mbit/8s"``, ``"flap@t=1s:200msx5"``,
    ``"blackhole@t=0s:2s"`` (';'-separate multiple faults). Mirrors
    ``pccltNetemInject``; see docs/05_fault_tolerance.md for the grammar
    and the live-connection caveat. An empty spec disarms the edge."""
    lib = _native.load()
    _check(lib.pccltNetemInject(endpoint.encode(), spec.encode()),
           "netem inject")


def trace_events() -> list:
    """The native recorder's current events as a list of Chrome trace-event
    dicts (the parsed form of trace_dump's output)."""
    import json
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        tmp = f.name
    try:
        trace_dump(tmp)
        with open(tmp) as f:
            return json.load(f)["traceEvents"]
    finally:
        import os

        try:
            os.unlink(tmp)
        except OSError:
            pass


class MasterNode:
    """Standalone orchestration master (reference: pccl.MasterNode /
    the ccoip_master binary). Control plane only — bulk data never flows
    through it.

    ``journal_path`` enables master HA: authoritative state (registrations,
    membership, ring order, shared-state revision, bandwidth matrix) is
    write-ahead-logged there, and a later ``MasterNode`` pointed at the same
    journal resumes the same world view under a bumped :attr:`epoch` —
    clients re-attach via session resume instead of re-registering
    (docs/10_high_availability.md). ``None`` falls back to the
    ``PCCLT_MASTER_JOURNAL`` env var; pass ``""`` to force-disable."""

    def __init__(self, listen_address: str = "0.0.0.0", port: int = 48501,
                 journal_path: Optional[str] = None):
        self._lib = _native.load()
        handle = ctypes.c_void_p()
        if journal_path is not None and not hasattr(self._lib,
                                                    "pccltCreateMasterEx"):
            raise PcclError(Result.INVALID_USAGE,
                            "this libpcclt.so predates master HA "
                            "(pccltCreateMasterEx); rebuild the native core")
        if hasattr(self._lib, "pccltCreateMasterEx"):
            _check(self._lib.pccltCreateMasterEx(
                listen_address.encode(), port,
                journal_path.encode() if journal_path is not None else None,
                ctypes.byref(handle)), "create master")
        else:
            _check(self._lib.pccltCreateMaster(listen_address.encode(), port,
                                               ctypes.byref(handle)),
                   "create master")
        self._h = handle
        self._ran = False

    def run(self) -> None:
        _check(self._lib.pccltRunMaster(self._h), "run master")
        self._ran = True

    @property
    def port(self) -> int:
        return int(self._lib.pccltMasterPort(self._h))

    @property
    def epoch(self) -> int:
        """This incarnation's epoch: 1 fresh (or journal-less), +1 on every
        journaled restart. Valid after run()."""
        if not hasattr(self._lib, "pccltMasterEpoch"):
            return 0
        return int(self._lib.pccltMasterEpoch(self._h))

    @property
    def metrics_port(self) -> int:
        """Bound port of the plain-HTTP ``/metrics`` (Prometheus text) +
        ``/health`` (JSON) endpoint — enabled by the
        ``PCCLT_MASTER_METRICS_PORT`` env var (``"0"`` = kernel-assigned,
        read the real port here). 0 while disabled or before run()."""
        if not hasattr(self._lib, "pccltMasterMetricsPort"):
            return 0
        return int(self._lib.pccltMasterMetricsPort(self._h))

    def health(self) -> dict:
        """The master's fleet health model as a dict (the ``/health`` JSON:
        epoch, world/client/limbo counts, per-peer digest freshness and
        per-edge EWMA throughput/stall with straggler flags). Works with
        the HTTP endpoint disabled — this reads the native state directly.
        Peers appear once they push telemetry digests
        (``PCCLT_TELEMETRY_PUSH_MS``); see docs/09_observability.md."""
        import json

        if not hasattr(self._lib, "pccltMasterGetHealth"):
            raise PcclError(Result.INVALID_USAGE,
                            "this libpcclt.so predates the observability "
                            "plane (pccltMasterGetHealth); rebuild")
        need = ctypes.c_uint64()
        _check(self._lib.pccltMasterGetHealth(self._h, None, 0,
                                              ctypes.byref(need)), "health")
        # size-then-fetch can race live digests growing the document: the
        # copy call re-reports the true length, so retry until it fits
        for _ in range(8):
            cap = need.value + 256  # slack absorbs small growth in one trip
            buf = ctypes.create_string_buffer(cap)
            _check(self._lib.pccltMasterGetHealth(self._h, buf, cap,
                                                  ctypes.byref(need)),
                   "health")
            if need.value < cap:
                return json.loads(buf.value.decode())
        raise PcclError(Result.INTERNAL_ERROR,
                        "health document kept outgrowing its buffer")

    def interrupt(self) -> None:
        _check(self._lib.pccltInterruptMaster(self._h))

    def await_termination(self) -> None:
        _check(self._lib.pccltMasterAwaitTermination(self._h))

    def destroy(self) -> None:
        if self._h:
            self._lib.pccltDestroyMaster(self._h)
            self._h = None

    def __enter__(self) -> "MasterNode":
        self.run()
        return self

    def __exit__(self, *exc) -> None:
        self.interrupt()
        self.destroy()

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass


# ---------------------------------------------------------------- tensors

@dataclass
class TensorInfo:
    """One named shared-state entry (reference: pccl.TensorInfo,
    _pccl.py:350-372). Keeps the backing buffer alive."""

    name: str
    data: np.ndarray                  # host buffer the native core reads/writes
    dtype: DataType
    device: DeviceType = DeviceType.HOST
    allow_content_inequality: bool = False
    _source: Any = field(default=None, repr=False)  # torch tensor / jax array
    # device-hash path (from_jax_device): hash computed on the accelerator,
    # host staging deferred until the native core actually serves the bytes
    _precomputed_hash: Any = field(default=None, repr=False)
    _materialize_cb: Any = field(default=None, repr=False)  # keepalive
    _updated: bool = field(default=False, repr=False)

    @staticmethod
    def from_numpy(name: str, arr: np.ndarray,
                   allow_content_inequality: bool = False) -> "TensorInfo":
        if not arr.flags["C_CONTIGUOUS"]:
            raise ValueError("array must be C-contiguous")
        if not arr.flags["WRITEABLE"]:
            raise ValueError("array must be writable (sync writes into it)")
        return TensorInfo(name, arr, _np_dtype_of(arr), DeviceType.HOST,
                          allow_content_inequality)

    @staticmethod
    def from_torch(name: str, tensor,
                   allow_content_inequality: bool = False) -> "TensorInfo":
        if tensor.device.type != "cpu":
            raise ValueError("torch tensor must be on CPU (stage accelerator "
                             "state via .cpu() or use from_jax for TPU arrays)")
        arr = tensor.detach().numpy()
        ti = TensorInfo.from_numpy(name, arr, allow_content_inequality)
        ti._source = tensor  # in-place: numpy view shares storage
        return ti

    @staticmethod
    def from_jax(name: str, arr,
                 allow_content_inequality: bool = False) -> "TensorInfo":
        """Stage a jax.Array to a host copy. After sync_shared_state, read the
        (possibly updated) content back with .jax_value()."""
        host = np.asarray(arr)
        if not host.flags["WRITEABLE"]:
            host = host.copy()
        ti = TensorInfo(name, host, _np_dtype_of(host), DeviceType.TPU,
                        allow_content_inequality)
        ti._source = arr
        return ti

    @staticmethod
    def from_jax_device(name: str, arr,
                        allow_content_inequality: bool = False
                        ) -> "TensorInfo":
        """TPU-resident entry whose content hash is computed ON DEVICE
        (ops.hashing.jax_simplehash_device — 8 bytes cross to the host);
        the array is staged to the host ONLY if the sync actually needs
        the bytes (this peer is elected distributor, via the native
        materialize callback, or the entry arrives outdated). A clean
        sync of N gigabytes therefore moves 8 bytes instead of N — the
        invariant the reference preserves by hashing CUDA buffers on-GPU
        (/root/reference/ccoip/src/cuda/simplehash_cuda.cu).

        Requires PCCLT_SS_HASH=simple-tpu group-wide (the one hash type a
        TPU can compute over resident bytes); raises otherwise so a
        mismatched configuration fails loudly instead of looping forever
        on phantom hash drift. After sync, read the authoritative value
        with .jax_value() (device content unless the sync updated it)."""
        import os

        from ..ops.hashing import jax_simplehash_device

        if os.environ.get("PCCLT_SS_HASH") != "simple-tpu":
            raise RuntimeError(
                "TensorInfo.from_jax_device needs PCCLT_SS_HASH=simple-tpu "
                "(every peer of the group must hash with the TPU-computable "
                "type); set the env var or use from_jax for staged syncs")
        host = np.empty(arr.shape, arr.dtype)   # unmaterialized until needed
        ti = TensorInfo(name, host, _np_dtype_of(host), DeviceType.TPU,
                        allow_content_inequality)
        ti._source = arr
        lazy = True
        if not allow_content_inequality:
            try:
                ti._precomputed_hash = jax_simplehash_device(arr)
            except ValueError:
                # 8-byte dtypes have no device word stream (TPUs run 32-bit
                # ints); fall back to eager staging + the host twin of the
                # SAME hash type, so the group-wide digest still agrees
                from ..ops.hashing import simplehash_tpu

                np.copyto(host, np.asarray(arr))
                ti._precomputed_hash = simplehash_tpu(host)
                lazy = False

        if lazy:
            def _materialize(_ctx):
                # called from a native serving thread (ctypes re-acquires
                # the GIL); one staging D2H, exactly once per sync window.
                # Closes over `arr`, not `ti`: a ti -> callback -> ti cycle
                # would keep the device array alive until a gc pass
                np.copyto(host, np.asarray(arr))

            ti._materialize_cb = _native.MaterializeFn(_materialize)
        return ti

    def jax_value(self):
        """Device array with the current authoritative content: the synced
        host bytes when the sync wrote any (or for staged entries, which
        always hold current content), else the untouched device array."""
        import jax

        if self._materialize_cb is not None and not self._updated:
            # lazy entry the sync never wrote to: the host buffer may be
            # unmaterialized garbage — the device array is authoritative
            return self._source
        if not hasattr(self._source, "sharding"):
            raise ValueError(
                f"shared-state entry {self.name!r} was not built from a "
                "jax.Array (from_jax / from_jax_device), so there is no "
                "device to put its value on")
        return jax.device_put(self.data, self._source.sharding)

    def _as_c(self, keepalive: list) -> _native.TensorInfoC:
        name_b = self.name.encode()
        keepalive.append(name_b)
        has_h = self._precomputed_hash is not None
        if self._materialize_cb is not None:
            keepalive.append(self._materialize_cb)
        return _native.TensorInfoC(
            name=name_b,
            data=self.data.ctypes.data_as(ctypes.c_void_p),
            count=self.data.size,
            dtype=int(self.dtype),
            device=int(self.device),
            allow_content_inequality=1 if self.allow_content_inequality else 0,
            precomputed_hash=self._precomputed_hash if has_h else 0,
            has_precomputed_hash=1 if has_h else 0,
            materialize=self._materialize_cb if self._materialize_cb
            else _native.MaterializeFn(),
            materialize_ctx=None,
            updated=0,
        )


@dataclass
class SharedState:
    """Revisioned named tensor set, synced bit-identically across peers
    (reference: pccl.SharedState, _pccl.py:373-421)."""

    infos: Sequence[TensorInfo]
    revision: int = 0


@dataclass
class SharedStateSyncInfo:
    tx_bytes: int
    rx_bytes: int
    revision: int


@dataclass
class ReduceInfo:
    tx_bytes: int
    rx_bytes: int
    world_size: int


@dataclass
class ReduceDescriptor:
    """Per-op config: wire tag, reduction, optional on-the-wire quantization
    (reference pcclReduceDescriptor_t, pccl.h:140-168)."""

    tag: int = 0
    op: ReduceOp = ReduceOp.SUM
    quantization: QuantizationAlgorithm = QuantizationAlgorithm.NONE
    quantized_dtype: DataType = DataType.UINT8

    def _as_c(self) -> _native.ReduceDescriptor:
        return _native.ReduceDescriptor(
            tag=self.tag, op=int(self.op), quant_algo=int(self.quantization),
            quant_dtype=int(self.quantized_dtype))


class AsyncReduceHandle:
    """Handle for an in-flight all-reduce (reference: _pccl.py:422-459).
    Holds buffer references so the native op never outlives its memory."""

    def __init__(self, comm: "Communicator", tag: int, keepalive: tuple):
        self._comm = comm
        self._tag = tag
        self._keepalive = keepalive
        self._done = False

    def wait(self) -> ReduceInfo:
        if self._done:
            raise PcclError(Result.INVALID_USAGE, "handle already awaited")
        self._done = True
        info = _native.ReduceInfo()
        code = self._comm._lib.pccltAwaitAsyncReduce(
            self._comm._h, self._tag, ctypes.byref(info))
        self._keepalive = ()
        _check(code, f"await reduce tag={self._tag}")
        return ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size)


# ---------------------------------------------------------------- communicator

class Communicator:
    """One peer of the collective (reference: pccl.Communicator,
    _pccl.py:460-813).

    Usage mirrors the reference loop (README.md:90-130):

        comm = Communicator("10.0.0.1", 48501)
        comm.connect()
        while training:
            comm.update_topology()          # admit joiners / adopt new ring
            comm.optimize_topology()        # optional: bandwidth-aware ring
            try:
                comm.all_reduce(grads, op=ReduceOp.AVG)
            except (ConnectionLostError, OperationAbortedError):
                continue                    # world shrank; retry
    """

    def __init__(self, master_ip: str, master_port: int = 48501, *,
                 peer_group: int = 0, advertised_ip: Optional[str] = None,
                 p2p_port: int = 0, ss_port: int = 0, bench_port: int = 0,
                 p2p_connection_pool_size: int = 1,
                 reconnect_attempts: Optional[int] = None,
                 reconnect_backoff_ms: int = 0,
                 reconnect_backoff_cap_ms: int = 0):
        """``reconnect_*`` tune master-HA session resume: on a lost master
        link the client retries with bounded exponential backoff + jitter
        (keeping p2p connections alive) and re-attaches under its old UUID
        against a journaled master. ``reconnect_attempts`` ``None`` = env
        ``PCCLT_RECONNECT_ATTEMPTS`` (default 8), ``0`` disables; backoff
        ms fields default to env ``PCCLT_RECONNECT_BACKOFF_MS`` (100) /
        ``PCCLT_RECONNECT_MAX_BACKOFF_MS`` (2000). See
        docs/10_high_availability.md."""
        self._lib = _native.load()
        params = _native.CommCreateParams(
            master_ip=master_ip.encode(),
            master_port=master_port,
            peer_group=peer_group,
            advertised_ip=advertised_ip.encode() if advertised_ip else None,
            p2p_port=p2p_port,
            ss_port=ss_port,
            bench_port=bench_port,
            p2p_connection_pool_size=p2p_connection_pool_size,
            reconnect_attempts=(-1 if reconnect_attempts is None
                                else reconnect_attempts),
            reconnect_backoff_ms=reconnect_backoff_ms,
            reconnect_backoff_cap_ms=reconnect_backoff_cap_ms,
        )
        handle = ctypes.c_void_p()
        _check(self._lib.pccltCreateCommunicator(ctypes.byref(params),
                                                 ctypes.byref(handle)))
        self._h = handle
        self._tag_lock = threading.Lock()
        self._next_tag = self._AUTO_TAG_BASE

    # -- lifecycle --

    def connect(self) -> None:
        _check(self._lib.pccltConnect(self._h), "connect")

    def destroy(self) -> None:
        if self._h:
            self._lib.pccltDestroyCommunicator(self._h)
            self._h = None

    def __enter__(self) -> "Communicator":
        self.connect()
        return self

    def __exit__(self, *exc) -> None:
        self.destroy()

    def __del__(self):
        try:
            self.destroy()
        except Exception:
            pass

    # -- membership / topology --

    def get_attribute(self, attr: Attribute) -> int:
        out = ctypes.c_int64()
        _check(self._lib.pccltGetAttribute(self._h, int(attr), ctypes.byref(out)))
        return out.value

    @property
    def world_size(self) -> int:
        return self.get_attribute(Attribute.PEER_GROUP_WORLD_SIZE)

    @property
    def global_world_size(self) -> int:
        return self.get_attribute(Attribute.GLOBAL_WORLD_SIZE)

    @property
    def num_peer_groups(self) -> int:
        return self.get_attribute(Attribute.NUM_DISTINCT_PEER_GROUPS)

    @property
    def largest_peer_group(self) -> int:
        """Largest group's world size — with num_peer_groups, the grid
        fullness check: global == num_groups * largest (docs 07)."""
        return self.get_attribute(Attribute.LARGEST_PEER_GROUP_WORLD_SIZE)

    @property
    def master_epoch(self) -> int:
        """The master epoch observed at welcome / last session resume. A
        journaled master bumps its epoch on every restart, so a change here
        = 'the master restarted under us and we resumed'."""
        return self.get_attribute(Attribute.MASTER_EPOCH)

    @property
    def reconnect_count(self) -> int:
        """How many times this communicator resumed its master session
        (HA blips absorbed without re-registering)."""
        return self.get_attribute(Attribute.RECONNECT_COUNT)

    @property
    def shared_state_revision(self) -> int:
        """Last shared-state revision known COMPLETE group-wide (from a
        sync Done, or the resume ack after a master restart). If a sync
        raised and this already covers its revision, the round finished
        just before the crash — skip the retry instead of wedging the
        group on a revision disagreement."""
        return self.get_attribute(Attribute.SHARED_STATE_REVISION)

    def update_topology(self) -> None:
        _check(self._lib.pccltUpdateTopology(self._h), "update topology")

    # -- telemetry --

    def stats(self) -> dict:
        """Flight-recorder counter snapshot for THIS communicator:

            {"counters": {collectives_ok, collectives_aborted, ...},
             "edges": {"ip:port": {tx_bytes, rx_bytes, tx_frames,
                                   rx_frames, connects, stall_ms,
                                   tx_zc_frames, tx_zc_reaps}, ...}}

        Edge keys are canonical remote endpoints (the peer's advertised
        p2p listen endpoint — the same key netem's PCCLT_WIRE_*_MAP uses).
        Counters are monotonic since connect and always on; see
        docs/09_observability.md for field semantics."""
        cs = _native.CommStats()
        _check(self._lib.pccltCommGetStats(self._h, ctypes.byref(cs)), "stats")
        counters = {name: int(getattr(cs, name)) for name, _ in cs._fields_}
        n = ctypes.c_uint64()
        _check(self._lib.pccltCommGetEdgeStats(self._h, None, 0,
                                               ctypes.byref(n)), "edge stats")
        edges = {}
        if n.value:
            buf = (_native.EdgeStats * n.value)()
            _check(self._lib.pccltCommGetEdgeStats(self._h, buf, n.value,
                                                   ctypes.byref(n)),
                   "edge stats")
            for i in range(min(n.value, len(buf))):
                e = buf[i]
                edges[e.endpoint.decode()] = {
                    "tx_bytes": int(e.tx_bytes), "rx_bytes": int(e.rx_bytes),
                    "tx_frames": int(e.tx_frames),
                    "rx_frames": int(e.rx_frames),
                    "connects": int(e.connects), "stall_ms": int(e.stall_ms),
                    "tx_zc_frames": int(e.tx_zc_frames),
                    "tx_zc_reaps": int(e.tx_zc_reaps),
                    # edge watchdog + window failover (docs/05)
                    "wd_state": int(e.wd_state),
                    "wd_suspects": int(e.wd_suspects),
                    "wd_confirms": int(e.wd_confirms),
                    "wd_reissues": int(e.wd_reissues),
                    "wd_relays": int(e.wd_relays),
                    "rx_relay_bytes": int(e.rx_relay_bytes),
                    "rx_relay_windows": int(e.rx_relay_windows),
                    "dup_bytes": int(e.dup_bytes),
                    "dup_windows": int(e.dup_windows),
                    # shared-state chunk plane (docs/04)
                    "tx_sync_bytes": int(e.tx_sync_bytes),
                    "rx_sync_bytes": int(e.rx_sync_bytes),
                    # multipath striping (docs/08)
                    "tx_stripe_windows": int(e.tx_stripe_windows),
                    "tx_stripe_bytes": int(e.tx_stripe_bytes),
                }
        return {"counters": counters, "edges": edges}

    def trace_events(self) -> list:
        """Native flight-recorder events as Chrome trace-event dicts. The
        recorder is process-global (one ring per process, every comm and
        the in-process master feed it); exposed here for symmetry with
        stats(). Enable capture with PCCLT_TRACE=path or trace_enable()."""
        return trace_events()

    def are_peers_pending(self) -> bool:
        out = ctypes.c_int()
        _check(self._lib.pccltArePeersPending(self._h, ctypes.byref(out)))
        return out.value != 0

    def optimize_topology(self) -> None:
        _check(self._lib.pccltOptimizeTopology(self._h), "optimize topology")

    # -- collectives --

    # auto tags live in a high band so they can never collide with the small
    # deterministic tags used by blocking all_reduce (0) and
    # all_reduce_multiple_with_retry (0..n-1) or typical user-chosen tags
    _AUTO_TAG_BASE = 1 << 32
    # all_reduce_multiple_with_retry uses deterministic tags in this reserved
    # band (disjoint from the blocking default 0, typical user tags, and the
    # auto band above) so concurrent collectives never collide on tag 0
    _RETRY_TAG_BASE = 1 << 16

    def _auto_tag(self) -> int:
        with self._tag_lock:
            t = self._next_tag
            self._next_tag += 1
            return t

    @staticmethod
    def _buffers(send, recv):
        # the buffer the native core writes into must be the caller's memory —
        # a silent ascontiguousarray copy would discard the result
        if recv is None:
            if not isinstance(send, np.ndarray) or not send.flags["C_CONTIGUOUS"]:
                raise ValueError(
                    "in-place all_reduce requires a C-contiguous ndarray "
                    "(pass a separate contiguous recv buffer otherwise)")
            if not send.flags["WRITEABLE"]:
                raise ValueError("in-place all_reduce requires a writable array")
            return send, send
        if not isinstance(recv, np.ndarray) or not recv.flags["C_CONTIGUOUS"]:
            raise ValueError("recv must be a C-contiguous ndarray")
        if not recv.flags["WRITEABLE"]:
            raise ValueError("recv must be writable")
        send = np.ascontiguousarray(send)  # send is read-only; a copy is fine
        if recv.dtype != send.dtype or recv.size != send.size:
            raise ValueError("recv buffer must match send dtype/size")
        return send, recv

    def all_reduce(self, send, recv=None, *, op: ReduceOp = ReduceOp.SUM,
                   tag: int = 0,
                   quantization: QuantizationAlgorithm = QuantizationAlgorithm.NONE,
                   quantized_dtype: DataType = DataType.UINT8,
                   dtype: Optional[DataType] = None) -> ReduceInfo:
        """Blocking ring all-reduce. recv=None → in place. Raises
        ConnectionLostError / OperationAbortedError on peer churn.

        The tag identifies the op ACROSS peers: every group member must call
        with the same tag for the op to commence (reference descriptor tags).
        The default tag 0 is stable, so late joiners match incumbents; pass
        distinct explicit tags only for concurrent reduces.

        dtype overrides the wire dtype when numpy cannot express it —
        e.g. pass DataType.BFLOAT16 with uint16 arrays holding bf16 bit
        patterns (numpy has no bfloat16)."""
        send, recv = self._buffers(send, recv)
        desc = ReduceDescriptor(tag, op, quantization, quantized_dtype)._as_c()
        info = _native.ReduceInfo()
        wire_dtype = dtype if dtype is not None else _np_dtype_of(send)
        if dtype is not None and \
                _DTYPE_ITEMSIZE[wire_dtype] != send.dtype.itemsize:
            # a mismatched override would silently reinterpret a fraction of
            # the buffer (element COUNT is passed, not bytes)
            raise ValueError(
                f"wire dtype {wire_dtype.name} is "
                f"{_DTYPE_ITEMSIZE[wire_dtype]} bytes/elem but the arrays "
                f"hold {send.dtype.itemsize}-byte elements")
        code = self._lib.pccltAllReduce(
            self._h, send.ctypes.data_as(ctypes.c_void_p),
            recv.ctypes.data_as(ctypes.c_void_p), send.size,
            int(wire_dtype), ctypes.byref(desc), ctypes.byref(info))
        _check(code, "all_reduce")
        return ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size)

    def all_gather(self, send, recv=None, *, tag: int = 0) -> tuple:
        """Ring all-gather (pcclt extension; the reference lists All-Gather
        as unshipped roadmap work). Every peer contributes `send`; returns
        (recv, ReduceInfo) where segment i belongs to the peer at sorted-
        uuid position i (stable across ring re-orderings; your own index is
        `gather_slot`). recv=None allocates (world_size, *send.shape); a
        caller-provided recv must be a writable C-contiguous array of
        send's dtype with capacity >= world_size * send.size. The native
        side re-checks capacity against the commence-time world, so a
        joiner admitted mid-call aborts the op instead of overflowing."""
        send = np.ascontiguousarray(send)
        world = self.world_size
        if recv is None:
            recv = np.empty((world,) + send.shape, dtype=send.dtype)
        if recv.dtype != send.dtype:
            raise ValueError(f"recv dtype {recv.dtype} != send {send.dtype}")
        if not recv.flags["C_CONTIGUOUS"] or not recv.flags["WRITEABLE"]:
            raise ValueError("recv must be writable and C-contiguous")
        if recv.size < world * send.size:
            raise ValueError(f"recv capacity {recv.size} < world*send "
                             f"{world * send.size}")
        if world <= 1:
            # solo: own segment at slot 0, zero wire traffic — honoring the
            # docstring's unconditional contract instead of surfacing the
            # native layer's group_world<2 rejection
            np.copyto(recv.reshape(-1)[:send.size].reshape(send.shape), send)
            return recv, ReduceInfo(0, 0, 1)
        info = _native.ReduceInfo()
        code = self._lib.pccltAllGather(
            self._h, send.ctypes.data_as(ctypes.c_void_p),
            recv.ctypes.data_as(ctypes.c_void_p), send.size, recv.size,
            int(_np_dtype_of(send)), tag, ctypes.byref(info))
        _check(code, "all_gather")
        return recv, ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size)

    @property
    def gather_slot(self) -> int:
        """This peer's segment index in all_gather output (position among
        the current ring's sorted peer UUIDs; re-query after churn)."""
        slot = ctypes.c_uint64()
        _check(self._lib.pccltGatherSlot(self._h, ctypes.byref(slot)),
               "gather_slot")
        return int(slot.value)

    def reduce_scatter(self, send, recv=None, *, tag: int = 0,
                       quantization: QuantizationAlgorithm =
                       QuantizationAlgorithm.NONE,
                       quantized_dtype: DataType = DataType.UINT8) -> tuple:
        """Ring reduce-scatter (docs/12): the group SUM of `send` is computed
        and each peer keeps only its own contiguous chunk of the result.
        Returns (chunk, offset, ReduceInfo): `chunk` is a view of recv
        holding this peer's reduced elements and `offset` is its element
        offset within the full count — recv[i] == sum_of_send[offset + i].
        Chunk ownership follows ring rank, so the (offset, count) pair can
        change across churn; always use the returned values. The fold is
        SUM (quantization fields still apply to the wire format). recv=None
        allocates ceil(count/world) elements; a caller-provided recv must
        be writable, C-contiguous, send's dtype, capacity >=
        ceil(count/world) — re-checked natively against the commence-time
        world so mid-call churn aborts instead of overflowing."""
        send = np.ascontiguousarray(send)
        if not hasattr(self._lib, "pccltReduceScatter"):
            raise PcclError(Result.INVALID_USAGE,
                            "this libpcclt.so predates the schedule "
                            "synthesizer (pccltReduceScatter); rebuild")
        world = self.world_size
        if recv is None:
            cap = (send.size + max(world, 1) - 1) // max(world, 1)
            recv = np.empty(max(cap, 1), dtype=send.dtype)
        if recv.dtype != send.dtype:
            raise ValueError(f"recv dtype {recv.dtype} != send {send.dtype}")
        if not recv.flags["C_CONTIGUOUS"] or not recv.flags["WRITEABLE"]:
            raise ValueError("recv must be writable and C-contiguous")
        if world <= 1:
            # solo: the SUM over one peer is the peer's own buffer
            if recv.size < send.size:
                raise ValueError(f"recv capacity {recv.size} < {send.size}")
            np.copyto(recv.reshape(-1)[:send.size],
                      send.reshape(-1))
            return recv.reshape(-1)[:send.size], 0, ReduceInfo(0, 0, 1)
        desc = ReduceDescriptor(tag, ReduceOp.SUM, quantization,
                                quantized_dtype)._as_c()
        info = _native.ReduceInfo()
        off = ctypes.c_uint64()
        cnt = ctypes.c_uint64()
        code = self._lib.pccltReduceScatter(
            self._h, send.ctypes.data_as(ctypes.c_void_p),
            recv.ctypes.data_as(ctypes.c_void_p), send.size, recv.size,
            int(_np_dtype_of(send)), ctypes.byref(desc), ctypes.byref(off),
            ctypes.byref(cnt), ctypes.byref(info))
        _check(code, "reduce_scatter")
        return (recv.reshape(-1)[:int(cnt.value)], int(off.value),
                ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size))

    def broadcast(self, buf, *, root: int, tag: int = 0,
                  quantization: QuantizationAlgorithm =
                  QuantizationAlgorithm.NONE,
                  quantized_dtype: DataType = DataType.UINT8) -> ReduceInfo:
        """In-place broadcast from the peer at sorted-uuid slot `root` (its
        `gather_slot`; every peer must pass the SAME root — a mismatch is a
        parameter disagreement and gets the minority kicked). On return buf
        holds the root's bytes bit-identically on every peer. The schedule
        synthesizer may run this over a bandwidth-weighted tree instead of
        the ring (docs/12); the result is identical either way."""
        if not isinstance(buf, np.ndarray) or not buf.flags["C_CONTIGUOUS"] \
                or not buf.flags["WRITEABLE"]:
            raise ValueError("broadcast buffer must be a writable "
                             "C-contiguous ndarray (updated in place)")
        if not hasattr(self._lib, "pccltBroadcast"):
            raise PcclError(Result.INVALID_USAGE,
                            "this libpcclt.so predates the schedule "
                            "synthesizer (pccltBroadcast); rebuild")
        if self.world_size <= 1:
            return ReduceInfo(0, 0, 1)
        desc = ReduceDescriptor(tag, ReduceOp.SUM, quantization,
                                quantized_dtype)._as_c()
        info = _native.ReduceInfo()
        code = self._lib.pccltBroadcast(
            self._h, buf.ctypes.data_as(ctypes.c_void_p), buf.size,
            int(root), int(_np_dtype_of(buf)), ctypes.byref(desc),
            ctypes.byref(info))
        _check(code, "broadcast")
        return ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size)

    def all_to_all(self, send, recv=None, *, tag: int = 0,
                   quantization: QuantizationAlgorithm =
                   QuantizationAlgorithm.NONE,
                   quantized_dtype: DataType = DataType.UINT8) -> tuple:
        """All-to-all personalized exchange (docs/12): `send` is world_size
        equal blocks in sorted-uuid slot order; block j lands as block
        `my_slot` at the peer holding slot j, and recv block i is the block
        peer i addressed to us. send.size must be divisible by world_size.
        recv=None allocates send's shape; a caller-provided recv must be
        writable, C-contiguous, send's dtype, capacity >= send.size
        (re-checked natively against the commence-time world). Returns
        (recv, ReduceInfo)."""
        send = np.ascontiguousarray(send)
        if not hasattr(self._lib, "pccltAllToAll"):
            raise PcclError(Result.INVALID_USAGE,
                            "this libpcclt.so predates the schedule "
                            "synthesizer (pccltAllToAll); rebuild")
        world = self.world_size
        if recv is None:
            recv = np.empty(send.shape, dtype=send.dtype)
        if recv.dtype != send.dtype:
            raise ValueError(f"recv dtype {recv.dtype} != send {send.dtype}")
        if not recv.flags["C_CONTIGUOUS"] or not recv.flags["WRITEABLE"]:
            raise ValueError("recv must be writable and C-contiguous")
        if recv.size < send.size:
            raise ValueError(f"recv capacity {recv.size} < send {send.size}")
        if world <= 1:
            np.copyto(recv.reshape(-1)[:send.size], send.reshape(-1))
            return recv, ReduceInfo(0, 0, 1)
        if send.size % world:
            raise ValueError(f"send size {send.size} not divisible by "
                             f"world {world}")
        desc = ReduceDescriptor(tag, ReduceOp.SUM, quantization,
                                quantized_dtype)._as_c()
        info = _native.ReduceInfo()
        code = self._lib.pccltAllToAll(
            self._h, send.ctypes.data_as(ctypes.c_void_p),
            recv.ctypes.data_as(ctypes.c_void_p), send.size // world,
            recv.size, int(_np_dtype_of(send)), ctypes.byref(desc),
            ctypes.byref(info))
        _check(code, "all_to_all")
        return recv, ReduceInfo(info.tx_bytes, info.rx_bytes, info.world_size)

    def all_reduce_async(self, send, recv=None, *, op: ReduceOp = ReduceOp.SUM,
                         tag: Optional[int] = None,
                         quantization: QuantizationAlgorithm = QuantizationAlgorithm.NONE,
                         quantized_dtype: DataType = DataType.UINT8) -> AsyncReduceHandle:
        """Async variant. tag=None auto-allocates a locally increasing tag —
        fine for a static world, but under dynamic membership every peer must
        pass the SAME explicit tag per op or the group cannot reach consensus
        (see all_reduce)."""
        send, recv = self._buffers(send, recv)
        tag = tag if tag is not None else self._auto_tag()
        desc = ReduceDescriptor(tag, op, quantization, quantized_dtype)._as_c()
        code = self._lib.pccltAllReduceAsync(
            self._h, send.ctypes.data_as(ctypes.c_void_p),
            recv.ctypes.data_as(ctypes.c_void_p), send.size,
            int(_np_dtype_of(send)), ctypes.byref(desc))
        _check(code, "all_reduce_async")
        return AsyncReduceHandle(self, tag, (send, recv))

    def all_reduce_multiple_with_retry(self, tensors: Sequence,
                                       *, op: ReduceOp = ReduceOp.SUM,
                                       quantization: QuantizationAlgorithm =
                                       QuantizationAlgorithm.NONE,
                                       quantized_dtype: DataType = DataType.UINT8,
                                       ) -> list[ReduceInfo]:
        """Launch one reduce per tensor (in place), retrying as the world
        shrinks until all succeed (reference pcclAllReduceMultipleWithRetry)."""
        for t in tensors:
            if not isinstance(t, np.ndarray) or not t.flags["C_CONTIGUOUS"] \
                    or not t.flags["WRITEABLE"]:
                raise ValueError("tensors must be writable C-contiguous ndarrays "
                                 "(reduced in place)")
        arrs = list(tensors)
        if not arrs:
            return []
        dt = _np_dtype_of(arrs[0])
        for a in arrs:
            if _np_dtype_of(a) != dt:
                raise ValueError("all tensors must share a dtype")
        n = len(arrs)
        sendp = (ctypes.c_void_p * n)(*[a.ctypes.data_as(ctypes.c_void_p).value
                                        for a in arrs])
        recvp = (ctypes.c_void_p * n)(*[a.ctypes.data_as(ctypes.c_void_p).value
                                        for a in arrs])
        counts = (ctypes.c_uint64 * n)(*[a.size for a in arrs])
        descs = (_native.ReduceDescriptor * n)()
        for i in range(n):
            # deterministic tags (reserved band + tensor index): peers match
            # ops by tag, and a late joiner's counter must not drift from
            # incumbents'. The band keeps these disjoint from the blocking
            # default tag 0 and from user-chosen small tags, so a foreground
            # all_reduce can run concurrently with a background retry batch.
            d = ReduceDescriptor(self._RETRY_TAG_BASE + i, op, quantization,
                                 quantized_dtype)._as_c()
            descs[i] = d
        infos = (_native.ReduceInfo * n)()
        code = self._lib.pccltAllReduceMultipleWithRetry(
            self._h, sendp, recvp, counts, int(dt), descs, n, infos)
        _check(code, "all_reduce_multiple_with_retry")
        return [ReduceInfo(i.tx_bytes, i.rx_bytes, i.world_size) for i in infos]

    # -- shared state --

    def sync_shared_state(self, state: SharedState,
                          strategy: SharedStateSyncStrategy =
                          SharedStateSyncStrategy.ENFORCE_POPULAR,
                          ) -> SharedStateSyncInfo:
        keepalive: list = []
        infos = (_native.TensorInfoC * len(state.infos))()
        for i, ti in enumerate(state.infos):
            infos[i] = ti._as_c(keepalive)
        st = _native.SharedStateC(revision=state.revision, count=len(state.infos),
                                  infos=infos)
        out = _native.SharedStateSyncInfo()
        code = self._lib.pccltSynchronizeSharedState(
            self._h, ctypes.byref(st), int(strategy), ctypes.byref(out))
        _check(code, "sync_shared_state")
        for i, ti in enumerate(state.infos):
            # per-entry received-content flag (device-hash entries use it
            # to decide between the untouched device array and the synced
            # host bytes in jax_value)
            ti._updated = bool(infos[i].updated)
        return SharedStateSyncInfo(out.tx_bytes, out.rx_bytes, out.revision)
