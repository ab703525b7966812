"""pccl_tpu.comm — fault-tolerant collectives over TCP (native core).

Public surface (reference parity: python/framework/pccl/__init__.py):
Communicator, MasterNode, SharedState, TensorInfo, ReduceOp, DataType,
QuantizationAlgorithm, SharedStateSyncStrategy, Attribute, AsyncReduceHandle,
ReduceDescriptor, plus the PcclError exception family.

The native library loads lazily on first Communicator/MasterNode use, so
importing this package never requires the C++ build (pure-JAX users never
touch it).
"""

from .api import (  # noqa: F401
    AsyncReduceHandle,
    Attribute,
    Communicator,
    ConnectionLostError,
    DataType,
    DeviceType,
    KickedError,
    MasterNode,
    MasterUnreachableError,
    OperationAbortedError,
    PcclError,
    QuantizationAlgorithm,
    ReduceDescriptor,
    ReduceInfo,
    ReduceOp,
    Result,
    SharedState,
    SharedStateSyncInfo,
    SharedStateSyncStrategy,
    TooFewPeersError,
    TensorInfo,
    shm_ndarray,
    netem_inject,
    trace_clear,
    trace_dump,
    trace_enable,
    trace_events,
)
