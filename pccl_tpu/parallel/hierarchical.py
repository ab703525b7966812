"""Hierarchical all-reduce: ICI inside the slice, the TCP ring across slices.

This is the TPU north star of the build (BASELINE.json, SURVEY.md §5
"Distributed communication backend"): each TPU slice is ONE logical peer of
the CCoIP-style ring. The reference has no equivalent — its peers are single
CUDA hosts — so this module is new design, not a port.

Data path for a global all-reduce of a sharded array tree:

  1. **intra-slice reduce (ICI, jitted)** — if the tree carries a
     data-parallel axis to fold (e.g. per-device gradients under shard_map),
     a `psum`/mean over the mesh axis runs on-device; for trees produced by
     an SPMD `jit` step the gradients are already slice-reduced and this is
     the identity.
  2. **host staging** — the fp32 flat vector (codec.build_codec) is fetched
     once per slice. With `jax.sharding`, `device_get` of a fully-addressable
     array performs the gather over ICI, not over PCIe per-shard.
  3. **inter-slice ring (DCN)** — this process, acting as its slice's one
     peer, runs the fault-tolerant ring all-reduce with optional on-the-wire
     quantization (the reference's piquant path over WAN).
  4. **broadcast back (ICI)** — `device_put` with the original sharding lays
     the result back out across the slice; every device receives identical
     bytes, preserving the bit-parity invariant the shared-state machinery
     depends on (reference simplehash design, SURVEY.md §2 #13).

Fault tolerance: ConnectionLost/Aborted → update_topology() → retry, same
contract as the flat ring (reference README.md:90-130).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from ..comm import Communicator, DataType, QuantizationAlgorithm
from .codec import build_codec, leaf_shardings, restore_shardings
from .ring import avg_all_reduce_windowed


def local_mean(tree: Any, mesh, axis: str = "dp") -> Any:
    """Explicit intra-slice mean over a mesh axis via shard_map + psum.

    Each leaf's LEADING dim is the per-device stack (length = mesh axis
    size × k); the output folds it away: [n·k, ...] → [k, ...] holding the
    mean, replicated. Only needed when the caller holds per-device values
    OUTSIDE an SPMD jit step; gradients from a jitted step are already
    reduced by XLA."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]

    def _mean(x):
        return jax.lax.psum(x, axis) / n

    fn = jax.shard_map(lambda t: jax.tree.map(_mean, t), mesh=mesh,
                       in_specs=P(axis), out_specs=P())
    return fn(tree)


class HierarchicalAllReduce:
    """Slice-as-one-peer global averaging.

    Usage (one process per slice)::

        h = HierarchicalAllReduce(comm, grads_template)
        grads = h.all_reduce(grads)       # global mean across all slices

    `comm=None` degrades to the single-slice case (identity), so the same
    training loop runs on one slice or many.
    """

    def __init__(self, comm: Optional[Communicator], template: Any, *,
                 quantization: QuantizationAlgorithm = QuantizationAlgorithm.NONE,
                 quantized_dtype: DataType = DataType.UINT8,
                 max_retries: int = 16, shm_staging: bool = False,
                 windows: int = 1):
        self.comm = comm
        self.quantization = quantization
        self.quantized_dtype = quantized_dtype
        self.max_retries = max_retries
        # windows>1: split the reduce into concurrent tagged collectives
        # (ring.avg_all_reduce_windowed) to saturate fat pipes
        self.windows = windows
        # shm_staging: stage the flat vector in a registered shm buffer so
        # same-host slices ring-reduce zero-copy (one extra copy per reduce;
        # see DilocoConfig.shm_staging for the trade-off)
        self.shm_staging = shm_staging
        self._shm_stage = None
        self._codec = build_codec(template)
        # sharding of the template leaves, reapplied on the way back
        self._shardings = leaf_shardings(template)

    @property
    def count(self) -> int:
        return self._codec.count

    def _ring_avg(self, vec: np.ndarray) -> int:
        assert self.comm is not None
        return avg_all_reduce_windowed(
            self.comm, vec, windows=self.windows,
            quantization=self.quantization,
            quantized_dtype=self.quantized_dtype, max_retries=self.max_retries)

    def all_reduce(self, tree: Any) -> Any:
        """Global mean of `tree` across slices. Returns a tree with the
        original dtypes and shardings."""
        vec = self._codec.flat(tree)
        if self.comm is None:
            return self._codec.unflat(vec)
        # np.asarray: device_get already yields a host ndarray — a second
        # np.array copy would cost another params-sized memcpy per reduce
        host = np.asarray(jax.device_get(vec), dtype=np.float32)
        # quantized rings send from quantize scratch, not the staged buffer —
        # shm staging would be a pure extra copy there (see DilocoConfig)
        if self.shm_staging and self.quantization == QuantizationAlgorithm.NONE:
            if self._shm_stage is None:
                from pccl_tpu.comm.api import shm_ndarray

                self._shm_stage = shm_ndarray(self._codec.count, np.float32)
            np.copyto(self._shm_stage, host)
            host = self._shm_stage  # same-host slices reduce zero-copy
        elif not host.flags["WRITEABLE"] or not host.flags["C_CONTIGUOUS"]:
            host = np.array(host, dtype=np.float32)  # ring reduces in place
        self._ring_avg(host)
        # back to where the flat vector came from, not the default device
        out = self._codec.unflat(jax.device_put(host, vec.sharding))
        return restore_shardings(out, self._shardings)
