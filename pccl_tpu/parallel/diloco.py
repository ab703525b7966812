"""DiLoCo — low-communication data parallelism over the WAN ring.

Capability parity: the reference ships sync DiLoCo
(/root/reference/python/examples/nanogpt_diloco/sync_diloco.py:396-510,
docs/md/07-.../02-SyncDiloco.md) and async one-step-delayed DiLoCo
(async_diloco.py, docs/md/07-.../03-AsyncDiloco.md) as torch training loops
over the pccl bindings. Here the same algorithm is a library component,
designed TPU-first:

- the inner loop is whatever jitted SPMD train step the caller owns
  (pccl_tpu.parallel.train); DiLoCo never sees it;
- pseudo-gradients (outer_params - inner_params) are computed ON DEVICE by a
  jitted function that flattens every leaf into ONE contiguous fp32 vector —
  a single large buffer is the shape the ring reduce wants (few tags, big
  chunks saturate the pipe), and the flatten/unflatten round-trip is free
  for XLA to fuse;
- only that one vector crosses host↔device per outer step; the outer
  (Nesterov SGD) update runs jitted on device;
- the WAN hop supports on-the-wire quantization (MinMax / ZeroPointScale),
  mirroring the reference's piquant path;
- fault tolerance follows the reference contract: ConnectionLost/Aborted →
  update_topology() → retry with the surviving world.

Shared-state integration: `shared_state()` exposes outer params + outer
optimizer momentum + step as a revisioned pccl_tpu.comm.SharedState so
late joiners catch up bit-identically (reference sync_diloco.py keeps the
same three groups in its shared state).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..comm import (
    Communicator,
    DataType,
    QuantizationAlgorithm,
    ReduceOp,
    SharedState,
    SharedStateSyncStrategy,
    TensorInfo,
)
from . import codec
from .ring import avg_all_reduce_windowed


@dataclasses.dataclass(frozen=True)
class DilocoConfig:
    """Hyperparameters of the outer loop (reference defaults:
    sync_diloco.py outer SGD lr=0.7, nesterov momentum=0.9, H~50-500)."""

    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    nesterov: bool = True
    inner_steps: int = 50
    quantization: QuantizationAlgorithm = QuantizationAlgorithm.NONE
    quantized_dtype: DataType = DataType.UINT8
    max_retries: int = 16
    # Stage the pseudo-gradient in a REGISTERED shm buffer (comm.shm_ndarray)
    # so same-host peers take the zero-copy collective path. Costs one extra
    # params-sized copy per outer step, so enable it when peers share hosts
    # (workers per TPU host, bench loops); leave off for pure-WAN rings.
    shm_staging: bool = False
    # Split the outer reduce into this many concurrent tagged collectives
    # (ring.avg_all_reduce_windowed) — the reference's MultipleWithRetry
    # recipe for saturating fat pipes with multiple flows. 1 = single op.
    comm_windows: int = 1
    # Record a per-phase wall-clock breakdown of each outer step in
    # Diloco.last_profile (fences phases with block_until_ready, so leave
    # off in production — it defeats the pipelined reduce overlap).
    profile: bool = False


from .codec import build_codec


class Diloco:
    """Synchronous DiLoCo driver around a Communicator.

    Usage::

        dl = Diloco(comm, params, cfg)
        while training:
            comm.update_topology()                 # admit joiners
            dl.sync_shared_state()                 # catch up if outdated
            params = dl.params()                   # donation-safe copy
            for _ in range(cfg.inner_steps):
                params, opt_state, loss = inner_step(params, opt_state, ...)
            params = dl.outer_step(params)         # WAN ring + outer SGD

    The returned `params` after outer_step are the new global (outer) params,
    already on device with the original shardings — continue inner training
    from them (reference: sync_diloco.py resets inner params to outer).
    """

    def __init__(self, comm: Optional[Communicator], params: Any,
                 cfg: DilocoConfig = DilocoConfig()):
        self.comm = comm
        self.cfg = cfg
        self.step = 0
        c = build_codec(params)
        self._delta_fn, self._flat_fn, self._unflat_fn = c.flat_delta, c.flat, c.unflat
        self._delta_vec_fn, self.count = c.flat_delta_vec, c.count
        self._shm_stage = None  # lazy registered staging buffers (cfg.shm_staging)
        self._shm_out = None
        self._host_out = None  # pooled recv for the unstaged out-of-place ring
        # leaf shardings of the template, reapplied after every unflatten so
        # outer params keep the caller's TP/DP layout
        self._shardings = codec.leaf_shardings(params)
        # The CANONICAL outer state is the flat fp32 vector — the form every
        # per-step consumer wants (pseudo-gradient subtract, ring reduce,
        # outer SGD, shared-state offer). The param TREE is materialized only
        # at the API boundary (params(), outer_step return, the outer_params
        # property), where _unflat_fn's jit outputs are fresh buffers and so
        # donation-safe without a defensive full-tree copy. This removes two
        # params-sized copies and one flatten per outer step vs. keeping the
        # tree canonical. Committed placement from step 0: uncommitted inputs
        # would retrace the jitted helpers once their outputs come back
        # committed — at 100M+ params each spurious retrace costs seconds.
        # Everything this driver owns lives where the template's flat vector
        # landed (self._outer_vec.sharding), never on the default device:
        # several peers of one process each own a chip.
        self._outer_vec = self._flat_fn(params)
        self._momentum_vec = jnp.zeros((self.count,), jnp.float32,
                                       device=self._outer_vec.sharding)
        # last in-flight apply output: overwriting the reused shm staging
        # buffer must wait for it (device_put on the CPU backend can alias
        # staged host memory zero-copy, so a pending apply may still read it)
        self._applied = None
        self.last_profile: Optional[dict] = None

        lr, mu, nesterov = cfg.outer_lr, cfg.outer_momentum, cfg.nesterov

        def _apply(outer_vec, mom, delta):
            mom = mu * mom + delta
            upd = delta + mu * mom if nesterov else mom
            return outer_vec - lr * upd, mom

        # outer_vec and momentum are dead after the call — donate their
        # buffers so the update runs in place instead of allocating 2 more
        # param-sized arrays
        self._apply_fn = jax.jit(_apply, donate_argnums=(0, 1))

        # fused apply+unflatten for the sync outer step: ONE dispatch yields
        # the updated vector, the momentum, and the output tree — XLA slices
        # the tree leaves out of the same pass that writes the update, so
        # the separate unflat dispatch (a full params-sized re-read; 0.58 s
        # at 100M params on the bench host) disappears from the step
        unflat = c.unflat

        def _apply_tree(outer_vec, mom, delta):
            new_vec, mom = _apply(outer_vec, mom, delta)
            return new_vec, mom, unflat(new_vec)

        self._apply_tree_fn = jax.jit(_apply_tree, donate_argnums=(0, 1))

    # -- the outer step --

    @property
    def outer_params(self) -> Any:
        """Current outer params as a device pytree (fresh buffers, laid out
        with the caller's shardings). Assignment flattens back into the
        canonical vector."""
        return self._restore_shardings(self._unflat_fn(self._outer_vec))

    @outer_params.setter
    def outer_params(self, tree: Any) -> None:
        self._outer_vec = self._flat_fn(tree)

    def params(self) -> Any:
        """Current outer params, safe to hand to a donating train step (the
        driver keeps only the flat vector; these buffers are fresh)."""
        return self.outer_params

    def _restore_shardings(self, tree: Any) -> Any:
        return codec.restore_shardings(tree, self._shardings)

    def _reduce_host(self, vec: np.ndarray, out: np.ndarray = None) -> int:
        assert self.comm is not None
        return avg_all_reduce_windowed(
            self.comm, vec, windows=self.cfg.comm_windows, out=out,
            quantization=self.cfg.quantization,
            quantized_dtype=self.cfg.quantized_dtype,
            max_retries=self.cfg.max_retries)

    # tag band for pipelined window reduces: disjoint from the blocking
    # default 0, user small tags, the MultipleWithRetry band (1<<16), and
    # the auto band (1<<32); deterministic so every peer matches by window
    _WINDOW_TAG_BASE = 1 << 20

    def _ensure_shm_stage(self) -> None:
        if self._shm_stage is None:
            from pccl_tpu.comm.api import shm_ndarray

            # double-buffered: the ring reduces stage -> out out-of-place,
            # which skips the native in-place abort-restore backup (a full
            # params-sized memcpy per outer step)
            self._shm_stage = shm_ndarray(self.count, np.float32)
            self._shm_out = shm_ndarray(self.count, np.float32)

    def _reduce_pipelined(self, delta) -> bool:
        """Overlapped outer reduce: device->host of window k+1 overlaps the
        ring reduce of window k (the windows are independent tagged
        collectives). Falls back (returns False) when windowing is off or
        the vector is too small; failed windows retry over the survivor
        world via MultipleWithRetry, completed ones stand — the documented
        mixed-world windowed semantics."""
        from pccl_tpu.comm import PcclError, TooFewPeersError
        from .ring import _MIN_WINDOW_ELEMS

        k = min(self.cfg.comm_windows, max(1, self.count // _MIN_WINDOW_ELEMS), 8)
        if k <= 1:
            return False
        self._ensure_shm_stage()
        # the stage may still be read by the previous step's apply (CPU
        # backend device_put can alias it zero-copy) — wait it out
        if self._applied is not None:
            jax.block_until_ready(self._applied)
            self._applied = None
        bounds = [self.count * i // k for i in range(k + 1)]
        # slice on device and start every D2H up front; np.asarray(win)
        # then only blocks for ITS window while later windows keep copying
        wins = [jax.lax.slice_in_dim(delta, bounds[i], bounds[i + 1], axis=0)
                for i in range(k)]
        for w in wins:
            w.copy_to_host_async()
        handles, views, failed = [], [], []
        for i, w in enumerate(wins):
            view = self._shm_stage[bounds[i]:bounds[i + 1]]
            out_view = self._shm_out[bounds[i]:bounds[i + 1]]
            np.copyto(view, np.asarray(w, dtype=np.float32))
            views.append(out_view)
            # launch this window's ring while the next window's D2H runs —
            # out-of-place into the second stage, so the native ring skips
            # its in-place abort-restore backup copy. A launch-time failure
            # must NOT escape with earlier windows still in flight on this
            # shared buffer — record it for the retry batch and keep going
            # to the join below.
            try:
                handles.append((i, self.comm.all_reduce_async(
                    view, out_view, op=ReduceOp.AVG,
                    tag=self._WINDOW_TAG_BASE + i)))
            except TooFewPeersError:
                np.copyto(out_view, view)  # alone: the window is its own avg
            except PcclError:
                # never launched: the out view holds stale bytes — seed it
                # with the input so the in-place retry below reduces real data
                np.copyto(out_view, view)
                failed.append(i)
        for i, h in handles:
            try:
                h.wait()
            except TooFewPeersError:
                np.copyto(views[i], self._shm_stage[bounds[i]:bounds[i + 1]])
            except PcclError:
                # aborted mid-op: the native ring restored the out view from
                # the untouched staged input, so the retry sees real data
                failed.append(i)
        if failed:
            # survivors agree on the failed SET (exactly-one-abort
            # accounting), but not necessarily its order (launch-time vs
            # wait-time detection interleave differently per peer) — and
            # MultipleWithRetry assigns tags by list POSITION. Sort so the
            # retry batch pairs the same window across all peers.
            failed = sorted(set(failed))
            self.comm.update_topology()
            try:
                self.comm.all_reduce_multiple_with_retry(
                    [views[i] for i in failed], op=ReduceOp.AVG)
            except TooFewPeersError:
                pass
        return True

    def outer_step(self, inner_params: Any) -> Any:
        """Average pseudo-gradients across peers, apply outer Nesterov SGD,
        return the new global params (device pytree).

        ``inner_params`` is CONSUMED (its buffers are donated to the
        pseudo-gradient computation — see codec.build_codec); continue
        training from the returned tree. The returned tree has fresh
        buffers, safe to hand to a donating train step; the driver keeps
        only the canonical flat vector.

        With ``cfg.profile`` set, ``self.last_profile`` holds a per-phase
        wall-clock breakdown (seconds) of this step — each phase is fenced
        with block_until_ready, which serializes the device pipeline, so
        profiled steps run slightly slower than unprofiled ones."""
        prof: Optional[dict] = {} if self.cfg.profile else None
        cpu_mark = [time.process_time()]

        def mark(name, t0, *sync):
            if prof is not None:
                for a in sync:
                    jax.block_until_ready(a)
                t1 = time.perf_counter()
                prof[name] = t1 - t0
                # cpu seconds alongside wall: on a contended host the gap
                # between them is scheduler wait / peer wait, not phase work
                c1 = time.process_time()
                prof[name + "_cpu"] = c1 - cpu_mark[0]
                cpu_mark[0] = c1
                return t1
            return t0

        t = time.perf_counter()
        delta = self._delta_vec_fn(self._outer_vec, inner_params)
        t = mark("delta_compute", t, delta)
        # quantized rings send from quantize scratch, not from the staged
        # buffer — shm staging would be a pure extra copy there, so gate it
        use_shm = (self.cfg.shm_staging and self.comm is not None
                   and self.cfg.quantization == QuantizationAlgorithm.NONE)
        if (use_shm and self.cfg.comm_windows > 1
                and self._reduce_pipelined(delta)):
            # pipelined: D2H of window k+1 overlaps the ring of window k, so
            # the phases are not separable — profiled, this records as one
            # combined phase. The branch must NOT depend on cfg.profile:
            # the reduce path is a cross-peer protocol (window tags must
            # match on every rank), and profile is a local flag.
            host = self._shm_out
            t = mark("d2h_stage_ring_pipelined", t)
        else:
            # np.asarray: device_get already yields a host ndarray — a second
            # np.array copy would cost another params-sized memcpy per step
            host = np.asarray(jax.device_get(delta), dtype=np.float32)
            t = mark("d2h", t)
            if self._applied is not None:  # see _reduce_pipelined
                jax.block_until_ready(self._applied)
                self._applied = None
            if use_shm:
                self._ensure_shm_stage()
                np.copyto(self._shm_stage, host)
                t = mark("stage_copy", t)
                if self.comm is not None:
                    # out-of-place between the two registered stages: the
                    # same-host ring reduces zero-copy AND skips the native
                    # in-place backup memcpy
                    self._reduce_host(self._shm_stage, out=self._shm_out)
                host = self._shm_out
            else:
                if not host.flags["C_CONTIGUOUS"]:
                    host = np.ascontiguousarray(host, dtype=np.float32)
                t = mark("stage_copy", t)
                if self.comm is not None:
                    if self._host_out is None or self._host_out.size != self.count:
                        self._host_out = np.empty(self.count, np.float32)
                    self._reduce_host(host, out=self._host_out)
                    host = self._host_out
            t = mark("ring_reduce", t)
        new_vec, self._momentum_vec, out = self._apply_tree_fn(
            self._outer_vec, self._momentum_vec,
            jax.device_put(host, self._outer_vec.sharding))
        self._outer_vec = self._applied = new_vec
        t = mark("h2d_apply", t, new_vec)
        self.step += 1
        # tree materialization fused into the apply dispatch above; what's
        # left here is only the (usually no-op) sharding restore
        out = self._restore_shardings(out)
        mark("unflat_out", t, out)
        if prof is not None:
            prof["total"] = sum(v for k, v in prof.items() if not k.endswith("_cpu"))
            self.last_profile = prof
        return out

    # -- shared state --

    def shared_state(self) -> SharedState:
        """Outer params + momentum + step as a revisioned SharedState.
        Revision = outer step count (one-increment rule of the master,
        reference ccoip_master_state.cpp:1066-1090)."""
        self._ss_vec = np.array(
            jax.device_get(self._outer_vec), dtype=np.float32)
        self._ss_mom = np.array(jax.device_get(self._momentum_vec),
                                  dtype=np.float32)
        self._ss_step = np.array([self.step], dtype=np.uint64)
        return SharedState([
            TensorInfo.from_numpy("diloco.outer_params", self._ss_vec),
            TensorInfo.from_numpy("diloco.outer_momentum", self._ss_mom),
            TensorInfo.from_numpy("diloco.step", self._ss_step),
        ], revision=self.step)

    def sync_shared_state(
            self,
            strategy: SharedStateSyncStrategy = SharedStateSyncStrategy.ENFORCE_POPULAR):
        """Sync outer state with the group; adopt whatever wins the election
        into the outer vector / momentum / step. Returns the
        SharedStateSyncInfo (tx/rx bytes, revision); take the adopted params
        via self.params()."""
        assert self.comm is not None
        st = self.shared_state()
        info = self.comm.sync_shared_state(st, strategy)
        # adopt (possibly received) content. The last apply's output is the
        # vector shared_state() just read back, so it has landed: drop the
        # handle, or the replaced vector stays on the device (4 B/param)
        # until the next outer step
        self._applied = None
        self.step = int(self._ss_step[0])
        sharding = self._outer_vec.sharding
        self._momentum_vec = jax.device_put(self._ss_mom, sharding)
        self._outer_vec = jax.device_put(self._ss_vec, sharding)
        return info


class AsyncDiloco(Diloco):
    """One-step-delayed DiLoCo: the reduce of outer step t overlaps with the
    inner compute of step t+1 (reference async_diloco.py,
    docs/md/07-.../03-AsyncDiloco.md:1-112).

    outer_step_async(inner_params) kicks the WAN reduce on a background
    thread and returns IMMEDIATELY with params to continue training from
    (the current outer params — the delayed update lands next call).
    Call .finish() (or the next outer_step_async) to join the in-flight
    reduce and apply it.
    """

    def __init__(self, comm, params, cfg: DilocoConfig = DilocoConfig()):
        super().__init__(comm, params, cfg)
        self._inflight: Optional[threading.Thread] = None
        self._inflight_host: Optional[np.ndarray] = None
        self._async_out: Optional[np.ndarray] = None  # pooled reduce output
        self._err: Optional[BaseException] = None
        # flat outer vector the inner phase started from (pseudo-gradient
        # baseline — before the delayed update from step t-1 lands)
        self._baseline: Optional[jax.Array] = None

    def _reduce_bg(self, host: np.ndarray, out: np.ndarray) -> None:
        try:
            if self.comm is not None:
                # out-of-place into the pooled buffer: skips the native
                # in-place snapshot memcpy (same win as the sync path)
                self._reduce_host(host, out=out)
            else:
                np.copyto(out, host)
        except BaseException as e:  # noqa: BLE001 — surfaced on join
            self._err = e

    def _join_inflight(self) -> None:
        if self._inflight is None:
            return
        self._inflight.join()
        self._inflight = None
        if self._err is not None:
            err, self._err = self._err, None
            self._inflight_host = None
            raise err
        self._inflight_host = None
        # NOT the fused _apply_tree_fn: the async path reads outer_params at
        # times decoupled from the join (sync_shared_state may adopt a new
        # vector in between, and donating callers need fresh buffers per
        # read), so a cached tree would be a staleness hazard for a minor
        # win in a phase that already overlaps inner compute.
        new_vec, self._momentum_vec = self._apply_fn(
            self._outer_vec, self._momentum_vec,
            jax.device_put(self._async_out, self._outer_vec.sharding))
        self._outer_vec = self._applied = new_vec
        self.step += 1

    def outer_step_async(self, inner_params: Any) -> Any:
        """Apply the previous in-flight reduce (if any), launch the reduce of
        this step's pseudo-gradient, return params to continue from.

        Like the sync path, ``inner_params`` is CONSUMED (buffers donated
        into the pseudo-gradient); read any eval/logging values from it
        BEFORE this call and continue from the returned tree."""
        # the pseudo-gradient baseline is the outer vector the inner phase
        # STARTED from — before the delayed update from step t-1 lands
        # (reference async semantics, docs/md/07-.../03-AsyncDiloco.md)
        baseline = self._baseline if self._baseline is not None else self._outer_vec
        delta = self._delta_vec_fn(baseline, inner_params)
        host = np.array(jax.device_get(delta), dtype=np.float32)
        self._join_inflight()
        if self._async_out is None:
            self._async_out = np.empty(self.count, np.float32)
        # the pooled out buffer may still feed the apply just dispatched
        # (device_put can alias it zero-copy on the CPU backend) — the
        # background ring must not overwrite it until that apply lands
        if self._applied is not None:
            jax.block_until_ready(self._applied)
            self._applied = None
        self._inflight_host = host
        self._inflight = threading.Thread(target=self._reduce_bg,
                                          args=(host, self._async_out),
                                          daemon=True)
        self._inflight.start()
        self._baseline = self._outer_vec
        # fresh jit-output buffers: safe for a donating train step
        return self.outer_params

    def sync_shared_state(
            self,
            strategy: SharedStateSyncStrategy = SharedStateSyncStrategy.ENFORCE_POPULAR):
        """Land (or fail) the in-flight delayed update BEFORE the election so
        the offered state is self-consistent, and drop the pseudo-gradient
        baseline afterwards — adopted params invalidate it (the delta would
        otherwise include the whole sync jump)."""
        self._join_inflight()
        info = super().sync_shared_state(strategy)
        self._baseline = None
        return info

    def finish(self) -> Any:
        """Join any in-flight reduce and apply it; returns final outer params
        (fresh buffers, donation-safe)."""
        self._join_inflight()
        return self.outer_params
