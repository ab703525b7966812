#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on the chip.

    python chip_smoke.py                  # demands TPUs; ONE process owns them all
    python chip_smoke.py --cpu-rehearsal  # tiny shapes on the CPU; proves nothing

Drives make_train_state -> build_train_step -> Diloco.sync_shared_state /
Diloco.outer_step -> Communicator over a libpcclt.so built here from the
committed sources, at the full width and depth of gpt2-medium, through the
entry points a user calls. Peers are threads of this process, one per chip,
each with its own one-device mesh; on a single chip a second ring peer lives
on the host CPU device and adopts state with RECEIVE_ONLY.

Every phase either passes its checks or raises: nothing is caught to let the
run go on. The line before last of stdout, "result: {...}", is one JSON object
with each phase's result and seconds; the last line is the verdict alone,
{"ok": ..., "device": {"platform", "kind", "count"}}, with the device as jax
reports it. The exit code is non-zero if any phase failed, and when no TPU
answers nothing is printed as a result.
Times printed here are single observations, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import functools
import importlib.metadata
import json
import os
import shutil
import subprocess
import sys
import threading
import time
import traceback
from pathlib import Path
from unittest import mock

REPO = Path(__file__).resolve().parent

# The real run: gpt2-medium at its published width and depth, bf16 compute.
# batch/remat: beside DiLoCo's outer vector and momentum (8 B/param, 2.63 GiB)
# a v5e's 15.75 GiB leave 9.16 for the step's scratch. Seen on the chip, one
# run per config (PR 21): "dots" loads b8 and b12 (8.73 GiB) but not b16
# (10.89); full remat loads b24 and b32 but not b40 (10.39). b12 + "dots",
# model_bench's shape, passed this script on one chip and failed to load in
# the async period on four, so the smoke keeps a margin and runs b8.
REAL = dict(preset="gpt2-medium", batch=8, seq=1024, remat="dots",
            attn_T=2048, long_ctx=((8192, None), (32768, 2048)),
            hash_elems=(256 << 20) // 4 + 7, fence_n=8192, fence_iters=64)
REHEARSAL = dict(preset="tiny", batch=2, seq=128, remat=True,
                 attn_T=256, long_ctx=((256, None), (512, 128)),
                 hash_elems=(1 << 20) // 4 + 7, fence_n=256, fence_iters=8)
INNER_STEPS, PERIODS = 4, 2
# the contract gives 1200 s; a hung peer must still end in a non-zero exit
WATCHDOG_S = 1140
PEER_TIMEOUT_S = 900

RESULT: dict = {"ok": False, "device": None, "phases": {}}


def verdict_line(result: dict) -> str:
    """The last line of stdout: exactly `ok` and `device`, nothing else (the
    driver's check parses it; the detail goes on the line before)."""
    return json.dumps({"ok": bool(result["ok"]), "device": result["device"]})


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Counts jax's backend compiles, their seconds and persistent-cache hits
    (jax.monitoring events), process-wide."""

    def __init__(self):
        import jax

        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def run_phase(name: str, fn, *args) -> None:
    log(f"== phase {name}")
    RESULT["failed_phase"] = name
    t0 = time.perf_counter()
    out = fn(*args)            # raises on failure: no later phase runs
    RESULT["phases"][name] = {"ok": True,
                              "s": round(time.perf_counter() - t0, 1),
                              **(out or {})}
    del RESULT["failed_phase"]


def run_peers(targets, barrier=None) -> list:
    """Run one thread per (fn, args) peer and re-raise the first peer failure.
    A failed peer breaks `barrier`, so the others stop at their next wait
    instead of sitting out its timeout; they get 30 s to do so."""
    results, errors = [None] * len(targets), []

    def wrap(i, fn, args):
        try:
            results[i] = fn(*args)
        except BaseException as e:  # noqa: BLE001 — re-raised in the caller
            errors.append((i, e, time.monotonic()))
            traceback.print_exc()
            if barrier is not None:
                barrier.abort()

    threads = [threading.Thread(target=wrap, args=(i, fn, args), daemon=True)
               for i, (fn, args) in enumerate(targets)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + PEER_TIMEOUT_S
    while any(t.is_alive() for t in threads) and time.monotonic() < deadline:
        if errors:
            deadline = min(deadline, errors[0][2] + 30)
        time.sleep(0.05)
    if errors:
        raise RuntimeError(f"peer {errors[0][0]} failed") from errors[0][1]
    hung = [i for i, t in enumerate(threads) if t.is_alive()]
    if hung:
        raise TimeoutError(f"peers {hung} still running after "
                           f"{PEER_TIMEOUT_S} s")
    return results


def connect(master_port: int, world: int):
    """A Communicator that keeps the ReduceInfo of every ring op the drivers
    issue (Diloco and HierarchicalAllReduce drop it), admitted into a world of
    `world` peers. Listener ports are kernel-assigned."""
    from pccl_tpu.comm import Communicator

    class RecordingComm(Communicator):
        reduces: list

        def all_reduce(self, *a, **kw):
            info = super().all_reduce(*a, **kw)
            self.reduces.append(info)
            return info

    comm = RecordingComm("127.0.0.1", master_port)
    comm.reduces = []
    comm.connect()
    deadline = time.monotonic() + 120
    while comm.world_size < world:
        if time.monotonic() > deadline:
            raise TimeoutError(f"world never reached {world}")
        if comm.are_peers_pending():
            comm.update_topology()
        time.sleep(0.01)
    return comm


def assert_on(dev, what: str, tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        if leaf.devices() != {dev}:
            raise AssertionError(f"{what}: array on {leaf.devices()}, "
                                 f"expected {dev}")


def hbm_report(dev) -> str:
    """What holds `dev`'s memory: bytes in use as the runtime counts them, and
    the live jax arrays of 64 MiB and more (GiB, largest first)."""
    import jax

    big = sorted((a.nbytes for a in jax.live_arrays()
                  if a.devices() == {dev} and a.nbytes >= 64 << 20),
                 reverse=True)
    in_use = (dev.memory_stats() or {}).get("bytes_in_use")
    return (f"{dev}: bytes_in_use {in_use}, live arrays GiB "
            + " ".join(f"{n / 2**30:.2f}" for n in big))


# ------------------------------------------------------------- phase: native

def phase_native() -> dict:
    """Configure and build libpcclt.so from the committed sources into the
    gitignored build directory, and make THAT file the one the loader takes
    (PCCLT_LIB is the loader's first candidate, so whatever it or a packaged
    pccl_tpu/_lib/ held is overridden)."""
    src = REPO / "pccl_tpu" / "native"
    build = src / "build"
    cache = build / "CMakeCache.txt"
    if cache.exists() and \
            f"CMAKE_HOME_DIRECTORY:INTERNAL={src}\n" not in cache.read_text():
        # configured where this tree was copied from: cmake refuses a moved
        # source directory, and its objects are not this tree's
        log(f"stale build directory (configured elsewhere): removing {build}")
        shutil.rmtree(build)
    for cmd in (["cmake", "-S", str(src), "-B", str(build), "-G", "Ninja"],
                ["ninja", "-C", str(build), "pcclt"]):
        subprocess.run(cmd, check=True, stdout=sys.stderr)
    lib = build / "libpcclt.so"
    os.environ["PCCLT_LIB"] = str(lib)
    from pccl_tpu.comm import _native

    loaded = _native.load()
    if Path(loaded._name) != lib:
        raise AssertionError(f"loader took {loaded._name}, not {lib}")
    info = loaded.pccltGetBuildInfo().decode()
    log(f"native core: {lib} ({info})")
    return {"lib": str(lib.relative_to(REPO))}


# -------------------------------------------------------------- phase: fence

def phase_fence(dev, sizes, rehearsal: bool) -> dict:
    """Does block_until_ready wait for execution on this chip? A chained
    matmul is timed to block_until_ready and to a scalar readback; the two
    must agree and neither may beat the chip's peak."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pccl_tpu.benchmarks.model_bench import peak_tflops

    n, iters = sizes["fence_n"], sizes["fence_iters"]
    m = jnp.full((n, n), 1.0 / n, jnp.bfloat16, device=dev)

    @jax.jit
    def chain(x, w):
        return lax.fori_loop(0, iters,
                             lambda i, y: (y @ w).astype(jnp.bfloat16), x)

    jax.block_until_ready(chain(m, m))               # compile
    t0 = time.perf_counter()
    jax.block_until_ready(chain(m, m))
    t_fence = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(chain(m, m)[0, 0])
    t_read = time.perf_counter() - t0
    tflops = 2.0 * n ** 3 * iters / t_fence / 1e12
    log(f"chained {iters} x {n}^3 bf16 matmul: {t_fence:.4f} s to "
        f"block_until_ready ({tflops:.1f} TFLOP/s), {t_read:.4f} s to a "
        f"scalar readback")
    if not rehearsal:
        peak = peak_tflops(dev)
        if tflops > peak:
            raise AssertionError(
                f"block_until_ready returned after {t_fence:.4f} s, "
                f"{tflops:.0f} TFLOP/s against a {peak:.0f} peak: it does "
                f"not wait for execution here")
        if not 0.8 * t_read <= t_fence <= 1.25 * t_read:
            raise AssertionError(
                f"block_until_ready ({t_fence:.4f} s) and a scalar readback "
                f"({t_read:.4f} s) disagree about when the work is done")
    return {"block_until_ready_s": round(t_fence, 4),
            "readback_s": round(t_read, 4), "tflops": round(tflops, 1)}


# ------------------------------------------------------------ phase: kernels

# Tolerance of the kernel-vs-reference comparison, as max|a-b| / max|b|.
# Inputs are the same bf16 values on both sides; the reference runs fp32 at
# "highest" matmul precision. The kernels round p (and ds) to bf16 before the
# second gemm of each pair and round the outputs to bf16: two roundings of
# unit roundoff 2^-9 = 0.002 each plus fp32 accumulation over T terms, a few
# 1e-3 in all. 2e-2 passes that and fails what it must: bf16 ACCUMULATION
# over T=2048 terms (~sqrt(T) * 2^-9 = 0.09) or any 8-bit operand (2^-4).
KERNEL_TOL = 2e-2


def _rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _check_flash_against_reference(dev, T: int, H: int, Hkv: int, Dh: int,
                                   attn) -> float:
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops.flash_attention import reference_attention

    ks = jax.random.split(jax.random.PRNGKey(H * Dh), 4)
    with jax.default_device(dev):
        q = jax.random.normal(ks[0], (1, T, H, Dh), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, T, Hkv, Dh), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, T, Hkv, Dh), jnp.bfloat16)
        w = jax.random.normal(ks[3], (1, T, H, Dh), jnp.float32)

    def loss(fn, q, k, v, w):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    flash = jax.jit(jax.value_and_grad(functools.partial(loss, attn),
                                       argnums=(0, 1, 2), has_aux=True))
    ref = jax.jit(jax.value_and_grad(
        functools.partial(loss, reference_attention),
        argnums=(0, 1, 2), has_aux=True))
    (_, out_f), grads_f = flash(q, k, v, w)
    with jax.default_matmul_precision("highest"):
        (_, out_r), grads_r = ref(
            *(x.astype(jnp.float32) for x in (q, k, v)), w)
    errs = {"out": _rel_err(out_f, out_r)}
    for name, gf, gr in zip(("dq", "dk", "dv"), grads_f, grads_r):
        errs[name] = _rel_err(gf, gr)
    log(f"flash vs reference, {H}/{Hkv} heads x Dh={Dh}, T={T}: "
        + ", ".join(f"{k} {v:.1e}" for k, v in errs.items()))
    worst = max(errs.values())
    if not worst <= KERNEL_TOL:          # also catches NaN
        raise AssertionError(f"flash attention off the reference: {errs}")
    return worst


def phase_kernels(dev, sizes, rehearsal: bool) -> dict:
    import jax
    import numpy as np

    from examples.common import synth_batch
    from pccl_tpu.models import gpt
    from pccl_tpu.ops.flash_attention import flash_attention
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib

    # compiled for the chip; the rehearsal has no Mosaic, so it interprets
    attn = functools.partial(flash_attention, interpret=True) if rehearsal \
        else flash_attention
    worst = max(
        _check_flash_against_reference(dev, sizes["attn_T"], H, Hkv, Dh, attn)
        for H, Hkv, Dh in ((16, 16, 64), (12, 4, 128)))

    # the long-context claim, where the VMEM ceiling was met before: one
    # full-depth train step through the kernels under a remat'd scan
    mesh = mesh_lib.make_mesh([dev], shape=(1, 1))
    out = {"flash_worst_rel_err": round(worst, 5)}
    params, tx, opt_state = train_lib.make_train_state(
        jax.random.PRNGKey(0), gpt.named_config(sizes["preset"]), mesh)
    data_sharding = mesh_lib.batch_sharding(mesh)
    for T, chunk in sizes["long_ctx"]:
        cfg = gpt.named_config(sizes["preset"], block_size=T)
        step = train_lib.build_train_step(cfg, tx, mesh, attn_fn=attn,
                                          remat=True, loss_chunk=chunk)
        tok, tgt = (jax.device_put(x, data_sharding) for x in synth_batch(
            np.random.RandomState(T), 1, T, cfg.vocab_size))
        compiled = step.lower(params, opt_state, tok, tgt).compile()
        if not rehearsal and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"T={T}: no Mosaic custom call in the "
                                 f"compiled train step")
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tok, tgt)
        jax.block_until_ready((params, opt_state, loss))
        dt = time.perf_counter() - t0
        loss = float(loss)
        log(f"train step b1x{T} flash+remat"
            f"{f'+ce:{chunk}' if chunk else ''}: loss {loss:.4f}, "
            f"{dt:.3f} s (first execution)")
        if not np.isfinite(loss):
            raise AssertionError(f"T={T}: loss {loss}")
        out[f"step_b1x{T}_s"] = round(dt, 3)
    return out


# --------------------------------------------------------------- phase: hash

def phase_hash(master_port: int, dev, cpu_dev, sizes) -> dict:
    """The device digest against its numpy and native twins, then one
    device-hashed entry through a real sync_shared_state: the chip peer is
    the distributor, so the native core fires its _materialize callback (a
    D2H issued from a native serving thread)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pccl_tpu.comm import (SharedState, SharedStateSyncStrategy,
                               TensorInfo, _native)
    from pccl_tpu.ops.hashing import jax_simplehash_device, simplehash_tpu

    n = sizes["hash_elems"]
    with jax.default_device(dev):
        arr = jax.random.normal(jax.random.PRNGKey(7), (n,), jnp.float32)
    h_dev = jax_simplehash_device(arr)               # compile
    t0 = time.perf_counter()
    h_dev = jax_simplehash_device(arr)
    t_dev = time.perf_counter() - t0
    host = np.asarray(arr)
    h_np = simplehash_tpu(host)
    h_native = _native.load().pccltHashBuffer(2, host.ctypes.data, host.nbytes)
    log(f"simplehash_tpu of {host.nbytes >> 20} MiB: device {h_dev:#018x} "
        f"({t_dev:.4f} s), numpy {h_np:#018x}, native {h_native:#018x}")
    if not h_dev == h_np == h_native:
        raise AssertionError("device, numpy and native digests differ")

    def peer(source, strategy):
        comm = connect(master_port, 2)
        try:
            ti = TensorInfo.from_jax_device("w", source)
            info = comm.sync_shared_state(SharedState([ti], revision=1),
                                          strategy)
            return ti, info
        finally:
            comm.destroy()

    # the native core reads the variable at every sync
    with mock.patch.dict(os.environ, PCCLT_SS_HASH="simple-tpu"):
        (ti_tx, info_tx), (ti_rx, info_rx) = run_peers([
            (peer, (arr, SharedStateSyncStrategy.SEND_ONLY)),
            (peer, (jnp.zeros((n,), jnp.float32, device=cpu_dev),
                    SharedStateSyncStrategy.RECEIVE_ONLY))])
    log(f"device-hashed sync: sender tx {info_tx.tx_bytes} B, receiver rx "
        f"{info_rx.rx_bytes} B")
    if info_rx.rx_bytes != host.nbytes or not ti_rx._updated:
        raise AssertionError(f"receiver got {info_rx.rx_bytes} of "
                             f"{host.nbytes} bytes")
    if ti_tx._updated or not np.array_equal(ti_tx.data, host):
        raise AssertionError("the sender's _materialize callback did not "
                             "stage the device array")
    got = ti_rx.jax_value()
    assert_on(cpu_dev, "received entry", got)
    if simplehash_tpu(np.asarray(got)) != h_dev:
        raise AssertionError("received content differs from the sender's")
    return {"devhash_s": round(t_dev, 4)}


# ------------------------------------------------------------ phase: trainer

def _sync_strategy(period: int, rank: int, n_chips: int):
    """Period 0 everywhere, and every period on one chip: rank 0 seeds, the
    rest adopt. With several chips, period 1 votes: by then the chips' outer
    states must already be bit-identical."""
    from pccl_tpu.comm import SharedStateSyncStrategy as S

    if period == 0 or n_chips == 1:
        return S.SEND_ONLY if rank == 0 else S.RECEIVE_ONLY
    return S.ENFORCE_POPULAR


def _chip_peer(rank, dev, n_chips, world, master_port, barrier, sizes):
    import jax
    import numpy as np

    from examples.common import synth_batch
    from pccl_tpu.models import gpt
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib
    from pccl_tpu.parallel.diloco import AsyncDiloco, Diloco, DilocoConfig
    from pccl_tpu.parallel.hierarchical import HierarchicalAllReduce

    B, T = sizes["batch"], sizes["seq"]
    mesh = mesh_lib.make_mesh([dev], shape=(1, 1))
    cfg = gpt.named_config(sizes["preset"], block_size=T)
    # a seed per rank: period 0's sync then has real bytes to move
    params, tx, opt_state = train_lib.make_train_state(
        jax.random.PRNGKey(rank), cfg, mesh)
    step = train_lib.build_train_step(cfg, tx, mesh, remat=sizes["remat"])
    tok, tgt = (jax.device_put(x, mesh_lib.batch_sharding(mesh))
                for x in synth_batch(np.random.RandomState(1000 + rank),
                                     B, T, cfg.vocab_size))
    comm = connect(master_port, world)
    try:
        dl = Diloco(comm, params, DilocoConfig(inner_steps=INNER_STEPS))
        del params
        losses, step_s, syncs = [], [], []
        for period in range(PERIODS):
            barrier.wait()
            info = dl.sync_shared_state(_sync_strategy(period, rank, n_chips))
            syncs.append((info.tx_bytes, info.rx_bytes))
            params = dl.params()
            for _ in range(INNER_STEPS):
                t0 = time.perf_counter()
                params, opt_state, loss = step(params, opt_state, tok, tgt)
                jax.block_until_ready((params, opt_state, loss))
                step_s.append(time.perf_counter() - t0)
                losses.append(float(loss))
            if period == PERIODS - 1:      # one profiled outer step
                dl.cfg = dataclasses.replace(dl.cfg, profile=True)
            params = dl.outer_step(params)
        barrier.wait()
        assert_on(dev, f"rank {rank} params", params)
        assert_on(dev, f"rank {rank} opt_state", opt_state)
        assert_on(dev, f"rank {rank} outer state",
                  (dl._outer_vec, dl._momentum_vec))
        count, ring = dl.count, list(comm.reduces)
        profile = dl.last_profile
        del dl                             # AsyncDiloco brings its own 8 B/param

        mean = HierarchicalAllReduce(comm, params).all_reduce(params)
        assert_on(dev, f"rank {rank} hierarchical mean", mean)
        # every peer holds the same params after an outer step, so their
        # mean is those params (up to CPU-vs-TPU rounding of the host peer)
        for name in mean:
            err = _rel_err(mean[name], params[name])
            if not err <= 1e-3:
                raise AssertionError(f"hierarchical mean of {name} is off "
                                     f"the params by {err:.1e}")
        del mean

        adl = AsyncDiloco(comm, params, DilocoConfig(inner_steps=INNER_STEPS,
                                                     outer_momentum=0.0))
        async_losses = []
        for _ in range(INNER_STEPS):
            params, opt_state, loss = step(params, opt_state, tok, tgt)
            async_losses.append(float(loss))
        adl.outer_step_async(params)
        params = adl.finish()
        assert_on(dev, f"rank {rank} async params", params)
        assert_on(dev, f"rank {rank} async outer state",
                  (adl._outer_vec, adl._momentum_vec))
        if not (np.isfinite(async_losses).all()
                and all(bool(np.isfinite(np.asarray(x)).all())
                        for x in (params["lnf_g"], adl._outer_vec[:1024]))):
            raise AssertionError(f"rank {rank}: async period went non-finite")
    except BaseException:
        log(f"rank {rank} FAILED with {hbm_report(dev)}")
        raise
    finally:
        comm.destroy()
    stats = dev.memory_stats() or {}
    return dict(rank=rank, losses=losses, step_s=step_s, syncs=syncs,
                ring=ring, count=count, profile=profile,
                async_losses=async_losses,
                peak_bytes=stats.get("peak_bytes_in_use"))


def _host_peer(rank, cpu_dev, world, master_port, barrier, sizes):
    """The ring peer that holds no chip: a real Diloco whose template sits on
    the host CPU device. It adopts the chip peer's outer state and takes no
    inner steps, so its pseudo-gradient is zero."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.comm import SharedStateSyncStrategy
    from pccl_tpu.models import gpt
    from pccl_tpu.parallel.diloco import AsyncDiloco, Diloco, DilocoConfig
    from pccl_tpu.parallel.hierarchical import HierarchicalAllReduce

    cfg = gpt.named_config(sizes["preset"], block_size=sizes["seq"])
    shapes = jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    template = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=cpu_dev), shapes)
    comm = connect(master_port, world)
    try:
        dl = Diloco(comm, template, DilocoConfig(inner_steps=INNER_STEPS))
        del template
        syncs = []
        for _ in range(PERIODS):
            barrier.wait()
            info = dl.sync_shared_state(SharedStateSyncStrategy.RECEIVE_ONLY)
            syncs.append((info.tx_bytes, info.rx_bytes))
            params = dl.outer_step(dl.params())
        barrier.wait()
        assert_on(cpu_dev, "host peer params", params)
        assert_on(cpu_dev, "host peer outer state",
                  (dl._outer_vec, dl._momentum_vec))
        del dl
        params = HierarchicalAllReduce(comm, params).all_reduce(params)
        assert_on(cpu_dev, "host peer hierarchical mean", params)
        adl = AsyncDiloco(comm, params, DilocoConfig(inner_steps=INNER_STEPS,
                                                     outer_momentum=0.0))
        adl.outer_step_async(adl.params())
        assert_on(cpu_dev, "host peer async params", adl.finish())
        assert_on(cpu_dev, "host peer async outer state",
                  (adl._outer_vec, adl._momentum_vec))
    finally:
        comm.destroy()
    return dict(rank=rank, syncs=syncs)


def phase_trainer(master_port: int, chips, cpu_dev, sizes, compiles,
                  rehearsal: bool) -> dict:
    """PERIODS outer periods of INNER_STEPS inner steps with one peer thread
    per chip (plus the host peer on a single chip), then one hierarchical
    all-reduce and one async period; checks what the peers report."""
    import numpy as np

    n_chips = len(chips)
    world = max(n_chips, 2)
    marks = []                 # compile count at each period boundary
    barrier = threading.Barrier(world, timeout=PEER_TIMEOUT_S,
                                action=lambda: marks.append(compiles.count))
    targets = [(_chip_peer, (r, d, n_chips, world, master_port, barrier,
                             sizes)) for r, d in enumerate(chips)]
    if n_chips == 1:
        targets.append((_host_peer, (1, cpu_dev, world, master_port, barrier,
                                     sizes)))
    results = run_peers(targets, barrier)
    chip_results = results[:n_chips]

    count = chip_results[0]["count"]
    vec_bytes = 4 * count
    # ring all-reduce of N bytes over a world of W: each peer sends and
    # receives 2 (W-1)/W N (reduce-scatter + all-gather)
    ring_bytes = 2 * (world - 1) * vec_bytes // world
    for r in chip_results:
        rank, losses = r["rank"], r["losses"]
        log(f"rank {rank}: loss " + " ".join(f"{x:.3f}" for x in losses)
            + " | async period "
            + " ".join(f"{x:.3f}" for x in r["async_losses"]))
        log(f"rank {rank}: inner step s "
            + " ".join(f"{x:.3f}" for x in r["step_s"])
            + " (each to block_until_ready; the first compiles)")
        log(f"rank {rank}: sync (tx, rx) bytes per period {r['syncs']}")
        log(f"rank {rank}: ring ops "
            + str([(i.world_size, i.tx_bytes, i.rx_bytes) for i in r["ring"]]))
        log(f"rank {rank}: peak_bytes_in_use {r['peak_bytes']}")
        if not (np.isfinite(losses).all() and losses[-1] < losses[0]):
            raise AssertionError(f"rank {rank}: loss did not fall: {losses}")
        if len(r["ring"]) != PERIODS:
            raise AssertionError(f"rank {rank}: {len(r['ring'])} ring ops")
        for info in r["ring"]:
            if info.world_size != world:
                raise AssertionError(f"rank {rank}: ring world "
                                     f"{info.world_size}, expected {world}")
            for moved in (info.tx_bytes, info.rx_bytes):
                if not ring_bytes <= moved <= 1.02 * ring_bytes:
                    raise AssertionError(
                        f"rank {rank}: ring moved {moved} B, a {vec_bytes} B "
                        f"vector over {world} peers is {ring_bytes}")
        # period 0 moves the outer params only: the momentum is still zero
        # everywhere and the step counts agree, so their hashes match
        if rank > 0 and r["syncs"][0][1] != vec_bytes:
            raise AssertionError(f"rank {rank}: period 0 adopted "
                                 f"{r['syncs'][0][1]} B, the outer params "
                                 f"are {vec_bytes}")
        if n_chips > 1 and r["syncs"][1] != (0, 0):
            raise AssertionError(
                f"rank {rank}: period 1 sync moved {r['syncs'][1]} bytes: "
                f"outer state is not bit-identical across chips")
    if n_chips == 1:
        log(f"host peer: sync (tx, rx) bytes per period {results[1]['syncs']}"
            f" (period 1 rx is 0 only where the CPU's outer update matched "
            f"the chip's bit for bit)")
        if results[1]["syncs"][0][1] != vec_bytes:
            raise AssertionError("host peer did not adopt the outer params")
    profile_s = {k: round(v, 3) for k, v in chip_results[0]["profile"].items()
                 if not k.endswith("_cpu")}
    log("rank 0 profiled outer step (s): " + json.dumps(profile_s))
    in_period_2 = marks[PERIODS] - marks[PERIODS - 1]
    if in_period_2:
        raise AssertionError(f"{in_period_2} compilations inside the second "
                             f"period")
    peaks = [r["peak_bytes"] for r in chip_results]
    if not rehearsal:          # the CPU backend reports no memory_stats
        if None in peaks or max(peaks) > 1.5 * min(peaks):
            raise AssertionError(f"per-device peak memory missing or "
                                 f"uneven across chips: {peaks}")
    steady = [x for r in chip_results for x in r["step_s"][INNER_STEPS:]]
    return {"world": world, "params": count,
            "batch": sizes["batch"], "remat": sizes["remat"],
            "loss_first": round(chip_results[0]["losses"][0], 3),
            "loss_last": round(chip_results[0]["losses"][-1], 3),
            "inner_step_s": round(float(np.median(steady)), 3),
            "outer_step_profile_s": profile_s,
            "peak_bytes_in_use": peaks}


# ---------------------------------------------------------------------- main

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny shapes on the CPU with the kernels interpreted:"
                         " checks this script's control flow and proves "
                         "nothing about the chip")
    args = ap.parse_args()
    rehearsal = args.cpu_rehearsal
    sizes = REHEARSAL if rehearsal else REAL
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    import jax
    import jaxlib

    # the environment may say cpu; the real run demands the chip in code,
    # before the backend starts. cpu comes second: the host ring peer's device
    jax.config.update("jax_platforms", "cpu" if rehearsal else "tpu,cpu")
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU: {e}")
    log(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu "
        f"{importlib.metadata.version('libtpu')}")
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if rehearsal:
        log("CPU REHEARSAL: tiny shapes, interpreted kernels. This proves "
            "nothing about the chip.")
        RESULT["rehearsal"] = True
    else:
        from pccl_tpu.benchmarks.model_bench import peak_tflops

        for d in devs:
            if d.platform != "tpu":
                sys.exit(f"chip_smoke: {d} is not a TPU")
            peak_tflops(d)               # raises on an unknown device_kind
    RESULT["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}

    from pccl_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    log(f"compile cache: {cache_dir}")
    cpu_dev = jax.devices("cpu")[0]
    t_start = time.perf_counter()
    master = None
    try:
        run_phase("native", phase_native)
        from pccl_tpu.comm import MasterNode

        master = MasterNode("127.0.0.1")     # its default port, 48501
        master.run()
        run_phase("fence", phase_fence, devs[0], sizes, rehearsal)
        run_phase("kernels", phase_kernels, devs[0], sizes, rehearsal)
        run_phase("hash", phase_hash, master.port, devs[0], cpu_dev, sizes)
        run_phase("trainer", phase_trainer, master.port, devs, cpu_dev, sizes,
                  compiles, rehearsal)
        RESULT["ok"] = True
    finally:
        if master is not None:
            master.interrupt()
            master.destroy()
        RESULT["compile"] = {"count": compiles.count,
                             "seconds": round(compiles.seconds, 1),
                             "cache_hits": compiles.cache_hits,
                             "cache_dir": cache_dir}
        RESULT["total_s"] = round(time.perf_counter() - t_start, 1)
        log(f"compiles: {compiles.count} in {compiles.seconds:.1f} s, "
            f"{compiles.cache_hits} persistent-cache hits")
        log("result: " + json.dumps(RESULT))
        log(verdict_line(RESULT))


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 — reported, then the process ends
        # a peer thread stuck in a collective must not keep a failed run
        # (and the chip) alive through interpreter shutdown
        traceback.print_exc()
        sys.stdout.flush()
        os._exit(1)
