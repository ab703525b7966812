"""DiLoCo end-to-end on loopback peers.

Reference parity: the sync/async DiLoCo example loops
(/root/reference/python/examples/nanogpt_diloco/) and the mnist_diloco
convergence e2e test (/root/reference/python/tests/end_to_end/)."""

import threading
import time
from pathlib import Path

import numpy as np
import pytest

LIB = Path(__file__).resolve().parent.parent / "pccl_tpu" / "native" / "build" / "libpcclt.so"
needs_native = pytest.mark.skipif(not LIB.exists(), reason="native lib not built")


def _toy_problem(seed):
    """Linear regression: fit w to y = X @ w_true, loss = mse."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    w_true = jnp.asarray(np.arange(8, dtype=np.float32))
    y = X @ w_true

    def loss_fn(params):
        pred = X @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    loss_jit = jax.jit(loss_fn)
    return loss_jit, grad_fn


def _inner_sgd(params, grad_fn, steps, lr=0.05):
    import jax

    for _ in range(steps):
        g = grad_fn(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    return params


def test_diloco_local_no_comm():
    """comm=None: outer step must still apply the update locally."""
    import jax.numpy as jnp

    from pccl_tpu.parallel.diloco import Diloco, DilocoConfig

    loss_jit, grad_fn = _toy_problem(0)
    params = {"w": jnp.zeros(8), "b": jnp.zeros(())}
    dl = Diloco(None, params, DilocoConfig(inner_steps=20, outer_lr=0.7))
    p = params
    l0 = float(loss_jit(p))
    for _ in range(5):
        p = _inner_sgd(p, grad_fn, 20)
        p = dl.outer_step(p)
    assert float(loss_jit(p)) < l0 * 0.05
    assert dl.step == 5


def test_async_diloco_sync_resets_baseline(monkeypatch):
    """sync_shared_state must join the in-flight reduce and invalidate the
    pseudo-gradient baseline — adopted params make the old baseline bogus."""
    import jax.numpy as jnp

    from pccl_tpu.parallel import diloco as dmod

    params = {"w": jnp.zeros(4)}
    dl = dmod.AsyncDiloco(None, params)
    dl.outer_step_async(params)          # sets _baseline, no comm → no-op reduce
    assert dl._baseline is not None
    monkeypatch.setattr(dmod.Diloco, "sync_shared_state",
                        lambda self, strategy=None: "info")
    assert dl.sync_shared_state() == "info"
    assert dl._baseline is None
    assert dl._inflight is None


@needs_native
@pytest.mark.parametrize("async_mode", [False, True])
def test_diloco_two_peers_converge(async_mode):
    import jax.numpy as jnp

    from pccl_tpu.comm import MasterNode
    from pccl_tpu.parallel.diloco import AsyncDiloco, Diloco, DilocoConfig

    master = MasterNode("0.0.0.0", 52000 if not async_mode else 52100)
    master.run()
    results = {}
    errors = []

    def peer(rank):
        try:
            from pccl_tpu.comm import Communicator

            base = (53000 if not async_mode else 53500) + rank * 16
            comm = Communicator("127.0.0.1", master.port, p2p_port=base,
                                ss_port=base + 4, bench_port=base + 8)
            comm.connect()
            deadline = time.time() + 30
            while comm.world_size < 2:
                if time.time() > deadline:
                    raise TimeoutError("world never reached 2")
                if comm.are_peers_pending():
                    comm.update_topology()
                time.sleep(0.01)

            loss_jit, grad_fn = _toy_problem(seed=100 + rank)  # different data shards
            params = {"w": jnp.zeros(8), "b": jnp.zeros(())}
            cls = AsyncDiloco if async_mode else Diloco
            # delayed gradients + heavy momentum oscillate on a quadratic, so
            # the async path trains with momentum off (the delay is the point
            # under test, not the momentum schedule)
            cfg = DilocoConfig(inner_steps=10, outer_lr=0.7,
                               outer_momentum=0.0 if async_mode else 0.9)
            dl = cls(comm, params, cfg)
            p = params
            for _ in range(16 if async_mode else 8):
                p = _inner_sgd(p, grad_fn, 10)
                p = (dl.outer_step_async(p) if async_mode else dl.outer_step(p))
            if async_mode:
                p = dl.finish()
            results[rank] = (np.asarray(p["w"]), float(loss_jit(p)))
            comm.destroy()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=peer, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    master.interrupt()
    master.destroy()
    assert not errors, f"peer failures: {errors}"
    w0, l0 = results[0]
    w1, l1 = results[1]
    # outer params must be bit-identical across peers (same averaged deltas)
    np.testing.assert_array_equal(w0, w1)
    # and close to the true solution despite different local shards
    np.testing.assert_allclose(w0, np.arange(8, dtype=np.float32), atol=0.5)


@needs_native
def test_diloco_shared_state_joiner_catchup():
    """A second peer joining late must adopt the first peer's outer state via
    sync_shared_state (reference: late-joiner flow, 03-AsyncDiloco.md)."""
    import jax.numpy as jnp

    from pccl_tpu.comm import Communicator, MasterNode, SharedStateSyncStrategy
    from pccl_tpu.parallel.diloco import Diloco, DilocoConfig

    master = MasterNode("0.0.0.0", 52200)
    master.run()
    errors = []
    adopted = {}
    barrier = threading.Barrier(2, timeout=60)

    def peer(rank):
        try:
            base = 54000 + rank * 16
            comm = Communicator("127.0.0.1", master.port, p2p_port=base,
                                ss_port=base + 4, bench_port=base + 8)
            comm.connect()
            deadline = time.time() + 30
            while comm.world_size < 2:
                if time.time() > deadline:
                    raise TimeoutError("world never reached 2")
                if comm.are_peers_pending():
                    comm.update_topology()
                time.sleep(0.01)

            params = {"w": jnp.zeros(8)}
            dl = Diloco(comm, params, DilocoConfig())
            if rank == 0:
                # advance rank 0's outer state locally before the sync
                dl.outer_params = {"w": jnp.full(8, 3.25)}
                dl.step = 4
            else:
                dl.step = 4  # same revision, stale content
            barrier.wait()
            dl.sync_shared_state(SharedStateSyncStrategy.SEND_ONLY if rank == 0
                                 else SharedStateSyncStrategy.RECEIVE_ONLY)
            adopted[rank] = np.asarray(dl.outer_params["w"])
            comm.destroy()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=peer, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    master.interrupt()
    master.destroy()
    assert not errors, f"peer failures: {errors}"
    np.testing.assert_array_equal(adopted[0], adopted[1])
    np.testing.assert_allclose(adopted[1], np.full(8, 3.25))


@needs_native
def test_diloco_pipelined_windowed_reduce():
    """comm_windows>1 + shm_staging takes the pipelined path (per-window
    D2H overlapped with per-window tagged reduces); the averaged result
    must be exact and bit-identical across peers."""
    import jax.numpy as jnp

    from pccl_tpu.comm import MasterNode
    from pccl_tpu.parallel.diloco import Diloco, DilocoConfig

    n = (2 << 20) + 321  # two windows and a ragged tail
    master = MasterNode("0.0.0.0", 52400)
    master.run()
    results = {}
    errors = []

    def peer(rank):
        try:
            from pccl_tpu.comm import Communicator

            base = 53800 + rank * 16
            comm = Communicator("127.0.0.1", master.port, p2p_port=base,
                                ss_port=base + 4, bench_port=base + 8)
            comm.connect()
            deadline = time.time() + 30
            while comm.world_size < 2:
                if time.time() > deadline:
                    raise TimeoutError("world never reached 2")
                if comm.are_peers_pending():
                    comm.update_topology()
                time.sleep(0.01)

            params = {"w": jnp.zeros((n,), jnp.float32)}
            cfg = DilocoConfig(outer_lr=1.0, outer_momentum=0.0,
                               nesterov=False, shm_staging=True,
                               comm_windows=2)
            dl = Diloco(comm, params, cfg)
            # pseudo-gradient = outer - inner = rank+1 everywhere
            inner = {"w": params["w"] - float(rank + 1)}
            out = dl.outer_step(inner)
            results[rank] = np.asarray(out["w"])
            comm.destroy()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=peer, args=(r,)) for r in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    master.interrupt()
    master.destroy()
    assert not errors, f"peer failures: {errors}"
    # avg pseudo-gradient = 1.5; lr=1, momentum 0 -> new = 0 - 1.5
    assert np.array_equal(results[0], results[1]), "bit parity across peers"
    np.testing.assert_allclose(results[0], -1.5, rtol=0)
