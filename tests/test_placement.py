"""Several peers of one process each own a chip, so whatever a driver owns must
stay on the device its template is committed to — never the default device.
Checked on the 8-virtual-device CPU mesh with a template on device 5."""

from types import SimpleNamespace

import numpy as np
import pytest


class _StubComm:
    """Alone in the ring (a reduce is the identity); every shared-state sync
    is lost to another peer, so each entry comes back overwritten."""

    def all_reduce(self, send, recv=None, **_):
        if recv is not None and recv is not send:
            np.copyto(recv, send)
        return SimpleNamespace(tx_bytes=0, rx_bytes=0, world_size=1)

    def sync_shared_state(self, state, strategy):
        for ti in state.infos:
            ti.data[...] = ti.data + 1
        return SimpleNamespace(tx_bytes=0, revision=state.revision,
                               rx_bytes=sum(ti.data.nbytes
                                            for ti in state.infos))


def _assert_on(dev, *trees):
    import jax

    for leaf in jax.tree.leaves(trees):
        assert leaf.committed and leaf.devices() == {dev}, \
            (leaf.shape, leaf.devices())


def _template(dev):
    import jax
    import jax.numpy as jnp

    assert dev != jax.devices()[0]
    return jax.device_put({"w": jnp.arange(8, dtype=jnp.float32),
                           "b": jnp.ones((2, 3), jnp.bfloat16)}, dev)


@pytest.mark.parametrize("async_mode", [False, True])
def test_diloco_keeps_its_arrays_on_the_template_device(eight_devices,
                                                        async_mode):
    import jax

    from pccl_tpu.parallel.diloco import AsyncDiloco, Diloco

    dev = eight_devices[5]
    params = _template(dev)
    dl = (AsyncDiloco if async_mode else Diloco)(None, params)

    def owned():
        return dl._outer_vec, dl._momentum_vec

    def inner():
        return jax.tree.map(lambda p: p - 1, dl.params())

    _assert_on(dev, owned(), dl.params())
    _assert_on(dev, dl.outer_step(inner()), owned())
    dl.comm = _StubComm()
    w_before = np.asarray(dl.params()["w"])
    assert dl.sync_shared_state().rx_bytes > 0
    np.testing.assert_array_equal(np.asarray(dl.params()["w"]), w_before + 1)
    # the vector the sync replaced must not stay on the device
    assert dl._applied is None
    _assert_on(dev, owned(), dl.params())
    _assert_on(dev, dl.outer_step(inner()), owned())
    if async_mode:
        _assert_on(dev, dl.outer_step_async(inner()))
        _assert_on(dev, dl.finish(), owned())


def test_hierarchical_all_reduce_returns_to_the_template_device(eight_devices):
    from pccl_tpu.parallel.hierarchical import HierarchicalAllReduce

    dev = eight_devices[5]
    tree = _template(dev)
    for comm in (None, _StubComm()):
        out = HierarchicalAllReduce(comm, tree).all_reduce(tree)
        _assert_on(dev, out)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))


def test_train_state_lives_on_its_mesh(eight_devices):
    """make_train_state: the optimizer state is committed to the mesh like
    the params (its zeros depend on no input, so left to jax they land
    uncommitted on the default device), and the step then compiles once."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.models import gpt
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib

    dev = eight_devices[5]
    mesh = mesh_lib.make_mesh([dev], shape=(1, 1))
    cfg = gpt.tiny_config(n_layer=1, block_size=16)
    params, tx, opt_state = train_lib.make_train_state(
        jax.random.PRNGKey(0), cfg, mesh)
    _assert_on(dev, params, opt_state)
    step = train_lib.build_train_step(cfg, tx, mesh)
    tok = jax.device_put(jnp.zeros((2, 16), jnp.int32),
                         mesh_lib.batch_sharding(mesh))
    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tok, tok)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    _assert_on(dev, params, opt_state, loss)
    assert len(compiles) == 1, compiles
