"""Ring attention and flash attention parity vs the dense reference."""

import numpy as np


def _qkv(B=2, T=64, H=4, Dh=16, seed=0):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, Dh)
    import jax.numpy as jnp

    q = jax.random.normal(ks[0], shape, jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    return q, k, v


def test_flash_interpret_matches_reference():
    from pccl_tpu.ops import flash_attention, reference_attention

    q, k, v = _qkv(T=128)
    ref = reference_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal_interpret():
    from pccl_tpu.ops import flash_attention, reference_attention

    q, k, v = _qkv(T=64)
    ref = reference_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grad_matches_reference():
    """flash_attention must be differentiable (training-path attn_fn)."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops import flash_attention, reference_attention

    q, k, v = _qkv(T=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_matches_dense(eight_devices):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pccl_tpu.ops import reference_attention, ring_attention
    from pccl_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(eight_devices, axis_names=("dp", "sp"),
                              shape=(2, 4))
    q, k, v = _qkv(B=4, T=64, H=4, Dh=16)
    ref = reference_attention(q, k, v)
    sh = NamedSharding(mesh, P("dp", "sp"))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grad_flows(eight_devices):
    """Ring attention must be differentiable (training path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pccl_tpu.ops import reference_attention, ring_attention
    from pccl_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(eight_devices[:4], axis_names=("sp",), shape=(4,))
    q, k, v = _qkv(B=2, T=32, H=2, Dh=8)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, batch_axis=None) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring))(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_gpt_forward_with_ring_attention(eight_devices):
    """Full model forward under sequence parallelism matches dense."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pccl_tpu.models import gpt
    from pccl_tpu.ops.ring_attention import make_ring_attn_fn
    from pccl_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(eight_devices[:4], axis_names=("sp",), shape=(4,))
    cfg = gpt.tiny_config(block_size=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)

    dense = gpt.forward(params, tokens, cfg)
    tok_sp = jax.device_put(tokens, NamedSharding(mesh, P(None, "sp")))
    ringed = jax.jit(lambda p, t: gpt.forward(
        p, t, cfg, attn_fn=make_ring_attn_fn(mesh, batch_axis=None)))(params, tok_sp)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)  # bf16 compute


def test_llama_forward_with_ring_attention(eight_devices):
    """Llama's GQA must compose with the attn_fn override: kv heads are
    repeated to the full head count on device BEFORE the attention op
    (models/llama.py:_block), so ring attention sees ordinary multi-head
    inputs and sequence parallelism works unchanged for the second family."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pccl_tpu.models import llama
    from pccl_tpu.ops.ring_attention import make_ring_attn_fn
    from pccl_tpu.parallel import mesh as mesh_lib

    import jax.numpy as jnp

    mesh = mesh_lib.make_mesh(eight_devices[:4], axis_names=("sp",), shape=(4,))
    # fp32 compute: the test checks GQA/ring COMPOSITION, and SwiGLU's
    # multiplicative gating amplifies bf16 attention rounding past any
    # meaningful tolerance (observed 0.05 on logits for an exact ring)
    cfg = llama.tiny_config(block_size=64, compute_dtype=jnp.float32)
    assert cfg.n_kv_head != cfg.n_head   # n_kv_head=2 < n_head=4: real GQA
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)

    dense = llama.forward(params, tokens, cfg)
    tok_sp = jax.device_put(tokens, NamedSharding(mesh, P(None, "sp")))
    ringed = jax.jit(lambda p, t: llama.forward(
        p, t, cfg, attn_fn=make_ring_attn_fn(mesh, batch_axis=None)))(
            params, tok_sp)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_flash_grad_noncausal_and_asym_blocks():
    """Regression cover for the fused backward's untested corners: the
    non-causal branch and block_q != block_k (exercises the dkv kernel's
    diagonal start-block arithmetic j0 = ki*block_k // block_q)."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops.flash_attention import _flash_diff, reference_attention

    q, k, v = _qkv(B=1, T=128, H=2, Dh=16)

    for causal, bq, bk in ((False, 32, 32), (True, 16, 64), (True, 64, 16)):
        def loss_f(q, k, v):
            return jnp.sum(_flash_diff(q, k, v, causal, bq, bk, True) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4), (causal, bq, bk)


def test_flash_with_lse_pair_grads():
    """flash_attention_with_lse returns a DIFFERENTIABLE (out, lse) pair —
    the form ring attention folds per shard. The backward folds the lse
    cotangent into delta (ds = p*(dp - (delta - dlse))), so a loss that
    touches BOTH outputs must match the jnp twin exactly."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops.flash_attention import (dense_attention_with_lse,
                                              flash_attention_with_lse)

    q, k, v = _qkv(B=2, T=64, H=2, Dh=16)

    for causal in (True, False):
        of, lf = flash_attention_with_lse(q, k, v, causal, 32, 32, True)
        od, ld = dense_attention_with_lse(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(of), np.asarray(od),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                                   rtol=1e-5, atol=1e-5)

        def loss_f(q, k, v):
            o, l = flash_attention_with_lse(q, k, v, causal, 32, 32, True)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))  # both outputs live

        def loss_d(q, k, v):
            o, l = dense_attention_with_lse(q, k, v, causal)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def _gqa_qkv(B=2, T=128, H=8, Hkv=2, Dh=16, seed=3):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, Dh), jnp.float32)
    return q, k, v


def test_flash_gqa_matches_repeated_dense():
    """GQA-native kernels (Hkv-shaped K/V, head mapping in the BlockSpec
    index maps — no jnp.repeat anywhere on the kernel path) must match
    dense attention over explicitly repeated K/V, forward and backward.
    VERDICT r4 ask #2: llama's K/V repeat erased the architecture's
    KV-bytes advantage."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops.flash_attention import _flash_diff, reference_attention

    q, k, v = _gqa_qkv()
    G = q.shape[2] // k.shape[2]
    krep = jnp.repeat(k, G, axis=2)
    vrep = jnp.repeat(v, G, axis=2)

    for causal in (True, False):
        out = _flash_diff(q, k, v, causal, 32, 32, True)
        ref = reference_attention(q, krep, vrep, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def loss_f(q, k, v):
        return jnp.sum(_flash_diff(q, k, v, True, 32, 32, True) ** 2)

    def loss_r(q, k, v):
        out = reference_attention(q, jnp.repeat(k, G, axis=2),
                                  jnp.repeat(v, G, axis=2))
        return jnp.sum(out ** 2)

    # autodiff through loss_r's jnp.repeat already folds the G copies, so
    # both sides produce the native Hkv-shaped dk/dv
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_gqa_with_lse_pair():
    """The (out, lse) pair path (ring attention's per-shard form) with
    GQA-shaped K/V: values and both-output grads match the jnp twin."""
    import jax
    import jax.numpy as jnp

    from pccl_tpu.ops.flash_attention import (dense_attention_with_lse,
                                              flash_attention_with_lse)

    q, k, v = _gqa_qkv(B=1, T=64, H=4, Hkv=2)

    of, lf = flash_attention_with_lse(q, k, v, True, 32, 32, True)
    od, ld = dense_attention_with_lse(q, k, v, True)
    np.testing.assert_allclose(np.asarray(of), np.asarray(od),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=1e-5, atol=1e-5)

    def loss_f(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, True, 32, 32, True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))

    def loss_d(q, k, v):
        o, l = dense_attention_with_lse(q, k, v, True)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_has_no_silent_dense_path():
    """flash_attention IS the kernel: a backend it cannot compile for, a T
    its blocks do not divide and (compiled) a block that is not a whole
    number of 128-lane tiles all raise — none returns the dense reference."""
    import pytest

    from pccl_tpu.ops import flash_attention
    from pccl_tpu.ops.flash_attention import check_blocks, default_blocks

    q, k, v = _qkv(T=128)
    with pytest.raises(ValueError, match="compiles for TPU only"):
        flash_attention(q, k, v)                      # CPU backend, compiled
    q, k, v = _qkv(T=96)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    # what a TPU backend would be asked to compile
    for T in (16, 64, 320):
        with pytest.raises(ValueError, match="128-lane tile"):
            check_blocks(T, *default_blocks(T, 64), interpret=False)
    for T in (128, 384, 1024, 1280, 32768):
        check_blocks(T, *default_blocks(T, 64), interpret=False)
