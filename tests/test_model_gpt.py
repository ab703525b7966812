import jax
import jax.numpy as jnp
import numpy as np

from pccl_tpu.models import gpt


def test_forward_shapes():
    cfg = gpt.tiny_config()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = gpt.forward_jit(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_loss_decreases_one_sgd_step():
    cfg = gpt.tiny_config()
    params = gpt.init_params(jax.random.PRNGKey(1), cfg)
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    loss0, grads = jax.value_and_grad(gpt.loss_fn)(params, tokens, targets, cfg)
    params2 = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    loss1 = gpt.loss_fn(params2, tokens, targets, cfg)
    assert float(loss1) < float(loss0)


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = gpt.tiny_config()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jnp.zeros((1, 8), dtype=jnp.int32)
    t2 = t1.at[0, 7].set(3)
    l1 = gpt.forward(params, t1, cfg)
    l2 = gpt.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :7]), np.asarray(l2[0, :7]), atol=1e-5)


def test_graft_entry_and_dryrun(eight_devices):
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2
    ge.dryrun_multichip(8)


def test_named_configs():
    """Preset ladder: GPT-2 124M dims and MXU-padded vocab; overrides win."""
    c = gpt.named_config("gpt2")
    assert (c.n_layer, c.n_head, c.n_embd, c.block_size) == (12, 12, 768, 1024)
    assert c.vocab_size % 64 == 0  # padded for MXU-friendly embed matmuls
    c2 = gpt.named_config("gpt2", block_size=256, vocab_size=256)
    assert c2.block_size == 256 and c2.vocab_size == 256
    assert set(gpt.PRESETS) >= {"tiny", "gpt2", "gpt2-medium", "gpt2-large",
                                "gpt2-xl"}


def test_profiler_sections():
    from pccl_tpu.utils.profiler import Profiler

    prof = Profiler()
    with prof.section("a"):
        with prof.section("b"):
            pass
    with prof.section("a"):
        pass
    stats = prof.stats()
    assert stats["a"].count == 2 and stats["b"].count == 1
    table = prof.summary()
    assert "a" in table and "mean_ms" in table
    import json as _json
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".json", mode="r") as f:
        prof.export_chrome_trace(f.name)
        trace = _json.load(open(f.name))
    # three sections ("X" complete events) beside the process_name record
    assert [e["ph"] for e in trace["traceEvents"]].count("X") == 3
    prof.reset()
    assert prof.stats() == {}


def test_remat_modes_match_no_remat():
    """Both checkpointing modes (full remat, "dots" policy) are pure
    memory/recompute trades — loss AND grads must match the stash-everything
    path (same ops, re-executed; CPU fp is deterministic)."""
    import jax
    import numpy as np

    from pccl_tpu.models import gpt

    # n_layer=4: "sqrt" groups as G=2 — L=2 would degenerate to G=1 and
    # silently skip the grouped two-level path this test must cover
    cfg = gpt.tiny_config(n_layer=4)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.block_size), 0,
                             cfg.vocab_size)

    def lg(remat):
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tok, tok, cfg, None, remat)))(params)

    l0, g0 = lg(False)
    for mode in (True, "dots", "sqrt"):
        l1, g1 = lg(mode)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g0, g1)


def test_chunked_ce_matches_full():
    """loss_chunk is a pure memory/recompute trade: the chunked
    (scan + checkpoint) CE must match the full-logits path in loss AND
    grads for both the tied and untied head (same matmuls re-executed;
    CPU fp is deterministic up to reduction order, hence the tolerances)."""
    import jax
    import numpy as np

    from pccl_tpu.models import gpt

    for untie in (False, True):
        cfg = gpt.tiny_config(untie_head=untie)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, cfg.block_size),
                                 0, cfg.vocab_size)

        def lg(chunk):
            return jax.jit(jax.value_and_grad(
                lambda p: gpt.loss_fn(p, tok, tok, cfg, None, False,
                                      chunk)))(params)

        l0, g0 = lg(None)
        l1, g1 = lg(cfg.block_size // 4)
        np.testing.assert_allclose(float(l1), float(l0), rtol=2e-5)
        # non-head leaves come out bit-identical; the head grad differs by
        # bf16 accumulation order (chunked partial sums vs one big matmul),
        # measured maxabs ~1e-4 on grads of magnitude ~0.03
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=5e-4), g0, g1)


def test_loss_chunk_must_divide():
    """A non-dividing loss_chunk raises immediately — a silent fall-back to
    the full-logits path would resurface as an opaque multi-GB OOM in
    exactly the configs the flag exists to rescue."""
    import jax
    import pytest

    from pccl_tpu.models import gpt

    cfg = gpt.tiny_config()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.block_size), 0,
                             cfg.vocab_size)
    with pytest.raises(ValueError, match="must divide"):
        gpt.loss_fn(params, tok, tok, cfg, None, False, 100)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=A over [A, B, T] must match one step over [A·B, T]:
    CE is a per-sequence mean, so the average of A microbatch means (and
    grads) equals the full-batch mean exactly — same updated params, same
    loss, up to fp32 reduction order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pccl_tpu.models import gpt
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib

    import optax

    cfg = gpt.tiny_config()
    mesh = mesh_lib.make_mesh(jax.devices()[:2], ("dp", "tp"))
    tok = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (4, cfg.block_size), 0, cfg.vocab_size))

    def run(accum):
        params, _, _ = train_lib.make_train_state(
            jax.random.PRNGKey(0), cfg, mesh)
        # plain SGD(1.0): new_params − old_params == −grads, so the
        # comparison is of the accumulated GRADIENTS themselves (AdamW's
        # m/√v would sign-normalize noise-level grads and amplify bf16
        # reduction-order dust into lr-scale diffs)
        tx = optax.sgd(1.0)
        opt = tx.init(params)
        step = train_lib.build_train_step(cfg, tx, mesh, accum_steps=accum)
        t = jnp.asarray(tok.reshape(2, 2, -1) if accum > 1 else tok)
        return step(params, opt, t, t)

    p1, _, l1 = run(1)
    p2, _, l2 = run(2)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-2, atol=5e-5), p1, p2)
