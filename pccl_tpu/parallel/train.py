"""Sharded training-step construction for the model families.

Builds a jitted SPMD train step over a Mesh: parameters laid out by the
tensor-parallel rules in mesh.py (dispatched on the config's family — GPT or
Llama), batch sharded over dp, optimizer = AdamW (optax). Gradients reduce
over dp implicitly through XLA's SPMD partitioner — inside a slice this rides
ICI; across slices the DiLoCo outer loop (pccl_tpu/parallel/diloco.py) moves
pseudo-gradients over the CCoIP-style ring.

Reference parity: this replaces the torch training loops in
/root/reference/python/examples/ (train_pccl.py, sync_diloco.py) as the
in-slice compute engine.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import gpt, llama
from . import mesh as mesh_lib


def family(cfg):
    """(model module, param-sharding builder) for a config's family — the
    public dispatch examples and user loops should use."""
    if isinstance(cfg, llama.LlamaConfig):
        return llama, mesh_lib.llama_param_sharding
    return gpt, mesh_lib.gpt_param_sharding


def make_train_state(key, cfg, mesh, lr: float = 3e-4, schedule=None):
    """Init params + AdamW optimizer state, placed with TP/DP shardings.

    schedule: optional optax schedule (steps -> lr) used INSTEAD of the
    constant `lr` — e.g. cosine_warmup_schedule below (the reference
    loops' warmup + cosine decay, sync_diloco_fsdp.py:get_lr)."""
    model, sharding_fn = family(cfg)
    param_sharding = sharding_fn(mesh, cfg)
    init = jax.jit(model.init_params, static_argnames=("cfg",),
                   out_shardings=param_sharding)
    params = init(key, cfg)
    tx = optax.adamw(schedule if schedule is not None else lr,
                     b1=0.9, b2=0.95, weight_decay=0.1)
    # tx.init's zeros depend on no input, so with out_shardings left open
    # they come back UNCOMMITTED on the default device: 8 B/param parked on
    # device 0 whatever `mesh` is, and a second compile of the train step
    # once its outputs return committed. Every params-shaped subtree of the
    # state (the moments) takes the params' shardings; the rest (step
    # counts) is replicated over the mesh.
    params_def = jax.tree.structure(params)

    def params_like(node):
        return jax.tree.structure(node) == params_def

    opt_sharding = jax.tree.map(
        lambda node: param_sharding if params_like(node)
        else mesh_lib.replicated(mesh),
        jax.eval_shape(tx.init, params), is_leaf=params_like)
    opt_state = jax.jit(tx.init, out_shardings=opt_sharding)(params)
    return params, tx, opt_state


def cosine_warmup_schedule(lr: float, total_steps: int,
                           warmup_steps: int = 0, min_lr: float = 0.0):
    """The reference loops' LR policy (linear warmup -> cosine decay to
    min_lr; /root/reference/python/examples/nanogpt_diloco/
    sync_diloco_fsdp.py:get_lr), as an optax schedule usable by
    make_train_state(schedule=...) — the schedule runs INSIDE the jitted
    step off the optimizer's step count, no host-side LR pokes."""
    warmup_steps = max(0, warmup_steps)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0 if warmup_steps else lr, peak_value=lr,
        warmup_steps=warmup_steps,
        # optax requires decay_steps > warmup_steps (the cosine part must
        # be non-empty) — warmup >= total collapses to warmup-then-min_lr
        decay_steps=max(warmup_steps + 1, total_steps), end_value=min_lr)


def accum_value_and_grad(base_lg, accum_steps: int):
    """Wrap a (params, tokens, targets) -> (loss, grads) function with
    scan-based microbatch accumulation: the wrapped function takes
    [A, B, T] tokens/targets, runs one microbatch's activations at a time
    under `lax.scan`, and accumulates grads in an fp32 tree. Loss and
    grads are the exact mean over all A·B sequences (CE is a per-sequence
    mean, so averaging A microbatch means equals the full-batch mean).
    Shapes are static under jit, so a data pipeline whose leading axis
    disagrees with `accum_steps` fails LOUDLY at trace time instead of
    silently mis-scaling gradients."""

    def fn(params, tokens, targets):
        a = tokens.shape[0]
        assert a == accum_steps, (
            f"got {a} microbatches, step was built for accum_steps="
            f"{accum_steps}")

        def micro(carry, tt):
            loss_sum, grad_acc = carry
            loss, grads = base_lg(params, tt[0], tt[1])
            grad_acc = jax.tree.map(
                lambda acc, g: acc + g.astype(jnp.float32), grad_acc, grads)
            return (loss_sum + loss, grad_acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, grads), _ = jax.lax.scan(
            micro, (jnp.zeros((), jnp.float32), zeros), (tokens, targets))
        return loss_sum / a, jax.tree.map(lambda g: g / a, grads)

    return fn


def build_train_step(cfg, tx, mesh, attn_fn=None,
                     seq_axis: str | None = None, remat: "bool | str" = False,
                     loss_chunk: "int | None" = None,
                     accum_steps: int = 1):
    """Returns jitted (params, opt_state, tokens, targets) -> (params, opt_state, loss).

    attn_fn: optional attention override (e.g. ring attention for sequence
    parallelism over `seq_axis`). remat: per-block activation checkpointing
    (models/_common.py:maybe_checkpoint) — True trades ~1/3 more FLOPs for
    O(1-layer) activation memory, the standard fit-big-batches move on a
    16 GB chip; "dots" saves weight-matmul outputs and recomputes only the
    rest (less recompute, more memory than True). loss_chunk: compute the
    vocab matmul + CE in recompute-checkpointed sequence chunks so the
    full [B, T, vocab] logits never exist (the T ≥ 32k memory enabler;
    models/_common.py:chunked_ce_loss).

    accum_steps: gradient accumulation (reference parity: the torch loops'
    gradient_accumulation_steps, e.g. sync_diloco_fsdp.py). With A > 1 the
    step takes tokens/targets shaped [A, B, T] — an EXPLICIT leading
    microbatch axis, scanned with `lax.scan` so one microbatch's
    activations are live at a time while per-microbatch grads accumulate
    in an fp32 tree; batch sharding applies to the B axis. Loss and grads
    are the exact mean over all A·B sequences (CE is a per-sequence mean,
    so averaging A microbatch means equals the full-batch mean — grads
    match a single [A·B, T] step bitwise up to reduction order)."""
    model, sharding_fn = family(cfg)
    param_sharding = sharding_fn(mesh, cfg)
    data_sharding = mesh_lib.batch_sharding(mesh, seq_axis=seq_axis)
    if accum_steps > 1:
        # [A, B, T]: microbatch axis unsharded, batch over dp as usual
        spec = data_sharding.spec
        data_sharding = NamedSharding(mesh, P(None, *spec))

    base_lg = jax.value_and_grad(
        lambda p, tok, tgt: model.loss_fn(p, tok, tgt, cfg, attn_fn, remat,
                                          loss_chunk))
    lg = accum_value_and_grad(base_lg, accum_steps) if accum_steps > 1 \
        else base_lg

    def step(params, opt_state, tokens, targets):
        loss, grads = lg(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(
        step,
        in_shardings=(param_sharding, None, data_sharding, data_sharding),
        out_shardings=(param_sharding, None, None),
        donate_argnums=(0, 1),
    )
