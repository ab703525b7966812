"""Ring attention — sequence parallelism over a mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §2.3: "no
TP/PP/SP/EP/CP/ring-attention anywhere in the reference"); on TPU it is a
first-class requirement, so it lives here as a core op, not an example.

Design (Liu et al., Ring Attention; implemented the XLA-collective way):
Q/K/V are sequence-sharded over mesh axis `sp`. Each step, every device
runs ONE per-shard attention of its resident Q block against the currently
held K/V block — the fused flash-attention pallas kernels on a mesh of TPUs
(forward and backward; no [Tl, Tl] tensor ever), the jnp twin on any other
mesh — and folds
the (out, log-sum-exp) pair into its accumulator, then rotates K/V one
hop around the ring with `lax.ppermute` — after sp_size steps every Q block
has seen every K/V block while K/V traffic only ever crosses neighboring
devices (rides ICI, never DCN). XLA's latency-hiding scheduler overlaps the
ppermute with the next step's kernel; peak per-device attention memory is
one kernel tile on TPU (O(T²/n²) dense logits on the jnp twin).

Causality uses GLOBAL positions (rank-offset iota), so the result is
bit-equivalent in exact arithmetic to dense causal attention over the full
sequence.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _shard_attn_with_lse(q, k, v, blk_causal: bool, on_tpu: bool):
    """Per-shard attention returning (out, lse [B, H, Tl]). On a mesh of
    TPUs: the fused pallas kernels (forward AND backward; no [Tl, Tl]
    tensor), which raise on a shard length they cannot tile. On any other
    mesh the kernels cannot compile at all, so the jnp twin runs."""
    from .flash_attention import (check_blocks, default_blocks,
                                  dense_attention_with_lse,
                                  flash_attention_with_lse)

    if not on_tpu:
        return dense_attention_with_lse(q, k, v, blk_causal)
    Tl = q.shape[1]
    bq, bk = default_blocks(Tl, q.shape[-1])
    check_blocks(Tl, bq, bk, interpret=False)
    return flash_attention_with_lse(q, k, v, blk_causal, bq, bk, False)


def _ring_attn_local(q, k, v, *, axis_name: str, causal: bool,
                     on_tpu: bool):
    """Per-device body under shard_map. q,k,v: [B, Tl, H, Dh] (local).

    The ring is UNROLLED over the (static) axis size: at step s the device
    holds the K/V block of rank (r − s) mod n, so under causal masking the
    visibility of the whole block is all-or-nothing — s == 0 is the
    diagonal (a causal per-shard call), s > 0 is fully visible iff r ≥ s.
    Each step is therefore ONE per-shard attention (the fused flash kernel
    on TPU) plus a log-sum-exp fold:

        lse' = logaddexp(lse, lse_s)
        o'   = o·exp(lse − lse') + o_s·exp(lse_s − lse')

    with an invisible step entering as lse_s = −inf (weight exactly 0).
    Step 0 runs first and is always visible, so the accumulator lse is
    finite from the first fold and no −inf − −inf NaN can arise.
    ppermute rotates K/V between steps; XLA's latency-hiding scheduler
    overlaps the rotation with the next step's kernel.

    Tradeoff of the unroll: HLO size and compile time grow linearly with
    the sp axis size (×2 with the backward) — negligible at sp ≤ 8, worth
    a scan over the uniform s > 0 steps (step 0 peeled) if sp worlds of
    dozens of devices become a target."""
    B, Tl, H, Dh = q.shape
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o = jnp.zeros((B, Tl, H, Dh), jnp.float32)
    lse = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    kb, vb = k, v
    for s in range(n):
        o_s, lse_s = _shard_attn_with_lse(q, kb, vb, causal and s == 0,
                                          on_tpu)
        if causal and s > 0:
            visible = r >= s                       # whole-block visibility
            lse_s = jnp.where(visible, lse_s, -jnp.inf)
        lse_new = jnp.logaddexp(lse, lse_s)
        w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        w_new = jnp.exp(lse_s - lse_new).transpose(0, 2, 1)[..., None]
        o = o * w_old + o_s.astype(jnp.float32) * w_new
        lse = lse_new
        if s != n - 1:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    return o.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   *, axis: str = "sp", batch_axis: Optional[str] = "dp",
                   causal: bool = True) -> jax.Array:
    """Sequence-parallel causal attention.

    q,k,v: [B, T, H, Dh] with T sharded over mesh axis `axis` and B
    (optionally) over `batch_axis`. Returns [B, T, H, Dh], same layout.
    Composes inside an outer jit."""
    ba = batch_axis if batch_axis and batch_axis in mesh.shape else None
    spec = P(ba, axis)
    # the mesh says where this runs; the default backend does not (a CPU
    # mesh on a TPU host must not be handed a Mosaic kernel)
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    # check_vma=False: pallas_call outputs carry no varying-mesh-axes
    # annotation, which the checker refuses inside a checked shard_map
    fn = jax.shard_map(
        partial(_ring_attn_local, axis_name=axis, causal=causal,
                on_tpu=on_tpu),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def make_ring_attn_fn(mesh: Mesh, axis: str = "sp",
                      batch_axis: Optional[str] = "dp"):
    """Adapter matching models.gpt's attn_fn signature (q, k, v) -> out."""
    def attn(q, k, v):
        return ring_attention(q, k, v, mesh, axis=axis, batch_axis=batch_axis,
                              causal=True)
    return attn
