"""Flash attention — fused causal attention pallas kernels for one TPU core.

The single-chip hot op under the flagship model (the reference has no model
compute at all — its examples lean on torch SDPA; here the TPU-native
equivalent is a pallas kernel feeding the MXU).

Layout (k-blocked, round 5): the grid streams K/V through VMEM in
`block_k` tiles — K/V are grid dimensions, not full-T VMEM residents, so
VMEM per step is O(block) and the kernels reach T=16384/32768 where the
round-4 full-T layout tripped the ~16 MB scoped-VMEM limit. The forward
grid is (B·H, q blocks, k blocks) with the online-softmax state (running
max m, normalizer l, output accumulator) carried across the innermost k
dimension in fp32 VMEM scratch; TPU pallas executes the grid sequentially,
so the carry is exact. Causal skipping is zero-FLOP: k blocks strictly
above the diagonal run no gemms (`pl.when`), and their BlockSpec index is
clamped to the last visible block so the pipeline re-uses the resident
tile instead of fetching dead bytes.

Logits never materialize beyond a [block_q, block_k] tile in EITHER
direction: the backward is a fused FlashAttention-2-style pair of kernels
(dq, then dk/dv) that rebuild p = exp(s − lse) from the forward's saved
log-sum-exp, so long-context training never touches a [T, T] tensor. All
gemms run with bf16 operands and fp32 accumulation on the MXU.

GQA is native (round 5): K/V may carry fewer heads than Q
(n_kv_head = H / G). The kernels never repeat K/V — the q-head grid index
maps onto its kv head inside the BlockSpec index maps (kv row = i // G for
the forward/dq grids), and the dk/dv kernel accumulates the G q-heads
sharing a kv head in scratch over an extra grid dimension. HBM holds and
moves only Hkv-shaped K/V, which is the entire point of the architecture
(the reference never faces this: its CUDA examples use torch SDPA,
/root/reference/python/examples; grouped-query K/V shrinkage is a
TPU-side design goal, not a port).

`flash_attention` IS the kernel: it compiles for a TPU backend, runs under
the pallas interpreter with `interpret=True` (kernel-logic tests anywhere),
and raises on a backend or a shape it cannot take. The dense jnp twin is
`reference_attention` / `dense_attention_with_lse`; a caller that wants it
calls it by name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width of the VPU: the online-softmax running stats (m, l) live in
# VMEM scratch replicated across this many lanes so every update is a
# full-width vector op instead of a sub-tile.
_LANES = 128


def reference_attention(q, k, v, causal: bool = True):
    """Dense jnp causal attention; q: [B, T, H, Dh], k/v: [B, T, Hkv, Dh]
    (Hkv may divide H — GQA). One source of truth with ring attention's
    dense twin: softmax == exp(logits − lse)."""
    return dense_attention_with_lse(q, k, v, causal)[0]


def _causal_mask(s, row0, col0, bq: int, bk: int):
    """Mask entries of the [bq, bk] score tile whose absolute column index
    exceeds its row index. Shared by the forward and BOTH backward kernels
    so masking semantics can never desynchronize between directions."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(cols <= rows, s, -1e30)


def _causal_nk(qi, nk, block_q: int, block_k: int):
    """Last k block (exclusive) any row of q block `qi` may attend to."""
    return jnp.minimum(nk, ((qi + 1) * block_q + block_k - 1) // block_k)


def _causal_j0(ki, block_q: int, block_k: int):
    """First q block (inclusive) that can see any column of k block `ki`."""
    return (ki * block_k) // block_q


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                      *, block_q: int, block_k: int, causal: bool,
                      scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    nk_eff = _causal_nk(qi, nk, block_q, block_k) if causal else nk

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(ki < nk_eff)
    def _step():
        # the matmuls stay in the input dtype (bf16) with fp32
        # ACCUMULATION — fp32 operands would run the MXU at a fraction of
        # its rate, and at long T the QK^T/PV gemms are the whole kernel
        q = q_ref[0]                                 # [bq, Dh]
        k = k_ref[0]                                 # [bk, Dh]
        v = v_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, block_q, block_k)
        m_prev = m_sc[...]                           # [bq, LANES] f32
        l_prev = l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])                # f32 [bq, bk]
        m_sc[...] = m_new
        l_sc[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, :1] + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_sc[:, :1]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        # log-sum-exp per row: everything the backward needs to rebuild p
        # from scratch (p = exp(s - lse)) without storing any [T, T]
        # tensor. lse rides as [BH, 1, T] (full-T row block — tiny: T·4
        # bytes) because TPU lowering wants the last two block dims
        # (8, 128)-divisible or equal to the array's.
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = \
            m_sc[:, 0] + jnp.log(l_sc[:, 0])


def _kv_index(i, G: int):
    """Row of the [B·Hkv, T, Dh] K/V array feeding q-head row `i` of
    [B·H, ...]: with q head h sharing kv head h // G and i = b·H + h,
    (b·H + h) // G = b·Hkv + h // G exactly (H = G·Hkv)."""
    return i // G if G > 1 else i


def _make_kv_map(nk: int, G: int, block_q: int, block_k: int, causal: bool):
    """BlockSpec index map for K/V on the (BH, q blocks, k blocks) grids
    (forward and dq backward — ONE definition so their fetch behavior can
    never desynchronize). Causal k indices above the diagonal clamp to the
    last visible block: the pipeline sees an unchanged index and skips the
    fetch, so dead tiles cost no HBM bandwidth."""
    def kv_map(i, qi, ki):
        kj = jnp.minimum(ki, _causal_nk(qi, nk, block_q, block_k) - 1) \
            if causal else ki
        return (_kv_index(i, G), kj, 0)
    return kv_map


def _flash_bhtd(qt, kt, vt, *, block_q: int, block_k: int, causal: bool,
                interpret: bool):
    """qt: [BH, T, Dh]; kt/vt: [BKV, T, Dh], BKV dividing BH (GQA) →
    ([BH, T, Dh] out, [BH, T] f32 lse). K/V stream through VMEM in
    block_k tiles (grid dim 2); softmax state carries in VMEM scratch."""
    BH, T, Dh = qt.shape
    G = BH // kt.shape[0]
    scale = 1.0 / math.sqrt(Dh)
    nk = T // block_k
    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale)
    kv_map = _make_kv_map(nk, G, block_q, block_k, causal)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((BH, T, Dh), qt.dtype),
                   jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)),
        grid=(BH, T // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda i, j, s: (i, j, 0)),
            pl.BlockSpec((1, block_k, Dh), kv_map),
            pl.BlockSpec((1, block_k, Dh), kv_map),
        ],
        out_specs=(pl.BlockSpec((1, block_q, Dh), lambda i, j, s: (i, j, 0)),
                   pl.BlockSpec((1, 1, T), lambda i, j, s: (i, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # normalizer l
            pltpu.VMEM((block_q, Dh), jnp.float32),       # output accum
        ],
        interpret=interpret,
    )(qt, kt, vt)


# --- fused backward (FlashAttention-2 shape): two kernels, no [T, T]
# tensor ever materialized. dq: grid (BH, q blocks, k blocks), dq carried
# in scratch across the k dim. dk/dv: grid (BKV, k blocks, G, q blocks),
# dk/dv carried in scratch across the (g, q) dims — the G q-heads sharing
# a kv head accumulate into ONE Hkv-shaped gradient without any repeated
# K/V or G×-sized temporaries. Both rebuild p = exp(s − lse) from the
# saved log-sum-exp and use delta = rowsum(do · o) for the softmax
# jacobian:   ds = p ⊙ (do·vᵀ − delta) · scale
# All gemms run in the input dtype on the MXU with fp32 accumulation.

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_sc, *, block_q: int, block_k: int,
                         causal: bool, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    nk_eff = _causal_nk(qi, nk, block_q, block_k) if causal else nk

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(ki < nk_eff)
    def _step():
        q = q_ref[0]                                 # [bq, Dh]
        do = do_ref[0]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]    # [bq] f32
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        k = k_ref[0]                                 # [bk, Dh]
        v = v_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        dq_sc[...] = dq_sc[...] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_sc, dv_sc, *, block_q: int,
                          block_k: int, causal: bool, scale: float):
    ki, g, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    j0 = _causal_j0(ki, block_q, block_k) if causal else 0

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(qi >= j0)
    def _step():
        k = k_ref[0]                                 # [bk, Dh]
        v = v_ref[0]
        q = q_ref[0]                                 # [bq, Dh]
        do = do_ref[0]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi * block_q, ki * block_k, block_q, block_k)
        p = jnp.exp(s - lse[:, None])                # [bq, bk] f32
        pt = p.astype(do.dtype)
        dv_sc[...] = dv_sc[...] + lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_sc[...] = dk_sc[...] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((g == pl.num_programs(2) - 1) & (qi == nq - 1))
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_bhtd(qt, kt, vt, ot, do, lse, *, block_q: int, block_k: int,
                    causal: bool, interpret: bool, n_kv_head: int = 0,
                    delta_override=None):
    """Fused backward; qt/ot/do: [BH, T, Dh], kt/vt: [BKV, T, Dh] →
    (dq [BH..], dk [BKV..], dv [BKV..]).

    n_kv_head: Hkv (needed to invert i_kv → q-head rows in the dkv grid;
    0 means MHA, BKV == BH). delta_override: callers differentiating an
    (out, lse) PAIR pass delta − dlse here (flash_attention_with_lse's
    backward)."""
    BH, T, Dh = qt.shape
    BKV = kt.shape[0]
    G = BH // BKV
    Hkv = n_kv_head if n_kv_head else BKV            # MHA: any split works
    H = Hkv * G
    scale = 1.0 / math.sqrt(Dh)
    nq, nk = T // block_q, T // block_k
    if delta_override is None:
        delta = jnp.sum(do.astype(jnp.float32) * ot.astype(jnp.float32),
                        axis=-1)[:, None, :]         # [BH, 1, T]
    else:
        delta = delta_override
    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  scale=scale)
    row3 = lambda i, j, s: (i, j, 0)  # noqa: E731
    vec3 = pl.BlockSpec((1, 1, T), lambda i, j, s: (i, 0, 0))
    kv_map3 = _make_kv_map(nk, G, block_q, block_k, causal)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dh), qt.dtype),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), row3),      # q
            pl.BlockSpec((1, block_k, Dh), kv_map3),   # k
            pl.BlockSpec((1, block_k, Dh), kv_map3),   # v
            pl.BlockSpec((1, block_q, Dh), row3),      # do
            vec3,                                      # lse
            vec3,                                      # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), row3),
        scratch_shapes=[pltpu.VMEM((block_q, Dh), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)

    # dk/dv grid: (BKV, k blocks, G, q blocks) — q innermost so the
    # scratch carry sweeps all (g, q) pairs of one kv-head k block before
    # the output tile flushes. Under causality q blocks strictly above
    # the diagonal are zero-FLOP and their fetch index clamps to j0.
    def q_row(i_kv, ki, g, qi):
        qj = jnp.maximum(qi, _causal_j0(ki, block_q, block_k)) \
            if causal else qi
        return ((i_kv // Hkv) * H + (i_kv % Hkv) * G + g, qj, 0)

    def q_vec(i_kv, ki, g, qi):
        return ((i_kv // Hkv) * H + (i_kv % Hkv) * G + g, 0, 0)

    kv_row = lambda i_kv, ki, g, qi: (i_kv, ki, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        out_shape=(jax.ShapeDtypeStruct((BKV, T, Dh), kt.dtype),
                   jax.ShapeDtypeStruct((BKV, T, Dh), vt.dtype)),
        grid=(BKV, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), q_row),     # q
            pl.BlockSpec((1, block_k, Dh), kv_row),    # k
            pl.BlockSpec((1, block_k, Dh), kv_row),    # v
            pl.BlockSpec((1, block_q, Dh), q_row),     # do
            pl.BlockSpec((1, 1, T), q_vec),            # lse
            pl.BlockSpec((1, 1, T), q_vec),            # delta
        ],
        out_specs=(pl.BlockSpec((1, block_k, Dh), kv_row),
                   pl.BlockSpec((1, block_k, Dh), kv_row)),
        scratch_shapes=[pltpu.VMEM((block_k, Dh), jnp.float32),
                        pltpu.VMEM((block_k, Dh), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)
    return dq, dk, dv


def _to_bhtd(x):
    B, T, H, Dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)


def _from_bhtd(x, B, H):
    BH, T, Dh = x.shape
    return x.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, block_q, block_k, interpret):
    B, _, H, _ = q.shape
    out, _ = _flash_bhtd(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v),
                         block_q=block_q, block_k=block_k, causal=causal,
                         interpret=interpret)
    return _from_bhtd(out, B, H)


def _flash_diff_fwd(q, k, v, causal, block_q, block_k, interpret):
    B, _, H, _ = q.shape
    Hkv = k.shape[2]
    qt, kt, vt = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
    out, lse = _flash_bhtd(qt, kt, vt, block_q=block_q, block_k=block_k,
                           causal=causal, interpret=interpret)
    return _from_bhtd(out, B, H), (qt, kt, vt, out, lse, B, H, Hkv)


def _flash_diff_bwd(causal, block_q, block_k, interpret, res, g):
    # Fused flash backward: rebuilds p from the saved lse per tile — the
    # O(T²) score matrix never exists in HBM in either direction, which is
    # what makes long-context training fit (a dense backward at T=8192
    # wants a 4 GB probs tensor PER LAYER).
    qt, kt, vt, ot, lse, B, H, Hkv = res
    dq, dk, dv = _flash_bwd_bhtd(qt, kt, vt, ot, _to_bhtd(g), lse,
                                 block_q=block_q, block_k=block_k,
                                 causal=causal, interpret=interpret,
                                 n_kv_head=Hkv)
    return (_from_bhtd(dq, B, H), _from_bhtd(dk, B, Hkv),
            _from_bhtd(dv, B, Hkv))


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def default_blocks(T: int, Dh: int) -> tuple:
    """Measured-on-chip default tile sizes (v5e, bf16, fwd+bwd sweep at
    T=8192..32768). With K/V streamed per q block, refetch traffic scales
    1/block_q — arithmetic intensity of the refetch is ~block_q flops/byte
    vs the v5e ridge of ~240 — so blocks must be LARGE: (1024, 1024) for
    Dh=64 (17.6 vs 23.0 ms at the round-4 (256, 512)), (2048, 1024) for
    Dh=128 (10.7 vs 18.5 ms). bk=2048 or bq=4096 trip the VMEM ceiling
    (fp32 [bq, bk] score tiles), and so does bq=2048 at Dh=128 once the
    kernel sits under a remat'd scan (T=16384 train: scoped-vmem over by
    420K from the remat stack) — hence bq drops back to 1024 for
    T > 8192 (a tile-size cap only; the k-blocked kernels themselves run
    to T=32768+)."""
    bq = 2048 if (Dh >= 128 and T <= 8192) else 1024
    return snap_block(bq, T), snap_block(1024, T)


def snap_block(b: int, T: int) -> int:
    """Snap a block size DOWN (by halving) toward a divisor of T so mid-size
    T (1280, 2560, ...) keeps a large tile. Whether the result is usable is
    `check_blocks`'s call, not this function's. Shared by flash_attention
    and the ring-attention per-shard path."""
    b = min(b, T)
    while b >= 128 and T % b:
        b //= 2
    return b


def check_blocks(T: int, block_q: int, block_k: int, interpret: bool) -> None:
    """Raise on a (T, block) combination the kernels cannot take. Compiled
    for the chip, a block must be a whole number of 128-lane tiles: the
    score tile is [block_q, block_k] and the lse row is stored at a dynamic
    lane offset qi * block_q. The interpreter has no tiles, so there any
    divisor of T runs (the CPU tests use 16..64)."""
    if T % block_q or T % block_k:
        raise ValueError(
            f"flash attention: blocks ({block_q}, {block_k}) do not divide "
            f"T={T}; pad the sequence or use reference_attention")
    if not interpret and (block_q % _LANES or block_k % _LANES):
        raise ValueError(
            f"flash attention: blocks ({block_q}, {block_k}) for T={T} are "
            f"not multiples of the {_LANES}-lane tile; T must be a multiple "
            f"of {_LANES} on the chip")


def dense_attention_with_lse(q, k, v, causal: bool = True):
    """jnp twin of flash_attention_with_lse — the reference the kernels are
    checked against, and ring attention's per-shard op on a mesh that is
    not made of TPUs: returns (out [B,T,H,Dh], lse [B,H,T] f32). Accepts
    GQA-shaped K/V ([B,T,Hkv,Dh], Hkv dividing H) by repeating — the twin
    optimizes for correctness, the kernels for bytes. Plain jnp, so
    autodiff covers it."""
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    Dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(Dh)
    if causal:
        T = q.shape[1]
        qi = lax.broadcasted_iota(jnp.int32, (T, T), 0)
        ki = lax.broadcasted_iota(jnp.int32, (T, T), 1)
        logits = jnp.where(ki <= qi, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)          # [B, H, T]
    p = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal, block_q, block_k, interpret):
    """Fused attention returning (out, lse [B, H, T] f32) — the form block-
    combiners need (ring attention folds per-shard results by lse). Both
    outputs are differentiable: the backward folds the incoming dlse into
    delta (d lse/d s = p, so ds = p ⊙ (dp − (delta − dlse))) and reuses the
    same fused kernels. K/V may be GQA-shaped ([B, T, Hkv, Dh])."""
    B, _, H, _ = q.shape
    out, lse = _flash_bhtd(_to_bhtd(q), _to_bhtd(k), _to_bhtd(v),
                           block_q=block_q, block_k=block_k, causal=causal,
                           interpret=interpret)
    T = lse.shape[-1]
    return _from_bhtd(out, B, H), lse.reshape(B, H, T)


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, interpret):
    B, _, H, _ = q.shape
    Hkv = k.shape[2]
    qt, kt, vt = _to_bhtd(q), _to_bhtd(k), _to_bhtd(v)
    out, lse = _flash_bhtd(qt, kt, vt, block_q=block_q, block_k=block_k,
                           causal=causal, interpret=interpret)
    T = lse.shape[-1]
    return ((_from_bhtd(out, B, H), lse.reshape(B, H, T)),
            (qt, kt, vt, out, lse, B, H, Hkv))


def _flash_lse_bwd(causal, block_q, block_k, interpret, res, g):
    do, dlse = g
    qt, kt, vt, ot, lse, B, H, Hkv = res
    dot = _to_bhtd(do)
    # delta_eff = rowsum(do·o) − dlse: the lse cotangent enters every ds
    # tile through the same row-broadcast slot delta occupies, so the
    # kernels need no change — see _flash_bwd_bhtd's delta_override
    delta = (jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                     axis=-1)
             - dlse.reshape(ot.shape[0], ot.shape[1]))[:, None, :]
    dq, dk, dv = _flash_bwd_bhtd(qt, kt, vt, ot, dot, lse,
                                 block_q=block_q, block_k=block_k,
                                 causal=causal, interpret=interpret,
                                 n_kv_head=Hkv, delta_override=delta)
    return (_from_bhtd(dq, B, H), _from_bhtd(dk, B, Hkv),
            _from_bhtd(dv, B, Hkv))


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 0,
                    block_k: int = 0, interpret: bool = False):
    """Fused causal attention. q: [B, T, H, Dh], k/v: [B, T, Hkv, Dh]
    (Hkv == H for MHA, Hkv dividing H for GQA) → [B, T, H, Dh].

    Runs the pallas kernels, compiled on a TPU backend or interpreted under
    `interpret`; raises ValueError on any other backend and on a T the
    blocks cannot tile (`check_blocks`) — there is no silent dense path.
    Differentiable: forward AND backward are fused kernels (custom_vjp over
    the saved log-sum-exp), so it drops into build_train_step and stays
    O(T) in memory for long-context training."""
    B, T, H, Dh = q.shape
    if H % k.shape[2]:
        raise ValueError(f"GQA requires n_kv_head to divide n_head; got "
                         f"H={H}, Hkv={k.shape[2]}")
    if not interpret and jax.default_backend() != "tpu":
        raise ValueError(
            f"flash_attention compiles for TPU only (backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            f"kernel logic here, or call reference_attention")
    dbq, dbk = default_blocks(T, Dh)
    block_q = snap_block(block_q, T) if block_q else dbq
    block_k = snap_block(block_k, T) if block_k else dbk
    check_blocks(T, block_q, block_k, interpret)
    return _flash_diff(q, k, v, causal, block_q, block_k, interpret)
