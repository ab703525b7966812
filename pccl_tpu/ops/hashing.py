"""Device-independent content hashing — the Python twin of the native hash.

Reference parity: the reference's simplehash deliberately makes its CPU
implementation emulate the CUDA grid (256-thread blocks, warp shuffles) so
CPU and GPU produce identical digests (/root/reference/ccoip/src/cpp/
simplehash/simplehash_cpu.cpp:7-58) — bit parity across devices is the core
invariant of shared-state drift detection.

TPU-first re-design (matches pccl_tpu/native/src/hash.cpp exactly): bytes →
little-endian u32 words (zero-padded tail); word i feeds lane (i % 256) via
Horner with P; lanes combine with a second Horner pass with Q, seeded with
the byte length; murmur-style avalanche finalizes. The lane structure means
the whole digest is expressible as vectorized numpy over a [n_chunks, 256]
word matrix — no per-element Python loop — and the SAME digest is reproduced
by the C++ core (pccltHashBuffer), so a TPU host process can hash staged HBM
bytes wherever convenient and compare against any peer.

CRC32 (hash type 1) needs no twin: the native implementation matches
zlib.crc32 (IEEE reflected polynomial).
"""

from __future__ import annotations

import numpy as np

LANES = 256
P = np.uint64(0x100000001B3)          # FNV-1a prime
Q = np.uint64(0x9E3779B97F4A7C15)     # 2^64 / phi
SEED = np.uint64(0xCBF29CE484222325)  # FNV offset basis
_M1 = np.uint64(0xFF51AFD7ED558CCD)
_M2 = np.uint64(0xC4CEB9FE1A85EC53)


_BLOCK = 4096  # full rows folded per vectorized step


def _p_powers(n: int) -> np.ndarray:
    """P^0..P^n with uint64 wraparound, computed once at import."""
    with np.errstate(over="ignore"):
        pows = np.empty(n + 1, dtype=np.uint64)
        pows[0] = np.uint64(1)
        for i in range(1, n + 1):
            pows[i] = pows[i - 1] * P
    return pows


_P_POWS = _p_powers(_BLOCK)


def _avalanche64(x: np.uint64) -> np.uint64:
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(33)
        x *= _M1
        x ^= x >> np.uint64(33)
        x *= _M2
        x ^= x >> np.uint64(33)
    return x


def simplehash(buf) -> int:
    """Digest of a bytes-like / ndarray's raw content. Bit-identical to the
    native pcclt::hash::simplehash."""
    if isinstance(buf, np.ndarray):
        data = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
    else:
        data = np.frombuffer(memoryview(buf), dtype=np.uint8)
    nbytes = data.size

    n_words = (nbytes + 3) // 4
    padded = np.zeros(((n_words + LANES - 1) // LANES) * LANES * 4,
                      dtype=np.uint8)
    padded[:nbytes] = data
    words = padded.view("<u4").astype(np.uint64).reshape(-1, LANES)

    # lane[l] = Horner over its word column. Full rows fold in blocks of B
    # (lane = lane * P^B + Σ words[r] * P^(B-1-r)), so the work is a
    # vectorized weighted sum instead of a per-row Python loop.
    lane = np.full(LANES, SEED, dtype=np.uint64)
    n_rows = n_words // LANES          # full rows of the word matrix
    with np.errstate(over="ignore"):
        pows = _P_POWS
        r = 0
        while r < n_rows:
            b = min(_BLOCK, n_rows - r)
            block = words[r:r + b]
            weights = pows[b - 1::-1][:, None]      # P^(b-1) ... P^0
            lane = lane * pows[b] + (block * weights).sum(axis=0,
                                                          dtype=np.uint64)
            r += b
        if n_rows * LANES != n_words:  # partial last row
            k = n_words - n_rows * LANES
            lane[:k] = lane[:k] * P + words[n_rows, :k]
        acc = SEED ^ (np.uint64(nbytes) * Q)
        for lv in lane:
            acc = acc * Q + lv
    return int(_avalanche64(acc))


def jax_simplehash(arr) -> int:
    """Digest of a jax.Array's content: stages to host once (over ICI for a
    sharded array) and hashes the canonical row-major bytes. Every device
    layout of the same logical array yields the same digest."""
    import jax

    host = np.asarray(jax.device_get(arr))
    return simplehash(host)


# --- TPU-native hash (hash type 2, pcclt::hash::kSimpleTpu) ---------------
# The digest an accelerator can compute over HBM-RESIDENT bytes with pure
# u32 arithmetic: a clean shared-state sync then ships 8 bytes over the
# wire instead of staging the array to host (hashing resident state via
# staging pays its full size in D2H even when nothing changed; the rate on
# a locally attached chip is not measured). The reference hashes CUDA buffers
# on-GPU for the same reason (/root/reference/ccoip/src/cuda/
# simplehash_cuda.cu, dispatched at ccoip_client_handler.cpp:383-416).
#
# Definition (bit-identical across this numpy twin, the C++ twin
# hash.cpp:simplehash_tpu, and the jitted device digest below): LE u32
# words, word i -> (row i // 65536, lane i % 65536), the last row
# zero-padded to the full lane grid; two parallel u32 Horner planes per
# lane (A/B with distinct primes/seeds); 16 levels of pairwise murmur3-
# step lane folding (non-linear — see _mix2); the two u32 plane digests
# concatenate to 64 bits, XOR the Q-scaled byte length, avalanche.

TPU_LANES = 65536
_TPA, _TSA = np.uint32(0x01000193), np.uint32(0x811C9DC5)
_TPB, _TSB = np.uint32(0x85EBCA6B), np.uint32(0x9E3779B9)


def _u32_powers(p: np.uint32, n: int) -> np.ndarray:
    """[p^n-1 ... p^1 p^0] mod 2^32 (the row weights for n rows)."""
    with np.errstate(over="ignore"):
        out = np.empty(n, dtype=np.uint32)
        acc = np.uint32(1)
        for i in range(n - 1, -1, -1):
            out[i] = acc
            acc = acc * p
    return out


_MC1, _MC2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
_MC5, _MC6 = np.uint32(5), np.uint32(0xE6546B64)


def _rotl32(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _mix2(h, k):
    """murmur3 stream step as a 2→1 lane combiner (h absorbs k). The
    combine must be NON-LINEAR with rotations: a linear fold (a*C + b or
    (a*C) ^ b) of IDENTICAL halves — exactly what uniform content such as
    zero-init params produces — cancels structurally (x*(C+1) accumulates
    even factors; (x*C)^x clears the lowest set bit per level), and 16
    levels of that made every constant array hash identically. Rotate +
    distinct multipliers break the alignment."""
    k = _rotl32(k * _MC1, 15) * _MC2
    return _rotl32(h ^ k, 13) * _MC5 + _MC6


def _tpu_fold(lane_a, lane_b):
    """Pairwise lane fold, generic over numpy/jnp arrays: 16 levels of
    _mix2 halving the lane vector (identical graph on device and host)."""
    half = TPU_LANES // 2
    while half >= 1:
        lane_a = _mix2(lane_a[:half], lane_a[half:2 * half])
        lane_b = _mix2(lane_b[:half], lane_b[half:2 * half])
        half //= 2
    return lane_a[0], lane_b[0]


def _tpu_finalize(acc_a, acc_b, nbytes: int) -> int:
    """64-bit tail (host arithmetic): concat planes, mix length, avalanche."""
    with np.errstate(over="ignore"):
        d = (np.uint64(acc_a) << np.uint64(32)) | np.uint64(acc_b)
        return int(_avalanche64(d ^ (np.uint64(nbytes) * Q)))


def _tpu_fold_mix(lane_a: np.ndarray, lane_b: np.ndarray,
                  nbytes: int) -> int:
    with np.errstate(over="ignore"):
        a, b = _tpu_fold(lane_a, lane_b)
    return _tpu_finalize(a, b, nbytes)


def simplehash_tpu(buf) -> int:
    """numpy twin of the TPU-native hash. Bit-identical to the C++
    pcclt::hash::simplehash_tpu and to jax_simplehash_device."""
    if isinstance(buf, np.ndarray):
        data = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
    else:
        data = np.frombuffer(memoryview(buf), dtype=np.uint8)
    nbytes = data.size
    n_words = (nbytes + 3) // 4
    rows = (n_words + TPU_LANES - 1) // TPU_LANES
    padded = np.zeros(max(rows, 1) * TPU_LANES * 4, dtype=np.uint8)
    padded[:nbytes] = data
    words = padded.view("<u4").reshape(-1, TPU_LANES)[:rows]

    with np.errstate(over="ignore"):
        wa = _u32_powers(_TPA, rows)[:, None]
        wb = _u32_powers(_TPB, rows)[:, None]
        pa_rows = (wa[0, 0] * _TPA) if rows else np.uint32(1)  # _TPA^rows
        pb_rows = (wb[0, 0] * _TPB) if rows else np.uint32(1)
        lane_a = (words * wa).sum(axis=0, dtype=np.uint32) + _TSA * pa_rows
        lane_b = (words * wb).sum(axis=0, dtype=np.uint32) + _TSB * pb_rows
    return _tpu_fold_mix(lane_a, lane_b, nbytes)


def _words_u32(x):
    """Canonical LE u32 word stream of a flattened jax array (device op).
    Supports 1/2/4-byte dtypes; 8-byte dtypes raise (callers fall back to
    the staging hash — TPUs run with 32-bit ints by default anyway)."""
    import jax.numpy as jnp
    from jax import lax

    x = x.reshape(-1)
    size = x.dtype.itemsize
    if size == 4:
        return lax.bitcast_convert_type(x, jnp.uint32)
    if size == 2:
        h = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        if h.shape[0] % 2:
            h = jnp.concatenate([h, jnp.zeros(1, jnp.uint32)])
        h = h.reshape(-1, 2)
        return h[:, 0] | (h[:, 1] << 16)
    if size == 1:
        b = lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
        pad = (-b.shape[0]) % 4
        if pad:
            b = jnp.concatenate([b, jnp.zeros(pad, jnp.uint32)])
        b = b.reshape(-1, 4)
        return b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)
    raise ValueError(f"no device word stream for itemsize {size}")


import functools


@functools.lru_cache(maxsize=512)
def _device_planes_fn(shape, dtype_name):
    """Jitted (lane_a, lane_b) digest planes for one (shape, dtype) —
    cached so repeated syncs of the same state pay dispatch, not retrace
    (a fresh inner @jax.jit per call retraces and recompiles; the cached
    fn costs the dispatch + 8-byte readback)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def planes(x):
        w = _words_u32(x)
        n = w.shape[0]
        rows = max(1, -(-n // TPU_LANES))
        pad = rows * TPU_LANES - n
        if pad:
            w = jnp.concatenate([w, jnp.zeros(pad, jnp.uint32)])
        w = w.reshape(rows, TPU_LANES)
        wa = jnp.asarray(_u32_powers(_TPA, rows)[:, None])
        wb = jnp.asarray(_u32_powers(_TPB, rows)[:, None])
        with np.errstate(over="ignore"):
            pa_rows = np.uint32(_u32_powers(_TPA, rows)[0] * _TPA)
            pb_rows = np.uint32(_u32_powers(_TPB, rows)[0] * _TPB)
        lane_a = (w * wa).sum(axis=0, dtype=jnp.uint32) + _TSA * pa_rows
        lane_b = (w * wb).sum(axis=0, dtype=jnp.uint32) + _TSB * pb_rows
        return _tpu_fold(lane_a, lane_b)   # fold ON DEVICE: 8 bytes out

    return planes


def jax_simplehash_device(arr) -> int:
    """TPU-native digest of a jax.Array computed ON DEVICE: only the two
    u32 plane accumulators (8 bytes) cross to the host. Bit-identical to
    simplehash_tpu of the same logical bytes; the row-weight constants
    are baked at trace time (shapes are static)."""
    nbytes = arr.size * arr.dtype.itemsize
    if arr.size == 0:
        # rows=0 case: the device graph below pads to one zero row, which
        # would advance every Horner chain once and diverge from the
        # twins' rows=0 digest — hash the empty byte stream on host
        return simplehash_tpu(np.empty(0, np.uint8))
    acc_a, acc_b = _device_planes_fn(tuple(arr.shape), str(arr.dtype))(arr)
    return _tpu_finalize(np.uint32(acc_a), np.uint32(acc_b), nbytes)
