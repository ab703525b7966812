"""On-chip shared-state hash benchmark: the clean-sync invariant.

The reference hashes CUDA buffers on the GPU so a clean shared-state sync
never stages device memory to host (/root/reference/ccoip/src/cuda/
simplehash_cuda.cu). This leg measures the TPU twin of that invariant:
`jax_simplehash_device` (hash type 2 — the digest computed on the chip,
8 bytes crossing to the host) against the staging path (`device_get` the
whole array, hash on host) at growing state sizes. The staging path scales
with state size at the D2H rate while the device digest should stay flat —
which is exactly the claim: clean-sync cost is independent of state size.

Run as __main__ in a subprocess (libtpu is process-exclusive); prints one
JSON line.
"""

from __future__ import annotations

import time
from typing import Dict


def run_hash_bench(sizes_mb=(16, 64, 256)) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.hashing import jax_simplehash_device, simplehash_tpu
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if not any(d.platform == "tpu" for d in jax.devices()):
        raise RuntimeError("no TPU device present")

    out: Dict[str, float] = {}
    for mb in sizes_mb:
        n = mb * (1 << 20) // 4
        arr = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
        arr.block_until_ready()

        # device digest: the int() conversion inside is the host readback
        # fence (8 bytes)
        h_dev = jax_simplehash_device(arr)      # warmup incl. compile
        t0 = time.perf_counter()
        h_dev = jax_simplehash_device(arr)
        out[f"devhash_{mb}mb_s"] = time.perf_counter() - t0

        # staging path: what from_jax (eager) pays every sync — the full
        # array to the host, then the host-side twin
        t0 = time.perf_counter()
        host = np.asarray(jax.device_get(arr))
        h_host = simplehash_tpu(host)
        out[f"stagehash_{mb}mb_s"] = time.perf_counter() - t0
        assert h_host == h_dev, "device/host digest parity broke"
    return out


if __name__ == "__main__":
    import json

    print(json.dumps({k: round(v, 4) for k, v in run_hash_bench().items()}))
