"""On-chip train-step benchmark: tokens/s and MFU on the real TPU.

The reference's culture is to publish its headline numbers
(/root/reference/docs/md/01_Introduction.md:8 — "45 Gbit/s sustained");
its model compute lives in torch training loops
(/root/reference/python/examples/nanogptddp/train_pccl.py). pccl_tpu's
equivalent headline is the thing the reference cannot measure at all: the
jitted bf16 train step (parallel/train.py:build_train_step) executing on an
actual TPU chip, reported as tokens/s and model-FLOPs utilization.

Methodology notes:

- **Fencing.** Each timed window ends in `block_until_ready` on the
  window's last outputs; `chip_smoke.py`'s fence phase checks on the chip
  at hand that this waits for execution (a chained matmul timed against a
  scalar readback, and against the chip's peak). Steps inside a window
  chain through the donated params, so the window measures the real
  back-to-back step rate, including dispatch.

- **MFU convention.** Model FLOPs are the algorithmic count (6·matmul-params
  per token + 12·L·T·d attention, the PaLM-appendix formula); recompute done
  by the flash-attention backward does NOT count toward the numerator, so
  the reported MFU is conservative.
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict

import numpy as np


# Peak dense bf16 FLOP/s per chip, by `device_kind` prefix (public TPU
# datasheet numbers). Used as the MFU denominator.
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,   # v6e / Trillium
    "TPU v6e": 918.0,
}

# Per-family on-chip bench shapes: largest preset whose train state
# (fp32 params + 2 AdamW moments + transient fp32 grads) plus activations
# fits a single 16 GB v5e comfortably. Tuned empirically on the chip:
# remat is mandatory (every no-remat shape at these sizes OOMs — dense b8
# wants 34.6 GB), and XLA's dense attention beats the pallas flash kernel
# at T<=2048 (the kernel pays grid overhead per tiny block; it earns its
# keep at long T where dense probs don't fit — see ops/flash_attention.py).
DEFAULT_SHAPES = {
    # gpt: the "dots" policy (save weight-matmul outputs, recompute the
    # rest) beats full remat at b12 (31.3% vs 30.1% MFU) with HBM headroom
    "gpt": dict(preset="gpt2-medium", batch=12, seq=1024, remat="dots"),
    # llama: full remat at b4 (36.2%) beats dots, which only fits b2 (34.8%)
    "llama": dict(preset="700m", batch=4, seq=2048, remat=True),
}


def peak_tflops(device) -> float:
    kind = getattr(device, "device_kind", "")
    for prefix, tf in sorted(PEAK_BF16_TFLOPS.items(),
                             key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return tf
    raise ValueError(f"unknown TPU device kind {kind!r}; "
                     "add it to PEAK_BF16_TFLOPS")


def flops_per_token(cfg, seq: int) -> float:
    """Algorithmic train FLOPs per token (fwd 2×matmul-params + attention,
    backward = 2× forward)."""
    from ..models import llama

    d, L = cfg.n_embd, cfg.n_layer
    if isinstance(cfg, llama.LlamaConfig):
        kv = cfg.n_kv_head * cfg.head_dim
        per_layer = d * d + d * 2 * kv + d * d + 3 * d * cfg.ffn_dim
        head = cfg.vocab_size * d            # untied unembedding
    else:
        per_layer = 12 * d * d               # qkv + out + mlp_in + mlp_out
        head = cfg.vocab_size * d            # tied unembedding matmul
    matmul_params = L * per_layer + head
    # attention: QK^T + AV are 2·T·d each fwd per layer → ×3 for fwd+bwd
    return 6.0 * matmul_params + 12.0 * L * seq * d


def _named_config(family: str, preset: str, seq: int, **overrides):
    from ..models import gpt, llama

    mod = llama if family == "llama" else gpt
    return mod.named_config(preset, block_size=seq, **overrides)


def run_tpu_train_bench(family: str = "gpt", preset: str | None = None,
                        batch: int | None = None, seq: int | None = None,
                        steps_per_window: int = 8, windows: int = 5,
                        use_flash: bool = False,
                        remat: "bool | str | None" = None,
                        repeat_kv: bool = False,
                        loss_chunk: int = 0,
                        **cfg_overrides) -> Dict[str, Any]:
    """Measure the jitted train step on the first TPU device.

    Returns {config, tokens_s (median), tokens_s_min/max, step_s, mfu,
    model_tflops_per_step, loss_first, loss_last}. Raises RuntimeError when
    no TPU is present."""
    import jax
    import jax.numpy as jnp

    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if not tpus:
        raise RuntimeError("no TPU device present")
    dev = tpus[0]

    shape = dict(DEFAULT_SHAPES[family])
    if preset:
        shape["preset"] = preset
    if batch:
        shape["batch"] = batch
    if seq:
        shape["seq"] = seq
    if remat is not None:
        shape["remat"] = remat
    B, T = shape["batch"], shape["seq"]
    do_remat = shape.get("remat", False)
    cfg = _named_config(family, shape["preset"], T, **cfg_overrides)

    from jax.sharding import Mesh
    from ..parallel import train as train_lib
    from ..ops.flash_attention import flash_attention

    mesh = Mesh(np.array(tpus[:1]).reshape(1, 1), ("dp", "tp"))
    attn_fn = flash_attention if use_flash else None
    if repeat_kv and use_flash:
        # A/B ablation: the round-4 degraded path — materialize K/V at the
        # full head count in HBM before the kernel, forfeiting GQA's
        # KV-bytes shrink. Measures what the GQA-native kernels buy.
        def attn_fn(q, k, v):  # noqa: F811 — deliberate override
            H, Hkv = q.shape[2], k.shape[2]
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
            return flash_attention(q, k, v)
    with mesh:
        params, tx, opt_state = train_lib.make_train_state(
            jax.random.PRNGKey(0), cfg, mesh)
        step = train_lib.build_train_step(cfg, tx, mesh, attn_fn=attn_fn,
                                          remat=do_remat,
                                          loss_chunk=loss_chunk or None)

        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                             dtype=jnp.int32)
        targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)),
                              dtype=jnp.int32)

        # warmup: the compile stays outside the timed windows
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        loss_first = float(loss)

        rates = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps_per_window):
                params, opt_state, loss = step(params, opt_state, tokens,
                                               targets)
            jax.block_until_ready((params, opt_state, loss))
            dt = time.perf_counter() - t0
            rates.append(steps_per_window * B * T / dt)
        loss_last = float(loss)

    tok_s = statistics.median(rates)
    ftok = flops_per_token(cfg, T)
    peak = peak_tflops(dev) * 1e12
    return {
        "config": f"{family}/{shape['preset']} b{B}x{T} "
                  f"{'flash' if use_flash else 'dense'}"
                  f"{'+remat' if do_remat is True else ''}"
                  f"{'+remat:' + do_remat if isinstance(do_remat, str) else ''}"
                  f"{'+ce:' + str(loss_chunk) if loss_chunk else ''}"
                  f"{'+repeatkv' if repeat_kv else ''}"
                  f" ({dev.device_kind})",
        "tokens_s": round(tok_s, 1),
        "tokens_s_min": round(min(rates), 1),
        "tokens_s_max": round(max(rates), 1),
        "step_s": round(B * T / tok_s, 4),
        "model_tflops_per_step": round(ftok * B * T / 1e12, 2),
        "mfu": round(tok_s * ftok / peak, 4),
        "loss_first": round(loss_first, 3),
        "loss_last": round(loss_last, 3),
    }


if __name__ == "__main__":
    import json
    import sys

    fam = sys.argv[1] if len(sys.argv) > 1 else "gpt"
    kw = {}
    for a in sys.argv[2:]:
        k, v = a.split("=")
        if k == "preset":
            kw[k] = v
        elif k == "remat":
            kw[k] = v if v in ("dots", "sqrt") else bool(int(v))
        elif k in ("use_flash", "untie_head", "repeat_kv"):
            kw[k] = bool(int(v))
        else:
            kw[k] = int(v)  # batch/seq/windows + int config overrides
                            # (n_head, n_embd, ... — ablation legs)
    print(json.dumps(run_tpu_train_bench(fam, **kw)))
