"""Async DiLoCo on nanoGPT — the outer reduce overlaps the next inner phase.

Reference parity: /root/reference/python/examples/nanogpt_diloco/
async_diloco.py and docs/md/07-.../03-AsyncDiloco.md — the reduce of outer
step t runs on a background thread while inner steps of t+1 compute; the
delayed update lands at the next outer boundary (one-step-delayed
pseudo-gradients). TPU angle: the inner phase keeps the chips busy the whole
time — the WAN hop is fully hidden behind jitted SPMD compute.

Run: same as sync_diloco.py, swapping the script name.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))


import common


def main() -> int:
    ap = argparse.ArgumentParser()
    common.add_comm_args(ap)
    ap.add_argument("--outer-steps", type=int, default=10)
    ap.add_argument("--inner-steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--inner-lr", type=float, default=1e-3)
    common.add_lr_schedule_args(ap)
    common.add_data_args(ap)
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--quantize", choices=["none", "minmax"], default="none")
    ap.add_argument("--seed", type=int, default=0)
    common.add_model_args(ap)
    args = ap.parse_args()

    common.start_jax()
    import jax
    import jax.numpy as jnp

    from pccl_tpu.comm import DataType
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib
    from pccl_tpu.parallel.diloco import AsyncDiloco, DilocoConfig

    comm = common.connect(args)

    mesh = mesh_lib.make_mesh(jax.devices(), ("dp", "tp"))
    cfg = common.model_config(args, char_level=args.data == "text")
    schedule = common.make_schedule(
        args, args.inner_lr, args.outer_steps * args.inner_steps)
    params, tx, opt_state = train_lib.make_train_state(
        jax.random.PRNGKey(args.seed), cfg, mesh, lr=args.inner_lr,
        schedule=schedule)
    step_fn = train_lib.build_train_step(cfg, tx, mesh)
    data_sharding = mesh_lib.batch_sharding(mesh)

    # delayed gradients oscillate with heavy momentum; reference async runs
    # tame the outer momentum (docs/md/07-.../03-AsyncDiloco.md)
    dl = AsyncDiloco(comm, params,
                     DilocoConfig(inner_steps=args.inner_steps,
                                  outer_lr=args.outer_lr, outer_momentum=0.0,
                                  quantization=common.quant_from_arg(args.quantize),
                                  quantized_dtype=DataType.UINT8))

    from pccl_tpu.utils.profiler import Profiler

    prof = Profiler(enabled=args.profile or bool(args.trace_out))
    next_batch = common.make_batch_fn(args, cfg.vocab_size)
    first_loss = last_loss = None
    for outer in range(args.outer_steps):
        common.admit_pending(comm)
        with prof.section("inner"):
            for _ in range(args.inner_steps):
                tok, tgt = next_batch()
                tok = jax.device_put(jnp.asarray(tok), data_sharding)
                tgt = jax.device_put(jnp.asarray(tgt), data_sharding)
                params, opt_state, loss = step_fn(params, opt_state, tok, tgt)
        with prof.section("outer/launch+join_prev"):
            # kicks the ring reduce on a background thread; returns
            # immediately (the section times joining the PREVIOUS reduce)
            params = dl.outer_step_async(params)
        loss = float(loss)
        first_loss = first_loss if first_loss is not None else loss
        last_loss = loss
        world = comm.world_size if comm is not None else 1
        print(f"outer {outer} loss {loss:.4f} world {world}", flush=True)
    params = dl.finish()  # land the last in-flight reduce

    common.finish_profile(args, prof)
    return common.report_final(first_loss, last_loss, comm)


if __name__ == "__main__":
    sys.exit(main())
