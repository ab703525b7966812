"""Sync DiLoCo on nanoGPT — H local steps, then one outer reduce.

Reference parity: /root/reference/python/examples/nanogpt_diloco/
sync_diloco.py (torch inner AdamW + outer Nesterov SGD on pseudo-gradients,
shared-state revision per outer step, late joiners catch up via
sync_shared_state). TPU-first: the inner loop is a jitted SPMD step over the
local mesh (pccl_tpu.parallel.train); only one flat fp32 pseudo-gradient
vector crosses the ring per outer step, optionally quantized.

Run (2 peers):
    python -m pccl_tpu.comm.master --port 48500 &
    python examples/nanogpt_diloco/sync_diloco.py --master-port 48500 \
        --base-port 56000 --min-world 2 &
    python examples/nanogpt_diloco/sync_diloco.py --master-port 48500 \
        --base-port 56100 --min-world 2
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
    ap.add_argument("--outer-steps", type=int, default=8)
    ap.add_argument("--inner-steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--inner-lr", type=float, default=1e-3)
    common.add_lr_schedule_args(ap)
    common.add_data_args(ap)
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--quantize", choices=["none", "minmax"], default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shm-staging", action="store_true",
                    help="stage pseudo-gradients in a registered shm buffer "
                         "(zero-copy ring when peers share this host)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save outer state here every --checkpoint-every "
                         "steps and resume from the newest snapshot")
    ap.add_argument("--checkpoint-every", default=10,
                    type=lambda v: max(1, int(v)))
    common.add_model_args(ap)
    args = ap.parse_args()

    common.start_jax()
    import jax
    import jax.numpy as jnp

    from pccl_tpu.comm import DataType
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib
    from pccl_tpu.parallel.diloco import Diloco, DilocoConfig

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

    dl = Diloco(comm, params,
                DilocoConfig(inner_steps=args.inner_steps,
                             outer_lr=args.outer_lr,
                             quantization=common.quant_from_arg(args.quantize),
                             quantized_dtype=DataType.UINT8,
                             shm_staging=args.shm_staging))

    from pccl_tpu.utils.profiler import Profiler

    ckpt = start = None
    if args.checkpoint_dir:
        from pccl_tpu.utils.checkpoint import DilocoCheckpoint

        ckpt = DilocoCheckpoint(args.checkpoint_dir)
        start = ckpt.maybe_restore(dl)
        if start:
            # continue INNER training from the restored outer params —
            # training from seed-init params would make the first
            # pseudo-gradient (outer − inner) a restored-vs-seed jump
            # that the outer SGD then applies toward the seed
            params = dl.params()
            if schedule is not None:
                # the schedule's position lives in the optimizer's step
                # count, which resumes at 0 — shift it so the decay
                # continues where the run left off instead of re-running
                # warmup (inner Adam moments restart fresh by design:
                # DiLoCo shares only the outer state)
                shifted = common.make_schedule(
                    args, args.inner_lr,
                    args.outer_steps * args.inner_steps,
                    offset=start * args.inner_steps)
                _, tx, opt_state = train_lib.make_train_state(
                    jax.random.PRNGKey(args.seed), cfg, mesh,
                    lr=args.inner_lr, schedule=shifted)
                step_fn = train_lib.build_train_step(cfg, tx, mesh)
            print(f"resumed from outer step {start}", flush=True)

    prof = Profiler(enabled=args.profile or bool(args.trace_out))
    next_batch = common.make_batch_fn(args, cfg.vocab_size)
    if start:
        # fast-forward the deterministic data stream past the batches outer
        # steps [0, start) already consumed — without this a resumed run
        # retrains the replayed prefix (train_ddp.py's resume path drains
        # its stream the same way)
        for _ in range(start * args.inner_steps):
            next_batch()
    first_loss = last_loss = None
    for outer in range(start or 0, args.outer_steps):
        common.admit_pending(comm)
        with prof.section("inner"):
            for _ in range(args.inner_steps):
                tok, tgt = next_batch()
                tok = jax.device_put(jnp.asarray(tok), data_sharding)
                tgt = jax.device_put(jnp.asarray(tgt), data_sharding)
                params, opt_state, loss = step_fn(params, opt_state, tok, tgt)
        with prof.section("outer/ring+sgd"):
            params = dl.outer_step(params)  # ring AVG of pseudo-grads + SGD
        loss = float(loss)
        first_loss = first_loss if first_loss is not None else loss
        last_loss = loss
        world = comm.world_size if comm is not None else 1
        print(f"outer {outer} loss {loss:.4f} world {world} "
              f"revision {dl.step}", flush=True)
        if ckpt is not None and (outer + 1) % args.checkpoint_every == 0:
            ckpt.save(dl)

    common.finish_profile(args, prof)
    return common.report_final(first_loss, last_loss, comm)


if __name__ == "__main__":
    sys.exit(main())
