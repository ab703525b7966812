"""nanoGPT DDP over the WAN ring — per-step gradient averaging.

Reference parity: /root/reference/python/examples/nanogptddp/train_pccl.py
(torch DDP loop with pcclAllReduce per step). TPU-first redesign:

- each peer process is one SLICE: the train step is a jitted SPMD program
  over the local device mesh (dp x tp — pass --tp for in-slice tensor
  parallelism; this is the reference's FSDP x PCCL grid pattern,
  docs/md/8_CommonFootguns.md, with XLA sharding in place of FSDP);
- per-step gradients cross the ring as ONE flat fp32 vector
  (HierarchicalAllReduce: ICI in-jit, TCP across slices) with optional
  on-the-wire quantization (--quantize minmax);
- peer churn: ConnectionLost/Aborted -> update_topology -> retry, and
  pending joiners are admitted between steps.

Run (2 peers on loopback):
    python -m pccl_tpu.comm.master --port 48500 &
    python examples/nanogpt_ddp/train_ddp.py --master-port 48500 \
        --base-port 56000 --min-world 2 --steps 50 &
    python examples/nanogpt_ddp/train_ddp.py --master-port 48500 \
        --base-port 56100 --min-world 2 --steps 50
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import numpy as np

import common


def main() -> int:
    ap = argparse.ArgumentParser()
    common.add_comm_args(ap)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--tp", type=int, default=0,
                    help="in-slice tensor-parallel degree (0 = auto mesh)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="gradient accumulation microbatches per step "
                         "(reference gradient_accumulation_steps); the "
                         "ring still moves ONE averaged gradient per step")
    common.add_lr_schedule_args(ap)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="every N steps, report mean loss over "
                         "--eval-batches held-out batches (reference "
                         "estimate_loss)")
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="save {params, opt_state} here every "
                         "--checkpoint-every steps and resume from the "
                         "newest snapshot (reference ckpt.pt save/resume)")
    ap.add_argument("--checkpoint-every", default=20,
                    type=lambda v: max(1, int(v)))
    ap.add_argument("--quantize", choices=["none", "minmax"], default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shm-staging", action="store_true",
                    help="stage the flat gradient in a registered shm buffer "
                         "(zero-copy ring when peers share this host)")
    common.add_data_args(ap)
    common.add_model_args(ap)
    args = ap.parse_args()

    common.start_jax()
    import jax
    import jax.numpy as jnp
    import optax

    from pccl_tpu.comm import DataType
    from pccl_tpu.parallel import mesh as mesh_lib
    from pccl_tpu.parallel.hierarchical import HierarchicalAllReduce
    from pccl_tpu.parallel.train import family

    comm = common.connect(args)

    # --- in-slice SPMD setup ---
    devices = jax.devices()
    if args.tp > 0:
        shape = (max(1, len(devices) // args.tp), args.tp)
        mesh = mesh_lib.make_mesh(devices[: shape[0] * shape[1]], ("dp", "tp"),
                                  shape)
    else:
        mesh = mesh_lib.make_mesh(devices, ("dp", "tp"))
    cfg = common.model_config(args, char_level=args.data == "text")
    model, sharding_fn = family(cfg)  # gpt or llama by config family
    param_sharding = sharding_fn(mesh, cfg)  # must match make_train_state's
    data_sharding = mesh_lib.batch_sharding(mesh)

    from pccl_tpu.parallel.train import make_train_state

    schedule = common.make_schedule(args, args.lr, args.steps)
    params, tx, opt_state = make_train_state(
        jax.random.PRNGKey(args.seed), cfg, mesh, lr=args.lr,
        schedule=schedule)

    base_lg = jax.value_and_grad(functools.partial(model.loss_fn, cfg=cfg))
    if args.grad_accum > 1:
        # tokens/targets arrive [A, B, T]; the shared library wrapper
        # (parallel/train.py:accum_value_and_grad) scans the microbatches
        # so one microbatch's activations are live at a time
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pccl_tpu.parallel.train import accum_value_and_grad

        data_sharding = NamedSharding(mesh, P(None, *data_sharding.spec))
        base_lg = accum_value_and_grad(base_lg, args.grad_accum)
    loss_and_grad = jax.jit(
        base_lg,
        in_shardings=(param_sharding, data_sharding, data_sharding),
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       out_shardings=(param_sharding, None))
    def apply(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    # --- cross-slice gradient averaging ---
    # params serve as the gradient template: same shapes/dtypes/shardings.
    # Factory, not a one-off: a KickedError recovery below reconnects and
    # needs a ring bound to the fresh communicator.
    def make_ring():
        return HierarchicalAllReduce(
            comm, params, quantization=common.quant_from_arg(args.quantize),
            quantized_dtype=DataType.UINT8, shm_staging=args.shm_staging)

    ring = make_ring()

    from pccl_tpu.utils.profiler import Profiler

    prof = Profiler(enabled=args.profile or bool(args.trace_out))
    next_batch = common.make_batch_fn(args, cfg.vocab_size)  # per-peer shard
    # background device prefetch: the H2D copy of batch k+1 overlaps the
    # device compute of batch k (pccl_tpu.utils.data)
    from pccl_tpu.utils.data import prefetch_to_device

    def _replicate_loose(tree):
        """Optimizer scalars (step counts) come back from checkpoint
        restore or shared-state adoption COMMITTED to a single device
        while params are mesh-sharded — one jit cannot mix the two device
        sets, so re-place any non-mesh-sharded leaf replicated."""
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda x: x if isinstance(getattr(x, "sharding", None),
                                      NamedSharding)
            else jax.device_put(x, mesh_lib.replicated(mesh)), tree)

    ckpt = None
    start = 0
    if args.checkpoint_dir:
        from pccl_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(args.checkpoint_dir)
        latest = ckpt.latest_step()
        if latest is not None:
            tree = ckpt.restore({"params": params, "opt_state": opt_state},
                                latest)
            params, opt_state = tree["params"], tree["opt_state"]
            opt_state = _replicate_loose(opt_state)
            start = latest
            # advance the deterministic data stream past the replayed
            # prefix — otherwise resumed steps retrain on the exact
            # batches steps [0, start) already consumed. MUST happen
            # before the prefetch thread below starts drawing.
            for _ in range(start * max(1, args.grad_accum)):
                next_batch()
            print(f"resumed from step {latest}", flush=True)

    def batches():
        while True:
            if args.grad_accum > 1:
                ms = [next_batch() for _ in range(args.grad_accum)]
                yield (np.stack([m[0] for m in ms]),
                       np.stack([m[1] for m in ms]))
            else:
                yield next_batch()

    feed = prefetch_to_device(batches(), size=2, sharding=data_sharding)

    # held-out eval (reference estimate_loss): the val split — a disjoint
    # tail slice of the text corpus (or a fresh synthetic stream, which is
    # held out by construction) — through a grad-free jitted loss
    eval_fn = eval_batch = None
    if args.eval_every > 0:
        eval_fn = jax.jit(functools.partial(model.loss_fn, cfg=cfg))
        eval_batch = common.make_batch_fn(args, cfg.vocab_size, split="val")

    # --- per-step shared-state sync (reference train_pccl.py keeps its
    # model+optimizer in the pccl shared state and syncs every step) ---
    # The DDP invariant is IDENTICAL params on every peer; topology alone
    # cannot keep it — a late joiner starts from seed params and a
    # checkpoint-resumed peer from its snapshot. The sync REVISION is the
    # master's strict one-increment counter, NOT the step: after the first
    # sync every peer offers info.revision + 1, and the step consensus
    # rides in the "ddp.step" entry. Revision equals step only on the
    # common path (a cohort that started together), and the first offer
    # depends on how this peer came up:
    #  * fresh start — offer revision 0 (a late joiner's 0 can never trip
    #    the master's `revision > last+1` kick; if the cohort is ahead the
    #    mismatch marks us outdated and we adopt params/opt/step below);
    #  * checkpoint resume into a possibly-initialized cohort — offering
    #    the snapshot step would be revision last+2-or-more and the master
    #    KICKS for it ("shared-state revision increment violation"; before
    #    this fix the retry loop below then spun forever on the dead
    #    conn). The first sync is instead a probe at revision 0 — in
    #    receive-only SPIRIT, but declared ENFORCE_POPULAR because the
    #    master's all-or-nothing mixing rule (reference parity) kicks a
    #    literal rx-only request alongside enforce-popular incumbents. A
    #    revision-0 enforce-popular offer is never kickable (0 <= last+1
    #    always) and never wins an election against revision-matched
    #    incumbents, so against an initialized cohort it degenerates to
    #    "adopt their params/opt/step"; in a whole-cohort restart (every
    #    member probing at 0) the popularity election converges everyone
    #    onto one checkpoint's content instead of kicking the round;
    #  * checkpoint resume running solo (world 1) — offer the snapshot
    #    step; the fresh master bootstraps at any first revision.
    # Cost note: without PCCLT_SS_HASH=simple-tpu the hash compare stages
    # every leaf to the host each step — fine for example scale; TPU
    # deployments set the env var group-wide so clean syncs ship 8 bytes
    # per entry instead (pccl_tpu.ops.hashing, TensorInfo.from_jax_device).
    import os as _os

    from pccl_tpu.comm import (KickedError, PcclError, SharedState,
                               SharedStateSyncStrategy, TensorInfo)

    _mk = (TensorInfo.from_jax_device
           if _os.environ.get("PCCLT_SS_HASH") == "simple-tpu"
           else TensorInfo.from_jax)

    sync_ctl = {"next_revision": None,  # None until the first sync lands
                "probe": start > 0}     # resumed: rx-only@0 first (see above)

    def sync_state(params, opt_state, step):
        leaves_p, tdef_p = jax.tree.flatten(params)
        leaves_o, tdef_o = jax.tree.flatten(opt_state)
        step_arr = np.array([step], dtype=np.uint64)
        entries = ([_mk(f"ddp.p{i}", l) for i, l in enumerate(leaves_p)]
                   + [_mk(f"ddp.o{i}", l) for i, l in enumerate(leaves_o)]
                   + [TensorInfo.from_numpy("ddp.step", step_arr)])
        probe = sync_ctl["probe"] and comm.world_size >= 2
        if probe:
            revision = 0  # adopt-the-cohort probe (see the comment above)
        else:
            revision = (sync_ctl["next_revision"]
                        if sync_ctl["next_revision"] is not None else step)
        strategy = SharedStateSyncStrategy.ENFORCE_POPULAR
        st = SharedState(entries, revision=revision)
        # churn mid-election: retry at the SAME revision until the survivor
        # group elects (grid_diloco.py's sync_with_retry contract). Training
        # through a failed sync would increment the offer and violate the
        # master's one-increment rule. A kick is terminal for this
        # communicator — surface it instead of spinning on a dead conn.
        while True:
            try:
                info = comm.sync_shared_state(st, strategy)
                break
            except KickedError:
                raise
            except PcclError:
                time.sleep(0.1)
                try:
                    if comm.are_peers_pending():
                        comm.update_topology()
                except KickedError:
                    raise
                except PcclError:
                    pass
        sync_ctl["next_revision"] = info.revision + 1
        sync_ctl["probe"] = False
        if info.rx_bytes:  # outdated: adopt the cohort's state
            n = len(leaves_p)
            params = jax.tree.unflatten(
                tdef_p, [e.jax_value() for e in entries[:n]])
            opt_state = _replicate_loose(jax.tree.unflatten(
                tdef_o, [e.jax_value() for e in entries[n:n + len(leaves_o)]]))
            step = int(step_arr[0])
            print(f"adopted shared state at step {step}", flush=True)
        return params, opt_state, step

    first_loss = last_loss = None
    step = start
    while step < args.steps:
        common.admit_pending(comm)
        if comm is not None:
            try:
                params, opt_state, step = sync_state(params, opt_state, step)
            except KickedError:
                # Safety net: a kick is terminal for the communicator (the
                # old code spun forever retrying on the dead conn). The
                # probe path above cannot be kicked, but a solo-resumed
                # peer whose cohort materialized mid-run, or a master-side
                # policy we did not anticipate, still can. Reconnect and
                # re-offer revision 0 enforce-popular — never kickable, so
                # this cannot loop; the election then converges us onto
                # the cohort's content (incl. its ddp.step).
                print("kicked during sync; reconnecting with revision-0 "
                      "enforce-popular offer", flush=True)
                try:
                    comm.destroy()
                except PcclError:
                    pass
                comm = common.connect(args)
                ring = make_ring()
                sync_ctl["probe"] = False
                sync_ctl["next_revision"] = 0
                continue
            if step >= args.steps:
                break
        tok, tgt = next(feed)
        with prof.section("fwd+bwd"):
            loss, grads = loss_and_grad(params, tok, tgt)
        with prof.section("ring/all_reduce"):
            grads = ring.all_reduce(grads)  # global mean (identity when solo)
        with prof.section("apply"):
            params, opt_state = apply(params, opt_state, grads)
        loss = float(loss)
        first_loss = first_loss if first_loss is not None else loss
        last_loss = loss
        world = comm.world_size if comm is not None else 1
        print(f"step {step} loss {loss:.4f} world {world}", flush=True)
        if eval_fn is not None and (step + 1) % args.eval_every == 0:
            vals = []
            for _ in range(args.eval_batches):
                et, ey = eval_batch()
                vals.append(float(eval_fn(params, jnp.asarray(et),
                                          jnp.asarray(ey))))
            print(f"eval step {step} loss {np.mean(vals):.4f}", flush=True)
        if ckpt is not None and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, {"params": params, "opt_state": opt_state})
        step += 1

    common.finish_profile(args, prof)
    return common.report_final(first_loss, last_loss, comm)


if __name__ == "__main__":
    sys.exit(main())
