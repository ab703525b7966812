"""2D-grid DiLoCo: sharded outer state × per-shard rings (FSDP × PCCL).

Reference parity: /root/reference/python/examples/nanogpt_diloco/
sync_diloco_fsdp.py (peer group = FSDP shard index, shared state = the local
shard of outer params + momentum, grid-fullness gate) and the footguns doc
/root/reference/docs/md/8_CommonFootguns.md:4-100 (the 2D matrix of FSDP
ranks × PCCL dynamic membership, `global < fsdp_world × largest_group` →
wait, and the memory-mapping recipe for same-host shard exchange).

The grid, TPU-first. Each process is one cell (shard g, replica r):

                     ring (comm, peer group = g)
                 ┌───────────────┬───────────────┐
    shard 0      │ cell (0, 0)   │ cell (0, 1)   │  ← group 0 ring averages
                 ├───────────────┼───────────────┤    pseudo-grad shard 0
    shard 1      │ cell (1, 0)   │ cell (1, 1)   │  ← group 1 ring averages
                 └───────────────┴───────────────┘    pseudo-grad shard 1
                    replica 0       replica 1
                 └── column = one host, shards exchanged via grid file ──┘

- INTRA-CELL: the model itself is sharded over the cell's local device mesh
  (tensor-parallel axis; XLA inserts the ICI collectives). This replaces the
  reference's cross-process NCCL/FSDP dimension — on TPU the fast
  interconnect is inside the slice, so the heavy per-inner-step sharding
  stays in-process where it costs nothing to coordinate.
- CROSS-REPLICA: the flat fp32 outer state is split into `--num-shards`
  contiguous shards. A cell's SHARED STATE (and its ring traffic) is only
  its own shard — each ring carries 1/G of the bytes, exactly the
  reference's per-rank sharding of the outer reduce.
- CROSS-SHARD (same column/host): groups publish their updated shard into a
  mapped grid file (`--grid-file`, one per host); cells assemble the full
  outer vector from it before each inner phase. This is the footguns doc's
  recommended memory-mapping alternative to cross-process FSDP gathers.

Grid-fullness gate (the FSDP×PCCL deadlock footgun): no cell may start an
outer iteration until `global_world == num_shards × largest_group` — a
partially-joined column would wedge its groups' rings, so everyone admits
and waits until the grid is rectangular.

Consistency: the ring average is bitwise identical on every member, and the
outer SGD on a shard is deterministic host arithmetic from ring output +
previous shard — so a shard's content stays bit-identical across its group
(the shared-state hash check passes with rx_bytes=0). Adjacent groups may
run at most ONE outer step apart (a cell at step s only needs every shard
at ≥ s), so a cell can observe a neighbor shard one step newer — harmless
drift in inner INIT only, never in shared state.

Fault tolerance, per the reference's own caveat (footguns doc §"Reduced
fault tolerance"): the COLUMN is the failure unit. If one cell dies, the
grid is no longer rectangular and every cell holds at the fullness gate
until the dead cell's column-mates are also gone (or a replacement joins) —
exactly the reference's behavior, where a dead GPU takes its whole FSDP
column down via the NCCL timeout. When an entire column dies, each group's
ring retries down to the survivor world and training continues.

Run (2 shards × 2 replicas, one host):
    python -m pccl_tpu.comm.master --port 48500 &
    for g in 0 1; do for r in 0 1; do
        python examples/grid_fsdp/grid_diloco.py --master-port 48500 \
            --num-shards 2 --peer-group $g --base-port $((56000+g*200+r*100)) \
            --min-replicas 2 &
    done; done
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))

import numpy as np

import common


class GridFile:
    """Per-host mapped exchange of outer-state shards.

    Layout: int64 [magic, num_shards, count] identity header, then int64[G]
    sequence header (outer step of each shard's content, -1 = never
    written), then the float32[count] full outer vector. Writers publish
    data-then-seq; readers wait for every seq ≥ their step. Same-host mmap
    coherence makes this ordering sufficient (this file never crosses
    hosts — each column has its own).

    Lifecycle: the file is scoped to ONE run — every cell unlinks it on
    clean exit (`remove`, idempotent), and an incompatible pre-existing
    file (wrong shape/magic — e.g. a crashed run with a different model or
    shard count) is a LOUD error, never attached. A crashed run of the
    same shape must be cleaned up by the launcher (`rm <grid-file>`); its
    stale sequence numbers cannot be told apart from a live cohort's."""

    MAGIC = 0x70636C74_67726964  # "pclt" "grid"
    MAGIC_FILL = -1
    _HDR = 3  # identity int64s before the per-shard sequence header

    def __init__(self, path: str, num_shards: int, count: int):
        self.path = path
        self.g = num_shards
        self.count = count
        nbytes = 8 * (self._HDR + num_shards) + 4 * count
        if not os.path.exists(path):
            # initialize privately, then hardlink into place: the file
            # appears ATOMICALLY with identity + -1 sentinels set, so a
            # racing attacher can never read a zero-filled header (seq 0
            # would claim step-0 content that was never published)
            tmp = f"{path}.init.{os.getpid()}"
            mm = np.memmap(tmp, dtype=np.uint8, mode="w+", shape=(nbytes,))
            hdr = mm[:8 * self._HDR].view(np.int64)
            hdr[0], hdr[1], hdr[2] = self.MAGIC, num_shards, count
            mm[8 * self._HDR:8 * (self._HDR + num_shards)].view(
                np.int64)[:] = self.MAGIC_FILL
            mm.flush()
            del mm
            try:
                os.link(tmp, path)
            except FileExistsError:
                pass  # another cell won the race — validate + attach below
            finally:
                os.unlink(tmp)
        if os.path.getsize(path) != nbytes:
            raise RuntimeError(
                f"stale/incompatible grid file {path} "
                f"({os.path.getsize(path)} bytes, want {nbytes}) — remove "
                "it; grid files are scoped to one run")
        self._mm = np.memmap(path, dtype=np.uint8, mode="r+", shape=(nbytes,))
        hdr = self._mm[:8 * self._HDR].view(np.int64)
        if not (hdr[0] == self.MAGIC and hdr[1] == num_shards
                and hdr[2] == count):
            raise RuntimeError(
                f"grid file {path} identity mismatch "
                f"(magic/shards/count = {list(hdr)}, want "
                f"[{self.MAGIC}, {num_shards}, {count}]) — remove it")
        self.seq = self._mm[8 * self._HDR:
                            8 * (self._HDR + num_shards)].view(np.int64)
        self.vec = self._mm[8 * (self._HDR + num_shards):].view(np.float32)
        self.bounds = [count * i // num_shards for i in range(num_shards + 1)]

    def remove(self) -> None:
        """Best-effort end-of-run unlink (idempotent across cells; mapped
        views of same-run laggards stay valid on the unlinked inode)."""
        try:
            os.unlink(self.path)
        except OSError:
            pass

    def publish(self, shard: int, step: int, data: np.ndarray) -> None:
        lo, hi = self.bounds[shard], self.bounds[shard + 1]
        self.vec[lo:hi] = data
        self._mm.flush()  # data lands before the sequence tick
        self.seq[shard] = step

    def wait_all(self, step: int, timeout: float = 300.0) -> None:
        deadline = time.time() + timeout
        while bool(np.any(self.seq < step)):
            if time.time() > deadline:
                raise TimeoutError(
                    f"grid shards stuck below step {step}: {list(self.seq)}")
            time.sleep(0.002)

    def read_full(self) -> np.ndarray:
        return np.array(self.vec, dtype=np.float32)


def wait_grid_full(comm, num_shards: int, ever_full: bool = False,
                   grid: "GridFile" = None, step: int = 0,
                   timeout: float = 300.0) -> None:
    """Admit pending peers until the grid is rectangular (footguns doc:
    proceed only when global == num_shards × largest group).

    ``ever_full``: once a cell has seen the full grid, a whole shard group
    VANISHING no longer blocks the gate — but only when the departed
    group's grid-file seq already covers this cell's current ``step``
    (groups may finish their final outer step one iteration apart, and a
    faster group that completed and left must not strand the lagging
    group; its terminal shard is already published). A group that CRASHED
    mid-run has stale seq entries, so the gate keeps holding for a
    replacement column instead of sailing into wait_all's timeout. During
    bootstrap (never yet full) the strict rectangularity condition
    stands."""
    deadline = time.time() + timeout
    while True:
        if comm.are_peers_pending():
            comm.update_topology()
        if comm.global_world_size == num_shards * comm.largest_peer_group:
            return
        if ever_full and comm.num_peer_groups < num_shards and (
                grid is None or bool(np.all(grid.seq >= step))):
            return  # a group finished its run and left — don't wait for it
        if time.time() > deadline:
            raise TimeoutError("grid never filled (a column is incomplete)")
        time.sleep(0.05)


def sync_with_retry(comm, state) -> None:
    """sync_shared_state with the reference's churn-retry loop around it
    (sync_diloco_fsdp.py retries the sync until the survivor group elects)."""
    from pccl_tpu.comm import PcclError

    while True:
        try:
            comm.sync_shared_state(state)
            return
        except PcclError:
            time.sleep(0.1)
            if comm.are_peers_pending():
                comm.update_topology()


def ring_average_shard(comm, shard: np.ndarray) -> None:
    """In-place AVG of `shard` across the cell's peer group, retrying over
    the survivor world on churn (reference all_reduce_multiple_with_retry
    pattern). Alone in the group → own value is the average."""
    from pccl_tpu.comm import PcclError, ReduceOp, TooFewPeersError

    try:
        comm.all_reduce(shard, op=ReduceOp.AVG)
        return
    except TooFewPeersError:
        return
    except PcclError:
        pass
    while True:
        try:
            comm.update_topology()
            comm.all_reduce_multiple_with_retry([shard], op=ReduceOp.AVG)
            return
        except TooFewPeersError:
            return
        except PcclError:
            time.sleep(0.1)


def main() -> int:
    ap = argparse.ArgumentParser()
    common.add_comm_args(ap)
    ap.add_argument("--num-shards", type=int, default=2,
                    help="outer-state shards = peer groups = grid rows; "
                         "--peer-group selects this cell's shard")
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="wait until this cell's group has this many peers")
    ap.add_argument("--grid-file", default=None,
                    help="per-host mapped shard-exchange file "
                         "(default /dev/shm keyed by master port)")
    ap.add_argument("--outer-steps", type=int, default=8,
                    help="terminal shared-state revision (joiners resume "
                         "from the synced revision and run the remainder)")
    ap.add_argument("--inner-steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--inner-lr", type=float, default=1e-3)
    ap.add_argument("--outer-lr", type=float, default=0.7)
    ap.add_argument("--outer-momentum", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    common.add_data_args(ap)
    common.add_model_args(ap)
    args = ap.parse_args()
    if args.solo:
        raise SystemExit("the grid example needs a comm (no --solo)")
    g = args.peer_group
    assert 0 <= g < args.num_shards, "--peer-group must be < --num-shards"

    common.start_jax()
    import jax

    from pccl_tpu.comm import SharedState, TensorInfo
    from pccl_tpu.parallel import codec as codec_lib
    from pccl_tpu.parallel import mesh as mesh_lib, train as train_lib

    # intra-cell sharding: the model is tensor-parallel over the local
    # mesh. Built BEFORE connect(): once admitted, this cell owes topology
    # votes to the group, and a half-minute of XLA compilation between
    # admission and the first vote would stall everyone's update_topology.
    mesh = mesh_lib.make_mesh(jax.devices(), ("dp", "tp"))
    cfg = common.model_config(args, char_level=args.data == "text")
    params, tx, opt_state = train_lib.make_train_state(
        jax.random.PRNGKey(args.seed), cfg, mesh, lr=args.inner_lr)
    step_fn = train_lib.build_train_step(cfg, tx, mesh)
    data_sharding = mesh_lib.batch_sharding(mesh)
    shardings = codec_lib.leaf_shardings(params)
    codec = codec_lib.build_codec(params)

    # min-world gates the cell's OWN group; the grid gate below handles
    # the cross-group (column-completeness) condition
    args.min_world = max(args.min_world, args.min_replicas)
    comm = common.connect(args)

    path = args.grid_file or f"/dev/shm/pcclt_grid_{args.master_port}.bin"
    grid = GridFile(path, args.num_shards, codec.count)
    lo, hi = grid.bounds[g], grid.bounds[g + 1]

    # this cell's slice of the outer state: its shard of the flat params
    # (identical across cells at init — same seed) + the shard's momentum
    outer_full = np.asarray(jax.device_get(codec.flat(params)),
                            dtype=np.float32)
    own_shard = np.array(outer_full[lo:hi])
    momentum = np.zeros(hi - lo, dtype=np.float32)
    step_arr = np.zeros(1, dtype=np.uint64)
    lr, mu = args.outer_lr, args.outer_momentum

    next_batch = common.make_batch_fn(args, cfg.vocab_size)
    first_loss = last_loss = None
    step = 0
    ever_full = False
    while step < args.outer_steps:
        wait_grid_full(comm, args.num_shards, ever_full, grid=grid, step=step)
        ever_full = True

        # shard-g shared state: joiners adopt the group's shard + revision
        step_arr[0] = step
        st = SharedState([
            TensorInfo.from_numpy("grid.outer_shard", own_shard),
            TensorInfo.from_numpy("grid.outer_momentum", momentum),
            TensorInfo.from_numpy("grid.step", step_arr),
        ], revision=step)
        sync_with_retry(comm, st)
        step = int(step_arr[0])
        if step >= args.outer_steps:
            grid.publish(g, step, own_shard)  # column-mates may still wait
            break

        # column exchange: publish shard g, assemble the full outer vector
        grid.publish(g, step, own_shard)
        grid.wait_all(step)
        outer_full = grid.read_full()
        params = codec_lib.restore_shardings(
            codec.unflat(jax.device_put(outer_full)), shardings)

        # inner phase: H jitted SPMD steps on the local tensor-parallel mesh
        import jax.numpy as jnp
        for _ in range(args.inner_steps):
            tok, tgt = next_batch()
            tok = jax.device_put(jnp.asarray(tok), data_sharding)
            tgt = jax.device_put(jnp.asarray(tgt), data_sharding)
            params, opt_state, loss = step_fn(params, opt_state, tok, tgt)

        # outer step, shard g only: ring-average the pseudo-gradient across
        # the group, then deterministic Nesterov SGD on the shard
        inner_flat = np.asarray(jax.device_get(codec.flat(params)),
                                dtype=np.float32)
        delta = outer_full[lo:hi] - inner_flat[lo:hi]
        ring_average_shard(comm, delta)
        momentum = mu * momentum + delta
        own_shard = outer_full[lo:hi] - lr * (delta + mu * momentum)
        step += 1
        grid.publish(g, step, own_shard)

        loss = float(loss)
        first_loss = first_loss if first_loss is not None else loss
        last_loss = loss
        print(f"outer {step} loss {loss:.4f} "
              f"grid {args.num_shards}x{comm.largest_peer_group} "
              f"global {comm.global_world_size} shard {g} "
              f"[{lo}:{hi}]", flush=True)

    code = common.report_final(first_loss, last_loss, comm)
    grid.remove()  # file is scoped to this run
    return code


if __name__ == "__main__":
    sys.exit(main())
