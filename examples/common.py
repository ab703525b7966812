"""Shared plumbing for the example training loops.

Reference parity: the reference examples (/root/reference/python/examples/
nanogptddp/train_pccl.py, nanogpt_diloco/sync_diloco.py) share the same
skeleton — connect to the master, wait for the world, per-step topology
updates, retry on churn. Here that skeleton is TPU-first: every peer process
is one "slice" running a jitted SPMD step over its local device mesh, and
only the cross-slice hop rides the TCP ring.

The dataset is synthetic (zero-egress environment): token t+1 is an affine
function of token t plus rare noise, so next-token loss falls fast and
convergence is assertable in CI.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def add_comm_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--master-ip", default="127.0.0.1")
    ap.add_argument("--master-port", type=int, default=48500)
    ap.add_argument("--base-port", type=int, default=56000,
                    help="p2p/shared-state/bench listen ports (bump-allocated)")
    ap.add_argument("--min-world", type=int, default=1,
                    help="wait until this many peers joined before training")
    ap.add_argument("--peer-group", type=int, default=0)
    ap.add_argument("--connect-timeout", type=float, default=120.0,
                    help="seconds to wait for --min-world peers (raise when "
                         "many peers cold-start jax on a loaded host)")
    ap.add_argument("--solo", action="store_true",
                    help="run without a comm (single slice, no master)")


def connect(args):
    """Create + connect a Communicator and wait for --min-world peers.
    Returns None under --solo."""
    if args.solo:
        return None
    from pccl_tpu.comm import Communicator

    comm = Communicator(args.master_ip, args.master_port,
                        peer_group=args.peer_group,
                        p2p_port=args.base_port, ss_port=args.base_port + 4,
                        bench_port=args.base_port + 8)
    comm.connect()
    deadline = time.time() + getattr(args, "connect_timeout", 120.0)
    while comm.world_size < args.min_world:
        if time.time() > deadline:
            raise TimeoutError(f"world never reached {args.min_world}")
        if comm.are_peers_pending():
            comm.update_topology()
        time.sleep(0.02)
    return comm


def admit_pending(comm) -> None:
    """Between-steps topology vote (reference update-topology loop)."""
    if comm is not None and comm.are_peers_pending():
        comm.update_topology()


def synth_batch(rng: np.random.RandomState, batch: int, block: int,
                vocab: int):
    """Learnable synthetic LM data: x[t+1] = (5*x[t] + 7) % vocab, with 5%
    uniform noise. Returns (tokens, targets) int32 [B, T]."""
    x = np.empty((batch, block + 1), dtype=np.int64)
    x[:, 0] = rng.randint(0, vocab, size=batch)
    for t in range(block):
        x[:, t + 1] = (5 * x[:, t] + 7) % vocab
    noise = rng.rand(batch, block + 1) < 0.05
    x[noise] = rng.randint(0, vocab, size=int(noise.sum()))
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


_CORPUS = None


def text_corpus(max_bytes: int = 2 << 20) -> np.ndarray:
    """Real char-level corpus without network egress: concatenated Python
    standard-library sources (docstring-heavy English + code). This plays
    the role of the reference's real-dataset e2e runs (mnist_ddp /
    mnist_diloco, /root/reference/python/tests/end_to_end/) — genuine,
    structured data rather than a synthetic token rule. Byte-level,
    vocab 256, deterministic file order."""
    global _CORPUS
    if _CORPUS is not None:
        return _CORPUS
    import sysconfig
    from pathlib import Path

    stdlib = Path(sysconfig.get_paths()["stdlib"])
    buf = bytearray()
    for f in sorted(stdlib.glob("*.py")):
        try:
            buf += f.read_bytes()
        except OSError:
            continue
        if len(buf) >= max_bytes:
            break
    assert len(buf) > 64 * 1024, "stdlib corpus unexpectedly small"
    _CORPUS = np.frombuffer(bytes(buf[:max_bytes]), dtype=np.uint8)
    return _CORPUS


def text_batch(corpus: np.ndarray, rng: np.random.RandomState, batch: int,
               block: int):
    """Random contiguous char windows -> (tokens, targets) int32 [B, T].
    (Direct sampler; make_batch_fn routes the text path through the
    library's TokenDataset instead.)"""
    idx = rng.randint(0, len(corpus) - block - 1, size=batch)
    x = np.stack([corpus[i:i + block + 1] for i in idx])
    return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)


def add_data_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--data", choices=["synthetic", "text"],
                    default="synthetic",
                    help="synthetic affine tokens, or real char-level text "
                         "(python stdlib sources)")


def add_model_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--model", default="nano",
                    help="model preset: nano (CI default) | tiny | gpt2 | "
                         "gpt2-medium | gpt2-large | gpt2-xl "
                         "(pccl_tpu.models.gpt.PRESETS); with "
                         "--family llama: nano | tiny | 700m | 1b | 7b | 8b")
    ap.add_argument("--family", choices=["gpt", "llama"], default="gpt",
                    help="model family (pccl_tpu.models)")
    ap.add_argument("--profile", action="store_true",
                    help="print a per-section time table at the end "
                         "(pccl_tpu.utils.profiler)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON of the run")


def model_config(args, *, char_level: bool):
    """Model config from --family and the --model preset, with --block as
    the sequence length; char-level text data caps the vocab at 256 bytes."""
    from pccl_tpu.models import gpt, llama

    family = gpt if getattr(args, "family", "gpt") == "gpt" else llama
    overrides = {"block_size": args.block}
    if char_level:
        overrides["vocab_size"] = 256
    return family.named_config(args.model, **overrides)


def finish_profile(args, prof) -> None:
    if prof is None:
        return
    if args.trace_out:
        prof.export_chrome_trace(args.trace_out)
        print(f"trace written to {args.trace_out}", flush=True)
    if args.profile:
        print(prof.summary(), flush=True)


def make_batch_fn(args, vocab: int, split: str = "train"):
    """Per-peer batch sampler for the chosen dataset; the shard is keyed
    off the peer's base port either way. The text path samples through the
    library's TokenDataset (random-crop next-token pairs, disjoint stream
    per worker_index); split="val" crops a DISJOINT tail 10% of the corpus
    (the reference's train.bin/val.bin estimate_loss split) — a different
    rng stream alone would still sample the training text. The synthetic
    rule is the distribution itself, so there a fresh stream IS held out."""
    if getattr(args, "data", "synthetic") == "text":
        from pccl_tpu.utils.data import TokenDataset

        corpus = text_corpus()
        cut = int(len(corpus) * 0.9)
        corpus = corpus[cut:] if split == "val" else corpus[:cut]
        ds = TokenDataset(corpus, args.block, args.batch,
                          seed=1000 if split == "train" else 7919,
                          worker_index=args.base_port % 997)
        return ds.sample
    rng = data_rng(args) if split == "train" else \
        np.random.RandomState(7919 + (args.base_port % 997))
    return lambda: synth_batch(rng, args.batch, args.block, vocab)


def quant_from_arg(name: str):
    """Map the --quantize CLI choice to a QuantizationAlgorithm."""
    from pccl_tpu.comm import QuantizationAlgorithm

    return {"none": QuantizationAlgorithm.NONE,
            "minmax": QuantizationAlgorithm.MIN_MAX,
            "zps": QuantizationAlgorithm.ZERO_POINT_SCALE}[name]


def data_rng(args) -> np.random.RandomState:
    """Per-peer data shard: seeded off the peer's unique base port."""
    return np.random.RandomState(1000 + (args.base_port % 997))


def report_final(first_loss, last_loss, comm) -> int:
    """Print the FINAL line (parsed by tests/test_examples_e2e.py) and
    return the process exit code (0 = loss decreased). None losses mean no
    step ran (e.g. a checkpoint resume at/past --outer-steps) — report
    cleanly and exit 0."""
    # FINAL goes out BEFORE destroy: a churn-wedged teardown must not
    # suppress the result line the e2e harness parses
    if first_loss is None or last_loss is None:
        print("FINAL no steps ran (resumed at or past the step budget)",
              flush=True)
        code = 0
    else:
        print(f"FINAL first_loss={first_loss:.4f} last_loss={last_loss:.4f}",
              flush=True)
        code = 0 if last_loss < first_loss else 4
    if comm is not None:
        comm.destroy()
    return code


def start_jax() -> None:
    """An example's first touch of jax: place the compile cache
    (pccl_tpu.utils.compile_cache) and say which devices this process now
    holds. A TPU chip belongs to ONE process, so a second example started on
    the same host must be given other devices (e.g. JAX_PLATFORMS=cpu)."""
    import jax

    from pccl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    print(f"jax: platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} count={len(devs)}", flush=True)


def add_lr_schedule_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--lr-schedule", choices=["const", "cosine"],
                    default="const",
                    help="cosine = linear warmup then cosine decay to "
                         "--min-lr over the run (reference get_lr)")
    ap.add_argument("--warmup-steps", type=int, default=0)
    ap.add_argument("--min-lr", type=float, default=0.0)


def make_schedule(args, peak_lr: float, total_steps: int, offset: int = 0):
    """The --lr-schedule CLI -> an optax schedule (or None for const).
    offset shifts the schedule's step count — a resumed run continues the
    decay from where it left off instead of rerunning warmup (the inner
    optimizer state, including its step count, is rebuilt fresh on
    resume)."""
    if getattr(args, "lr_schedule", "const") != "cosine":
        return None
    from pccl_tpu.parallel.train import cosine_warmup_schedule

    base = cosine_warmup_schedule(peak_lr, total_steps, args.warmup_steps,
                                  args.min_lr)
    if not offset:
        return base
    return lambda count: base(count + offset)
