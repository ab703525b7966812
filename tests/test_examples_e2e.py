"""End-to-end: the example training loops over a real master + peer processes.

Reference parity: the reference's subprocess-orchestrated e2e tests
(/root/reference/python/tests/end_to_end/ — basic reduce, mnist_ddp,
mnist_diloco convergence) — a pytest launches a master + N peer OS processes
on loopback and asserts exit codes. Dataset here is synthetic (zero-egress).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
LIB = REPO / "pccl_tpu" / "native" / "build" / "libpcclt.so"
pytestmark = pytest.mark.skipif(not LIB.exists(), reason="native lib not built")

from conftest import alloc_ports as _next_port


def _peer_env() -> dict:
    env = dict(os.environ)
    # each peer process = one "slice" with a small virtual CPU mesh
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    # tests run without the persistent compile cache the examples enable
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def _run_example(script: Path, n_peers: int, extra: list[str],
                 timeout: float = 600):
    from pccl_tpu.comm import MasterNode

    master = MasterNode("0.0.0.0", _next_port())
    master.run()
    procs = []
    try:
        base = _next_port(span=64 * n_peers)
        for r in range(n_peers):
            # same --seed everywhere: peers must start from identical params
            # (data shards already differ via the per-peer base-port rng)
            cmd = [sys.executable, str(script),
                   "--master-port", str(master.port),
                   "--base-port", str(base + r * 16),
                   "--min-world", str(n_peers)] + extra
            procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                          stderr=subprocess.STDOUT, text=True,
                                          env=_peer_env()))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
        for p, out in zip(procs, outs):
            assert p.returncode == 0, f"{script.name} peer failed:\n{out[-2000:]}"
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        master.interrupt()
        master.destroy()


def _final_losses(out: str):
    for ln in out.splitlines():
        if ln.startswith("FINAL"):
            parts = dict(kv.split("=") for kv in ln.split()[1:])
            return float(parts["first_loss"]), float(parts["last_loss"])
    raise AssertionError(f"no FINAL line in output:\n{out[-2000:]}")


def test_nanogpt_ddp_two_peers():
    outs = _run_example(REPO / "examples" / "nanogpt_ddp" / "train_ddp.py", 2,
                        ["--steps", "10", "--batch", "4"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "world 2" in out  # actually trained together


def test_sync_diloco_two_peers():
    outs = _run_example(
        REPO / "examples" / "nanogpt_diloco" / "sync_diloco.py", 2,
        ["--outer-steps", "4", "--inner-steps", "5", "--batch", "4"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "world 2" in out


def test_async_diloco_two_peers():
    outs = _run_example(
        REPO / "examples" / "nanogpt_diloco" / "async_diloco.py", 2,
        ["--outer-steps", "5", "--inner-steps", "5", "--batch", "4"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "world 2" in out


# --- real-data convergence (reference: mnist_ddp / mnist_diloco e2e) ---
# char-level LM on real text (python stdlib sources, common.text_corpus);
# the model must actually LEARN — a substantial loss drop is asserted, not
# just any decrease. Solo calibration: 5.66 -> 2.80 in 60 steps.


def test_nanogpt_ddp_chars_convergence():
    outs = _run_example(
        REPO / "examples" / "nanogpt_ddp" / "train_ddp.py", 2,
        ["--data", "text", "--steps", "40", "--batch", "8", "--lr", "3e-3"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first - 1.0, f"insufficient learning: {first} -> {last}"
        assert "world 2" in out


def test_sync_diloco_chars_convergence():
    # --shm-staging: the real-training loop also exercises the registered
    # zero-copy transport (peers share this host)
    outs = _run_example(
        REPO / "examples" / "nanogpt_diloco" / "sync_diloco.py", 2,
        ["--data", "text", "--outer-steps", "5", "--inner-steps", "10",
         "--batch", "8", "--inner-lr", "3e-3", "--shm-staging"])
    for out in outs:
        first, last = _final_losses(out)
        # first_loss is captured after warmup inside the first outer round,
        # so the visible drop is smaller than DDP's full-curve drop
        assert last < first - 0.5, f"insufficient learning: {first} -> {last}"
        assert "world 2" in out


def test_llama_diloco_chars_convergence():
    """Family parity for the flagship e2e: llama must LEARN through the
    full DiLoCo loop (inner AdamW + pseudo-gradient ring + outer Nesterov)
    on real text, with the same substantial-drop bound as the GPT twin —
    not just `last < first`. Proves the second family rides the whole
    training substrate, not only the DDP demo."""
    # The heaviest example e2e (2 llama peers x 150 steps) is sensitive
    # to full-suite host load (a descheduled peer can get churn-kicked on
    # a 1-core box); one retry absorbs that while the learning bound
    # itself stays strict — it passes solo deterministically.
    for attempt in (1, 2):
        try:
            outs = _run_example(
                REPO / "examples" / "nanogpt_diloco" / "sync_diloco.py", 2,
                ["--family", "llama", "--data", "text", "--outer-steps", "5",
                 "--inner-steps", "30", "--batch", "8", "--inner-lr", "3e-3"])
            for out in outs:
                first, last = _final_losses(out)
                # llama-nano descends fast then grinds: by the time the
                # first loss is reported (after the first outer round's 30
                # inner steps) it is already ~2.8-3.2, so a fixed DELTA
                # bound would reward stopping early. Assert the absolute
                # level instead: 2.7 is well below the first report and
                # only reachable by learning through the full run
                # (calibrated 2.35-2.41; cold start is ~5.5).
                assert last < 2.7, f"insufficient learning: {first} -> {last}"
                assert last < first, f"loss rose: {first} -> {last}"
                assert "world 2" in out
            return
        except AssertionError:
            if attempt == 2:
                raise
            print("retrying llama convergence e2e after a load-flaky run",
                  flush=True)


def test_llama_ddp_two_peers():
    """The llama family rides the same DDP loop end-to-end (--family
    dispatches model init/loss and the tensor-parallel sharding rules)."""
    outs = _run_example(REPO / "examples" / "nanogpt_ddp" / "train_ddp.py", 2,
                        ["--family", "llama", "--steps", "10", "--batch", "4"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "world 2" in out


def test_nanogpt_ddp_grad_accum():
    """--grad-accum 2: the loop scans 2 microbatches per step and still
    moves ONE averaged gradient over the ring (reference
    gradient_accumulation_steps)."""
    outs = _run_example(REPO / "examples" / "nanogpt_ddp" / "train_ddp.py", 2,
                        ["--steps", "8", "--batch", "4", "--grad-accum", "2"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "world 2" in out


def test_nanogpt_ddp_schedule_and_eval():
    """--lr-schedule cosine + periodic held-out eval (reference get_lr +
    estimate_loss): the run trains and emits eval lines from a disjoint
    data stream."""
    outs = _run_example(
        REPO / "examples" / "nanogpt_ddp" / "train_ddp.py", 2,
        ["--steps", "10", "--batch", "4", "--lr-schedule", "cosine",
         "--warmup-steps", "2", "--eval-every", "5"])
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "eval step 4 loss" in out and "eval step 9 loss" in out


def test_nanogpt_ddp_checkpoint_resume(tmp_path):
    """Checkpoint + resume in the DDP loop (reference ckpt.pt save/resume):
    a second invocation picks up params/opt_state at the newest snapshot
    and runs only the remaining steps."""
    script = REPO / "examples" / "nanogpt_ddp" / "train_ddp.py"
    base = [sys.executable, str(script), "--solo", "--batch", "4",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--checkpoint-every", "3"]
    r1 = subprocess.run(base + ["--steps", "6"], capture_output=True,
                        text=True, env=_peer_env(), timeout=300)
    assert r1.returncode == 0, r1.stdout[-2000:] + r1.stderr[-2000:]
    r2 = subprocess.run(base + ["--steps", "9"], capture_output=True,
                        text=True, env=_peer_env(), timeout=300)
    assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
    assert "resumed from step 6" in r2.stdout
    assert "step 6 " in r2.stdout and "step 8 " in r2.stdout
    assert "step 5 " not in r2.stdout  # did NOT redo pre-resume steps


def test_nanogpt_ddp_late_join_adopts_state():
    """A peer joining mid-run must ADOPT the cohort's params/opt/step via
    the per-step shared-state election (reference train_pccl.py keeps its
    model in the pccl shared state for exactly this) — not ring-average
    its seed params against a trained model."""
    from pccl_tpu.comm import MasterNode

    master = MasterNode("0.0.0.0", _next_port())
    master.run()
    script = REPO / "examples" / "nanogpt_ddp" / "train_ddp.py"
    base = _next_port(span=64)

    def spawn(port, extra):
        cmd = [sys.executable, str(script), "--master-port", str(master.port),
               "--base-port", str(port), "--steps", "400", "--batch", "4",
               "--block", "128", "--connect-timeout", "300"] + extra
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True,
                                env=_peer_env())
    # deterministic gate: spawn B only once A's own output shows training
    # under way (a fixed sleep races A finishing all steps on a fast box —
    # 400 steps at block 128 gives B's cold jax start a wide window)
    import threading

    a = spawn(base, ["--min-world", "1"])
    a_lines = []
    pump = threading.Thread(
        target=lambda: a_lines.extend(iter(a.stdout.readline, "")),
        daemon=True)
    pump.start()
    deadline = time.time() + 300
    while not any(ln.startswith("step 5 ") for ln in a_lines):
        assert time.time() < deadline and a.poll() is None, \
            "A never reached step 5:\n" + "".join(a_lines)[-3000:]
        time.sleep(0.2)
    b = spawn(base + 16, ["--min-world", "2"])
    try:
        b_out, _ = b.communicate(timeout=420)
        assert b.returncode == 0, b_out[-3000:]
        a.wait(timeout=420)
        pump.join(timeout=10)
        a_out = "".join(a_lines)
        assert a.returncode == 0, a_out[-3000:]
        outs = [a_out, b_out]
    finally:
        for p in (a, b):
            if p.poll() is None:
                p.kill()
        master.interrupt()
        master.destroy()
    # B adopted a nonzero step from the election instead of starting at 0
    import re

    m = re.search(r"adopted shared state at step (\d+)", outs[1])
    assert m and int(m.group(1)) > 0, outs[1][-3000:]
    assert "world 2" in outs[0]
