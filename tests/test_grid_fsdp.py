"""End-to-end: the 2D-grid (FSDP × PCCL) example over a real master.

Reference parity: the grid pattern of /root/reference/python/examples/
nanogpt_diloco/sync_diloco_fsdp.py and the footguns doc
(/root/reference/docs/md/8_CommonFootguns.md:4-100) — peer group = shard
index, grid-fullness gate, reduced fault tolerance caveat. Cells are OS
processes on loopback; each runs a 2-device virtual CPU mesh (intra-cell
tensor sharding), so the full composition — in-mesh XLA collectives ×
per-shard TCP rings × mapped-file column exchange — is exercised.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
LIB = REPO / "pccl_tpu" / "native" / "build" / "libpcclt.so"
SCRIPT = REPO / "examples" / "grid_fsdp" / "grid_diloco.py"
pytestmark = pytest.mark.skipif(not LIB.exists(), reason="native lib not built")

from conftest import alloc_ports as _next_port


def _cell_env() -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    # tests run without the persistent compile cache the examples enable
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def _spawn_cell(master_port: int, shard: int, base_port: int,
                grid_file: str, num_shards: int = 2, min_replicas: int = 1,
                outer_steps: int = 4, extra: list[str] = ()) -> subprocess.Popen:
    cmd = [sys.executable, str(SCRIPT),
           "--master-port", str(master_port),
           "--num-shards", str(num_shards), "--peer-group", str(shard),
           "--base-port", str(base_port), "--grid-file", grid_file,
           "--min-replicas", str(min_replicas),
           "--outer-steps", str(outer_steps),
           "--inner-steps", "4", "--batch", "4", "--block", "32",
           # 4 cells cold-start jax on one loaded core: joining can take
           # minutes of wall, so the world-wait must outlast it
           "--connect-timeout", "600",
           *extra]
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env=_cell_env())


def _finish(proc: subprocess.Popen, timeout: float = 420) -> str:
    out, _ = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, f"grid cell failed:\n{out[-3000:]}"
    return out


def _final_losses(out: str):
    for ln in out.splitlines():
        if ln.startswith("FINAL first"):
            parts = dict(kv.split("=") for kv in ln.split()[1:])
            return float(parts["first_loss"]), float(parts["last_loss"])
    raise AssertionError(f"no FINAL line:\n{out[-3000:]}")


@pytest.fixture
def master():
    from pccl_tpu.comm import MasterNode

    m = MasterNode("0.0.0.0", _next_port())
    m.run()
    yield m
    m.interrupt()
    m.destroy()


@pytest.fixture
def grid_file(tmp_path):
    return str(tmp_path / "grid.bin")


def test_grid_2x2_trains(master, grid_file):
    """Full rectangular grid: 2 shard groups × 2 replicas. Every cell must
    see the complete grid, train, and end at the same revision."""
    base = _next_port(span=16 * 4)
    procs = [_spawn_cell(master.port, g, base + (g * 2 + r) * 16, grid_file,
                         min_replicas=2)
             for g in (0, 1) for r in (0, 1)]
    try:
        outs = [_finish(p) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
        assert "grid 2x2 global 4" in out  # the full grid actually formed


def test_grid_late_column_join(master, grid_file):
    """A second replica column joins mid-run: the grid gate holds the run
    open until BOTH cells of the new column are admitted (the footgun this
    pattern exists for), then joiners adopt the group's shard + revision and
    everyone terminates at the same revision."""
    base = _next_port(span=16 * 4)
    incumbents = [_spawn_cell(master.port, g, base + g * 16, grid_file,
                              outer_steps=6) for g in (0, 1)]
    time.sleep(12)  # incumbents make progress as a 2x1 grid first
    joiners = [_spawn_cell(master.port, g, base + (2 + g) * 16, grid_file,
                           outer_steps=6) for g in (0, 1)]
    procs = incumbents + joiners
    try:
        outs = [_finish(p) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        first, last = _final_losses(out)
        assert last < first
    # the joined grid was observed rectangular at width 2 by some cell
    assert any("grid 2x2 global 4" in out for out in outs)


def test_grid_survives_killed_column(master, grid_file):
    """SIGKILL an entire replica column mid-run — the grid's failure unit
    (footguns doc: a dead GPU takes its whole FSDP column down). Once the
    master kicks the dead cells the grid is rectangular at width 1 again;
    each group's ring retries down to its survivor and column 0 finishes."""
    base = _next_port(span=16 * 4)
    procs = [_spawn_cell(master.port, g, base + (g * 2 + r) * 16, grid_file,
                         min_replicas=2, outer_steps=6)
             for g in (0, 1) for r in (0, 1)]
    victims = [procs[3], procs[1]]  # column r=1: cells (1,1) and (0,1)
    survivors = [procs[0], procs[2]]
    try:
        # kill only once the grid actually formed and finished an outer
        # step — the grid file's sequence header says so (jax cold-start
        # of 4 cells on one loaded core can take minutes)
        deadline = time.time() + 360
        while time.time() < deadline:
            try:
                # [magic, G, count, seq0, seq1] — GridFile._HDR = 3
                hdr = np.fromfile(grid_file, dtype=np.int64, count=5)
                if len(hdr) == 5 and (hdr[3:] >= 1).all():
                    break
            except (FileNotFoundError, OSError):
                pass
            time.sleep(0.5)
        for v in victims:
            v.kill()
        outs = [_finish(p, timeout=600) for p in survivors]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for out in outs:
        first, last = _final_losses(out)
        assert last < first


def _grid_file_cls():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "grid_diloco", REPO / "examples" / "grid_fsdp" / "grid_diloco.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.GridFile


def test_grid_file_lifecycle(tmp_path):
    """GridFile guarantees: atomic init with -1 sentinels, publish/wait
    ordering, attach-compatible, LOUD rejection of incompatible stale
    files (a silent attach would hand a new run another run's params)."""
    GridFile = _grid_file_cls()
    path = str(tmp_path / "g.bin")
    g = GridFile(path, 2, 100)
    assert list(g.seq) == [-1, -1]
    data = np.arange(50, dtype=np.float32)
    g.publish(0, 3, data)
    assert g.seq[0] == 3 and g.seq[1] == -1
    # same-shape attacher sees the published shard
    h = GridFile(path, 2, 100)
    np.testing.assert_array_equal(h.read_full()[:50], data)
    h.publish(1, 3, np.zeros(50, np.float32))
    g.wait_all(3, timeout=5)
    # wrong size -> loud error, never a misaligned attach
    with pytest.raises(RuntimeError, match="grid file"):
        GridFile(path, 2, 200)
    # same byte size (8·(3+4)+4·96 == 8·(3+2)+4·100) but different layout
    # -> the identity header catches what the size check cannot
    with pytest.raises(RuntimeError, match="identity mismatch"):
        GridFile(path, 4, 96)
    g.remove()
    g.remove()  # idempotent
    assert not (tmp_path / "g.bin").exists()
