#!/usr/bin/env python
"""Headline benchmark + BASELINE.md config sweep.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Headline (BASELINE config 1): fp32 all-reduce busbw, 2 loopback peers.
Baseline: the reference's best sustained all-reduce number is 45 Gbit/s
(= 5.625 GB/s, collocated nodes, "limited only by NIC speed" —
/root/reference/docs/md/01_Introduction.md:8; see BASELINE.md). vs_baseline is
value / 5.625.

"extra" carries the remaining BASELINE configs (all on the native stack):
  quant4_busbw_gbps     — config 2: int8-ZPS quantized concurrent reduces,
                          4 peers (reference concurrent_reduce_test workload)
  shared_state4_step_s  — config 3: SyncSharedState + allreduce per step,
                          4 peers
  diloco_outer_step_s   — DiLoCo outer-step wall-clock, 100M params, 2 peers

PCCLT_BENCH_FAST=1 skips the extra configs (headline only). The full suite
ends with the on-chip legs: with no TPU attached, or any of them failing,
the JSON line is still printed and the exit code is non-zero.
"""

import json
import os
import sys

BASELINE_GBPS = 45.0 / 8.0  # 45 Gbit/s → GB/s


def main() -> None:
    nbytes = int(os.environ.get("PCCLT_BENCH_BYTES", str(64 << 20)))
    # 16 iterations: the median is stable to ~5% on a loaded single-core
    # host (10 left ~15% run-to-run spread)
    iters = int(os.environ.get("PCCLT_BENCH_ITERS", "16"))

    extra = {}
    # names of on-chip legs that failed: any entry makes the exit code
    # non-zero (a chip number that could not be taken is not a skip)
    chip_failures = []
    from pccl_tpu.comm import native_bench

    stats = native_bench.run_allreduce_bench(nbytes=nbytes, iters=iters,
                                             return_stats=True)
    busbw = stats["med"]
    extra["headline_gbps_minmax"] = [round(stats["min"], 3),
                                     round(stats["max"], 3)]
    # flight-recorder phase breakdown for the headline op (mean per
    # reduce, seconds): where a regression lives — ring phases vs
    # wire-stall (docs/09_observability.md)
    if "phases" in stats:
        extra["allreduce_phases_s"] = stats["phases"]

    if os.environ.get("PCCLT_BENCH_FAST", "0") != "1":
        for key, fn in [
            ("bf16_busbw_gbps", native_bench.run_allreduce_bench_bf16),
            ("quant4_busbw_gbps", native_bench.run_quantized_concurrent_bench),
            # fp32 twin of config 2: records the loopback inversion (fp32
            # beats u8 on a free wire) in the artifact itself
            ("concurrent4_fp32_busbw_gbps",
             lambda: native_bench.run_quantized_concurrent_bench(
                 quantize=False)),
            ("shared_state4_step_s", native_bench.run_shared_state_bench),
            # world-8 burst of 12 tagged 8M-element reduces (the reference
            # concurrent_reduce_test workload at scale)
            ("soak8_step_s", native_bench.run_soak_bench),
        ]:
            try:
                extra[key] = round(fn(), 4)
            except Exception as e:  # noqa: BLE001 — extras must not kill headline
                print(f"bench: {key} failed ({type(e).__name__}: {e})",
                      file=sys.stderr)
                extra[key] = None
        try:
            med, phases = native_bench.run_diloco_outer_bench()
            extra["diloco_outer_step_s"] = round(med, 4)
            extra["diloco_phases_s"] = phases  # one fenced step's breakdown
        except Exception as e:  # noqa: BLE001
            print(f"bench: diloco failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["diloco_outer_step_s"] = None
            extra["diloco_phases_s"] = None
        # BASELINE config 5 churn clause: 4 peers, one SIGKILL + rejoin
        # mid-run; steady vs churn-window outer-step time
        try:
            for k, v in native_bench.run_diloco_churn_bench().items():
                extra[k] = round(v, 4) if isinstance(v, float) else v
        except Exception as e:  # noqa: BLE001
            print(f"bench: diloco churn failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            for k in ("diloco_steady_step_s", "diloco_churn_step_s",
                      "worlds_seen", "steps_completed", "rejoiner_joined"):
                extra[k] = None
        # THE driver-configured BASELINE metric: DiLoCo outer step at 1B
        # params (4 GB fp32 per peer). Gated on RAM — each peer wants
        # ~25 GB; skip quietly on small hosts.
        try:
            avail_kb = 0
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemAvailable"):
                        avail_kb = int(line.split()[1])
                        break
            if avail_kb > 70 * 1024 * 1024:
                for k, v in native_bench.run_diloco_1b_bench().items():
                    extra[k] = (round(v, 4) if isinstance(v, float)
                                else [round(x, 4) for x in v])
            else:
                print("bench: skipping 1B diloco leg "
                      f"(MemAvailable {avail_kb >> 20} GB < 70)",
                      file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"bench: diloco 1b failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["diloco_1b_step_s"] = None
        # BASELINE config 4 shape: 2 emulated slices, plain vs quantized DCN
        try:
            for k, v in native_bench.run_hierarchical_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: hierarchical failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["hier2_step_s"] = None
            extra["hier2_q8_step_s"] = None
        # BASELINE config 4 under its real wire: same hierarchical shape,
        # cross-slice hop paced to 100 Mbit/s — where the quantized DCN
        # hop must win (on unpaced loopback the A/B inverts)
        try:
            for k, v in native_bench.run_hierarchical_wan_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: hierarchical wan failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["hier2_wan_quant_speedup"] = None
        # one paced DiLoCo outer step, fp32 ring vs u8-ZPS ring
        try:
            for k, v in native_bench.run_diloco_wan_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: diloco wan failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["diloco_wan_quant_speedup"] = None
        # the constrained-wire A/B: quantization's reason to exist. 4-peer
        # ring over an emulated 100 Mbit/s WAN egress (PCCLT_WIRE_MBPS),
        # fp32 vs u8-ZPS, both reported as fp32-equivalent busbw.
        try:
            for k, v in native_bench.run_wan_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: wan failed ({type(e).__name__}: {e})", file=sys.stderr)
            extra["wan_quant_speedup"] = None
        # bf16 twin: the TPU gradient dtype, plain vs u8-ZPS (typed SIMD
        # widen-to-f32 kernels), bytes-adjusted on the same paced wire
        try:
            for k, v in native_bench.run_wan_bf16_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: wan bf16 failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["wan_bf16_quant_speedup"] = None
        # the fat-pipe A/B: same ring on an emulated 1 Gbit/s x 50 ms RTT
        # pipe (bandwidth pacing + delivery delay line), single flow vs 4
        # concurrent windowed collectives — the regime windowing exists for
        try:
            for k, v in native_bench.run_wan_rtt_windowed_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: wan rtt failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["wan_rtt_windowed_speedup"] = None
        # the pipelined data plane on the SAME fat-long-pipe map: one flow,
        # windowed quantize→send→recv→dequant pipeline + io_uring batched
        # submission (docs/08) — must beat both r05 keys above
        try:
            base = {k: extra.get(k) for k in ("wan_rtt_single_busbw_gbps",
                                              "wan_rtt_windowed_busbw_gbps")}
            for k, v in native_bench.run_wan_pipelined_bench(
                    baselines=base).items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: wan pipelined failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["wan_pipelined_speedup"] = None
        # multipath striping on the SAME fat-long-pipe map (docs/08): the
        # full pipelined plane with the op's window chain striped across 4
        # pool conns sharing one striped-bucket edge, vs the same plane
        # pinned to ONE conn (the PR-8 baseline) — same run, same host
        try:
            for k, v in native_bench.run_wan_striped_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: wan striped failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["wan_striped_speedup"] = None
        # master HA recovery: SIGKILL the journaled master mid-run, restart
        # on the same port; master_recovery_s = SIGKILL -> first
        # post-restart collective completing over resumed sessions
        # (docs/10_high_availability.md). Includes the ~0.5 s scripted
        # outage window, so the floor is downtime + one resume backoff.
        try:
            for k, v in native_bench.run_master_recovery_bench().items():
                extra[k] = round(v, 4) if isinstance(v, float) else v
        except Exception as e:  # noqa: BLE001
            print(f"bench: master recovery failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["master_recovery_s"] = None
        # the topology-optimizer proof: 4 peers on a heterogeneous emulated
        # mesh (per-edge netem, one pessimal 25 Mbit edge on the naive
        # ring); after optimize_topology() the ATSP ring routes around the
        # degraded link — the reference's headline capability, measured
        try:
            for k, v in native_bench.run_topology_opt_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: topology opt failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["topology_opt_speedup"] = None
        # the schedule synthesizer's proof (docs/12): forced tree vs ring
        # broadcast on a hub-and-spoke wire, forced mesh vs ring all-to-all
        # on a two-datacenter wire — same-run ring baselines, same wire
        try:
            for k, v in native_bench.run_schedule_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: schedule bench failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["sched_hub_speedup"] = None
            extra["sched_2dc_speedup"] = None
        # the observability plane's cost, pinned in history: loopback step
        # time with digest pushes + trace capture ON vs OFF (docs/09's
        # <= 1% bound; counters are always on in both legs)
        try:
            for k, v in native_bench.run_telemetry_overhead_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: telemetry overhead failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["telemetry_overhead_pct"] = None
        # critical-path attribution (docs/09): every BENCH run explains its
        # own numbers — trace_critic decomposes a paced 2-peer world's
        # steps into stall/codec/setup fractions + the dominant verdict
        try:
            for k, v in native_bench.run_attribution_bench().items():
                extra[k] = round(v, 4) if isinstance(v, float) else v
        except Exception as e:  # noqa: BLE001
            print(f"bench: attribution failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["attribution_coverage"] = None
        # straggler-immune data plane (docs/05): mid-run edge degradation →
        # wall-clock to the first back-to-baseline step (watchdog →
        # re-issue → relay ladder), plus the armed-but-idle plane's step
        # overhead (<= 1% bound)
        try:
            for k, v in native_bench.run_degraded_recovery_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: degraded recovery failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["degraded_recovery_s"] = None
            extra["relay_overhead_pct"] = None
        # shared-state chunk plane (docs/04): N cold joiners over the
        # content-addressed multi-source fetch vs the single-seeder
        # baseline (acceptance gate >= 2x), conservation byte-exact
        try:
            for k, v in native_bench.run_sync_swarm_bench().items():
                extra[k] = round(v, 4)
        except Exception as e:  # noqa: BLE001
            print(f"bench: sync swarm failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["sync_swarm_speedup"] = None
        # fleet-scale observability (docs/09): 1000 observer sessions x 8
        # edges at ~12 Hz through the off-dispatcher ingest queue; the
        # scrape gate (bounded top-K /metrics < 1 s, promlint-clean, zero
        # queue drops) plus journal-replay cold-restart cost
        try:
            for k, v in native_bench.run_master_scale_bench().items():
                extra[k] = round(v, 6) if isinstance(v, float) else v
        except Exception as e:  # noqa: BLE001
            print(f"bench: master scale failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            extra["master_scale_ingest_rate"] = None

    # On-chip model legs: the jitted bf16 train step on the real TPU —
    # tokens/s + MFU per family. They are part of the full suite: with no
    # TPU attached, or with any leg failing, the CPU results above are
    # still printed and the exit code is non-zero.
    #
    # Every TPU touch happens in a SUBPROCESS: libtpu is process-exclusive,
    # so if this parent initialized the backend (even just to probe
    # jax.devices()), the spawned rank-0 of the diloco-tpu leg could never
    # acquire the chip. Probe, model legs, and the diloco leg therefore
    # each run sequentially in their own process.
    if os.environ.get("PCCLT_BENCH_FAST", "0") != "1":
        import subprocess

        # a probe that fails or hangs is a failed leg like any other: the
        # CPU results above are still printed, the exit code is non-zero
        try:
            probe = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(any(d.platform == 'tpu' "
                 "for d in jax.devices()))"],
                capture_output=True, text=True, timeout=300, check=True)
            tpu_attached = probe.stdout.strip().endswith("True")
        except (subprocess.SubprocessError, OSError) as e:
            print(f"bench: chip probe failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
            tpu_attached = False
        if tpu_attached:
            for fam in ("gpt", "llama"):
                try:
                    p = subprocess.run(
                        [sys.executable, "-m",
                         "pccl_tpu.benchmarks.model_bench", fam],
                        capture_output=True, text=True, timeout=900,
                        check=True)
                    r = json.loads(p.stdout.strip().splitlines()[-1])
                    extra[f"tpu_train_tokens_s_{fam}"] = r["tokens_s"]
                    extra[f"tpu_mfu_{fam}"] = r["mfu"]
                    extra[f"tpu_config_{fam}"] = r["config"]
                    extra[f"tpu_step_s_{fam}"] = r["step_s"]
                    extra[f"tpu_tokens_s_minmax_{fam}"] = [
                        r["tokens_s_min"], r["tokens_s_max"]]
                except Exception as e:  # noqa: BLE001
                    print(f"bench: tpu {fam} failed ({type(e).__name__}: {e})",
                          file=sys.stderr)
                    extra[f"tpu_train_tokens_s_{fam}"] = None
                    chip_failures.append(f"tpu_{fam}")
            # long-context legs: single-chip training through the fused
            # k-blocked flash fwd+bwd pallas kernels (a dense backward at
            # these T wants a multi-GB probs tensor per layer; the round-4
            # full-T-resident kernels topped out at T=8192 on the VMEM
            # ceiling). The llama leg is GQA-native: Hkv-shaped K/V all
            # the way through the kernels.
            for key, fam, seq, ab in (
                    ("tpu_longctx", "gpt", 8192, ()),
                    ("tpu_longctx16k", "gpt", 16384, ()),
                    ("tpu_longctx_llama", "llama", 8192, ()),
                    ("tpu_longctx16k_llama", "llama", 16384, ()),
                    # T=32768: enabled by the chunked CE (loss_chunk) —
                    # the full [1, 32768, vocab] fp32 logits + cotangent
                    # alone would blow the 15.75 GB chip
                    ("tpu_longctx32k", "gpt", 32768, ("loss_chunk=2048",)),
                    ("tpu_longctx32k_llama", "llama", 32768,
                     ("loss_chunk=2048",)),
                    # the GQA A/B: same llama leg with K/V repeated to full
                    # head count in HBM before the kernel (the degraded
                    # round-4 path) — the GQA-native win is the ratio
                    ("tpu_longctx_llama_repeatkv", "llama", 8192,
                     ("repeat_kv=1",))):
                try:
                    p = subprocess.run(
                        [sys.executable, "-m",
                         "pccl_tpu.benchmarks.model_bench", fam, "batch=1",
                         f"seq={seq}", "use_flash=1", "remat=1", *ab],
                        capture_output=True, text=True, timeout=900,
                        check=True)
                    r = json.loads(p.stdout.strip().splitlines()[-1])
                    extra[f"{key}_tokens_s"] = r["tokens_s"]
                    extra[f"{key}_mfu"] = r["mfu"]
                    extra[f"{key}_config"] = r["config"]
                except Exception as e:  # noqa: BLE001
                    print(f"bench: {key} failed ({type(e).__name__}: {e})",
                          file=sys.stderr)
                    extra[f"{key}_tokens_s"] = None
                    chip_failures.append(key)
            # clean-sync invariant: the on-device shared-state digest
            # (hash type 2) stays flat across state sizes while the
            # staging path scales with the D2H rate
            try:
                p = subprocess.run(
                    [sys.executable, "-m", "pccl_tpu.benchmarks.hash_bench"],
                    capture_output=True, text=True, timeout=600, check=True)
                for k, v in json.loads(
                        p.stdout.strip().splitlines()[-1]).items():
                    extra[f"tpu_{k}"] = v
            except Exception as e:  # noqa: BLE001
                print(f"bench: hash bench failed ({type(e).__name__}: {e})",
                      file=sys.stderr)
                extra["tpu_devhash_256mb_s"] = None
                chip_failures.append("hash_bench")
            # headline aliases point at the flagship (gpt) leg
            extra["tpu_train_tokens_s"] = extra.get("tpu_train_tokens_s_gpt")
            extra["tpu_mfu"] = extra.get("tpu_mfu_gpt")
            # on-chip DiLoCo outer step over a paced wire: rank 0 stages
            # from the real TPU; the pipelined leg hides D2H under the
            # ring. Spawned peers acquire the chip themselves — this
            # parent never holds it.
            try:
                for k, v in native_bench.run_diloco_tpu_bench().items():
                    extra[k] = round(v, 4) if isinstance(v, float) else v
            except Exception as e:  # noqa: BLE001
                print(f"bench: diloco tpu failed ({type(e).__name__}: {e})",
                      file=sys.stderr)
                extra["diloco_tpu_step_s"] = None
                chip_failures.append("diloco_tpu")
            # async DiLoCo's overlap, on chip: steady-state step ≈ inner
            # compute with the paced ring hidden, vs the sync twin's
            # compute+wire sum (VERDICT r4 #5)
            try:
                for k, v in native_bench.run_async_diloco_tpu_bench().items():
                    extra[k] = round(v, 4) if isinstance(v, float) else v
            except Exception as e:  # noqa: BLE001
                print(f"bench: async diloco tpu failed "
                      f"({type(e).__name__}: {e})", file=sys.stderr)
                extra["async_diloco_tpu_step_s"] = None
                chip_failures.append("async_diloco_tpu")
        else:
            print("bench: no TPU answered the probe; on-chip legs not run",
                  file=sys.stderr)
            chip_failures.append("no_tpu")

    print(json.dumps({
        "metric": "allreduce_busbw_fp32_2peer_loopback(native)",
        "value": round(busbw, 3),
        "unit": "GB/s",
        "vs_baseline": round(busbw / BASELINE_GBPS, 3),
        "extra": extra,
    }))
    if chip_failures:
        sys.exit(f"bench: on-chip legs failed: {', '.join(chip_failures)}")


if __name__ == "__main__":
    main()
