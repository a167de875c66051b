"""Benchmark: flagship ResNet-20 CIFAR10 training throughput on a TPU.

The default mode measures the chip or fails: it exits non-zero when the
backend is not a TPU, when any unit of the final record carries an
``error``, and when a signal cut the run short.  There is no CPU
fallback; ``GEOMX_BENCH_PLATFORM=cpu`` is the explicit route for
debugging the harness (what the tests use), and its record names the
CPU as its device.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", "device", "mfu",
   "configs": {<5 BASELINE.json configs>: {samples_per_sec, step_time_ms,
   mfu, wire_bytes_per_step}}, "microbench": {...}, ...}

Robustness: the measurement runs in a child process watched by this
parent, which never touches JAX itself (one process per chip).  A hung
TPU backend init or a wedged config is killed at a deadline and the
parent still emits a parseable one-line JSON record with partial
results and a diagnostic — never silence — and then exits non-zero.
Backend init is retried in FRESH child processes, same environment
(GEOMX_BENCH_INIT_ATTEMPTS, default 2, with backoff) because a wedged
TPU runtime can only be shaken loose by a new process; each attempt's
failure reason is recorded.

Survivability under an EXTERNAL kill (round 4's failure: the driver's
own timeout fired before this script's watchdog, rc=124 with empty
output): the parent re-prints the full aggregated one-line JSON after
EVERY completed phase (backend up, each config, TTA, ...), flushed, so
whoever records the tail of stdout always holds a valid, monotonically
growing record — intermediate lines carry "partial": true.  SIGTERM /
SIGINT / SIGHUP are trapped and emit one final line before exiting with
128 + the signal number.  Only SIGKILL can silence it, and even then the
tail is the last completed phase, not emptiness.

Baseline note: the reference publishes no benchmark tables (BASELINE.md);
its demo hardware is a V100-class GPU per worker.  vs_baseline compares
against an estimated 10_000 samples/sec for GeoMX-CUDA ResNet-20/CIFAR10
on one such GPU, so vs_baseline > 1.0 means one TPU chip outruns one
reference GPU.  MFU is reported alongside as the self-grounding number
(measured model FLOPs / chip peak bf16 FLOPs).

Micro-modes:
  bench.py --compare-bucketing [--model=resnet20]
      One JSON line comparing the per-leaf vs fused-bucket dc-tier paths
      for each compression spec on the seed model: collective launches
      per step (counted in the traced jaxpr), wire bytes, and per-bucket
      payloads.  CPU, seconds, no TPU needed.
  bench.py --compare-pipeline [--model=resnet20] [--dcn-ms=100]
           [--compression=none] [--batch=64] [--iters=8]
      One JSON line comparing synchronous vs pipelined
      (GEOMX_PIPELINE_DEPTH=1) dc-tier sync: measured compute step time,
      the DCE-verified count of dc collectives the weight update waits
      on (0 under pipelining), and the modeled step time / overlap ratio
      under an injected DCN delay.  CPU, no TPU needed.
  bench.py --compare-zero [--model=resnet20] [--compression=bsc,0.01]
           [--batch=32] [--steps=4]
      One JSON line for the ZeRO-sharded bucketed weight update
      (GEOMX_ZERO, train/zero.py) on a 2x4 CPU mesh: the DCE'd weight
      path swaps the worker-tier allreduce for psum_scatter +
      all_gather, per-chip optimizer-state bytes shrink ~1/W vs the
      replicated update, final params match the replicated path within
      1e-6 (vanilla, pipelined-drained, degraded-membership), and the
      bsc shard path's wire format is bit-identical between the jnp
      and fused kernels.  Runs in a watchdog-watched child: a wedge
      publishes watchdog.phase/init_phases/stacks forensics.  CPU, no
      TPU needed.
  bench.py --compare-resilience [--model=resnet20] [--steps=9]
           [--schedule="seed=1234;blackout@3:party=1,steps=3"]
           [--compression=none] [--pipeline-depth=0]
      One JSON line replaying a seeded chaos schedule (party blackout +
      re-admission) on a 2-party CPU mesh: the run completes without
      stalling, degraded steps apply the renormalized survivor mean
      (bit-exact vs a single-party run + step-metadata live count), the
      re-admission catch-up payload is measured, and the party count /
      WAN wire-volume accounting return to pre-failure values.  CPU, no
      TPU needed (docs/resilience.md).
  bench.py --compare-recovery [--steps=12] [--parties=2] [--dim=256]
           [--schedule="seed=7;kill@4:node=server,restart_after=2;..."]
           [--corrupt-schedule="seed=7;corrupt@1:party=0,rate=35,steps=8"]
      One JSON line for the durable host plane (docs/resilience.md
      "Host-plane recovery"): a seeded host-plane training run whose
      chaos schedule kills and restarts the global GeoPSServer AND the
      GeoScheduler mid-run finishes with params BIT-EXACT vs an
      uninterrupted same-seed baseline (atomic-snapshot + journal
      store, generation-token session resume) within a bounded stall;
      scheduler ids stay stable across its restart with no grace-window
      mass eviction; a seeded corrupt@ bit-flip replay yields zero
      process crashes, nonzero geomx_wire_crc_errors_total and
      unchanged final params; a hostile frame-length prefix is
      rejected at GEOMX_MAX_FRAME_BYTES.  Pure service plane (sockets
      + numpy) — no jax mesh, CPU, seconds.
  bench.py --compare-manyparty [--steps=10] [--parties=16] [--shards=4]
           [--dim=1024] [--keys=8] [--seed=991]
           [--schedule="seed=991;kill@3:node=shard1,restart_after=2;..."]
      One JSON line for the many-party sharded global tier
      (docs/resilience.md "Many-party global tier"): 16+ virtual
      parties (session-resume-armed ShardedGlobalClients pushing
      P3-chunked gradients) against a key-range sharded tier of N
      durable GeoPSServers under a shard-targeted chaos schedule —
      one shard kill+restart in place, one shard failover onto a NEW
      port (journal replay + scheduler map bump), a seeded corrupt@
      epoch and a throttle@ epoch — finishing params BIT-EXACT vs an
      uninterrupted same-seed baseline with zero lost rounds and a
      bounded stall; plus a scheduler-driven load rebalance on a live
      tier (exact-once merges across the key migration) and a merge-
      throughput curve over shard count that must scale.  Pure
      service plane (sockets + numpy) — no jax mesh, CPU.
  bench.py --compare-fleetobs [--steps=10] [--parties=16] [--shards=4]
           [--dim=1024] [--keys=8] [--seed=661] [--rebalance-at=5]
           [--out-dir=DIR]
      One JSON line for the fleet round ledger (docs/telemetry.md
      "Round ledger"): a 16-party x 4-shard chaos run — in-place
      shard kill, shard failover onto a new port, seeded corrupt@
      epoch, scheduler rebalance with traffic in flight — where every
      completed round yields a GAPLESS per-(key, round) hop chain
      (push/merge/journal/reply incl. each P3 chunk), measured socket
      bytes (counted at the Msg.encode/decode choke point) reconcile
      with declared wire bytes within the documented per-frame bound
      on clean rounds, and every injected fault is attributed to a
      named hop in a named round.  Pure service plane — no jax mesh.
  bench.py --compare-sparseagg [--model=resnet20] [--steps=5]
           [--batch=24] [--wan-mbps=200] [--rtt-ms=30]
      One JSON line for compressed-domain aggregation (GEOMX_SPARSE_AGG,
      compression/sparseagg.py, docs/performance.md): on a 3-party CPU
      mesh, GX-PURITY-001 audits the FULL merged bsc path clean (no
      dense-size operand between compress and final decompress,
      including the ZeRO shard composition) while the dense_merge
      corpus entry stays flagged; the owner-routed merge is
      bit-identical between the jnp and Pallas paths; the host-plane
      sorted-sender sparse merge is bit-exact across shuffled push
      arrival orders (pulls reply sparse); fp16/2bit trace to ONE
      quantized-lattice psum with no gather; and measured 3-party
      training with the modeled WAN link gives bsc samples/sec >=
      vanilla dense — reversing the BENCH_CAPTURED_r05 on-chip
      regression at the multi-party topology.  CPU, no TPU needed.
  bench.py --audit [--model=mlp]
      One JSON line for the Graft Auditor (geomx_tpu/analysis/,
      docs/analysis.md): every green tier-1 step program (vanilla, bsc,
      MPQ, pipelined, degraded-membership) audits to zero findings,
      every seeded known-bad corpus program is flagged with its rule
      id, and audit_cross_party proves 2-party signature equality plus
      detection of an injected divergence.  CPU, seconds, no TPU.
  bench.py --compare-telemetry [--model=resnet20] [--iters=6]
           [--compression=bsc,0.01] [--out-dir=/tmp/...]
      One JSON line for the telemetry plane (docs/telemetry.md): the
      GEOMX_TELEMETRY=0 step jaxpr is byte-identical to a probe-excised
      build, the enabled path's in-graph probe values and measured
      overhead, a Prometheus exposition round-trip through the strict
      parser, and a merged 2-party WAN round trace with round_id-linked
      spans.  Artifacts (merged trace + JSONL event log) land in
      --out-dir.  CPU, no TPU needed.
  bench.py --compare-mfu [--model=resnet20] [--steps=6] [--batch=32]
           [--seq-len=128] [--out-dir=/tmp/...]
      One JSON line for the compute-phase step-time engine
      (docs/performance.md "Compute-phase engine"): the per-leaf optax
      chain is DCE-verified GONE from the lowered weight update under
      GEOMX_FUSED_OPTIM (fused bucket closure -> tpu_custom_call with
      zero stablehlo.multiply; the full TPU-lowered train step shows
      the same swap) with fused-vs-unfused params matching to the
      documented FMA tolerance; the GEOMX_PRECISION=bf16 build's loss
      trajectory tracks fp32 and the GX-DTYPE-001 precision audit both
      passes a legitimate bf16 model and flags an fp32 imposter; the
      loader's GEOMX_PREFETCH double-buffering drops the attributed
      host_stall fraction (the four phase fractions still sum to ~1.0)
      with prefetched batches bit-identical to synchronous ones; and
      measured step time -> roofline MFU + bound verdict for BOTH
      first-class workloads (ResNet-20 and the transformer sequence
      classifier — the TRANSFORMER_r*.json trend series).  CPU, no
      TPU needed.
  bench.py --attribute [--model=resnet20] [--iters=6] [--dcn-ms=100]
           [--batch=64] [--out-dir=/tmp/...]
      One JSON line for the step-time observatory (docs/telemetry.md):
      per-step phase breakdown (compute / hidden comms / exposed comms
      / host stall — the four fractions sum to ~1.0) for vanilla, bsc
      and pipelined configs on the 2x4 mesh, the modeled breakdown
      under an injected DCN delay (exposed comms must drop under
      GEOMX_PIPELINE_DEPTH=1), MFU + roofline bound verdict from
      cost_analysis, a LinkObservatory replay reproducing an injected
      per-link bandwidth asymmetry, and a deterministic flight-recorder
      NaN auto-dump naming the poisoned party.  Artifacts (per-config
      phase JSON, flight bundle, merged WAN trace) land in --out-dir.
      CPU, no TPU needed.

Env knobs:
  GEOMX_BENCH_PLATFORM=cpu   debug on the host CPU (tiny shapes)
  GEOMX_BENCH_BATCH          per-chip batch (default 2048; 256 on cpu)
  GEOMX_BENCH_ITERS          timed iterations (default 100; 5 on cpu)
  GEOMX_BENCH_INIT_TIMEOUT   seconds for backend init, per attempt
                             (default 480)
  GEOMX_BENCH_INIT_ATTEMPTS  fresh-child init attempts (default 2)
  GEOMX_BENCH_TIMEOUT        seconds for measurement after init
                             (default 1500 — the default phase set is
                             sized to finish well inside this)
  GEOMX_BENCH_CONFIGS        comma list of config names to run (default
                             all — use to debug/time one config)
  GEOMX_COMPILE_CACHE=0      disable the persistent XLA compile cache
                             (JAX_COMPILATION_CACHE_DIR where set, else
                             <checkout>/.geomx_compile_cache), which
                             makes every bench run after the first warm
  GEOMX_BENCH_TTA=0          skip time-to-accuracy (runs by default:
                             real CIFAR10 when present/fetchable under
                             GEOMX_DATA_DIR, else the synthetic proxy)
  GEOMX_BENCH_TTA_TARGET     test-acc target (default 0.92 real / 0.90 syn)
  GEOMX_BENCH_EXTRAS=1       also run the kernel microbench, per-op
                             roofline profile, and batch sweep (off by
                             default — they are diagnostics, not the
                             scorecard, and they don't fit a tight
                             driver budget)
"""

import json
import os
import queue
import signal
import subprocess
import sys
import tempfile
import threading
import time

REFERENCE_GPU_SAMPLES_PER_SEC = 10_000.0
METRIC = "resnet20_cifar10_train_samples_per_sec_per_chip"

# --------------------------------------------------------------------------
# child: owns the JAX backend, emits JSON events on stdout
# --------------------------------------------------------------------------

def _emit(obj):
    print(json.dumps(obj), flush=True)


def _build_configs(n_devices: int):
    """The five BASELINE.json configs as (name, GeoConfig overrides,
    num_parties).  On one chip both mesh axes collapse to 1 and the
    collective short-circuits, so the configs measure the compression /
    sync compute the chip pays; on >=2 devices the dc tier is real."""
    parties = 2 if n_devices >= 2 and n_devices % 2 == 0 else 1
    return [
        # examples/cnn.py — vanilla, single-worker local kvstore
        ("vanilla_local", {"sync_mode": "fsa", "compression": "none"}, 1),
        # examples/cnn.py dist_sync HiPS
        ("dist_sync_hips", {"sync_mode": "fsa", "compression": "none"}, parties),
        # examples/cnn_bsc.py — Bi-Sparse over HiPS
        ("bsc", {"sync_mode": "fsa", "compression": "bsc,0.01"}, parties),
        # examples/cnn_fp16.py / cnn_mpq.py — fp16 / mixed-precision comm
        ("fp16_mpq", {"sync_mode": "fsa", "compression": "mpq,0.01"}, parties),
        # examples/cnn_hfa.py — HFA + DGT priority transport.  3 deferral
        # channels (reference scripts/cpu/run_dgt.sh runs
        # DMLC_UDP_CHANNEL_NUM=3) with k=0.5: non-drain steps move the
        # top half of the blocks, every 3rd step drains — amortized wire
        # ~(0.5*2+1)/3 = 67% of dense, so the deferral is visible in
        # wire_bytes_per_sync (VERDICT r3: channels=1 made every step a
        # drain and DGT deferred nothing)
        ("hfa_dgt", {"sync_mode": "hfa", "hfa_k1": 20, "hfa_k2": 10,
                     "enable_dgt": 2, "udp_channel_num": 3, "dgt_k": 0.5,
                     "compression": "none"}, parties),
        # TPU-optimized flagship variant (VERDICT r3 #4 / r4 weak #3):
        # 2x2 space-to-depth stem (on CIFAR this halves every stage's
        # resolution — a ~4x-fewer-FLOP sibling of ResNet-20) plus
        # MXU-friendly transition shortcuts (s2d+1x1 instead of the
        # fill-starved stride-2 1x1 projection).  Its accuracy evidence
        # is the dedicated tta_s2d phase.
        ("vanilla_s2d", {"sync_mode": "fsa", "compression": "none",
                         "model_kwargs": {"space_to_depth": True,
                                          "mxu_shortcuts": True}}, 1),
    ]


def _measure_config(name, overrides, parties, batch, iters, peak):
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import ResNet20
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    n_dev = jax.device_count()
    parties = min(parties, n_dev)
    workers = max(1, n_dev // parties) if n_dev >= parties else 1
    topo = HiPSTopology(num_parties=parties, workers_per_party=workers)
    overrides = dict(overrides)
    model_kwargs = overrides.pop("model_kwargs", {})
    cfg = GeoConfig.from_env(num_parties=parties, workers_per_party=workers,
                             **overrides)
    sync = get_sync_algorithm(cfg)
    trainer = Trainer(ResNet20(num_classes=10, **model_kwargs), topo,
                      optax.sgd(0.1, momentum=0.9), sync=sync, config=cfg)

    local_b = batch // (parties * workers)
    rng = np.random.RandomState(0)
    x = (rng.rand(parties, workers, local_b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(parties, workers, local_b)).astype(np.int32)
    sharding = topo.batch_sharding(trainer.mesh)
    xb = jax.device_put(x, sharding)
    yb = jax.device_put(y, sharding)

    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])

    # compile once, reuse the executable (also the FLOPs source)
    lowered = trainer.train_step.lower(state, xb, yb)
    compiled = lowered.compile()
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0)) or None
    except Exception:
        pass

    for _ in range(3):
        state, metrics = compiled(state, xb, yb)
    jax.block_until_ready(metrics["loss"])

    # min of two timed passes: host dispatch jitter between
    # otherwise-identical runs
    dt = None
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = compiled(state, xb, yb)
        jax.block_until_ready(metrics["loss"])
        d = time.perf_counter() - t0
        dt = d if dt is None else min(dt, d)

    step_s = dt / iters
    sps_chip = batch * iters / dt / max(1, n_dev if parties * workers > 1 else 1)
    mfu = None
    if flops and peak:
        mfu = flops / step_s / peak

    # cross-dc wire accounting: what the dc-tier compressor puts on the
    # WAN per sync, vs dense fp32 (the claim BENCH verifies in-graph via
    # tests/test_wire_volume.py)
    wire = None
    comp = getattr(sync, "dc_compressor", None)
    if comp is not None:
        params = jax.tree.map(lambda a: a[0, 0], state.params)
        wire = {"compressed": int(comp.wire_bytes(params)),
                "dense_fp32": int(sum(leaf.size * 4
                                      for leaf in
                                      jax.tree.leaves(params)))}
        # every accelerator config must actually reduce the WAN payload —
        # a "compression" config whose wire equals dense is a misconfig
        # (VERDICT r3: hfa_dgt with 1 channel deferred nothing)
        if comp.name != "none":
            wire["reduces"] = wire["compressed"] < wire["dense_fp32"]
            assert wire["reduces"], (
                f"{name}: compressed wire bytes {wire['compressed']} !< "
                f"dense {wire['dense_fp32']} — config defers/compresses "
                "nothing")

    return {
        "config": name,
        "topology": f"{parties}x{workers}",
        "batch": batch,
        "samples_per_sec_per_chip": round(sps_chip, 1),
        "step_time_ms": round(step_s * 1e3, 3),
        "flops_per_step": flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "wire_bytes_per_sync": wire,
    }


def _scan_slope(step, init_carry, lo: int, hi: int, reps: int) -> float:
    """Per-iteration device seconds for ``step``: the slope of total time
    vs lax.scan length, min over ``reps``, with the carry value-fetched so
    completion can't be faked.  The slope cancels the fixed dispatch cost
    exactly; ``step`` must
    thread its inputs through the carry so nothing hoists out of the
    loop."""
    import jax
    import jax.numpy as jnp

    tot = {}
    for iters in (lo, hi):
        @jax.jit
        def run(c, iters=iters):
            c = jax.lax.scan(lambda cc, _: (step(cc), None), c,
                             None, length=iters)[0]
            return jax.tree.map(jnp.sum, c)
        # compile + one throwaway fetch
        jax.tree.map(lambda a: float(a), run(init_carry))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.tree.map(lambda a: float(a), run(init_carry))
            ts.append(time.perf_counter() - t0)
        tot[iters] = min(ts)
    return max(0.0, (tot[hi] - tot[lo]) / (hi - lo))


def _per_op_profile(batch, peak, on_tpu: bool):
    """Conv-by-conv roofline table for ResNet-20 (VERDICT r3 #4): each
    distinct conv shape in the network is slope-timed in isolation
    (forward, bf16 inputs, fp32 accumulation — the training step's
    regime; backward convs have the same shapes at ~2x the FLOPs).  The
    per-shape MXU utilization shows where the step's MFU ceiling comes
    from: CIFAR channel widths (16/32/64) fill at most 12-50% of a
    128-wide MXU systolic array by construction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    B = batch if on_tpu else 64
    lo, hi, reps = (200, 1000, 5) if on_tpu else (2, 8, 3)
    # (label, in_hw, cin, cout, k, stride, count_in_resnet20)
    convs = [
        ("stem 3x3 3->16 @32", 32, 3, 16, 3, 1, 1),
        ("stage1 3x3 16->16 @32", 32, 16, 16, 3, 1, 6),
        ("stage2 3x3 16->32 /2", 32, 16, 32, 3, 2, 1),
        ("stage2 1x1 16->32 /2", 32, 16, 32, 1, 2, 1),
        ("stage2 3x3 32->32 @16", 16, 32, 32, 3, 1, 5),
        ("stage3 3x3 32->64 /2", 16, 32, 64, 3, 2, 1),
        ("stage3 1x1 32->64 /2", 16, 32, 64, 1, 2, 1),
        ("stage3 3x3 64->64 @8", 8, 64, 64, 3, 1, 5),
    ]
    rows = []
    total_t = total_f = total_best = 0.0
    for label, hw, cin, cout, k, stride, count in convs:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(B, hw, hw, cin), jnp.bfloat16)
        w = jnp.asarray(rng.randn(k, k, cin, cout) * 0.1, jnp.bfloat16)
        wmat = w.reshape(-1, cout)

        def step(c, w=w, stride=stride):
            y = lax.conv_general_dilated(
                c, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)
            # fold the output into a runtime scalar factor on the input:
            # the next iteration's conv depends on this one (no hoisting)
            return c * (1.0 + 1e-9 * jnp.mean(y)).astype(jnp.bfloat16)

        # alternative lowering: explicit im2col patches + one matmul
        # whose contraction is cin*k*k (144 for a 16-channel 3x3 — full
        # systolic width, where the direct conv contracts only cin).
        # Timing-equivalent formulation: weight-layout permutation would
        # not change the cost, and only a mean scalar is consumed.
        def step_im2col(c, wmat=wmat, stride=stride, k=k):
            p = lax.conv_general_dilated_patches(
                c, (k, k), (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            y = jnp.dot(p.astype(jnp.bfloat16), wmat,
                        preferred_element_type=jnp.float32)
            return c * (1.0 + 1e-9 * jnp.mean(y)).astype(jnp.bfloat16)

        t = _scan_slope(step, x, lo, hi, reps)
        t_i2c = _scan_slope(step_im2col, x, lo, hi, reps)
        hout = -(-hw // stride)
        fl = 2.0 * B * hout * hout * cout * cin * k * k
        t_best = min(t, t_i2c)
        total_t += t * count
        total_f += fl * count
        total_best += t_best * count
        rows.append({
            "op": label, "count": count, "batch": B,
            "time_us": round(t * 1e6, 2),
            "im2col_time_us": round(t_i2c * 1e6, 2),
            "gflops": round(fl / 1e9, 3),
            "tflops_per_sec": round(fl / t / 1e12, 2) if t > 0 else None,
            "mxu_util": round(fl / t / peak, 4) if peak and t > 0 else None,
            "best_util": round(fl / t_best / peak, 4)
            if peak and t_best > 0 else None,
            # rough fill indicator: output channels over the 128-wide
            # systolic dimension (XLA's conv lowering can beat it by
            # packing spatial positions into the contraction)
            "cout_over_128": round(min(1.0, cout / 128.0), 3),
        })
    out = {"note": ("forward convs in isolation; backward shapes "
                    "identical at ~2x FLOPs.  mxu_util is measured on "
                    "XLA's direct conv lowering; im2col_time_us races "
                    "the same shape as explicit patches + one matmul "
                    "(contraction cin*k*k), and best_util documents the "
                    "better of the two — the achievable per-op bound "
                    "this hardware/compiler pair gives these CIFAR "
                    "channel widths"),
           "convs": rows}
    if total_t > 0 and peak:
        out["weighted_forward_mxu_util"] = round(total_f / total_t / peak, 4)
    if total_best > 0 and peak:
        out["weighted_forward_mxu_bound"] = round(
            total_f / total_best / peak, 4)
    return out


def _microbench_kernels(peak, on_tpu: bool):
    """Compression-kernel microbench: Pallas vs jnp 2-bit quantize, exact
    vs approx BSC top-k (VERDICT r1 #7 / r3 #1: prove the Pallas path).

    Methodology (r4): each candidate runs as a jitted lax.scan of
    dependent applications whose FULL outputs are consumed into the
    carry, and the reported per-iteration time is the SLOPE between a
    low and a high iteration count (min over reps, value-fetched).  Two
    failure modes of the r3 methodology are closed: (a) a single
    dispatch has a fixed host cost that at 50 iterations swamped the
    tens-of-µs kernels — the slope cancels the fixed cost exactly;
    (b) carrying only the residual let XLA dead-code-eliminate the jnp path's packing (the opaque pallas_call
    can't be DCE'd), making the comparison unfair — summing the packed
    words into the carry forces both paths to do the full job.

    Note on the roofline: at 4M f32 the working set (input + carry,
    32 MB) is VMEM-resident across scan iterations on a 128 MB-VMEM
    chip, so per-iteration times can beat the naive HBM roofline; the
    numbers are compute/VMEM-bound kernel times, the right regime for
    a fused compression kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = 4 * 1024 * 1024 if on_tpu else 1024 * 1024
    lo, hi, reps = (1000, 5000, 5) if on_tpu else (4, 16, 3)
    g = jnp.asarray(np.random.RandomState(0).randn(n), jnp.float32)
    res = jnp.zeros((n,), jnp.float32)
    out = {"method": f"scan-slope iters {lo}->{hi}, min of {reps}, "
                     "outputs consumed", "elements": n}

    def _slope(step, init_carry, lo=lo, hi=hi):
        return _scan_slope(step, init_carry, lo, hi, reps)

    from geomx_tpu.ops.twobit_pallas import quantize_2bit_ref

    def jnp_q(g, r):
        return quantize_2bit_ref(g, r, 0.5)
    z32 = jnp.zeros((), jnp.int32)

    # the error-feedback residual carries; the packed words fold into an
    # int accumulator so neither path's pack can be eliminated
    def _jnp_step(c):
        r, acc = c
        packed, newr = jnp_q(g, r)
        return newr, acc + jnp.sum(packed)
    out["twobit_jnp_ms"] = round(_slope(_jnp_step, (res, z32)) * 1e3, 4)
    if on_tpu:
        try:
            from geomx_tpu.ops import dequantize_2bit, quantize_2bit

            def _pallas_step(c):
                r, acc = c
                packed, newr = quantize_2bit(g, r, 0.5)
                return newr, acc + jnp.sum(packed)
            out["twobit_pallas_ms"] = round(
                _slope(_pallas_step, (res, z32)) * 1e3, 4)
            packed0, _ = quantize_2bit(g, res, 0.5)
            packed0 = jax.block_until_ready(packed0)

            # the carry XORs into the packed words so the dequant input
            # depends on the previous iteration — loop-invariant code
            # motion cannot hoist the kernel out of the scan
            def _dequant_step(c):
                s, acc = c
                vals = dequantize_2bit(packed0 ^ s, n, 0.5)
                return (1 - s), acc + jnp.sum(vals)
            out["twobit_dequant_pallas_ms"] = round(_slope(
                _dequant_step, (z32, jnp.zeros(()))) * 1e3, 4)
        except Exception as e:
            out["twobit_pallas_error"] = repr(e)

    k = n // 100
    # carry the vector through a tiny perturbation so each top_k input
    # depends on the previous iteration (no CSE/hoisting); fold the
    # selected values in so the selection itself can't be eliminated
    out["bsc_topk_exact_ms"] = round(_slope(
        lambda v: v * (1.0 + 1e-12 * jax.lax.top_k(
            jnp.abs(v), k)[0][0]), g,
        lo=max(1, lo // 5), hi=max(2, hi // 5)) * 1e3, 4)
    out["bsc_topk_approx_ms"] = round(_slope(
        lambda v: v * (1.0 + 1e-12 * jax.lax.approx_max_k(
            jnp.abs(v), k)[0][0]), g) * 1e3, 4)

    from geomx_tpu.ops.bsc_pallas import sampled_boundary_guv
    from geomx_tpu.ops.sampled_topk import sampled_threshold_select

    def _sampled_step(v):
        thr = sampled_boundary_guv(jnp.zeros_like(v), jnp.zeros_like(v), v, k)
        vals, _idx, _keep = sampled_threshold_select(v, jnp.abs(v), k, thr)
        return v * (1.0 + 1e-12 * vals[0])
    out["bsc_topk_sampled_ms"] = round(
        _slope(_sampled_step, g) * 1e3, 4)

    # long-context attention: fused Pallas kernel vs the dense jnp graph
    # (which materializes [B, H, L, L] scores+probs in HBM).  The carry
    # perturbs q so every iteration depends on the last.
    if on_tpu:
        try:
            from geomx_tpu.ops import fused_attention_supported
            from geomx_tpu.ops.flash_attention import flash_attention
            from geomx_tpu.parallel.ring_attention import (
                full_attention_reference)
            if fused_attention_supported():
                Ba, La, Ha, Da = 4, 2048, 8, 64
                rs = np.random.RandomState(1)
                qa, ka, va = (jnp.asarray(
                    rs.normal(size=(Ba, La, Ha, Da)), jnp.bfloat16)
                    for _ in range(3))
                alo, ahi = max(1, lo // 100), max(2, hi // 100)

                def _flash_step(qc):
                    o = flash_attention(qc, ka, va, causal=True)
                    return qc * 0.999 + o.astype(qc.dtype) * 1e-3
                out["attn_flash_pallas_ms"] = round(_slope(
                    _flash_step, qa, lo=alo, hi=ahi) * 1e3, 4)

                def _dense_step(qc):
                    o = full_attention_reference(qc, ka, va, causal=True)
                    return qc * 0.999 + o.astype(qc.dtype) * 1e-3
                out["attn_dense_xla_ms"] = round(_slope(
                    _dense_step, qa, lo=alo, hi=ahi) * 1e3, 4)
                out["attn_shape"] = f"B{Ba} L{La} H{Ha} D{Da} causal bf16"

                # gradient path: flash fwd+bwd kernels vs dense
                # autodiff.  BOTH differentiate w.r.t. (q, k, v) and
                # fold all three grads into the carry — grad w.r.t. q
                # alone would let XLA prune the dense path's dk/dv work
                # while the opaque flash bwd always computes all three
                # (the unfair-comparison class the 2-bit bench fixed)
                from geomx_tpu.ops import fused_attention

                def _flash_grad_step(qc):
                    gq, gk, gv = jax.grad(
                        lambda qq, kk, vv: jnp.sum(
                            fused_attention(qq, kk, vv, True, False)
                            .astype(jnp.float32)),
                        argnums=(0, 1, 2))(qc, ka, va)
                    return (qc * 0.999 - (gq + gk + gv)
                            .astype(qc.dtype) * 1e-6)
                out["attn_flash_grad_ms"] = round(_slope(
                    _flash_grad_step, qa, lo=alo, hi=ahi) * 1e3, 4)

                def _dense_grad_step(qc):
                    gq, gk, gv = jax.grad(
                        lambda qq, kk, vv: jnp.sum(
                            full_attention_reference(qq, kk, vv,
                                                     causal=True)
                            .astype(jnp.float32)),
                        argnums=(0, 1, 2))(qc, ka, va)
                    return (qc * 0.999 - (gq + gk + gv)
                            .astype(qc.dtype) * 1e-6)
                out["attn_dense_grad_ms"] = round(_slope(
                    _dense_grad_step, qa, lo=alo, hi=ahi) * 1e3, 4)
        except Exception as e:
            out["attn_flash_error"] = repr(e)
    return out


def _time_to_accuracy(batch, model_kwargs=None):
    """Train the flagship to the target test accuracy; wall-clock seconds.
    The north star is time-to-92% on REAL CIFAR-10 (BASELINE.md): the
    dataset is fetched in-run when the environment has egress
    (tools/fetch_cifar10.py); a no-egress environment falls back to the
    synthetic proxy at a 0.90 target, and the result records both the
    fallback and the denial reason.

    ``model_kwargs``: flagship variant to train — the s2d TTA phase
    passes the TPU-optimized stem so its 4x step-time win carries its
    own accuracy evidence (VERDICT r4 weak #3: a faster variant without
    time-to-target at the same accuracy bar is not a win)."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.data import load_dataset
    from geomx_tpu.models import ResNet20
    from geomx_tpu.sync import FSA
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    root = os.environ.get("GEOMX_DATA_DIR", "/root/data")
    fetch_note = None
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        import fetch_cifar10
        if not fetch_cifar10.ensure(root, quiet=True):
            fetch_note = ("cifar10 absent and download failed (no egress "
                          "in this environment); synthetic proxy used — "
                          "run tools/fetch_cifar10.py where network exists")
    except Exception as e:
        fetch_note = f"fetch_cifar10 unavailable: {e!r}"
    finally:
        sys.path.pop(0)
    data = load_dataset("cifar10", root=root, synthetic_train_n=8192)
    synthetic = data["synthetic"]
    if synthetic:
        print("# bench: no local cifar10 — time-to-accuracy runs on the "
              'synthetic substitute ("synthetic": true)', file=sys.stderr,
              flush=True)
    else:
        # real data found (fetched earlier, or pre-mounted under a layout
        # ensure() does not probe, e.g. <root>/cifar10/...): a stale
        # download-failure note would mislabel a real-CIFAR run
        fetch_note = None
    target = float(os.environ.get("GEOMX_BENCH_TTA_TARGET",
                                  "0.90" if synthetic else "0.92"))
    max_epochs = int(os.environ.get("GEOMX_BENCH_TTA_EPOCHS", "40"))

    topo = HiPSTopology.from_devices()
    model = ResNet20(num_classes=10, **(model_kwargs or {}))
    local_b = max(8, batch // topo.total_workers)
    # time-to-target wants an aggressive-then-annealed schedule, not the
    # constant lr the throughput configs use: linear warmup to a
    # large-batch-scaled peak, cosine to a floor (never to 0 — the run
    # must still be able to cross the target at the epoch budget's tail)
    spe = max(1, len(data["train_x"]) // (local_b * topo.total_workers))
    peak_lr = 0.1 * max(1.0, (local_b * topo.total_workers) / 512)
    total_steps = max_epochs * spe
    # warmup ~2 epochs but never the whole budget (tiny debug budgets)
    warmup = min(2 * spe, max(1, total_steps // 10))
    sched = optax.schedules.warmup_cosine_decay_schedule(
        init_value=peak_lr / 10, peak_value=peak_lr,
        warmup_steps=warmup, decay_steps=max(total_steps, warmup + 1),
        end_value=peak_lr / 20)
    trainer = Trainer(model, topo,
                      optax.sgd(sched, momentum=0.9, nesterov=True),
                      sync=FSA())
    loader = trainer.make_loader(data["train_x"], data["train_y"], local_b,
                                 augment=not synthetic, device_cache=True)
    state = trainer.init_state(jax.random.PRNGKey(0),
                               data["train_x"][:2])
    scan = jax.devices()[0].platform == "tpu"
    t0 = time.perf_counter()
    best = 0.0
    ep_secs = []  # per-epoch wall time: epoch 1 carries the jit compiles

    def _result(reached, epochs, acc):
        out = {"dataset": "synthetic" if synthetic else "cifar10",
               # a named dataset that was replaced says so in the record
               "synthetic": bool(synthetic),
               "target": target, "reached": reached, "epochs": epochs,
               "seconds": round(time.perf_counter() - t0, 2),
               "test_acc": round(acc, 4)}
        # one-time jit compiles land in epoch 1 (and amortize to ~0 under
        # the persistent compile cache); the split lets the reader
        # separate time-to-accuracy from process-startup compile — for
        # variants with different step costs (s2d vs standard) the
        # compile-free number is the architecture comparison
        if len(ep_secs) >= 2:
            steady = sorted(ep_secs[1:])[len(ep_secs[1:]) // 2]
            jit_overhead = max(0.0, ep_secs[0] - steady)
            out["first_epoch_seconds"] = round(ep_secs[0], 2)
            out["steady_epoch_seconds"] = round(steady, 2)
            out["seconds_excl_jit"] = round(out["seconds"] - jit_overhead,
                                            2)
        if fetch_note:
            out["note"] = fetch_note
        return out

    for ep in range(max_epochs):
        t_ep = time.perf_counter()
        if scan:
            sel, key = loader.epoch_indices(ep)
            run = trainer._epoch_runner(loader)
            state, _ = run(state, loader._dev_x, loader._dev_y, sel, key)
        else:
            for i, (xb, yb) in enumerate(loader.epoch(ep)):
                state, metrics = trainer.train_step(state, xb, yb)
                if i % 32 == 0:
                    jax.block_until_ready(metrics["loss"])
        acc = trainer.evaluate(state, data["test_x"], data["test_y"])
        ep_secs.append(time.perf_counter() - t_ep)
        best = max(best, acc)
        if acc >= target:
            return _result(True, ep + 1, acc)
    return _result(False, max_epochs, best)


def _fit_overhead(batch, iters, bare_sps):
    """Measure the Trainer.fit loop (device-cached loader + scanned
    epochs) against the bare compiled-step loop: VERDICT r2 #2's
    criterion is fit within 10% of bare."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.models import ResNet20
    from geomx_tpu.sync import FSA
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    topo = HiPSTopology(num_parties=1, workers_per_party=1)
    trainer = Trainer(ResNet20(num_classes=10), topo,
                      optax.sgd(0.1, momentum=0.9), sync=FSA())
    rng = np.random.RandomState(0)
    n = batch * max(8, iters)  # enough steps to amortize per-epoch cost
    x = (rng.rand(n, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(n,)).astype(np.int32)
    loader = trainer.make_loader(x, y, batch, device_cache=True)
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    # scanned epochs pay off on the chip (one dispatch/epoch); on the CPU
    # debug platform the scan recompiles under donation churn, so use the
    # per-step path there
    scan = jax.devices()[0].platform == "tpu"
    # two warm epochs: compile, then the donated-layout fixed point
    state, _ = trainer.fit(state, loader, epochs=2, scan_epochs=scan)
    epochs = 3 if scan else 1
    t0 = time.perf_counter()
    state, _ = trainer.fit(state, loader, epochs=epochs, scan_epochs=scan)
    jax.block_until_ready(state.step)
    dt = time.perf_counter() - t0
    sps = epochs * loader.steps_per_epoch * batch / dt
    out = {"samples_per_sec": round(sps, 1),
           "steps": loader.steps_per_epoch}
    if bare_sps:
        out["vs_bare_compiled"] = round(sps / bare_sps, 4)
    return out


def child_main():
    # watchdog diagnosability (a hung init once burned 2x480s with zero clue
    # where init hung): the parent sends SIGUSR1 before killing a
    # wedged child, and faulthandler dumps EVERY thread's stack to
    # stderr — which the parent attaches to the published error.  The
    # per-phase timestamps below bound WHICH init phase ate the budget.
    t_child0 = time.monotonic()

    def _phase(name):
        _emit({"event": "phase", "phase": name,
               "elapsed_s": round(time.monotonic() - t_child0, 2)})
    try:
        import faulthandler
        faulthandler.register(signal.SIGUSR1, all_threads=True)
    except (AttributeError, ValueError, OSError):
        pass  # non-main thread / unsupported platform: dumps just absent
    _phase("child_start")
    hang = os.environ.get("GEOMX_BENCH_FAULT_HANG_INIT")
    if hang:
        # test hook: wedge init deterministically so the watchdog's
        # forensic path (SIGUSR1 stack dump + per-phase timestamps) is
        # exercisable in seconds instead of a real 480s hang
        time.sleep(float(hang))

    # validate the config filter BEFORE backend init: the name list is
    # static, and a typo must fail in a second, not after backend init
    # (and without triggering a guaranteed-futile resume respawn)
    only = set(filter(None, os.environ.get(
        "GEOMX_BENCH_CONFIGS", "").split(",")))
    all_names = {n for n, _, _ in _build_configs(1)}
    if only - all_names:
        raise ValueError(f"GEOMX_BENCH_CONFIGS: unknown config(s) "
                         f"{sorted(only - all_names)}; "
                         f"valid: {sorted(all_names)}")

    platform = os.environ.get("GEOMX_BENCH_PLATFORM")
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    _phase("jax_imported")
    devs = jax.devices()
    _phase("devices_enumerated")
    on_tpu = devs[0].platform == "tpu"
    if not platform and not on_tpu:
        # the default mode measures the chip or fails: no CPU number is
        # ever written under a device metric's name.  The CPU route is
        # explicit (GEOMX_BENCH_PLATFORM=cpu, what the tests use).
        raise SystemExit(
            f"bench: the default backend is {devs[0].platform!r}, not a "
            "TPU — nothing measured.  Set GEOMX_BENCH_PLATFORM=cpu to "
            "debug the harness on the host CPU")
    # persistent compile cache: each step program costs the TPU compiler
    # ~30 s; the cache (JAX_COMPILATION_CACHE_DIR where set, else
    # <checkout>/.geomx_compile_cache) makes every run after the first
    # warm.  GEOMX_COMPILE_CACHE=0 disables.
    from geomx_tpu.utils import enable_compile_cache
    enable_compile_cache()
    _phase("compile_cache_ready")
    kind = devs[0].device_kind
    peak = None
    if on_tpu:
        # one table, keyed by exact device_kind; an unknown chip raises
        from geomx_tpu.telemetry.roofline import device_peaks
        peak = device_peaks(kind)["bf16_flops_per_s"]
    # compute-gate the backend-up signal: backend_up flips the parent
    # watchdog from the (retried-in-a-fresh-child) init phase to the
    # measurement phase — emit it only after a real matmul round-trips
    # a value on EVERY device (one wedged chip of several must stay an
    # init-phase failure, which retries fresh)
    import jax.numpy as jnp
    for d in devs:
        a = jax.device_put(jnp.ones((256, 256)), d)
        probe = float(jnp.sum(a @ a))
        assert probe == 256.0 * 256 * 256, (d, probe)
    _phase("device_probe_done")
    _emit({"event": "backend_up", "platform": devs[0].platform,
           "device_kind": kind, "n_devices": len(devs),
           "peak_bf16_flops": peak})

    # 100 iters on the chip: enough that the one tail
    # block_until_ready round trip does not inflate the per-step time
    batch = int(os.environ.get("GEOMX_BENCH_BATCH",
                               2048 if on_tpu else 256))
    iters = int(os.environ.get("GEOMX_BENCH_ITERS", 100 if on_tpu else 5))

    # resume support: a respawned child skips units the parent already
    # holds good results for (the first child's TPU runtime can crash
    # mid-run and take every later phase down with it — a fresh process
    # recovers the rest)
    done_units = set(filter(None, os.environ.get(
        "GEOMX_BENCH_DONE", "").split(",")))
    # fault-injection hook for the resume test; fires only in a first
    # (non-resume) child so the respawn observes the unit succeeding
    fault_unit = (os.environ.get("GEOMX_BENCH_FAULT_UNIT")
                  if not done_units else None)

    bare_sps = None
    if os.environ.get("GEOMX_BENCH_BARE_SPS"):
        bare_sps = float(os.environ["GEOMX_BENCH_BARE_SPS"])
    for name, overrides, parties in _build_configs(len(devs)):
        if only and name not in only:
            continue
        if f"config:{name}" in done_units:
            continue
        try:
            if fault_unit == f"config:{name}":
                raise RuntimeError(
                    "injected fault (GEOMX_BENCH_FAULT_UNIT)")
            rec = _measure_config(name, overrides, parties, batch,
                                  iters, peak)
            if name == "vanilla_local":
                bare_sps = rec.get("samples_per_sec_per_chip")
            _emit({"event": "config", **rec})
        except Exception as e:
            _emit({"event": "config", "config": name, "error": repr(e)})

    # time-to-accuracy is the north star — runs by DEFAULT (the r3
    # artifact lacked it because the driver didn't set the env) and
    # immediately after the configs, so a deadline kill still captures
    # it; GEOMX_BENCH_TTA=0 opts out.  The standard flagship runs first
    # (the parity metric), then the TPU-optimized s2d variant races the
    # SAME target — its 4x step-time win only counts with this evidence.
    if os.environ.get("GEOMX_BENCH_TTA", "1") != "0":
        if "tta" not in done_units:
            try:
                _emit({"event": "tta", **_time_to_accuracy(batch)})
            except Exception as e:
                _emit({"event": "tta", "error": repr(e)})
        if "tta_s2d" not in done_units:
            try:
                _emit({"event": "tta_s2d", **_time_to_accuracy(
                    batch,
                    {"space_to_depth": True, "mxu_shortcuts": True})})
            except Exception as e:
                _emit({"event": "tta_s2d", "error": repr(e)})

    if "fit_loop" not in done_units:
        try:
            _emit({"event": "fit_loop",
                   **_fit_overhead(batch, iters, bare_sps)})
        except Exception as e:
            _emit({"event": "fit_loop", "error": repr(e)})

    # Diagnostics beyond the scorecard (kernel microbench, per-op
    # roofline, batch sweep) are opt-in: round 4 ran them by default and
    # the grown runtime pushed the whole bench past the driver's budget
    # (BENCH_r04.json rc=124) — the extras cost the scorecard itself.
    extras = os.environ.get("GEOMX_BENCH_EXTRAS", "0") == "1"

    if extras:
        if "microbench" not in done_units:
            try:
                _emit({"event": "microbench",
                       **_microbench_kernels(peak, on_tpu)})
            except Exception as e:
                _emit({"event": "microbench", "error": repr(e)})

        if "profile" not in done_units:
            try:
                _emit({"event": "profile",
                       **_per_op_profile(batch, peak, on_tpu)})
            except Exception as e:
                _emit({"event": "profile", "error": repr(e)})

    # batch scaling for the vanilla config (how far MXU amortization
    # takes the headline); keys are GLOBAL batch — _measure_config
    # splits across devices, so per-chip batch = key / n_devices (equal
    # on the 1-chip bench).  Lowest priority — last, so a deadline kill
    # costs only this.
    if (extras and on_tpu and "batch_sweep" not in done_units
            and os.environ.get("GEOMX_BENCH_SWEEP", "1") != "0"):
        import jax
        n_dev = jax.device_count()
        sweep = {"note": "keys are GLOBAL batch; per_chip_batch in each "
                         "entry is what one chip actually runs"}
        for b in (1024, 2048, 4096, 8192):
            try:
                r = _measure_config("vanilla_local",
                                    {"sync_mode": "fsa",
                                     "compression": "none"}, 1, b,
                                    max(20, iters // 2), peak)
                sweep[str(b)] = {
                    "per_chip_batch": b // max(1, n_dev),
                    "samples_per_sec_per_chip":
                        r["samples_per_sec_per_chip"],
                    "step_time_ms": r["step_time_ms"], "mfu": r["mfu"]}
            except Exception as e:
                sweep[str(b)] = {"error": repr(e)}
        _emit({"event": "batch_sweep", **sweep})

    _emit({"event": "done"})


# --------------------------------------------------------------------------
# --compare-bucketing: per-leaf vs fused-bucket communication accounting
# --------------------------------------------------------------------------

# collective counting lives in the analysis subsystem now
# (geomx_tpu/analysis/passes.py count_collectives — same primitive set,
# same recursion through nested jaxprs)


def _compare_bucketing(model_name: str = "resnet20",
                       specs=("none", "fp16", "2bit,0.5", "bsc,0.01",
                              "mpq,0.01"),
                       bucket_bytes=None):
    """The ISSUE's acceptance measurement: for the seed model config,
    trace each compressor's dc-tier all-reduce on a 2-party mesh both
    per-leaf and bucketed, and count the collective launches actually in
    the jaxpr plus the wire bytes each path accounts.  Runs on CPU — the
    jaxpr and the accounting are platform-independent."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.compression import BucketedCompressor, get_compressor
    from geomx_tpu.compression.bucketing import (DEFAULT_BUCKET_BYTES,
                                                 _resolve_bucket_bytes)
    from geomx_tpu.models import get_model
    from geomx_tpu.parallel.collectives import shard_map_compat

    bucket_bytes = _resolve_bucket_bytes(bucket_bytes)
    if bucket_bytes <= 0:  # the compare mode exists to measure bucketing;
        bucket_bytes = DEFAULT_BUCKET_BYTES  # a 0 opt-out doesn't apply here
    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "compare-bucketing needs >= 2 devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    mesh = Mesh(np.array(devs[:2]), ("dc",))

    model = get_model(model_name, num_classes=10)
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = jax.jit(lambda r, x: model.init(r, x, train=False))(
        jax.random.PRNGKey(0), sample)["params"]
    leaves = jax.tree.leaves(params)
    dense_fp32 = sum(leaf.size * 4 for leaf in leaves)

    def trace_collectives(comp):
        state = comp.init_state(params)

        def f(gs, ss):
            g = jax.tree.map(lambda a: a[0], gs)
            s = jax.tree.map(lambda a: a[0], ss)
            out, s2 = comp.allreduce(g, s, "dc", 2)
            return (jax.tree.map(lambda a: a[None], out),
                    jax.tree.map(lambda a: a[None], s2))

        fn = shard_map_compat(f, mesh, in_specs=(P("dc"), P("dc")),
                              out_specs=(P("dc"), P("dc")))
        def stack(t):
            return jax.tree.map(lambda a: jnp.stack([a, a]), t)

        from geomx_tpu.analysis.passes import count_collectives
        return count_collectives(jax.make_jaxpr(fn)(stack(params),
                                                    stack(state)))

    out = {"mode": "compare_bucketing", "model": model_name,
           "num_leaves": len(leaves),
           "total_params": int(sum(leaf.size for leaf in leaves)),
           "dense_fp32_bytes": dense_fp32,
           "bucket_bytes": bucket_bytes, "specs": {}}
    for spec in specs:
        per_leaf = get_compressor(spec)
        bucketed = BucketedCompressor(get_compressor(spec), bucket_bytes)
        rec = {
            "per_leaf": {"collectives": trace_collectives(per_leaf),
                         "wire_bytes": int(per_leaf.wire_bytes(params))},
            "bucketed": {"collectives": trace_collectives(bucketed),
                         "num_buckets": len(bucketed.init_state(params)),
                         "wire_bytes": int(bucketed.wire_bytes(params)),
                         "buckets": bucketed.bucket_report(params)},
        }
        rec["collective_reduction"] = (
            rec["per_leaf"]["collectives"] / max(1, rec["bucketed"]["collectives"]))
        out["specs"][spec] = rec
    return out


def compare_bucketing_main(argv):
    model = "resnet20"
    for a in argv:
        if a.startswith("--model="):
            model = a.split("=", 1)[1]
    result = _compare_bucketing(model_name=model)
    _emit(result)


def _roofline_fields(make_record):
    """The roofline columns of a micro-mode record.  These modes run on
    whatever backend the process has; MFU and the bound verdict are
    device metrics, so off a device with published peaks
    (telemetry/roofline.DEVICE_PEAKS) they read "not measured" — never a
    number from another machine under the device metric's name."""
    import jax

    from geomx_tpu.telemetry.roofline import DEVICE_PEAKS
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        return {"device_kind": kind, "mfu": "not measured",
                "bound": "not measured"}
    roof = make_record()
    return {
        "device_kind": kind,
        "mfu": roof["mfu"],
        "arithmetic_intensity": roof["arithmetic_intensity"],
        "bound": roof["bound"],
        "bound_times_s": roof["bound_times_s"],
        "cost_analysis_available": roof["cost_analysis_available"],
        "wire_bytes_per_step": roof["wire_bytes_per_step"],
    }


# --------------------------------------------------------------------------
# --audit: the Graft Auditor's acceptance smoke (analysis/, docs/analysis.md)
# --------------------------------------------------------------------------

# the green step-program set the auditor must pass with ZERO findings:
# every tier-1 training configuration's traced step (vanilla, bsc, MPQ,
# pipelined, degraded-membership)
_AUDIT_GREEN_CONFIGS = (
    ("vanilla", {"compression": "none"}),
    ("bsc", {"compression": "bsc,0.05,min_sparse_size=16"}),
    ("mpq", {"compression": "mpq,0.05"}),
    ("pipelined", {"compression": "none", "pipeline_depth": 1}),
    ("degraded", {"compression": "none", "_membership": (True, False)}),
)


def _audit_mode(model_name: str = "mlp"):
    """One JSON line for the static auditor: per-rule pass/fail with
    finding counts.  Three claims gate CI:

    1. every seeded known-bad corpus program is flagged with its rule id
       (the auditor still fires);
    2. every green tier-1 step program audits to ZERO findings
       (collective consistency, wire accounting, compressed-path
       purity) — the auditor doesn't cry wolf.  (Donated-state alias
       coverage is verified in tests/test_analysis.py, not here);
    3. ``audit_cross_party`` proves signature equality for a 2-party
       config and detects an injected divergence.
    """
    import jax
    import numpy as np
    import optax

    from geomx_tpu.analysis import (AuditContext,
                                    CollectiveConsistencyPass,
                                    audit_compressed_path,
                                    audit_cross_party,
                                    audit_wire_accounting,
                                    collective_signature, summarize)
    from geomx_tpu.analysis.corpus import run_corpus
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "audit needs >= 2 devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, 4)).astype(np.int32)

    def build(overrides):
        membership = overrides.pop("_membership", None)
        cfg = GeoConfig(num_parties=2, workers_per_party=1, **overrides)
        tr = Trainer(get_model(model_name, num_classes=10), topo,
                     optax.sgd(0.1), sync=get_sync_algorithm(cfg),
                     config=cfg, donate=False)
        state = tr.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
        if membership is not None:
            state = tr.apply_membership(state, membership)
        sharding = topo.batch_sharding(tr.mesh)
        xb, yb = jax.device_put(x, sharding), jax.device_put(y, sharding)
        return tr, state, xb, yb

    # -- green set: zero findings across every tier-1 step program -----------
    green = {}
    green_findings = 0
    for name, overrides in _AUDIT_GREEN_CONFIGS:
        tr, state, xb, yb = build(dict(overrides))
        jx = jax.make_jaxpr(tr.train_step)(state, xb, yb)
        findings = CollectiveConsistencyPass().run(jx, AuditContext())
        params = jax.tree.map(lambda a: a[0, 0], state.params)
        dc = getattr(tr.sync, "dc_compressor", None) or getattr(
            getattr(tr.sync, "inner", None), "dc_compressor", None)
        if dc is not None:
            findings += audit_wire_accounting(dc, params)
            findings += audit_compressed_path(dc, params)
        green[name] = {"findings": len(findings),
                       "rules": summarize(findings),
                       "collectives": len(collective_signature(jx))}
        green_findings += len(findings)

    # -- cross-party: equality proven, injected divergence caught ------------
    def sig_of(overrides):
        tr, state, xb, yb = build(dict(overrides))
        return collective_signature(
            jax.make_jaxpr(tr.train_step)(state, xb, yb))

    # two INDEPENDENT builds of the same config prove trace determinism;
    # the divergence check reuses the first build's signature (a third
    # identical build would add a full model init for no new evidence)
    bsc_sig = sig_of({"compression": "bsc,0.05,min_sparse_size=16"})
    same = audit_cross_party({
        "party0": bsc_sig,
        "party1": sig_of({"compression": "bsc,0.05,min_sparse_size=16"}),
    })
    diverged = audit_cross_party({
        "party0": bsc_sig,
        "party1": sig_of({"compression": "none"}),
    })
    cross = {"identical_configs_equal": not same,
             "injected_divergence_detected": bool(diverged)}

    # -- corpus: every known-bad program flagged -----------------------------
    corpus = run_corpus()

    rules = {}
    for rec in corpus.values():
        rules[rec["expected_rule"]] = {
            "corpus_flagged": rec["flagged"],
            "green_findings": sum(
                g["rules"].get(rec["expected_rule"], 0)
                for g in green.values()),
        }
    ok = (green_findings == 0
          and all(r["corpus_flagged"] for r in rules.values())
          and cross["identical_configs_equal"]
          and cross["injected_divergence_detected"])
    return {"mode": "audit", "model": model_name, "ok": ok,
            "green": green, "green_findings_total": green_findings,
            "cross_party": cross, "corpus": corpus, "rules": rules}


def audit_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
    _emit(_audit_mode(**kwargs))


# --------------------------------------------------------------------------
# --compare-pipeline: synchronous vs double-buffered dc-tier sync
# --------------------------------------------------------------------------


def _collect_dc_collectives(jaxpr) -> int:
    """Count collectives over the "dc" mesh axis (analysis subsystem
    walker underneath, recursing into nested jaxprs)."""
    from geomx_tpu.analysis.passes import count_collectives
    return count_collectives(jaxpr, axis="dc")


def _dc_weight_path_analysis(train_step, state, xb, yb):
    """The structural claim --compare-pipeline verifies: how many dc-axis
    collectives the *weight update* actually waits on.  Dead-code-
    eliminate the traced step keeping only the params/opt_state/
    model_state outputs (jax's dce_jaxpr recurses through pjit/
    shard_map/cond), then count dc collectives in what survives.
    Synchronous FSA keeps its gradient collective and the BatchNorm-stat
    pmean (the optimizer and the next forward consume them); the
    pipelined step keeps NONE — its collectives feed only sync_state,
    i.e. the next step."""
    import jax

    closed = jax.make_jaxpr(train_step)(state, xb, yb)
    out_shapes = jax.eval_shape(train_step, state, xb, yb)
    flat, treedef = jax.tree.flatten(out_shapes)
    idx_tree = jax.tree.unflatten(treedef, list(range(len(flat))))
    new_state, _metrics = idx_tree
    keep = set(jax.tree.leaves((new_state.params, new_state.opt_state,
                                new_state.model_state)))
    used = [i in keep for i in range(len(flat))]
    total = _collect_dc_collectives(closed.jaxpr)
    from jax.interpreters import partial_eval as pe
    dced, _used_ins = pe.dce_jaxpr(closed.jaxpr, used)
    on_path = _collect_dc_collectives(dced)
    return {"dc_collectives_total": total,
            "dc_collectives_on_weight_path": on_path}


def _compare_pipeline(model_name: str = "resnet20", dcn_ms: float = 100.0,
                      compression: str = "none", batch: int = 64,
                      iters: int = 8, dcasgd_lambda: float = 0.04):
    """Synchronous vs pipelined dc-tier sync on a 2-party mesh: measured
    compute step time, the DCE-verified dependency structure, and the
    modeled step time under an injected DCN delay.

    The delay is *modeled*, not slept: a host backend executes programs
    serially, so a wall-clock sleep would penalize both modes equally.
    What IS measured from the real programs: (a) each mode's compute
    step time, and (b) — the load-bearing fact — whether the weight
    update waits on this step's dc collective (backward slice of the
    traced jaxpr).  The model then charges the delay only where the
    dependency structure says a step blocks on the WAN:

        sync      = t_step + dcn_delay          (collective on the path)
        pipelined = max(t_step, dcn_delay)      (full-step overlap)
    """
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    if dcn_ms <= 0:
        raise ValueError(f"--dcn-ms must be > 0 (got {dcn_ms:g}): the "
                         "mode exists to model a WAN delay; with no "
                         "delay there is nothing to overlap")
    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "compare-pipeline needs >= 2 devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    local_b = max(1, batch // 2)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, local_b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, local_b)).astype(np.int32)

    def measure(pipeline_depth):
        cfg = GeoConfig(num_parties=2, workers_per_party=1,
                        compression=compression,
                        pipeline_depth=pipeline_depth,
                        pipeline_dcasgd=(dcasgd_lambda
                                         if pipeline_depth else 0.0))
        sync = get_sync_algorithm(cfg)
        trainer = Trainer(get_model(model_name, num_classes=10), topo,
                          optax.sgd(0.1, momentum=0.9), sync=sync,
                          config=cfg)
        sharding = topo.batch_sharding(trainer.mesh)
        xb = jax.device_put(x, sharding)
        yb = jax.device_put(y, sharding)
        state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
        structure = _dc_weight_path_analysis(trainer.train_step, state,
                                             xb, yb)
        comp = sync.dc_compressor if pipeline_depth == 0 \
            else sync.inner.dc_compressor
        params = jax.tree.map(lambda a: a[0, 0], state.params)
        wire = int(comp.wire_bytes(params))
        state, metrics = trainer.train_step(state, xb, yb)  # compile+warm
        state, metrics = trainer.train_step(state, xb, yb)
        jax.block_until_ready(metrics["loss"])
        dt = None
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(iters):
                state, metrics = trainer.train_step(state, xb, yb)
            jax.block_until_ready(metrics["loss"])
            d = time.perf_counter() - t0
            dt = d if dt is None else min(dt, d)
        return {"step_time_ms": round(dt / iters * 1e3, 3),
                "wire_bytes_per_step": wire, **structure}

    sync_rec = measure(0)
    pipe_rec = measure(1)

    out = {"mode": "compare_pipeline", "model": model_name,
           "compression": compression, "batch": batch, "iters": iters,
           "dcn_delay_ms": dcn_ms,
           "pipeline_dcasgd_lambda": dcasgd_lambda,
           "sync": sync_rec, "pipelined": pipe_rec,
           "note": ("dcn delay is modeled on the DCE-verified dependency "
                    "structure (a host backend executes serially, so a "
                    "slept delay would block both modes); step_time_ms "
                    "and the collective counts are measured")}
    s_on = sync_rec.get("dc_collectives_on_weight_path")
    p_on = pipe_rec.get("dc_collectives_on_weight_path")
    if s_on is not None and p_on is not None:
        t_s, t_p = sync_rec["step_time_ms"], pipe_rec["step_time_ms"]

        def modeled(t, on_path, d):
            return t + d if on_path else max(t, d)

        # sweep: at delays far below the step's compute the pipeline's
        # buffer-copy overhead can outweigh the hidden latency (honest
        # negative); at geo-WAN delays the hidden round trip dominates
        sweep = {}
        for d in sorted({10.0, 25.0, 50.0, 100.0, 250.0, dcn_ms}):
            ms, mp = modeled(t_s, s_on, d), modeled(t_p, p_on, d)
            sweep[str(int(d) if float(d).is_integer() else d)] = {
                "sync_ms": round(ms, 3), "pipelined_ms": round(mp, 3),
                "overlap_ratio": round((ms - mp) / d, 4),
                "speedup": round(ms / mp, 4)}
        out["delay_sweep_ms"] = sweep
        model_s = modeled(t_s, s_on, dcn_ms)
        model_p = modeled(t_p, p_on, dcn_ms)
        out["sync"]["modeled_step_ms_under_delay"] = round(model_s, 3)
        out["pipelined"]["modeled_step_ms_under_delay"] = round(model_p, 3)
        out["overlap_ratio"] = round((model_s - model_p) / dcn_ms, 4)
        out["speedup_under_delay"] = round(model_s / model_p, 4)
        out["overlaps_compute"] = (p_on == 0 and model_p < model_s)
    return out


def compare_pipeline_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--dcn-ms="):
            kwargs["dcn_ms"] = float(a.split("=", 1)[1])
        elif a.startswith("--compression="):
            kwargs["compression"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--iters="):
            kwargs["iters"] = int(a.split("=", 1)[1])
    _emit(_compare_pipeline(**kwargs))


# --------------------------------------------------------------------------
# --compare-zero: replicated vs ZeRO-sharded bucketed weight update
# --------------------------------------------------------------------------


def _axis_collective_breakdown(jaxpr, axis: str) -> dict:
    """Per-primitive counts of collectives over the named mesh axis
    (walker from the analysis subsystem, recursing into nested
    jaxprs)."""
    from geomx_tpu.analysis.core import walk_jaxpr
    from geomx_tpu.analysis.passes import COLLECTIVE_PRIMS, _collective_axes
    out = {}
    for site in walk_jaxpr(jaxpr):
        if site.primitive in COLLECTIVE_PRIMS \
                and axis in _collective_axes(site.eqn):
            out[site.primitive] = out.get(site.primitive, 0) + 1
    return out


def _weight_path_collectives(train_step, state, xb, yb) -> dict:
    """The structural claim --compare-zero verifies: which collectives
    the *weight update* waits on, per mesh axis.  DCE the traced step
    keeping only the params/opt_state outputs (BatchNorm-stat pmeans
    feed model_state and are excluded on purpose — they are statistics
    maintenance, not the weight update), then break the surviving
    collectives down per primitive.  Replicated FSA keeps its
    worker-axis psum (the gradient allreduce); the ZeRO step keeps
    psum_scatter + all_gather and NO worker-axis psum."""
    import jax

    closed = jax.make_jaxpr(train_step)(state, xb, yb)
    out_shapes = jax.eval_shape(train_step, state, xb, yb)
    flat, treedef = jax.tree.flatten(out_shapes)
    idx_tree = jax.tree.unflatten(treedef, list(range(len(flat))))
    new_state, _metrics = idx_tree
    keep = set(jax.tree.leaves((new_state.params, new_state.opt_state)))
    used = [i in keep for i in range(len(flat))]
    from jax.interpreters import partial_eval as pe
    dced, _used_ins = pe.dce_jaxpr(closed.jaxpr, used)
    return {"worker_axis": _axis_collective_breakdown(dced, "worker"),
            "dc_axis": _axis_collective_breakdown(dced, "dc")}


def _bsc_shard_wire_format(shard_elems: int = 2048,
                           ratio: float = 0.05) -> dict:
    """PR 4's wire-format guarantee extended to shard-sized payloads:
    the (values, indices) pairs one bucket *shard* emits must be
    byte-identical between the jnp sampled path and the fused Pallas
    kernels (interpret mode — runs on CPU)."""
    import jax.numpy as jnp
    import numpy as np

    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.ops.bsc_pallas import (bsc_select_pack,
                                          sampled_boundary_guv,
                                          select_pack_ref)

    rng = np.random.RandomState(7)
    g = jnp.asarray(rng.standard_normal(shard_elems), jnp.float32)
    u = jnp.zeros_like(g)
    v = jnp.zeros_like(g)
    k = BiSparseCompressor(ratio=ratio).k_for(shard_elems)
    thr = sampled_boundary_guv(g, u, v, k)
    va, ia, _, _ = select_pack_ref(g, u, v, thr, k)
    vb, ib, _, _ = bsc_select_pack(g, u, v, thr, k, interpret=True)
    ident = (np.asarray(va).tobytes() == np.asarray(vb).tobytes()
             and np.asarray(ia).tobytes() == np.asarray(ib).tobytes())
    return {"wire_format_bit_identical": bool(ident),
            "wire_format_pairs": int(va.shape[0]),
            "wire_format_shard_elems": shard_elems}


def _compare_zero(model_name: str = "resnet20",
                  compression: str = "bsc,0.01", batch: int = 32,
                  steps: int = 4, on_phase=None):
    """Replicated vs ZeRO-sharded weight update on a 2x4 CPU mesh
    (train/zero.py, GEOMX_ZERO): one JSON line proving

    (a) structure — in the DCE'd weight path the worker-tier gradient
        allreduce is replaced by psum_scatter + all_gather;
    (b) memory — per-chip optimizer-state bytes shrink ~1/W vs the
        replicated update (state-shape accounting, plus XLA's
        ``memory_analysis()`` where the backend provides it);
    (c) parity — final params match the replicated path within 1e-6
        for the vanilla config, composed with pipelined (drained) and
        degraded-membership runs; the bsc shard path runs finite and
        its wire format is bit-identical between the jnp and fused
        kernels at shard sizes.
    """
    import jax
    import numpy as np
    import optax

    from geomx_tpu.analysis.passes import _GATHER_PRIMS, _SCATTER_PRIMS
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    def phase(name):
        if on_phase is not None:
            on_phase(name)

    n_parties, n_workers = 2, 4
    devs = jax.devices()
    if len(devs) < n_parties * n_workers:
        raise RuntimeError(
            "compare-zero needs >= 8 devices for the 2x4 mesh (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    topo = HiPSTopology(num_parties=n_parties,
                        workers_per_party=n_workers)
    local_b = max(1, batch // (n_parties * n_workers))
    rng = np.random.RandomState(0)
    xs = (rng.rand(steps, n_parties, n_workers, local_b, 32, 32, 3)
          * 255).astype(np.uint8)
    ys = rng.randint(0, 10, size=(steps, n_parties, n_workers,
                                  local_b)).astype(np.int32)

    def build(zero, comp="none", pipeline=0, mask=None):
        cfg = GeoConfig(num_parties=n_parties,
                        workers_per_party=n_workers, zero=zero,
                        compression=comp, pipeline_depth=pipeline)
        tr = Trainer(get_model(model_name, num_classes=10), topo,
                     optax.sgd(0.1, momentum=0.9),
                     sync=get_sync_algorithm(cfg), config=cfg)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0, :2])
        if mask is not None:
            st = tr.apply_membership(st, mask)
        return tr, st

    def run(tr, st, drain=False):
        sharding = topo.batch_sharding(tr.mesh)
        for s in range(steps):
            st, _m = tr.train_step(st, jax.device_put(xs[s], sharding),
                                   jax.device_put(ys[s], sharding))
        if drain:
            st = tr.drain_pipeline(st)
        jax.block_until_ready(st.step)
        return st

    def params00(st):
        return jax.tree.map(lambda a: np.asarray(a, np.float64)[0, 0],
                            st.params)

    def gap(a, b):
        return max(jax.tree.leaves(jax.tree.map(
            lambda u, v: float(np.max(np.abs(u - v))), a, b)))

    out = {"mode": "compare_zero", "model": model_name,
           "topology": f"{n_parties}x{n_workers}",
           "compression": compression, "batch": batch, "steps": steps}

    # -- (a) structure + (b) memory on the vanilla pair ----------------------
    phase("build_replicated")
    tr_rep, st_rep = build(False)
    sharding = topo.batch_sharding(tr_rep.mesh)
    xb = jax.device_put(xs[0], sharding)
    yb = jax.device_put(ys[0], sharding)
    phase("build_zero")
    tr_zero, st_zero = build(True)
    phase("structure_analysis")
    s_rep = _weight_path_collectives(tr_rep.train_step, st_rep, xb, yb)
    s_zero = _weight_path_collectives(tr_zero.train_step, st_zero, xb, yb)

    def fam_count(rec, fam):
        return sum(v for k, v in rec.get("worker_axis", {}).items()
                   if k in fam)

    scat = fam_count(s_zero, _SCATTER_PRIMS)
    gath = fam_count(s_zero, _GATHER_PRIMS)
    psum_zero = s_zero.get("worker_axis", {}).get("psum", 0)
    psum_rep = s_rep.get("worker_axis", {}).get("psum", 0)
    out["structure"] = {
        "replicated": s_rep, "zero": s_zero,
        "zero_psum_scatter_on_weight_path": scat,
        "zero_all_gather_on_weight_path": gath,
        "zero_worker_allreduce_on_weight_path": psum_zero,
        "worker_allreduce_replaced": bool(
            scat and gath and psum_zero == 0 and psum_rep > 0
            and fam_count(s_rep, _SCATTER_PRIMS) == 0),
    }
    phase("memory_analysis")
    mem_rep = tr_rep.step_memory_stats(st_rep, xb, yb)
    mem_zero = tr_zero.step_memory_stats(st_zero, xb, yb)
    ratio = (mem_zero["opt_state_bytes_per_chip"]
             / max(1.0, mem_rep["opt_state_bytes_per_chip"]))
    out["memory"] = {
        "replicated": mem_rep, "zero": mem_zero,
        "opt_state_per_chip_ratio": round(ratio, 4),
        "expected_ratio": round(1.0 / n_workers, 4),
        # padding + per-bucket scalars keep the ratio a whisker above
        # exactly 1/W; "shrinks" = at most halfway between 1/W and 1
        "opt_state_shrinks_with_workers":
            ratio <= (1.0 / n_workers + 1.0) / 2.0,
    }

    # -- (c) parity: vanilla, pipelined (drained), degraded ------------------
    phase("parity_vanilla")
    g_vanilla = gap(params00(run(tr_rep, st_rep)),
                    params00(run(tr_zero, st_zero)))
    parity = {"vanilla_gap": g_vanilla}
    phase("parity_pipelined")
    tr_a, st_a = build(False, pipeline=1)
    tr_b, st_b = build(True, pipeline=1)
    parity["pipelined_gap"] = gap(params00(run(tr_a, st_a, drain=True)),
                                  params00(run(tr_b, st_b, drain=True)))
    phase("parity_degraded")
    tr_a, st_a = build(False, mask=(True, False))
    tr_b, st_b = build(True, mask=(True, False))
    parity["degraded_gap"] = gap(params00(run(tr_a, st_a)),
                                 params00(run(tr_b, st_b)))
    parity["tolerance"] = 1e-6
    parity["within_tolerance"] = all(
        v <= 1e-6 for k, v in parity.items() if k.endswith("_gap"))
    out["parity"] = parity

    # -- bsc: the compressed shard path --------------------------------------
    phase("bsc_zero")
    tr_b, st_b = build(True, comp=compression)
    st_b = run(tr_b, st_b)
    finite = all(bool(np.isfinite(np.asarray(leaf)).all())
                 for leaf in jax.tree.leaves(st_b.params))
    dc = tr_b.sync.dc_compressor
    params0 = jax.tree.map(lambda a: a[0, 0], st_b.params)
    wire = _bsc_shard_wire_format()
    out["bsc"] = {
        "finite": finite,
        "shard_wire_bytes_per_chip": int(
            dc.shard_wire_bytes(params0, n_workers)),
        "bucket_wire_bytes_replicated": int(dc.wire_bytes(params0)),
        **wire,
    }
    phase("verdict")
    out["ok"] = bool(out["structure"]["worker_allreduce_replaced"]
                     and out["memory"]["opt_state_shrinks_with_workers"]
                     and parity["within_tolerance"] and finite
                     and wire["wire_format_bit_identical"])
    return out


def _compare_zero_child(kwargs):
    """The measurement half of --compare-zero, run in a watched child:
    registers the SIGUSR1 faulthandler (the parent signals before
    killing, so a wedge names its frame) and streams per-phase events
    the parent folds into the record's forensics fields."""
    t0 = time.monotonic()
    try:
        import faulthandler
        faulthandler.register(signal.SIGUSR1, all_threads=True)
    except (AttributeError, ValueError, OSError):
        pass  # unsupported platform: stack dumps just absent

    def phase(name):
        _emit({"event": "phase", "phase": name,
               "elapsed_s": round(time.monotonic() - t0, 2)})

    phase("child_start")
    hang = os.environ.get("GEOMX_BENCH_FAULT_HANG_INIT")
    if hang:
        # test hook (shared with the main bench): wedge deterministically
        # so the forensic path is exercisable in seconds
        time.sleep(float(hang))
    import jax  # backend init: the classic silent-wedge point
    jax.devices()
    phase("backend_up")
    rec = _compare_zero(on_phase=phase, **kwargs)
    _emit({"event": "result", "record": rec})


def _compare_zero_parent(argv):
    """Watchdog parent for --compare-zero (the main bench's watchdog applied
    to the micro-modes): the child is killed after ``timeout`` seconds
    of SILENCE — the deadline re-arms on every phase event, so a
    healthy-but-slow host streaming progress is never mistaken for a
    wedge — and the emitted record still names the wedged phase
    (``watchdog.phase``), carries the per-phase timestamp trail
    (``init_phases``) and the child's all-thread stacks — never 480
    silent seconds."""
    timeout = float(os.environ.get("GEOMX_BENCH_TIMEOUT", "480"))
    env = dict(os.environ, GEOMX_BENCH_COMPARE_CHILD="1")
    # One process per chip: like parent_main, this watchdog parent never
    # imports jax or geomx_tpu — the child alone initializes a backend.
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--compare-zero",
         *argv],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    q: "queue.Queue" = queue.Queue()
    threading.Thread(target=_drain, args=(proc.stdout, q),
                     daemon=True).start()
    stderr_buf = []
    stderr_thread = threading.Thread(target=lambda: stderr_buf.extend(
        proc.stderr.read().splitlines()[-200:]), daemon=True)
    stderr_thread.start()

    record = None
    phases = {}
    last_phase = None
    error = None
    deadline = time.monotonic() + timeout
    while True:
        try:
            line = q.get(timeout=max(0.1, deadline - time.monotonic()))
        except queue.Empty:
            last = last_phase or "child_start"
            error = (f"watchdog: --compare-zero made no progress for "
                     f"{timeout:g}s in phase {last!r}")
            try:
                proc.send_signal(signal.SIGUSR1)
                time.sleep(2.0)
            except (OSError, AttributeError):
                pass
            proc.kill()
            break
        if line is None:
            break
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            continue
        kind = ev.get("event")
        if kind == "phase":
            last_phase = str(ev.get("phase"))
            phases[last_phase] = ev.get("elapsed_s")
            deadline = time.monotonic() + timeout  # progress re-arms
        elif kind == "result":
            record = ev.get("record")
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
    stderr_thread.join(timeout=5)
    if error is None and record is None:
        error = (f"compare-zero child exited rc={proc.poll()} without "
                 "a result")
    out = record if record is not None else {"mode": "compare_zero",
                                             "ok": False}
    if phases:
        out["init_phases"] = phases
    if error is not None:
        out["error"] = error
        out["watchdog"] = {
            "phase": last_phase or "child_start",
            "init_phases": dict(phases),
            "stacks": stderr_buf[-120:],
        }
        if stderr_buf:
            out["error"] += " | " + " | ".join(stderr_buf[-5:])[-2000:]
    _emit(out)


def compare_zero_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--compression="):
            kwargs["compression"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
    if os.environ.get("GEOMX_BENCH_COMPARE_CHILD") == "1":
        _compare_zero_child(kwargs)
    else:
        _compare_zero_parent([a for a in argv
                              if a != "--compare-zero"])


# --------------------------------------------------------------------------
# --compare-resilience: seeded mid-run party blackout + re-admission
# --------------------------------------------------------------------------


def _compare_resilience(model_name: str = "resnet20",
                        compression: str = "none", batch: int = 32,
                        steps: int = 9, schedule_spec: str = None,
                        pipeline_depth: int = 0):
    """The resilience acceptance run: a seeded chaos schedule blacks out
    party 1 mid-run on a 2-party CPU mesh; the run must complete without
    stalling, the degraded steps must apply the renormalized survivor
    mean (verified two ways: the step metadata's static live-party
    count, and a bit-exact comparison of one degraded step against a
    single-party run from the same state), and after re-admission the
    party count and per-step WAN wire-volume accounting must return to
    their pre-failure values.  The re-admitted party's catch-up payload
    (checkpoint-format state broadcast) is measured in bytes."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.resilience import (ChaosEngine, ChaosSchedule,
                                      PartyLivenessController)
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "compare-resilience needs >= 2 devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    # from_env so GEOMX_CHAOS_SCHEDULE / GEOMX_RESILIENCE_* apply; the
    # mode's own axes are pinned (sync_mode stays fsa — the solo
    # reference in _verify_survivor_mean is an FSA run)
    cfg = GeoConfig.from_env(num_parties=2, workers_per_party=1,
                             sync_mode="fsa", compression=compression,
                             pipeline_depth=pipeline_depth)
    if schedule_spec is None:
        # precedence: --schedule > GEOMX_CHAOS_SCHEDULE (via the config)
        # > the seeded default (party 1 dies at step 3, returns at 6)
        env_sched = ChaosSchedule.from_config(cfg)
        schedule = env_sched if env_sched is not None else \
            ChaosSchedule.from_spec("seed=1234;blackout@3:party=1,steps=3")
    else:
        schedule = ChaosSchedule.from_spec(schedule_spec)
    if schedule.last_step >= steps:
        raise ValueError(
            f"--steps={steps} ends before the schedule's last event "
            f"(step {schedule.last_step}); raise --steps")
    sync = get_sync_algorithm(cfg)
    trainer = Trainer(get_model(model_name, num_classes=10), topo,
                      optax.sgd(0.1, momentum=0.9), sync=sync, config=cfg,
                      donate=False)
    local_b = max(1, batch // 2)
    rng = np.random.RandomState(0)
    # parties get DIFFERENT data so the renormalized survivor mean is a
    # real claim, not an identity
    x = (rng.rand(2, 1, local_b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, local_b)).astype(np.int32)
    sharding = topo.batch_sharding(trainer.mesh)
    xb = jax.device_put(x, sharding)
    yb = jax.device_put(y, sharding)
    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])

    def wan_bytes_per_step(num_live):
        # per-party dc-tier payload x live parties actually transmitting
        comp = sync.dc_compressor if pipeline_depth == 0 \
            else sync.inner.dc_compressor
        params = jax.tree.map(lambda a: a[0, 0], state.params)
        return int(comp.wire_bytes(params)) * num_live

    controller = PartyLivenessController.from_config(cfg)
    timeline = []
    epochs_log = []
    catchup_bytes = None
    degraded_check = None
    current = controller.epoch
    with ChaosEngine(schedule, controller) as engine:
        for step in range(steps):
            fired = engine.tick(step)
            ep = controller.epoch
            if ep.version != current.version:
                readmitting = ep.num_live > current.num_live
                if readmitting:
                    # what the survivors broadcast to the returning
                    # party before the mask widens back over it
                    catchup_bytes = len(trainer.catchup_payload(state))
                state = trainer.apply_membership(state, ep)
                epochs_log.append({"step": step, "version": ep.version,
                                   "live_mask": list(ep.live_mask),
                                   "events": [e.kind for e in fired]})
                current = ep
                # the solo-run cross-check only holds for the lossless
                # path: a 1-party reference short-circuits the dc
                # compressor (axis size 1), so under lossy compression
                # the two runs differ by the compression error itself,
                # not by the membership algebra (which
                # tests/test_resilience.py proves bit-exact in-program)
                if not ep.all_live and degraded_check is None \
                        and pipeline_depth == 0 and compression == "none":
                    degraded_check = _verify_survivor_mean(
                        trainer, state, x, y, model_name)
            state, metrics = trainer.train_step(state, xb, yb)
            timeline.append({
                "step": step,
                "num_live": float(metrics["num_live_parties"]),
                "loss": round(float(metrics["loss"]), 5),
                "wan_bytes": wan_bytes_per_step(ep.num_live)})
    jax.block_until_ready(jax.tree.leaves(state.params)[0])
    # the replicas must agree after the full blackout/readmit cycle
    leaf = np.asarray(jax.device_get(jax.tree.leaves(state.params)[0]))
    replicas_consistent = bool(np.array_equal(leaf[0, 0], leaf[1, 0]))

    pre = timeline[0]
    post = timeline[-1]
    degraded_steps = [t for t in timeline if t["num_live"] < 2]
    out = {
        "mode": "compare_resilience",
        "model": model_name, "compression": compression,
        "pipeline_depth": pipeline_depth, "batch": batch, "steps": steps,
        "schedule": schedule.spec(),
        "membership_epochs": epochs_log,
        "timeline": timeline,
        "completed_without_stall": len(timeline) == steps,
        "degraded_steps": len(degraded_steps),
        "degraded_num_live": ([t["num_live"] for t in degraded_steps][:1]
                              or [None])[0],
        "catchup_bytes": catchup_bytes,
        "replicas_consistent_after_cycle": replicas_consistent,
        "party_count_restored": post["num_live"] == pre["num_live"],
        "wire_volume_restored": post["wan_bytes"] == pre["wan_bytes"],
    }
    if degraded_check is not None:
        out.update(degraded_check)
    return out


def _verify_survivor_mean(trainer, state, x, y, model_name):
    """One degraded step vs a single-party run from the SAME state and
    the survivor's batch: under the live mask (True, False) both must
    produce the survivor-mean update.  The masked AGGREGATE itself is
    bit-exact (tests/test_resilience.py proves it inside one program);
    across the two differently-compiled programs here XLA may
    reassociate reductions by an ulp, so the check tolerates float32
    rounding and records the max deviation.  Also records the
    dc-collective count in the degraded step's traced jaxpr (the
    collective is still present; the mask renormalizes it)."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.models import get_model
    from geomx_tpu.sync import FSA
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    from geomx_tpu.train.state import unreplicate_tree

    sharding = trainer.topology.batch_sharding(trainer.mesh)
    xb = jax.device_put(x, sharding)
    yb = jax.device_put(y, sharding)
    structure = _dc_weight_path_analysis(trainer.train_step, state, xb, yb)
    s_deg, m_deg = trainer.train_step(state, xb, yb)

    host = jax.tree.map(lambda a: np.asarray(jax.device_get(a))[0, 0],
                        (state.params, state.opt_state, state.model_state))
    topo1 = HiPSTopology(num_parties=1, workers_per_party=1)
    solo = Trainer(get_model(model_name, num_classes=10), topo1,
                   optax.sgd(0.1, momentum=0.9), sync=FSA(), donate=False)
    from geomx_tpu.train.state import TrainState, replicate_tree
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    p, o, ms = host
    solo_state = TrainState(
        step=jax.device_put(jnp.asarray(0, jnp.int32),
                            NamedSharding(solo.mesh, PartitionSpec())),
        params=replicate_tree(p, topo1, solo.mesh),
        opt_state=replicate_tree(o, topo1, solo.mesh),
        model_state=replicate_tree(ms, topo1, solo.mesh),
        sync_state=replicate_tree(
            solo.sync.init_state(p, model_state=ms), topo1, solo.mesh))
    sh1 = topo1.batch_sharding(solo.mesh)
    s_solo, m_solo = solo.train_step(
        solo_state, jax.device_put(x[:1], sh1), jax.device_put(y[:1], sh1))

    pd = unreplicate_tree(s_deg.params)
    ps = unreplicate_tree(s_solo.params)
    max_diff = max((float(np.max(np.abs(a - b))) if a.size else 0.0)
                   for a, b in zip(jax.tree.leaves(pd),
                                   jax.tree.leaves(ps)))
    close = all(np.allclose(a, b, rtol=1e-6, atol=1e-8)
                for a, b in zip(jax.tree.leaves(pd), jax.tree.leaves(ps)))
    return {"degraded_matches_survivor_mean": bool(close),
            "survivor_mean_max_abs_diff": max_diff,
            "degraded_dc_collectives_total":
                structure.get("dc_collectives_total"),
            "degraded_loss_vs_solo": [round(float(m_deg["loss"]), 6),
                                      round(float(m_solo["loss"]), 6)]}


def compare_resilience_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--compression="):
            kwargs["compression"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--pipeline-depth="):
            kwargs["pipeline_depth"] = int(a.split("=", 1)[1])
    _emit(_compare_resilience(**kwargs))


# --------------------------------------------------------------------------
# --compare-telemetry: the unified telemetry plane's acceptance mode
# --------------------------------------------------------------------------


def _host_plane_trace(out_dir: str) -> dict:
    """A 2-party in-process WAN round with per-party profilers: two
    local GeoPSServers relay to one global server, every server dumps a
    Chrome trace, and merge_traces folds them into ONE timeline whose
    push/merge/relay/pull spans share a round_id per WAN round.  Writes
    the merged trace (and the per-rank dumps) into ``out_dir``; returns
    the linkage verdict."""
    import json as _json

    import numpy as np

    from geomx_tpu.service import GeoPSClient, GeoPSServer
    from geomx_tpu.telemetry import merge_traces, rounds_in_trace

    glob = GeoPSServer(num_workers=2, mode="sync", rank=0).start()
    locs = [GeoPSServer(num_workers=1, mode="sync", rank=r + 1,
                        global_addr=("127.0.0.1", glob.port)).start()
            for r in range(2)]
    for s in (glob, *locs):
        s.profiler.set_state(True)
    clients = [GeoPSClient(("127.0.0.1", s.port), sender_id=i)
               for i, s in enumerate(locs)]
    merged_path = os.path.join(out_dir, "geomx_telemetry_merged_trace.json")
    try:
        for c in clients:
            c.init("w", np.zeros((64,), np.float32))
        rounds_run = 2
        for _ in range(rounds_run):
            for i, c in enumerate(clients):
                c.push("w", np.full((64,), float(i + 1), np.float32))
            for c in clients:
                c.pull("w", timeout=60.0)
        paths = [s.profiler.dump(os.path.join(
            out_dir, f"geomx_telemetry_rank{s.rank}.json"))
            for s in (glob, *locs)]
        merged = merge_traces(paths, labels=["global", "party0", "party1"])
        with open(merged_path, "w") as f:
            _json.dump(merged, f)
        rounds = {rk: evs for rk, evs in rounds_in_trace(merged).items()
                  if rk[0] == "w"}
        # every WAN round must appear on BOTH sides of the wire: spans
        # from >= 2 processes (a party's relay + the global's merge)
        linked = bool(rounds) and all(
            len(evs) >= 3 and len({e["pid"] for e in evs}) >= 2
            for evs in rounds.values())
    finally:
        for c in clients:
            c.stop_server()
            c.close()
        glob.join(10)
        for s in locs:
            s.join(10)
    return {"wan_rounds_traced": len(rounds),
            "trace_rounds_linked": linked,
            "merged_trace": merged_path}


def _compare_telemetry(model_name: str = "resnet20", batch: int = 64,
                       iters: int = 6, compression: str = "bsc,0.01",
                       out_dir: str = None):
    """The telemetry acceptance run on a 2-party CPU mesh:

    1. disabled path — the traced step's jaxpr must be byte-identical
       (addresses canonicalized) to a build with the probe module
       excised, and the probe collector must never be called;
    2. enabled path — run real steps, read the in-graph probe values
       back, and measure the overhead against the disabled path;
    3. export plane — publish the probes, render the registry as
       Prometheus text and round-trip it through the strict parser;
    4. tracing plane — an in-process 2-party host-plane round produces
       one merged Chrome trace with round_id-linked WAN spans.

    One JSON line out, artifacts (merged trace + JSONL event log) in
    ``out_dir`` for CI to upload.
    """
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry import (parse_prometheus_text,
                                     render_prometheus)
    from geomx_tpu.telemetry import probes as probes_mod
    from geomx_tpu.telemetry.probes import canonicalize_jaxpr
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            "compare-telemetry needs >= 2 devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    out_dir = out_dir or tempfile.mkdtemp(prefix="geomx_telemetry_")
    os.makedirs(out_dir, exist_ok=True)
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    local_b = max(1, batch // 2)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, local_b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, local_b)).astype(np.int32)
    events_path = os.path.join(out_dir, "geomx_telemetry_events.jsonl")
    try:
        os.unlink(events_path)
    except OSError:
        pass

    def build(telemetry: bool):
        cfg = GeoConfig(num_parties=2, workers_per_party=1,
                        compression=compression, telemetry=telemetry,
                        telemetry_events=events_path if telemetry else "")
        return Trainer(get_model(model_name, num_classes=10), topo,
                       optax.sgd(0.1, momentum=0.9),
                       sync=get_sync_algorithm(cfg), config=cfg,
                       donate=False)

    def time_steps(trainer, state):
        state, m = trainer.train_step(state, xb, yb)  # compile + warm
        state, m = trainer.train_step(state, xb, yb)
        jax.block_until_ready(m["loss"])
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                state, m = trainer.train_step(state, xb, yb)
            jax.block_until_ready(m["loss"])
            d = (time.perf_counter() - t0) / iters
            best = d if best is None else min(best, d)
        return best, state, m

    # -- disabled path: jaxpr identity vs a probe-excised build --------------
    saved_env = os.environ.pop("GEOMX_TELEMETRY", None)
    try:
        tr_off = build(False)
        sharding = topo.batch_sharding(tr_off.mesh)
        xb = jax.device_put(x, sharding)
        yb = jax.device_put(y, sharding)
        state_off = tr_off.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
        jaxpr_off = canonicalize_jaxpr(str(
            jax.make_jaxpr(tr_off.train_step)(state_off, xb, yb)))
        probe_calls = {"n": 0}
        orig = probes_mod.collect_step_probes

        def _raiser(*a, **k):
            probe_calls["n"] += 1
            raise AssertionError("probe collector ran on the disabled path")

        probes_mod.collect_step_probes = _raiser
        try:
            tr_base = build(False)
            jaxpr_base = canonicalize_jaxpr(str(
                jax.make_jaxpr(tr_base.train_step)(state_off, xb, yb)))
        finally:
            probes_mod.collect_step_probes = orig
        jaxpr_identical = (jaxpr_off == jaxpr_base
                           and probe_calls["n"] == 0)
        t_off, state_off, _ = time_steps(tr_off, state_off)

        # -- enabled path: probe values + overhead ---------------------------
        tr_on = build(True)
        state_on = tr_on.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
        t_on, state_on, m = time_steps(tr_on, state_on)
        m = jax.device_get(m)
        telem = m.get("telemetry", {})
        probes_out = {
            k: (float(v) if np.ndim(v) == 0
                else [float(u) for u in np.asarray(v)])
            for k, v in sorted(telem.items())}
        tr_on._publish_telemetry(telem, iteration=iters)
        overhead_pct = 100.0 * (t_on - t_off) / t_off if t_off else 0.0

        # -- export plane: registry -> text -> strict parser ----------------
        text = render_prometheus()
        parsed = parse_prometheus_text(text)
        prometheus_valid = ("geomx_step_probe" in parsed
                            and any(parsed[f]["samples"]
                                    for f in parsed))

        # -- tracing plane: merged 2-party WAN round trace -------------------
        trace_info = _host_plane_trace(out_dir)
    finally:
        if saved_env is not None:
            os.environ["GEOMX_TELEMETRY"] = saved_env

    return {
        "mode": "compare_telemetry", "model": model_name,
        "compression": compression, "batch": batch, "iters": iters,
        "probes": probes_out,
        "step_time_ms_off": round(t_off * 1e3, 3),
        "step_time_ms_on": round(t_on * 1e3, 3),
        "overhead_pct": round(overhead_pct, 2),
        "overhead_within_bound": overhead_pct <= 2.0,
        "jaxpr_identical_when_disabled": bool(jaxpr_identical),
        "disabled_path_probe_calls": probe_calls["n"],
        "prometheus_valid": bool(prometheus_valid),
        "prometheus_families": len(parsed),
        "wan_rounds_traced": trace_info["wan_rounds_traced"],
        "trace_rounds_linked": trace_info["trace_rounds_linked"],
        "artifacts": {"merged_trace": trace_info["merged_trace"],
                      "event_log": events_path},
    }


def compare_telemetry_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--compression="):
            kwargs["compression"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--iters="):
            kwargs["iters"] = int(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_telemetry(**kwargs))


# --------------------------------------------------------------------------
# --attribute: the step-time observatory's acceptance mode
# --------------------------------------------------------------------------


def _modeled_attribution_trace(compute_us, dcn_us, comm_on_weight_path):
    """Synthesize a Chrome-trace timeline from MEASURED per-step compute
    durations plus the DCE-verified dependency structure, with the DCN
    delay injected per that structure — compare-pipeline's modeling rule
    in trace form:

    - collective ON the weight path (synchronous): the step blocks on
      the wire, so the comm span follows compute serially inside the
      step window (it all shows up as exposed_comms);
    - collective OFF the weight path (pipelined): the collective
      launched as step t's gradients land completes under step t+1's
      compute, so the comm span overlaps the next window (hidden_comms,
      with only the part outrunning compute exposed).

    attribute_trace over this timeline is the modeled phase breakdown
    under the delay.  On a serial host backend the modeling is the only
    honest way to show the overlap: a slept delay would block both
    modes equally (see _compare_pipeline)."""
    events = []
    t = 0.0
    inflight_end = 0.0
    for i, c in enumerate(compute_us):
        if comm_on_weight_path:
            step_dur = c + dcn_us
            comm_start = t + c
        else:
            step_dur = max(c, inflight_end - t)
            comm_start = t + c           # launch when the grads are ready
            inflight_end = comm_start + dcn_us
        events.append({"name": "train/step", "cat": "step", "ph": "X",
                       "ts": t, "dur": step_dur, "pid": 1, "tid": 1,
                       "args": {"step": i}})
        events.append({"name": "train/compute", "cat": "compute",
                       "ph": "X", "ts": t, "dur": c, "pid": 1, "tid": 1})
        events.append({"name": ("dc_allreduce/injected"
                                if comm_on_weight_path
                                else "dc_pipeline/launch"),
                       "cat": "comm", "ph": "X", "ts": comm_start,
                       "dur": dcn_us, "pid": 1, "tid": 2})
        t += step_dur
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"modeled": True, "dcn_us": dcn_us,
                         "comm_on_weight_path": bool(comm_on_weight_path)}}


def _attribute_links_record(out_dir: str) -> dict:
    """The LinkObservatory half of --attribute: fold a REAL 2-party
    host-plane round trace (the compare-telemetry harness) into one
    observatory, then replay two synthetic per-party round traces with
    an INJECTED 8x bandwidth asymmetry and verify the snapshot
    reproduces it."""
    from geomx_tpu.telemetry.links import LinkObservatory

    obs_real = LinkObservatory()
    real = _host_plane_trace(out_dir)
    with open(real["merged_trace"]) as f:
        merged = json.load(f)
    folded_real = obs_real.ingest_trace(merged)
    real_links = sorted(obs_real.snapshot())

    # injected asymmetry: party0's uplink moves the same payload 8x
    # faster than party1's.  Timestamps/anchors are pinned constants —
    # replaying the same rounds must produce the same snapshot.
    anchor_us = 1_700_000_000 * 1e6
    payload = 1 << 20                      # 1 MiB per round
    fast_s, ratio_injected = 0.050, 8.0
    slow_s = fast_s * ratio_injected
    obs = LinkObservatory(alpha=0.3, stale_after_s=30.0)
    for rank, secs in ((0, fast_s), (1, slow_s)):
        events = []
        ts = 0.0
        for r in range(6):
            events.append({"name": "RelayToGlobal:w", "cat": "comm",
                           "ph": "X", "ts": ts, "dur": secs * 1e6,
                           "pid": 100 + rank, "tid": 1,
                           "args": {"key": "w", "round_id": r,
                                    "payload_bytes": payload}})
            ts += 2 * secs * 1e6
        obs.ingest_trace({"traceEvents": events,
                          "metadata": {"anchor_unix_us": anchor_us,
                                       "rank": rank}})
    snap = obs.snapshot(now=anchor_us / 1e6 + 1.0)
    bw0 = snap["rank0->global"]["throughput_bps"]
    bw1 = snap["rank1->global"]["throughput_bps"]
    ratio_measured = bw0 / bw1 if bw1 else None
    return {
        "real_rounds_folded": folded_real,
        "real_links": real_links,
        "wan_rounds_traced": real["wan_rounds_traced"],
        "trace_rounds_linked": real["trace_rounds_linked"],
        "injected_bandwidth_ratio": ratio_injected,
        "measured_bandwidth_ratio": (round(ratio_measured, 4)
                                     if ratio_measured else None),
        "asymmetry_reproduced": (
            ratio_measured is not None
            and abs(ratio_measured - ratio_injected) / ratio_injected
            < 0.01),
        "snapshot": {k: {f: snap[k][f] for f in
                         ("throughput_bps", "rtt_s", "loss_rate",
                          "samples", "confidence", "stale")}
                     for k in sorted(snap)},
    }


def _attribute_flight_record(out_dir: str, healthy_probes: list) -> dict:
    """The flight-recorder half of --attribute: prime a recorder with
    REAL probe records from the measured run, then replay a seeded
    healthy tail and inject a NaN into party 1's per-party vector at a
    known step.  The auto-dump must fire at exactly that step and the
    bundle must name the poisoned party."""
    import numpy as np

    from geomx_tpu.telemetry.flight import FlightRecorder

    flight_dir = os.path.join(out_dir, "flight")
    rec = FlightRecorder(capacity=64, dump_dir=flight_dir)
    step = 0
    for probes in healthy_probes:
        fired = rec.record(step, probes)
        assert not fired, f"healthy probes fired {fired}"
        step += 1
    rng = np.random.RandomState(1234)
    base = healthy_probes[-1] if healthy_probes else {
        "grad_norm_global": 1.0, "party_grad_nonfinite": [0.0, 0.0]}
    for _ in range(8):                       # seeded healthy tail
        p = dict(base)
        p["grad_norm_global"] = float(
            abs(base.get("grad_norm_global", 1.0))
            * (1.0 + 0.01 * rng.randn()))
        p["party_grad_nonfinite"] = [0.0, 0.0]
        fired = rec.record(step, p)
        step += 1
    poison_step = step
    poisoned = dict(base)
    poisoned["grad_norm_global"] = float("nan")
    poisoned["party_grad_nonfinite"] = [0.0, 1.0]
    fired = rec.record(poison_step, poisoned)
    bundle = None
    if rec.dumps:
        with open(rec.dumps[-1]) as f:
            bundle = json.load(f)
    return {
        "fired_rules": sorted({f["rule"] for f in fired}),
        "fired_at_step": poison_step if fired else None,
        "bundle_path": rec.dumps[-1] if rec.dumps else None,
        "bundle_poisoned_parties": (bundle or {}).get("poisoned_parties"),
        "bundle_ring_len": len((bundle or {}).get("ring", [])),
        "deterministic_trigger": bool(
            fired and bundle
            and bundle["step"] == poison_step
            and bundle["poisoned_parties"] == [1]),
    }


def _attribute(model_name: str = "resnet20", batch: int = 64,
               iters: int = 6, dcn_ms: float = 100.0,
               out_dir: str = None):
    """The step-time observatory's acceptance run on a 2x4 CPU mesh
    (8 virtual devices), for three configs — vanilla, bsc, pipelined:

    1. run real steps with the host profiler bracketing each dispatch
       (train/step + train/compute, the same spans Trainer.fit emits)
       and attribute the REAL trace: the four phase fractions must sum
       to ~1.0 by construction;
    2. model the phase breakdown under an injected DCN delay from the
       measured compute durations + the DCE-verified dependency
       structure (_modeled_attribution_trace): the exposed-comms
       fraction must DROP when GEOMX_PIPELINE_DEPTH=1 takes the
       collective off the weight path;
    3. grade each config against the roofline (telemetry/roofline.py):
       MFU + compute/memory/wire bound verdict from
       ``compiled.cost_analysis()`` and the sync algorithm's wire
       accounting;
    4. fold WAN round traces into the LinkObservatory and verify an
       injected per-link bandwidth asymmetry is reproduced from replay;
    5. prime a flight recorder with the run's real probe records and
       verify the seeded NaN injection auto-dumps a bundle naming the
       poisoned party.

    One JSON line out; artifacts (per-config phase JSON, flight
    bundles, merged WAN trace) land in ``out_dir`` for CI to upload.
    """
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry.attribution import (attribute_trace,
                                                 publish_attribution)
    from geomx_tpu.telemetry.roofline import trainer_roofline
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    from geomx_tpu.utils.profiler import Profiler

    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            "--attribute needs the 8-virtual-device mesh (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    out_dir = out_dir or tempfile.mkdtemp(prefix="geomx_attribute_")
    os.makedirs(out_dir, exist_ok=True)
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    local_b = max(1, batch // 8)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 4, local_b, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 4, local_b)).astype(np.int32)
    dcn_us = dcn_ms * 1e3

    configs = {
        "vanilla": dict(compression="none", pipeline_depth=0),
        "bsc": dict(compression="bsc,0.01", pipeline_depth=0),
        "pipelined": dict(compression="none", pipeline_depth=1),
    }
    per_config = {}
    healthy_probes = []
    for name, kw in configs.items():
        cfg = GeoConfig(num_parties=2, workers_per_party=4,
                        telemetry=True, **kw)
        trainer = Trainer(get_model(model_name, num_classes=10), topo,
                          optax.sgd(0.1, momentum=0.9),
                          sync=get_sync_algorithm(cfg), config=cfg,
                          donate=False)
        sharding = topo.batch_sharding(trainer.mesh)
        xb = jax.device_put(x, sharding)
        yb = jax.device_put(y, sharding)
        state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
        structure = _dc_weight_path_analysis(trainer.train_step, state,
                                             xb, yb)
        state, m = trainer.train_step(state, xb, yb)   # compile + warm
        jax.block_until_ready(m["loss"])

        prof = Profiler(profile_all=True)
        prof.set_state(True)
        for i in range(iters):
            with prof.scope("train/step", "step", args={"step": i}):
                with prof.scope("train/compute", "compute"):
                    state, m = trainer.train_step(state, xb, yb)
                    jax.block_until_ready(m["loss"])
        prof.set_state(False)
        telem = jax.device_get(m.get("telemetry", {}))
        if telem:
            healthy_probes.append({
                k: (float(v) if np.ndim(v) == 0
                    else [float(u) for u in np.asarray(v)])
                for k, v in telem.items()})

        att_real = attribute_trace(prof.to_doc())
        compute_us = [s["compute"] + s["hidden_comms"]
                      for s in att_real["steps"]]
        on_path = structure.get("dc_collectives_on_weight_path")
        att_model = attribute_trace(_modeled_attribution_trace(
            compute_us, dcn_us, comm_on_weight_path=bool(on_path)))
        step_s = (sum(compute_us) / len(compute_us)) / 1e6
        roof = _roofline_fields(lambda: trainer_roofline(
            trainer, state, xb, yb, step_time_s=step_s,
            wire_seconds=dcn_ms / 1e3))
        publish_attribution(att_model["summary"])
        frac_sum = sum(att_real["summary"].values())
        per_config[name] = {
            **structure,
            "steps": att_real["num_steps"],
            "phase_fractions": {k: round(v, 4)
                                for k, v in att_real["summary"].items()},
            "phase_fractions_sum": round(frac_sum, 6),
            "fractions_sum_ok": abs(frac_sum - 1.0) < 1e-6,
            "modeled_under_delay": {
                k: round(v, 4) for k, v in att_model["summary"].items()},
            "step_time_ms": round(step_s * 1e3, 3),
            **roof,
        }
        with open(os.path.join(out_dir, f"attribution_{name}.json"),
                  "w") as f:
            json.dump({"real": att_real, "modeled": att_model,
                       "roofline": roof}, f, indent=2, default=str)

    sync_exposed = per_config["vanilla"]["modeled_under_delay"][
        "exposed_comms"]
    pipe_exposed = per_config["pipelined"]["modeled_under_delay"][
        "exposed_comms"]
    links = _attribute_links_record(out_dir)
    flight = _attribute_flight_record(out_dir, healthy_probes)
    return {
        "mode": "attribute", "model": model_name, "batch": batch,
        "iters": iters, "dcn_delay_ms": dcn_ms,
        "configs": per_config,
        "exposed_comms_sync": sync_exposed,
        "exposed_comms_pipelined": pipe_exposed,
        "exposed_drops_under_pipelining": pipe_exposed < sync_exposed,
        "links": links,
        "flight": flight,
        "artifacts": {"out_dir": out_dir},
    }


def attribute_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--iters="):
            kwargs["iters"] = int(a.split("=", 1)[1])
        elif a.startswith("--dcn-ms="):
            kwargs["dcn_ms"] = float(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_attribute(**kwargs))


# --------------------------------------------------------------------------
# --compare-control: the Graft Pilot's closed-loop acceptance mode
# --------------------------------------------------------------------------

class _WanModel:
    """Deterministic WAN time model for the control acceptance replay.

    Link quality is a pure function of the active chaos shaping
    overrides (``protocol.get_link_shaping`` — the SAME hook the real
    relay transport sleeps on), so the seeded schedule fully determines
    the bandwidth/delay timeline.  Routing: ``routes == ()`` is direct
    fan-in; a relay order's head is the merge sink (the paper's ASK1
    pairing) — non-sink parties cross the fast intra-overlay link to
    the sink, which forwards ONE merged payload up its own uplink.

    The per-party wire bytes come from the run's own telemetry
    (capacity x the measured emitted fraction): sentinel tails pack
    LAST in the fixed-k wire layout, so a length-prefixed transport
    sends only the real pairs — the byte saving the traced ratio scale
    buys without a recompile (docs/control.md).
    """

    def __init__(self, num_parties: int, base_bps: float,
                 p2p_bps: float, base_delay_s: float, compute_s: float):
        self.P = int(num_parties)
        self.base_bps = float(base_bps)
        self.p2p_bps = float(p2p_bps)
        self.base_delay_s = float(base_delay_s)
        self.compute_s = float(compute_s)

    def _bw(self, party: int) -> float:
        from geomx_tpu.service.protocol import get_link_shaping
        return self.base_bps * get_link_shaping(party).get("factor", 1.0)

    def _delay(self, party: int) -> float:
        from geomx_tpu.service.protocol import get_link_shaping
        return self.base_delay_s + \
            get_link_shaping(party).get("delay_ms", 0.0) / 1e3

    def uplink_seconds(self, party: int, nbytes: float) -> float:
        return self._delay(party) + nbytes / self._bw(party)

    def round_seconds(self, nbytes: float, routes: tuple) -> float:
        """One synchronous WAN round: every party's aggregate reaches
        the global tier; the gate waits for the slowest path."""
        if not routes:
            return max(self.uplink_seconds(p, nbytes)
                       for p in range(self.P))
        sink = int(routes[0])
        hop = max((nbytes / self.p2p_bps
                   for p in range(self.P) if p != sink), default=0.0)
        return hop + self.uplink_seconds(sink, nbytes)

    def step_seconds(self, nbytes: float, depth: int,
                     routes: tuple) -> dict:
        wan = self.round_seconds(nbytes, routes)
        hidden = min(wan, self.compute_s) if depth else 0.0
        exposed = wan - hidden
        total = self.compute_s + exposed
        return {"total": total, "wan": wan, "exposed": exposed,
                "hidden": hidden}

    def feed_observatory(self, obs, nbytes: float, t: float) -> None:
        """Per-round link probes: every party's DIRECT uplink gets a
        payload-sized observation each step (the host heartbeat
        doubling as a link probe), so measured throughput is goodput at
        the real transfer size, a rerouted party's estimate stays
        fresh, and the relay can release when the link recovers."""
        for p in range(self.P):
            obs.observe(f"party{p}", "global", nbytes=nbytes,
                        seconds=self.uplink_seconds(p, nbytes), t=t)

    def publish_phases(self, rec: dict) -> None:
        from geomx_tpu.telemetry.attribution import publish_attribution
        total = rec["total"] or 1.0
        publish_attribution({
            "compute": (self.compute_s - rec["hidden"]) / total,
            "hidden_comms": rec["hidden"] / total,
            "exposed_comms": rec["exposed"] / total,
            "host_stall": 0.0})


def _control_make_data(n: int = 1536, seed: int = 0):
    """Learnable synthetic classification data (class-prototype images
    + noise): the loss really descends, so time-to-loss-target is a
    live metric, and generation is seeded."""
    import numpy as np
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 10, size=n).astype(np.int32)
    # signal/noise tuned so the smoothed loss crosses the floor-derived
    # target in the run's LAST third — after the chaos window — for
    # every grid config: time-to-target then prices the degradation
    # into every run instead of letting an early crosser skip it
    protos = rng.rand(10, 32, 32, 3) * 70
    x = protos[y] + rng.rand(n, 32, 32, 3) * 185
    return np.clip(x, 0, 255).astype(np.uint8), y


def _control_run(model_name: str, schedule_spec: str, steps: int,
                 batch: int, ratio: float, depth: int, wan_kw: dict,
                 controller: bool, ratio_bounds=None):
    """One seeded replay: a real CPU training run whose WAN wall-clock
    is modeled per step from the chaos-shaped link timeline.  Returns
    the per-step record list plus (for controller runs) the decision
    log snapshot and the jit-cache pin evidence."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.control import (ControlActuator, ControlSensors,
                                   DepthPolicy, GraftPilot, RatioPolicy,
                                   RelayPolicy, reset_decision_log)
    from geomx_tpu.models import get_model
    from geomx_tpu.resilience import ChaosEngine, ChaosSchedule
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry import reset_link_observatory, reset_registry
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    P = 3
    reset_registry()
    observatory = reset_link_observatory()
    log = reset_decision_log()

    topo = HiPSTopology(num_parties=P, workers_per_party=1)
    cfg = GeoConfig(num_parties=P, workers_per_party=1,
                    compression=f"bsc,{ratio}", bucket_bytes=1 << 20,
                    pipeline_depth=depth, telemetry=True,
                    control=controller)
    sync = get_sync_algorithm(cfg)
    # lr inside the staleness-1 stability envelope: the d1 grid configs
    # (and the controller's own depth-1 episodes) must converge, not
    # oscillate (sync/pipeline.py's halved-headroom note)
    trainer = Trainer(get_model(model_name, num_classes=10), topo,
                      optax.sgd(0.012), sync=sync, config=cfg,
                      donate=False)
    x, y = _control_make_data()
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    sharding = topo.batch_sharding(trainer.mesh)
    local_b = batch // P

    model = _WanModel(P, **wan_kw)
    routes: tuple = ()
    pilot = actuator = None
    ratio_cache_sizes = []
    if controller:
        sensors = ControlSensors(observatory=observatory,
                                 min_confidence=0.5,
                                 compute_s_fn=lambda s: model.compute_s)
        pilot = GraftPilot(
            sensors,
            ratio=RatioPolicy(ratio, bounds=ratio_bounds, cooldown=3,
                              deadband=0.2),
            # wide Schmitt band ABOVE the healthy wan fraction (~0.25
            # at the calibrated bandwidth): depth-1 engages only while
            # degradation is unrouted and releases once the relay (or a
            # lower ratio) brings the wire back under compute — the
            # staleness toll is paid for a handful of steps, not the
            # whole run
            depth=DepthPolicy(enter=0.45, exit=0.40, confirm=2,
                              cooldown=3),
            relay=RelayPolicy(min_gain=2.0, cooldown=3,
                              min_confidence=0.5))

        def relay_apply(order):
            nonlocal routes
            routes = tuple(int(p[5:]) for p in order)  # "party<i>" -> i

        actuator = ControlActuator(trainer=trainer,
                                   relay_apply=relay_apply, log=log)

    schedule = ChaosSchedule.from_spec(schedule_spec)
    clock = 0.0
    timeline = []
    # the no-recompile pin: a ratio actuation only rewrites a host-side
    # operand, so any recompile it caused would surface at the NEXT
    # dispatch — the "after" sample must come from the step FOLLOWING
    # the actuation, against the same compiled program (a depth switch
    # in between legitimately swaps the program; that pair is skipped)
    pending_pin = None   # (step_fn, cache_size_before_actuation)
    with ChaosEngine(schedule, controller=None) as engine:
        for it in range(steps):
            engine.tick(it)
            sel = (np.arange(batch) + it * batch) % len(x)
            xb = jax.device_put(
                x[sel].reshape(P, 1, local_b, 32, 32, 3), sharding)
            yb = jax.device_put(y[sel].reshape(P, 1, local_b), sharding)
            state, metrics = trainer.train_step(state, xb, yb)
            if pending_pin is not None:
                step_fn, before = pending_pin
                if step_fn is trainer.train_step:
                    ratio_cache_sizes.append(
                        (before, step_fn._cache_size()))
                pending_pin = None
            telem = jax.device_get(metrics["telemetry"])
            trainer._publish_telemetry(telem, it + 1)
            emitted = float(telem.get("bsc_emitted_fraction", 1.0))
            nbytes = float(telem["dc_wire_bytes"]) * emitted
            rec = model.step_seconds(nbytes, trainer.control_depth(),
                                     routes)
            clock += rec["total"]
            model.feed_observatory(observatory, nbytes, clock)
            model.publish_phases(rec)
            timeline.append({
                "step": it, "loss": float(metrics["loss"]),
                "t": round(clock, 6), "wan_s": round(rec["wan"], 6),
                "exposed_s": round(rec["exposed"], 6),
                "bytes": nbytes, "depth": trainer.control_depth(),
                "routes": list(routes)})
            if pilot is not None:
                for dec in pilot.tick(it, now=clock):
                    if dec.kind == "ratio":
                        pending_pin = (trainer.train_step,
                                       trainer.train_step._cache_size())
                    state = actuator.apply(state, dec)
    jax.block_until_ready(state.step)
    return {"timeline": timeline,
            "decisions": log.snapshot() if controller else [],
            "ratio_cache_sizes": ratio_cache_sizes}


def _smoothed_losses(timeline, window: int = 3):
    import numpy as np
    losses = [rec["loss"] for rec in timeline]
    return [float(np.mean(losses[max(0, i - window + 1):i + 1]))
            for i in range(len(losses))]


def _time_to_target(timeline, target: float):
    for rec, sm in zip(timeline, _smoothed_losses(timeline)):
        if sm <= target:
            return rec["t"]
    return None


def _compare_control(model_name: str = "mlp", batch: int = 48,
                     steps: int = 60, schedule_spec: str = None,
                     loss_target: float = None, out_dir: str = None):
    """The control-plane acceptance replay (docs/control.md): under a
    seeded WAN-degradation chaos schedule, the Graft Pilot must beat
    every static (ratio x depth) config on time-to-loss-target, its
    decision log must reproduce bit-identically across two runs of the
    same seed, and ratio retuning must leave the cached-executable
    count untouched (the no-recompile guarantee)."""
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if len(devs) < 3:
        raise RuntimeError(
            "compare-control needs >= 3 devices for the 3-party dc axis "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=3)")
    ratio_hi = 0.25
    ratio_lo = ratio_hi / 8.0
    if schedule_spec is None:
        # party 1's uplink degrades hard for two thirds of the run: 8x
        # throughput throttle plus 300 ms of added round latency — the
        # delay-dominated regime where neither a lower ratio nor
        # pipelining alone saves a static config, only re-forming the
        # relay chain does.  The window opens at step 2 so no config
        # can cross the loss target before paying it
        schedule_spec = ("seed=77;throttle@2:party=1,factor=0.125,"
                        "steps=38;delay@2:party=1,ms=300,steps=38")
    # WAN constants: healthy uplinks move the hi-ratio payload in ~10%
    # of a compute step (wire comfortably hidden by compute — the depth
    # policy has no reason to pay staleness while links are healthy),
    # the intra-overlay link is 8x wider (metro DC pairs vs WAN)
    compute_s = 0.05
    wan_kw = dict(base_bps=0.0, p2p_bps=0.0, base_delay_s=0.01,
                  compute_s=compute_s)

    # calibrate base bandwidth from the model's real wire accounting
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor
    from geomx_tpu.models import get_model
    probe_model = get_model(model_name, num_classes=10)
    variables = jax.eval_shape(
        lambda: probe_model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((2, 32, 32, 3), jnp.uint8),
                                 train=False))
    params_shapes = dict(variables)["params"]
    comp = BucketedCompressor(BiSparseCompressor(ratio=ratio_hi),
                              bucket_bytes=1 << 20)
    hi_bytes = float(comp.wire_bytes(params_shapes))
    wan_kw["base_bps"] = hi_bytes / (0.1 * compute_s)
    wan_kw["p2p_bps"] = 8.0 * wan_kw["base_bps"]

    grid = {
        "hi_d0": (ratio_hi, 0), "hi_d1": (ratio_hi, 1),
        "lo_d0": (ratio_lo, 0), "lo_d1": (ratio_lo, 1),
    }
    static = {}
    for name, (r, d) in grid.items():
        run = _control_run(model_name, schedule_spec, steps, batch,
                           r, d, wan_kw, controller=False)
        static[name] = run

    bounds = (ratio_lo, ratio_hi)
    ctrl = _control_run(model_name, schedule_spec, steps, batch,
                        ratio_hi, 0, wan_kw, controller=True,
                        ratio_bounds=bounds)
    ctrl2 = _control_run(model_name, schedule_spec, steps, batch,
                         ratio_hi, 0, wan_kw, controller=True,
                         ratio_bounds=bounds)
    dec_a = json.dumps(ctrl["decisions"], sort_keys=True)
    dec_b = json.dumps(ctrl2["decisions"], sort_keys=True)

    if loss_target is None:
        # the tightest loss EVERY config eventually achieved (plus a 2%
        # knife-edge margin): everyone reaches it, so the comparison is
        # purely about TIME under the shared degradation
        floors = [min(_smoothed_losses(run["timeline"]))
                  for run in list(static.values()) + [ctrl]]
        loss_target = round(max(floors) * 1.02, 6)

    static_times = {name: _time_to_target(run["timeline"], loss_target)
                    for name, run in static.items()}
    ctrl_time = _time_to_target(ctrl["timeline"], loss_target)
    beats = ctrl_time is not None and all(
        t is None or ctrl_time < t for t in static_times.values())
    ratio_pinned = bool(ctrl["ratio_cache_sizes"]) and all(
        a == b for a, b in ctrl["ratio_cache_sizes"])

    out = {
        "mode": "compare_control",
        "model": model_name, "batch": batch, "steps": steps,
        "schedule": schedule_spec,
        "loss_target": loss_target,
        "wan": {k: round(v, 6) if isinstance(v, float) else v
                for k, v in wan_kw.items()},
        "ratio_grid": [ratio_lo, ratio_hi],
        "static": {
            name: {
                "ratio": grid[name][0], "depth": grid[name][1],
                "time_to_target_s": static_times[name],
                "final_loss": round(
                    _smoothed_losses(run["timeline"])[-1], 5),
                "total_time_s": round(run["timeline"][-1]["t"], 4),
            } for name, run in static.items()},
        "controller": {
            "time_to_target_s": ctrl_time,
            "final_loss": round(_smoothed_losses(ctrl["timeline"])[-1], 5),
            "total_time_s": round(ctrl["timeline"][-1]["t"], 4),
            "decisions": ctrl["decisions"],
            "decision_count": len(ctrl["decisions"]),
            "decision_kinds": sorted({d["kind"]
                                      for d in ctrl["decisions"]}),
        },
        "controller_beats_all_static": bool(beats),
        "decision_log_deterministic": dec_a == dec_b,
        "ratio_retune_without_recompile": ratio_pinned,
        "ratio_actuations": len(ctrl["ratio_cache_sizes"]),
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        from geomx_tpu.utils.atomicio import atomic_json_dump
        atomic_json_dump(os.path.join(out_dir, "control_decisions.json"),
                         {"decisions": ctrl["decisions"],
                          "timeline": ctrl["timeline"],
                          "static": {n: r["timeline"]
                                     for n, r in static.items()}})
        out["artifacts"] = {"decision_log":
                            os.path.join(out_dir,
                                         "control_decisions.json")}
    return out


def compare_control_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--loss-target="):
            kwargs["loss_target"] = float(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_control(**kwargs))


# --------------------------------------------------------------------------
# --compare-capsule: run capsules — whole-run capture, bit-exact offline
# replay, and the fitted step-time cost model (docs/telemetry.md "Run
# capsules", docs/performance.md "What-if search over capsules")
# --------------------------------------------------------------------------

def _capsule_pilot_factory(ratio_hi, ratio_bounds):
    """The ONE policy-stack constructor the live run and the offline
    replay share: identical constructor args + identical observations
    = identical decision sequence (policies are deterministic)."""
    from geomx_tpu.control import (DepthPolicy, GraftPilot, RatioPolicy,
                                   RelayPolicy)

    def factory(sensors):
        return GraftPilot(
            sensors,
            ratio=RatioPolicy(ratio_hi, bounds=ratio_bounds, cooldown=3,
                              deadband=0.2),
            depth=DepthPolicy(enter=0.45, exit=0.40, confirm=2,
                              cooldown=3),
            relay=RelayPolicy(min_gain=2.0, cooldown=3,
                              min_confidence=0.5))
    return factory


def _capsule_run(model_name: str, schedule_spec: str, steps: int,
                 batch: int, compression: str, depth: int, wan_kw: dict,
                 controller: bool = False, ratio_bounds=None,
                 ratio_hi: float = None, capsule_path: str = None,
                 sample_every: int = 10):
    """One seeded 3-party replay on the chaos-shaped WAN clock (the
    --compare-control harness), optionally recording a RunCapsule:
    per-step sensor records + timing at the publish boundary, the link
    journal via the observatory tap, periodic registry samples on the
    virtual clock, the profiler trace, and (controller runs) the
    decision log — everything the offline replay and the cost model
    consume."""
    import jax
    import numpy as np
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.control import (ControlActuator, ControlSensors,
                                   reset_decision_log)
    from geomx_tpu.models import get_model
    from geomx_tpu.resilience import ChaosEngine, ChaosSchedule
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry import (RunCapsule, reset_link_observatory,
                                     reset_registry)
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    from geomx_tpu.utils.profiler import get_profiler

    P = 3
    reset_registry()
    observatory = reset_link_observatory()
    log = reset_decision_log()
    prof = get_profiler()

    topo = HiPSTopology(num_parties=P, workers_per_party=1)
    cfg = GeoConfig(num_parties=P, workers_per_party=1,
                    compression=compression, bucket_bytes=1 << 20,
                    pipeline_depth=depth, telemetry=True,
                    control=controller)
    sync = get_sync_algorithm(cfg)
    net = get_model(model_name, num_classes=10)
    trainer = Trainer(net, topo, optax.sgd(0.012), sync=sync,
                      config=cfg, donate=False)
    x, y = _control_make_data()
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    sharding = topo.batch_sharding(trainer.mesh)
    local_b = batch // P

    model = _WanModel(P, **wan_kw)
    capsule = None
    if capsule_path:
        capsule = RunCapsule(
            capsule_path, config=cfg,
            extra_manifest={"wan": {k: float(v)
                                    for k, v in wan_kw.items()},
                            "schedule": schedule_spec,
                            "compression": compression, "depth": depth})
        capsule.attach_observatory(observatory)
        # record the MODEL's parameter layout (abstract init), not the
        # TrainState's party-stacked device arrays — the cost model's
        # candidate wire accounting is per party per step
        import jax.numpy as jnp
        from jax.tree_util import keystr, tree_flatten_with_path
        abstract = jax.eval_shape(
            lambda: net.init(jax.random.PRNGKey(0),
                             jnp.zeros((2, 32, 32, 3), jnp.uint8),
                             train=False))
        flat, _ = tree_flatten_with_path(dict(abstract)["params"])
        capsule.set_param_shapes(
            {keystr(path): {"shape": list(leaf.shape),
                            "dtype": str(leaf.dtype)}
             for path, leaf in flat})
        prof.reset()
        prof.set_state(True)

    routes: tuple = ()
    pilot = actuator = None
    if controller:
        sensors = ControlSensors(observatory=observatory,
                                 min_confidence=0.5,
                                 compute_s_fn=lambda s: model.compute_s)
        pilot = _capsule_pilot_factory(ratio_hi, ratio_bounds)(sensors)

        def relay_apply(order):
            nonlocal routes
            routes = tuple(int(p[5:]) for p in order)

        actuator = ControlActuator(trainer=trainer,
                                   relay_apply=relay_apply, log=log)

    schedule = ChaosSchedule.from_spec(schedule_spec) \
        if schedule_spec else ChaosSchedule.from_spec("seed=1")
    clock = 0.0
    timeline = []
    with ChaosEngine(schedule, controller=None) as engine:
        for it in range(steps):
            engine.tick(it)
            sel = (np.arange(batch) + it * batch) % len(x)
            xb = jax.device_put(
                x[sel].reshape(P, 1, local_b, 32, 32, 3), sharding)
            yb = jax.device_put(y[sel].reshape(P, 1, local_b), sharding)
            with prof.scope("train/step", "step", args={"step": it}):
                with prof.scope("train/compute", "compute"):
                    state, metrics = trainer.train_step(state, xb, yb)
            telem = jax.device_get(metrics["telemetry"])
            trainer._publish_telemetry(telem, it + 1)
            emitted = float(telem.get("bsc_emitted_fraction", 1.0))
            nbytes = float(telem["dc_wire_bytes"]) * emitted
            rec = model.step_seconds(nbytes, trainer.control_depth(),
                                     routes)
            clock += rec["total"]
            model.feed_observatory(observatory, nbytes, clock)
            model.publish_phases(rec)
            if capsule is not None:
                # heartbeat-sized probe per uplink on a separate peer:
                # invisible to the policies (they filter peer=="global")
                # but it gives the cost model the second equation that
                # separates link latency from bandwidth per step
                # (telemetry/costmodel.fit_paired_link)
                for p in range(P):
                    observatory.observe(
                        f"party{p}", "probe", nbytes=4096.0,
                        seconds=model.uplink_seconds(p, 4096.0),
                        t=clock)
            timeline.append({
                "step": it, "loss": float(metrics["loss"]),
                "t": round(clock, 6), "total_s": rec["total"],
                "wan_s": rec["wan"], "exposed_s": rec["exposed"],
                "bytes": nbytes, "depth": trainer.control_depth()})
            if capsule is not None:
                capsule.record_step(
                    it, t=clock,
                    timing={"total_s": rec["total"],
                            "compute_s": model.compute_s,
                            "wan_s": rec["wan"],
                            "exposed_s": rec["exposed"]},
                    extra={"wire_bytes": nbytes})
                if it % sample_every == 0 or it == steps - 1:
                    capsule.sampler.sample(now=clock)
            if pilot is not None:
                for dec in pilot.tick(it, now=clock):
                    state = actuator.apply(state, dec)
    jax.block_until_ready(state.step)
    live_snapshot = observatory.snapshot(now=clock)
    out = {"timeline": timeline,
           "decisions": log.snapshot() if controller else [],
           "live_snapshot": live_snapshot,
           "end_clock": clock,
           "mean_step_s": sum(r["total_s"] for r in timeline)
           / max(len(timeline), 1)}
    if capsule is not None:
        capsule.add_trace(prof.to_doc(), label="rank0")
        prof.set_state(False)
        out["capsule"] = capsule.write(now=clock)
    return out


def _compare_capsule(model_name: str = "mlp", batch: int = 48,
                     steps: int = 48, schedule_spec: str = None,
                     out_dir: str = None):
    """The run-capsule acceptance (ISSUE 15): a 3-party CPU mesh under
    a seeded chaos schedule proves (a) ONE capsule captures the run —
    manifest, registry time series, step records, link journal, trace,
    decisions; (b) offline replay reproduces the live LinkObservatory
    snapshot AND the GraftPilot decision sequence bit-identically; (c)
    the fitted step-time cost model ranks a 6-point ratio x depth x
    compressor grid in the same order as measured step times, with
    per-config relative error reported; (d) ``runcap explain`` on a
    clean-vs-throttled capsule pair names the degraded link and the
    phase that moved."""
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if len(devs) < 3:
        raise RuntimeError(
            "compare-capsule needs >= 3 devices for the 3-party dc axis "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=3)")
    out_dir = out_dir or "/tmp/geomx_capsule_bench"
    os.makedirs(out_dir, exist_ok=True)

    # byte-distinct grid levels: bsc pairs cost 8 B/emitted element, so
    # ratio 0.125 = 1 B/elem and 0.015625 = 0.125 B/elem sit clear of
    # fp16's 2 B/elem — no two configs tie on wire bytes
    ratio_hi = 0.125
    ratio_lo = ratio_hi / 8.0
    if schedule_spec is None:
        # party 1's uplink degrades 8x (+150 ms) for the middle of the
        # run: the capsule must record the degradation, the replay must
        # reproduce the controller's response to it, and the cost model
        # must price it into every candidate at the steps it covered
        schedule_spec = ("seed=77;throttle@4:party=1,factor=0.125,"
                        "steps=24;delay@4:party=1,ms=150,steps=24")
    compute_s = 0.05
    wan_kw = dict(base_bps=0.0, p2p_bps=0.0, base_delay_s=0.01,
                  compute_s=compute_s)
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.bucketing import BucketedCompressor
    from geomx_tpu.models import get_model
    probe_model = get_model(model_name, num_classes=10)
    variables = jax.eval_shape(
        lambda: probe_model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((2, 32, 32, 3), jnp.uint8),
                                 train=False))
    params_shapes = dict(variables)["params"]
    comp = BucketedCompressor(BiSparseCompressor(ratio=ratio_hi),
                              bucket_bytes=1 << 20)
    hi_bytes = float(comp.wire_bytes(params_shapes))
    wan_kw["base_bps"] = hi_bytes / (0.1 * compute_s)
    wan_kw["p2p_bps"] = 8.0 * wan_kw["base_bps"]
    bounds = (ratio_lo, ratio_hi)

    # ---- (a)+(b): the controller capsule + bit-exact offline replay
    cap_a_path = os.path.join(out_dir, "capsule_controller.json")
    ctrl = _capsule_run(model_name, schedule_spec, steps, batch,
                        f"bsc,{ratio_hi}", 0, wan_kw, controller=True,
                        ratio_bounds=bounds, ratio_hi=ratio_hi,
                        capsule_path=cap_a_path)
    from geomx_tpu.telemetry import Capsule, StepTimeCostModel
    cap_a = Capsule.load(cap_a_path)
    manifest_ok = all(
        cap_a.manifest.get(k) for k in
        ("kind", "version", "config", "env", "build", "observatory",
         "param_shapes")) and bool(cap_a.registry_samples) \
        and len(cap_a.steps) == steps and bool(cap_a.traces) \
        and bool(cap_a.decisions) \
        and cap_a.manifest.get("journal_dropped", 1) == 0 \
        and cap_a.manifest.get("steps_dropped", 1) == 0
    replay_snap = cap_a.link_snapshot(now=ctrl["end_clock"])
    snap_identical = (json.dumps(replay_snap, sort_keys=True)
                      == json.dumps(ctrl["live_snapshot"],
                                    sort_keys=True))
    replay_decs = cap_a.replay_decisions(
        _capsule_pilot_factory(ratio_hi, bounds), min_confidence=0.5,
        compute_s_fn=lambda s: compute_s)
    decs_identical = (json.dumps(replay_decs, sort_keys=True)
                      == json.dumps(ctrl["decisions"], sort_keys=True))

    # ---- (c): cost model fitted from the capsule vs measured grid
    cost_model = StepTimeCostModel.fit(cap_a)
    grid = {
        "bsc_hi_d0": (f"bsc,{ratio_hi}", 0),
        "bsc_hi_d1": (f"bsc,{ratio_hi}", 1),
        "bsc_lo_d0": (f"bsc,{ratio_lo}", 0),
        "bsc_lo_d1": (f"bsc,{ratio_lo}", 1),
        "fp16_d0": ("fp16", 0),
        "fp16_d1": ("fp16", 1),
    }
    cap_b_path = os.path.join(out_dir, "capsule_throttled.json")
    grid_out = {}
    for name, (spec, d) in grid.items():
        run = _capsule_run(
            model_name, schedule_spec, steps, batch, spec, d, wan_kw,
            capsule_path=cap_b_path if name == "bsc_hi_d0" else None)
        pred = cost_model.predict({"compression": spec, "depth": d,
                                   "bucket_bytes": 1 << 20})
        measured = run["mean_step_s"]
        grid_out[name] = {
            "compression": spec, "depth": d,
            "measured_step_s": round(measured, 6),
            "predicted_step_s": round(pred["mean_step_s"], 6),
            "predicted_wire_bytes": pred["wire_bytes"],
            "rel_error": round(
                abs(pred["mean_step_s"] - measured) / measured, 4),
        }
    measured_order = sorted(grid_out,
                            key=lambda n: grid_out[n]["measured_step_s"])
    predicted_order = sorted(
        grid_out, key=lambda n: grid_out[n]["predicted_step_s"])
    rank_exact = measured_order == predicted_order
    max_rel_err = max(g["rel_error"] for g in grid_out.values())

    # ---- (d): runcap explain names the injected degradation
    cap_c_path = os.path.join(out_dir, "capsule_clean.json")
    _capsule_run(model_name, "", steps, batch, f"bsc,{ratio_hi}", 0,
                 wan_kw, capsule_path=cap_c_path)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        import runcap
    finally:
        sys.path.pop(0)
    findings = runcap.explain_docs(runcap.load_doc(cap_c_path),
                                   runcap.load_doc(cap_b_path))
    names_link = any(
        f["kind"] == "link" and "party1" in f["name"]
        and (f["metric"] == "throughput_bps" or f["metric"] == "rtt_s")
        for f in findings)
    names_phase = any(f["kind"] == "phase"
                      and f["name"] == "exposed_comms"
                      for f in findings)

    out = {
        "mode": "compare_capsule",
        "model": model_name, "batch": batch, "steps": steps,
        "parties": 3,
        "schedule": schedule_spec,
        "wan": {k: round(float(v), 6) for k, v in wan_kw.items()},
        "capsule_recorded": bool(manifest_ok),
        "capsule_sections": {
            "steps": len(cap_a.steps),
            "link_observations": len(cap_a.link_journal),
            "registry_samples": len(cap_a.registry_samples),
            "traces": len(cap_a.traces),
            "decisions": len(cap_a.decisions),
            "events": len(cap_a.events),
        },
        "replay_snapshot_bit_identical": bool(snap_identical),
        "replay_decisions_bit_identical": bool(decs_identical),
        "decision_count": len(ctrl["decisions"]),
        "cost_model": cost_model.to_json(),
        "grid": grid_out,
        "measured_order": measured_order,
        "predicted_order": predicted_order,
        "cost_model_rank_exact": bool(rank_exact),
        "cost_model_max_rel_err": round(max_rel_err, 4),
        "cost_model_error_bounded": bool(max_rel_err <= 0.35),
        "explain_findings": [f["text"] for f in findings],
        "explain_names_degraded_link": bool(names_link),
        "explain_names_phase": bool(names_phase),
        "artifacts": {"capsule_controller": cap_a_path,
                      "capsule_throttled": cap_b_path,
                      "capsule_clean": cap_c_path},
    }
    out["ok"] = all(out[k] for k in (
        "capsule_recorded", "replay_snapshot_bit_identical",
        "replay_decisions_bit_identical", "cost_model_rank_exact",
        "cost_model_error_bounded", "explain_names_degraded_link",
        "explain_names_phase"))
    return out


def compare_capsule_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_capsule(**kwargs))


# --------------------------------------------------------------------------
# parent: watchdog + single-line aggregation
# --------------------------------------------------------------------------

_CHILD_PROC = None  # the live bench child, for the signal handler to kill


def _drain(pipe, q):
    for line in iter(pipe.readline, ""):
        q.put(line)
    q.put(None)


def _run_attempt(init_timeout, total_timeout, results, on_event=None,
                 extra_env=None):
    """Spawn one fresh bench child; fill `results` from its event stream.
    Returns (init_ok, error): init_ok False means the backend never came
    up in this child (worth retrying in a new process).  ``on_event`` is
    called after every absorbed event so the parent can re-print its
    aggregated snapshot line (the external-kill survivability path).
    ``extra_env``: resume-state overrides scoped to THIS child — the
    resume vars are stripped from the inherited environment so a stale
    GEOMX_BENCH_DONE leaked by a wrapper can't skip units in a first
    child."""
    global _CHILD_PROC
    env = dict(os.environ, GEOMX_BENCH_CHILD="1")
    env.pop("GEOMX_BENCH_DONE", None)
    env.pop("GEOMX_BENCH_BARE_SPS", None)
    # per-ATTEMPT phase trail: a watchdog bundle must diagnose the child
    # that hung, not inherit how far some earlier attempt got
    results.pop("init_phases", None)
    if extra_env:
        env.update(extra_env)
    # One process per chip: this parent has imported nothing that brings
    # up a JAX backend (bench.py's module scope is stdlib only; jax and
    # geomx_tpu are imported inside the child's functions), so the child
    # is the only process that ever holds the chip.
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    _CHILD_PROC = proc
    q: "queue.Queue" = queue.Queue()
    threading.Thread(target=_drain, args=(proc.stdout, q),
                     daemon=True).start()
    stderr_buf = []
    stderr_thread = threading.Thread(target=lambda: stderr_buf.extend(
        proc.stderr.read().splitlines()[-200:]), daemon=True)
    stderr_thread.start()

    t_start = time.monotonic()
    t_backend = None
    error = None
    done = False
    watchdog_fired = None

    while True:
        if t_backend is None:
            deadline = t_start + init_timeout
            phase, budget = "backend init", init_timeout
        else:
            deadline = t_backend + total_timeout
            phase, budget = "measurement", total_timeout
        try:
            line = q.get(timeout=max(0.1, deadline - time.monotonic()))
        except queue.Empty:
            error = (f"watchdog: {phase} exceeded {budget:g}s — "
                     "TPU backend hung or config wedged")
            watchdog_fired = phase
            # diagnosability (a silent 480s burn says nothing): ask the
            # child for all-thread stack dumps (faulthandler is
            # registered on SIGUSR1 in child_main) and give it a moment
            # to flush stderr before the kill
            try:
                proc.send_signal(signal.SIGUSR1)
                time.sleep(2.0)
            except (OSError, AttributeError):
                pass
            proc.kill()
            break
        if line is None:  # child exited (rc checked after the reap below)
            break
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            continue
        kind = ev.pop("event", None)
        if kind == "phase":
            # per-phase init timestamps: bounds WHICH phase a later
            # watchdog trip was stuck in
            results.setdefault("init_phases", {})[
                str(ev.get("phase"))] = ev.get("elapsed_s")
        elif kind == "backend_up":
            t_backend = time.monotonic()
            results["backend"] = ev
        elif kind == "config":
            results["configs"][ev.pop("config",
                                      f"config{len(results['configs'])}")] = ev
        elif kind == "fit_loop":
            results["fit_loop"] = ev
        elif kind == "microbench":
            results["microbench"] = ev
        elif kind == "profile":
            results["profile"] = ev
        elif kind == "batch_sweep":
            results["batch_sweep"] = ev
        elif kind == "tta":
            results["tta"] = ev
        elif kind == "tta_s2d":
            results["tta_s2d"] = ev
        elif kind == "done":
            done = True
        if kind is not None and on_event is not None:
            on_event()

    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
    stderr_thread.join(timeout=5)
    if error is None and not done and proc.poll() not in (0, None):
        # stdout EOF can arrive before the process is reaped; re-check
        # so a crashed child is reported, not silently absorbed
        error = f"bench child exited rc={proc.poll()}"
    if watchdog_fired is not None:
        # the full diagnostic rides the record (structured, not crammed
        # into the error string): which phase hung, how far init got,
        # and the child's all-thread stacks at kill time
        results["watchdog"] = {
            "phase": watchdog_fired,
            "init_phases": dict(results.get("init_phases", {})),
            "stacks": stderr_buf[-120:],
        }
        phases = results.get("init_phases", {})
        if phases:
            last = max(phases, key=lambda k: phases[k] or 0)
            error += (f" | last init phase: {last} at "
                      f"{phases[last]}s; stacks in watchdog.stacks")
    if error is not None and stderr_buf:
        error += " | " + " | ".join(stderr_buf[-5:])[-2000:]
    return t_backend is not None, error


def _unit_ok(rec):
    """A phase result counts as good when it exists and neither it nor
    any of its sub-entries (batch-sweep points) recorded an error."""
    return (rec is not None and "error" not in rec
            and not any(isinstance(v, dict) and "error" in v
                        for v in rec.values()))


_RESUMABLE = ("tta", "tta_s2d", "fit_loop", "microbench", "profile",
              "batch_sweep")

def _completed_units(results):
    units = {f"config:{name}" for name, rec in results["configs"].items()
             if _unit_ok(rec)}
    units.update(k for k in _RESUMABLE if _unit_ok(results[k]))
    return units


def _has_failures(results, error):
    """True when a resume child could improve the record: the attempt
    itself errored (child crash / watchdog) or some recorded phase
    carries an error."""
    if error is not None:
        return True
    if any(not _unit_ok(rec) for rec in results["configs"].values()):
        return True
    return any(results[k] is not None and not _unit_ok(results[k])
               for k in _RESUMABLE)


def _resume_clears_error(results, r_ok, r_err):
    """Whether a finished resume attempt justifies clearing the record's
    top-level error: only when the attempt itself was clean AND no
    recorded unit still carries a failure — a resume that re-ran some
    units while others kept their errors must not report success."""
    return bool(r_ok) and r_err is None and not _has_failures(results, None)


def _aggregate(results, error, attempt_log, partial):
    """The one-line JSON record.  Called after every phase (partial=True)
    and once at exit (partial=False) — the last line printed is always
    the authoritative record, however the process ends."""
    backend = results["backend"]
    configs = results["configs"]

    headline = configs.get("vanilla_local") or next(
        (c for c in configs.values() if "samples_per_sec_per_chip" in c), None)
    value = (headline or {}).get("samples_per_sec_per_chip") or 0.0
    out = {
        "metric": METRIC,
        "value": value,
        "unit": "samples/sec",
        "vs_baseline": round(value / REFERENCE_GPU_SAMPLES_PER_SEC, 3),
        "baseline_note": ("reference publishes no numbers (BASELINE.md); "
                          "10k samples/sec is our documented estimate for "
                          "its V100-class demo GPU"),
        "device": backend,
        "mfu": (headline or {}).get("mfu"),
        "configs": configs,
        "fit_loop": results["fit_loop"],
        "microbench": results["microbench"],
        "profile": results["profile"],
        "batch_sweep": results["batch_sweep"],
    }
    if results["tta"] is not None:
        out["time_to_accuracy"] = results["tta"]
    if results["tta_s2d"] is not None:
        out["time_to_accuracy_s2d"] = results["tta_s2d"]
        t_std = (results["tta"] or {}).get("seconds")
        t_s2d = results["tta_s2d"].get("seconds")
        if (t_std and t_s2d
                and (results["tta"] or {}).get("reached")
                and results["tta_s2d"].get("reached")):
            # >1 means the TPU-optimized variant hits the same accuracy
            # bar faster in wall-clock (the only comparison that counts)
            out["s2d_time_to_target_speedup"] = round(t_std / t_s2d, 3)
            e_std = (results["tta"] or {}).get("seconds_excl_jit")
            e_s2d = results["tta_s2d"].get("seconds_excl_jit")
            if e_std and e_s2d:
                # compile-free: the architecture comparison once the
                # one-time jit cost (cached across runs) is excluded
                out["s2d_time_to_target_speedup_excl_jit"] = round(
                    e_std / e_s2d, 3)
    if results.get("init_phases"):
        out["init_phases"] = results["init_phases"]
    if results.get("watchdog"):
        # the watchdog's forensic bundle: hung phase, per-phase init
        # timestamps, and the child's all-thread stack dumps at kill
        out["watchdog"] = results["watchdog"]
    if partial:
        out["partial"] = True
    if error is not None:
        out["error"] = error
    if attempt_log and (len(attempt_log) > 1
                        or any(a.get("error") for a in attempt_log)):
        out["init_attempts"] = attempt_log
    return out


def parent_main():
    init_timeout = float(os.environ.get("GEOMX_BENCH_INIT_TIMEOUT", "480"))
    total_timeout = float(os.environ.get("GEOMX_BENCH_TIMEOUT", "1500"))
    attempts = int(os.environ.get("GEOMX_BENCH_INIT_ATTEMPTS", "2"))

    results = {"configs": {}, "backend": None, "fit_loop": None,
               "microbench": None, "profile": None, "batch_sweep": None,
               "tta": None, "tta_s2d": None}
    attempt_log = []

    def print_snapshot(error=None, partial=True):
        print(json.dumps(_aggregate(results, error, attempt_log, partial)),
              flush=True)

    def on_signal(signum, frame):
        # the driver's timeout, not ours.  The handler may interrupt the
        # main thread mid-print, so the final record goes out as one
        # atomic os.write on its own line — the tail stays parseable even
        # if it splices after a half-written snapshot.  And the child
        # MUST die with us: an orphaned bench child keeps the TPU runtime
        # wedged for the next process (round-4 failure mode).
        if _CHILD_PROC is not None and _CHILD_PROC.poll() is None:
            try:
                _CHILD_PROC.kill()
            except OSError:
                pass
        out = _aggregate(results, f"killed by signal {signum} mid-run; "
                         "this record is complete through the last "
                         "finished phase", attempt_log, True)
        os.write(1, ("\n" + json.dumps(out) + "\n").encode())
        os._exit(128 + signum)  # a run cut short is a failed run

    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        try:
            signal.signal(sig, on_signal)
        except (ValueError, OSError):
            pass

    # a valid line exists from second zero — even a SIGKILL during
    # backend init leaves a parseable (if empty) record as the tail
    print_snapshot(error="startup: no phase completed yet")

    error = None
    init_ok = False
    for i in range(max(1, attempts)):
        # a retry is the same environment in a fresh process: nothing is
        # scrubbed, switched off or moved to another backend on the way
        init_ok, error = _run_attempt(init_timeout, total_timeout, results,
                                      on_event=print_snapshot)
        attempt_log.append({"attempt": i + 1, "init_ok": init_ok,
                            "error": error})
        if init_ok:  # measurement ran (even if partially) — don't redo
            break
        if i + 1 < attempts:  # backoff before a fresh child
            print_snapshot(error=error)
            time.sleep(min(60.0, 5.0 * (i + 1)))

    # the TPU runtime can crash MID-measurement (extras run r5: configs
    # succeeded, then every later phase died UNAVAILABLE in the same
    # child) — a fresh process recovers the chip, so respawn one child
    # that skips the units already held good and re-runs the rest.  The
    # incremental snapshots mean a resume can only ever improve the
    # final record, never lose what the first child measured.
    resume = int(os.environ.get("GEOMX_BENCH_RESUME_ATTEMPTS", "1"))
    for i in range(resume):
        if not (init_ok and _has_failures(results, error)):
            break
        renv = {"GEOMX_BENCH_DONE": ",".join(
            sorted(_completed_units(results)))}
        bare = (results["configs"].get("vanilla_local") or {}).get(
            "samples_per_sec_per_chip")
        if bare:  # fit_loop's vs_bare_compiled denominator
            renv["GEOMX_BENCH_BARE_SPS"] = str(bare)
        print_snapshot(error=error)
        time.sleep(5.0)
        r_ok, r_err = _run_attempt(init_timeout, total_timeout, results,
                                   on_event=print_snapshot, extra_env=renv)
        attempt_log.append({"attempt": f"resume{i + 1}",
                            "init_ok": r_ok, "error": r_err})
        init_ok = init_ok or r_ok
        if _resume_clears_error(results, r_ok, r_err):
            error = None  # the resume was clean and every unit is good
        # a FAILED resume must not downgrade the record: whatever the
        # first attempt established keeps its error state (the failed
        # resume is on the attempt log), so resume only ever improves

    print_snapshot(error=error, partial=False)
    # the exit code is part of the record: a run whose backend never
    # came up, or whose final record carries an error anywhere, failed
    return 1 if _has_failures(results, error) or not init_ok else 0


# --------------------------------------------------------------------------
# --compare-recovery: kill/restart the global server AND the scheduler
# mid-training; finish bit-exact vs an uninterrupted same-seed baseline
# --------------------------------------------------------------------------


class _RecoveryCluster:
    """One host-plane training cluster: scheduler + global GeoPSServer
    (durable) + per-party local servers relaying up + one worker client
    per party (session-resume armed).  The chaos ``kill@`` verbs drive
    :meth:`lifecycle`: kill = ``crash()`` (abrupt socket severing, only
    the durable store survives), restart = a replacement process image
    on the same durable dir and port."""

    def __init__(self, base_dir: str, parties: int, keys, dim: int,
                 grace_s: float = 30.0):
        import numpy as np

        from geomx_tpu.service import (GeoPSClient, GeoPSServer,
                                       GeoScheduler, SchedulerClient)
        self.np = np
        self.parties = parties
        self.keys = list(keys)
        self.dim = dim
        self.base_dir = base_dir
        self.grace_s = grace_s
        self._GeoPSServer = GeoPSServer
        self._GeoScheduler = GeoScheduler
        self.sched_dir = os.path.join(base_dir, "scheduler")
        self.global_dir = os.path.join(base_dir, "global")
        self.scheduler = GeoScheduler(durable_dir=self.sched_dir,
                                      restart_grace_s=grace_s).start()
        self.sched_port = self.scheduler.port
        self.glob = GeoPSServer(num_workers=parties, mode="sync",
                                accumulate=True, rank=0,
                                durable_dir=self.global_dir,
                                durable_name="global").start()
        self.glob_port = self.glob.port
        self.locals = [
            GeoPSServer(num_workers=1, mode="sync", rank=1 + p,
                        global_addr=("127.0.0.1", self.glob_port),
                        global_sender_id=1000 + p,
                        reconnect=True).start()
            for p in range(parties)]
        self.workers = [
            GeoPSClient(("127.0.0.1", self.locals[p].port), sender_id=p,
                        reconnect=True)
            for p in range(parties)]
        # every party registers with the scheduler under a stable tag —
        # the id-stability-across-restart probe re-registers these
        self.sched_clients = [SchedulerClient(("127.0.0.1",
                                               self.sched_port))
                              for _ in range(parties)]
        self.node_ids = {}
        for p, sc in enumerate(self.sched_clients):
            sc.register("worker", tag=f"{p}.0")
            sc.start_heartbeat(interval_s=1.0)
            self.node_ids[p] = sc.node_id
        for p, w in enumerate(self.workers):
            for key in self.keys:
                w.init(key, np.zeros(dim, np.float32))
        self.restarts = {"server": 0, "scheduler": 0}
        self.kill_t = {}
        self.outage_s = 0.0
        self.killed = set()
        self.post_restart = {"ids_stable": None, "mass_evicted": None,
                             "is_recovery": None, "in_grace": None}

    def lifecycle(self, action: str, node: str) -> None:
        now = time.monotonic()
        if node == "server":
            if action == "kill":
                self.kill_t[node] = now
                self.glob.crash()
                self.killed.add(node)
            else:
                self.glob = self._GeoPSServer(
                    num_workers=self.parties, mode="sync",
                    accumulate=True, rank=0, port=self.glob_port,
                    durable_dir=self.global_dir,
                    durable_name="global").start()
                self.restarts[node] += 1
                self.killed.discard(node)
                self.outage_s += now - self.kill_t.pop(node, now)
        elif node == "scheduler":
            if action == "kill":
                self.kill_t[node] = now
                self.scheduler.crash()
                self.killed.add(node)
            else:
                self.scheduler = self._GeoScheduler(
                    port=self.sched_port, durable_dir=self.sched_dir,
                    restart_grace_s=self.grace_s).start()
                self.restarts[node] += 1
                self.killed.discard(node)
                self.outage_s += now - self.kill_t.pop(node, now)
                self._probe_scheduler_recovery()

    def _probe_scheduler_recovery(self) -> None:
        """Right after a scheduler restart: every party re-registers
        under its original (role, tag) and must get its OLD id back
        (is_recovery), and the grace window must hold the dead list
        shut — a restart is not a mass party death."""
        from geomx_tpu.service import SchedulerClient
        probe = SchedulerClient(("127.0.0.1", self.sched_port))
        try:
            ids_ok, recovery_ok = True, True
            for p in range(self.parties):
                meta = probe.register("worker", tag=f"{p}.0")
                ids_ok &= probe.node_id == self.node_ids[p]
                recovery_ok &= bool(meta["is_recovery"])
            dead = probe.dead_nodes()
            self.post_restart = {
                "ids_stable": ids_ok,
                "is_recovery": recovery_ok,
                "mass_evicted": len(dead) > 0,
                "in_grace": self.scheduler.in_restart_grace()}
        finally:
            probe.close()

    def close(self, stop_tiers: bool = True) -> None:
        if stop_tiers:
            for w in self.workers:
                try:
                    w.stop_server()
                except Exception:
                    pass
        for w in self.workers:
            w.close()
        for sc in self.sched_clients:
            try:
                sc.close()
            except Exception:
                pass
        for srv in self.locals:
            try:
                srv.stop(forward=False)
            except Exception:
                pass
        try:
            self.glob.stop(forward=False)
        except Exception:
            pass
        try:
            self.scheduler.stop()
        except Exception:
            pass


def _recovery_train(base_dir: str, steps: int, parties: int, keys,
                    dim: int, schedule=None, seed: int = 777,
                    stall_dwell_s: float = 0.4):
    """One seeded host-plane training run; returns final params (per
    key, from worker 0), per-step losses, wall time and restart stats.
    With a chaos ``schedule``, the driver replays it on a logical step
    clock that keeps ticking while an outage stalls worker progress —
    so ``restart_after=N`` fires even when the killed node is the very
    thing progress is waiting on."""
    import numpy as np

    from geomx_tpu.resilience.chaos import (ChaosEngine,
                                            set_node_lifecycle_hook)
    cluster = _RecoveryCluster(base_dir, parties, keys, dim)
    targets = {p: {key: np.full(dim, (p + 1) * (k_i + 1), np.float32)
                   for k_i, key in enumerate(keys)}
               for p in range(parties)}
    progress = [0] * parties
    errors = []
    losses = [[] for _ in range(parties)]
    # LOCK-STEP chaos clock: workers may not START step s until the
    # driver has ticked the schedule at s, so kill@s always lands
    # before any step-s traffic — machine speed can neither batch
    # kill+restart into a zero-length outage nor let the run finish
    # before the first kill ever fires
    cond = threading.Condition()
    allowed = [0]

    def worker_loop(p):
        rng = np.random.default_rng(seed + p)
        w = cluster.workers[p]
        try:
            for step in range(steps):
                with cond:
                    while step >= allowed[0]:
                        cond.wait(0.5)
                step_loss = 0.0
                for key in keys:
                    val = w.pull(key, timeout=120.0)
                    g = (val - targets[p][key]) * 0.1 \
                        + rng.normal(0.0, 0.01, dim).astype(np.float32)
                    w.push(key, (-0.05 * g).astype(np.float32))
                    step_loss += float(np.mean(
                        (val - targets[p][key]) ** 2))
                losses[p].append(step_loss / len(keys))
                progress[p] = step + 1
        except Exception as e:  # surfaced in the record, fails the gate
            errors.append(f"party {p}: {e!r}")

    threads = [threading.Thread(target=worker_loop, args=(p,),
                                daemon=True) for p in range(parties)]
    t0 = time.monotonic()
    engine = None
    if schedule is not None:
        engine = ChaosEngine(schedule, controller=None)
        set_node_lifecycle_hook(cluster.lifecycle)
    try:
        for t in threads:
            t.start()
        for s in range(steps):
            if engine is not None:
                engine.tick(s)
            with cond:
                allowed[0] = s + 1
                cond.notify_all()
            # wait for every worker to finish step s before the next
            # tick; during an outage progress stalls on the killed
            # node, so a dwell escape keeps the logical clock moving —
            # that is what delivers the paired restart@ event
            stall_t = time.monotonic()
            last = min(progress)
            while min(progress) <= s:
                if errors or not any(t.is_alive() for t in threads):
                    break
                if min(progress) > last:
                    last, stall_t = min(progress), time.monotonic()
                if cluster.killed and \
                        time.monotonic() - stall_t > stall_dwell_s:
                    break  # outage: advance the clock toward restart@
                time.sleep(0.02)
            if errors:
                break
        with cond:
            allowed[0] = steps  # release anyone still gated
            cond.notify_all()
        for t in threads:
            t.join(timeout=300.0)
        wall_s = time.monotonic() - t0
        final = {key: np.asarray(cluster.workers[0].pull(key,
                                                         timeout=60.0))
                 for key in keys} if not errors else {}
        return {"final": final, "losses": losses, "wall_s": wall_s,
                "errors": errors, "restarts": dict(cluster.restarts),
                "outage_s": cluster.outage_s,
                "post_restart": dict(cluster.post_restart),
                "journal": {
                    "records": (cluster.glob._durable.records_appended
                                if cluster.glob._durable else 0),
                    "journal_bytes": (cluster.glob._durable.journal_bytes()
                                      if cluster.glob._durable else 0),
                    "generation": cluster.glob.generation}}
    finally:
        if engine is not None:
            engine.close()
            set_node_lifecycle_hook(None)
        cluster.close(stop_tiers=not errors)


def _frame_cap_probe() -> dict:
    """Craft a frame whose 4-byte length prefix announces more than
    GEOMX_MAX_FRAME_BYTES: the server must close the connection (no
    allocation, no crash) and keep serving its other clients."""
    import socket as _socket
    import struct as _struct

    import numpy as np

    from geomx_tpu.service import GeoPSClient, GeoPSServer
    from geomx_tpu.service.protocol import max_frame_bytes
    srv = GeoPSServer(num_workers=1, mode="sync", accumulate=True).start()
    try:
        c = GeoPSClient(("127.0.0.1", srv.port), sender_id=0)
        c.init("w", np.zeros(8, np.float32))
        evil = _socket.create_connection(("127.0.0.1", srv.port),
                                         timeout=5.0)
        evil.settimeout(5.0)
        announced = max_frame_bytes() + 1
        evil.sendall(_struct.pack("<I", announced & 0xFFFFFFFF))
        try:
            closed = evil.recv(1) == b""
        except OSError:
            closed = True
        evil.close()
        # the tier survived: the well-behaved client still round-trips
        c.push("w", np.ones(8, np.float32))
        alive = bool(np.allclose(c.pull("w"), 1.0))
        c.stop_server()
        c.close()
        return {"announced_bytes": int(announced),
                "connection_closed": bool(closed),
                "server_survived": alive,
                "enforced": bool(closed and alive)}
    finally:
        srv.join(5)


def _compare_recovery(steps: int = 12, parties: int = 2, dim: int = 256,
                      schedule_spec: str = None,
                      corrupt_spec: str = None, seed: int = 777):
    """The host-plane recovery acceptance (docs/resilience.md):

    1. BASELINE — an uninterrupted seeded run; final params recorded.
    2. RECOVERY — the same seeds with a chaos schedule that kills and
       restarts the global server AND the scheduler mid-training
       (``kill@...restart_after=...``): must finish with params
       BIT-EXACT vs baseline, a bounded stall, stable scheduler ids and
       no grace-window mass eviction.
    3. CORRUPT — the same seeds under a seeded ``corrupt@`` bit-flip
       epoch: zero process crashes, a nonzero
       ``geomx_wire_crc_errors_total``, params again bit-exact (the
       wire-CRC gate turns corruption into retries, not divergence).
    4. FRAME CAP — a hostile length prefix is rejected without an
       allocation and without taking the tier down.
    """
    import numpy as np

    from geomx_tpu.resilience.chaos import ChaosSchedule
    from geomx_tpu.service.protocol import wire_crc_errors
    if schedule_spec is None:
        schedule_spec = (f"seed={seed};"
                         "kill@4:node=server,restart_after=2;"
                         "kill@8:node=scheduler,restart_after=1")
    if corrupt_spec is None:
        corrupt_spec = f"seed={seed};corrupt@1:party=0,rate=35,steps=8"
    schedule = ChaosSchedule.from_spec(schedule_spec)
    corrupt_schedule = ChaosSchedule.from_spec(corrupt_spec)
    keys = ["w0", "w1"]
    rec = {"mode": "compare_recovery", "steps": steps,
           "parties": parties, "dim": dim, "keys": keys,
           "schedule": schedule.spec(),
           "corrupt_schedule": corrupt_schedule.spec()}

    with tempfile.TemporaryDirectory(prefix="geomx_recovery_") as td:
        base = _recovery_train(os.path.join(td, "baseline"), steps,
                               parties, keys, dim, schedule=None,
                               seed=seed)
        reco = _recovery_train(os.path.join(td, "recovery"), steps,
                               parties, keys, dim, schedule=schedule,
                               seed=seed)
        crc_before = wire_crc_errors()
        corr = _recovery_train(os.path.join(td, "corrupt"), steps,
                               parties, keys, dim,
                               schedule=corrupt_schedule, seed=seed)
        crc_errors = wire_crc_errors() - crc_before

    def digest(final):
        import hashlib
        h = hashlib.sha256()
        for key in keys:
            h.update(np.ascontiguousarray(final[key]).tobytes())
        return h.hexdigest()

    def bit_exact(a, b):
        return bool(a and b and all(
            np.array_equal(a[key], b[key]) for key in keys))

    stall_s = max(0.0, reco["wall_s"] - base["wall_s"])
    rec["baseline"] = {"wall_s": round(base["wall_s"], 3),
                       "errors": base["errors"],
                       "loss_final": base["losses"][0][-1]
                       if base["losses"][0] else None,
                       "params_digest": digest(base["final"])
                       if base["final"] else None}
    rec["recovery"] = {"wall_s": round(reco["wall_s"], 3),
                       "errors": reco["errors"],
                       "restarts": reco["restarts"],
                       "outage_s": round(reco["outage_s"], 3),
                       "post_restart": reco["post_restart"],
                       "journal": reco["journal"],
                       "params_digest": digest(reco["final"])
                       if reco["final"] else None}
    rec["corrupt"] = {"wall_s": round(corr["wall_s"], 3),
                      "errors": corr["errors"],
                      "crc_errors": crc_errors,
                      "loss_final": corr["losses"][0][-1]
                      if corr["losses"][0] else None,
                      "params_digest": digest(corr["final"])
                      if corr["final"] else None}
    rec["frame_cap"] = _frame_cap_probe()

    # ---- the acceptance gates (benchtrend + recovery-smoke CI) -------
    rec["params_bit_exact"] = bit_exact(base["final"], reco["final"])
    rec["server_restarted"] = reco["restarts"]["server"] >= 1
    rec["scheduler_restarted"] = reco["restarts"]["scheduler"] >= 1
    rec["recovery_stall_s"] = round(stall_s, 3)
    # bounded: the stall may not exceed the injected outage plus a
    # fixed resume allowance (reconnect backoff + resend timers)
    rec["recovery_stall_bounded"] = bool(
        stall_s <= reco["outage_s"] + 15.0)
    rec["scheduler_ids_stable"] = bool(
        reco["post_restart"].get("ids_stable")
        and reco["post_restart"].get("is_recovery"))
    rec["scheduler_no_mass_evict"] = \
        reco["post_restart"].get("mass_evicted") is False
    rec["corrupt_zero_crashes"] = not corr["errors"]
    rec["corrupt_crc_nonzero"] = crc_errors > 0
    rec["corrupt_loss_unchanged"] = bit_exact(base["final"],
                                              corr["final"])
    rec["frame_cap_enforced"] = rec["frame_cap"]["enforced"]
    rec["ok"] = bool(
        not base["errors"] and not reco["errors"]
        and rec["params_bit_exact"] and rec["server_restarted"]
        and rec["scheduler_restarted"] and rec["recovery_stall_bounded"]
        and rec["scheduler_ids_stable"]
        and rec["scheduler_no_mass_evict"]
        and rec["corrupt_zero_crashes"] and rec["corrupt_crc_nonzero"]
        and rec["corrupt_loss_unchanged"] and rec["frame_cap_enforced"])
    return rec


def compare_recovery_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--parties="):
            kwargs["parties"] = int(a.split("=", 1)[1])
        elif a.startswith("--dim="):
            kwargs["dim"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--corrupt-schedule="):
            kwargs["corrupt_spec"] = a.split("=", 1)[1]
        elif a.startswith("--seed="):
            kwargs["seed"] = int(a.split("=", 1)[1])
    _emit(_compare_recovery(**kwargs))


# --------------------------------------------------------------------------
# --compare-manyparty: 16+ virtual parties against a key-range SHARDED
# global tier (scheduler-owned map) under shard-targeted chaos — finish
# bit-exact vs an uninterrupted same-seed baseline, with merge
# throughput scaling over shard count (docs/resilience.md "Many-party
# global tier")
# --------------------------------------------------------------------------


class _ManyPartyCluster:
    """Scheduler + N durable GeoPSServer shards (key-range map v1) +
    P virtual parties, each a session-resume-armed ShardedGlobalClient
    pushing P3-chunked gradients.  The chaos ``kill@...node=shard<i>``
    verbs drive :meth:`lifecycle`: kill = ``crash()``; restart = a
    replacement on the same durable journal — same port for most
    shards, but ``failover_shard`` restarts on a NEW port plus a
    scheduler ``shard_failover`` map bump (the missed-restart-window
    path: journal replayed into a replacement, clients redirected)."""

    def __init__(self, base_dir: str, parties: int, shards: int, keys,
                 dim: int, failover_shard=None, grace_s: float = 30.0,
                 p3: bool = True):
        import numpy as np

        from geomx_tpu.service import (GeoScheduler, ShardedGlobalClient,
                                       start_sharded_global_tier)
        from geomx_tpu.service.server import GeoPSServer
        from geomx_tpu.service.shardmap import even_bounds
        self.np = np
        self.parties, self.num_shards = parties, shards
        self.keys, self.dim = list(keys), dim
        self.failover_shard = failover_shard
        self._GeoPSServer = GeoPSServer
        self.tier_dir = os.path.join(base_dir, "tier")
        self.bounds = even_bounds(shards)
        self.scheduler = GeoScheduler(
            durable_dir=os.path.join(base_dir, "scheduler"),
            restart_grace_s=grace_s).start()
        self.sched_addr = ("127.0.0.1", self.scheduler.port)
        self.shards = start_sharded_global_tier(
            self.sched_addr, num_shards=shards, num_workers=parties,
            durable_dir=self.tier_dir)
        self.ports = [s.port for s in self.shards]
        self.workers = [
            ShardedGlobalClient(self.sched_addr, sender_id=p,
                                reconnect=True,
                                p3_slice_elems=(max(8, dim // 2)
                                                if p3 else None),
                                reconnect_timeout_s=8.0,
                                op_timeout_s=240.0)
            for p in range(parties)]
        for key in self.keys:
            for w in self.workers:   # idempotent replays of one INIT
                w.init(key, np.zeros(dim, np.float32))
        self.restarts = {}
        self.kill_t = {}
        self.outage_s = 0.0
        self.killed = set()
        self.failovers = 0

    def lifecycle(self, action: str, node: str) -> None:
        from geomx_tpu.resilience.chaos import shard_node_index
        from geomx_tpu.service import SchedulerClient
        i = shard_node_index(node)
        if i is None or not 0 <= i < self.num_shards:
            raise ValueError(f"manyparty chaos targets shard<i> "
                             f"(got {node!r})")
        now = time.monotonic()
        if action == "kill":
            self.kill_t[node] = now
            self.shards[i].crash()
            self.killed.add(node)
            return
        failover = (i == self.failover_shard)
        # restart = a replacement server replaying shard<i>'s journal;
        # the failover path binds a NEW port and re-points the map
        repl = self._GeoPSServer(
            num_workers=self.parties, mode="sync", accumulate=True,
            rank=i, shard_index=i,
            port=0 if failover else self.ports[i],
            shard_range=(self.bounds[i], self.bounds[i + 1]),
            shard_map_version=1, durable_dir=self.tier_dir,
            durable_name=f"shard{i}").start()
        self.shards[i] = repl
        if failover:
            self.ports[i] = repl.port
            sc = SchedulerClient(self.sched_addr)
            try:
                sc.shard_failover(i, "127.0.0.1", repl.port)
            finally:
                sc.close()
            self.failovers += 1
        self.restarts[node] = self.restarts.get(node, 0) + 1
        self.killed.discard(node)
        self.outage_s += now - self.kill_t.pop(node, now)

    def map_version(self) -> int:
        from geomx_tpu.service import SchedulerClient
        sc = SchedulerClient(self.sched_addr)
        try:
            m = sc.shard_map()
            return 0 if m is None else int(m["version"])
        finally:
            sc.close()

    def close(self) -> None:
        for w in self.workers:
            try:
                w.close()
            except Exception:
                pass
        for s in self.shards:
            try:
                s.stop(forward=False)
            except Exception:
                pass
        try:
            self.scheduler.stop()
        except Exception:
            pass


def _manyparty_train(base_dir: str, steps: int, parties: int,
                     shards: int, keys, dim: int, schedule=None,
                     seed: int = 991, failover_shard=None,
                     stall_dwell_s: float = 0.4,
                     rebalance_at=None,
                     chaos_mid_step: float = 0.0):
    """One seeded many-party run on the sharded tier; the same
    lock-step chaos clock as ``_recovery_train`` (kill@s always lands
    before step-s traffic; outages cannot be batched away by machine
    speed).  ``rebalance_at=s`` drives a scheduler rebalance
    (min_gain=0) at driver tick ``s`` — a boundary move with live
    traffic in flight, the mid-round migration the fleet-observability
    acceptance attributes hop by hop.  ``chaos_mid_step > 0`` ticks
    the chaos engine that many seconds AFTER releasing the step
    instead of before it, so a ``kill@`` lands while the step's round
    is OPEN (pushes merged, gate unsatisfied) — the in-flight-loss
    case whose session-resume replay the fleet ledger must attribute;
    the lock-step bit-exactness runs keep the default quiesced tick.
    Returns final params, per-worker progress, wall/outage times and
    restart stats."""
    import numpy as np

    from geomx_tpu.resilience.chaos import (ChaosEngine,
                                            set_node_lifecycle_hook)
    from geomx_tpu.service.protocol import shaping_extra_seconds
    cluster = _ManyPartyCluster(base_dir, parties, shards, keys, dim,
                                failover_shard=failover_shard)
    targets = {p: {key: np.full(dim, (p % 7 + 1) * (k_i + 1) * 0.5,
                                np.float32)
                   for k_i, key in enumerate(keys)}
               for p in range(parties)}
    progress = [0] * parties
    errors = []
    losses = [[] for _ in range(parties)]
    cond = threading.Condition()
    allowed = [0]

    def worker_loop(p):
        rng = np.random.default_rng(seed + p)
        w = cluster.workers[p]
        try:
            for step in range(steps):
                with cond:
                    while step >= allowed[0]:
                        cond.wait(0.5)
                t0 = time.monotonic()
                step_loss = 0.0
                for key in keys:
                    val = w.pull(key, timeout=200.0)
                    g = (val - targets[p][key]) * 0.1 \
                        + rng.normal(0.0, 0.01, dim).astype(np.float32)
                    w.push(key, (-0.05 * g).astype(np.float32))
                    step_loss += float(np.mean(
                        (val - targets[p][key]) ** 2))
                # chaos throttle@/delay@: this party's WAN link is
                # shaped — realize the injected degradation as real
                # wall-clock, bounded so the bench stays finite
                extra = shaping_extra_seconds(
                    p, time.monotonic() - t0)
                if extra > 0:
                    time.sleep(min(extra, 2.0))
                losses[p].append(step_loss / len(keys))
                progress[p] = step + 1
        except Exception as e:   # surfaced in the record, fails the gate
            errors.append(f"party {p}: {e!r}")

    threads = [threading.Thread(target=worker_loop, args=(p,),
                                daemon=True) for p in range(parties)]
    t0 = time.monotonic()
    engine = None
    if schedule is not None:
        engine = ChaosEngine(schedule, controller=None)
        set_node_lifecycle_hook(cluster.lifecycle)
    try:
        for t in threads:
            t.start()
        rebalance_res = None
        for s in range(steps):
            if engine is not None and not chaos_mid_step:
                engine.tick(s)
            if rebalance_at is not None and s == rebalance_at:
                from geomx_tpu.service import SchedulerClient
                sc = SchedulerClient(cluster.sched_addr)
                try:
                    rebalance_res = sc.rebalance_shards(min_gain=0.0)
                except Exception as e:
                    rebalance_res = {"changed": False, "error": repr(e)}
                finally:
                    sc.close()
            with cond:
                allowed[0] = s + 1
                cond.notify_all()
            if engine is not None and chaos_mid_step:
                time.sleep(chaos_mid_step)
                engine.tick(s)
            stall_t = time.monotonic()
            last = min(progress)
            while min(progress) <= s:
                if errors or not any(t.is_alive() for t in threads):
                    break
                if min(progress) > last:
                    last, stall_t = min(progress), time.monotonic()
                if cluster.killed and \
                        time.monotonic() - stall_t > stall_dwell_s:
                    break   # outage: keep the logical clock moving so
                    # the paired restart@ can fire
                time.sleep(0.02)
            if errors:
                break
        with cond:
            allowed[0] = steps
            cond.notify_all()
        for t in threads:
            t.join(timeout=600.0)
        wall_s = time.monotonic() - t0
        final, prog = {}, []
        if not errors:
            final = {key: np.asarray(
                cluster.workers[0].pull(key, timeout=120.0))
                for key in keys}
            prog = [cluster.workers[p].progress()
                    for p in range(parties)]
        return {"final": final, "losses": losses, "wall_s": wall_s,
                "errors": errors, "restarts": dict(cluster.restarts),
                "outage_s": cluster.outage_s,
                "failovers": cluster.failovers,
                "map_version": cluster.map_version() if not errors
                else None,
                "rebalance": rebalance_res,
                "progress": prog}
    finally:
        if engine is not None:
            engine.close()
            set_node_lifecycle_hook(None)
        cluster.close()


# one shard of the key-range tier as its OWN process: shard-count
# scaling must measure real parallelism, and threads sharing one
# interpreter would share one GIL for the decode/reply halves of every
# merge — subprocesses are the production shape anyway
_MANYPARTY_SHARD_CHILD = """
import sys
from geomx_tpu.service.server import GeoPSServer
from geomx_tpu.service.shardmap import even_bounds
total, idx = map(int, sys.argv[1:3])
b = even_bounds(total)
srv = GeoPSServer(num_workers=1, mode="async", accumulate=True, rank=idx,
                  shard_index=idx, shard_range=(b[idx], b[idx+1]),
                  shard_map_version=1).start()
print("PORT", srv.port, flush=True)
srv.join()
"""


def _manyparty_throughput(shard_counts, nkeys: int = 8,
                          dim: int = 65536, pushes_per_key: int = 48,
                          threads: int = 4, repeats: int = 2):
    """Global-tier merge throughput vs shard count.  Each shard runs as
    its OWN subprocess (threads in one interpreter would share a GIL
    and hide the scaling); the parent blasts pre-encoded async PUSH
    frames through a bounded pipeline window and counts merged ACKs —
    the merge path itself (decode + sender-ordered accumulate + reply),
    no sync-gate coordination in the measurement.  One shard serializes
    every merge behind a single process/lock; key-range sharding splits
    the work across processes, so the rate must grow with shard count.
    Returns per-count {shards, wall_s, pushes_per_s} (best of
    ``repeats``)."""
    import bisect
    import socket as _socket
    import subprocess

    import numpy as np

    from geomx_tpu.service.protocol import (Msg, MsgType, recv_frame,
                                            send_frame)
    from geomx_tpu.service.shardmap import even_bounds, key_hash
    keys = [f"t{i}" for i in range(nkeys)]

    def run_once(S):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs, ports = [], []
        try:
            for i in range(S):
                p = subprocess.Popen(
                    [sys.executable, "-c", _MANYPARTY_SHARD_CHILD,
                     str(S), str(i)],
                    stdout=subprocess.PIPE, env=env, text=True)
                line = p.stdout.readline()
                if not line.startswith("PORT"):
                    raise RuntimeError(
                        f"shard child failed to start: {line!r}")
                ports.append(int(line.split()[1]))
                procs.append(p)
            bounds = even_bounds(S)
            owner = {k: bisect.bisect_right(bounds, key_hash(k)) - 1
                     for k in keys}
            for k in keys:   # one INIT per key at its owner
                s = _socket.create_connection(("127.0.0.1",
                                               ports[owner[k]]))
                m = Msg(MsgType.INIT, key=k,
                        array=np.zeros(dim, np.float32))
                m.meta["rid"] = 1
                send_frame(s, m)
                recv_frame(s)
                s.close()
            groups = [[k for j, k in enumerate(keys)
                       if j % threads == t] for t in range(threads)]
            errs = []

            def blast(t):
                try:
                    conns, frames = {}, {}
                    for k in groups[t]:
                        o = owner[k]
                        if o not in conns:
                            conns[o] = _socket.create_connection(
                                ("127.0.0.1", ports[o]))
                        msg = Msg(MsgType.PUSH, key=k,
                                  array=np.full(dim, 1.0, np.float32))
                        msg.sender = t
                        msg.meta["rid"] = 7
                        frames[k] = msg.encode()
                    window, inflight = 16, []
                    for _i in range(pushes_per_key):
                        for k in groups[t]:
                            c = conns[owner[k]]
                            f = frames[k]
                            c.sendall(len(f).to_bytes(4, "little") + f)
                            inflight.append(c)
                            if len(inflight) >= window:
                                recv_frame(inflight.pop(0))
                    for c in inflight:
                        recv_frame(c)
                    for c in conns.values():
                        c.close()
                except Exception as e:
                    errs.append(repr(e))

            ths = [threading.Thread(target=blast, args=(t,),
                                    daemon=True)
                   for t in range(threads)]
            t0 = time.monotonic()
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=600.0)
            wall = time.monotonic() - t0
            if errs:
                raise RuntimeError(f"throughput blast failed: {errs}")
            return wall
        finally:
            for p in procs:
                p.kill()
                p.wait(timeout=10)

    out = []
    for S in shard_counts:
        best = None
        for _rep in range(repeats):
            wall = run_once(S)
            rate = pushes_per_key * nkeys / max(wall, 1e-9)
            if best is None or rate > best["pushes_per_s"]:
                best = {"shards": S, "wall_s": round(wall, 3),
                        "pushes_per_s": round(rate, 1)}
        out.append(best)
    return out


def _manyparty_rebalance_probe(dim: int = 64) -> dict:
    """Scheduler-driven rebalance on a live 2-shard tier under skewed
    load: boundaries move toward the observed per-key push counts, the
    hot keys' state migrates (rounds, per-sender counts), the map
    version bumps, and post-rebalance traffic merges exactly once."""
    import numpy as np

    from geomx_tpu.service import (GeoScheduler, SchedulerClient,
                                   ShardedGlobalClient,
                                   start_sharded_global_tier)
    from geomx_tpu.service.shardmap import ShardMap
    sched = GeoScheduler().start()
    servers = start_sharded_global_tier(("127.0.0.1", sched.port),
                                        num_shards=2, num_workers=2)
    ws = [ShardedGlobalClient(("127.0.0.1", sched.port), sender_id=p,
                              reconnect=True) for p in range(2)]
    sc = SchedulerClient(("127.0.0.1", sched.port))
    try:
        m = ShardMap.from_meta(sc.shard_map())
        hot = [f"h{i}" for i in range(64)
               if m.shard_for(f"h{i}") == 0][:6]
        cold = [f"c{i}" for i in range(64)
                if m.shard_for(f"c{i}") == 1][:2]
        for key in hot + cold:
            for w in ws:
                w.init(key, np.zeros(dim, np.float32))
        for _r in range(3):
            for key in hot:
                for w in ws:
                    w.push(key, np.ones(dim, np.float32))
                for w in ws:
                    w.pull(key)
        for key in cold:
            for w in ws:
                w.push(key, np.ones(dim, np.float32))
            for w in ws:
                w.pull(key)
        res = sc.rebalance_shards(min_gain=0.05)
        m2 = ShardMap.from_meta(res["map"])
        moved = [k for k in hot if m2.shard_for(k) != 0]
        post_exact = True
        for key in hot:
            for w in ws:
                w.push(key, np.ones(dim, np.float32))
            got = ws[0].pull(key, timeout=60.0)
            post_exact &= bool(np.allclose(got, 8.0))  # 4 rounds x 2
        prog = ws[0].progress()
        return {"changed": bool(res["changed"]),
                "moved_keys": int(res["moved_keys"]),
                "map_version": int(res["map"]["version"]),
                "keys_rerouted": len(moved),
                "rounds_preserved": all(prog[k] == 4 for k in hot),
                "post_rebalance_exact": post_exact,
                "ok": bool(res["changed"] and res["moved_keys"] > 0
                           and moved and post_exact
                           and all(prog[k] == 4 for k in hot))}
    finally:
        sc.close()
        for w in ws:
            w.close()
        for srv in servers:
            try:
                srv.stop(forward=False)
            except Exception:
                pass
        sched.stop()


def _compare_manyparty(steps: int = 10, parties: int = 16,
                       shards: int = 4, dim: int = 1024,
                       nkeys: int = 8, schedule_spec: str = None,
                       seed: int = 991, throughput_dim: int = 65536):
    """The many-party acceptance (docs/resilience.md "Many-party
    global tier"):

    1. BASELINE — ``parties`` virtual parties x ``shards`` key-range
       shards, uninterrupted; P3-chunked pushes, session resume armed.
    2. CHAOS — same seeds under a shard-targeted schedule: one shard
       kill+restart in place, one shard kill whose restart FAILS OVER
       to a new port (journal replay + scheduler map bump), a seeded
       corrupt@ epoch and a throttle@ epoch.  Must finish params
       BIT-EXACT vs baseline with zero lost rounds and a bounded
       stall.
    3. REBALANCE — scheduler-driven boundary move from observed load
       on a live tier, exact-once merges across the migration.
    4. THROUGHPUT — the same traffic against 1..N shards: merge
       throughput must scale with shard count.
    """
    import numpy as np

    from geomx_tpu.resilience.chaos import ChaosSchedule
    from geomx_tpu.service.protocol import wire_crc_errors
    if shards < 2:
        raise SystemExit("--compare-manyparty needs --shards >= 2")
    if schedule_spec is None:
        schedule_spec = (
            f"seed={seed};"
            "corrupt@2:party=3,rate=30,steps=5;"
            "kill@3:node=shard1,restart_after=2;"
            "throttle@4:party=2,factor=0.4,steps=3;"
            f"kill@6:node=shard{shards - 1},restart_after=2")
    schedule = ChaosSchedule.from_spec(schedule_spec)
    keys = [f"w{i}" for i in range(nkeys)]
    rec = {"mode": "compare_manyparty", "steps": steps,
           "parties": parties, "shards": shards, "dim": dim,
           "keys": keys, "schedule": schedule.spec(), "seed": seed}

    with tempfile.TemporaryDirectory(prefix="geomx_manyparty_") as td:
        base = _manyparty_train(os.path.join(td, "baseline"), steps,
                                parties, shards, keys, dim,
                                schedule=None, seed=seed)
        crc_before = wire_crc_errors()
        reco = _manyparty_train(os.path.join(td, "chaos"), steps,
                                parties, shards, keys, dim,
                                schedule=schedule, seed=seed,
                                failover_shard=shards - 1)
        crc_errors = wire_crc_errors() - crc_before

    def digest(final):
        import hashlib
        h = hashlib.sha256()
        for key in keys:
            h.update(np.ascontiguousarray(final[key]).tobytes())
        return h.hexdigest()

    def bit_exact(a, b):
        return bool(a and b and all(
            np.array_equal(a[key], b[key]) for key in keys))

    stall_s = max(0.0, reco["wall_s"] - base["wall_s"])
    zero_lost = bool(reco["progress"] and all(
        prog.get(key, 0) == steps
        for prog in reco["progress"] for key in keys))
    rec["baseline"] = {"wall_s": round(base["wall_s"], 3),
                       "errors": base["errors"],
                       "params_digest": digest(base["final"])
                       if base["final"] else None}
    rec["chaos"] = {"wall_s": round(reco["wall_s"], 3),
                    "errors": reco["errors"],
                    "restarts": reco["restarts"],
                    "outage_s": round(reco["outage_s"], 3),
                    "failovers": reco["failovers"],
                    "map_version": reco["map_version"],
                    "crc_errors": crc_errors,
                    "params_digest": digest(reco["final"])
                    if reco["final"] else None}
    rec["rebalance"] = _manyparty_rebalance_probe()
    shard_counts = sorted({1, 2, shards} - {0})
    shard_counts = [s for s in shard_counts if s <= shards]
    rec["throughput"] = {"dim": throughput_dim,
                         "curve": _manyparty_throughput(
                             shard_counts, nkeys=nkeys,
                             dim=throughput_dim)}
    curve = rec["throughput"]["curve"]
    base_thr = curve[0]["pushes_per_s"]
    peak_thr = curve[-1]["pushes_per_s"]
    rec["throughput"]["scaling"] = round(peak_thr / max(base_thr, 1e-9),
                                         3)

    # ---- acceptance gates (benchtrend + manyparty-smoke CI) ----------
    rec["params_bit_exact"] = bit_exact(base["final"], reco["final"])
    rec["zero_lost_rounds"] = zero_lost
    rec["shard_restarted"] = sum(reco["restarts"].values()) >= 2
    rec["failover_performed"] = reco["failovers"] >= 1
    rec["map_version_bumped"] = bool(
        reco["map_version"] and reco["map_version"] > 1)
    rec["corrupt_crc_nonzero"] = crc_errors > 0
    rec["stall_s"] = round(stall_s, 3)
    rec["stall_bounded"] = bool(
        stall_s <= reco["outage_s"] + 30.0)
    rec["rebalance_applied"] = bool(rec["rebalance"]["ok"])
    rec["throughput_scales"] = bool(
        rec["throughput"]["scaling"] >= 1.15)
    rec["ok"] = bool(
        not base["errors"] and not reco["errors"]
        and rec["params_bit_exact"] and rec["zero_lost_rounds"]
        and rec["shard_restarted"] and rec["failover_performed"]
        and rec["map_version_bumped"] and rec["corrupt_crc_nonzero"]
        and rec["stall_bounded"] and rec["rebalance_applied"]
        and rec["throughput_scales"])
    return rec


def compare_manyparty_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--parties="):
            kwargs["parties"] = int(a.split("=", 1)[1])
        elif a.startswith("--shards="):
            kwargs["shards"] = int(a.split("=", 1)[1])
        elif a.startswith("--dim="):
            kwargs["dim"] = int(a.split("=", 1)[1])
        elif a.startswith("--keys="):
            kwargs["nkeys"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--seed="):
            kwargs["seed"] = int(a.split("=", 1)[1])
        elif a.startswith("--throughput-dim="):
            kwargs["throughput_dim"] = int(a.split("=", 1)[1])
    if "shards" not in kwargs:
        from geomx_tpu.service.sharded import default_num_shards
        env_default = default_num_shards()
        kwargs["shards"] = env_default if env_default > 1 else 4
    _emit(_compare_manyparty(**kwargs))


# --------------------------------------------------------------------------
# --compare-fleetobs: the fleet round ledger acceptance — causal
# per-round tracing + byte-true wire accounting across the sharded host
# plane under chaos (docs/telemetry.md "Round ledger")
# --------------------------------------------------------------------------


def _fleetobs_keys(nkeys: int, shards: int):
    """Deterministic key pick with a deliberately UNEVEN shard
    ownership: the mid-run rebalance (min_gain=0) must actually move a
    boundary, which needs observed-load skew — a perfectly even key
    split would refuse the move and the redirect-attribution gate
    would have nothing to attribute."""
    import bisect

    from geomx_tpu.service.shardmap import even_bounds, key_hash
    bounds = even_bounds(shards)

    def owner(k):
        return bisect.bisect_right(bounds, key_hash(k)) - 1

    cands = [f"w{i}" for i in range(64 * nkeys)]
    by_shard = {}
    for k in cands:
        by_shard.setdefault(owner(k), []).append(k)
    if len(by_shard) < shards:
        raise SystemExit(
            f"--compare-fleetobs: no candidate key hashes into every "
            f"shard ({sorted(by_shard)} of {shards})")
    hot = max(by_shard, key=lambda s: (len(by_shard[s]), -s))
    # one key per shard FIRST (every shard must see traffic — the
    # per-shard phase histograms and the kill targets depend on it),
    # then load the hot shard with the remainder
    keys = [by_shard[s][0] for s in sorted(by_shard)]
    for k in by_shard[hot][1:]:
        if len(keys) < nkeys:
            keys.append(k)
    for s in sorted(by_shard):
        for k in by_shard[s][1:]:
            if len(keys) < nkeys:
                keys.append(k)
    return keys[:nkeys], hot


def _fleetobs_gapless(rec, durable: bool = True) -> bool:
    """One completed round's gapless-chain verdict: causally ordered
    push -> merge -> (journal) -> reply hops with contiguous sequence
    numbers."""
    if rec["status"] != "complete":
        return False
    kinds = [h["hop"] for h in rec["hops"]]
    if not ("push" in kinds and "merge" in kinds and "reply" in kinds):
        return False
    if durable and "journal" not in kinds:
        return False
    seqs = [h["seq"] for h in rec["hops"]]
    if seqs != list(range(len(seqs))):
        return False
    first_push = min(h["t"] for h in rec["hops"] if h["hop"] == "push")
    merge_t = max(h["t"] for h in rec["hops"] if h["hop"] == "merge")
    # small tolerance: hop timestamps come from different threads
    return first_push <= merge_t + 0.05


def _fleetobs_kill_probe(failover: bool, dim: int = 256) -> dict:
    """Deterministic kill-attribution probe: open a round (one of two
    workers pushed, gate unsatisfied), kill the owning shard
    MID-ROUND, restart it — in place (session-resume ``replay``) or
    onto a NEW port + scheduler map bump (wrapper ``failover_replay``)
    — and assert the fleet ledger attributes the kill to the exact
    (key, round) hop.  The big chaos run exercises the same machinery
    under load, but whether one of ITS kills catches an open round is
    a scheduling race; this probe pins the attribution itself."""
    import bisect

    import numpy as np

    from geomx_tpu.service import (GeoScheduler, SchedulerClient,
                                   ShardedGlobalClient,
                                   start_sharded_global_tier)
    from geomx_tpu.service.server import GeoPSServer
    from geomx_tpu.service.shardmap import even_bounds, key_hash
    from geomx_tpu.telemetry.ledger import get_round_ledger
    bounds = even_bounds(2)
    key = next(k for k in (f"p{i}" for i in range(256))
               if bisect.bisect_right(bounds, key_hash(k)) - 1 == 1)
    out = {"failover": failover, "key": key}
    with tempfile.TemporaryDirectory(prefix="geomx_fleetobs_kp_") as td:
        sched = GeoScheduler(
            durable_dir=os.path.join(td, "sched")).start()
        addr = ("127.0.0.1", sched.port)
        tier = os.path.join(td, "tier")
        shards = start_sharded_global_tier(addr, num_shards=2,
                                           num_workers=2,
                                           durable_dir=tier)
        ws = [ShardedGlobalClient(addr, sender_id=p, reconnect=True,
                                  p3_slice_elems=dim // 2,
                                  reconnect_timeout_s=6.0,
                                  op_timeout_s=90.0)
              for p in range(2)]
        repl = None
        try:
            for w in ws:
                w.init(key, np.zeros(dim, np.float32))
            for w in ws:                   # round 1 completes clean
                w.push(key, np.ones(dim, np.float32))
            for w in ws:
                w.pull(key, timeout=30.0)
            ws[0].push(key, np.ones(dim, np.float32))  # round 2 OPEN
            old_port = shards[1].port
            shards[1].crash()              # the injected kill
            repl = GeoPSServer(
                num_workers=2, mode="sync", accumulate=True, rank=1,
                shard_index=1, port=0 if failover else old_port,
                shard_range=(bounds[1], bounds[2]),
                shard_map_version=1, durable_dir=tier,
                durable_name="shard1").start()
            if failover:
                sc = SchedulerClient(addr)
                try:
                    sc.shard_failover(1, "127.0.0.1", repl.port)
                finally:
                    sc.close()
            done = []

            def other_push():
                ws[1].push(key, np.ones(dim, np.float32))
                done.append(True)

            t = threading.Thread(target=other_push, daemon=True)
            t.start()
            val = ws[0].pull(key, timeout=60.0)
            t.join(30.0)
            out["round_completed"] = bool(done) and \
                bool(np.allclose(val, 4.0))
            rec = get_round_ledger().get(key, 2)
            hops = (rec or {}).get("hops", [])
            want = "failover_replay" if failover else "replay"
            named = [h for h in hops
                     if h["hop"] == want and h.get("shard") == 1]
            out["hop"] = want
            out["attributed"] = bool(named)
            out["record_status"] = (rec or {}).get("status")
            out["hops"] = [h["hop"] for h in hops]
            out["ok"] = bool(out["round_completed"] and named
                             and out["record_status"] == "complete")
        finally:
            for w in ws:
                try:
                    w.close()
                except Exception:
                    pass
            for s in [shards[0], repl]:
                if s is None:
                    continue
                try:
                    s.stop(forward=False)
                except Exception:
                    pass
            sched.stop()
    return out


def _merge_throughput_probe(parties: int = 16, pairs_per_party: int = 256,
                            dim: int = 1024, threads: int = 4,
                            iters: int = 300, seed: int = 11) -> dict:
    """Host-plane merge throughput, the native fast path (nogil C++
    ``gx_merge_pairs`` behind ``merge_pairs_host``) vs the legacy
    pure-numpy fold (``GEOMX_NATIVE_WIRE=0``), on the same pair sets:
    ``threads`` Python threads each folding a realistic small-key round
    (``parties`` contributions x ``pairs_per_party`` pairs into a
    ``dim``-long dense index space) ``iters`` times.  Best-of-3 per
    codec to shave scheduler noise; reported in Mpairs/s."""
    import threading as _threading

    import numpy as np

    from geomx_tpu.compression.sparseagg import merge_pairs_host
    from geomx_tpu.runtime import native_available
    from geomx_tpu.service.protocol import reset_wire_codec_cache
    rng = np.random.default_rng(seed)
    parts = [(rng.standard_normal(pairs_per_party).astype(np.float32),
              rng.integers(0, dim,
                           size=pairs_per_party).astype(np.int64))
             for _ in range(parties)]
    total_pairs = threads * iters * parties * pairs_per_party

    def run_once() -> float:
        barrier = _threading.Barrier(threads)

        def worker():
            barrier.wait()
            for _ in range(iters):
                merge_pairs_host(parts)

        ts = [_threading.Thread(target=worker) for _ in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return total_pairs / (time.perf_counter() - t0)

    native = max(run_once() for _ in range(3))
    old = os.environ.get("GEOMX_NATIVE_WIRE")
    os.environ["GEOMX_NATIVE_WIRE"] = "0"
    reset_wire_codec_cache()
    try:
        legacy = max(run_once() for _ in range(3))
    finally:
        if old is None:
            os.environ.pop("GEOMX_NATIVE_WIRE", None)
        else:
            os.environ["GEOMX_NATIVE_WIRE"] = old
        reset_wire_codec_cache()
    return {"threads": threads, "iters": iters, "parties": parties,
            "pairs_per_party": pairs_per_party, "dim": dim,
            "native_mpairs_s": round(native / 1e6, 2),
            "legacy_mpairs_s": round(legacy / 1e6, 2),
            "speedup": round(native / legacy, 2),
            "native_engaged": bool(native_available())}


def _compare_fleetobs(steps: int = 10, parties: int = 16,
                      shards: int = 4, dim: int = 1024,
                      nkeys: int = 8, schedule_spec: str = None,
                      seed: int = 661, rebalance_at: int = None,
                      out_dir: str = None):
    """The fleet-observability acceptance (docs/telemetry.md "Round
    ledger"): a 16-party x 4-shard chaos run — an in-place shard kill,
    a shard kill whose restart FAILS OVER to a new port, a seeded
    corrupt@ epoch, and a scheduler rebalance with traffic in flight —
    where

    1. every completed round yields a GAPLESS ledger record (push ->
       merge -> journal -> reply hop chain, contiguous seq);
    2. measured socket bytes reconcile with the sender-declared wire
       bytes within the documented clean-link bound (the active codec's
       per-frame framing allowance — 192 B binary / 512 B legacy) on
       every fault-free round, and under the binary codec the honesty
       ratio stays <= 1.02 while the native merge fast path clears 3x
       the legacy fold's throughput on this host;
    3. each injected fault is attributed to a named hop in a named
       round: corrupt@ -> a ``corrupt`` hop naming the shaped party,
       the in-place kill -> a session-resume ``replay`` hop naming the
       shard, the failover kill -> a ``failover_replay`` hop, the
       rebalance -> a ``redirect`` hop carrying the bumped map version;
    4. the per-shard phase histograms, the merged Chrome timeline
       (ledger ``to_doc`` through ``merge_traces``) and the
       ``LinkObservatory.ingest_ledger`` sensor path all see the run.
    """
    import numpy as np

    from geomx_tpu.resilience.chaos import ChaosSchedule
    from geomx_tpu.telemetry import merge_traces, rounds_in_trace
    from geomx_tpu.telemetry.ledger import (HONESTY_BOUND,
                                            active_frame_overhead_bound,
                                            reset_round_ledger)
    from geomx_tpu.telemetry.links import LinkObservatory
    from geomx_tpu.telemetry.registry import get_registry
    if shards < 2:
        raise SystemExit("--compare-fleetobs needs --shards >= 2")
    failover_shard = shards - 1
    if rebalance_at is None:
        # rebalance LAST (with one step of traffic left to redirect):
        # both kills must land while their shard still owns its
        # constructed keys, which a load-driven boundary move would
        # un-pin
        rebalance_at = steps - 1
    if schedule_spec is None:
        schedule_spec = (
            f"seed={seed};"
            "corrupt@2:party=3,rate=40,steps=2;"
            "kill@3:node=shard1,restart_after=2;"
            f"kill@6:node=shard{failover_shard},restart_after=2")
    schedule = ChaosSchedule.from_spec(schedule_spec)
    keys, hot_shard = _fleetobs_keys(nkeys, shards)
    ledger = reset_round_ledger(capacity=max(4096, 4 * nkeys * steps))
    frame_bound = active_frame_overhead_bound()
    rec = {"mode": "compare_fleetobs", "steps": steps,
           "parties": parties, "shards": shards, "dim": dim,
           "keys": keys, "hot_shard": hot_shard,
           "schedule": schedule.spec(), "seed": seed,
           "rebalance_at": rebalance_at,
           "frame_overhead_bound": frame_bound}

    with tempfile.TemporaryDirectory(prefix="geomx_fleetobs_") as td:
        run = _manyparty_train(os.path.join(td, "chaos"), steps,
                               parties, shards, keys, dim,
                               schedule=schedule, seed=seed,
                               failover_shard=failover_shard,
                               rebalance_at=rebalance_at,
                               chaos_mid_step=0.08)

    records = ledger.records()
    by_id = {(r["key"], r["round"]): r for r in records}
    rec["errors"] = run["errors"]
    rec["restarts"] = run["restarts"]
    rec["failovers"] = run["failovers"]
    rec["map_version"] = run["map_version"]
    rec["rebalance"] = run["rebalance"]
    rec["wall_s"] = round(run["wall_s"], 3)
    rec["ledger"] = {"records": len(records),
                     "completed": sum(1 for r in records
                                      if r["status"] == "complete"),
                     "orphaned": sum(1 for r in records
                                     if r["status"] == "orphaned"),
                     "open": sum(1 for r in records
                                 if r["status"] == "open")}

    # ---- 1. gapless per-round records --------------------------------
    zero_lost = bool(run["progress"] and all(
        prog.get(key, 0) == steps
        for prog in run["progress"] for key in keys))
    missing, broken = [], []
    for key in keys:
        for r in range(1, steps + 1):
            rr = by_id.get((key, r))
            if rr is None:
                missing.append((key, r))
            elif not _fleetobs_gapless(rr):
                broken.append((key, r, [h["hop"] for h in rr["hops"]]))
    rec["gapless"] = {"missing": missing[:8], "broken": broken[:8],
                      "checked": nkeys * steps}
    rec["zero_lost_rounds"] = zero_lost
    rec["gapless_ledger"] = bool(zero_lost and not missing
                                 and not broken)

    # ---- 2. byte-true reconciliation on clean rounds -----------------
    clean = [r for r in records
             if r["status"] == "complete" and r["faults"] == 0]
    bad_rec = [(r["key"], r["round"], r["honesty_ratio"])
               for r in clean
               if not (r["declared_rx_bytes"] > 0
                       and r["declared_rx_bytes"]
                       <= r["wire"].get("push_rx_bytes", 0)
                       <= r["declared_rx_bytes"] + frame_bound
                       * r["wire"].get("push_rx_frames", 0))]
    ratios = sorted(r["honesty_ratio"] for r in clean
                    if r["honesty_ratio"] is not None)
    rec["reconciliation"] = {
        "clean_rounds": len(clean),
        "violations": bad_rec[:8],
        "honesty_ratio_min": round(ratios[0], 4) if ratios else None,
        "honesty_ratio_max": round(ratios[-1], 4) if ratios else None,
        "honesty_ratio_median":
            round(ratios[len(ratios) // 2], 4) if ratios else None,
    }
    rec["bytes_reconciled"] = bool(clean and not bad_rec)

    # declared ≈ measured under the binary codec: every clean round's
    # honesty ratio within HONESTY_BOUND (the ≤ 1.02 acceptance the
    # zero-copy frame exists to hit; the legacy pickled codec sat at
    # ~1.09 — FLEETOBS_r01)
    from geomx_tpu.service.protocol import binary_wire_enabled
    rec["honesty_bound"] = HONESTY_BOUND
    if binary_wire_enabled():
        rec["honesty_ok"] = bool(ratios and ratios[-1] <= HONESTY_BOUND)
    else:
        rec["honesty_ok"] = True  # legacy codec: bound not claimed

    # host-plane merge throughput, native fast path vs legacy fold
    rec["merge_throughput"] = _merge_throughput_probe(
        parties=parties, dim=dim)
    rec["merge_speedup_ok"] = bool(
        rec["merge_throughput"]["speedup"] >= 3.0)

    # ---- 3. fault -> named hop in a named round ----------------------
    def hops_of(kind):
        return [(r["key"], r["round"], h) for r in records
                for h in r["hops"] if h["hop"] == kind]

    corrupt = [(k, rd) for k, rd, h in hops_of("corrupt")
               if h.get("party") == 3]
    replays = [(k, rd) for k, rd, h in hops_of("replay")]
    fo = [(k, rd) for k, rd, h in hops_of("failover_replay")]
    redirects = [(k, rd) for k, rd, h in hops_of("redirect")
                 if (h.get("detail") or {}).get("map_version", 0) >= 2]
    rec["fault_attribution"] = {
        "corrupt_party3": corrupt[:4],
        "rebalance_redirects": redirects[:4],
        "counts": {"corrupt": len(corrupt), "replay": len(replays),
                   "failover_replay": len(fo),
                   "redirect": len(redirects)}}
    rebalanced = bool((run["rebalance"] or {}).get("changed"))
    # whether one of the chaos run's kills catches an OPEN round is a
    # scheduling race (a kill between rounds genuinely interrupts
    # nothing) — the kill-attribution claim itself is pinned by two
    # deterministic open-round probes
    rec["kill_probes"] = {
        "inplace": _fleetobs_kill_probe(failover=False),
        "failover": _fleetobs_kill_probe(failover=True)}
    rec["faults_attributed"] = bool(
        corrupt and rebalanced and redirects
        and rec["kill_probes"]["inplace"]["ok"]
        and rec["kill_probes"]["failover"]["ok"])

    # ---- 4. surfaces: histograms, merged trace, link sensor ----------
    fam = get_registry().get("geomx_round_phase_seconds")
    shard_phases = {}
    if fam is not None:
        for (shard, phase), child in fam.children():
            if child.count > 0:
                shard_phases.setdefault(shard, []).append(phase)
    covered = [s for s in map(str, range(shards))
               if {"gate_wait", "merge", "reply"} <=
               set(shard_phases.get(s, []))]
    rec["phase_histograms"] = {"shards_covered": sorted(covered),
                               "per_shard": {s: sorted(p) for s, p
                                             in shard_phases.items()}}
    rec["phase_histograms_ok"] = len(covered) == shards

    doc = ledger.to_doc(label="fleet-ledger")
    merged = merge_traces([doc], labels=["fleet-ledger"])
    linked = rounds_in_trace(merged)
    rec["trace"] = {"events": len(merged["traceEvents"]),
                    "linked_rounds": len(linked)}
    rec["trace_linked"] = len(linked) >= nkeys * steps

    obs = LinkObservatory()
    folded = obs.ingest_ledger(records)
    snap = obs.snapshot()
    rec["link_sensor"] = {"folded": folded, "links": len(snap)}
    rec["ledger_ingested"] = bool(folded > 0 and len(snap) >= parties)

    # ---- round latency ----------------------------------------------
    def _lat(rs):
        return sorted(
            (r["closed_unix"] - min(h["t"] for h in r["hops"]))
            for r in rs
            if r["status"] == "complete" and r["hops"]
            and r["closed_unix"] is not None)

    lats_all = _lat(records)
    if lats_all:
        # informational: chaos-run rounds legitimately span reconnect
        # windows and outage-stalled gates — gating this would gate
        # the chaos schedule, not the host plane
        rec["chaos_round_p99_s"] = round(
            lats_all[min(len(lats_all) - 1,
                         int(0.99 * (len(lats_all) - 1)))], 4)
    # the TRACKED p50/p99 (benchtrend FLEETOBS series, lower is
    # better) come from a dedicated chaos-free run on the same
    # topology, so the series measures the plane's round latency, not
    # the schedule's injected outages
    lat_ledger = reset_round_ledger(capacity=2048)
    with tempfile.TemporaryDirectory(prefix="geomx_fleetobs_lat_") as td:
        clean_run = _manyparty_train(
            os.path.join(td, "clean"), max(4, steps // 2), parties,
            shards, keys, dim, schedule=None, seed=seed + 1)
    rec["clean_run_errors"] = clean_run["errors"]
    lats = _lat([r for r in lat_ledger.records()
                 if r["faults"] == 0])
    if lats:
        rec["round_p50_s"] = round(lats[len(lats) // 2], 4)
        rec["round_p99_s"] = round(
            lats[min(len(lats) - 1, int(0.99 * (len(lats) - 1)))], 4)
        # the absolute percentiles are REPORTED, and gated only through
        # this generous bounded boolean: a clean 16-process round on
        # loopback measures host scheduling as much as the plane (the
        # unchanged legacy codec spans ~3x run-to-run at p99 on a
        # 4-core container), so a relative band would gate the CI
        # host's load, not the code — same reasoning as the manyparty
        # stall_bounded gate.  The bounds still catch a collapse.
        rec["round_latency_bounded"] = bool(
            rec["round_p50_s"] <= 0.5 and rec["round_p99_s"] <= 2.0)

    rec["ok"] = bool(
        not run["errors"] and not clean_run["errors"]
        and rec["gapless_ledger"]
        and rec["bytes_reconciled"] and rec["honesty_ok"]
        and rec["merge_speedup_ok"] and rec["faults_attributed"]
        and rec["phase_histograms_ok"] and rec["trace_linked"]
        and rec["ledger_ingested"]
        and rec.get("round_latency_bounded", True))

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "fleetobs_ledger.json"),
                  "w") as f:
            json.dump({"records": records,
                       "summary": ledger.summary()}, f, default=str)
        with open(os.path.join(out_dir, "fleetobs_trace.json"),
                  "w") as f:
            json.dump(merged, f, default=str)
    return rec


def compare_fleetobs_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--parties="):
            kwargs["parties"] = int(a.split("=", 1)[1])
        elif a.startswith("--shards="):
            kwargs["shards"] = int(a.split("=", 1)[1])
        elif a.startswith("--dim="):
            kwargs["dim"] = int(a.split("=", 1)[1])
        elif a.startswith("--keys="):
            kwargs["nkeys"] = int(a.split("=", 1)[1])
        elif a.startswith("--schedule="):
            kwargs["schedule_spec"] = a.split("=", 1)[1]
        elif a.startswith("--seed="):
            kwargs["seed"] = int(a.split("=", 1)[1])
        elif a.startswith("--rebalance-at="):
            kwargs["rebalance_at"] = int(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_fleetobs(**kwargs))


# --------------------------------------------------------------------------
# --compare-sparseagg: compressed-domain aggregation end to end
# --------------------------------------------------------------------------


def _sparseagg_dc_bit_parity(parties: int = 3, n: int = 8192,
                             ratio: float = 0.01) -> dict:
    """The owner-routed dc-tier merge must be BIT-identical between the
    jnp reference and the Pallas (interpret) engine — same sort, same
    combining tree, same final scatter (ops/merge_pallas.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.parallel.collectives import shard_map_compat

    mesh = Mesh(np.array(jax.devices()[:parties]), ("dc",))
    rng = np.random.RandomState(11)
    g = jnp.asarray(rng.standard_normal((parties, n)).astype(np.float32))

    def run(comp):
        def f(gs, us, vs):
            out, (u2, v2) = comp.allreduce_leaf(
                gs[0], (us[0], vs[0]), "dc", parties)
            return out[None], u2[None], v2[None]

        fn = shard_map_compat(f, mesh, in_specs=(P("dc"),) * 3,
                              out_specs=(P("dc"),) * 3)
        z = jnp.zeros((parties, n), jnp.float32)
        return [np.asarray(a) for a in jax.jit(fn)(g, z, z)]

    from geomx_tpu.ops.dispatch import kernels
    base = dict(ratio=ratio, min_sparse_size=1, sparse_agg=True)
    oj = run(BiSparseCompressor(**base))
    with kernels("interpret"):
        of = run(BiSparseCompressor(**base))
    bit = all(np.array_equal(a, b) for a, b in zip(oj, of))
    consistent = all(np.array_equal(oj[0][0], oj[0][p])
                     for p in range(parties))
    return {"merged_bit_exact_paths": bool(bit),
            "result_identical_across_parties": bool(consistent),
            "merged_nonzeros": int((oj[0][0] != 0).sum()),
            "elems": n}


def _sparseagg_server_orders(n: int = 4096, k: int = 96,
                             orders: int = 3) -> dict:
    """Host-plane sparse merge: shuffled push arrival orders must yield
    bit-identical sparse-merged rounds (sorted-sender + sorted-index
    fold, service/server.py), with the round pulled SPARSE."""
    import numpy as np

    from geomx_tpu.compression.sparseagg import encode_pairs_payload
    from geomx_tpu.service.client import GeoPSClient
    from geomx_tpu.service.server import GeoPSServer
    from geomx_tpu.telemetry import get_registry

    rng = np.random.RandomState(5)
    payloads = {}
    for s in range(3):
        idx = rng.choice(n, k, replace=False).astype(np.int64)
        vals = (rng.standard_normal(k) * 10.0 ** rng.randint(
            -3, 6, size=k)).astype(np.float32)
        payloads[s] = encode_pairs_payload(vals, idx)
    meta = {"comp": "bsc", "n": n, "shape": [n]}
    outs = []

    def merges_total():
        fam = get_registry().get("geomx_server_sparse_merges_total")
        return sum(ch.value for _, ch in fam.children()) if fam else 0.0

    before = merges_total()
    order_perms = [(0, 1, 2), (2, 0, 1), (1, 2, 0)][:orders]
    for perm in order_perms:
        srv = GeoPSServer(num_workers=3, mode="sync").start()
        cs = [GeoPSClient(("127.0.0.1", srv.port), sender_id=s)
              for s in range(3)]
        cs[0].init("w", np.zeros(n, np.float32))
        for s in perm:
            cs[s].push("w", payloads[s], meta=dict(meta))
        outs.append(np.asarray(cs[0].pull("w")))
        cs[0].stop_server()
        for c in cs:
            c.close()
        srv.join(5)
    bit = all(np.array_equal(outs[0], o) for o in outs[1:])
    return {"merged_bit_exact_orders": bool(bit),
            "server_sparse_merges": int(merges_total() - before),
            "orders": len(order_perms)}


def _sparseagg_lattice_structure(parties: int = 3) -> dict:
    """fp16/2bit under the gate must trace to ONE integer-lattice psum
    on the weight path and NO gather — the THC structure."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.analysis.core import walk_jaxpr
    from geomx_tpu.analysis.passes import _GATHER_PRIMS
    from geomx_tpu.compression.fp16 import FP16Compressor
    from geomx_tpu.compression.twobit import TwoBitCompressor
    from geomx_tpu.parallel.collectives import shard_map_compat

    mesh = Mesh(np.array(jax.devices()[:parties]), ("dc",))
    n = 4096
    rng = np.random.RandomState(3)
    g = rng.standard_normal((parties, n)).astype(np.float32)

    def structure(comp, with_state):
        def f(gs, ss):
            st = ss[0] if with_state else ()
            out, s2 = comp.allreduce_leaf(gs[0], st, "dc", parties)
            s2 = s2[None] if with_state else gs[:0]
            return out[None], s2

        fn = shard_map_compat(f, mesh, in_specs=(P("dc"), P("dc")),
                              out_specs=(P("dc"), P("dc")))
        ss = jnp.zeros((parties, n), jnp.float32)
        jx = jax.make_jaxpr(fn)(jnp.asarray(g), ss)
        prims = [s.primitive for s in walk_jaxpr(jx)]
        psum_int = 0
        for site in walk_jaxpr(jx):
            if site.primitive in ("psum", "psum2"):
                dts = {str(v.aval.dtype) for v in site.eqn.invars
                       if hasattr(v, "aval")}
                if dts & {"int8", "int16", "int32"}:
                    psum_int += 1
        out_np = np.asarray(jax.jit(fn)(jnp.asarray(g), ss)[0])
        return {"lattice_psums": psum_int,
                "gathers": sum(1 for p in prims if p in _GATHER_PRIMS),
                "finite": bool(np.isfinite(out_np).all()),
                "max_err_vs_exact": float(
                    np.max(np.abs(out_np[0] - _expected(comp, g)))),
                }

    def _expected(comp, g):
        if isinstance(comp, FP16Compressor):
            return g.sum(0)
        thr = comp.threshold
        codes = np.where(g >= thr, 1, np.where(g <= -thr, -1, 0))
        return codes.sum(0) * thr

    fp = structure(FP16Compressor(sparse_agg=True), with_state=False)
    tb = structure(TwoBitCompressor(0.5, sparse_agg=True), with_state=True)
    scale_tol = 3.0 * float(np.abs(g).max()) * parties * parties / 32767.0
    return {
        "fp16": fp, "twobit": tb,
        "fp16_lattice_psum": bool(fp["lattice_psums"] >= 1
                                  and fp["gathers"] == 0
                                  and fp["finite"]
                                  and fp["max_err_vs_exact"] <= scale_tol),
        "twobit_lattice_psum": bool(tb["lattice_psums"] >= 1
                                    and tb["gathers"] == 0
                                    and tb["max_err_vs_exact"] == 0.0),
    }


def _sparseagg_zero_parity(parties: int = 3, ratio: float = 0.02) -> dict:
    """ZeRO composition: the shard-sized streams run the same
    owner-routed merge — jnp vs Pallas paths bit-identical on
    ``BucketedCompressor.allreduce_shards``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.compression import BucketedCompressor
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.parallel.collectives import shard_map_compat

    mesh = Mesh(np.array(jax.devices()[:parties]), ("dc",))
    rng = np.random.RandomState(17)
    params = [jnp.asarray(rng.standard_normal(s).astype(np.float32))
              for s in (3000, 1100)]
    shardsW = 2

    def run(comp):
        bucketed = BucketedCompressor(comp, bucket_bytes=64 * 1024,
                                      pad_to=128 * shardsW)
        bk = bucketed.zero_bucketer(params)
        shard_sizes = [s // shardsW for s in bk.bucket_sizes]
        state = bucketed.init_shard_state(params, shardsW)
        buckets = bk.flatten(params)
        shards = [b[:s] for b, s in zip(buckets, shard_sizes)]

        def f(sh, ss):
            sh = [a[0] for a in sh]
            s = jax.tree.map(lambda a: a[0], ss)
            out, s2 = bucketed.allreduce_shards(sh, s, "dc", parties, bk)
            return ([a[None] for a in out],
                    jax.tree.map(lambda a: a[None], s2))

        fn = shard_map_compat(f, mesh, in_specs=(P("dc"), P("dc")),
                              out_specs=(P("dc"), P("dc")))

        def stack(t):
            return jax.tree.map(
                lambda a: jnp.stack([jnp.asarray(a)] * parties), t)

        out, s2 = jax.jit(fn)(stack(shards), stack(state))
        return ([np.asarray(a) for a in jax.tree.leaves(out)]
                + [np.asarray(a) for a in jax.tree.leaves(s2)])

    from geomx_tpu.ops.dispatch import kernels
    base = dict(ratio=ratio, min_sparse_size=1, sparse_agg=True)
    oj = run(BiSparseCompressor(**base))
    with kernels("interpret"):
        of = run(BiSparseCompressor(**base))
    bit = len(oj) == len(of) and all(
        np.array_equal(a, b) for a, b in zip(oj, of))
    return {"zero_shard_bit_exact_paths": bool(bit),
            "zero_shards": shardsW}


def _compare_sparseagg(model_name: str = "resnet20", steps: int = 5,
                       batch: int = 24, wan_mbps: float = 200.0,
                       rtt_ms: float = 30.0, ratio: float = 0.01):
    """Compressed-domain aggregation acceptance (ISSUE 12) — module
    docstring under --compare-sparseagg."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from geomx_tpu.analysis.corpus import run_corpus
    from geomx_tpu.analysis.passes import (audit_compressed_path,
                                           audit_zero_compressed_path)
    from geomx_tpu.compression import BucketedCompressor, get_compressor
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    parties = 3
    devs = jax.devices()
    if len(devs) < 4:
        # 3 for the multi-party meshes + a 4-wide axis for the corpus
        # replay's scatter_wire_lie entry
        raise RuntimeError(
            "compare-sparseagg needs >= 4 devices (3-party meshes + the "
            "4-wide corpus replay; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    out = {"mode": "compare_sparseagg", "model": model_name,
           "parties": parties, "steps": steps, "batch": batch,
           "wan_mbps": wan_mbps, "rtt_ms": rtt_ms, "ratio": ratio,
           "device": {"device_kind": devs[0].device_kind,
                      "n_devices": len(devs)}}

    # -- (a) purity: the FULL merged path, replicated and ZeRO-shard ------
    model = get_model(model_name, num_classes=10)
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = jax.jit(lambda r, x: model.init(r, x, train=False))(
        jax.random.PRNGKey(0), sample)["params"]
    sa_spec = f"bsc,{ratio},sparse_agg=1"
    bucketed = BucketedCompressor(get_compressor(sa_spec))
    findings = audit_compressed_path(bucketed, params,
                                     num_parties=parties)
    zbucketed = BucketedCompressor(get_compressor(sa_spec), pad_to=256)
    zfindings = audit_zero_compressed_path(zbucketed, params, 2,
                                           num_parties=parties)
    corpus = run_corpus()
    out["purity"] = {
        "findings": [f.message for f in findings],
        "zero_findings": [f.message for f in zfindings],
        "purity_clean": not findings,
        "zero_shard_purity_clean": not zfindings,
        "dense_merge_flagged": bool(corpus["dense_merge"]["flagged"]),
    }

    # -- (b) bit-exactness: engines and arrival orders --------------------
    out["dc_parity"] = _sparseagg_dc_bit_parity(parties=parties)
    out["server_merge"] = _sparseagg_server_orders()
    out["lattice"] = _sparseagg_lattice_structure(parties=parties)
    out["zero_parity"] = _sparseagg_zero_parity(parties=parties)

    # -- (c) samples/sec at the multi-party topology ----------------------
    topo = HiPSTopology(num_parties=parties, workers_per_party=1)
    local_b = max(1, batch // parties)
    rng = np.random.RandomState(0)
    xs = (rng.rand(steps + 2, parties, 1, local_b, 32, 32, 3)
          * 255).astype(np.uint8)
    ys = rng.randint(0, 10, size=(steps + 2, parties, 1,
                                  local_b)).astype(np.int32)

    def measure(comp_spec):
        cfg = GeoConfig(num_parties=parties, workers_per_party=1,
                        compression=comp_spec)
        tr = Trainer(get_model(model_name, num_classes=10), topo,
                     optax.sgd(0.1, momentum=0.9),
                     sync=get_sync_algorithm(cfg), config=cfg)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0, :2])
        sharding = topo.batch_sharding(tr.mesh)
        times = []
        for s in range(steps + 2):
            xb = jax.device_put(xs[s], sharding)
            yb = jax.device_put(ys[s], sharding)
            t0 = time.perf_counter()
            st, _m = tr.train_step(st, xb, yb)
            jax.block_until_ready(st.step)
            times.append(time.perf_counter() - t0)
        compute_s = float(np.median(times[2:]))
        wire = int(tr.sync.dc_compressor.wire_bytes(st.params))
        # deterministic multi-party WAN model: the dc payload crosses a
        # wan_mbps link once per step plus one RTT (identical model for
        # every config — only the payload differs)
        wan_s = wire * 8.0 / (wan_mbps * 1e6) + rtt_ms / 1e3
        step_s = compute_s + wan_s
        return {"compute_step_ms": compute_s * 1e3,
                "modeled_wan_ms": wan_s * 1e3,
                "step_time_ms": step_s * 1e3,
                "wire_bytes_per_step": wire,
                "samples_per_sec": parties * local_b / step_s,
                "on_chip_samples_per_sec": parties * local_b / compute_s}

    sa_train_spec = f"bsc,{ratio},sparse_agg=1"
    out["configs"] = {
        "vanilla": measure("none"),
        "bsc_sparseagg": measure(sa_train_spec),
    }
    dense = out["configs"]["vanilla"]["samples_per_sec"]
    sparse = out["configs"]["bsc_sparseagg"]["samples_per_sec"]
    out["sparse_vs_dense"] = sparse / dense if dense else 0.0
    out["sparse_beats_dense"] = bool(sparse >= dense)

    gates = ("purity_clean", "zero_shard_purity_clean",
             "dense_merge_flagged")
    out["ok"] = bool(
        all(out["purity"][g] for g in gates)
        and out["dc_parity"]["merged_bit_exact_paths"]
        and out["dc_parity"]["result_identical_across_parties"]
        and out["server_merge"]["merged_bit_exact_orders"]
        and out["server_merge"]["server_sparse_merges"] >= 3
        and out["lattice"]["fp16_lattice_psum"]
        and out["lattice"]["twobit_lattice_psum"]
        and out["zero_parity"]["zero_shard_bit_exact_paths"]
        and out["sparse_beats_dense"])
    return out


def compare_sparseagg_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--wan-mbps="):
            kwargs["wan_mbps"] = float(a.split("=", 1)[1])
        elif a.startswith("--rtt-ms="):
            kwargs["rtt_ms"] = float(a.split("=", 1)[1])
        elif a.startswith("--ratio="):
            kwargs["ratio"] = float(a.split("=", 1)[1])
    _emit(_compare_sparseagg(**kwargs))


def _compare_mfu(model_name: str = "resnet20", steps: int = 6,
                 batch: int = 32, seq_len: int = 128,
                 out_dir: str = None):
    """Compute-phase step-time engine acceptance (ISSUE 17) — module
    docstring under --compare-mfu.  Four sections, one JSON line:

    (a) fused optimizer: the per-leaf optax chain is structurally GONE
        from the lowered update (DCE-verified: the fused bucket closure
        lowers to tpu_custom_call with ZERO stablehlo.multiply, the
        unfused chain to zero custom calls and many multiplies; the
        FULL train step cross-lowered for TPU shows the same swap), and
        a short fused-vs-unfused training run lands the same params;
    (b) precision: the bf16 build's loss trajectory tracks fp32, the
        GX-DTYPE-001 precision audit is clean on a legitimately-built
        bf16 model (classifier head exempt) AND flags an fp32 model
        declared bf16 — the audit has teeth;
    (c) prefetch: host_stall fraction (telemetry/attribution.py) drops
        when the loader's double-buffered prefetch is on, phase
        fractions still sum to ~1.0, and prefetched batches are
        bit-identical to synchronous ones;
    (d) roofline: measured step time -> MFU + bound verdict for BOTH
        first-class workloads (ResNet-20 CIFAR10 and the transformer
        sequence classifier) — the record is the TRANSFORMER_r*.json
        trend series.  CPU-mesh runnable; no TPU needed.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from geomx_tpu.analysis.hlo import count_ops, lower_text
    from geomx_tpu.analysis.passes import audit_precision
    from geomx_tpu.config import GeoConfig
    from geomx_tpu.data import GeoDataLoader
    from geomx_tpu.models import get_model
    from geomx_tpu.ops.optim_pallas import (fused_apply, fused_optimizer,
                                            unfused_apply)
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.telemetry.attribution import attribute_trace
    from geomx_tpu.telemetry.roofline import trainer_roofline
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    from geomx_tpu.utils.profiler import get_profiler

    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            "--compare-mfu needs the 8-virtual-device mesh (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    out_dir = out_dir or tempfile.mkdtemp(prefix="geomx_mfu_")
    os.makedirs(out_dir, exist_ok=True)
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    out = {"mode": "compare_mfu", "model": model_name, "steps": steps,
           "batch": batch, "seq_len": seq_len,
           "device": {"device_kind": devs[0].device_kind,
                      "n_devices": len(devs)}}

    local_b = max(1, batch // 8)
    rng = np.random.RandomState(0)
    x_img = (rng.rand(steps + 2, 2, 4, local_b, 32, 32, 3)
             * 255).astype(np.uint8)
    y_img = rng.randint(0, 10,
                        size=(steps + 2, 2, 4, local_b)).astype(np.int32)

    def _trainer(cfg, tx, precision=None, model=None):
        model = model if model is not None else get_model(
            model_name, num_classes=10, precision=precision)
        return Trainer(model, topo, tx, sync=get_sync_algorithm(cfg),
                       config=cfg, donate=False)

    # -- (a) fused optimizer: DCE structure swap + params match -----------
    # a1: the update closure alone, over two buckets (one odd tail).
    # Contract (ops/optim_pallas.py): fused lowers to one
    # tpu_custom_call per bucket and ZERO stablehlo.multiply (bias
    # corrections are stablehlo.power); the per-leaf chain lowers to
    # zero custom calls and a multiply per hyperparameter per bucket.
    fo = fused_optimizer("adam", learning_rate=1e-3)
    buckets = [jnp.zeros((n,), jnp.float32) for n in (4096, 1037)]
    grads_b = [jnp.full((n,), 1e-3, jnp.float32) for n in (4096, 1037)]
    ostate = fo.init(buckets)

    def _fused_closure(ps, gs, st):
        return fused_apply(fo.spec, ps, gs, st, interpret=False)

    def _unfused_closure(ps, gs, st):
        return unfused_apply(fo, ps, gs, st)

    def _dce(fn):
        txt = lower_text(fn, buckets, grads_b, ostate)
        c = count_ops(txt, ("stablehlo.multiply", "stablehlo.power"))
        return {"custom_calls": txt.count("tpu_custom_call"),
                "multiplies": c.get("multiply", 0),
                "powers": c.get("power", 0)}

    dce_f, dce_u = _dce(_fused_closure), _dce(_unfused_closure)

    # a2: the FULL train step, cross-lowered for TPU on the CPU mesh
    # (GEOMX_FUSED_OPTIM_INTERPRET=0 forces native Mosaic lowering; such
    # a build lowers anywhere but only RUNS on TPU — we only lower it).
    def _step_custom_calls(fused, interpret_env=None):
        old = os.environ.get("GEOMX_FUSED_OPTIM_INTERPRET")
        if interpret_env is not None:
            os.environ["GEOMX_FUSED_OPTIM_INTERPRET"] = interpret_env
        try:
            cfg = GeoConfig(num_parties=2, workers_per_party=4,
                            bucket_bytes=1 << 20, fused_optim=fused)
            tr = _trainer(cfg, fused_optimizer("sgd", learning_rate=0.1,
                                               momentum=0.9))
        finally:
            if interpret_env is not None:
                if old is None:
                    os.environ.pop("GEOMX_FUSED_OPTIM_INTERPRET", None)
                else:
                    os.environ["GEOMX_FUSED_OPTIM_INTERPRET"] = old
        st = tr.init_state(jax.random.PRNGKey(0), x_img[0, 0, 0, :2])
        sharding = topo.batch_sharding(tr.mesh)
        xb = jax.device_put(x_img[0], sharding)
        yb = jax.device_put(y_img[0], sharding)
        return lower_text(tr.train_step, st, xb,
                          yb).count("tpu_custom_call")

    step_fused = _step_custom_calls(True, interpret_env="0")
    step_unfused = _step_custom_calls(False)

    # a3: fused (interpret mode on CPU) vs per-leaf chain, short run.
    # Accumulated FMA-contraction drift through adam/momentum is the
    # documented tolerance (ops/optim_pallas.py): 1e-4 over this horizon.
    def _fit_params(fused):
        cfg = GeoConfig(num_parties=2, workers_per_party=4,
                        bucket_bytes=1 << 20, fused_optim=fused)
        tr = _trainer(cfg, fused_optimizer("sgd", learning_rate=0.05,
                                           momentum=0.9))
        st = tr.init_state(jax.random.PRNGKey(0), x_img[0, 0, 0, :2])
        sharding = topo.batch_sharding(tr.mesh)
        for s in range(steps):
            st, m = tr.train_step(st,
                                  jax.device_put(x_img[s], sharding),
                                  jax.device_put(y_img[s], sharding))
        jax.block_until_ready(m["loss"])
        return jax.device_get(st.params)

    pf, pu = _fit_params(True), _fit_params(False)
    param_max_diff = max(
        float(np.max(np.abs(np.asarray(a, np.float64)
                            - np.asarray(b, np.float64))))
        for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pu)))
    out["fused_optimizer"] = {
        "bucket_update": {"fused": dce_f, "unfused": dce_u},
        "step_custom_calls": {"fused": step_fused,
                              "unfused": step_unfused},
        "per_leaf_chain_gone": bool(
            dce_f["custom_calls"] >= 1 and dce_f["multiplies"] == 0
            and dce_u["custom_calls"] == 0 and dce_u["multiplies"] > 0
            and step_fused >= 1 and step_unfused == 0),
        "param_max_diff": param_max_diff,
        "params_match": bool(param_max_diff < 1e-4),
    }

    # -- (b) precision: bf16 trajectory + audit teeth ---------------------
    def _loss_traj(precision):
        cfg = GeoConfig(num_parties=2, workers_per_party=4,
                        precision=precision)
        tr = _trainer(cfg, optax.sgd(0.1, momentum=0.9),
                      precision=precision)
        st = tr.init_state(jax.random.PRNGKey(0), x_img[0, 0, 0, :2])
        sharding = topo.batch_sharding(tr.mesh)
        losses = []
        for s in range(steps):
            st, m = tr.train_step(st,
                                  jax.device_put(x_img[s], sharding),
                                  jax.device_put(y_img[s], sharding))
            losses.append(float(m["loss"]))
        return losses

    traj_fp32 = _loss_traj("fp32")
    traj_bf16 = _loss_traj("bf16")
    loss_max_diff = max(abs(a - b)
                        for a, b in zip(traj_fp32, traj_bf16))

    sample_x = jnp.zeros((2, 32, 32, 3), jnp.float32)

    def _audit(model_precision):
        mdl = get_model(model_name, num_classes=10,
                        precision=model_precision)
        vs = jax.eval_shape(lambda: mdl.init(jax.random.PRNGKey(0),
                                             sample_x, train=False))
        vs = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), vs)
        return audit_precision(
            lambda xx: mdl.apply(vs, xx, train=False), sample_x,
            precision="bf16", allowed_fp32_sites=1)

    clean = _audit("bf16")            # legit bf16 build: head exempt
    leaks = _audit("fp32")            # fp32 model declared bf16: leaks
    out["precision"] = {
        "loss_fp32": [round(v, 6) for v in traj_fp32],
        "loss_bf16": [round(v, 6) for v in traj_bf16],
        "loss_max_diff": round(loss_max_diff, 6),
        "tolerance": 0.05,
        "bf16_matches_fp32": bool(loss_max_diff < 0.05),
        "audit_findings_bf16_model": [f.message for f in clean],
        "audit_findings_fp32_model": len(leaks),
        "dtype_audit_clean": not clean,
        "fp32_leak_detected": bool(leaks),
    }

    # -- (c) prefetch: host_stall drops, determinism ----------------------
    pf_b = 16
    pf_steps = 8
    n_pf = 8 * pf_b * pf_steps
    x_pf = (rng.rand(n_pf, 32, 32, 3) * 255).astype(np.uint8)
    y_pf = rng.randint(0, 10, size=(n_pf,)).astype(np.int32)

    def _stall(prefetch):
        cfg = GeoConfig(num_parties=2, workers_per_party=4,
                        prefetch=prefetch)
        tr = _trainer(cfg, optax.sgd(0.1, momentum=0.9), model=get_model(
            "cnn", num_classes=10))
        sharding = topo.batch_sharding(tr.mesh)
        loader = GeoDataLoader(x_pf, y_pf, topo, batch_size=pf_b,
                               seed=3, sharding=sharding, augment=True)
        st = tr.init_state(jax.random.PRNGKey(0), x_pf[:2])
        xb, yb = next(iter(loader.epoch(0, prefetch=0)))
        st, m = tr.train_step(st, xb, yb)          # compile + warm
        jax.block_until_ready(m["loss"])
        prof = get_profiler()
        prof.set_state(True)
        since = prof.now_us()
        st, _recs = tr.fit(st, loader, epochs=1)
        prof.set_state(False)
        att = attribute_trace(prof.to_doc(), since_us=since)
        with open(os.path.join(out_dir,
                               f"attribution_prefetch{prefetch}.json"),
                  "w") as f:
            json.dump(att, f, indent=2, default=str)
        return att

    att_off = _stall(0)
    att_on = _stall(2)
    sum_off = sum(att_off["summary"].values())
    sum_on = sum(att_on["summary"].values())

    la = GeoDataLoader(x_pf, y_pf, topo, batch_size=pf_b, seed=3,
                       augment=True)
    lb = GeoDataLoader(x_pf, y_pf, topo, batch_size=pf_b, seed=3,
                       augment=True)
    deterministic = all(
        np.array_equal(np.asarray(xa), np.asarray(xb))
        and np.array_equal(np.asarray(ya), np.asarray(yb))
        for (xa, ya), (xb, yb) in zip(la.epoch(1, prefetch=0),
                                      lb.epoch(1, prefetch=3)))
    stall_off = att_off["summary"]["host_stall"]
    stall_on = att_on["summary"]["host_stall"]
    out["prefetch"] = {
        "host_stall_fraction_off": round(stall_off, 4),
        "host_stall_fraction_on": round(stall_on, 4),
        "host_stall_drops": bool(stall_on < stall_off),
        "phase_fractions_off": {k: round(v, 4)
                                for k, v in att_off["summary"].items()},
        "phase_fractions_on": {k: round(v, 4)
                               for k, v in att_on["summary"].items()},
        "phase_sum_ok": bool(abs(sum_off - 1.0) < 1e-6
                             and abs(sum_on - 1.0) < 1e-6),
        "prefetch_deterministic": bool(deterministic),
    }

    # -- (d) roofline MFU for both first-class workloads ------------------
    def _roofline(workload):
        if workload == "transformer":
            mdl = get_model("transformer", num_classes=10)
            xs = rng.randint(0, 256, size=(steps + 2, 2, 4, local_b,
                                           seq_len)).astype(np.int32)
            ys = rng.randint(0, 10, size=(steps + 2, 2, 4,
                                          local_b)).astype(np.int32)
        else:
            mdl = get_model(workload, num_classes=10)
            xs, ys = x_img, y_img
        cfg = GeoConfig(num_parties=2, workers_per_party=4)
        tr = _trainer(cfg, optax.sgd(0.1, momentum=0.9), model=mdl)
        st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0, :2])
        sharding = topo.batch_sharding(tr.mesh)
        times = []
        for s in range(steps + 2):
            xb = jax.device_put(xs[s], sharding)
            yb = jax.device_put(ys[s], sharding)
            t0 = time.perf_counter()
            st, m = tr.train_step(st, xb, yb)
            jax.block_until_ready(m["loss"])
            times.append(time.perf_counter() - t0)
        step_s = float(np.median(times[2:]))
        return {
            "step_time_ms": round(step_s * 1e3, 3),
            "samples_per_sec": round(8 * local_b / step_s, 1),
            **_roofline_fields(lambda: trainer_roofline(
                tr, st, xb, yb, step_time_s=step_s)),
        }

    out["roofline"] = {
        "resnet20": _roofline(model_name),
        "transformer": _roofline("transformer"),
    }
    rooflines_present = all(
        r["step_time_ms"] > 0 for r in out["roofline"].values())

    out["per_leaf_chain_gone"] = out["fused_optimizer"][
        "per_leaf_chain_gone"]
    out["params_match"] = out["fused_optimizer"]["params_match"]
    out["bf16_matches_fp32"] = out["precision"]["bf16_matches_fp32"]
    out["host_stall_drops"] = out["prefetch"]["host_stall_drops"]
    out["phase_sum_ok"] = out["prefetch"]["phase_sum_ok"]
    out["artifacts"] = {"out_dir": out_dir}
    out["ok"] = bool(
        out["per_leaf_chain_gone"] and out["params_match"]
        and out["bf16_matches_fp32"]
        and out["precision"]["dtype_audit_clean"]
        and out["precision"]["fp32_leak_detected"]
        and out["host_stall_drops"] and out["phase_sum_ok"]
        and out["prefetch"]["prefetch_deterministic"]
        and rooflines_present)
    with open(os.path.join(out_dir, "mfu_record.json"), "w") as f:
        json.dump(out, f, indent=2, default=str)
    return out


def compare_mfu_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--model="):
            kwargs["model_name"] = a.split("=", 1)[1]
        elif a.startswith("--steps="):
            kwargs["steps"] = int(a.split("=", 1)[1])
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--seq-len="):
            kwargs["seq_len"] = int(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_mfu(**kwargs))


# --------------------------------------------------------------------------
# --serve: geo-distributed serving plane acceptance (docs/serving.md) —
# sparse-delta model registry + continuous-batching inference gateway.
# Three phases: (A) sustained gateway QPS with p50/p99 at the target
# batch and a bounded jit cache; (B) train-while-serving — dense base
# published once, then delta-only pair-format refresh rounds with the
# replica reconstructing bit-exact vs a dense checkpoint and delta-only
# verified via round-ledger byte accounting; (C) chaos — registry shard
# kill mid-refresh + failover restart on the same journal, replayed
# pushes absorbed by the (layer,round)/(sender,rid) dedup, serving p99
# bounded and ZERO lost requests throughout.
# --------------------------------------------------------------------------


def _serve_http_load(port, xs, n_requests, clients, rows_per_req,
                     stop_evt=None, deadline_s=30.0):
    """Fire ``n_requests`` POST /infer calls from ``clients`` threads
    (or run until ``stop_evt`` when n_requests is None).  Every request
    is accounted: ok (2xx), shed (503) or error — the zero-lost gate is
    issued == ok + shed + error."""
    import urllib.error
    import urllib.request

    import numpy as np

    lock = threading.Lock()
    stats = {"issued": 0, "ok": 0, "shed": 0, "error": 0,
             "latencies_s": [], "batch_sizes": []}
    url = f"http://127.0.0.1:{port}/infer"

    def one_request(rng):
        rows = [xs[rng.integers(0, len(xs))].tolist()
                for _ in range(rows_per_req)]
        body = json.dumps({"inputs": rows}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=deadline_s) as r:
                doc = json.loads(r.read())
                dt = time.monotonic() - t0
                with lock:
                    stats["ok"] += 1
                    stats["latencies_s"].append(dt)
                    stats["batch_sizes"].extend(doc.get("batch_sizes", []))
        except urllib.error.HTTPError as e:
            e.read()
            with lock:
                stats["shed" if e.code == 503 else "error"] += 1
                stats["latencies_s"].append(time.monotonic() - t0)
        except Exception:
            with lock:
                stats["error"] += 1

    def worker(wid):
        rng = np.random.default_rng(1000 + wid)
        while True:
            with lock:
                if n_requests is not None and stats["issued"] >= n_requests:
                    return
                if stop_evt is not None and stop_evt.is_set():
                    return
                stats["issued"] += 1
            one_request(rng)

    _run_load_threads(worker, clients, stats, deadline_s)
    return stats


def _run_load_threads(worker, clients, stats, deadline_s):
    """Shared load-gen tail: spawn client threads, then fold raw
    latencies into p50/p99 + sustained QPS (monotonic elapsed — a wall
    step mid-load must not fake a QPS number)."""
    import math

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(deadline_s * 4)
    stats["elapsed_s"] = time.monotonic() - t0
    lat = sorted(stats["latencies_s"])

    def pct(q):
        if not lat:
            return None
        return lat[min(len(lat) - 1, int(math.ceil(q * len(lat))) - 1)]

    stats["p50_s"], stats["p99_s"] = pct(0.50), pct(0.99)
    stats["qps"] = (stats["ok"] / stats["elapsed_s"]
                    if stats["elapsed_s"] > 0 else 0.0)
    del stats["latencies_s"]


def _serve_native_load(port, xs, n_requests, clients, rows_per_req,
                       stop_evt=None, deadline_s=30.0):
    """Native-wire twin of ``_serve_http_load``: ONE persistent binary
    connection per client thread speaking INFER/INFER_REPLY frames (the
    serving fast path, docs/serving.md) — no per-request TCP connect,
    no JSON float text.  Identical zero-lost bookkeeping: issued ==
    ok + shed + error, shed is the server's explicit refusal frame."""
    import numpy as np

    from geomx_tpu.serve.infer_wire import NativeInferenceClient

    lock = threading.Lock()
    stats = {"issued": 0, "ok": 0, "shed": 0, "error": 0,
             "latencies_s": [], "batch_sizes": []}

    def worker(wid):
        rng = np.random.default_rng(2000 + wid)
        cli = NativeInferenceClient(("127.0.0.1", port),
                                    timeout_s=deadline_s)
        try:
            while True:
                with lock:
                    if n_requests is not None \
                            and stats["issued"] >= n_requests:
                        return
                    if stop_evt is not None and stop_evt.is_set():
                        return
                    stats["issued"] += 1
                xb = np.stack([xs[rng.integers(0, len(xs))]
                               for _ in range(rows_per_req)])
                t0 = time.monotonic()
                try:
                    rep = cli.infer(xb)
                    dt = time.monotonic() - t0
                    with lock:
                        if "outputs" in rep:
                            stats["ok"] += 1
                            stats["latencies_s"].append(dt)
                            stats["batch_sizes"].extend(
                                rep.get("batch_sizes", []))
                        elif rep.get("error") == "shed":
                            stats["shed"] += 1
                            stats["latencies_s"].append(dt)
                        else:
                            stats["error"] += 1
                except Exception:
                    with lock:
                        stats["error"] += 1
        finally:
            cli.close()

    _run_load_threads(worker, clients, stats, deadline_s)
    return stats


def _compare_serve(rounds: int = 5, qps_requests: int = 120,
                   clients: int = 4, rows_per_req: int = 2,
                   max_batch: int = 8, queue_ms: float = 2.0,
                   delta_frac: float = 0.01, seed: int = 0,
                   out_dir=None):
    import jax
    import numpy as np

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.serve.gateway import (InferenceGateway, flatten_params)
    from geomx_tpu.serve.registry import RegistryClient, RegistryServer
    from geomx_tpu.serve.replica import ServingReplica
    from geomx_tpu.serve.infer_wire import serve_native
    from geomx_tpu.telemetry.ledger import (get_request_ledger,
                                            get_round_ledger,
                                            reset_request_ledger,
                                            reset_round_ledger)

    cfg = GeoConfig.from_env()
    rng = np.random.default_rng(seed)
    t_bench0 = time.time()
    out = {"mode": "compare_serve", "rounds": rounds,
           "max_batch": max_batch, "queue_ms": queue_ms,
           "staleness_budget_s": cfg.serve_staleness_s}

    reset_round_ledger()
    reset_request_ledger()

    # ---- model + registry publish (dense base, once) --------------------
    model = get_model("mlp", num_classes=10)
    feat = 28 * 28
    x0 = np.zeros((1, feat), np.float32)
    variables = model.init(jax.random.PRNGKey(seed), x0)
    named, treedef = flatten_params(variables)
    named = {k: np.ascontiguousarray(v, np.float32)
             for k, v in named.items()}
    dense_ckpt = {k: v.copy() for k, v in named.items()}
    dense_bytes = int(sum(v.nbytes for v in named.values()))
    out["model"] = {"name": "mlp", "layers": len(named),
                    "dense_bytes": dense_bytes}

    durable_dir = tempfile.mkdtemp(prefix="geomx_serve_registry_")
    srv = RegistryServer(durable_dir=durable_dir)
    srv.start()
    trainer = RegistryClient(srv.addr, sender=0, timeout_s=20.0)
    trainer.publish("v1", named)

    replica_cli = RegistryClient(srv.addr, sender=1, timeout_s=20.0)
    replica = ServingReplica("v1", party=1)
    first = replica.sync(replica_cli)
    out["base_sync"] = first

    # the fast path (docs/serving.md "Serving fast path"): every
    # (bucket, input-shape) executable compiles in start(), BEFORE the
    # first request — the r01 p99/p50 gap was first-request compiles
    gw = InferenceGateway(replica, treedef=treedef, model_name="mlp",
                          num_classes=10, max_batch=max_batch,
                          queue_ms=queue_ms, warmup_shapes=[(feat,)])
    gw.start()
    out["warmup_compiles"] = int(gw.warmup_compiles)
    httpd = gw.serve_http(port=cfg.serve_port)
    port = httpd.server_address[1]
    nsrv = serve_native(gw, port=0)      # None when the knob is off
    out["native_wire_enabled"] = nsrv is not None
    xs = rng.normal(size=(16, feat)).astype(np.float32)

    def _fill(sizes):
        # mean dispatched batch over the bucket ceiling: 1.0 = every
        # forward ran full, the r01 ragged-batch waste eliminated
        return (round(sum(sizes) / (len(sizes) * max_batch), 4)
                if sizes else None)

    try:
        # ---- phase A: sustained QPS at the target batch -----------------
        _serve_http_load(port, xs, 8, 2, rows_per_req)  # warm http door
        reset_request_ledger()
        load_http = _serve_http_load(port, xs, qps_requests, clients,
                                     rows_per_req)
        load = load_http
        if nsrv is not None:
            # headline QPS is the native lane; http stays reported as
            # the slow door so the trend gate can watch both
            load = _serve_native_load(nsrv.port, xs, qps_requests,
                                      clients, rows_per_req)
            out["qps_phase_http"] = load_http
            out["serve_qps_http"] = round(load_http["qps"], 2)
            out["serve_p50_ms_http"] = round(
                1e3 * (load_http["p50_s"] or 0.0), 3)
            out["serve_p99_ms_http"] = round(
                1e3 * (load_http["p99_s"] or 0.0), 3)
            out["batch_fill_fraction_http"] = _fill(
                load_http["batch_sizes"])
        out["qps_phase"] = load
        out["serve_transport"] = "native" if nsrv is not None else "http"
        out["serve_qps"] = round(load["qps"], 2)
        out["serve_p50_ms"] = round(1e3 * (load["p50_s"] or 0.0), 3)
        out["serve_p99_ms"] = round(1e3 * (load["p99_s"] or 0.0), 3)
        out["batch_fill_fraction"] = _fill(load["batch_sizes"])
        out["batch_max_seen"] = int(max(
            (load["batch_sizes"] or [0]) + (load_http["batch_sizes"]
                                            or [0])))
        out["jit_cache_size"] = gw.jit_cache_size()
        out["jit_cache_bounded"] = bool(
            gw.jit_cache_size() <= len(gw.buckets))
        out["batch_bounded"] = bool(out["batch_max_seen"] <= max_batch)
        # pre-warm pins compiles out of request latency: the cache must
        # still hold EXACTLY the executables start() compiled — any
        # growth means a request paid a compile after all
        out["prewarm_no_recompile"] = bool(
            out["warmup_compiles"] > 0
            and gw.jit_cache_size() == out["warmup_compiles"])
        if nsrv is not None:
            # byte-true honesty audit: actual on-wire frame bytes vs
            # the sender's declared payload, from the request ledger's
            # per-transport lanes.  Gated on the payload-bearing
            # request direction (replies are a 10-class logits row —
            # header-dominated by construction, reported not gated).
            lane = get_request_ledger().summary().get(
                "wire", {}).get("native", {})
            out["native_wire"] = lane
            hr = lane.get("honesty_ratio_rx")
            out["native_honesty_ratio"] = hr
            out["native_wire_honest"] = bool(
                hr is not None and hr <= 1.02)

        # ---- phase B: train-while-serving, delta-only refresh ----------
        # background load runs over BOTH doors: refresh correctness and
        # staleness hold under the fast path, not just the http lane
        stop_evt = threading.Event()
        bg_stats = {}
        bg_native_stats = {}

        def bg_load():
            bg_stats.update(_serve_http_load(
                port, xs, None, 2, rows_per_req, stop_evt=stop_evt))

        bg = threading.Thread(target=bg_load, daemon=True)
        bg.start()
        bg_n = None
        if nsrv is not None:
            bg_n = threading.Thread(
                target=lambda: bg_native_stats.update(_serve_native_load(
                    nsrv.port, xs, None, 2, rows_per_req,
                    stop_evt=stop_evt)), daemon=True)
            bg_n.start()
        max_staleness = 0.0
        for r in range(1, rounds + 1):
            layers = {}
            for k, v in dense_ckpt.items():
                n = v.size
                kk = max(1, int(n * delta_frac))
                idx = rng.choice(n, size=kk, replace=False).astype(np.int64)
                vals = rng.normal(size=kk).astype(np.float32) * 0.01
                layers[k] = (vals, idx)
                np.add.at(v.reshape(-1), idx, vals)
            ack = trainer.push_delta("v1", r, layers)
            if ack["applied_layers"] != len(layers):
                raise RuntimeError(f"round {r} push under-applied: {ack}")
            replica.sync(replica_cli)
            max_staleness = max(max_staleness, replica.staleness_s())
        stop_evt.set()
        bg.join(30.0)
        if bg_n is not None:
            bg_n.join(30.0)
        out["train_while_serving"] = {
            "bg_requests": bg_stats.get("issued", 0),
            "bg_ok": bg_stats.get("ok", 0),
            "bg_shed": bg_stats.get("shed", 0),
            "bg_error": bg_stats.get("error", 0),
            "bg_native_requests": bg_native_stats.get("issued", 0),
            "bg_native_ok": bg_native_stats.get("ok", 0),
            "bg_native_error": bg_native_stats.get("error", 0),
            "max_staleness_s": round(max_staleness, 3),
        }
        out["staleness_bounded"] = bool(
            max_staleness <= cfg.serve_staleness_s)

        served = replica.params()
        bit_exact = all(
            np.array_equal(served[k], dense_ckpt[k]) for k in dense_ckpt)
        out["bit_exact"] = bool(bit_exact)

        # delta-only, verified via round-ledger byte accounting: the
        # registry wire frames carry meta["round"] + wire_declared, so
        # the protocol choke point attributed every byte.  Post-base
        # refresh must be pair frames a fraction of the dense size.
        base_rx = delta_rx = 0
        declared_honest = True
        for rec in get_round_ledger().records():
            if not str(rec.get("key", "")).startswith("v1/"):
                continue
            wire = rec.get("wire", {})
            got = int(wire.get("push_rx_bytes", 0))
            if int(rec.get("round", -1)) == 0:
                base_rx += got
            else:
                delta_rx += got
                declared = int(rec.get("declared_rx_bytes", 0) or 0)
                if declared <= 0 or declared > got:
                    declared_honest = False
        per_round = delta_rx / max(1, rounds)
        out["ledger_bytes"] = {
            "base_push_rx": base_rx, "delta_push_rx": delta_rx,
            "delta_per_round": round(per_round, 1),
            "declared_honest": declared_honest,
        }
        out["delta_only"] = bool(
            base_rx > 0 and delta_rx > 0 and declared_honest
            and per_round < 0.5 * dense_bytes)

        # ---- phase C: chaos — registry kill mid-refresh + failover -----
        reset_request_ledger()
        stop_evt2 = threading.Event()
        chaos_stats = {}
        chaos_native_stats = {}

        def chaos_load():
            chaos_stats.update(_serve_http_load(
                port, xs, None, 2, rows_per_req, stop_evt=stop_evt2))

        bg2 = threading.Thread(target=chaos_load, daemon=True)
        bg2.start()
        bg2_n = None
        if nsrv is not None:
            bg2_n = threading.Thread(
                target=lambda: chaos_native_stats.update(
                    _serve_native_load(nsrv.port, xs, None, 2,
                                       rows_per_req,
                                       stop_evt=stop_evt2)),
                daemon=True)
            bg2_n.start()

        chaos_round = rounds + 1
        layers = {}
        for k, v in dense_ckpt.items():
            kk = max(1, int(v.size * delta_frac))
            idx = rng.choice(v.size, size=kk, replace=False).astype(np.int64)
            vals = rng.normal(size=kk).astype(np.float32) * 0.01
            layers[k] = (vals, idx)
            np.add.at(v.reshape(-1), idx, vals)
        # half the layers land, then the registry dies mid-refresh
        names = list(layers)
        half = {k: layers[k] for k in names[:max(1, len(names) // 2)]}
        trainer.push_delta("v1", chaos_round, half)
        srv.crash()
        gen_old = srv.generation

        failover = RegistryServer(durable_dir=durable_dir)
        failover.start()
        out["failover_generation"] = {"old": gen_old,
                                      "new": failover.generation}
        trainer2 = RegistryClient(failover.addr, sender=0, timeout_s=20.0)
        # replay the WHOLE round against the failover: the half that
        # already landed must dedup ((layer, round) journaled), only the
        # torn-off remainder applies — the no-double-apply gate
        ack = trainer2.push_delta("v1", chaos_round, layers)
        expected_new = len(layers) - len(half)
        out["chaos_replay"] = {
            "layers": len(layers), "pre_crash": len(half),
            "replay_applied": int(ack["applied_layers"]),
        }
        no_double_apply = ack["applied_layers"] == expected_new

        replica_cli2 = RegistryClient(failover.addr, sender=1,
                                      timeout_s=20.0)
        post = replica.sync(replica_cli2)
        out["chaos_sync"] = post
        served = replica.params()
        chaos_bit_exact = all(
            np.array_equal(served[k], dense_ckpt[k]) for k in dense_ckpt)
        no_double_apply = no_double_apply and chaos_bit_exact

        stop_evt2.set()
        bg2.join(30.0)
        if bg2_n is not None:
            bg2_n.join(30.0)
        out["chaos_load"] = chaos_stats
        if nsrv is not None:
            out["chaos_load_native"] = chaos_native_stats

        def _lane_zero_lost(st):
            lost = (st.get("issued", 0) - st.get("ok", 0)
                    - st.get("shed", 0) - st.get("error", 0))
            return (lost == 0 and st.get("error", 0) == 0
                    and st.get("issued", 0) > 0)

        # zero-lost and the chaos p99 bound must hold on EVERY door
        # that took load — a native request lost during failover is as
        # lost as an http one
        lanes = [chaos_stats] + ([chaos_native_stats]
                                 if nsrv is not None else [])
        out["zero_lost"] = bool(all(_lane_zero_lost(s) for s in lanes))
        chaos_p99 = max(s.get("p99_s") or 0.0 for s in lanes)
        out["chaos_p99_ms"] = round(1e3 * chaos_p99, 3)
        out["chaos_p99_bounded"] = bool(0.0 < chaos_p99 < 2.0)
        out["no_double_apply"] = bool(no_double_apply)
        out["restart_detected"] = bool(post.get("restart_detected"))
        out["replica"] = replica.snapshot()

        # ---- SLO policy sanity: the pilot's fourth family fires --------
        from geomx_tpu.control.policy import SloPolicy
        from geomx_tpu.control.sensors import ControlObservation
        pol = SloPolicy(lambda: {"p99_s": 10.0}, target_p99_s=0.5,
                        confirm=1, cooldown=1)
        obs = ControlObservation(step=1, links={}, exposed_comms=0.0,
                                 hidden_comms=0.0, compute_s=0.0,
                                 ef_residual_norm=0.0, grad_norm=0.0,
                                 dc_dense_bytes=0)
        d = pol.decide(obs)
        out["slo_shed_decision"] = bool(
            d is not None and d.value[0] == "shed" and d.value[1] > 0)

        trainer2.close()
        replica_cli2.close()
        failover.stop()
        failover.join(5.0)
    finally:
        if nsrv is not None:
            nsrv.stop()
        httpd.shutdown()
        gw.stop()
        trainer.close()
        replica_cli.close()
        srv.stop()
        srv.join(5.0)

    out["elapsed_s"] = round(time.time() - t_bench0, 3)
    native_ok = (nsrv is None) or bool(
        out.get("native_wire_honest")
        and out.get("serve_qps_http", 0) > 0)
    out["ok"] = bool(
        out.get("bit_exact") and out.get("delta_only")
        and out.get("staleness_bounded") and out.get("zero_lost")
        and out.get("chaos_p99_bounded") and out.get("no_double_apply")
        and out.get("jit_cache_bounded") and out.get("batch_bounded")
        and out.get("restart_detected") and out.get("slo_shed_decision")
        and out.get("prewarm_no_recompile")
        and out.get("serve_qps", 0) > 0 and native_ok)
    if out_dir:
        from geomx_tpu.telemetry.ledger import (get_request_ledger,
                                                get_round_ledger)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "serve_record.json"), "w") as f:
            json.dump(out, f, indent=2, default=str)
        with open(os.path.join(out_dir, "serve_ledger.json"), "w") as f:
            json.dump({
                "rounds": get_round_ledger().records(),
                "requests": get_request_ledger().records(),
                "request_summary": get_request_ledger().summary(),
            }, f, indent=2, default=str)
        out["artifacts"] = {"out_dir": out_dir}
    return out


def compare_serve_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--rounds="):
            kwargs["rounds"] = int(a.split("=", 1)[1])
        elif a.startswith("--requests="):
            kwargs["qps_requests"] = int(a.split("=", 1)[1])
        elif a.startswith("--clients="):
            kwargs["clients"] = int(a.split("=", 1)[1])
        elif a.startswith("--max-batch="):
            kwargs["max_batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--queue-ms="):
            kwargs["queue_ms"] = float(a.split("=", 1)[1])
        elif a.startswith("--delta-frac="):
            kwargs["delta_frac"] = float(a.split("=", 1)[1])
        elif a.startswith("--seed="):
            kwargs["seed"] = int(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_serve(**kwargs))


# --------------------------------------------------------------------------
# --fleetscope: fleet-wide observability acceptance (docs/telemetry.md
# "Fleetscope") — scheduler-colocated aggregator + gradient-to-inference
# freshness tracing.  Four gates: (A) train-while-serving on BOTH
# inference transports with per-round propagation latency (merge ->
# publish -> apply -> first served) measured as p50/p99; (B) registry
# kill + failover shows up as a NAMED node-health transition in the
# fleet document with a bounded propagation spike, while every healthy
# node's fold degrades gracefully (marked, never fatal); (C) the
# multi-window burn-rate breach fires deterministically on a seeded
# latency-inflation chaos series — bit-identical across two same-seed
# runs; (D) the versioned fleet document serves over GET /fleet and
# renders through tools/gxtop.py.
# --------------------------------------------------------------------------


def _fleetscope_burn_series(seed, windows="20:4,60:2"):
    """One deterministic burn-monitor run over a seeded latency-
    inflation chaos window (virtual time: t = tick index, no clock
    sampled anywhere) — returns the breach list as canonical JSON so
    two same-seed runs can be compared byte-for-byte."""
    import numpy as np

    from geomx_tpu.telemetry.fleetscope import BurnRateMonitor

    rng = np.random.default_rng(seed)
    mon = BurnRateMonitor(windows=windows, slo_target=0.99)
    breaches = []
    for i in range(140):
        t = float(i)
        good, bad = 50.0, 0.0
        if 60 <= i < 95:
            # seeded chaos: inflated latencies push a seeded fraction
            # of the tick's traffic over the latency SLO
            infl = 1.0 + float(rng.random())
            bad = round(25.0 * infl, 6)
            good = round(max(0.0, 50.0 - bad), 6)
        mon.record(t, good, bad)
        b = mon.evaluate(t)
        if b is not None:
            breaches.append(b)
    return json.dumps(breaches, sort_keys=True), len(breaches)


def _compare_fleetscope(rounds: int = 6, clients: int = 2,
                        rows_per_req: int = 2, max_batch: int = 8,
                        queue_ms: float = 2.0, delta_frac: float = 0.01,
                        seed: int = 0, out_dir=None):
    import urllib.request

    import jax
    import numpy as np

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.serve.gateway import (InferenceGateway, flatten_params)
    from geomx_tpu.serve.infer_wire import serve_native
    from geomx_tpu.serve.registry import RegistryClient, RegistryServer
    from geomx_tpu.serve.replica import ServingReplica
    from geomx_tpu.service.scheduler import GeoScheduler, SchedulerClient
    from geomx_tpu.telemetry.fleetscope import (
        get_propagation_tracker, note_propagation,
        reset_propagation_tracker)
    from geomx_tpu.telemetry.ledger import (reset_request_ledger,
                                            reset_round_ledger)

    # arm the scheduler-colocated aggregator BEFORE the scheduler is
    # constructed (the /fleet route + poll thread attach at metrics-http
    # start); tight interval + heartbeat so the kill phase resolves in
    # bench time
    os.environ["GEOMX_FLEETSCOPE"] = "1"
    os.environ["GEOMX_FLEETSCOPE_INTERVAL_S"] = "0.25"

    cfg = GeoConfig.from_env()
    rng = np.random.default_rng(seed)
    t_bench0 = time.time()
    out = {"mode": "compare_fleetscope", "rounds": rounds, "seed": seed}

    reset_round_ledger()
    reset_request_ledger()
    tracker = reset_propagation_tracker()

    sched = GeoScheduler(heartbeat_timeout=1.5, metrics_port=0).start()
    out["fleetscope_armed"] = sched.fleetscope is not None

    # ---- model + serving plane (the --serve topology, roster-joined) ----
    model = get_model("mlp", num_classes=10)
    feat = 28 * 28
    variables = model.init(jax.random.PRNGKey(seed),
                           np.zeros((1, feat), np.float32))
    named, treedef = flatten_params(variables)
    named = {k: np.ascontiguousarray(v, np.float32)
             for k, v in named.items()}
    dense_ckpt = {k: v.copy() for k, v in named.items()}

    durable_dir = tempfile.mkdtemp(prefix="geomx_fleetscope_registry_")
    srv = RegistryServer(durable_dir=durable_dir)
    srv.start()
    trainer = RegistryClient(srv.addr, sender=0, timeout_s=20.0)
    trainer.publish("v1", named)
    replica_cli = RegistryClient(srv.addr, sender=1, timeout_s=20.0)
    replica = ServingReplica("v1", party=1)
    replica.sync(replica_cli)

    gw = InferenceGateway(replica, treedef=treedef, model_name="mlp",
                          num_classes=10, max_batch=max_batch,
                          queue_ms=queue_ms, warmup_shapes=[(feat,)])
    gw.start()
    httpd = gw.serve_http(port=cfg.serve_port)
    port = httpd.server_address[1]
    nsrv = serve_native(gw, port=0)
    out["native_wire_enabled"] = nsrv is not None
    xs = rng.normal(size=(16, feat)).astype(np.float32)

    # roster joins: the gateway registers as node kind "serve" (its
    # registered port IS the HTTP surface FleetScope polls); the
    # registry joins heartbeat-only (port 0 — no HTTP surface), so its
    # crash becomes a NAMED heartbeat death, not a silent poll gap
    gw_client = gw.register_with_scheduler(
        ("127.0.0.1", sched.port), http_port=port,
        heartbeat_interval_s=0.3)
    reg_client = SchedulerClient(("127.0.0.1", sched.port))
    reg_client.register("serve", port=0, tag="registry")
    reg_client.start_heartbeat(0.3)

    trainer2 = replica_cli2 = failover = None
    try:
        # ---- phase A: train-while-serving + propagation join ------------
        stop_evt = threading.Event()
        bg_http, bg_native = {}, {}
        bg = threading.Thread(target=lambda: bg_http.update(
            _serve_http_load(port, xs, None, clients, rows_per_req,
                             stop_evt=stop_evt)), daemon=True)
        bg.start()
        bg_n = None
        if nsrv is not None:
            bg_n = threading.Thread(target=lambda: bg_native.update(
                _serve_native_load(nsrv.port, xs, None, clients,
                                   rows_per_req, stop_evt=stop_evt)),
                daemon=True)
            bg_n.start()

        def push_round(r, client, rep_client):
            # the round's "merge" instant: the training plane finished
            # folding this round (in a full run the RoundLedger's merge
            # hop lands here — the bench IS the trainer, so it notes
            # the hop where the merge would be)
            note_propagation(r, "merge")
            layers = {}
            for k, v in dense_ckpt.items():
                kk = max(1, int(v.size * delta_frac))
                idx = rng.choice(v.size, size=kk,
                                 replace=False).astype(np.int64)
                vals = rng.normal(size=kk).astype(np.float32) * 0.01
                layers[k] = (vals, idx)
                np.add.at(v.reshape(-1), idx, vals)
            client.push_delta("v1", r, layers)
            replica.sync(rep_client)

        for r in range(1, rounds + 1):
            push_round(r, trainer, replica_cli)
            time.sleep(0.2)     # let both doors serve the fresh round

        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if tracker.summary()["rounds_completed"] >= rounds:
                break
            time.sleep(0.1)
        stop_evt.set()
        bg.join(30.0)
        if bg_n is not None:
            bg_n.join(30.0)
        prop = tracker.summary()
        out["propagation"] = prop
        out["load"] = {"http_ok": bg_http.get("ok", 0),
                       "native_ok": bg_native.get("ok", 0)}
        out["propagation_measured"] = bool(
            prop["rounds_completed"] >= max(1, rounds - 1)
            and prop["p99_s"] > 0.0)
        by_lane = prop["by_transport"]
        out["propagation_both_transports"] = bool(
            by_lane.get("http", 0) > 0
            and (nsrv is None or by_lane.get("native", 0) > 0))

        # the fleet document must be live over GET /fleet by now
        fleet_url = f"http://127.0.0.1:{sched.metrics_port}/fleet"
        doc = {}
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with urllib.request.urlopen(fleet_url, timeout=5.0) as resp:
                doc = json.loads(resp.read())
            if doc.get("fleet_version", 0) > 0 \
                    and "serve:gateway" in (doc.get("nodes") or {}):
                break
            time.sleep(0.2)
        version_a = int(doc.get("fleet_version", 0))
        out["fleet_route_ok"] = bool(
            version_a > 0 and "serve:gateway" in doc.get("nodes", {})
            and "serve:registry" in doc.get("nodes", {}))

        # ---- phase B: registry kill -> named death + bounded spike ------
        srv.crash()
        reg_client.close()      # the dead process stops heartbeating
        failover = RegistryServer(durable_dir=durable_dir)
        failover.start()
        # a DISTINCT sender id: the fresh client's rid counter restarts
        # at 1, and the journal-restored dedup set already holds
        # (sender=0, rid) pairs from phase A — same-sender pushes would
        # be silently deduped as replays
        trainer2 = RegistryClient(failover.addr, sender=2,
                                  timeout_s=20.0)
        replica_cli2 = RegistryClient(failover.addr, sender=1,
                                      timeout_s=20.0)

        chaos_rounds = [rounds + 1, rounds + 2]
        for r in chaos_rounds:
            push_round(r, trainer2, replica_cli2)
            # a short burst on each door so the failover rounds get a
            # "served" hop without the continuous load threads
            _serve_http_load(port, xs, 6, 2, rows_per_req)
            if nsrv is not None:
                _serve_native_load(nsrv.port, xs, 6, 2, rows_per_req)

        # the served hop lands on the gateway's batch thread after the
        # reply fan-out — give the last burst's note a bounded window
        spike = []
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            spike = [r.get("propagation_s") for r in tracker.rounds()
                     if r["round"] in chaos_rounds
                     and "propagation_s" in r]
            if len(spike) == len(chaos_rounds):
                break
            time.sleep(0.1)
        out["failover_propagation_s"] = spike
        out["propagation_spike_bounded"] = bool(
            len(spike) == len(chaos_rounds)
            and max(spike) < 15.0)

        # the death must surface as a NAMED transition in the document
        named_death = None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            doc = sched.fleetscope.document() or {}
            named_death = next(
                (t for t in doc.get("transitions", [])
                 if t.get("node") == "serve:registry"
                 and t.get("to") == "dead"), None)
            if named_death is not None:
                break
            time.sleep(0.2)
        out["death_transition"] = named_death
        out["death_named"] = bool(named_death is not None)

        # degradation: the dead registry is MARKED, every healthy node
        # keeps folding and the document keeps versioning
        doc = sched.fleetscope.document() or {}
        nodes = doc.get("nodes", {})
        out["degrade_ok"] = bool(
            nodes.get("serve:registry", {}).get("health") == "dead"
            and nodes.get("serve:gateway", {}).get("health") == "ok"
            and doc.get("rollups", {}).get("nodes_dead", 0) >= 1
            and int(doc.get("fleet_version", 0)) > version_a)
        out["fleet_document_version"] = int(doc.get("fleet_version", 0))

        # ---- phase C: seeded burn-rate determinism ----------------------
        run1, n1 = _fleetscope_burn_series(seed)
        run2, n2 = _fleetscope_burn_series(seed)
        out["burn"] = {"breaches": n1,
                       "deterministic": bool(run1 == run2 and n1 == n2)}
        out["burn_breached"] = bool(n1 >= 1)
        out["burn_deterministic"] = bool(out["burn"]["deterministic"])

        # ---- artifacts: fleet document + gxtop rendering ----------------
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fleet_path = os.path.join(out_dir, "fleetscope_fleet.json")
            with open(fleet_path, "w") as f:
                json.dump(doc, f, indent=2, default=str)
            import importlib.util
            gx_spec = importlib.util.spec_from_file_location(
                "gxtop", os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "tools", "gxtop.py"))
            gxtop = importlib.util.module_from_spec(gx_spec)
            gx_spec.loader.exec_module(gxtop)
            rendered = gxtop.render(doc)
            with open(os.path.join(out_dir,
                                   "fleetscope_gxtop.txt"), "w") as f:
                f.write(rendered + "\n")
            out["gxtop_renders"] = bool("serve:gateway" in rendered)
        else:
            out["gxtop_renders"] = True
    finally:
        for c in (trainer2, replica_cli2, trainer, replica_cli,
                  gw_client):
            if c is not None:
                try:
                    c.close()
                except Exception:
                    pass
        if failover is not None:
            failover.stop()
            failover.join(5.0)
        if nsrv is not None:
            nsrv.stop()
        httpd.shutdown()
        gw.stop()
        srv.stop()
        srv.join(5.0)
        sched.stop()
        os.environ.pop("GEOMX_FLEETSCOPE", None)
        os.environ.pop("GEOMX_FLEETSCOPE_INTERVAL_S", None)

    out["propagation_p50_s"] = round(prop["p50_s"], 6)
    out["propagation_p99_s"] = round(prop["p99_s"], 6)
    out["elapsed_s"] = round(time.time() - t_bench0, 3)
    out["ok"] = bool(
        out.get("fleetscope_armed") and out.get("fleet_route_ok")
        and out.get("propagation_measured")
        and out.get("propagation_both_transports")
        and out.get("death_named")
        and out.get("propagation_spike_bounded")
        and out.get("degrade_ok")
        and out.get("burn_breached") and out.get("burn_deterministic")
        and out.get("gxtop_renders"))
    if out_dir:
        with open(os.path.join(out_dir,
                               "fleetscope_record.json"), "w") as f:
            json.dump(out, f, indent=2, default=str)
        out["artifacts"] = {"out_dir": out_dir}
    return out


def compare_fleetscope_main(argv):
    kwargs = {}
    for a in argv:
        if a.startswith("--rounds="):
            kwargs["rounds"] = int(a.split("=", 1)[1])
        elif a.startswith("--clients="):
            kwargs["clients"] = int(a.split("=", 1)[1])
        elif a.startswith("--max-batch="):
            kwargs["max_batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--queue-ms="):
            kwargs["queue_ms"] = float(a.split("=", 1)[1])
        elif a.startswith("--delta-frac="):
            kwargs["delta_frac"] = float(a.split("=", 1)[1])
        elif a.startswith("--seed="):
            kwargs["seed"] = int(a.split("=", 1)[1])
        elif a.startswith("--out-dir="):
            kwargs["out_dir"] = a.split("=", 1)[1]
    _emit(_compare_fleetscope(**kwargs))


def main():
    if "--audit" in sys.argv:
        # static-analysis acceptance smoke: in-process on the CPU
        # backend with a 4-device virtual mesh (env before first
        # import) — the scatter_wire_lie corpus entry needs a 4-wide
        # axis for the (N-1)/N accounting gap to be visible
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        audit_main(sys.argv[1:])
    elif "--attribute" in sys.argv:
        # step-time observatory acceptance: in-process on the CPU
        # backend with the 2x4 virtual mesh (8 devices, env before the
        # first jax import) — same mesh the MULTICHIP matrix uses
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        attribute_main(sys.argv[1:])
    elif "--compare-telemetry" in sys.argv:
        # telemetry acceptance micro-mode: in-process on the CPU backend
        # with a 2-device virtual mesh (env before the first jax import)
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()
        compare_telemetry_main(sys.argv[1:])
    elif "--compare-control" in sys.argv:
        # Graft Pilot acceptance replay: in-process on the CPU backend
        # with a 3-device virtual mesh (3 parties — relay re-forming
        # needs a third party to route around the degraded one)
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=3").strip()
        compare_control_main(sys.argv[1:])
    elif "--compare-capsule" in sys.argv:
        # run-capsule acceptance: whole-run capture + bit-exact offline
        # replay + fitted cost model, on the --compare-control 3-party
        # CPU mesh (3 devices, env before the first jax import)
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=3").strip()
        compare_capsule_main(sys.argv[1:])
    elif "--compare-recovery" in sys.argv:
        # host-plane recovery acceptance: pure service-plane (sockets +
        # numpy), no jax mesh — runs anywhere in seconds
        compare_recovery_main(sys.argv[1:])
    elif "--compare-sparseagg" in sys.argv:
        # compressed-domain aggregation acceptance: in-process on the
        # CPU backend, 4 virtual devices — the training/parity meshes
        # use 3 (the multi-party topology the ISSUE's perf gate names);
        # the corpus replay's scatter_wire_lie entry needs a 4-wide axis
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        compare_sparseagg_main(sys.argv[1:])
    elif "--compare-mfu" in sys.argv:
        # compute-phase engine acceptance: in-process on the CPU
        # backend with the 2x4 virtual mesh (8 devices, env before the
        # first jax import) — the fused-optimizer DCE section
        # cross-lowers the step for TPU, it never executes it
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        compare_mfu_main(sys.argv[1:])
    elif "--serve" in sys.argv:
        # serving-plane acceptance: host-plane registry/gateway plus a
        # single-device jit'd forward — CPU backend, no mesh needed
        # (env before the first jax import)
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        compare_serve_main(sys.argv[1:])
    elif "--fleetscope" in sys.argv:
        # fleet-wide observability acceptance: the --serve topology
        # joined to a scheduler roster with the FleetScope aggregator
        # colocated — same single-device CPU forward, no mesh
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        compare_fleetscope_main(sys.argv[1:])
    elif "--compare-manyparty" in sys.argv:
        # many-party sharded-global-tier acceptance: pure service-plane
        # (sockets + numpy, 16+ worker threads), no jax mesh
        compare_manyparty_main(sys.argv[1:])
    elif "--compare-fleetobs" in sys.argv:
        # fleet round ledger acceptance (docs/telemetry.md "Round
        # ledger"): pure service-plane chaos run, no jax mesh
        compare_fleetobs_main(sys.argv[1:])
    elif "--compare-resilience" in sys.argv:
        # chaos/structure micro-mode like --compare-pipeline: in-process
        # on the CPU backend with a 2-device virtual mesh
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()
        compare_resilience_main(sys.argv[1:])
    elif "--compare-zero" in sys.argv:
        # ZeRO sharded-update micro-mode: a 2x4 virtual mesh (8 CPU
        # devices).  The measurement runs in a watchdog-watched child
        # (parent half of compare_zero_main), so a wedged backend init
        # publishes watchdog.phase forensics instead of burning the
        # budget silently
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        compare_zero_main(sys.argv[1:])
    elif "--compare-pipeline" in sys.argv:
        # accounting/structure micro-mode like --compare-bucketing:
        # in-process on the CPU backend with a 2-device virtual mesh
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()
        compare_pipeline_main(sys.argv[1:])
    elif "--compare-bucketing" in sys.argv:
        # accounting micro-mode, not a perf mode: runs in-process on the
        # CPU backend with a 2-device virtual mesh (env must be set
        # before the first jax import — bench.py imports jax lazily)
        os.environ.setdefault("JAX_PLATFORMS",
                              os.environ.get("GEOMX_BENCH_PLATFORM", "cpu"))
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()
        compare_bucketing_main(sys.argv[1:])
    elif os.environ.get("GEOMX_BENCH_CHILD") == "1":
        child_main()
    else:
        sys.exit(parent_main())


if __name__ == "__main__":
    main()
