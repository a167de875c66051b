#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of the flagship (ResNet-20 for CIFAR-10: 3x3
stages of 16/32/64 channels, 272,474 parameters) and a per-chip
batch of 2,048, on seeded synthetic CIFAR-shaped uint8 data
asked for by name.  It checks what comes out and fails on the first
phase that is wrong; there is no fallback anywhere in it.

    python chip_smoke.py              # one chip: device, kernels, train, fit, serve
    python chip_smoke.py --chips 4    # four chips: ONLY the two-tier mesh check

Every phase prints one JSON object on its own line.  The last line of
stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only when every phase passed on a TPU.  When JAX finds no
TPU (``JAX_PLATFORMS=cpu`` here in the sandbox) the script exits
non-zero before any phase and prints no result.  Wall times in the
output are smoke timings — how long this script waited — not metrics.

Phases (one chip):

- ``device``   what JAX reports, the versions, the compile-cache
               directory, and whether the native host runtime was built
               from ``native/*.cpp`` or the pure-Python path runs;
- ``kernels``  every Pallas kernel of the compression/optimizer path run
               natively at ResNet-20 size against its jnp reference
               (bitwise where the interpret-mode tests say bitwise);
- ``train``    the paper's five configs on a 1x1 topology through
               GeoConfig + get_sync_algorithm + Trainer: one warm-up
               step, five steps closed by block_until_ready, finite loss
               that is lower on a fixed batch afterwards, changed
               parameters, ``tpu_custom_call`` in the lowered step of
               every config that reaches a fused kernel, and zero
               compilations after warm-up;
- ``fit``      20 steps of Trainer.fit with the loader and prefetch on,
               then Trainer.evaluate — the host loop's non-CPU branch;
- ``serve``    one gateway with one in-process replica answers 8
               requests on the native lane, each equal to model.apply —
               the gateway's donating non-CPU branch.

``--chips 4`` runs none of those.  It runs ResNet-20 at per-chip batch
2,048 on 2 parties x 2 workers under FSA against the flat 1 x 4 golden
run on the same global batches (the identity
``__graft_entry__.dryrun_multichip`` asserts on the CPU), then Bi-Sparse
on 2 x 2, and prints where every array of the train state and batch
lives and which collectives the compiler emitted.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

FLAGSHIP_PARAMS = 272_474
PER_CHIP_BATCH = 2048
# the paper's five (BASELINE.json's configs) as GeoConfig overrides.
# hfa_dgt: 3 deferral channels with k=0.5 (the reference's
# scripts/cpu/run_dgt.sh runs DMLC_UDP_CHANNEL_NUM=3), so that two steps
# in three move the top half of the blocks and the third drains
CONFIGS = {
    "vanilla_local": {"sync_mode": "fsa", "compression": "none"},
    "dist_sync_hips": {"sync_mode": "fsa", "compression": "none"},
    "bsc": {"sync_mode": "fsa", "compression": "bsc,0.01"},
    "fp16_mpq": {"sync_mode": "fsa", "compression": "mpq,0.01"},
    "hfa_dgt": {"sync_mode": "hfa", "hfa_k1": 20, "hfa_k2": 10,
                "enable_dgt": 2, "udp_channel_num": 3, "dgt_k": 0.5,
                "compression": "none"},
}
FIVE = tuple(CONFIGS)
# those whose dc tier runs the fused bucket/BSC kernels on a TPU
# (hfa_dgt's tree-level DGT fuses the gradient tree itself)
FUSED_CONFIGS = ("vanilla_local", "dist_sync_hips", "bsc", "fp16_mpq")


def config_overrides(name: str) -> dict:
    """The GeoConfig overrides of one of the five configs (a copy)."""
    return dict(CONFIGS[name])


class SmokeFailure(AssertionError):
    """A phase produced something wrong.  Never caught: the script dies
    on the first one."""


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(phase: str, **fields) -> dict:
    rec = {"phase": phase, **fields}
    print(json.dumps(rec), flush=True)
    return rec


def flagship():
    from geomx_tpu.models import ResNet20
    return ResNet20(num_classes=10)


def fingerprint(tree) -> float:
    """Sum of |x| over copy (0, 0) of every leaf, in float64 — the
    scalar ``dryrun_multichip`` compares trajectories by."""
    import jax
    # fetched whole and indexed on the host: no device program, so
    # nothing here can count as a compilation
    return float(sum(np.abs(np.asarray(leaf)[0, 0].astype(np.float64)).sum()
                     for leaf in jax.tree.leaves(jax.device_get(tree))))


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    """Fails unless JAX's first device is a TPU and at least ``chips``
    are there."""
    import jax
    import jaxlib

    devs = jax.devices()
    require(devs[0].platform == "tpu",
            f"JAX found no TPU: platform is {devs[0].platform!r} — "
            "nothing to smoke, and nothing falls back")
    require(len(devs) >= chips,
            f"--chips {chips} needs {chips} devices, JAX reports {len(devs)}")
    # one table, exact device_kind: an unknown chip is an error here too
    from geomx_tpu.telemetry.roofline import device_peaks
    device_peaks(devs[0].device_kind)

    from geomx_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()

    # the host runtime is built from what git commits (native/*.cpp);
    # a host with no toolchain runs the documented pure-Python paths
    from geomx_tpu.runtime import build_native, load_native
    built = build_native()
    require(not built or load_native(build=False) is not None,
            "native/libgeops.so was built but does not bind")

    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a version string, no more
        libtpu = "not a pip package here"
    return emit(
        "device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        native_runtime=("built from native/*.cpp" if built
                        else "pure-Python path (no toolchain)"))


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def kernels_phase(seed: int, interpret: bool = False, model=None) -> dict:
    """Each Pallas kernel against its jnp reference at the flagship's
    size (``model``: a smaller stand-in for the CPU tests).  Both sides
    run under jit on the same device."""
    import functools

    import jax
    import jax.numpy as jnp

    from geomx_tpu.compression import BiSparseCompressor
    from geomx_tpu.compression.bucketing import GradientBucketer
    from geomx_tpu.ops import (dequantize_2bit, fused_adam,
                               fused_sgd_momentum, quantize_2bit)
    from geomx_tpu.ops.bsc_pallas import (bsc_sampled_boundary,
                                          bsc_scatter_add, bsc_select_pack,
                                          sampled_boundary_guv,
                                          scatter_add_ref, select_pack_ref)
    from geomx_tpu.ops.bucket_pallas import (flatten_ref, fused_flatten,
                                             fused_unflatten, unflatten_ref)
    from geomx_tpu.ops.merge_pallas import merge_sorted_pairs
    from geomx_tpu.ops.optim_pallas import adam_ref, sgd_momentum_ref

    model = model or flagship()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False))
    rng = np.random.RandomState(seed)
    leaves = [jnp.asarray(rng.normal(0, 1, leaf.shape).astype(np.float32))
              for leaf in jax.tree.leaves(shapes["params"])]
    checked = {}

    def same(name, got, want):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            require(np.array_equal(np.asarray(a), np.asarray(b)),
                    f"kernel {name}: output {i} is not bit-identical to "
                    "its jnp reference")
        checked[name] = "bitwise"

    def close(name, got, want, rtol, atol):
        for i, (a, b) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            require(np.allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                atol=atol),
                    f"kernel {name}: output {i} differs from its jnp "
                    f"reference beyond rtol={rtol} atol={atol}")
        checked[name] = f"rtol={rtol} atol={atol}"

    # bucket (un)flatten: a pure permutation
    bk = GradientBucketer(leaves)
    flat = [leaf.reshape(-1) for leaf in leaves]
    layout, sizes = bk._layout(), tuple(bk.bucket_sizes)
    leaf_sizes = tuple(bk.leaf_sizes)
    buckets = jax.jit(lambda *ls: flatten_ref(ls, layout, sizes))(*flat)
    same("fused_flatten",
         fused_flatten(flat, layout, sizes, interpret=interpret), buckets)
    same("fused_unflatten",
         fused_unflatten(buckets, layout, leaf_sizes, interpret=interpret),
         jax.jit(lambda *bs: unflatten_ref(bs, layout, leaf_sizes))(*buckets))

    # Bi-Sparse select/pack on the flagship's one bucket, two "parties"
    g = buckets[0]
    n = int(g.shape[0])
    k = BiSparseCompressor(0.01).k_for(n)
    u = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 0.2, n).astype(np.float32))
    g2 = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))

    # the boundary both compress with: the streamed probe (what the
    # engine's door takes at this size on a TPU) against the gathers
    def boundary_with(probe):
        return jax.jit(lambda g, u, v: probe(g, u, v, k))

    for name, grad in (("bsc_sampled_boundary", g),
                       ("bsc_sampled_boundary/2", g2)):
        same(name,
             boundary_with(functools.partial(
                 bsc_sampled_boundary, interpret=interpret))(grad, u, v),
             boundary_with(sampled_boundary_guv)(grad, u, v))

    def compress_with(select):
        return jax.jit(lambda g, u, v: select(
            g, u, v, sampled_boundary_guv(g, u, v, k), k))

    compress_ref = compress_with(select_pack_ref)
    compress_fused = compress_with(functools.partial(
        bsc_select_pack, interpret=interpret))
    sel = compress_fused(g, u, v)
    same("bsc_select_pack", sel, compress_ref(g, u, v))
    sel2 = compress_fused(g2, u, v)
    same("bsc_select_pack/2", sel2, compress_ref(g2, u, v))
    require(int((np.asarray(sel[1]) >= 0).sum()) > k // 2,
            "bsc_select_pack emitted fewer than k/2 real pairs on "
            "gaussian input")

    # decompress: bitwise without collisions, to rounding with them
    def dec_ref(a, b):
        return scatter_add_ref(a, b, n)

    def dec_fused(a, b):
        return bsc_scatter_add(a, b, n, interpret=interpret)

    same("bsc_scatter_add", dec_fused(sel[0], sel[1]),
         jax.jit(dec_ref)(sel[0], sel[1]))
    all_vals = jnp.concatenate([sel[0], sel2[0]])
    all_idx = jnp.concatenate([sel[1], sel2[1]])
    close("bsc_scatter_add/2 parties", dec_fused(all_vals, all_idx),
          jax.jit(dec_ref)(all_vals, all_idx), rtol=0.0, atol=1e-5)

    # compressed-domain merge of the same two parties' pairs
    same("merge_tree",
         jax.jit(lambda a, b: merge_sorted_pairs(
             a, b, 2, fused=True, interpret=interpret))(all_vals, all_idx),
         jax.jit(lambda a, b: merge_sorted_pairs(a, b, 2))(all_vals,
                                                           all_idx))

    # 2-bit quantize: the threshold rule and error-feedback mass
    thr = 0.5
    r = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    packed, new_r = quantize_2bit(g, r, thr, interpret=interpret)
    deq = dequantize_2bit(packed, n, thr, interpret=interpret)
    acc = np.asarray(g) + np.asarray(r)
    close("quantize_2bit", deq,
          np.where(acc >= thr, thr, np.where(acc <= -thr, -thr, 0.0)),
          rtol=0.0, atol=1e-6)
    close("dequantize_2bit", np.asarray(deq) + np.asarray(new_r), acc,
          rtol=0.0, atol=1e-5)

    # fused optimizers: the documented contract is moments bitwise and
    # params to one rounding of the final multiply-subtract
    m = jnp.asarray(rng.normal(0, 0.1, n).astype(np.float32))
    v2 = jnp.asarray(np.abs(rng.normal(0, 0.1, n)).astype(np.float32))
    sgd = dict(lr=0.1, momentum=0.9)
    new_p, new_m = fused_sgd_momentum(g2, g, m, interpret=interpret, **sgd)
    ref_p, ref_m = jax.jit(
        lambda p, gg, mm: sgd_momentum_ref(p, gg, mm, **sgd))(g2, g, m)
    same("fused_sgd_momentum/moment", new_m, ref_m)
    close("fused_sgd_momentum/params", new_p, ref_p, rtol=1e-6, atol=1e-8)
    adam = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8)
    bc1, bc2 = jnp.float32(1 - 0.9 ** 3), jnp.float32(1 - 0.999 ** 3)
    got = fused_adam(g2, g, m, v2, bc1, bc2, interpret=interpret, **adam)
    want = jax.jit(lambda p, gg, mm, vv, a, b: adam_ref(
        p, gg, mm, vv, a, b, **adam))(g2, g, m, v2, bc1, bc2)
    same("fused_adam/moments", got[1:], want[1:])
    close("fused_adam/params", got[0], want[0], rtol=1e-6, atol=1e-8)

    # an expert pool's rows back into the token array: 1,024 places in
    # two tiles, the second half full; a token sits in up to three runs,
    # so the segments meet a row again (small whole numbers: exact in any
    # order)
    from geomx_tpu.ops.moe_rows_pallas import (moe_row_scatter_add,
                                               row_scatter_add_ref)
    tokens, wide, places = 512, 256, 1024
    runs = np.asarray([300, 0, 412, 56], np.int32)
    valid = int(runs.sum())
    place_token = jnp.asarray(np.concatenate(
        [rng.permutation(tokens)[:size] for size in runs]
        + [tokens + np.arange(places - valid)]).astype(np.int32))
    y0 = jnp.asarray(rng.randint(-8, 9, (tokens, wide)).astype(np.float32))
    addends = jnp.asarray(np.where(
        np.arange(places)[:, None] < valid,
        rng.randint(-8, 9, (places, wide)), 0).astype(np.float32))
    same("moe_row_scatter_add",
         moe_row_scatter_add(y0, addends, place_token, jnp.asarray(runs),
                             interpret=interpret),
         jax.jit(row_scatter_add_ref)(y0, addends, place_token, runs))

    # a grouped-query mixer's norm + rotary pass: 8 heads on 2 of 128, 300
    # tokens (a ragged second tile), bf16; XLA elides the jnp form's round
    # trip through bf16 between norm and rotary and the kernel keeps it:
    # the last place of bf16 may differ
    from geomx_tpu.ops import gqa_elementwise as ge
    wide = lambda heads: jnp.asarray(rng.normal(
        0, 1, (1, 300, heads, 128)).astype(np.float32)).astype(jnp.bfloat16)
    scales = [jnp.asarray(1 + 0.1 * rng.normal(0, 1, 128), jnp.float32)
              for _ in range(2)]
    qk = wide(8), wide(2)
    close("gqa_norm_rotary",
          [a.astype(jnp.float32) for a in ge.norm_rotary(
              *qk, *scales, 1e-5, 10000.0, interpret)],
          [a.astype(jnp.float32) for a in jax.jit(
              lambda *a: ge.norm_rotary_ref(*a, 1e-5, 10000.0))(
                  *qk, *scales)], rtol=0.0, atol=0.04)

    return emit("kernels", native=not interpret, elements=n, k=k,
                leaves=len(leaves), checked=checked)


# --------------------------------------------------------------------------
# train: the five configs on one chip
# --------------------------------------------------------------------------

def _batches(data, parties: int, workers: int, local_batch: int, count: int):
    """``count`` global batches [P, W, b, ...], cycling through the set."""
    x, y = data["train_x"], data["train_y"]
    per = parties * workers * local_batch
    require(len(x) >= per, f"dataset of {len(x)} < one global batch {per}")
    out = []
    for i in range(count):
        lo = (i * per) % (len(x) - per + 1)
        out.append((x[lo:lo + per].reshape(parties, workers, local_batch,
                                           *x.shape[1:]),
                    y[lo:lo + per].reshape(parties, workers, local_batch)))
    return out


def _build_trainer(overrides: dict, parties: int, workers: int, model=None):
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    cfg = GeoConfig.from_env(num_parties=parties, workers_per_party=workers,
                             **overrides)
    topo = HiPSTopology(num_parties=parties, workers_per_party=workers)
    return Trainer(model or flagship(), topo,
                   optax.adam(3e-3),
                   sync=get_sync_algorithm(cfg), config=cfg)


def _run_steps(trainer, state, batches, counter):
    """Warm-up on batch 0, the middle batches closed by
    block_until_ready, then batch 0 again as the fixed-batch probe.
    Returns (state, record)."""
    import jax

    sharding = trainer.topology.batch_sharding(trainer.mesh)
    put = [(jax.device_put(x, sharding), jax.device_put(y, sharding))
           for x, y in batches]
    lowered = trainer.train_step.lower(state, *put[0]).as_text()
    before = fingerprint(state.params)

    t0 = time.perf_counter()
    state, metrics = trainer.train_step(state, *put[0])
    losses = [float(metrics["loss"])]
    warm_s = time.perf_counter() - t0
    compiles_at_warm = counter.compiles

    t0 = time.perf_counter()
    for xb, yb in put[1:]:
        state, metrics = trainer.train_step(state, xb, yb)
        losses.append(metrics["loss"])
    jax.block_until_ready(metrics["loss"])
    steps_s = time.perf_counter() - t0
    losses = [float(v) for v in losses]
    state, metrics = trainer.train_step(state, *put[0])
    losses.append(float(metrics["loss"]))

    rec = {
        "losses": [round(v, 5) for v in losses],
        "loss_fixed_batch": [round(losses[0], 5), round(losses[-1], 5)],
        "params_moved": abs(fingerprint(state.params) - before),
        "tpu_custom_call": "tpu_custom_call" in lowered,
        "compiles_after_warmup": counter.compiles - compiles_at_warm,
        "smoke_compile_and_first_step_s": round(warm_s, 2),
        "smoke_steps_s": round(steps_s, 4),
        "timed_steps": len(put) - 1,
    }
    return state, rec


def _check_steps(name: str, rec: dict) -> None:
    require(all(np.isfinite(rec["losses"])),
            f"{name}: a loss is not finite: {rec['losses']}")
    lo, hi = rec["loss_fixed_batch"][1], rec["loss_fixed_batch"][0]
    require(lo < hi, f"{name}: loss on the fixed batch did not fall "
                     f"({hi} -> {lo})")
    require(rec["params_moved"] > 0, f"{name}: parameters did not change")
    require(rec["compiles_after_warmup"] == 0,
            f"{name}: {rec['compiles_after_warmup']} compilation(s) after "
            "warm-up, expected 0")


def train_phase(data, seed: int, counter, batch: int = PER_CHIP_BATCH,
                steps: int = 5, model=None) -> dict:
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    batches = _batches(data, 1, 1, batch, steps + 1)
    configs = {}
    for name in FIVE:
        trainer = _build_trainer(config_overrides(name), 1, 1, model)
        state = trainer.init_state(jax.random.PRNGKey(seed),
                                   batches[0][0][0, 0, :2])
        _state, rec = _run_steps(trainer, state, batches, counter)
        _check_steps(name, rec)
        if on_tpu and name in FUSED_CONFIGS:
            require(rec["tpu_custom_call"],
                    f"{name}: no tpu_custom_call in the lowered step — the "
                    "fused kernels were not reached")
        configs[name] = rec
    return emit("train", topology="1x1", per_chip_batch=batch,
                note="smoke_* are smoke timings, not metrics",
                configs=configs)


# --------------------------------------------------------------------------
# fit + evaluate: the host loop
# --------------------------------------------------------------------------

def fit_phase(data, seed: int, counter, batch: int = PER_CHIP_BATCH,
              steps: int = 20, model=None):
    """Returns (record, model, variables of copy (0, 0)) — the serve
    phase serves what this phase trained."""
    import jax

    trainer = _build_trainer(config_overrides("vanilla_local"), 1, 1, model)
    n = steps * batch
    require(len(data["train_x"]) >= n,
            f"fit needs {n} samples, the set has {len(data['train_x'])}")
    loader = trainer.make_loader(data["train_x"][:n], data["train_y"][:n],
                                 batch, seed=seed)
    require(loader.steps_per_epoch == steps,
            f"loader yields {loader.steps_per_epoch} steps, wanted {steps}")
    require(trainer.config.prefetch > 0, "the loader's prefetch is off")
    state = trainer.init_state(jax.random.PRNGKey(seed),
                               data["train_x"][:2])
    t0 = time.perf_counter()
    state, records = trainer.fit(state, loader, epochs=1,
                                 log_every=max(1, steps // 4),
                                 log_fn=lambda _line: None)
    jax.block_until_ready(state.step)
    fit_s = time.perf_counter() - t0
    losses = [r["loss"] for r in records if "loss" in r]
    require(int(state.step) == steps,
            f"fit ran {int(state.step)} steps, wanted {steps}")
    require(losses and all(np.isfinite(losses)),
            f"fit logged no finite loss: {losses}")
    acc = trainer.evaluate(state, data["test_x"], data["test_y"])
    require(0.0 <= acc <= 1.0, f"evaluate returned {acc}")
    rec = emit("fit", steps=steps, per_chip_batch=batch,
               prefetch=trainer.config.prefetch,
               logged_losses=[round(v, 5) for v in losses],
               test_acc=round(float(acc), 4), test_n=len(data["test_x"]),
               smoke_fit_s=round(fit_s, 2))
    variables = {
        "params": jax.tree.map(lambda a: np.asarray(a[0, 0]), state.params),
        **jax.tree.map(lambda a: np.asarray(a[0, 0]), state.model_state)}
    return rec, trainer.model, variables


# --------------------------------------------------------------------------
# serve: gateway + one in-process replica, native lane
# --------------------------------------------------------------------------

def serve_phase(model, variables, data, requests: int = 8,
                model_name: str = "resnet20") -> dict:
    import jax

    from geomx_tpu.serve.gateway import InferenceGateway, flatten_params
    from geomx_tpu.serve.infer_wire import (NativeInferenceClient,
                                            NativeInferenceServer)
    from geomx_tpu.serve.replica import ServingReplica

    named, treedef = flatten_params(variables)
    replica = ServingReplica("smoke")
    for order, (name, arr) in enumerate(named.items()):
        replica.install_base(name, arr, order=order)
    x = (data["test_x"][:requests].astype(np.float32) / 255.0)
    feat = tuple(x.shape[1:])
    # one bucket: every batch pads to `requests`, one executable
    gateway = InferenceGateway(replica, treedef, model_name=model_name,
                               num_classes=10, max_batch=requests,
                               buckets=(requests,), queue_ms=5.0,
                               warmup_shapes=[feat], warmup=True)
    want = np.asarray(jax.jit(
        lambda vs, xb: model.apply(vs, xb, train=False))(variables, x))
    gateway.start()
    server = NativeInferenceServer(gateway, port=0).start()
    client = NativeInferenceClient(("127.0.0.1", server.port),
                                   timeout_s=120.0)
    try:
        outs = []
        for lo in range(0, requests, 2):   # persistent lane, 2 rows a frame
            reply = client.infer(x[lo:lo + 2])
            require("error" not in reply, f"native lane error: {reply}")
            outs.append(np.asarray(reply["outputs"]))
    finally:
        client.close()
        server.stop()
        gateway.stop()
    got = np.concatenate(outs)
    require(got.shape == want.shape,
            f"served {got.shape}, model.apply gives {want.shape}")
    require(np.all(np.isfinite(got)), "a served logit is not finite")
    err = float(np.max(np.abs(got - want)))
    require(np.allclose(got, want, rtol=1e-4, atol=1e-4),
            f"served logits differ from model.apply by {err}")
    require(gateway.requests_ok == requests,
            f"gateway counted {gateway.requests_ok} ok of {requests}")
    return emit("serve", requests=requests, lane="native",
                donated_input=jax.default_backend() != "cpu",
                warmup_compiles=gateway.warmup_compiles,
                batches=gateway.batches_dispatched,
                max_abs_err_vs_model_apply=err)


# --------------------------------------------------------------------------
# --chips 4: the two-tier mesh, and nothing else
# --------------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute")


def _placement(trainer, state, xb, yb, want: int) -> dict:
    """Where every array lives; fails on a replica-axes leaf that sits
    on fewer than ``want`` devices."""
    import jax

    def ids(arr):
        return sorted(d.id for d in arr.sharding.device_set)

    table = {}
    named = {"params": state.params, "opt_state": state.opt_state,
             "model_state": state.model_state,
             "sync_state": state.sync_state, "batch": (xb, yb)}
    for group, tree in named.items():
        sets = [ids(leaf) for leaf in jax.tree.leaves(tree)]
        for leaf, s in zip(jax.tree.leaves(tree), sets):
            require(len(s) >= want,
                    f"{group}: a leaf of shape {leaf.shape} lives on "
                    f"{len(s)} device(s) {s}, wanted {want}")
        table[group] = {"leaves": len(sets),
                        "device_sets": sorted({tuple(s) for s in sets})}
    table["step"] = {"leaves": 1, "device_sets": [tuple(ids(state.step))]}
    table["mesh"] = {
        f"dc={p},worker={w}": {"id": d.id,
                               "coords": getattr(d, "coords", None)}
        for (p, w), d in np.ndenumerate(trainer.mesh.devices)}
    return table


def multichip_phase(data, seed: int, counter, batch: int = PER_CHIP_BATCH,
                    fsa_steps: int = 3, bsc_steps: int = 5,
                    model=None) -> dict:
    import jax

    chips = 4
    fsa = config_overrides("dist_sync_hips")
    bsc = config_overrides("bsc")
    hier = _batches(data, 2, 2, batch, max(fsa_steps, bsc_steps + 1))

    def run_fsa(parties, workers):
        trainer = _build_trainer(fsa, parties, workers, model)
        sharding = trainer.topology.batch_sharding(trainer.mesh)
        state = trainer.init_state(jax.random.PRNGKey(seed),
                                   hier[0][0][0, 0, :2])
        place = text = None
        for x, y in hier[:fsa_steps]:
            # the SAME global batch, laid out for this topology
            xb = jax.device_put(
                x.reshape(parties, workers, batch, *x.shape[3:]), sharding)
            yb = jax.device_put(y.reshape(parties, workers, batch), sharding)
            if place is None:
                place = _placement(trainer, state, xb, yb, chips)
                text = trainer.train_step.lower(state, xb,
                                                yb).compile().as_text()
            state, metrics = trainer.train_step(state, xb, yb)
            jax.block_until_ready(metrics["loss"])
        require(np.isfinite(float(metrics["loss"])), "fsa loss not finite")
        return (fingerprint(state.params), float(metrics["loss"]), place,
                sorted(c for c in _COLLECTIVES if c in text))

    fp_hier, loss_hier, place, coll_hier = run_fsa(2, 2)
    fp_flat, loss_flat, _place, coll_flat = run_fsa(1, 4)
    gap = abs(fp_hier - fp_flat)
    # the tolerance __graft_entry__.dryrun_multichip uses
    require(gap < 1e-3 * max(1.0, fp_hier),
            f"hierarchical 2x2 FSA diverged from the flat 1x4 golden run: "
            f"fingerprints {fp_hier} vs {fp_flat}")
    require(coll_hier, "the 2x2 step compiled with no collective at all")

    trainer = _build_trainer(bsc, 2, 2, model)
    sharding = trainer.topology.batch_sharding(trainer.mesh)
    state = trainer.init_state(jax.random.PRNGKey(seed),
                               hier[0][0][0, 0, :2])
    xb, yb = (jax.device_put(a, sharding) for a in hier[0])
    place_bsc = _placement(trainer, state, xb, yb, chips)
    text = trainer.train_step.lower(state, xb, yb).compile().as_text()
    coll_bsc = sorted(c for c in _COLLECTIVES if c in text)
    require(coll_bsc, "the bsc 2x2 step compiled with no collective at all")
    _state, rec = _run_steps(trainer, state, hier[:bsc_steps + 1], counter)
    _check_steps("bsc 2x2", rec)
    if jax.devices()[0].platform == "tpu":
        require(rec["tpu_custom_call"], "bsc 2x2: no tpu_custom_call")

    return emit(
        "multichip", per_chip_batch=batch, global_batch=chips * batch,
        fsa={"steps": fsa_steps, "fingerprint_2x2": fp_hier,
             "fingerprint_1x4": fp_flat, "gap": gap,
             "tolerance": 1e-3 * max(1.0, fp_hier),
             "loss_2x2": loss_hier, "loss_1x4": loss_flat,
             "collectives_2x2": coll_hier, "collectives_1x4": coll_flat},
        bsc_2x2={**rec, "collectives": coll_bsc},
        placement={"fsa_2x2": place, "bsc_2x2": place_bsc},
        note="smoke_* are smoke timings, not metrics")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the two-tier mesh check on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the data and the weights")
    args = ap.parse_args(argv)

    import jax

    from geomx_tpu.data import load_dataset

    device_phase(args.chips)
    # what XLA was asked to compile and what the persistent cache
    # answered: the program's own record (telemetry/layers.CompileLog)
    from geomx_tpu.telemetry.layers import compile_log
    counter = compile_log()
    t0 = time.perf_counter()
    # six global batches for the mesh check, the 20 fit steps otherwise
    samples = (6 * 4 if args.chips == 4 else 20) * PER_CHIP_BATCH
    data = load_dataset("synthetic", seed=args.seed,
                        synthetic_train_n=samples)
    require(data["synthetic"] and data["shape"] == (32, 32, 3),
            "asked for the synthetic set by name, got something else")
    if args.chips == 4:
        multichip_phase(data, args.seed, counter)
    else:
        kernels_phase(args.seed)
        train_phase(data, args.seed, counter)
        _rec, model, variables = fit_phase(data, args.seed, counter)
        params = sum(int(np.size(leaf)) for leaf in
                     jax.tree.leaves(variables["params"]))
        require(params == FLAGSHIP_PARAMS,
                f"the flagship has {params} parameters, not "
                f"{FLAGSHIP_PARAMS}: not the full-width model")
        serve_phase(model, variables, data)
    emit("summary", smoke_total_s=round(time.perf_counter() - t0, 1),
         **counter.snapshot())

    devs = jax.devices()
    print(json.dumps({"ok": True,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
