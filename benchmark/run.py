"""Runs one cell of the chip benchmark.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object the driver reads
(its last key, `checks`, holds every number compared beside its limit, and
the same are standard error's last lines); earlier lines carry the itemised set-up, every segment's rate, the host
loop's counters of the window (`LOOP_STATS`), every number compared
beside its limit, and the time the check took.

Order of one run (PERF.md, section 2):

1. set-up: imports, backend, `Trainer`, weights made on the device from
   the seed, seeded data, then the cell's first `n_check` steps driven
   through `Trainer.fit` (loader, prefetch, `train_step`) one step a call;
   the first of them compiles the step program or loads it from the cache;
2. the window: the same trainer and state go on through one
   `Trainer.fit(log_every=K, log_fn=<stamp>)`; the window is the whole
   segments that end inside `--seconds`;
3. peak memory is read, the trainer's state is dropped, and the plain
   reference follows the first steps from the same weights and rows.

With `--trace 1`, between 1 and 2, a `fit` of its own over the cell's
`trace_segments` runs under the profiler and hands its state to the
window: the device's numbers come from that trace, the rates from the
untraced window.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, estimator  # noqa: E402
from benchmark.cells import Registry  # noqa: E402


class WindowClosed(Exception):
    """Raised by the benchmark's `log_fn` to leave `Trainer.fit`."""


class Clock:
    """Itemised set-up: seconds since the previous mark, by name."""

    def __init__(self, start: float):
        self.start = start
        self.last = start
        self.items = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.items.append((name, now - self.last))
        self.last = now


def say(tag: str, payload) -> None:
    print(tag + " " + json.dumps(payload), flush=True)


def configure_compile_cache() -> str:
    """JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR` where the
    machine sets it, else at a fixed path inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".benchmark_cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    # a size limit set from outside (the chip tool's machine sets 192 MiB)
    # evicts BERT-large's step program before the next run can read it
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(chips: int) -> dict:
    """No TPU, too few chips, or a chip the peak table does not list is an
    error: no CPU number is ever printed under a metric's name."""
    import jax
    from benchmark.peaks import device_peaks
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: JAX's platform is "
                         f"{devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devices)}")
    return device_peaks(devices[0].device_kind)


def geo_config(cell: dict):
    """The program's GeoConfig from the cell's data files."""
    from geomx_tpu.config import GeoConfig
    config, traffic = cell["config"], cell["traffic"]
    return GeoConfig(num_parties=traffic["parties"],
                     workers_per_party=traffic["workers"],
                     precision={"bfloat16": "bf16", "float32": "fp32"}[
                         config["precision"]],
                     bucket_bytes=traffic["bucket_bytes"],
                     **traffic["geoconfig"])


def build_trainer(cell: dict):
    import optax
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    config, traffic = cell["config"], cell["traffic"]
    parties, workers = traffic["parties"], traffic["workers"]
    if parties * workers != cell["chips"]:
        raise ValueError(f"{cell['name']}: {parties}x{workers} slots on "
                         f"{cell['chips']} chips")
    geo = geo_config(cell)
    opt = config["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"optimizer {opt['name']!r} is not wired")
    tx = optax.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
    topo = HiPSTopology(num_parties=parties, workers_per_party=workers)
    return Trainer(cell["family"].build_model(config), topo, tx,
                   sync=get_sync_algorithm(geo), config=geo)


def delta_norms(params, start):
    """Per-leaf L2 norm of replica (0, 0) of `params - start`, in one
    program on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    norms = jax.jit(lambda p, q: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a[0, 0] - b[0, 0])))
         for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q))]))
    return np.asarray(norms(params, start), np.float64)


def adam_first_moment(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("the optimizer state holds no first moment")


def first_gradient(cell: dict, state) -> list:
    """The first step's gradient, leaf by leaf on the host, worked out from
    the state one step left behind.  Dense tier: Adam's first moment over
    (1 - b1) is the gradient the optimizer got.  Bi-Sparse tier: what the
    optimizer got is the top 1%, whose share of a small leaf is decided by
    which side of the boundary a few elements fell, so the gradient
    compared is the one the compressor got: what was sent plus what each
    party kept back in its velocity buffer (u = v = g on the first step),
    averaged over parties.  What was sent is held to the rule's own
    guarantees by `bsc_facts`."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import bisparse
    traffic = cell["traffic"]
    b1 = cell["config"]["optimizer"]["b1"]
    mu = jax.tree.leaves(adam_first_moment(state.opt_state))
    sparse = traffic["geoconfig"]["compression"].startswith("bsc")
    layout = bisparse.bucket_layout([int(x[0, 0].size) for x in mu],
                                    traffic["bucket_bytes"])

    @jax.jit
    def gradient(mu_leaves, bucket_states):
        if not sparse:
            return [x[0, 0] / (1.0 - b1) for x in mu_leaves]
        out = []
        for (lo, hi, _n), bucket_state in zip(layout, bucket_states):
            kept = (jnp.mean(bucket_state[1][:, 0], axis=0)
                    if len(bucket_state) else None)
            off = 0
            for leaf in mu_leaves[lo:hi]:
                g = leaf[0, 0] / (1.0 - b1)
                if kept is not None:
                    g = g + kept[off:off + g.size].reshape(g.shape)
                off += g.size
                out.append(g)
        return out

    return jax.device_get(gradient(
        mu, state.sync_state["dc_comp"] if sparse else None))


def bsc_facts(cell: dict, state) -> dict | None:
    """What the Bi-Sparse rule guarantees of the first push, read from the
    state one step left behind: with one party, what the optimizer got is
    that party's own payload.  None where the cell does not compress so."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import bisparse
    traffic = cell["traffic"]
    kind, _, ratio = traffic["geoconfig"]["compression"].partition(",")
    if kind != "bsc":
        return None
    if traffic["parties"] != 1:
        raise ValueError("the optimizer's gradient is one party's push only "
                         "where there is one party")
    b1 = cell["config"]["optimizer"]["b1"]
    mu = jax.tree.leaves(adam_first_moment(state.opt_state))
    bucket_states = state.sync_state["dc_comp"]
    layout = bisparse.bucket_layout([int(x[0, 0].size) for x in mu],
                                    traffic["bucket_bytes"])

    @functools.partial(jax.jit, static_argnames=("n",))
    def bucket(leaves, n):
        flat = jnp.concatenate([x[0, 0].reshape(-1) for x in leaves])
        return jnp.pad(flat, (0, n - flat.shape[0])) / (1.0 - b1)

    totals = {}
    for (lo, hi, n), bucket_state in zip(layout, bucket_states):
        if n < bisparse.MIN_SPARSE:
            continue
        v = bucket_state[1]
        facts = bisparse.payload_facts(
            bucket(mu[lo:hi], n=n), v[0, 0].reshape(-1), float(ratio))
        for key, value in facts.items():
            totals[key] = totals.get(key, 0) + int(value)
    return totals


def initial_state(cell, trainer, seed: int, sample):
    """(state, shapes): `init_state` gives shapes, shardings, optimizer and
    sync state; the weights are made on the device from the seed in one
    jitted call (`benchmark/weights.py`), as the reference's are."""
    import jax
    from benchmark.weights import make_weights, seed_key
    traffic = cell["traffic"]
    state = trainer.init_state(seed_key(seed), sample)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype),
                          state.params)
    state = state.replace(params=make_weights(
        cell["family"], shapes, seed, (traffic["parties"], traffic["workers"]),
        jax.tree.map(lambda a: a.sharding, state.params)))
    jax.block_until_ready(state.params)
    return state, shapes


def first_steps(cell, trainer, state, shapes, x, y, seed: int, clock=None):
    """Drives the first `n_check` steps through `Trainer.fit`, one step a
    call on rows that all differ, and reads what the comparison needs."""
    import jax
    from benchmark.weights import make_weights
    config, traffic = cell["config"], cell["traffic"]
    slots = traffic["parties"] * traffic["workers"]
    rows = slots * config["per_chip_batch"]
    lead = (traffic["parties"], traffic["workers"])
    shardings = jax.tree.map(lambda a: a.sharding, state.params)
    out = {"losses": []}
    for i in range(traffic["n_check"]):
        lo = i * rows
        loader = trainer.make_loader(x[lo:lo + rows], y[lo:lo + rows],
                                     config["per_chip_batch"], seed=seed)
        state, records = trainer.fit(state, loader, epochs=1, log_every=1,
                                     log_fn=lambda _line: None)
        out["losses"].append(
            [r["loss"] for r in records if "loss" in r][-1])
        if clock:
            clock.mark(f"first_step_{i + 1}_through_fit_s")
        if i == 0:
            out["first_grad"] = first_gradient(cell, state)
            out["bsc"] = bsc_facts(cell, state)
            if clock:
                clock.mark("read_first_gradient_s")
    start = make_weights(cell["family"], shapes, seed, lead, shardings)
    out["delta_norms"] = delta_norms(state.params, start)
    del start
    return state, out


def run_reference(cell, shapes, x, y, seed: int, precision: str = "float32"):
    """The plain reference over the same first steps, from the same seed."""
    from benchmark.references.numerics import Numerics
    from benchmark.references.trainer import reference_steps
    from benchmark.weights import make_weights
    config, traffic = cell["config"], cell["traffic"]
    slots = (traffic["parties"], traffic["workers"])
    rows = slots[0] * slots[1] * config["per_chip_batch"]
    batches = []
    for i in range(traffic["n_check"]):
        xs, ys = x[i * rows:(i + 1) * rows], y[i * rows:(i + 1) * rows]
        batches.append((xs.reshape(slots + (-1,) + xs.shape[1:]),
                        ys.reshape(slots + (-1,) + ys.shape[1:])))
    return reference_steps(
        cell["family"].reference_loss(config, Numerics(precision)),
        lambda: make_weights(cell["family"], shapes, seed),
        batches, config["optimizer"], traffic["geoconfig"]["compression"],
        traffic["bucket_bytes"])


def run_window(trainer, state, loader, log_every: int, seconds: float,
               max_segments: int | None = None, devices=()):
    """One `Trainer.fit` with the benchmark's stamping `log_fn`; returns
    (stamps, losses, held) of the whole segments that ended inside the
    window.  `held` is the most bytes a chip held at a stamp, in use plus
    reserved: XLA keeps a loaded program's scratch space reserved, outside
    `bytes_in_use`, and the state lives only inside `fit`."""
    stamps, losses, held = [], [], [0]

    def stamp(line: str) -> None:
        now = time.perf_counter()
        if now - stamps[0] > seconds:
            raise WindowClosed
        stamps.append(now)
        losses.append(json.loads(line)["loss"])
        for d in devices:
            stats = d.memory_stats() or {}
            held[0] = max(held[0], stats.get("bytes_in_use", 0)
                          + stats.get("bytes_reserved", 0))
        if max_segments is not None and len(losses) >= max_segments:
            raise WindowClosed

    stamps.append(time.perf_counter())
    try:
        trainer.fit(state, loader, epochs=10 ** 9, log_every=log_every,
                    log_fn=stamp)
    except WindowClosed:
        pass
    return stamps, losses, held[0]


def traced_segments(cell, trainer, state, x, y, seed: int, trace_dir: str):
    """`--trace 1`: a `Trainer.fit` of its own over `trace_segments` whole
    segments of the window's data, which ends by itself and hands the
    state on to the window.  The profiler starts before it and stops after
    it: starting or stopping the tracer takes some tenths of a second in
    which the host dispatches nothing, and inside a running `fit` the
    device would sit idle meanwhile.  The device's numbers come from this
    trace, the rates from the untraced window that follows (the tracer
    slows the host loop).  Where the segments hold more steps than the
    data, the loader cycles the data as the window's does."""
    import jax
    config, traffic = cell["config"], cell["traffic"]
    batch = config["per_chip_batch"]
    rows = traffic["parties"] * traffic["workers"] * batch
    log_every = cell["workload"]["log_every"]
    steps = cell["workload"]["trace_segments"] * log_every
    per_epoch = min(steps, config["data_steps"])
    if steps % per_epoch:
        raise ValueError(f"{cell['name']}: {steps} traced steps are not "
                         f"whole epochs of {per_epoch}")
    lo = traffic["n_check"] * rows
    loader = trainer.make_loader(x[lo:lo + per_epoch * rows],
                                 y[lo:lo + per_epoch * rows], batch, seed=seed)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # host spans name what the host did in a device gap, but with them on
    # the TPU client's layout change of an image batch on its way to the
    # device (`XlaLinearize`) takes 1.8 s in place of 55 ms and starves the
    # device (PERF.md): such a cell's file sets `trace_host_level` to 0
    options.host_tracer_level = cell["workload"].get("trace_host_level", 1)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        state, _ = trainer.fit(state, loader, epochs=steps // per_epoch,
                               log_every=log_every,
                               log_fn=lambda _line: None)
        jax.block_until_ready(state.params)
    finally:
        jax.profiler.stop_trace()
    return state


def run_cell(reg: Registry, name: str, seed: int, seconds: float,
             trace: bool, rehearse_segments: int | None = None) -> dict:
    """Everything after argument parsing.  `rehearse_segments` (tests):
    skip the look for a chip, close the window after that many segments,
    and report counts and `correct` only."""
    clock = Clock(_T0)
    cell = reg.cell(name)
    config, traffic, workload = cell["config"], cell["traffic"], cell["workload"]
    rehearsal = rehearse_segments is not None

    import jax
    import numpy as np
    cache_dir = configure_compile_cache()
    from benchmark.compile_counter import CompileCounter
    clock.mark("import_s")
    peaks = None if rehearsal else require_chips(cell["chips"])
    devices = jax.devices()[:cell["chips"]]
    counter = CompileCounter()
    clock.mark("backend_start_s")

    trainer = build_trainer(cell)
    slots = traffic["parties"] * traffic["workers"]
    batch = config["per_chip_batch"]
    rows = slots * batch
    n_check, data_steps = traffic["n_check"], config["data_steps"]
    x, y = cell["family"].make_data(config, np.random.default_rng(seed),
                                    rows * (n_check + data_steps))
    clock.mark("build_and_data_s")

    state, shapes = initial_state(cell, trainer, seed, x[:2])
    clock.mark("state_init_s")

    before = counter.snapshot()
    state, program = first_steps(cell, trainer, state, shapes, x, y, seed,
                                 clock)
    after = counter.snapshot()
    clock.mark("read_parameter_change_s")

    loader = trainer.make_loader(x[n_check * rows:], y[n_check * rows:],
                                 batch, seed=seed)
    clock.mark("window_loader_s")
    setup_s = time.perf_counter() - clock.start
    say("SETUP", {"rehearsal": True} if rehearsal else {"setup_s": setup_s, "items": dict(clock.items),
                  "cache_dir": cache_dir,
                  "compiles_in_first_steps": after["compiles"] - before["compiles"],
                  "cache_hits": after["cache_hits"],
                  "cache_misses": after["cache_misses"]})

    log_every = workload["log_every"]
    trace_dir = None
    if trace and not rehearsal:
        trace_dir = os.path.join(ROOT, ".benchmark_cache", "trace", name)
        state = traced_segments(cell, trainer, state, x, y, seed, trace_dir)
    compiles_before = counter.compiles
    stamps, losses, held = run_window(trainer, state, loader, log_every,
                                      seconds, rehearse_segments, devices)
    compiles_in_window = counter.compiles - compiles_before
    # the peak on the fullest chip: the allocator's own high-water mark
    # (set-up included: init_state holds the state twice while it
    # replicates it), or what a chip held during the window, scratch
    # space included, whichever is more
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max([held] + [s.get("peak_bytes_in_use", 0) for s in stats])
    say("MEMORY", {"peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
                   "peak_bytes_reserved": [s.get("peak_bytes_reserved") for s in stats],
                   "held_in_window": held, "reported_peak": peak_bytes})

    del state, loader, trainer
    gc.collect()
    window = estimator.window_summary(stamps, log_every * rows, cell["chips"])
    say("SEGMENTS", {"log_every": log_every, "samples_per_step": rows,
                     "segments": window["segments"],
                     **({} if rehearsal else {
                         "rates_per_chip": window["rates_per_chip"],
                         "window_s": window["window_s"]})})

    # the window's host-loop counters, untraced runs too: a stall gets its
    # phase and step (`phases[p].max_s`, `max_step`)
    from benchmark.layer_metrics import _step_layers
    loop_stats = _step_layers.loop_stats({})

    t_check = time.perf_counter()
    reference = run_reference(cell, shapes, x, y, seed)
    numbers = check.compare(program, reference,
                            workload["first_grad_floor"]["value"])
    numbers["nonfinite_losses"] = float(sum(not math.isfinite(v) for v in losses))
    numbers["compiles_in_window"] = float(compiles_in_window)
    correct, lines = check.verdict(numbers, workload["limits"])
    for line in lines:
        say("CHECK", line)
    say("CHECK_TIME", {"check_s": time.perf_counter() - t_check,
                       "reference_precision": "float32",
                       "steps_followed": n_check})

    attempted = (len(stamps) - 1) * log_every
    failed = int(numbers["nonfinite_losses"]) * log_every
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": device}
    # each number compared beside its limit, in the result's line too, as
    # its last key (a value that is not finite goes as its name: the line
    # stays JSON)
    checks = {line["number"]: {
        "value": line["value"] if math.isfinite(line["value"])
        else repr(line["value"]), "limit": line["limit"]} for line in lines}
    if rehearsal:
        result["segments"] = window["segments"]
        result["checks"] = checks
        return result

    wire = trainer_wire_bytes(cell, shapes)
    context = {
        "cell": cell, "peaks": peaks, "window": window, "program": program,
        "compiles_in_window": compiles_in_window, "shapes": shapes,
        "trace": None, "loop_stats": loop_stats,
    }
    if trace:
        from benchmark import trace_reduce
        context["trace"] = trace_reduce.reduce_trace(trace_dir)
        device["busy_s"] = context["trace"]["busy_s_mean"]
        device["window_s"] = context["trace"]["window_s"]
        result["breakdown"] = trace_reduce.breakdown(context["trace"])
        say("TRACE", {"steps": context["trace"]["steps"],
                      "step_module": context["trace"]["step_module"],
                      "busy_s": context["trace"]["busy_s_busiest"],
                      "window_s": context["trace"]["window_s"],
                      "idle_gaps_name_s_at_s": context["trace"]["idle_gaps"][:5],
                      "top_families": sorted(
                          context["trace"]["by_family_s"].items(),
                          key=lambda kv: -kv[1])[:25]})
        shutil.rmtree(trace_dir, ignore_errors=True)
        for module in reg.layer_metrics():
            if not module.applies(cell):
                continue
            value = module.read(context)
            if value is not None:
                result["metrics"][module.NAME] = {"value": float(value),
                                                  "unit": module.UNIT}
    else:
        result["metrics"] = {
            "samples_per_s_chip": {"value": window["mean_per_chip"],
                                   "unit": "samples/s/chip"},
            "wan_bytes_per_sample": {"value": wire, "unit": "B/sample"},
            "peak_hbm_gib": {"value": peak_bytes / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["checks"] = checks
    return result


def trainer_wire_bytes(cell: dict, shapes) -> float:
    """Bytes one party puts on the dc tier per sync over the samples that
    party trains per sync: the program's own static accounting
    (`sync.wire_accounting`), read from a sync algorithm built from the
    cell's data; dense fp32 payload where there is no compressor."""
    from geomx_tpu.sync import get_sync_algorithm
    traffic = cell["traffic"]
    account = get_sync_algorithm(geo_config(cell)).wire_accounting(shapes)
    per_party = traffic["workers"] * cell["config"]["per_chip_batch"]
    return float(account["dc_wire_bytes"]) / per_party


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(Registry(ROOT), args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, rec in result["checks"].items():      # standard error's last lines
        print(f"CHECK {name} = {rec['value']} (limit {rec['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
