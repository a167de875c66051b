"""What the check holds on the device: runs `run.run_reference`'s path
alone (weights from the seed, the plain reference trainer, the traffic's
`n_check` steps) for one cell's configuration and traffic, with overrides
of the configuration from the command line, and prints the allocator's
high-water mark beside its limit.  Run on the chip:

    chiprun -- python3 benchmark/tools/reference_memory.py \
        --workload bertlarge-fsa-1c [--set num_hidden_layers=46] [--seed <n>]

Nothing of the program runs and nothing of its state is ever allocated:
the parameters' shapes come from `jax.eval_shape` of the model's `init`.
The last line of standard output is one JSON object: parameters,
`peak_bytes_in_use`, `bytes_limit`, `peak_over_parameter_bytes`, the
seconds the steps took and the losses.  An allocation the chip cannot
hold ends the run with XLA's RESOURCE_EXHAUSTED error and no such line.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parameter_shapes(cell: dict, sample):
    """The tree of the model's parameter shapes, nothing allocated."""
    import jax
    model = cell["family"].build_model(cell["config"])
    variables = jax.eval_shape(
        lambda key, x: model.init(key, x, train=False),
        jax.random.PRNGKey(0), sample)
    return variables["params"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON",
                        help="override one key of the configuration")
    args = parser.parse_args(argv)

    import jax
    import numpy as np
    from benchmark import run
    from benchmark.cells import Registry
    cell = Registry(ROOT).cell(args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        if key not in cell["config"]:
            raise SystemExit(f"the configuration has no key {key!r}")
        cell["config"][key] = json.loads(value)
    config, traffic = cell["config"], cell["traffic"]
    run.configure_compile_cache()
    device = jax.devices()[0]
    rows = (traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
            * traffic["n_check"])
    x, y = cell["family"].make_data(config, np.random.default_rng(args.seed),
                                    rows)
    shapes = parameter_shapes(cell, x[:2])
    parameters = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    run.say("REFERENCE_MEMORY_START", {
        "workload": args.workload, "overrides": args.set,
        "parameters": parameters, "parameter_bytes": 4 * parameters,
        "compression": traffic["geoconfig"]["compression"],
        "device": device.device_kind})
    t0 = time.perf_counter()
    reference = run.run_reference(cell, shapes, x, y, args.seed)
    seconds = time.perf_counter() - t0
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(json.dumps({
        "workload": args.workload, "overrides": args.set,
        "platform": device.platform, "parameters": parameters,
        "parameter_bytes": 4 * parameters, "peak_bytes_in_use": peak,
        "bytes_limit": stats.get("bytes_limit"),
        "peak_over_parameter_bytes": peak / (4.0 * parameters) if peak else None,
        "seconds": seconds, "steps": traffic["n_check"],
        "losses": reference["losses"],
        "delta_norm_max": float(np.max(reference["delta_norms"]))}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
