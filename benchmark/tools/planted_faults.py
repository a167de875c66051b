"""What the comparison reads when a fault is planted, at a cell's own
size: the upper reading of each limit that no lower precision moves.

    chiprun -- python3 benchmark/tools/planted_faults.py \
        --workload nemotron3super-fsa-1c [--seed <n>]

Nothing of the program runs.  The plain reference (float32) follows the
cell's `n_check` steps once as it stands and once with **half of every
step's rows left out** (the first half of each slot's rows kept), and
`check.compare` reads the second against the first; three more faults
are planted in the first run's own readings: **the first gradient
scaled** by a half, **one leaf's gradient missing** (the leaf of the
median norm), and **a state left unchanged** (every leaf's change zero).
One `FAULT` line each: the numbers as `check.compare` gives them, and
the names of the cell's limits each fault fails (`caught_by`).  A cell's
`workloads/<cell>.json` names these readings in its limits' `from`.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def half_of_every_step(x, steps: int, slots: int):
    """The first half of each slot's rows of each step."""
    per = x.shape[0] // (steps * slots)
    kept = x.reshape((steps, slots, per) + x.shape[1:])[:, :, :per // 2]
    return kept.reshape((-1,) + x.shape[1:])


def planted(reference: dict) -> dict:
    """The faults that need no run of their own, by name."""
    import numpy as np
    grad = reference["first_grad"]
    norms = [float(np.linalg.norm(np.asarray(g, np.float64))) for g in grad]
    median = int(np.argsort(norms)[len(norms) // 2])
    return {
        "gradient_scaled_by_half": dict(
            reference, first_grad=[0.5 * np.asarray(g) for g in grad]),
        "one_leaf_missing": dict(reference, first_grad=[
            np.zeros_like(g) if i == median else g
            for i, g in enumerate(grad)]),
        "state_unchanged": dict(
            reference, delta_norms=np.zeros_like(reference["delta_norms"])),
    }


def read_faults(cell: dict, seed: int) -> dict:
    """{fault: what `check.compare` reads of it against the sound
    reference}, at the cell's floor for the first gradient's norm."""
    import numpy as np
    from benchmark import check, run
    from benchmark.tools.reference_memory import parameter_shapes
    config, traffic = cell["config"], cell["traffic"]
    if config["per_chip_batch"] < 2:
        raise SystemExit("a slot of one row has no half to leave out")
    steps = traffic["n_check"]
    slots = traffic["parties"] * traffic["workers"]
    x, y = cell["family"].make_data(
        config, np.random.default_rng(seed),
        slots * config["per_chip_batch"] * steps)
    shapes = parameter_shapes(cell, x[:2])
    reference = run.run_reference(cell, shapes, x, y, seed)
    halved = dict(cell, config=dict(
        config, per_chip_batch=config["per_chip_batch"] // 2))
    faults = {"half_the_batch_left_out": run.run_reference(
        halved, shapes, half_of_every_step(x, steps, slots),
        half_of_every_step(y, steps, slots), seed)}
    faults.update(planted(reference))
    floor = cell["workload"]["first_grad_floor"]["value"]
    return {name: check.compare(fault, reference, floor)
            for name, fault in faults.items()}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=2147483659)
    args = parser.parse_args(argv)

    from benchmark import check, run
    from benchmark.cells import Registry
    cell = Registry(ROOT).cell(args.workload)
    run.configure_compile_cache()
    limits = cell["workload"]["limits"]
    for name, numbers in read_faults(cell, args.seed).items():
        lines = check.verdict(numbers, limits)[1]
        run.say("FAULT", {"fault": name, "seed": args.seed, **numbers,
                          "caught_by": [line["number"] for line in lines
                                        if not line["ok"]]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
