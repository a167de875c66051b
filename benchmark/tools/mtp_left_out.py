"""What the comparison reads when a multi-token-prediction module's loss
is left out of the total, at a cell's own size: one more planted fault
beside those of `planted_faults.py`, for a cell whose configuration has
such a module (`num_nextn_predict_layers` above 0).

    chiprun -- python3 benchmark/tools/mtp_left_out.py \
        --workload glm47flash-fsa-1c [--seed <n>]

Nothing of the program runs.  The plain reference (float32) follows the
cell's `n_check` steps once as it stands and once with **the module's loss
weight set to 0** (the total is the main loss alone, the module's own
leaves get no gradient, embedding, head and the layers under it lose the
module's part), and `check.compare` reads the second against the first.
The three faults `planted_faults.planted()` plants in a reference's own
readings (the gradient scaled by a half, the leaf of the median norm
missing, a state left unchanged) are read too, so that a cell of one row a
slot, which `planted_faults.py` itself refuses, gets all its upper readings
from one call.  One `FAULT` line each: the numbers as `check.compare` gives
them and the names of the cell's limits each fault fails (`caught_by`).
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WEIGHT = "mtp_loss_weight"


def read_faults(cell: dict, seed: int) -> dict:
    """{fault: what `check.compare` reads of it against the sound
    reference}, at the cell's floor for the first gradient's norm."""
    import numpy as np
    from benchmark import check, run
    from benchmark.tools.planted_faults import planted
    from benchmark.tools.reference_memory import parameter_shapes
    config, traffic = cell["config"], cell["traffic"]
    if not config.get("num_nextn_predict_layers") or not config.get(WEIGHT):
        raise SystemExit("the configuration has no module whose loss counts")
    rows = (traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
            * traffic["n_check"])
    x, y = cell["family"].make_data(config, np.random.default_rng(seed), rows)
    shapes = parameter_shapes(cell, x[:2])
    reference = run.run_reference(cell, shapes, x, y, seed)
    without = dict(cell, config=dict(config, **{WEIGHT: 0.0}))
    faults = {"mtp_loss_left_out": run.run_reference(without, shapes, x, y,
                                                     seed)}
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
