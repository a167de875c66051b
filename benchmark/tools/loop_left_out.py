"""What the comparison reads when a looped stack's loss is computed
wrongly, at a cell's own size: three more planted faults beside those of
`planted_faults.py`, for a cell whose configuration runs its stack more
than once (`total_ut_steps` above 1).

    chiprun -- python3 benchmark/tools/loop_left_out.py \
        --workload ouro26b-fsa-1c [--seed <n>]

Nothing of the program runs.  The plain reference (float32) follows the
cell's `n_check` steps once as it stands and once with each of

- **a loop step fewer** (`total_ut_steps` T - 1: the last pass, its head
  pass and its share of the exit mass are missing);
- **`exit_beta` 0** (the entropy term left out of the total);
- **the wrong last mass** (the last step given `lambda^T prod_{j<T} (1 -
  lambda^j)` and not the rest of the mass, so that a token's masses fall
  short of 1),

and `check.compare` reads each against the first.  The three faults
`planted_faults.planted()` plants in a reference's own readings (the
gradient scaled by a half, the leaf of the median norm missing, a state
left unchanged) are read too, so that a cell of one row a slot, which
`planted_faults.py` itself refuses, gets all its upper readings from one
call.  One `FAULT` line each: the numbers as `check.compare` gives them
and the names of the cell's limits each fault fails (`caught_by`).
"""
import argparse
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEPS, BETA = "total_ut_steps", "exit_beta"


def wrong_last_mass(family):
    """The family with a reference loss whose last step takes lambda^T's
    share of what is left, not all of it."""
    def reference_loss(config, nx):
        from benchmark.references import ouro
        s = family.sizes(config)
        return lambda params, x, y: ouro.loss(params, x, y, s, nx,
                                              last_takes_rest=False)
    return types.SimpleNamespace(**{**vars(family),
                                    "reference_loss": reference_loss})


def read_faults(cell: dict, seed: int) -> dict:
    """{fault: what `check.compare` reads of it against the sound
    reference}, at the cell's floor for the first gradient's norm."""
    import numpy as np
    from benchmark import check, run
    from benchmark.tools.planted_faults import planted
    from benchmark.tools.reference_memory import parameter_shapes
    config, traffic = cell["config"], cell["traffic"]
    if config.get(STEPS, 1) < 2:
        raise SystemExit("the configuration runs its stack once: no loop")
    rows = (traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
            * traffic["n_check"])
    x, y = cell["family"].make_data(config, np.random.default_rng(seed), rows)
    shapes = parameter_shapes(cell, x[:2])
    reference = run.run_reference(cell, shapes, x, y, seed)
    changed = lambda **keys: dict(cell, config=dict(config, **keys))
    faults = {name: run.run_reference(other, shapes, x, y, seed)
              for name, other in (
        ("a_loop_step_fewer", changed(**{STEPS: config[STEPS] - 1})),
        ("entropy_term_left_out", changed(**{BETA: 0.0})),
        ("wrong_last_mass", dict(cell,
                                 family=wrong_last_mass(cell["family"]))))}
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
