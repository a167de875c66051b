"""Reads, in one process and one set-up, what a cell's limits are set
from: over many seeds the numbers the comparison gives for sound runs of
the program, and over a few seeds the numbers it gives for the control
(the plain reference put in the program's place and computed in the
nearest precision below the one the configuration states).

    python3 -m benchmark.seeds --workload <name> --seeds 12 --control 3 --first-seed <n>

No measured window: training's readings need none.  Prints one SOUND line
per seed, one CONTROL line per control seed, and a SUMMARY with each
number's largest sound reading and smallest control reading; `--dump`
writes every side's raw readings (losses, per-leaf norms) as JSON lines,
so that a number can be chosen from them without another chip run.

One trainer serves every seed; each seed's state is made as a run makes
it (`run.initial_state`).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, run  # noqa: E402
from benchmark.cells import Registry  # noqa: E402


def _plain(readings: dict, reference: dict) -> dict:
    """One side's raw readings without the gradient itself."""
    out = {"losses": readings["losses"], "bsc": readings.get("bsc"),
           "delta_norms": [float(x) for x in readings["delta_norms"]]}
    grad = check.gradient_readings(readings["first_grad"],
                                   reference["first_grad"])
    out.update({"grad_" + k: [float(x) for x in v] for k, v in grad.items()})
    return out


def read_seeds(reg: Registry, name: str, seeds, control_seeds,
               on_chip: bool = True, dump: str | None = None) -> dict:
    import numpy as np
    from benchmark.references.numerics import next_lower
    cell = reg.cell(name)
    config, traffic = cell["config"], cell["traffic"]
    run.configure_compile_cache()
    if on_chip:
        run.require_chips(cell["chips"])
    trainer = run.build_trainer(cell)
    rows = (traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
            * traffic["n_check"])
    control_precision = next_lower(config["precision"])
    floor = cell["workload"]["first_grad_floor"]["value"]
    sound, control, norms = [], [], []
    out = open(dump, "w") if dump else None
    for seed in seeds:
        x, y = cell["family"].make_data(config, np.random.default_rng(seed),
                                        rows)
        state, shapes = run.initial_state(cell, trainer, seed, x[:2])
        state, program = run.first_steps(cell, trainer, state, shapes, x, y,
                                         seed)
        del state
        gc.collect()
        reference = run.run_reference(cell, shapes, x, y, seed)
        numbers = check.compare(program, reference, floor)
        sound.append(numbers)
        norms.append(float(np.sqrt(np.sum(np.square(check.gradient_readings(
            reference["first_grad"], reference["first_grad"])["reference"])))))
        run.say("SOUND", {"seed": seed, "reference_gradient_norm": norms[-1],
                          **numbers})
        record = {"seed": seed, "program": _plain(program, reference)}
        if seed in control_seeds:
            lower = run.run_reference(cell, shapes, x, y, seed,
                                      control_precision)
            numbers = check.compare(lower, reference, floor)
            control.append(numbers)
            run.say("CONTROL", {"seed": seed, "precision": control_precision,
                                **numbers})
            record["control"] = _plain(lower, reference)
        if out:
            out.write(json.dumps(record) + "\n")
            out.flush()
        # the next seed's state needs the room the gradients take
        program = reference = lower = record = None
        gc.collect()
    if out:
        out.close()
    summary = {}
    for key in sound[0]:
        summary[key] = {"sound_max": max(n[key] for n in sound)}
        if control and key in control[0]:
            summary[key]["control_min"] = min(n[key] for n in control)
    run.say("SUMMARY", {"workload": name, "seeds": len(sound),
                        "control_seeds": len(control), "numbers": summary,
                        "first_grad_floor": floor,
                        "reference_gradient_norm_median": float(np.median(norms))})
    return summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2147480000)
    parser.add_argument("--dump", default=None)
    args = parser.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    read_seeds(Registry(ROOT), args.workload, seeds,
               set(seeds[:args.control]), dump=args.dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
