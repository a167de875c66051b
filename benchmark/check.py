"""The comparison that decides `correct`: the program's first steps
against the plain reference's, each number beside a limit of its own
(the cell's `workloads/<cell>.json` holds the limits, PERF.md the
readings they were set from)."""
from __future__ import annotations

import math

import numpy as np


def worst_leaf_gap(program, reference, floor: str = "median",
                   weak: float = 1.0) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero).  `floor="rms"` measures against the root mean square over
    leaves where that is larger still: under a sparse tier most small
    leaves do not move at all in three steps, the median is zero, and
    whether one of them moved is decided by a rounding.  `weak` (at least
    1) raises the median leaf's floor by that factor: see `weakness`."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"{program.shape} leaves against {reference.shape}")
    scale = np.maximum(reference, weak * np.median(reference))
    if floor == "rms":
        scale = np.maximum(scale, np.sqrt(np.mean(np.square(reference))))
    gap = np.abs(program - reference) / scale
    return float(np.max(np.where(np.isfinite(gap), gap, np.inf)))


def weakness(reference_norm: float, floor: float) -> float:
    """How far the whole gradient's norm lies under the cell's floor, as a
    factor of at least 1.  A small batch of random labels now and then
    gives per-sample signals that all but cancel: the gradient is then a
    small difference of large terms, its norm a tenth of the usual, and
    every error measured against it reads ten times as large although the
    program did nothing else.  Errors are therefore measured against a
    gradient of at least the floor's norm (the cell's file has the floor
    and the readings it was set from)."""
    return max(1.0, float(floor) / max(float(reference_norm), 1e-300))


def whole_gradient_error(difference, reference, floor: float) -> float:
    """Norm of (program - reference) over the whole gradient against the
    reference's norm or the cell's floor, whichever is larger.  Unlike a
    gap between two norms it does not let rounding errors average out, so
    it is the number that tells one precision from the next."""
    difference = np.asarray(difference, np.float64)
    reference = np.asarray(reference, np.float64)
    value = float(np.sqrt(np.sum(np.square(difference)))
                  / max(np.sqrt(np.sum(np.square(reference))), float(floor)))
    return value if np.isfinite(value) else float("inf")


def gradient_readings(program_leaves, reference_leaves) -> dict:
    """Per leaf: the program's norm, the reference's norm and the norm of
    their difference (leaves of either side may be on the host)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.stack([jnp.sqrt(jnp.sum(a * a)), jnp.sqrt(jnp.sum(b * b)),
                          jnp.sqrt(jnp.sum(jnp.square(a - b)))])
    rows = np.asarray([np.asarray(one(a, b), np.float64)
                       for a, b in zip(program_leaves, reference_leaves)])
    return {"program": rows[:, 0], "reference": rows[:, 1],
            "difference": rows[:, 2]}


def compare(program: dict, reference: dict, grad_floor: float) -> dict:
    """Every number the run compares, by name.  `grad_floor`: the cell's
    floor for the norm of the first gradient (`weakness`)."""
    lp = np.asarray(program["losses"], np.float64)
    lr = np.asarray(reference["losses"], np.float64)
    grad = gradient_readings(program["first_grad"], reference["first_grad"])
    weak = weakness(np.sqrt(np.sum(np.square(grad["reference"]))), grad_floor)
    numbers = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "first_grad_gap": worst_leaf_gap(grad["program"], grad["reference"],
                                         weak=weak),
        "first_grad_error": whole_gradient_error(
            grad["difference"], grad["reference"], grad_floor),
        "delta_gap": worst_leaf_gap(program["delta_norms"],
                                    reference["delta_norms"], floor="rms"),
    }
    facts = program.get("bsc")
    if facts:
        numbers.update({
            "bsc_overlap": float(facts["overlap"]),
            "bsc_below_boundary": float(facts["below"]),
            "bsc_held_back": float(facts["held"]),
            "bsc_over_k": float(max(0, facts["count"] - facts["k"])),
            "bsc_count_gap": abs(facts["count"] - facts["plain_count"])
            / float(facts["k"]),
        })
    return numbers


def verdict(numbers: dict, limits: dict):
    """(correct, one line per number).  A number without a limit, or a
    value that is not finite, is not correct."""
    lines, correct = [], True
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        ok = (limit is not None and math.isfinite(value) and value <= limit)
        correct = correct and ok
        lines.append({"number": name, "value": value, "limit": limit,
                      "ok": bool(ok)})
    return correct, lines
