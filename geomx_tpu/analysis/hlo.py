"""Lowered-HLO assertions: the structural claims about compiled graphs.

PR 4's fused-kernel regression ("the ops that materialize a dense
gradient-sized intermediate are GONE from the fused graphs") lived as
private string matchers in ``tests/test_bsc_pallas.py`` and a harness
since removed.  This module is the single owner: cross-
lower a function for the TPU platform on any host (the same ``jax.export``
mechanism as the Mosaic lowering guards), count the HBM-materializing
stablehlo ops in the module text, and render the fused-vs-unfused
verdict the tests assert.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Sequence

# stablehlo ops that materialize an HBM-resident intermediate in the
# unfused compression graphs (scatter/sort/gather for the select chain,
# dynamic_update_slice/concatenate for the bucket (un)flatten,
# while/reduce_window for cumsum expansions).  The fused path replaces
# them with one tpu_custom_call per kernel.
MATERIALIZING_OPS = ("stablehlo.scatter", "stablehlo.sort",
                     "stablehlo.gather", "stablehlo.dynamic_update_slice",
                     "stablehlo.dynamic_slice", "stablehlo.concatenate",
                     "stablehlo.while", "stablehlo.reduce_window")


def lower_text(fn: Callable, *args, platform: str = "tpu") -> str:
    """Cross-lower ``fn`` for ``platform`` (works on any host) and
    return the StableHLO module text."""
    import jax
    from jax import export as jax_export
    return jax_export.export(jax.jit(fn), platforms=(platform,))(
        *args).mlir_module()


def count_ops(text: str, ops: Sequence[str]) -> Dict[str, int]:
    """Occurrences of each fully-qualified op name in module text,
    keyed by the short (post-dot) name; zero-count ops are omitted."""
    counts: Dict[str, int] = {}
    for op in ops:
        c = len(re.findall(re.escape(op) + r"\b", text))
        if c:
            counts[op.split(".")[-1]] = c
    return counts


def materialization_counts(fn: Callable, *args, extra_ops=()) -> Dict[str, int]:
    """Cross-lower ``fn`` for TPU and count the HBM-materializing
    stablehlo ops in the module text.  ``total`` sums them;
    ``tpu_custom_calls`` counts Mosaic kernel launches alongside."""
    text = lower_text(fn, *args)
    counts = count_ops(text, tuple(MATERIALIZING_OPS) + tuple(extra_ops))
    counts["total"] = sum(counts.values())
    counts["tpu_custom_calls"] = len(re.findall(r"tpu_custom_call", text))
    return counts


def hlo_verdict(unfused: Dict[str, int], fused: Dict[str, int],
                dense_ops: Sequence[str]) -> dict:
    """The structural acceptance check: the ops that write a dense
    gradient-sized intermediate in the unfused graph are GONE (not just
    fewer) from the fused one.  ``total``/``tpu_custom_calls`` carry the
    raw comparison alongside."""
    du = sum(unfused.get(o, 0) for o in dense_ops)
    df = sum(fused.get(o, 0) for o in dense_ops)
    return {"unfused": unfused, "fused": fused,
            "dense_ops": list(dense_ops), "dense_unfused": du,
            "dense_fused": df,
            "dense_intermediates_removed": bool(df == 0 and du > 0)}


def compare_paths(unfused_fn: Callable, fused_fn: Callable, *args,
                  dense_ops: Sequence[str], extra_ops=()) -> dict:
    """One-call form of the fused-vs-unfused comparison: lower both
    paths on identical arguments and return :func:`hlo_verdict`."""
    return hlo_verdict(
        materialization_counts(unfused_fn, *args, extra_ops=extra_ops),
        materialization_counts(fused_fn, *args, extra_ops=extra_ops),
        dense_ops)


def assert_dense_intermediates_removed(verdict: dict,
                                       min_custom_calls: int = 1) -> dict:
    """Raise AssertionError (with the full verdict) unless the fused
    path removed every dense op and actually launches kernels."""
    if not verdict.get("dense_intermediates_removed"):
        raise AssertionError(
            f"dense intermediates NOT removed from the fused graph: "
            f"{verdict}")
    calls = verdict.get("fused", {}).get("tpu_custom_calls", 0)
    if calls < min_custom_calls:
        raise AssertionError(
            f"fused graph has {calls} tpu_custom_call(s), expected >= "
            f"{min_custom_calls}: {verdict}")
    return verdict


def assert_ops_absent(fn: Callable, *args, ops: Sequence[str]) -> None:
    """Assert none of ``ops`` (fully-qualified stablehlo names) appear
    in ``fn``'s TPU-lowered module."""
    text = lower_text(fn, *args)
    present = count_ops(text, ops)
    if present:
        raise AssertionError(
            f"ops expected ABSENT from the lowered module are present: "
            f"{present}")
