"""Compressor interface and registry."""

from __future__ import annotations

import abc
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from geomx_tpu.parallel.collectives import tier_scope


class Compressor(abc.ABC):
    """A compressed all-reduce over one mesh axis.

    Operates leaf-wise on gradient pytrees. State (error-feedback residuals,
    momentum-corrected velocities, ...) mirrors the gradient pytree and lives
    per-party: inside shard_map every device holds its party's copy, exactly
    as each reference local server held its own residual NDArrays
    (reference: src/kvstore/kvstore_dist_server.h decomp_buf_/residual_).
    """

    name: str = "base"
    # True for compressors that already fuse the whole gradient tree into
    # flat buffers themselves (tree-level DGT, BucketedCompressor) — the
    # bucketing default skips these instead of double-wrapping.
    fuses_tree: bool = False

    # -- state ---------------------------------------------------------------
    def init_leaf_state(self, leaf: jax.Array) -> Any:
        """Per-leaf compressor state, built from an example (unsharded) leaf."""
        return ()

    def init_state(self, grads: Any) -> Any:
        return jax.tree.map(self.init_leaf_state, grads)

    # -- the compressed all-reduce -------------------------------------------
    @abc.abstractmethod
    def allreduce_leaf(self, g: jax.Array, state: Any, axis_name: str,
                       axis_size: int) -> Tuple[jax.Array, Any]:
        """Return (sum of g across `axis_name`, new state).

        Implementations must transfer only the compressed payload across the
        axis; everything dense stays device-local.
        """

    def allreduce(self, grads: Any, state: Any, axis_name: str,
                  axis_size: int) -> Tuple[Any, Any]:
        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = treedef.flatten_up_to(state)
        out_g, out_s = [], []
        for g, s in zip(flat_g, flat_s):
            og, os_ = self.allreduce_leaf(g, s, axis_name, axis_size)
            out_g.append(og)
            out_s.append(os_)
        return treedef.unflatten(out_g), treedef.unflatten(out_s)

    # -- accounting ----------------------------------------------------------
    def wire_bytes_leaf(self, leaf: jax.Array) -> int:
        """Bytes this leaf puts on the wire per participant per sync
        (for the bandwidth accounting the reference exposes via ps-lite byte
        counters, van.h:182-183).  The dense default transmits the leaf
        as-is, so a bf16/fp16 leaf costs 2 bytes/element, not a hardcoded
        fp32's 4."""
        return leaf.size * jnp.dtype(leaf.dtype).itemsize

    def wire_bytes(self, grads: Any) -> int:
        return sum(self.wire_bytes_leaf(leaf) for leaf in jax.tree.leaves(grads))


class NoCompressor(Compressor):
    """Dense fp32 all-reduce (the reference's default uncompressed path)."""

    name = "none"

    def allreduce_leaf(self, g, state, axis_name, axis_size):
        if axis_size == 1:
            return g, state
        with tier_scope(axis_name):
            return lax.psum(g, axis_name), state


def _parse_bool(v: str) -> bool:
    s = v.strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"expected a boolean, got {v!r}")


def _parse_int(v: str) -> int:
    return int(float(v))


# per-kind spec grammar: positional arg names (in order) and the full
# key=value vocabulary with its casts.  Positionals are the reference's
# original "type,threshold" encoding; keys cover everything a
# constructor accepts that the positional form cannot express.
_SPEC_GRAMMAR = {
    "none": ([], {}),
    "fp16": ([], {"bf16": _parse_bool, "sparse_agg": _parse_bool}),
    "2bit": (["threshold"], {"threshold": float,
                             "sparse_agg": _parse_bool}),
    "bsc": (["ratio"], {"ratio": float,
                        "min_sparse_size": _parse_int,
                        "sparse_agg": _parse_bool,
                        "sparse_agg_parties": _parse_int}),
    "mpq": (["ratio", "size_lower_bound"],
            {"ratio": float, "size_lower_bound": _parse_int,
             "bf16": _parse_bool}),
}


def get_compressor(spec) -> Compressor:
    """Parse a reference-style "type,args" spec string into a Compressor.

    Mirrors GradientCompression::DecodeParams
    (reference: src/kvstore/gradient_compression.cc:91-100), extended
    with ``key=value`` arguments for knobs the positional form cannot
    express: ``"bsc,0.01,min_sparse_size=2048"``,
    ``"fp16,bf16=1"``, ``"mpq,ratio=0.02,size_lower_bound=100000"``.
    Positional args must precede keyword args; unknown keys are rejected
    with the valid vocabulary in the error.
    """
    from geomx_tpu.compression.fp16 import FP16Compressor
    from geomx_tpu.compression.twobit import TwoBitCompressor
    from geomx_tpu.compression.bisparse import BiSparseCompressor
    from geomx_tpu.compression.mpq import MPQCompressor

    if spec is None:
        return NoCompressor()
    if isinstance(spec, Compressor):
        return spec
    parts = [p.strip() for p in str(spec).split(",")]
    kind = parts[0].lower()
    if kind == "":
        kind = "none"
    if kind not in _SPEC_GRAMMAR:
        raise ValueError(f"Unknown gradient compression type: {spec!r}")
    pos_names, vocab = _SPEC_GRAMMAR[kind]

    kwargs = {}
    seen_kw = False
    npos = 0
    for p in parts[1:]:
        if not p:
            continue
        if "=" in p:
            seen_kw = True
            key, _, val = p.partition("=")
            key = key.strip()
            if key not in vocab:
                raise ValueError(
                    f"Unknown argument {key!r} for compression type "
                    f"{kind!r} in spec {spec!r}; valid keys: "
                    f"{sorted(vocab) or 'none'}")
            if key in kwargs:
                raise ValueError(f"Duplicate argument {key!r} in spec "
                                 f"{spec!r}")
            kwargs[key] = vocab[key](val.strip())
        else:
            if seen_kw:
                raise ValueError(
                    f"Positional argument {p!r} after keyword arguments "
                    f"in spec {spec!r}")
            if npos >= len(pos_names):
                raise ValueError(
                    f"Too many positional arguments for compression type "
                    f"{kind!r} in spec {spec!r} (takes {pos_names or 'none'})")
            name = pos_names[npos]
            kwargs[name] = vocab[name](p)
            npos += 1

    if kind == "none":
        return NoCompressor()
    if kind == "fp16":
        return FP16Compressor(**kwargs)
    if kind == "2bit":
        return TwoBitCompressor(**kwargs)
    if kind == "bsc":
        return BiSparseCompressor(**kwargs)
    return MPQCompressor(**kwargs)
