"""Kernel or XLA: the one place that decides, from the platform.

Every op of the compression engine, a decoder's KDA scan, its expert
pools' row scatter-add and a grouped-query mixer's q/k norm with rotary
exist twice under ``ops/`` (the Mamba-2 scan, :func:`ssd`, has its jnp
form only so far and comes through here all the same): a Pallas kernel
and a jnp form with the same
results (bit for bit, except where an op's docstring says otherwise).  The
kernel is what a TPU runs; the jnp form is the only path elsewhere and the
oracle the tests hold the kernel to.  The functions below are what
``compression/``, ``models/`` and ``ops/held_experts.py`` call: each picks
its implementation while the program is traced, from :func:`kernel_mode`.
Nothing above ``ops/`` (a compressor's arguments, the spec string,
``GeoConfig``, the environment) can choose, and nothing above it asks.

``tools/*_timing.py`` and ``chip_smoke.py`` compare kernel and oracle by
calling both by name (``bsc_select_pack`` / ``select_pack_ref``,
``bsc_sampled_boundary`` / ``sampled_boundary_guv`` and so on), not
through here.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax

from geomx_tpu.ops import (bsc_pallas, bucket_pallas, gqa_elementwise,
                           kda as kda_jnp, kda_pallas, merge_pallas,
                           moe_rows_pallas, ssd as ssd_jnp, twobit_pallas)

_OVERRIDE: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "geomx_kernel_mode", default=None)


def kernel_mode() -> Optional[str]:
    """How an op that has a Pallas kernel runs in the program being
    traced: ``"native"`` (compiled by Mosaic: on a TPU), ``"interpret"``
    (the kernel under the Pallas interpreter) or ``None`` (no kernel: the
    op's XLA form, or interpret mode for an op that has no other)."""
    forced = _OVERRIDE.get()
    if forced is not None:
        return forced
    return "native" if jax.default_backend() == "tpu" else None


@contextlib.contextmanager
def kernels(mode: str):
    """Tests and tools only: trace what is inside with the kernels in
    ``"interpret"`` mode (parity on the CPU) or lowered ``"native"`` (to
    compile for a described TPU from a CPU host; such a program lowers
    anywhere and runs only on a TPU).  It acts while a program is TRACED:
    wrap the first call or the ``.lower()`` of a fresh ``jax.jit``, since
    a function jitted before keeps the program it was traced to."""
    if mode not in ("interpret", "native"):
        raise ValueError(f"kernels(): 'interpret' or 'native', got {mode!r}")
    token = _OVERRIDE.set(mode)
    try:
        yield
    finally:
        _OVERRIDE.reset(token)


def _door(kernel, ref, doc):
    """An op of the engine: ``kernel`` where :func:`kernel_mode` names
    one, else ``ref``; same arguments, same results."""
    def op(*args):
        mode = kernel_mode()
        if mode is None:
            return ref(*args)
        return kernel(*args, interpret=mode == "interpret")
    op.__doc__ = doc
    return op


sampled_boundary = _door(
    bsc_pallas.bsc_sampled_boundary, bsc_pallas.sampled_boundary_guv,
    """``(g, u, v, k)``: the Bi-Sparse boundary of one flat bucket, the
    (1 - k/n) quantile of the probe's momentum-corrected magnitudes; ``k``
    static or traced.  The kernel path picks how the samples are fetched
    from the bucket's size (``bsc_pallas.bsc_sampled_boundary``).""")
select_pack = _door(
    bsc_pallas.bsc_select_pack, bsc_pallas.select_pack_ref,
    """``(g, u, v, threshold, k)``: Bi-Sparse select/pack of one flat
    bucket against the boundary: ``(vals[k], idx[k], new_u, new_v)``.""")
scatter_add = _door(
    bsc_pallas.bsc_scatter_add, bsc_pallas.scatter_add_ref,
    """``(vals, idx, n)``: Bi-Sparse decompress, pairs into a dense [n].""")
flatten_buckets = _door(
    bucket_pallas.fused_flatten, bucket_pallas.flatten_ref,
    """``(leaves, layout, bucket_sizes)``: 1-D fp32 leaves -> flat fp32
    buckets (``bucket_pallas``'s layout).""")
unflatten_buckets = _door(
    bucket_pallas.fused_unflatten, bucket_pallas.unflatten_ref,
    """``(buckets, layout, leaf_sizes)``: flat buckets -> 1-D leaves.""")
quantize_2bit = _door(
    twobit_pallas.quantize_2bit, twobit_pallas.quantize_2bit_ref,
    """``(g, residual, threshold)``: ``(packed int32 words, new
    residual)``.  The words are opaque: the kernel's layout is row-blocked,
    the jnp form's is not, each the inverse of its own
    :func:`dequantize_2bit`; :func:`twobit_words` is the count either puts
    on the wire.""")
dequantize_2bit = _door(
    twobit_pallas.dequantize_2bit, twobit_pallas.dequantize_2bit_ref,
    """``(words, n, threshold)``: the values a party sent, dense [n].""")


def twobit_words(n: int) -> int:
    """int32 words :func:`quantize_2bit` emits here for ``n`` elements."""
    if kernel_mode() is None:
        return twobit_pallas.words_ref(n)
    return twobit_pallas.words_kernel(n)


def merge_pairs(vals, idx, max_duplicates: int):
    """Merge a (value, index) pair stream by index (``merge_pallas``)."""
    mode = kernel_mode()
    return merge_pallas.merge_sorted_pairs(
        vals, idx, max_duplicates, fused=mode is not None,
        interpret=mode == "interpret")


def kda(q, k, v, g, beta, chunk: int = 64, sub: int = 16,
        dtype=jax.numpy.float32):
    """The chunked gated delta rule, heads-major (``ops/kda.kda_chunked``
    says what the arguments are): the kernel pair of ``kda_pallas`` with
    its own backward where :func:`kernel_mode` names one, else the jnp
    form and JAX's backward.  Equal within the roundings of ``dtype``
    (``tests/test_kda_kernel.py`` states them)."""
    mode = kernel_mode()
    if mode is None:
        return kda_jnp.kda_chunked(q, k, v, g, beta, chunk=chunk, sub=sub,
                                   dtype=dtype)
    return kda_pallas.kda_scan(q, k, v, g, beta, chunk, sub, dtype,
                               mode == "interpret")


def ssd(x, dt, a, b, c, chunk: int = 128, dtype=jax.numpy.float32):
    """The Mamba-2 state-space scan in its chunkwise matrix form
    (``ops/ssd.ssd_chunked`` says what the arguments are).  One
    implementation on every platform today, plain matrix products under
    scope ``ssd/scan``; a kernel, once written, is chosen here from
    :func:`kernel_mode` as :func:`kda`'s is."""
    return ssd_jnp.ssd_chunked(x, dt, a, b, c, chunk=chunk, dtype=dtype)


def row_scatter_add(y, out, token, sizes):
    """An expert pool's rows back into the token array, ``y[token] += out``
    in float32: ``y`` [T, d], ``out`` [places, d], ``token`` [places];
    ``sizes`` [E] are the places of each expert's run, in order from place
    0 (a token is unique inside a run); the places behind the runs are
    dropped.  The kernel of ``moe_rows_pallas`` where :func:`kernel_mode`
    names one and a row of ``d`` floats is whole tiles as a slab
    (``moe_rows_pallas.slabs_are_whole``: the width decides, as a bucket's
    size decides how the boundary probe fetches), else XLA's scatter-add.
    Equal wherever a token sits at most twice in the pool, and within the
    reordering of a float32 sum of its addends elsewhere."""
    mode = kernel_mode()
    if mode is None or not moe_rows_pallas.slabs_are_whole(y.shape[1]):
        return moe_rows_pallas.row_scatter_add_ref(y, out, token, sizes)
    return moe_rows_pallas.moe_row_scatter_add(
        y, out, token, sizes, interpret=mode == "interpret")


def gqa_norm_rotary(q, k, q_scale, k_scale, eps: float, rope):
    """A grouped-query mixer's per-head RMSNorm of q ``[B, L, H, d]`` and
    k ``[B, L, KV, d]`` (one learned scale each) and, where ``rope`` is
    not None (a theta, or a ``gqa_elementwise.Yarn``), rotate-half rotary
    at positions 0..L-1 from its tables: ``(q, k)`` in their own dtype.
    With rotary, the kernel pair of ``gqa_elementwise`` with its own
    backward where :func:`kernel_mode` names one and the shapes
    are the kernels' (``gqa_elementwise.norm_rotary_plan``: a head of
    whole lane tiles, bf16 or float32, ``MIN_TILE`` tokens or more); else,
    and for the norm alone (which XLA streams as one pass itself), the
    jnp form and JAX's backward.  Equal forward wherever XLA keeps the jnp
    form's rounding to the caller's dtype between norm and rotary; on a
    TPU it elides that round trip, and the kernels, which keep it, differ
    from its program in the last place of bf16 (PERF.md, PR 37)."""
    mode = kernel_mode()
    if (mode is None or rope is None or k.dtype != q.dtype
            or gqa_elementwise.norm_rotary_plan(q.shape, k.shape,
                                                q.dtype) is None):
        return gqa_elementwise.norm_rotary_ref(q, k, q_scale, k_scale, eps,
                                               rope)
    return gqa_elementwise.norm_rotary(q, k, q_scale, k_scale, eps, rope,
                                       mode == "interpret")
