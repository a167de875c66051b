"""The Graft Auditor's pass catalog (docs/analysis.md).

Rule ids:

- ``GX-COLLECTIVE-001``  cross-program collective-signature divergence
  (would deadlock or silently diverge a multi-party mesh at run time)
- ``GX-COLLECTIVE-002``  a membership/pipeline recompile changed the
  collective program (Trainer.apply_membership boundary)
- ``GX-DONATE-001``      donated buffer has no aliased output (the
  program still reads it after every aliasing opportunity — the
  donation is a lie and the caller's buffer dies for nothing)
- ``GX-DONATE-002``      an expected state buffer (EF residual,
  pipeline double-buffer) is not covered by input_output_aliases
- ``GX-DTYPE-001``       fp32 compute op on a declared-16-bit path
- ``GX-DTYPE-002``       wire-dtype accounting mismatch: the bytes the
  traced collectives actually move disagree with
  ``Compressor.wire_bytes``
- ``GX-PURITY-001``      a dense(-sized) payload crosses the wire on a
  compressed dc path (the decompress-before-collective regression
  PR 4's hand-rolled HLO check guarded against, generalized)
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from geomx_tpu.analysis.core import (AuditContext, AuditPass, EqnSite,
                                     Finding, aval_bytes, aval_sig,
                                     walk_jaxpr)

# every cross-device primitive jax can put in a shard_map'd program on
# this jaxlib; psum2/all_gather_invariant are newer spellings kept for
# forward-compat
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "all_gather", "all_gather_invariant", "all_to_all",
    "ppermute", "pbroadcast", "psum_scatter", "reduce_scatter"})

# jaxpr-level ops that materialize a full-size intermediate when they
# appear dense-shaped (the XLA scatter/cumsum expansions the fused
# kernels exist to remove)
DENSE_MATERIALIZING_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
    "cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod", "sort"})

# the heavy compute ops the dtype-flow leak rule inspects: an fp32
# matmul/conv on a declared-bf16 path burns 2x the MXU bandwidth the
# declaration promised
_HEAVY_COMPUTE_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


def _collective_axes(eqn) -> Tuple[str, ...]:
    """The named mesh axes an equation communicates over (psum spells
    them ``axes``, the gather/permute family ``axis_name``)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


# ---------------------------------------------------------------------------
# collective-consistency
# ---------------------------------------------------------------------------

def count_collectives(jaxpr, axis: Optional[str] = None) -> int:
    """Number of collective equations in a traced program (recursing
    through pjit/shard_map/scan/cond bodies), optionally restricted to
    those communicating over the named ``axis``."""
    n = 0
    for site in walk_jaxpr(jaxpr):
        if site.primitive in COLLECTIVE_PRIMS:
            if axis is None or axis in _collective_axes(site.eqn):
                n += 1
    return n


def _collectives_by_axis(jaxpr) -> Dict[str, Dict[str, int]]:
    """``{axis: {primitive: n}}`` over every collective of a traced
    program; one over several axes counts under each."""
    out: Dict[str, Dict[str, int]] = {}
    for site in walk_jaxpr(jaxpr):
        if site.primitive in COLLECTIVE_PRIMS:
            for axis in _collective_axes(site.eqn):
                by_prim = out.setdefault(axis, {})
                by_prim[site.primitive] = by_prim.get(site.primitive, 0) + 1
    return out


def weight_path_collectives(
        step: Callable, state, xb, yb,
        keep: Sequence[str] = ("params", "opt_state"),
) -> Tuple[Dict[str, Dict[str, int]], Dict[str, Dict[str, int]]]:
    """Which collectives the weight update waits on.  Trace
    ``step(state, xb, yb) -> (new_state, metrics)``, dead-code-eliminate
    it down to the ``keep`` fields of ``new_state`` (``dce_jaxpr``
    recurses through pjit/shard_map/cond) and count what survives.

    Returns ``(on_path, whole)``, each ``{axis: {primitive: n}}``:
    ``on_path`` of the eliminated program, ``whole`` of the step as
    traced.  A BatchNorm-stat pmean feeds ``model_state`` alone: name
    that field in ``keep`` to count it (tests/test_pipeline.py), leave
    it out to count the gradient's path only (tests/test_zero.py)."""
    import jax
    from jax.interpreters import partial_eval as pe

    closed, out_shapes = jax.make_jaxpr(step, return_shape=True)(
        state, xb, yb)
    flat, treedef = jax.tree.flatten(out_shapes)
    new_state, _metrics = jax.tree.unflatten(treedef, range(len(flat)))
    kept = set(jax.tree.leaves([getattr(new_state, f) for f in keep]))
    dced, _used_ins = pe.dce_jaxpr(closed.jaxpr,
                                   [i in kept for i in range(len(flat))])
    return _collectives_by_axis(dced), _collectives_by_axis(closed.jaxpr)


def collective_signature(jaxpr) -> Tuple[Tuple[str, Tuple[str, ...],
                                               Tuple[Tuple[int, ...], str],
                                               tuple], ...]:
    """The ordered named-axis collective signature of a traced program:
    one ``(op, axes, (shape, dtype), extras)`` entry per collective
    *operand*, in deterministic walk order.  Two SPMD programs whose
    signatures differ cannot safely share a mesh — the first differing
    entry deadlocks (count/op mismatch) or silently mis-aggregates
    (shape/dtype/routing mismatch).

    A multi-operand collective (``lax.pmean`` over a dict traces ONE
    psum equation carrying every leaf) is normalized to one entry per
    operand: the wire payload sequence is the invariant, not the fusion
    packaging — XLA's all-reduce combiner merges/splits adjacent
    same-axis collectives regardless of how the jaxpr grouped them, so
    ``psum(a, b)`` and ``psum(a); psum(b)`` describe the same program.
    ``extras`` carries routing parameters that change peer pairing
    (ppermute's ``perm``, any ``axis_index_groups``)."""
    sig = []
    for site in walk_jaxpr(jaxpr):
        if site.primitive not in COLLECTIVE_PRIMS:
            continue
        extras = []
        perm = site.eqn.params.get("perm")
        if perm is not None:
            extras.append(("perm", tuple(map(tuple, perm))))
        groups = site.eqn.params.get("axis_index_groups")
        if groups is not None:
            extras.append(("axis_index_groups",
                           tuple(tuple(g) for g in groups)))
        axes = _collective_axes(site.eqn)
        for v in site.eqn.invars:
            if hasattr(v, "aval"):
                sig.append((site.primitive, axes, aval_sig(v.aval),
                            tuple(extras)))
    return tuple(sig)


def diff_collective_signatures(
        sigs: Mapping[str, tuple],
        rule_id: str = "GX-COLLECTIVE-001") -> List[Finding]:
    """Diff named collective signatures pairwise against the first
    entry; one finding per divergent party naming the first differing
    position (op/axes/operands or a missing/extra collective)."""
    findings: List[Finding] = []
    items = list(sigs.items())
    if len(items) < 2:
        return findings
    ref_name, ref = items[0]
    for name, sig in items[1:]:
        if sig == ref:
            continue
        pos = next((i for i, (a, b) in enumerate(zip(ref, sig)) if a != b),
                   min(len(ref), len(sig)))
        a = ref[pos] if pos < len(ref) else None
        b = sig[pos] if pos < len(sig) else None
        findings.append(Finding(
            rule_id=rule_id, severity="error",
            message=(f"collective sequence diverges between {ref_name!r} "
                     f"({len(ref)} collectives) and {name!r} ({len(sig)}) "
                     f"at position {pos}: {a} vs {b} — this program pair "
                     "deadlocks or silently diverges on a shared mesh"),
            detail={"parties": [ref_name, name], "position": pos,
                    "reference": a, "divergent": b}))
    return findings


def audit_cross_party(configs: Mapping[str, Any],
                      build: Optional[Callable[[Any], Any]] = None,
                      rule_id: str = "GX-COLLECTIVE-001") -> List[Finding]:
    """Diff the collective signature of a step program across party
    configurations — the trace-time form of "would this deployment
    deadlock at 2x2 mesh scale".

    ``configs`` maps a party label to any of: a (closed) jaxpr, a
    zero-arg callable returning one, or — with ``build`` given — an
    opaque config object ``build`` turns into a jaxpr.  Signatures are
    extracted per party and diffed against the first entry.  Empty
    result = every party traces the same collective program.
    """
    sigs: Dict[str, tuple] = {}
    for name, cfg in configs.items():
        if build is not None:
            jx = build(cfg)
        elif callable(cfg) and not hasattr(cfg, "eqns") \
                and not hasattr(cfg, "jaxpr"):
            jx = cfg()
        else:
            jx = cfg
        sigs[name] = (jx if isinstance(jx, tuple)
                      else collective_signature(jx))
    return diff_collective_signatures(sigs, rule_id=rule_id)


class CollectiveConsistencyPass(AuditPass):
    """Single-program form: record the signature into ``ctx.extras``
    (for cross-program diffing by the caller) and flag constructs that
    make per-party program shape diverge by design —
    ``axis_index_groups`` partitions a named axis into subgroups, so two
    parties' traces only match if every party computed the same groups."""

    rule_id = "GX-COLLECTIVE-001"

    def run(self, jaxpr, ctx: AuditContext) -> List[Finding]:
        findings: List[Finding] = []
        ctx.extras["collective_signature"] = collective_signature(jaxpr)
        for site in walk_jaxpr(jaxpr):
            if site.primitive not in COLLECTIVE_PRIMS:
                continue
            if site.eqn.params.get("axis_index_groups") is not None:
                findings.append(self.finding(
                    f"{site.primitive} uses axis_index_groups: subgroup "
                    "membership is baked per trace and diverges across "
                    "parties unless every party derives identical groups",
                    site=site, severity="warning"))
        return findings


# ---------------------------------------------------------------------------
# donation / aliasing
# ---------------------------------------------------------------------------

# StableHLO argument attributes jax emits for donation.  Unsharded jit:
# an aliased donor carries tf.aliasing_output = <result index>; a donor
# the program still needs (read after every aliasing opportunity) is
# left attribute-free and jax warns "Some donated buffers were not
# usable".  Sharded (shard_map/NamedSharding) programs defer the
# decision to the compiler and mark every donor jax.buffer_donor=true —
# the verdict then lives in the compiled module's input_output_alias
# table (:func:`parse_compiled_aliases`).
_ALIAS_ATTR = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DONOR_ATTR = re.compile(r"jax\.buffer_donor\s*=\s*true")
_TENSOR_TY = re.compile(r"tensor<([0-9x]*)x?([a-z][a-z0-9]+)>")
_COMPILED_ALIAS = re.compile(r"\{[0-9, ]*\}:\s*\((\d+)")

# MLIR element types -> numpy dtype names (the subset this codebase
# puts on program boundaries)
_MLIR_DTYPES = {"f64": "float64", "f32": "float32", "f16": "float16",
                "bf16": "bfloat16", "i64": "int64", "i32": "int32",
                "i16": "int16", "i8": "int8", "ui8": "uint8",
                "ui32": "uint32", "i1": "bool"}


def _main_args(lowered_text: str) -> List[dict]:
    """Parse the entry computation's argument list out of StableHLO
    text: per-arg tensor type plus donation/aliasing attributes."""
    m = re.search(r"func\.func\s+(?:public\s+)?@main\s*\((.*?)\)\s*->",
                  lowered_text, re.S)
    if not m:
        return []
    args: List[dict] = []
    # split on "%argN:" boundaries — attribute dicts contain commas, so a
    # naive comma split would shred them
    for piece in re.split(r"%arg\d+\s*:", m.group(1))[1:]:
        ty = _TENSOR_TY.search(piece)
        dims, dtype = (ty.group(1), ty.group(2)) if ty else ("", "?")
        shape = tuple(int(d) for d in dims.split("x") if d) if dims else ()
        size = 1
        for d in shape:
            size *= d
        alias = _ALIAS_ATTR.search(piece)
        args.append({
            "shape": shape, "dtype": _MLIR_DTYPES.get(dtype, dtype),
            "size": size,
            "aliased_output": int(alias.group(1)) if alias else None,
            "donor_deferred": bool(_DONOR_ATTR.search(piece)),
        })
    return args


def parse_compiled_aliases(compiled_text: str) -> frozenset:
    """Parameter indices the compiled module's ``input_output_alias``
    table aliases into outputs (``jax.stages.Compiled.as_text()``) —
    the ground truth for sharded programs whose StableHLO only says
    ``jax.buffer_donor``."""
    start = compiled_text.find("input_output_alias={")
    if start < 0:
        return frozenset()
    i = compiled_text.index("{", start)
    depth = 0
    for j in range(i, len(compiled_text)):
        if compiled_text[j] == "{":
            depth += 1
        elif compiled_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
    else:
        return frozenset()
    body = compiled_text[i + 1:j]
    return frozenset(int(p) for p in _COMPILED_ALIAS.findall(body))


class DonationPass(AuditPass):
    """Donation honesty on a lowered program (``ctx.lowered_text``):

    - GX-DONATE-001: a donated argument with no aliased output — the
      program reads the buffer after every chance to reuse it, so the
      caller loses the buffer AND the memory saving.  Donated flat-arg
      positions come from ``ctx.extras["donated_positions"]`` (this
      jaxlib leaves unusable donors attribute-free in unsharded module
      text, so intent must ride in from the caller) plus any arg the
      text itself marks.  A ``jax.buffer_donor`` arg defers the verdict
      to the compiler: it is judged against
      ``ctx.extras["compiled_alias_params"]``
      (:func:`parse_compiled_aliases`) when given, and left unjudged
      otherwise;
    - GX-DONATE-002: an expected-aliased buffer signature
      (``ctx.extras["expect_aliased"]``, e.g. the EF-residual and
      pipeline double-buffer leaves) has no aliased argument of that
      shape/dtype — the state round-trip reallocates every step.
    """

    rule_id = "GX-DONATE-001"

    def run(self, jaxpr, ctx: AuditContext) -> List[Finding]:
        text = ctx.lowered_text
        if not text:
            return []
        args = _main_args(text)
        donated = set(ctx.extras.get("donated_positions", ()))
        donated.update(i for i, a in enumerate(args)
                       if a["donor_deferred"]
                       or a["aliased_output"] is not None)
        compiled = ctx.extras.get("compiled_alias_params")
        findings: List[Finding] = []

        def _is_aliased(i, a):
            if a["aliased_output"] is not None:
                return True
            if compiled is not None:
                return i in compiled
            # deferred donor with no compiled table: unjudgeable — only
            # a donation the LOWERING already dropped is a finding
            return a["donor_deferred"]

        for i, a in enumerate(args):
            if i in donated and not _is_aliased(i, a):
                findings.append(self.finding(
                    f"donated arg {i} ({a['shape']} {a['dtype']}) has no "
                    "aliased output: the program still reads the buffer "
                    "after donation — drop the donation or restructure "
                    "so an output can reuse it",
                    detail={"arg": i, "shape": list(a["shape"]),
                            "dtype": a["dtype"]}))
        aliased = [(a["shape"], a["dtype"]) for i, a in enumerate(args)
                   if a["aliased_output"] is not None
                   or (compiled is not None and i in compiled)]
        for shape, dtype in ctx.extras.get("expect_aliased", ()):
            want = (tuple(shape), str(dtype))
            if want in aliased:
                aliased.remove(want)  # each expectation consumes one slot
                continue
            findings.append(self.finding(
                f"expected donated buffer {want[0]} {want[1]} (EF "
                "residual / pipeline double-buffer) is not covered by "
                "input_output_aliases — the sync state reallocates "
                "instead of updating in place",
                rule_id="GX-DONATE-002",
                detail={"shape": list(want[0]), "dtype": want[1]}))
        return findings


def audit_donation(fn: Callable, *args,
                   donate_argnums: Tuple[int, ...] = (),
                   expect_aliased: Sequence[Tuple[Sequence[int], str]] = (),
                   static_argnums: Tuple[int, ...] = ()) -> List[Finding]:
    """Lower ``fn`` with the given donation and run :class:`DonationPass`
    on the module text (suppressing jax's lowering-time warning — the
    pass reports the same fact as a structured finding).  Lowered with
    ``keep_unused=True`` so flat-argument positions stay 1:1 with the
    call signature and the donated set maps exactly."""
    import warnings

    import jax

    # map donated argnums to flattened argument positions (a pytree arg
    # contributes one flat position per leaf)
    pos = 0
    donated_positions = []
    for i, a in enumerate(args):
        nleaves = len(jax.tree.leaves(a))
        if i in donate_argnums:
            donated_positions.extend(range(pos, pos + nleaves))
        pos += nleaves
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        low = jax.jit(fn, donate_argnums=donate_argnums,
                      static_argnums=static_argnums,
                      keep_unused=True).lower(*args)
    ctx = AuditContext(lowered_text=low.as_text(),
                       extras={"expect_aliased": tuple(expect_aliased),
                               "donated_positions": donated_positions})
    return DonationPass().run(None, ctx)


# ---------------------------------------------------------------------------
# dtype-flow
# ---------------------------------------------------------------------------

class DtypeFlowPass(AuditPass):
    """GX-DTYPE-001: fp32 heavy-compute ops (dot/conv) on a path that
    declares 16-bit compute (``ctx.compute_dtype`` of "bfloat16" or
    "float16").  A leak burns double the MXU/HBM bandwidth the
    declaration promised and usually enters through one forgotten
    ``astype`` on a residual branch."""

    rule_id = "GX-DTYPE-001"

    def run(self, jaxpr, ctx: AuditContext) -> List[Finding]:
        declared = ctx.compute_dtype
        if declared not in ("bfloat16", "float16"):
            return []
        findings: List[Finding] = []
        for site in walk_jaxpr(jaxpr):
            if site.primitive not in _HEAVY_COMPUTE_PRIMS:
                continue
            op_dtypes = {aval_sig(v.aval)[1] for v in site.eqn.invars
                         if hasattr(v, "aval")}
            if "float32" in op_dtypes or "float64" in op_dtypes:
                findings.append(self.finding(
                    f"{site.primitive} computes in "
                    f"{sorted(op_dtypes & {'float32', 'float64'})} on a "
                    f"declared-{declared} path (fp32 leak)",
                    site=site,
                    detail={"operand_dtypes": sorted(op_dtypes)}))
        return findings


def audit_dtype_flow(fn: Callable, *args,
                     compute_dtype: str = "bfloat16") -> List[Finding]:
    """Trace ``fn`` and run the fp32-leak rule against the declared
    compute dtype."""
    import jax
    jx = jax.make_jaxpr(fn)(*args)
    return DtypeFlowPass().run(jx, AuditContext(compute_dtype=compute_dtype))


def audit_precision(fn: Callable, *args, precision: str = "bf16",
                    allowed_fp32_sites: int = 0) -> List[Finding]:
    """GX-DTYPE-001 for the first-class precision mode
    (``GEOMX_PRECISION``): audit a forward/loss closure built for
    ``precision`` and return the fp32 heavy-compute leaks.

    ``allowed_fp32_sites`` drops that many TRAILING findings before
    returning: the zoo's models intentionally compute the classifier
    head in fp32 (the last heavy op in the forward — softmax stability
    next to an fp32 loss), so a legitimately-built bf16 model audits
    clean with ``allowed_fp32_sites=1`` while a leak anywhere earlier
    in the network still surfaces.  ``precision="fp32"`` always returns
    [] (there is no declaration to violate)."""
    if str(precision).lower() in ("fp32", "float32", "f32"):
        return []
    findings = audit_dtype_flow(fn, *args, compute_dtype="bfloat16")
    if allowed_fp32_sites > 0:
        findings = findings[:-allowed_fp32_sites] \
            if len(findings) > allowed_fp32_sites else []
    return findings


def _traced_allreduce_jaxpr(compressor, params, num_parties: int = 2):
    """Trace ``compressor.allreduce`` over a ``num_parties``-wide dc
    mesh (virtual devices are fine: the jaxpr is platform-independent),
    returning the closed jaxpr.  The shared harness for the wire-
    accounting and purity audits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.parallel.collectives import shard_map_compat
    from geomx_tpu.topology import DC_AXIS

    devs = jax.devices()
    if len(devs) < num_parties:
        raise RuntimeError(
            f"audit needs {num_parties} devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_parties})")
    mesh = Mesh(np.array(devs[:num_parties]), (DC_AXIS,))
    state = compressor.init_state(params)

    def f(gs, ss):
        g = jax.tree.map(lambda a: a[0], gs)
        s = jax.tree.map(lambda a: a[0], ss)
        out, s2 = compressor.allreduce(g, s, DC_AXIS, num_parties)
        return (jax.tree.map(lambda a: a[None], out),
                jax.tree.map(lambda a: a[None], s2))

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS), P(DC_AXIS)),
                          out_specs=(P(DC_AXIS), P(DC_AXIS)))
    def stack(t):
        return jax.tree.map(
            lambda a: jnp.stack([jnp.asarray(a)] * num_parties), t)

    return jax.make_jaxpr(fn)(stack(params), stack(state))


# scatter-family primitives whose per-chip bytes differ from the
# "operand counts once" allreduce convention: a reduce_scatter
# (lax.psum_scatter) sends (N-1)/N of its full-size operand per chip,
# an all_gather forwards this chip's shard-size operand to N-1 peers.
# Both carry the mesh width in eqn.params["axis_size"].
_SCATTER_PRIMS = frozenset({"psum_scatter", "reduce_scatter"})
_GATHER_PRIMS = frozenset({"all_gather", "all_gather_invariant"})


def _collective_axis_size(eqn) -> Optional[int]:
    n = eqn.params.get("axis_size")
    try:
        return int(n) if n else None
    except (TypeError, ValueError):
        return None


def collective_wire_bytes(jaxpr, convention: str = "per_chip") -> int:
    """Bytes one participant puts on the wire per execution of the
    traced program, summed over its collectives' operands — the
    jaxpr-derived ground truth ``Compressor.wire_bytes`` must agree
    with.

    ``convention="per_chip"`` (default) counts physical bytes each chip
    sends per execution:

    - ``psum`` family: the operand counts once — the party's payload,
      the reference's ps-lite byte-counter convention;
    - ``psum_scatter`` / ``reduce_scatter``: the chip keeps its own 1/N
      shard, so it sends ``(N-1)/N`` of the full-size operand (the
      allreduce convention hard-coded here before the ZeRO path would
      overcount the kept shard);
    - ``all_gather``: the operand is this chip's shard and travels to
      every one of the N-1 peers, so it counts ``(N-1)`` times.

    ``N`` comes from the equation's ``axis_size`` param; a collective
    without one falls back to the operand-once convention.

    ``convention="payload"`` counts every collective operand exactly
    once — the N-independent per-party *contribution* convention that
    ``Compressor.wire_bytes`` declares (a psum's ring factor and a
    gather's (N-1) fan-out are transport properties, not payload)."""
    if convention not in ("per_chip", "payload"):
        raise ValueError(f"unknown wire-byte convention {convention!r}")
    total = 0.0
    for site in walk_jaxpr(jaxpr):
        if site.primitive not in COLLECTIVE_PRIMS:
            continue
        opb = sum(aval_bytes(v.aval) for v in site.eqn.invars
                  if hasattr(v, "aval"))
        n = _collective_axis_size(site.eqn)
        if convention == "payload":
            total += opb
        elif n and site.primitive in _SCATTER_PRIMS:
            total += opb * (n - 1) / n
        elif n and site.primitive in _GATHER_PRIMS:
            total += opb * (n - 1)
        else:
            total += opb
    return int(round(total))


def audit_wire_accounting(compressor, params, num_parties: int = 2,
                          rel_tol: float = 0.01,
                          abs_tol: int = 512) -> List[Finding]:
    """GX-DTYPE-002: diff ``compressor.wire_bytes(params)`` against the
    bytes the traced dc-tier collectives actually carry.  An accounting
    that under-reports hides wire cost from every telemetry consumer
    (``dc_compression_ratio``, byte counters); one that
    hardcodes fp32 for a 16-bit wire inflates it 2x.  Tolerances absorb
    lane padding (``abs_tol`` per program) and rounding.

    The diff runs under the *payload* convention (each collective
    operand once): ``wire_bytes`` documents the party's N-independent
    contribution, and an all_gather-emulated allreduce (bsc/fp16/2bit)
    fans that same payload to N-1 peers — per-chip counting would flag
    every honest gather-based compressor at ``num_parties > 2``.  A
    scatter+gather decomposition declared with the plain allreduce
    convention still trips the gate: its traced payload is the full
    operand plus the gathered shard, 1+1/N times the declared bytes."""
    jx = _traced_allreduce_jaxpr(compressor, params, num_parties)
    traced = collective_wire_bytes(jx, convention="payload")
    declared = int(compressor.wire_bytes(params))
    gap = abs(traced - declared)
    if gap <= abs_tol or gap <= rel_tol * max(traced, declared):
        return []
    return [Finding(
        rule_id="GX-DTYPE-002", severity="error",
        message=(f"wire accounting mismatch for compressor "
                 f"{compressor.name!r}: wire_bytes() declares {declared} "
                 f"B/party/step but the traced collectives carry "
                 f"{traced} B ({gap} B apart)"),
        detail={"declared": declared, "traced": traced,
                "compressor": compressor.name})]


# ---------------------------------------------------------------------------
# compressed-path purity
# ---------------------------------------------------------------------------

# scatter-family prims: the ops that MATERIALIZE a dense buffer from a
# sparse stream (the decompress).  The post-collective merge rule counts
# these — sort/cumsum stay out (they appear legitimately inside a later
# bucket's pre-collective select in multi-bucket programs)
_DENSIFY_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"})


class PurityPass(AuditPass):
    """GX-PURITY-001, both sides of the compressed dc path:

    - *compress-before-collective* (the original rule): a collective
      operand whose byte size reaches ``ctx.dense_bytes`` (the dense
      fp32 footprint of the largest bucket/leaf the compressor covers)
      means a dense intermediate crossed select/pack and the collective
      (the decompress-before-collective regression class);
    - *merge-without-densify* (the post-collective side): after the
      FINAL collective, the merged sparse stream may densify at most
      ``ctx.extras["allowed_dense_after_collective"]`` times (default
      1 — the single final decompress).  A per-party densify-then-sum
      merge materializes one dense scatter per party and is flagged
      here even though its wire payloads were all compressed.  The
      anchor is the last collective (not every collective) so a later
      bucket's pre-collective select chain in a multi-bucket program
      never reads as "post-collective" of an earlier bucket.

    Reusable against any bucket size and both the jnp and fused paths:
    the fused kernels are opaque calls, so only genuinely wire-bound
    avals and true XLA scatters are inspected."""

    rule_id = "GX-PURITY-001"

    def run(self, jaxpr, ctx: AuditContext) -> List[Finding]:
        dense = ctx.dense_bytes
        if not dense:
            return []
        findings: List[Finding] = []
        sites = list(walk_jaxpr(jaxpr))
        last_collective = -1
        for i, site in enumerate(sites):
            if site.primitive not in COLLECTIVE_PRIMS:
                continue
            last_collective = i
            for v in site.eqn.invars:
                if not hasattr(v, "aval"):
                    continue
                nbytes = aval_bytes(v.aval)
                if nbytes >= dense:
                    shape, dtype = aval_sig(v.aval)
                    findings.append(self.finding(
                        f"{site.primitive} puts a dense-size operand "
                        f"({shape} {dtype}, {nbytes} B >= dense "
                        f"{dense} B) on the compressed dc path — a "
                        "dense intermediate leaked between select/pack "
                        "and the collective",
                        site=site,
                        detail={"bytes": nbytes, "dense_bytes": dense,
                                "shape": list(shape), "dtype": dtype}))
        if last_collective < 0:
            return findings
        allowed = int(ctx.extras.get("allowed_dense_after_collective", 1))
        densifies = 0
        for site in sites[last_collective + 1:]:
            if site.primitive not in _DENSIFY_PRIMS:
                continue
            for v in site.eqn.outvars:
                if not hasattr(v, "aval") or aval_bytes(v.aval) < dense:
                    continue
                densifies += 1
                if densifies > allowed:
                    shape, dtype = aval_sig(v.aval)
                    findings.append(self.finding(
                        f"{site.primitive} materializes dense output "
                        f"#{densifies} ({shape} {dtype}) after the final "
                        f"collective (allowed: {allowed}) — the merge "
                        "densifies per party instead of combining in "
                        "the compressed domain",
                        site=site,
                        detail={"densify_count": densifies,
                                "allowed": allowed,
                                "shape": list(shape), "dtype": dtype}))
        return findings


def _dense_floor_bytes(compressor, params) -> int:
    """The dense fp32 footprint of the largest unit the compressor
    sparsifies: the largest bucket for tree-fusing compressors, the
    largest sparse-eligible leaf otherwise (leaves below
    ``min_sparse_size``/``size_lower_bound`` legitimately go dense)."""
    import jax
    leaves = jax.tree.leaves(params)
    bucketer = getattr(compressor, "_bucketer", None)
    if callable(bucketer):
        bk = bucketer(leaves)
        if bk.bucket_sizes:
            return 4 * max(bk.bucket_sizes)
    floor = max((getattr(compressor, "min_sparse_size", 1),
                 getattr(compressor, "size_lower_bound", 1)))
    eligible = [leaf.size for leaf in leaves if leaf.size >= floor]
    return 4 * max(eligible) if eligible else 0


def audit_zero_compressed_path(bucketed, params, num_shards: int,
                               num_parties: int = 2) -> List[Finding]:
    """GX-PURITY-001 for the ZeRO dc tier (train/zero.py): trace the
    per-shard compressed allreduce (``BucketedCompressor.
    allreduce_shards``) over a dc mesh and require every wire payload to
    stay below the *shard*-dense floor — the shard path's stronger form
    of the purity claim: not only does no bucket-dense intermediate
    cross the wire, no chip even materializes one on the dc tier.
    Dense inner compressors are skipped like :func:`audit_compressed_path`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.parallel.collectives import shard_map_compat
    from geomx_tpu.topology import DC_AXIS

    leaves = jax.tree.leaves(params)
    bk = bucketed.zero_bucketer(leaves)
    if not bk.bucket_sizes:
        return []
    shard_sizes = [n // num_shards for n in bk.bucket_sizes]
    dense_shard = 4 * max(shard_sizes)
    wire = int(bucketed.shard_wire_bytes(params, num_shards))
    if wire >= 4 * sum(shard_sizes):
        return []  # dense inner compressor: nothing to audit
    devs = jax.devices()
    if len(devs) < num_parties:
        raise RuntimeError(
            f"audit needs {num_parties} devices for the dc axis (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_parties})")
    mesh = Mesh(np.array(devs[:num_parties]), (DC_AXIS,))
    shards = [jnp.zeros((s,), jnp.float32) for s in shard_sizes]
    state = bucketed.init_shard_state(params, num_shards)

    def f(sh, ss):
        sh = [a[0] for a in sh]
        s = jax.tree.map(lambda a: a[0], ss)
        out, s2 = bucketed.allreduce_shards(sh, s, DC_AXIS, num_parties,
                                            bk)
        return ([a[None] for a in out],
                jax.tree.map(lambda a: a[None], s2))

    fn = shard_map_compat(f, mesh, in_specs=(P(DC_AXIS), P(DC_AXIS)),
                          out_specs=(P(DC_AXIS), P(DC_AXIS)))

    def stack(t):
        return jax.tree.map(
            lambda a: jnp.stack([jnp.asarray(a)] * num_parties), t)

    jx = jax.make_jaxpr(fn)(stack(shards), stack(state))
    return PurityPass().run(jx, AuditContext(dense_bytes=dense_shard))


def audit_compressed_path(compressor, params,
                          num_parties: int = 2) -> List[Finding]:
    """Trace the compressor's dc-tier allreduce over ``params`` and run
    :class:`PurityPass` with the dense floor derived from the
    compressor's own layout.  Dense compressors (``wire_bytes`` == dense
    fp32 bytes) are skipped — purity is a property of compressed paths."""
    import jax
    leaves = jax.tree.leaves(params)
    dense_fp32 = sum(leaf.size * 4 for leaf in leaves)
    wire = int(compressor.wire_bytes(params))
    if wire >= dense_fp32:
        return []  # dense path: nothing to audit
    dense_bytes = _dense_floor_bytes(compressor, params)
    if not dense_bytes:
        return []
    jx = _traced_allreduce_jaxpr(compressor, params, num_parties)
    # NOTE: device-local dense materializations (the jnp select chain's
    # cumsum/scatter) are legitimate here — the fused-path structural
    # claim that those ops are GONE from the lowered HLO lives in
    # analysis/hlo.py, not in this wire-purity rule.
    return PurityPass().run(jx, AuditContext(dense_bytes=dense_bytes))
