"""Graft Auditor suite (geomx_tpu/analysis/, docs/analysis.md).

Four layers of evidence, all CPU:

- *Framework*: the jaxpr walker sees nested equations with provenance,
  findings gate on severity, the config surface parses like every other
  GEOMX_* knob.
- *Known-bad corpus*: every seeded defect program (divergent
  collectives, read-after-donate, fp32 leak, lying wire accounting,
  dense compressed path) is flagged with exactly its rule id.
- *Green set*: every tier-1 training configuration's step program
  (vanilla, bsc, MPQ, pipelined, degraded-membership) audits to ZERO
  findings — the auditor doesn't cry wolf.
- *Boundary wiring*: ``audit_cross_party`` proves 2-party signature
  equality and catches an injected divergence; the Trainer runs the
  diff at the ``apply_membership`` recompile boundary and raises
  ``AuditError`` past the severity gate.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from geomx_tpu.analysis import (AuditContext, AuditError,
                                CollectiveConsistencyPass, DonationPass,
                                Finding, audit_compressed_path,
                                audit_cross_party, audit_donation,
                                audit_dtype_flow, audit_enabled,
                                audit_severity_gate,
                                audit_wire_accounting,
                                collective_signature,
                                diff_collective_signatures, enforce,
                                summarize, walk_jaxpr)
from geomx_tpu.analysis.corpus import CORPUS
from geomx_tpu.config import GeoConfig
from geomx_tpu.models import get_model
from geomx_tpu.sync import get_sync_algorithm
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer


# --------------------------------------------------------------------------
# framework
# --------------------------------------------------------------------------

def test_walker_sees_nested_equations_with_provenance():
    def inner(x):
        return jnp.sin(x) * 2.0

    def outer(x):
        y = jax.jit(inner)(x)
        return jax.lax.scan(lambda c, v: (c + v, v), 0.0, y)[0]

    jx = jax.make_jaxpr(outer)(jnp.zeros((8,)))
    prims = [(s.primitive, s.path) for s in walk_jaxpr(jx)]
    names = [p for p, _ in prims]
    assert "jit" in names and "scan" in names
    # nested ops carry the enclosing call path
    assert any(p == "sin" and "jit" in path for p, path in prims)
    assert any("scan" in path for _, path in prims)
    # walk order is stable across identical traces
    jx2 = jax.make_jaxpr(outer)(jnp.zeros((8,)))
    assert prims == [(s.primitive, s.path) for s in walk_jaxpr(jx2)]


def test_finding_severity_gate_and_enforce():
    ferr = Finding("GX-X-001", "error", "boom")
    fwarn = Finding("GX-X-002", "warning", "meh")
    # below the gate: returned, not raised
    assert enforce([fwarn], "error") == [fwarn]
    with pytest.raises(AuditError) as ei:
        enforce([fwarn, ferr], "error")
    assert "GX-X-001" in str(ei.value)
    assert ei.value.findings == [fwarn, ferr]
    with pytest.raises(AuditError):
        enforce([fwarn], "warning")
    assert summarize([ferr, fwarn, ferr]) == {"GX-X-001": 2, "GX-X-002": 1}
    with pytest.raises(ValueError):
        Finding("GX-X-003", "fatal", "bad severity")


def test_audit_gate_parses_like_other_knobs(monkeypatch):
    monkeypatch.delenv("GEOMX_AUDIT", raising=False)
    assert audit_enabled() is False
    assert audit_enabled(GeoConfig(audit=True)) is True
    monkeypatch.setenv("GEOMX_AUDIT", "1")
    assert audit_enabled() is True
    monkeypatch.setenv("GEOMX_AUDIT_SEVERITY", "warning")
    assert audit_severity_gate() == "warning"
    monkeypatch.setenv("GEOMX_AUDIT_SEVERITY", "fatal")
    with pytest.raises(ValueError):
        audit_severity_gate()


# --------------------------------------------------------------------------
# collective signatures
# --------------------------------------------------------------------------

def _dc_trace(body, n=64):
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.parallel.collectives import shard_map_compat
    mesh = Mesh(np.array(jax.devices()[:2]), ("dc",))
    fn = shard_map_compat(body, mesh, in_specs=(P("dc"),),
                          out_specs=P("dc"))
    return jax.make_jaxpr(fn)(jnp.zeros((2, n), jnp.float32))


def test_signature_normalizes_fused_vs_per_leaf_psum():
    """lax.pmean over a dict traces ONE psum with N operands; tree.map
    traces N psums of one operand.  XLA's all-reduce combiner makes the
    packaging a non-invariant — the signatures must compare equal."""
    def fused(v):
        d = {"a": v, "b": v * 2.0}
        out = jax.lax.pmean(d, "dc")
        return out["a"] + out["b"]

    def per_leaf(v):
        d = {"a": v, "b": v * 2.0}
        out = jax.tree.map(lambda x: jax.lax.psum(x, "dc") / 2.0, d)
        return out["a"] + out["b"]

    assert collective_signature(_dc_trace(fused)) == \
        collective_signature(_dc_trace(per_leaf))


def test_signature_carries_op_axes_shape_dtype_and_routing():
    def body(v):
        p = jax.lax.ppermute(v, "dc", [(0, 1), (1, 0)])
        return jax.lax.psum(v.astype(jnp.bfloat16), "dc") \
            .astype(jnp.float32) + p

    sig = collective_signature(_dc_trace(body))
    ops = [(op, axes, sd) for op, axes, sd, _extras in sig]
    assert ("ppermute", ("dc",), ((1, 64), "float32")) in ops
    assert ("psum", ("dc",), ((1, 64), "bfloat16")) in ops
    perm = [extras for op, _, _, extras in sig if op == "ppermute"][0]
    assert ("perm", ((0, 1), (1, 0))) in perm


def test_diff_names_first_divergent_position():
    def a(v):
        return jax.lax.psum(v, "dc")

    def b(v):
        return jax.lax.psum(v, "dc") + jax.lax.psum(v * 2, "dc")

    findings = diff_collective_signatures(
        {"p0": collective_signature(_dc_trace(a)),
         "p1": collective_signature(_dc_trace(b))})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule_id == "GX-COLLECTIVE-001" and f.severity == "error"
    assert f.detail["position"] == 1  # the extra psum
    assert "p1" in f.message and "deadlock" in f.message


def test_axis_index_groups_warns():
    def body(v):
        return jax.lax.psum(v, "dc", axis_index_groups=[[0], [1]])

    ctx = AuditContext()
    findings = CollectiveConsistencyPass().run(_dc_trace(body), ctx)
    assert [f.severity for f in findings] == ["warning"]
    assert "axis_index_groups" in findings[0].message
    # the signature still landed in the context for cross-program diffs
    assert len(ctx.extras["collective_signature"]) == 1


# --------------------------------------------------------------------------
# wire accounting: scatter-family per-chip conventions
# --------------------------------------------------------------------------

def test_collective_wire_bytes_scatter_family_counts_per_chip():
    """psum counts its operand once (the allreduce convention);
    psum_scatter sends (N-1)/N of its full operand per chip;
    all_gather forwards the shard operand to N-1 peers.  The hard-coded
    operand-once convention used to overcount the scatter's kept shard
    and undercount the gather at N > 2 — the ZeRO weight path is built
    from exactly these two."""
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.analysis.passes import collective_wire_bytes
    from geomx_tpu.parallel.collectives import shard_map_compat

    n_axis, n = 4, 1024
    mesh = Mesh(np.array(jax.devices()[:n_axis]), ("w",))

    def trace(body):
        fn = shard_map_compat(body, mesh, in_specs=(P("w"),),
                              out_specs=P("w"))
        return jax.make_jaxpr(fn)(jnp.zeros((n_axis, n), jnp.float32))

    def allreduce(v):
        return lax.psum(v, "w")

    def scatter_gather(v):
        sh = lax.psum_scatter(v[0].reshape(n_axis, n // n_axis), "w",
                              scatter_dimension=0)
        return lax.all_gather(sh, "w").reshape(1, n)

    assert collective_wire_bytes(trace(allreduce)) == 4 * n
    expect = 4 * n * (n_axis - 1) / n_axis \
        + 4 * (n // n_axis) * (n_axis - 1)
    assert collective_wire_bytes(trace(scatter_gather)) == int(expect)
    # the payload convention stays N-independent: every operand once
    assert collective_wire_bytes(trace(allreduce),
                                 convention="payload") == 4 * n
    assert collective_wire_bytes(trace(scatter_gather),
                                 convention="payload") \
        == 4 * n + 4 * (n // n_axis)


def test_wire_audit_keeps_honest_gather_compressors_clean_at_n4():
    """bsc/fp16/2bit emulate the dc allreduce with lax.all_gather and
    declare the documented per-party payload (operand once).  The audit
    diffs in that payload convention, so the gather's physical (N-1)
    fan-out must NOT flag them at num_parties > 2 — while the
    scatter_wire_lie corpus entry (operand + shard vs declared operand)
    still trips the gate at the same width."""
    from geomx_tpu.analysis.corpus import CORPUS
    from geomx_tpu.analysis.passes import audit_wire_accounting
    from geomx_tpu.compression import get_compressor

    params = {"w": jnp.zeros((4096,), jnp.float32)}
    for spec in ("fp16", "bsc,0.01", "2bit"):
        findings = audit_wire_accounting(get_compressor(spec), params,
                                         num_parties=4)
        assert findings == [], (spec, [f.message for f in findings])
    lie = next(e for e in CORPUS if e.name == "scatter_wire_lie").run()
    assert {f.rule_id for f in lie} == {"GX-DTYPE-002"}


# --------------------------------------------------------------------------
# weight_path_collectives: what survives elimination down to kept fields
# --------------------------------------------------------------------------

class _ToyState(NamedTuple):
    params: jax.Array
    opt_state: jax.Array
    model_state: jax.Array


def _toy_step(body):
    """``step(state, xb, yb) -> (new_state, metrics)`` whose per-device
    ``body(p, o, m, x)`` returns the three new fields, on a 2x2
    (dc, worker) mesh."""
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.parallel.collectives import shard_map_compat
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("dc", "worker"))
    spec = P("dc", "worker")
    fn = shard_map_compat(body, mesh, in_specs=(spec,) * 4,
                          out_specs=(spec,) * 3)

    def step(state, xb, yb):
        p, o, m = fn(state.params, state.opt_state, state.model_state, xb)
        return _ToyState(p, o, m), {"loss": jnp.sum(yb)}

    z = jnp.zeros((2, 2, 8), jnp.float32)
    return step, _ToyState(z, z, z), z, z


def test_weight_path_leaves_out_a_collective_feeding_no_kept_field():
    from geomx_tpu.analysis.passes import weight_path_collectives

    def body(p, o, m, x):
        g = jax.lax.psum(x, "worker")
        return p - g, o + g, jax.lax.pmean(m + x, "dc")

    on_path, whole = weight_path_collectives(*_toy_step(body))
    assert on_path == {"worker": {"psum": 1}}
    assert whole == {"worker": {"psum": 1}, "dc": {"psum": 1}}
    # name the field and the statistic's collective is on the path
    on_path, _ = weight_path_collectives(
        *_toy_step(body), keep=("params", "opt_state", "model_state"))
    assert on_path == whole


def test_weight_path_counts_by_axis_and_primitive():
    from geomx_tpu.analysis.passes import weight_path_collectives

    def body(p, o, m, x):
        shard = jax.lax.psum_scatter(x, "worker", scatter_dimension=2,
                                     tiled=True)
        g = jax.lax.all_gather(shard, "worker", axis=2, tiled=True)
        g = jax.lax.psum(g, ("dc", "worker"))   # counts under both axes
        return p - g, o + jax.lax.psum(x, "dc"), m

    on_path, whole = weight_path_collectives(*_toy_step(body))
    assert on_path == whole == {
        "worker": {"reduce_scatter": 1, "all_gather": 1, "psum": 1},
        "dc": {"psum": 2}}
    # only params kept: opt_state's own dc psum falls away
    on_path, _ = weight_path_collectives(*_toy_step(body),
                                         keep=("params",))
    assert on_path["dc"] == {"psum": 1}


def test_weight_path_enters_nested_programs():
    """Collectives under jit inside shard_map and in both branches of a
    cond are found, and eliminated with the branch output they feed."""
    from geomx_tpu.analysis.passes import weight_path_collectives

    @jax.jit
    def inner(x):
        return jax.lax.psum(x, "worker")

    def body(p, o, m, x):
        g = inner(x)
        kept, dropped = jax.lax.cond(
            jnp.sum(x) > 0,
            lambda v: (jax.lax.psum(v, "dc"),
                       jax.lax.ppermute(v, "dc", [(0, 1), (1, 0)])),
            lambda v: (jax.lax.psum(2 * v, "dc"), v), g)
        return p - kept, o, m + dropped

    on_path, whole = weight_path_collectives(*_toy_step(body))
    assert whole["worker"] == {"psum": 1}
    assert whole["dc"] == {"psum": 2, "ppermute": 1}
    assert on_path == {"worker": {"psum": 1}, "dc": {"psum": 2}}


# --------------------------------------------------------------------------
# known-bad corpus: every entry flagged with exactly its rule id
# --------------------------------------------------------------------------

@pytest.mark.parametrize("entry", CORPUS, ids=[e.name for e in CORPUS])
def test_corpus_program_flagged_with_right_rule(entry):
    findings = entry.run()
    rules = {f.rule_id for f in findings}
    assert entry.expected_rule in rules, \
        f"{entry.name} not flagged: {[f.format() for f in findings]}"
    # precision: a bad program must not shotgun unrelated rules
    assert rules == {entry.expected_rule}, rules
    for f in findings:
        assert f.severity == "error"
        assert f.message


# --------------------------------------------------------------------------
# green tier-1 step programs: zero findings
# --------------------------------------------------------------------------

GREEN_CONFIGS = (
    ("vanilla", {"compression": "none"}),
    ("bsc", {"compression": "bsc,0.05,min_sparse_size=16"}),
    ("bsc_sparseagg",
     {"compression": "bsc,0.05,min_sparse_size=16,sparse_agg=1"}),
    ("mpq", {"compression": "mpq,0.05"}),
    ("pipelined", {"compression": "none", "pipeline_depth": 1}),
    ("degraded", {"compression": "none", "_membership": (True, False)}),
)


def _green_trainer(overrides, donate=False, audit=False):
    overrides = dict(overrides)
    membership = overrides.pop("_membership", None)
    topo = HiPSTopology(num_parties=2, workers_per_party=1)
    cfg = GeoConfig(num_parties=2, workers_per_party=1, audit=audit,
                    **overrides)
    tr = Trainer(get_model("mlp", num_classes=10), topo, optax.sgd(0.1),
                 sync=get_sync_algorithm(cfg), config=cfg, donate=donate)
    rng = np.random.RandomState(0)
    x = (rng.rand(2, 1, 4, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(2, 1, 4)).astype(np.int32)
    state = tr.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    if membership is not None:
        state = tr.apply_membership(state, membership)
    sharding = topo.batch_sharding(tr.mesh)
    return tr, state, jax.device_put(x, sharding), \
        jax.device_put(y, sharding)


@pytest.mark.parametrize("name,overrides", GREEN_CONFIGS,
                         ids=[n for n, _ in GREEN_CONFIGS])
def test_green_step_programs_audit_clean(name, overrides):
    tr, state, xb, yb = _green_trainer(overrides)
    jx = jax.make_jaxpr(tr.train_step)(state, xb, yb)
    findings = CollectiveConsistencyPass().run(jx, AuditContext())
    params = jax.tree.map(lambda a: a[0, 0], state.params)
    dc = getattr(tr.sync, "dc_compressor", None) or getattr(
        getattr(tr.sync, "inner", None), "dc_compressor", None)
    if dc is not None:
        findings += audit_wire_accounting(dc, params)
        findings += audit_compressed_path(dc, params)
    assert findings == [], [f.format() for f in findings]
    # every green program still HAS a dc-tier collective story to audit
    assert len(collective_signature(jx)) >= 3


def test_green_donated_step_aliases_state_buffers():
    """The donated train step must alias every sync-state buffer (EF
    residuals) input->output: GX-DONATE coverage on the real program.
    Sharded lowering defers aliasing to the compiler, so the verdict
    reads the compiled module's input_output_alias table."""
    from geomx_tpu.analysis.passes import parse_compiled_aliases

    tr, state, xb, yb = _green_trainer(
        {"compression": "bsc,0.05,min_sparse_size=16"}, donate=True)
    lowered = tr.train_step.lower(state, xb, yb)
    compiled_params = parse_compiled_aliases(lowered.compile().as_text())
    n_state = len(jax.tree.leaves(state))
    expect = [(tuple(leaf.shape), str(leaf.dtype))
              for leaf in jax.tree.leaves(state.sync_state)]
    assert expect, "bsc sync state must carry EF residual buffers"
    ctx = AuditContext(lowered_text=lowered.as_text(), extras={
        "donated_positions": list(range(n_state)),
        "compiled_alias_params": compiled_params,
        "expect_aliased": expect})
    findings = DonationPass().run(None, ctx)
    assert findings == [], [f.format() for f in findings]
    # and the table really covered the whole donated TrainState
    assert compiled_params == frozenset(range(n_state))


def test_green_bf16_compute_path_is_leak_free():
    """A fully-bf16 matmul chain passes the dtype-flow rule; the same
    chain with an fp32 weight fails (the corpus covers the failing side
    end to end — this pins the green side)."""
    w = jnp.zeros((32, 32), jnp.bfloat16)

    def clean(x):
        return jnp.dot(jnp.dot(x, w), w)

    assert audit_dtype_flow(clean, jnp.zeros((4, 32), jnp.bfloat16)) == []


# --------------------------------------------------------------------------
# cross-party + the Trainer recompile boundary
# --------------------------------------------------------------------------

def test_audit_cross_party_equality_and_injected_divergence():
    def sig_for(spec):
        tr, state, xb, yb = _green_trainer({"compression": spec})
        return collective_signature(
            jax.make_jaxpr(tr.train_step)(state, xb, yb))

    bsc0 = sig_for("bsc,0.05,min_sparse_size=16")
    bsc1 = sig_for("bsc,0.05,min_sparse_size=16")
    assert audit_cross_party({"party0": bsc0, "party1": bsc1}) == []
    findings = audit_cross_party({"party0": bsc0,
                                  "party1": sig_for("none")})
    assert len(findings) == 1
    assert findings[0].rule_id == "GX-COLLECTIVE-001"
    assert findings[0].detail["parties"] == ["party0", "party1"]


def test_audit_cross_party_accepts_builders_and_jaxprs():
    def body(v):
        return jax.lax.psum(v, "dc")

    jx = _dc_trace(body)
    # jaxpr, zero-arg builder, and build= callable all coexist
    assert audit_cross_party({"a": jx, "b": lambda: _dc_trace(body)}) == []
    assert audit_cross_party({"a": 64, "b": 64},
                             build=lambda n: _dc_trace(body, n)) == []


def test_trainer_membership_recompile_audits_clean():
    """GEOMX_AUDIT on: fit arms the auditor, apply_membership re-traces
    and diffs — green masks swap without findings, and the signature
    cache holds one entry per membership program."""
    tr, state, xb, yb = _green_trainer(
        {"compression": "bsc,0.05,min_sparse_size=16"}, audit=True)
    rng = np.random.RandomState(0)
    xs = (rng.rand(16, 8, 8, 3) * 255).astype(np.uint8)
    ys = rng.randint(0, 10, size=(16,)).astype(np.int32)
    loader = tr.make_loader(xs, ys, batch_size=8)
    state, _ = tr.fit(state, loader, epochs=1)
    assert tr._audit_args is not None
    state = tr.apply_membership(state, (True, False))
    state = tr.apply_membership(state, (True, True))
    assert set(tr._audit_sigs) == {None, (True, False)}


def test_trainer_membership_divergence_raises_audit_error():
    """The boundary actually gates: against a divergent reference
    signature, apply_membership raises AuditError BEFORE swapping the
    step program in."""
    tr, state, xb, yb = _green_trainer(
        {"compression": "bsc,0.05,min_sparse_size=16"}, audit=True)
    rng = np.random.RandomState(0)
    xs = (rng.rand(16, 8, 8, 3) * 255).astype(np.uint8)
    ys = rng.randint(0, 10, size=(16,)).astype(np.int32)
    loader = tr.make_loader(xs, ys, batch_size=8)
    state, _ = tr.fit(state, loader, epochs=1)
    active_step = tr.train_step
    # simulate a reference program whose collective sequence the new
    # membership program cannot match (one psum short)
    ref_sig, ref_findings = tr._audit_sigs[None]
    tr._audit_sigs[None] = (ref_sig[:-1], ref_findings)
    with pytest.raises(AuditError) as ei:
        tr.apply_membership(state, (True, False))
    assert any(f.rule_id == "GX-COLLECTIVE-002"
               for f in ei.value.findings)
    assert tr.train_step is active_step  # no swap happened


def test_trainer_audit_off_is_inert(monkeypatch):
    monkeypatch.delenv("GEOMX_AUDIT", raising=False)
    tr, state, xb, yb = _green_trainer({"compression": "none"})
    assert tr._audit is False
    state, _m = tr.train_step(state, xb, yb)
    assert tr._audit_args is None and tr._audit_sigs == {}


# --------------------------------------------------------------------------
# GX-PURITY-001 post-collective side (merge-without-densify)
# --------------------------------------------------------------------------


def test_purity_post_collective_counts_only_after_last_collective():
    """The merge rule anchors at the FINAL collective: a two-bucket
    program whose bucket-1 select chain (incl. its dense EF-reset
    scatter) runs after bucket-0's gather must stay clean — only what
    follows the last collective counts, and the single final decompress
    is the allowed densify."""
    from geomx_tpu.compression import BucketedCompressor
    from geomx_tpu.compression.bisparse import BiSparseCompressor

    comp = BucketedCompressor(
        BiSparseCompressor(ratio=0.05, min_sparse_size=1, sparse_agg=False),
        bucket_bytes=16 * 1024)
    params = [jnp.zeros((4000,), jnp.float32),
              jnp.zeros((3800,), jnp.float32)]
    assert len(comp.init_state(params)) == 2  # really two buckets
    findings = audit_compressed_path(comp, params)
    assert findings == [], [f.format() for f in findings]


def test_purity_flags_second_densify_after_final_collective():
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P_

    from geomx_tpu.analysis.passes import PurityPass
    from geomx_tpu.parallel.collectives import shard_map_compat

    n, k = 4096, 64

    def bad(vals, idx):
        g = lax.all_gather(vals, "dc")            # compressed wire
        gi = lax.all_gather(idx, "dc")
        out = jnp.zeros((n,), jnp.float32)
        for p in range(2):                        # per-party densify
            ok = gi[p] >= 0
            out = out + jnp.zeros((n,), jnp.float32).at[
                jnp.where(ok, gi[p], 0)].add(jnp.where(ok, g[p], 0.0))
        return out

    mesh = Mesh(np.array(jax.devices()[:2]), ("dc",))
    fn = shard_map_compat(
        lambda v, i: bad(v[0], i[0])[None], mesh,
        in_specs=(P_("dc"), P_("dc")), out_specs=P_("dc"))
    jx = jax.make_jaxpr(fn)(jnp.zeros((2, k), jnp.float32),
                            jnp.zeros((2, k), jnp.int32))
    findings = PurityPass().run(jx, AuditContext(dense_bytes=4 * n))
    assert findings and all(f.rule_id == "GX-PURITY-001"
                            for f in findings)
    assert any("after the final collective" in f.message
               for f in findings)
    # raising the allowance to cover both densifies silences the rule
    clean = PurityPass().run(jx, AuditContext(
        dense_bytes=4 * n,
        extras={"allowed_dense_after_collective": 2}))
    assert clean == []
