"""Seeded known-bad corpus: programs the auditor MUST flag.

Each entry builds a minimal program exhibiting one defect class from
the pass catalog and runs the matching audit entry point.  The corpus
is the auditor's own regression suite — tests/test_analysis.py asserts
every entry is flagged with the right rule id, so a pass that silently
stops firing fails the gate, not a production trace.

Entries (name -> expected rule):

- ``divergent_collectives``  -> GX-COLLECTIVE-001   two parties trace
  different collective sequences (deadlock/divergence at mesh scale)
- ``read_after_donate``      -> GX-DONATE-001       a donated buffer the
  program still reads (no aliased output)
- ``fp32_leak_bf16_path``    -> GX-DTYPE-001        an fp32 matmul on a
  declared-bf16 compute path
- ``wire_accounting_lie``    -> GX-DTYPE-002        a compressor whose
  wire_bytes() claims half the bytes its collectives move
- ``scatter_wire_lie``       -> GX-DTYPE-002        a ZeRO-style
  reduce_scatter + all_gather pair accounted with the allreduce
  convention (operand-once), hiding the (N-1)/N scatter and the
  shard x (N-1) gather the chips actually send
- ``dense_compressed_path``  -> GX-PURITY-001       a "compressed" path
  that decompresses to dense BEFORE the collective
- ``dense_merge``            -> GX-PURITY-001       a compressed path
  whose wire payloads are all sparse but whose MERGE densifies each
  party's stream after the gather and sums the dense copies — the
  post-collective side of the purity rule (merge-without-densify)
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple

from geomx_tpu.analysis.core import Finding


class CorpusEntry(NamedTuple):
    name: str
    expected_rule: str
    run: Callable[[], List[Finding]]


# ---------------------------------------------------------------------------
# entry builders
# ---------------------------------------------------------------------------

def _divergent_collectives() -> List[Finding]:
    """Party 1's trace launches an extra all_gather party 0 never posts:
    at run time party 0 blocks in its psum while party 1 blocks in a
    gather rendezvous no peer joins."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from geomx_tpu.analysis.passes import audit_cross_party
    from geomx_tpu.parallel.collectives import shard_map_compat
    from geomx_tpu.topology import DC_AXIS

    mesh = Mesh(np.array(jax.devices()[:2]), (DC_AXIS,))
    x = jnp.zeros((2, 256), jnp.float32)

    def trace(body):
        fn = shard_map_compat(body, mesh, in_specs=(P(DC_AXIS),),
                              out_specs=P(DC_AXIS))
        return jax.make_jaxpr(fn)(x)

    def party0(v):
        return lax.psum(v, DC_AXIS) / 2.0

    def party1(v):
        g = lax.all_gather(v, DC_AXIS)       # the divergent launch
        return lax.psum(v, DC_AXIS) / 2.0 + g.sum()

    return audit_cross_party({"party0": lambda: trace(party0),
                              "party1": lambda: trace(party1)})


def _read_after_donate() -> List[Finding]:
    """The donated scratch buffer only feeds reductions — no output of
    its shape/dtype exists to reuse it, so the program reads the buffer
    after every aliasing opportunity and the caller's copy dies for
    nothing (jax warns "Some donated buffers were not usable"; the
    auditor makes it a structured finding)."""
    import jax.numpy as jnp

    from geomx_tpu.analysis.passes import audit_donation

    def step(params, scratch):
        # scratch (a different size than params) is read into scalars
        # only; donation can never be honored
        scale = 1.0 / (1.0 + jnp.sum(scratch * scratch))
        return params * scale, jnp.max(scratch)

    return audit_donation(step, jnp.zeros((256,)), jnp.zeros((512,)),
                          donate_argnums=(1,))


def _fp32_leak_bf16_path() -> List[Finding]:
    """A two-layer bf16 matmul chain with one forgotten astype: the
    second layer silently upcasts to fp32 (2x the promised MXU/HBM
    cost)."""
    import jax.numpy as jnp

    from geomx_tpu.analysis.passes import audit_dtype_flow

    w1 = jnp.zeros((64, 64), jnp.bfloat16)
    w2 = jnp.zeros((64, 64), jnp.float32)  # the leak: fp32 weights

    def fwd(x):
        h = jnp.dot(x, w1)                    # bf16 x bf16: clean
        return jnp.dot(h.astype(jnp.float32), w2)  # fp32 leak

    return audit_dtype_flow(fwd, jnp.zeros((8, 64), jnp.bfloat16),
                            compute_dtype="bfloat16")


def _wire_accounting_lie() -> List[Finding]:
    """An fp16-wire compressor whose accounting hardcodes the reference's
    2-bytes-per-element while the implementation gathers fp32 — the
    telemetry plane would report a 2x compression that never happens."""
    import jax.numpy as jnp
    from jax import lax

    from geomx_tpu.analysis.passes import audit_wire_accounting
    from geomx_tpu.compression.base import Compressor

    class LyingFP16(Compressor):
        name = "fp16_lie"

        def allreduce_leaf(self, g, state, axis_name, axis_size):
            gathered = lax.all_gather(g, axis_name)  # fp32 on the wire
            return jnp.sum(gathered, axis=0), state

        def wire_bytes_leaf(self, leaf):
            return leaf.size * 2  # claims the 16-bit wire it never built

    return audit_wire_accounting(LyingFP16(), jnp.zeros((4096,)))


def _scatter_wire_lie() -> List[Finding]:
    """A ZeRO-style sharded reducer (psum_scatter the gradient, update
    the shard, all_gather it back) whose accounting keeps the allreduce
    operand-once convention.  At N=4 the chips really send
    ``(N-1)/N * full`` for the scatter plus ``shard * (N-1)`` for the
    gather — 1.5x what the accounting claims, the physical gap
    ``collective_wire_bytes``'s per-chip convention now measures; the
    audit's payload-convention diff sees the decomposition carry
    ``full + shard`` = 1.25x the declared bytes and flags it at any
    mesh width."""
    import jax.numpy as jnp
    from jax import lax

    from geomx_tpu.analysis.passes import audit_wire_accounting
    from geomx_tpu.compression.base import Compressor

    n_axis = 4

    class LyingScatter(Compressor):
        name = "zero_scatter_lie"

        def allreduce_leaf(self, g, state, axis_name, axis_size):
            s = g.size // axis_size
            shard = lax.psum_scatter(
                g.reshape(-1).astype(jnp.float32).reshape(axis_size, s),
                axis_name, scatter_dimension=0)
            full = lax.all_gather(shard, axis_name).reshape(-1)
            return full.reshape(g.shape).astype(g.dtype), state

        def wire_bytes_leaf(self, leaf):
            return leaf.size * 4  # the allreduce convention: a lie here

    return audit_wire_accounting(LyingScatter(), jnp.zeros((4096,)),
                                 num_parties=n_axis)


def _dense_compressed_path() -> List[Finding]:
    """A BSC variant that decompresses each party's pairs to dense and
    THEN psums: the select/pack ran, but the WAN carries the full dense
    gradient — exactly the regression class PR 4's hand-rolled HLO
    check guarded against."""
    import jax.numpy as jnp
    from jax import lax

    from geomx_tpu.analysis.passes import audit_compressed_path
    from geomx_tpu.compression.bisparse import BiSparseCompressor

    class DenseLeakBSC(BiSparseCompressor):
        name = "bsc_dense_leak"

        def allreduce_leaf(self, g, state, axis_name, axis_size):
            n = g.size
            if not self._sparse_eligible(n):
                return lax.psum(g, axis_name), state
            u, v = state
            vals, idx, u, v = self.compress(
                g.reshape(-1).astype(jnp.float32), u.reshape(-1),
                v.reshape(-1))
            dense = self.decompress(vals, idx, n)  # dense BEFORE the wire
            out = lax.psum(dense, axis_name)
            return (out.reshape(g.shape).astype(g.dtype),
                    (u.reshape(g.shape), v.reshape(g.shape)))

    comp = DenseLeakBSC(ratio=0.01, min_sparse_size=1)
    return audit_compressed_path(comp, jnp.zeros((8192,), jnp.float32))


def _dense_merge() -> List[Finding]:
    """Every wire payload is compressed — the gather carries (value,
    index) pairs — but the merge decompresses EACH party's pairs into
    its own dense buffer and sums the dense copies: one dense scatter
    per party after the final collective, where the compressed-domain
    merge pays exactly one (the final decompress).  The post-collective
    side of GX-PURITY-001 flags the second scatter."""
    import jax.numpy as jnp
    from jax import lax

    from geomx_tpu.analysis.passes import audit_compressed_path
    from geomx_tpu.compression.bisparse import BiSparseCompressor

    class DenseMergeBSC(BiSparseCompressor):
        name = "bsc_dense_merge"

        def allreduce_leaf(self, g, state, axis_name, axis_size):
            n = g.size
            if not self._sparse_eligible(n):
                return lax.psum(g, axis_name), state
            u, v = state
            vals, idx, u, v = self.compress(
                g.reshape(-1).astype(jnp.float32), u.reshape(-1),
                v.reshape(-1))
            all_vals = lax.all_gather(vals, axis_name)  # sparse wire: fine
            all_idx = lax.all_gather(idx, axis_name)
            out = jnp.zeros((n,), jnp.float32)
            for p in range(axis_size):   # the defect: per-party densify
                out = out + self.decompress(all_vals[p], all_idx[p], n)
            return (out.reshape(g.shape).astype(g.dtype),
                    (u.reshape(g.shape), v.reshape(g.shape)))

    comp = DenseMergeBSC(ratio=0.01, min_sparse_size=1, sparse_agg=False)
    return audit_compressed_path(comp, jnp.zeros((8192,), jnp.float32))


CORPUS = (
    CorpusEntry("divergent_collectives", "GX-COLLECTIVE-001",
                _divergent_collectives),
    CorpusEntry("read_after_donate", "GX-DONATE-001", _read_after_donate),
    CorpusEntry("fp32_leak_bf16_path", "GX-DTYPE-001", _fp32_leak_bf16_path),
    CorpusEntry("wire_accounting_lie", "GX-DTYPE-002", _wire_accounting_lie),
    CorpusEntry("scatter_wire_lie", "GX-DTYPE-002", _scatter_wire_lie),
    CorpusEntry("dense_compressed_path", "GX-PURITY-001",
                _dense_compressed_path),
    CorpusEntry("dense_merge", "GX-PURITY-001", _dense_merge),
)


def run_corpus() -> Dict[str, dict]:
    """Run every corpus entry; each record carries the expected rule,
    the findings' rule ids, and the flagged verdict (expected rule among
    them).  The auditor is healthy iff every entry is flagged."""
    out: Dict[str, dict] = {}
    for entry in CORPUS:
        findings = entry.run()
        rules = sorted({f.rule_id for f in findings})
        out[entry.name] = {
            "expected_rule": entry.expected_rule,
            "finding_rules": rules,
            "finding_count": len(findings),
            "flagged": entry.expected_rule in rules,
        }
    return out
