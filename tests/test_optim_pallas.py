"""Fused optimizer apply (ops/optim_pallas.py, GEOMX_FUSED_OPTIM).

Evidence layers, all in Pallas interpret mode on the CPU backend:

- *Kernel parity*: fused SGD-momentum / Adam over flat vectors vs the
  jnp references (jitted — eager XLA skips the FMA contraction the
  jitted programs share): moment buffers BITWISE identical, updated
  params to one rounding of the final multiply-subtract (rtol=1e-6 /
  atol=1e-8, the documented contract), across odd tails and shard-like
  sizes, plus the cast_dtype master-weight copy.
- *State contract*: fused_apply round-trips the unmodified optax state
  structure over the bucket list, so checkpoints and the ZeRO reshard
  helpers never see a new layout; trajectory stays on the per-leaf
  optax chain within accumulated-FMA tolerance.
- *Structure*: the fused bucket update cross-lowers to tpu_custom_call
  with ZERO stablehlo.multiply; the per-leaf chain keeps its multiplies
  and has no custom call; the whole train step holds the kernels
  only when fused.
- *Training integration*: GeoConfig(fused_optim=True) lands on the
  unfused trajectory through the full shard_mapped step (replicated and
  ZeRO-sharded), and the loud rejections (plain optax tx, bucketing
  off, MultiGPS) fire at build time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from geomx_tpu.config import GeoConfig
from geomx_tpu.models import get_model
from geomx_tpu.ops.optim_pallas import (FusedOptimSpec, adam_ref,
                                        fused_adam, fused_apply,
                                        fused_optim_enabled,
                                        fused_optimizer, fused_sgd_momentum,
                                        fused_spec_of, sgd_momentum_ref,
                                        unfused_apply)
from geomx_tpu.sync import get_sync_algorithm
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.train import Trainer

P_, W_ = 2, 4
STEPS = 3

# odd tails on both sides of the lane (128) and block (256*128)
# boundaries, plus shard-like sizes (a 1/W ZeRO shard of a padded
# bucket is any multiple of 2 — exercise non-multiples too)
SIZES = [1, 7, 127, 128, 129, 1025, 4096, 32781]


def _vec(n, seed, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(n).astype(np.float32) * scale)


# --------------------------------------------------------------------------
# kernel parity vs the jitted jnp references
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", SIZES)
def test_sgd_momentum_parity(n):
    p, g, m = _vec(n, 0), _vec(n, 1, 1e-2), _vec(n, 2, 1e-2)
    np_, nm = fused_sgd_momentum(p, g, m, lr=0.1, momentum=0.9,
                                 interpret=True)
    ref = jax.jit(lambda p, g, m: sgd_momentum_ref(p, g, m, lr=0.1,
                                                   momentum=0.9))
    rp, rm = ref(p, g, m)
    # moments bitwise: the kernel's multiply-add contracts to the same
    # FMA the jitted reference's does
    np.testing.assert_array_equal(np.asarray(nm), np.asarray(rm))
    np.testing.assert_allclose(np.asarray(np_), np.asarray(rp),
                               rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("n", SIZES)
def test_adam_parity(n):
    p, g = _vec(n, 0), _vec(n, 1, 1e-2)
    m, v = _vec(n, 2, 1e-3), jnp.abs(_vec(n, 3, 1e-4))
    t = 3.0
    bc1 = jnp.float32(1.0 - 0.9 ** t)
    bc2 = jnp.float32(1.0 - 0.999 ** t)
    np_, nm, nv = fused_adam(p, g, m, v, bc1, bc2, lr=1e-3, b1=0.9,
                             b2=0.999, eps=1e-8, interpret=True)
    ref = jax.jit(lambda *a: adam_ref(*a, lr=1e-3, b1=0.9, b2=0.999,
                                      eps=1e-8))
    rp, rm, rv = ref(p, g, m, v, bc1, bc2)
    np.testing.assert_array_equal(np.asarray(nm), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(rv))
    np.testing.assert_allclose(np.asarray(np_), np.asarray(rp),
                               rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("kind", ["sgd", "adam"])
def test_cast_dtype_copy(kind):
    n = 1037
    p, g, m = _vec(n, 0), _vec(n, 1, 1e-2), _vec(n, 2, 1e-2)
    if kind == "sgd":
        outs = fused_sgd_momentum(p, g, m, lr=0.1, momentum=0.9,
                                  cast_dtype=jnp.bfloat16, interpret=True)
        np_, cast = outs[0], outs[-1]
    else:
        v = jnp.abs(_vec(n, 3, 1e-4))
        outs = fused_adam(p, g, m, v, jnp.float32(0.1), jnp.float32(0.01),
                          lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                          cast_dtype=jnp.bfloat16, interpret=True)
        np_, cast = outs[0], outs[-1]
    assert cast.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(cast),
                                  np.asarray(np_.astype(jnp.bfloat16)))


# --------------------------------------------------------------------------
# fused_apply: state contract + trajectory vs the per-leaf chain
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sgd", "adam"])
def test_fused_apply_state_roundtrip_and_trajectory(kind):
    fo = fused_optimizer(kind, learning_rate=0.05)
    buckets = [_vec(n, i) for i, n in enumerate((4096, 1037, 7))]
    sf = su = fo.init(buckets)
    pf = pu = buckets
    assert jax.tree.structure(sf) == jax.tree.structure(
        fo.init(buckets))
    for s in range(5):
        grads = [_vec(len(b), 100 + 10 * s + i, 1e-2)
                 for i, b in enumerate(buckets)]
        pf, sf = fused_apply(fo.spec, pf, grads, sf, interpret=True)
        pu, su = unfused_apply(fo, pu, grads, su)
        # the state structure never changes shape mid-run
        assert jax.tree.structure(sf) == jax.tree.structure(su)
    for a, b in zip(pf, pu):
        # accumulated FMA-contraction drift only (ops/optim_pallas.py)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fused_apply_ref_path_matches_kernels():
    fo = fused_optimizer("adam", learning_rate=1e-3)
    buckets = [_vec(300, 0)]
    st = fo.init(buckets)
    grads = [_vec(300, 1, 1e-2)]
    pk, sk = fused_apply(fo.spec, buckets, grads, st, interpret=True)
    pr, sr = fused_apply(fo.spec, buckets, grads, st, use_ref=True)
    np.testing.assert_allclose(np.asarray(pk[0]), np.asarray(pr[0]),
                               rtol=1e-6, atol=1e-8)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(sk[0].mu)[0]),
        np.asarray(jax.tree.leaves(sr[0].mu)[0]))


def test_error_paths():
    with pytest.raises(ValueError, match="unknown kind"):
        fused_optimizer("rmsprop", learning_rate=0.1)
    fo = fused_optimizer("sgd", learning_rate=0.1)
    st = fo.init([_vec(8, 0)])
    with pytest.raises(ValueError, match="different bucket list"):
        fused_apply(fo.spec, [_vec(8, 0), _vec(8, 1)],
                    [_vec(8, 2), _vec(8, 3)], st, interpret=True)
    with pytest.raises(ValueError, match="unknown spec kind"):
        fused_apply(FusedOptimSpec("lamb", 0.1), [_vec(8, 0)],
                    [_vec(8, 1)], st)
    assert fused_spec_of(optax.sgd(0.1)) is None
    assert fused_spec_of(fo) == fo.spec
    assert fused_optim_enabled(GeoConfig(fused_optim=True))
    assert not fused_optim_enabled(GeoConfig())


# --------------------------------------------------------------------------
# structure: the per-leaf chain is GONE from the fused lowering
# --------------------------------------------------------------------------

def test_fused_update_lowering_has_no_multiplies():
    from geomx_tpu.analysis.hlo import count_ops, lower_text

    fo = fused_optimizer("adam", learning_rate=1e-3)
    buckets = [jnp.zeros((n,), jnp.float32) for n in (4096, 1037)]
    grads = [jnp.ones((n,), jnp.float32) for n in (4096, 1037)]
    st = fo.init(buckets)

    fused_txt = lower_text(
        lambda ps, gs, s: fused_apply(fo.spec, ps, gs, s,
                                      interpret=False),
        buckets, grads, st)
    unfused_txt = lower_text(
        lambda ps, gs, s: unfused_apply(fo, ps, gs, s),
        buckets, grads, st)
    fc = count_ops(fused_txt, ("stablehlo.multiply",))
    uc = count_ops(unfused_txt, ("stablehlo.multiply",))
    assert fused_txt.count("tpu_custom_call") >= 2   # one per bucket
    assert fc.get("multiply", 0) == 0                # all flops in-kernel
    assert unfused_txt.count("tpu_custom_call") == 0
    assert uc.get("multiply", 0) > 0


# --------------------------------------------------------------------------
# training integration through the full shard_mapped step
# --------------------------------------------------------------------------

def _data(steps=STEPS, seed=0):
    rng = np.random.RandomState(seed)
    x = (rng.rand(steps, P_, W_, 2, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(steps, P_, W_, 2)).astype(np.int32)
    return x, y


def _trainer(tx, **over):
    topo = HiPSTopology(num_parties=P_, workers_per_party=W_)
    cfg = GeoConfig(num_parties=P_, workers_per_party=W_,
                    bucket_bytes=1 << 18, **over)
    tr = Trainer(get_model("mlp", num_classes=10), topo, tx,
                 sync=get_sync_algorithm(cfg), config=cfg)
    return tr, topo


def _run(tr, topo, xs, ys):
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0, :2])
    sh = topo.batch_sharding(tr.mesh)
    for s in range(len(xs)):
        st, _m = tr.train_step(st, jax.device_put(xs[s], sh),
                               jax.device_put(ys[s], sh))
    jax.block_until_ready(st.step)
    return jax.tree.map(lambda a: np.asarray(a, np.float64)[0, 0],
                        st.params)


@pytest.mark.parametrize("kind,zero", [
    ("sgd", 0), ("adam", 0), ("sgd", 1), ("adam", 1)])
def test_fused_step_matches_unfused(kind, zero):
    xs, ys = _data()
    tx = fused_optimizer(kind, learning_rate=0.05)
    pf = _run(*_trainer(tx, fused_optim=True, zero=zero), xs, ys)
    pu = _run(*_trainer(tx, zero=zero), xs, ys)
    gap = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b))), pf, pu)))
    assert gap < 1e-5, gap


@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused", "per_leaf"])
def test_step_lowering_holds_the_kernels_only_when_fused(fused):
    """The whole train step lowered for a TPU: the fused build holds
    Mosaic calls, the per-leaf optax chain none (the Trainer is built
    under ``kernels("native")`` so that the optimizer takes its kernels;
    the lowering itself runs on the CPU's dispatch)."""
    from geomx_tpu.analysis.hlo import lower_text
    from geomx_tpu.ops.dispatch import kernels

    xs, ys = _data(steps=1)
    with kernels("native"):
        tr, topo = _trainer(fused_optimizer("sgd", learning_rate=0.1,
                                            momentum=0.9),
                            fused_optim=fused)
    st = tr.init_state(jax.random.PRNGKey(0), xs[0, 0, 0, :2])
    sh = topo.batch_sharding(tr.mesh)
    calls = lower_text(tr.train_step, st, jax.device_put(xs[0], sh),
                       jax.device_put(ys[0], sh)).count("tpu_custom_call")
    assert (calls >= 1) if fused else (calls == 0), calls


def test_fused_requires_fused_optimizer():
    with pytest.raises(ValueError, match="fused_optimizer"):
        _trainer(optax.sgd(0.1, momentum=0.9), fused_optim=True)


def test_fused_requires_bucketing():
    topo = HiPSTopology(num_parties=P_, workers_per_party=W_)
    cfg = GeoConfig(num_parties=P_, workers_per_party=W_,
                    bucket_bytes=0, fused_optim=True)
    with pytest.raises(ValueError, match="bucket"):
        Trainer(get_model("mlp", num_classes=10), topo,
                fused_optimizer("sgd", learning_rate=0.1),
                sync=get_sync_algorithm(cfg), config=cfg)
