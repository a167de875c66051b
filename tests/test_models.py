"""Model zoo smoke tests.

Reference analogue: the gluon model zoo (python/mxnet/gluon/model_zoo/) is
exercised only through the demos; here every registered model gets a
forward-shape and gradient check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.models import get_model

ZOO = ["cnn", "mlp", "alexnet", "resnet20",
       pytest.param("resnet18", marks=pytest.mark.tier2)]


@pytest.mark.parametrize("name", ZOO)
def test_forward_shape(name):
    model = get_model(name, num_classes=10)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(0), x,
                                           train=False))()
    out = jax.jit(lambda v: model.apply(v, x, train=False))(variables)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out)))


def test_gradients_flow():
    model = get_model("mlp")
    x = jnp.asarray(np.random.RandomState(1).rand(4, 32, 32, 3), jnp.float32)
    y = jnp.asarray([0, 1, 2, 3])
    variables = jax.jit(lambda: model.init(jax.random.PRNGKey(0), x,
                                           train=False))()

    def loss(v):
        logits = model.apply(v, x, train=True)
        onehot = jax.nn.one_hot(y, 10)
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))

    grads = jax.jit(jax.grad(loss))(variables)
    norms = [float(jnp.linalg.norm(g)) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(n) for n in norms)
    assert any(n > 0 for n in norms)


def test_unknown_model_raises():
    with pytest.raises(ValueError):
        get_model("vgg99")


@pytest.mark.tier2
def test_resnet20_space_to_depth_variant_trains():
    """The flag-gated TPU stem experiment trains: the 2x2 space-to-depth stem halves every stage's resolution
    but keeps a working ResNet-20 sibling."""
    import optax

    from geomx_tpu.models import get_model
    from geomx_tpu.sync import FSA
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer

    model = get_model("resnet20_s2d")
    assert model.stem_space_to_depth
    assert model.mxu_shortcuts
    topo = HiPSTopology(num_parties=1, workers_per_party=2)
    trainer = Trainer(model, topo, optax.sgd(0.05, momentum=0.9),
                      sync=FSA())
    rng = np.random.RandomState(0)
    x = (rng.rand(1, 2, 4, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, size=(1, 2, 4)).astype(np.int32)
    sharding = topo.batch_sharding(trainer.mesh)
    state = trainer.init_state(jax.random.PRNGKey(0), x[0, 0, :2])
    losses = []
    for _ in range(3):
        state, metrics = trainer.train_step(
            state, jax.device_put(x, sharding), jax.device_put(y, sharding))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]  # same tiny batch refit: loss must drop


def test_resnet20_mxu_shortcuts_projection_shape():
    """mxu_shortcuts replaces the stride-2 1x1 projection (contraction
    cin, 3/4 of activations discarded) with space_to_depth + unstrided
    1x1 (contraction 4*cin, lossless): same output shapes, 4x the MXU
    systolic fill on the projection matmul."""
    from geomx_tpu.models import ResNet20

    model = ResNet20(num_classes=10, mxu_shortcuts=True)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    logits = jax.eval_shape(lambda v: model.apply(v, x, train=False),
                            variables)
    assert logits.shape == (2, 10)
    # the two transition shortcuts contract over 4*cin channels
    kernels = {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            variables["params"])
        if leaf.ndim == 4 and leaf.shape[:2] == (1, 1)
    }
    assert sorted(s[2] for s in kernels.values()) == [64, 128], kernels
