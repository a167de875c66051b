"""What the decoder families' test files share (`test_afmoe.py`,
`test_kimi_linear.py`, `test_nemotron_h.py`, `test_mellum.py`), not a test
file itself: a family's tiny model built once a file, its loss, counters
and gradient as ONE compiled program, the plain reference's as another,
and the checks every family repeats against those two results.  A file
holds its `Family`, a module-scoped fixture of `Built(FAMILY)`, and a
test of its own name around each check it takes."""
import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.numerics import Numerics
from geomx_tpu.models import get_model
from geomx_tpu.ops import dispatch
from geomx_tpu.utils.profiler import profile_scope

NX = Numerics("float32")


@dataclasses.dataclass(frozen=True)
class Family:
    name: str                       # `get_model`'s
    sizes: dict                     # its keywords at the tiny size
    plain: types.ModuleType         # benchmark/references/<family>.py
    reference_sizes: dict           # that module's `sizes`

    def model(self, **over):
        return get_model(self.name, **{**self.sizes, **over})


class Built:
    """A family's tiny model, seeded parameters and one batch of 2 x 40
    tokens; each program below is traced, compiled and run once, whatever
    the number of tests that read it."""

    def __init__(self, family: Family, **over):
        self.family, self.model = family, family.model(**over)
        tokens = np.random.default_rng(0).integers(0, 64, (2, 41))
        self.x, self.y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
        variables = jax.jit(
            lambda: self.model.init(jax.random.PRNGKey(1), self.x))()
        # norms' scales off one, so that a norm left out or misplaced shows
        self.params = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(
            lambda path, a: a + 0.1 * jax.random.normal(
                jax.random.PRNGKey(len(path)), a.shape)
            if path[-1].key == "scale" else a, tree))(variables["params"])

    def step_of(self, model):
        """`params -> ((loss, aux), gradient)` under the trainer's scope."""
        def step(p):
            with profile_scope("step/forward_backward"):
                return jax.value_and_grad(lambda p_: model.apply(
                    {"params": p_}, self.x, self.y, method="loss_and_aux"),
                    has_aux=True)(p)
        return jax.jit(step)

    @functools.cached_property
    def lowered(self):
        return self.step_of(self.model).lower(self.params)

    @functools.cached_property
    def compiled(self):
        return self.lowered.compile()

    @functools.cached_property
    def ours(self):
        """((loss, aux), gradient) through the dense fall-back a CPU takes:
        the baseline of the reference's, the kernels' and the un-
        rematerialised model's comparisons."""
        return self.compiled(self.params)

    @functools.cached_property
    def theirs(self):
        """(loss, gradient) by the plain reference."""
        f = self.family
        return jax.jit(jax.value_and_grad(lambda p: f.plain.loss(
            p, self.x, self.y, f.reference_sizes, NX)))(self.params)

    def scopes(self):
        """The scopes in the compiled step's instruction names."""
        from geomx_tpu.telemetry.layers import op_layers
        return {e.scope for e in op_layers(self.compiled.as_text()).values()
                if e.scope}


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def value_and_gradients(f, args, argnums, weight=1.0):
    """What `f(*args)` gives, and the gradients of the weighted sum of its
    (first) output: one compiled program."""
    def weighed(*a):
        out = f(*a)
        first = out[0] if isinstance(out, tuple) else out
        return jnp.sum(first * weight), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        weighed, argnums, has_aux=True))(*args)
    return out, grads


def relative_distance(got, want):
    """|got - want| over |want|, each the whole tree's 2-norm."""
    norm = np.sqrt(sum(float(jnp.sum(w * w)) for w in jax.tree.leaves(want)))
    off = np.sqrt(sum(float(jnp.sum((g - w) ** 2)) for g, w in
                      zip(jax.tree.leaves(got), jax.tree.leaves(want))))
    return off / norm


def loss_equals_the_reference(built):
    """The losses agree; the two gradients, for the family's own
    comparison."""
    ((loss, _), got), (want_loss, want) = built.ours, built.theirs
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    return got, want


def whole_logits_agree(built, atol):
    """The model's whole logits are the reference's, and the blocked loss
    is their cross-entropy; the step's counters, for the family's own
    assertions."""
    f = built.family
    logits = jax.jit(lambda p: built.model.apply({"params": p}, built.x))(
        built.params)
    want = jax.jit(lambda p: f.plain.logits(
        p, built.x, f.reference_sizes, NX))(built.params)
    np.testing.assert_allclose(logits, want, atol=atol)
    (loss, aux), _ = built.ours
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, built.y[..., None], -1)[..., 0]
    np.testing.assert_allclose(loss, jnp.mean(logz - picked), rtol=1e-6)
    assert float(aux["counters"]["moe/dropped"]) == 0.0
    return {k: float(v) for k, v in aux["counters"].items()}


def kernels_give_the_dense_fall_back(built):
    """The same step through the Pallas kernels (interpreted)."""
    with dispatch.kernels("interpret"):
        (loss, _), got = built.step_of(built.model)(built.params)
    (want_loss, _), want = built.ours
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def rematerialisation_changes_no_number(built, rtol, atol):
    assert built.model.cfg.remat
    (loss, _), got = built.step_of(built.family.model(remat=False))(
        built.params)
    (want_loss, _), want = built.ours
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)


def expert_shares_add_up(layer_of, reference, whole, x, uncut, once=0.0):
    """16 experts cut into 4 shares of 4: the layer `layer_of(lo)` on a
    share's weights is `reference(share, lo)`, and the routed parts the
    shares give, with what every chip computes alike (`once`) counted
    once, are `uncut`, the reference with all 16 held.  Gives the
    assignments that arrived, each on one share."""
    total, arrived = once, 0
    for lo in range(0, 16, 4):
        part = {k: (v[lo:lo + 4] if k.startswith("experts_") else v)
                for k, v in whole.items()}
        y, counts, dropped = jax.jit(layer_of(lo).apply)({"params": part}, x)
        np.testing.assert_allclose(y, reference(part, lo), atol=2e-5)
        total = total + (y - once)
        arrived += int(jnp.sum(counts))
        assert int(dropped) == 0
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    return arrived


def trainer_fits(family, learning_rate, epochs, **loader):
    """`Trainer.fit` on the decoder with no branch on its name: per-token
    labels through the loader, the model's loss in the step, its counters
    in `LoopStats`.  Gives the counters."""
    import optax
    from geomx_tpu import GeoConfig, HiPSTopology
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer
    cfg = GeoConfig(num_parties=1, workers_per_party=1, sync_mode="fsa",
                    compression="none")
    trainer = Trainer(family.model(), HiPSTopology(1, 1),
                      optax.adam(learning_rate),
                      sync=get_sync_algorithm(cfg), config=cfg)
    tokens = np.random.default_rng(1).integers(0, 64, (8, 41)).astype(
        np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    state = trainer.init_state(jax.random.PRNGKey(0), x[:2])
    state, records = trainer.fit(state, trainer.make_loader(x, y, 2, **loader),
                                 epochs=epochs, log_every=1,
                                 log_fn=lambda _line: None)
    losses = [r["loss"] for r in records if "loss" in r]
    assert len(losses) == 4 * epochs and losses[-1] < losses[0]
    counters = trainer.loop_stats.as_dict()["counters"]
    assert counters["moe/dropped"]["total"] == 0.0
    assert counters["moe/assignments_mean"]["count"] == 4 * epochs
    return counters
