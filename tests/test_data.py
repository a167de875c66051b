import numpy as np
import pytest

from geomx_tpu.data import (ClassSplitSampler, GeoDataLoader, SplitSampler,
                            load_dataset)
from geomx_tpu.data.samplers import class_sorted_indices
from geomx_tpu.topology import HiPSTopology


def test_split_sampler_contiguous():
    s = SplitSampler(100, num_parts=4, part_index=1)
    idx = list(s)
    assert idx == list(range(25, 50))
    assert len(s) == 25


def test_split_sampler_rejects_bad_index():
    with pytest.raises(ValueError):
        SplitSampler(100, num_parts=4, part_index=4)


def test_class_split_sampler_non_iid():
    labels = np.array([1, 0, 1, 0, 1, 0, 1, 0])
    order = class_sorted_indices(labels)
    s0 = ClassSplitSampler(order, len(labels), 2, 0)
    s1 = ClassSplitSampler(order, len(labels), 2, 1)
    assert set(labels[list(s0)]) == {0}
    assert set(labels[list(s1)]) == {1}


def test_synthetic_dataset_learnable_structure():
    d = load_dataset("synthetic")
    assert d["train_x"].dtype == np.uint8
    assert d["train_x"].shape[1:] == (32, 32, 3)
    assert d["synthetic"]
    # same class -> similar images (class-conditional structure)
    y = d["train_y"]
    x = d["train_x"].astype(np.float32)
    c0 = x[y == 0].mean(0)
    c1 = x[y == 1].mean(0)
    assert np.abs(c0 - c1).mean() > 5.0


def test_loader_shapes_and_sharding():
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    d = load_dataset("synthetic", synthetic_train_n=2048)
    loader = GeoDataLoader(d["train_x"], d["train_y"], topo, batch_size=8)
    xb, yb = next(iter(loader.epoch(0)))
    assert xb.shape == (2, 4, 8, 32, 32, 3)
    assert yb.shape == (2, 4, 8)
    assert loader.steps_per_epoch == 2048 // 8 // 8


def test_loader_disjoint_shards():
    topo = HiPSTopology(num_parties=2, workers_per_party=2)
    d = load_dataset("synthetic", synthetic_train_n=1024)
    loader = GeoDataLoader(d["train_x"], d["train_y"], topo, batch_size=4,
                           shuffle=False)
    shards = [set(s.tolist()) for s in loader.shards]
    for i in range(len(shards)):
        for j in range(i + 1, len(shards)):
            assert not shards[i] & shards[j]


def test_loader_augmentation_preserves_shapes_and_labels():
    """Random crop (reflect pad) + flip: same shapes/dtype, labels
    untouched, content actually changes, and the seed makes it
    deterministic."""
    import numpy as np

    from geomx_tpu.data.loader import GeoDataLoader
    from geomx_tpu.topology import HiPSTopology

    topo = HiPSTopology(1, 1)
    rng = np.random.RandomState(3)
    x = (rng.rand(64, 32, 32, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, 64).astype(np.int32)

    plain = GeoDataLoader(x, y, topo, batch_size=16, shuffle=False, seed=7)
    aug = GeoDataLoader(x, y, topo, batch_size=16, shuffle=False, seed=7,
                        augment=True)
    aug2 = GeoDataLoader(x, y, topo, batch_size=16, shuffle=False, seed=7,
                         augment=True)

    (xp, yp), (xa, ya), (xa2, _) = (next(iter(ld.epoch(0)))
                                    for ld in (plain, aug, aug2))
    xp, xa, xa2 = (np.asarray(v) for v in (xp, xa, xa2))
    assert xa.shape == xp.shape and xa.dtype == xp.dtype
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yp))
    assert not np.array_equal(xa, xp)          # something moved
    np.testing.assert_array_equal(xa, xa2)     # seeded determinism


def test_device_cache_loader_matches_host_path():
    """device_cache=True gathers batches on device: identical values to
    the host path without augmentation; with augmentation, shapes/labels
    hold and the crop/flip kernel is seed-deterministic."""
    import numpy as np

    from geomx_tpu.data.loader import GeoDataLoader
    from geomx_tpu.topology import HiPSTopology

    topo = HiPSTopology(2, 2)
    rng = np.random.RandomState(5)
    x = (rng.rand(128, 16, 16, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, 128).astype(np.int32)

    host = GeoDataLoader(x, y, topo, batch_size=8, seed=11)
    dev = GeoDataLoader(x, y, topo, batch_size=8, seed=11,
                        device_cache=True)
    for (xh, yh), (xd, yd) in zip(host.epoch(1), dev.epoch(1)):
        np.testing.assert_array_equal(np.asarray(xh), np.asarray(xd))
        np.testing.assert_array_equal(np.asarray(yh), np.asarray(yd))

    aug = GeoDataLoader(x, y, topo, batch_size=8, seed=11, augment=True,
                        device_cache=True)
    aug2 = GeoDataLoader(x, y, topo, batch_size=8, seed=11, augment=True,
                         device_cache=True)
    (xh, yh), (xa, ya), (xa2, _) = (next(iter(ld.epoch(0)))
                                    for ld in (host, aug, aug2))
    xa, xa2 = np.asarray(xa), np.asarray(xa2)
    assert xa.shape == np.asarray(xh).shape and xa.dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yh))
    assert not np.array_equal(xa, np.asarray(xh))
    np.testing.assert_array_equal(xa, xa2)


def test_prefetch_batches_bit_identical_to_synchronous():
    """epoch(prefetch=N) moves batch assembly to a producer thread but
    must not change a single byte — augmentation RNG included — nor the
    batch order (the GEOMX_PREFETCH determinism contract)."""
    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    rng = np.random.RandomState(9)
    x = (rng.rand(256, 16, 16, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, 256).astype(np.int32)
    sync_ld = GeoDataLoader(x, y, topo, batch_size=4, seed=13,
                            augment=True)
    pre_ld = GeoDataLoader(x, y, topo, batch_size=4, seed=13,
                           augment=True)
    for epoch in (0, 1):
        sync_batches = list(sync_ld.epoch(epoch, prefetch=0))
        pre_batches = list(pre_ld.epoch(epoch, prefetch=3))
        assert len(sync_batches) == len(pre_batches) > 0
        for (xs, ys), (xp, yp) in zip(sync_batches, pre_batches):
            np.testing.assert_array_equal(np.asarray(xs), np.asarray(xp))
            np.testing.assert_array_equal(np.asarray(ys), np.asarray(yp))


def test_prefetch_surfaces_producer_errors():
    """An exception on the producer thread re-raises in the consumer
    instead of hanging the bounded queue."""
    topo = HiPSTopology(num_parties=1, workers_per_party=1)
    x = np.zeros((16, 8, 8, 3), np.uint8)
    y = np.zeros((16,), np.int32)
    loader = GeoDataLoader(x, y, topo, batch_size=4, seed=0)

    def boom(epoch):
        yield from loader_batches_orig(epoch)
        raise RuntimeError("producer exploded")

    loader_batches_orig = loader._batches
    loader._batches = boom
    with pytest.raises(RuntimeError, match="producer exploded"):
        for _ in loader.epoch(0, prefetch=2):
            pass


def test_trainer_prefetch_params_bit_identical():
    """Trainer.fit with GeoConfig(prefetch=0) vs prefetch=2: the same
    program consumes the same batches, so final params are BIT-identical
    — overlap is a latency optimization, never a trajectory change."""
    import jax
    import optax

    from geomx_tpu.config import GeoConfig
    from geomx_tpu.models import get_model
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.train import Trainer

    topo = HiPSTopology(num_parties=2, workers_per_party=4)
    rng = np.random.RandomState(2)
    x = (rng.rand(128, 8, 8, 3) * 255).astype(np.uint8)
    y = rng.randint(0, 10, 128).astype(np.int32)

    def run(prefetch):
        cfg = GeoConfig(num_parties=2, workers_per_party=4,
                        prefetch=prefetch)
        tr = Trainer(get_model("mlp", num_classes=10), topo,
                     optax.sgd(0.1, momentum=0.9),
                     sync=get_sync_algorithm(cfg), config=cfg)
        loader = GeoDataLoader(x, y, topo, batch_size=2, seed=5,
                               augment=True,
                               sharding=topo.batch_sharding(tr.mesh))
        st = tr.init_state(jax.random.PRNGKey(0), x[:2])
        st, _recs = tr.fit(st, loader, epochs=2)
        jax.block_until_ready(st.step)
        return jax.tree.map(lambda a: np.asarray(a), st.params)

    p0, p2 = run(0), run(2)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, b)


def test_real_cifar10_binary_layout_is_discovered(tmp_path):
    """The auto-switch a time-to-accuracy run relies on: when the
    canonical cifar-10-batches-bin layout is present under the data
    root — however it got there (tools/fetch_cifar10.py with egress, or
    a pre-mounted volume) — load_dataset returns the REAL records with
    synthetic=False.  The on-disk format is synthesized here, so the
    branch is proven without network access."""
    import os

    from geomx_tpu.data import load_dataset

    rng = np.random.RandomState(3)
    bindir = tmp_path / "cifar10" / "cifar-10-batches-bin"
    bindir.mkdir(parents=True)
    per = 5  # records per batch file; format: [label u8][3072 CHW bytes]
    raw = {}
    for fname in [f"data_batch_{i}.bin" for i in range(1, 6)] + [
            "test_batch.bin"]:
        recs = np.concatenate(
            [np.concatenate([[rng.randint(0, 10)],
                             rng.randint(0, 256, size=3072)])[None]
             for _ in range(per)]).astype(np.uint8)
        recs.tofile(bindir / fname)
        raw[fname] = recs

    d = load_dataset("cifar10", root=str(tmp_path))
    assert d["synthetic"] is False
    assert d["train_x"].shape == (5 * per, 32, 32, 3)
    assert d["test_x"].shape == (per, 32, 32, 3)
    # first training record round-trips exactly (CHW planes -> HWC)
    rec0 = raw["data_batch_1.bin"][0]
    assert d["train_y"][0] == rec0[0]
    np.testing.assert_array_equal(
        d["train_x"][0], rec0[1:].reshape(3, 32, 32).transpose(1, 2, 0))

    # and the fetch tool agrees the dataset is "present" at the SAME
    # root a caller passes to ensure() (GEOMX_DATA_DIR), so that no
    # download is attempted for a pre-mounted volume
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import fetch_cifar10
        assert fetch_cifar10.present(str(tmp_path))
        assert fetch_cifar10.ensure(str(tmp_path), quiet=True)
    finally:
        sys.path.pop(0)
