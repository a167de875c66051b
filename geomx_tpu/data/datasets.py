"""Dataset loading: MNIST / FashionMNIST / CIFAR10, as in the reference
(examples/utils.py:39-80), from local files with a deterministic synthetic
fallback.

The synthetic fallback generates a *learnable* class-conditional dataset
(per-class Gaussian prototypes + noise), so convergence tests and
benchmarks run in hermetic environments with zero network egress.  Real
data is picked up automatically when present under ``root``:

- MNIST / FashionMNIST: idx-ubyte files (optionally .gz), the format the
  reference's MXNet iterators read (src/io/iter_mnist.cc);
- CIFAR10: the python pickle batches (cifar-10-batches-py) or the binary
  .bin format.
"""

from __future__ import annotations

import gzip
import os
import pickle
import struct
from typing import Tuple

import numpy as np

DATASETS = ("mnist", "fashion-mnist", "cifar10", "synthetic")

_SHAPES = {
    "mnist": (28, 28, 1),
    "fashion-mnist": (28, 28, 1),
    "cifar10": (32, 32, 3),
    "synthetic": (32, 32, 3),
}


def _maybe_open(path: str):
    if os.path.exists(path):
        return open(path, "rb")
    if os.path.exists(path + ".gz"):
        return gzip.open(path + ".gz", "rb")
    return None


def _read_idx_images(path: str):
    f = _maybe_open(path)
    if f is None:
        return None
    with f:
        magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            return None
        data = np.frombuffer(f.read(n * rows * cols), dtype=np.uint8)
        return data.reshape(n, rows, cols, 1)


def _read_idx_labels(path: str):
    f = _maybe_open(path)
    if f is None:
        return None
    with f:
        magic, n = struct.unpack(">II", f.read(8))
        if magic != 2049:
            return None
        return np.frombuffer(f.read(n), dtype=np.uint8).astype(np.int32)


def _load_mnist_like(root: str):
    candidates = [root, os.path.join(root, "raw")]
    for d in candidates:
        xs = _read_idx_images(os.path.join(d, "train-images-idx3-ubyte"))
        ys = _read_idx_labels(os.path.join(d, "train-labels-idx1-ubyte"))
        xt = _read_idx_images(os.path.join(d, "t10k-images-idx3-ubyte"))
        yt = _read_idx_labels(os.path.join(d, "t10k-labels-idx1-ubyte"))
        if all(v is not None for v in (xs, ys, xt, yt)):
            return xs, ys, xt, yt
    return None


def _load_cifar10(root: str):
    pydir = os.path.join(root, "cifar-10-batches-py")
    if os.path.isdir(pydir):
        xs, ys = [], []
        for i in range(1, 6):
            with open(os.path.join(pydir, f"data_batch_{i}"), "rb") as f:
                d = pickle.load(f, encoding="bytes")
            xs.append(d[b"data"])
            ys.append(np.asarray(d[b"labels"], np.int32))
        with open(os.path.join(pydir, "test_batch"), "rb") as f:
            d = pickle.load(f, encoding="bytes")
        xtest = d[b"data"]
        ytest = np.asarray(d[b"labels"], np.int32)

        def to_nhwc(a):
            return np.asarray(a, np.uint8).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)

        return (to_nhwc(np.concatenate(xs)), np.concatenate(ys),
                to_nhwc(xtest), ytest)
    bindir = os.path.join(root, "cifar-10-batches-bin")
    if os.path.isdir(bindir):
        def read_bin(paths):
            recs = []
            for p in paths:
                raw = np.fromfile(p, dtype=np.uint8).reshape(-1, 3073)
                recs.append(raw)
            raw = np.concatenate(recs)
            y = raw[:, 0].astype(np.int32)
            x = raw[:, 1:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
            return x, y
        train_files = [os.path.join(bindir, f"data_batch_{i}.bin") for i in range(1, 6)]
        if all(os.path.exists(p) for p in train_files):
            xs, ys = read_bin(train_files)
            xt, yt = read_bin([os.path.join(bindir, "test_batch.bin")])
            return xs, ys, xt, yt
    return None


def _synthetic(shape: Tuple[int, int, int], num_classes: int = 10,
               train_n: int = 4096, test_n: int = 1024, seed: int = 42):
    """Class-conditional Gaussian images: prototype[class] + noise."""
    rng = np.random.RandomState(seed)
    protos = rng.uniform(0, 255, size=(num_classes,) + shape).astype(np.float32)

    def make(n, seed2):
        r = np.random.RandomState(seed2)
        y = r.randint(0, num_classes, size=n).astype(np.int32)
        noise = r.normal(0, 64.0, size=(n,) + shape).astype(np.float32)
        x = np.clip(protos[y] + noise, 0, 255).astype(np.uint8)
        return x, y

    xs, ys = make(train_n, seed)
    xt, yt = make(test_n, seed + 1)
    return xs, ys, xt, yt


def load_dataset(name: str = "cifar10", root: str = "/root/data",
                 synthetic_fallback: bool = True,
                 synthetic_train_n: int = 4096, seed: int = 42):
    """Returns dict(train_x[u8 NHWC], train_y[i32], test_x, test_y, synthetic).

    ``"synthetic"`` asked for by name is the seeded class-conditional
    set.  A NAMED dataset with no local files is replaced by it only
    under ``synthetic_fallback`` — with a ``UserWarning``, and
    ``synthetic: True`` in the result, which callers that print or
    record results must carry along: a run on substitute data is never
    reported under the real dataset's name.

    Normalization to [0,1] floats happens in the loader/step, keeping the
    host->device transfer at 1 byte/pixel.
    """
    name = name.lower()
    if name not in DATASETS:
        raise ValueError(f"Unknown dataset {name!r}; options: {DATASETS}")
    shape = _SHAPES[name]
    loaded = None
    if name in ("mnist", "fashion-mnist"):
        loaded = _load_mnist_like(os.path.join(root, name))
    elif name == "cifar10":
        loaded = _load_cifar10(os.path.join(root, name)) or _load_cifar10(root)
    synthetic = loaded is None
    if synthetic:
        if name != "synthetic":
            if not synthetic_fallback:
                raise FileNotFoundError(
                    f"No local data for {name} under {root}")
            import warnings
            warnings.warn(
                f"no local {name} data under {root}: substituting the "
                "seeded synthetic set (result carries synthetic=True)",
                UserWarning, stacklevel=2)
        loaded = _synthetic(shape, train_n=synthetic_train_n, seed=seed)
    xs, ys, xt, yt = loaded
    return {"train_x": xs, "train_y": ys, "test_x": xt, "test_y": yt,
            "synthetic": synthetic, "shape": shape}
