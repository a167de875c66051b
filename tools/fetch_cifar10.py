"""Fetch CIFAR-10 (binary version) into the data root.

The north star is time-to-92%-accuracy on REAL CIFAR-10
(BASELINE.md); the dataset is not redistributable inside the repo, so
this script provisions it at run time when the environment has network
egress.  `ensure(quiet=True)` returns instead of raising when the
download is impossible.

Usage: python tools/fetch_cifar10.py [dest_root]
Dest defaults to $GEOMX_DATA_DIR or /root/data; the extracted layout is
<root>/cifar-10-batches-bin/*.bin, which geomx_tpu.data.load_dataset
discovers directly.
"""

from __future__ import annotations

import hashlib
import os
import sys
import tarfile
import tempfile
import urllib.request

URL = "https://www.cs.toronto.edu/~kriz/cifar-10-binary.tar.gz"
MD5 = "c32a1d4ab5d03f1284b67883e8d87530"
DIRNAME = "cifar-10-batches-bin"


def present(root: str) -> bool:
    """True iff the binary layout exists under any location
    ``load_dataset("cifar10", root=root)`` probes — both
    <root>/cifar10/cifar-10-batches-bin (pre-mounted volumes) and
    <root>/cifar-10-batches-bin (this tool's own download target).
    ensure() must agree with the loader, or a pre-mounted dataset
    triggers a pointless (and in egress-less environments, slow)
    download attempt before the loader finds the data anyway."""
    need = [f"data_batch_{i}.bin" for i in range(1, 6)] + ["test_batch.bin"]
    for d in (os.path.join(root, "cifar10", DIRNAME),
              os.path.join(root, DIRNAME)):
        if all(os.path.exists(os.path.join(d, f)) for f in need):
            return True
    return False


def ensure(root: str | None = None, quiet: bool = False,
           timeout: float = 300.0) -> bool:
    """Returns True iff the dataset is present (possibly after download)."""
    root = root or os.environ.get("GEOMX_DATA_DIR", "/root/data")
    if present(root):
        return True
    path = None
    try:
        os.makedirs(root, exist_ok=True)
        if not quiet:
            print(f"downloading {URL} -> {root}", flush=True)
        req = urllib.request.Request(URL, headers={"User-Agent": "geomx"})
        with urllib.request.urlopen(req, timeout=timeout) as r, \
                tempfile.NamedTemporaryFile(dir=root, suffix=".tar.gz",
                                            delete=False) as tmp:
            path = tmp.name
            h = hashlib.md5()
            while True:
                chunk = r.read(1 << 20)
                if not chunk:
                    break
                h.update(chunk)
                tmp.write(chunk)
        if h.hexdigest() != MD5:
            raise IOError(f"md5 mismatch: {h.hexdigest()} != {MD5}")
        with tarfile.open(path, "r:gz") as tf:
            try:
                tf.extractall(root, filter="data")
            except TypeError:  # Python < 3.12 without the filter arg
                tf.extractall(root)
        return present(root)
    except Exception as e:
        if not quiet:
            print(f"fetch failed: {e!r}", file=sys.stderr, flush=True)
        return False
    finally:
        if path is not None and os.path.exists(path):
            try:
                os.unlink(path)
            except OSError:
                pass


if __name__ == "__main__":
    ok = ensure(sys.argv[1] if len(sys.argv) > 1 else None)
    print("cifar10 present" if ok else "cifar10 UNAVAILABLE")
    sys.exit(0 if ok else 1)
