"""Compiles a cell's whole `train_step` for a DESCRIBED v5e (no chip: the
TPU compiler is installed here) from shapes alone, and writes the
optimized HLO with everything taken out that a change of names, lines or
comments moves: `metadata={...}`, the stack-frame tables, and the Pallas
kernels' serialized bodies (they hold source locations).  Two trees whose
outputs are equal line for line run the same program on the chip.

    JAX_PLATFORMS=cpu python3 tools/step_compile.py --out <dir> [cell ...]

prints one `STEP` line a cell (lines, sha256 of the stripped text,
seconds) and writes `<dir>/<cell>.hlo`.  To compare two commits, run it
in each tree (the parent's copy takes this file as it is) and `diff -q`
the two directories.  Nothing runs and nothing is allocated: the state's
shapes come from `jax.eval_shape`, so this says nothing about times."""
import argparse
import hashlib
import json
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_METADATA = re.compile(r',? ?metadata=\{(?:[^{}"]|"[^"]*")*\}')
_BODY = re.compile(r'"body":"[^"]*"')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def stripped(lines):
    """The HLO text's lines without metadata, stack-frame tables and
    kernel bodies."""
    in_table = False
    for line in lines:
        if in_table:
            in_table = bool(line.strip())
            continue
        if line.strip() in _TABLES:
            in_table = True
            continue
        yield _BODY.sub('"body":""', _METADATA.sub("", line))


def abstract_step(cell, devices):
    """(trainer on a mesh of `devices`, (state, x, y) as shapes with
    their shardings on it) of a cell's plain path: dense optimizer state,
    the sync algorithm's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from benchmark import run
    from geomx_tpu.sync import get_sync_algorithm
    from geomx_tpu.topology import HiPSTopology
    from geomx_tpu.train import Trainer
    from geomx_tpu.train.state import TrainState
    from geomx_tpu.train.step import _norm_input

    config, traffic = cell["config"], cell["traffic"]
    slots = (traffic["parties"], traffic["workers"])
    geo, opt = run.geo_config(cell), config["optimizer"]
    topo = HiPSTopology(num_parties=slots[0], workers_per_party=slots[1])
    trainer = Trainer(
        cell["family"].build_model(config), topo,
        optax.adam(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"]),
        sync=get_sync_algorithm(geo), config=geo,
        mesh=topo.build_mesh(devices))
    batch = config["per_chip_batch"]
    x, y = cell["family"].make_data(config, np.random.default_rng(0),
                                    slots[0] * slots[1] * batch)
    variables = dict(jax.eval_shape(
        lambda r, x0: trainer.model.init(r, _norm_input(x0), train=False),
        jax.random.PRNGKey(0), jnp.asarray(x[:2])))
    params = variables.pop("params")
    opt_state = jax.eval_shape(trainer.tx.init, params)
    sync_state = jax.eval_shape(
        lambda p, m: trainer.sync.init_state(p, model_state=m),
        params, variables)
    replica = topo.replica_sharding(trainer.mesh)
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        slots + tuple(a.shape), a.dtype, sharding=replica), tree)
    state = TrainState(
        step=jax.ShapeDtypeStruct(
            (), jnp.int32, sharding=topo.replicated_sharding(trainer.mesh)),
        params=placed(params), opt_state=placed(opt_state),
        model_state=placed(variables), sync_state=placed(sync_state))
    rows = lambda a: jax.ShapeDtypeStruct(
        slots + (batch,) + a.shape[1:], a.dtype, sharding=replica)
    return trainer, (state, rows(x), rows(y))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cells", nargs="*")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    from jax.experimental import topologies
    from benchmark.cells import Registry
    from geomx_tpu.ops import dispatch
    reg = Registry(ROOT)
    os.makedirs(args.out, exist_ok=True)
    for name in args.cells or list(reg.workloads):
        begin = time.perf_counter()
        cell = reg.cell(name)
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[:cell["chips"]]
        trainer, signature = abstract_step(cell, devices)
        with dispatch.kernels("native"):
            text = trainer.train_step.lower(*signature).compile().as_text()
        digest, count = hashlib.sha256(), 0
        with open(os.path.join(args.out, name + ".hlo"), "w") as f:
            for line in stripped(m.group(0) for m in
                                 re.finditer(r"[^\n]*\n?", text) if m.group(0)):
                f.write(line)
                digest.update(line.encode())
                count += 1
        del text
        print("STEP " + json.dumps({
            "cell": name, "lines": count, "sha256": digest.hexdigest(),
            "seconds": time.perf_counter() - begin}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
