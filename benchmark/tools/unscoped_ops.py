"""Prints, from one traced run of a cell, the device time the program's
table of its step charges to no scope of the vocabulary, instruction by
instruction: what `unscoped_device_pct` is made of.  Run on the chip:

    chiprun -- python3 benchmark/tools/unscoped_ops.py --workload <cell> \
        --seed <n> [--top 40]

It sets a cell up as `benchmark/run.py` does (same trainer, weights,
data, first steps), runs the cell's traced segments, joins the trace's own
times by instruction (`trace_reduce`) with `Trainer.step_layers` and with
the compiled step's HLO text, and prints:

- UNSCOPED: the sums, a step: everything, the instructions the compiler
  made (no op name), those the program named outside its vocabulary, and
  those the trace holds but the table does not;
- BY_OPCODE lines: the unscoped time by HLO opcode and enclosing
  computation (`ENTRY` or a loop's body), largest first;
- OP lines: the `--top` largest unscoped instructions with opcode, result
  type and enclosing computation.
"""
import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_HEAD = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*?)\s([a-z][a-z0-9\-]*)\(")


def instructions_of(hlo_text: str) -> dict:
    """{instruction name: (opcode, result type, computation)} of every
    computation's instructions; a name that repeats (inside fused
    computations) keeps its first meaning, which the table never asks
    for."""
    out, current = {}, None
    for match in re.finditer(r"[^\n]+", hlo_text):
        line = match.group(0)
        if current is None:
            head = _HEAD.match(line)
            if head:
                current = "ENTRY" if head.group(1) else head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        ins = _INSTRUCTION.match(line.split("metadata=", 1)[0])
        if ins:
            out.setdefault(ins.group(1), (ins.group(3), ins.group(2)[:80],
                                          current[:48]))
    return out


def unscoped_rows(by_op_s: dict, table: dict, where: dict) -> list:
    """[(seconds, instruction, kind, opcode, type, computation)] of the
    trace's instructions under no scope, largest first.  kind: `unnamed`
    (the compiler made it), `named` (an op name outside the vocabulary),
    `unknown` (not in the table)."""
    rows = []
    for name, seconds in by_op_s.items():
        entry = table.get(name)
        if entry is not None and entry.scope:
            continue
        kind = ("unknown" if entry is None
                else "unnamed" if entry.scope is None else "named")
        rows.append((seconds, name, kind)
                    + where.get(name, ("?", "?", "?")))
    return sorted(rows, reverse=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--top", type=int, default=40)
    args = parser.parse_args(argv)

    import numpy as np
    from benchmark import run, trace_reduce
    from benchmark.cells import Registry
    from geomx_tpu.telemetry import layers
    cell = Registry(ROOT).cell(args.workload)
    run.configure_compile_cache()
    run.require_chips(cell["chips"])
    config, traffic = cell["config"], cell["traffic"]
    rows = traffic["parties"] * traffic["workers"] * config["per_chip_batch"]
    trainer = run.build_trainer(cell)
    x, y = cell["family"].make_data(
        config, np.random.default_rng(args.seed),
        rows * (traffic["n_check"] + config["data_steps"]))
    state, shapes = run.initial_state(cell, trainer, args.seed, x[:2])
    state, _ = run.first_steps(cell, trainer, state, shapes, x, y, args.seed)
    trace_dir = os.path.join(ROOT, ".benchmark_cache", "unscoped_ops",
                             args.workload)
    run.traced_segments(cell, trainer, state, x, y, args.seed, trace_dir)
    del state
    trace = trace_reduce.reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    signature = layers.last_step_signature()
    table = trainer.step_layers(*signature)["ops"]
    where = instructions_of(
        trainer.train_step.lower(*signature).compile().as_text())
    found = unscoped_rows(trace["by_op_s"], table, where)
    steps = trace["steps"]
    ms = lambda seconds: 1e3 * seconds / steps
    total = sum(trace["by_op_s"].values())
    print("UNSCOPED " + json.dumps({
        "steps": steps, "all_ops_ms": ms(total),
        "unscoped_ms": ms(sum(r[0] for r in found)),
        **{kind + "_ms": ms(sum(r[0] for r in found if r[2] == kind))
           for kind in ("unnamed", "named", "unknown")},
        "instructions": len(found)}))
    groups = {}
    for seconds, _name, kind, opcode, _type, computation in found:
        key = (kind, opcode, computation)
        rec = groups.setdefault(key, [0.0, 0])
        rec[0] += seconds
        rec[1] += 1
    for (kind, opcode, computation), (seconds, count) in sorted(
            groups.items(), key=lambda kv: -kv[1][0])[:args.top]:
        print("BY_OPCODE " + json.dumps({
            "ms": ms(seconds), "count": count, "kind": kind,
            "opcode": opcode, "in": computation}))
    for seconds, name, kind, opcode, result, computation in found[:args.top]:
        print("OP " + json.dumps({
            "ms": ms(seconds), "name": name, "kind": kind, "opcode": opcode,
            "type": result, "in": computation}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
