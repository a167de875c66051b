"""Prints, from one traced run of a cell, the device time under one scope
of the program's vocabulary by what the ops are: the split of a layer's
milliseconds by op name (`benchmark/tools/unscoped_ops.py` does the same
for the time under no scope; this copies that tool's cell set-up and
imports its `instructions_of`, because a PR that is no `benchmark` PR may
not edit `benchmark/` to share it: ROADMAP D20).  Run on the chip:

    chiprun -- python3 tools/scope_ops.py --workload kimilinear-fsa-1c \
        --seed <n> --scope kda/scan [--top 40] [--exact]

``--exact``: only the instructions whose scope path holds ``--scope`` and
nothing else, so ``--scope step/forward_backward --exact`` lists that
scope's self time (what ``fwd_bwd_self_ms`` reads), the tails then from
the name stack's last ``step/forward_backward`` on with ``layer<i>``
dropped, so that the blocks fold together.

- SCOPE: the scope's sum a step, by pass (first forward, recomputed
  forward, backward: ``telemetry/layers.PASSES``), and the instructions
  the compiler made inside the loops the scope's callers run (no op name;
  the loop's scope is theirs);
- PIECE lines: the time by pass and by the op name's tail after the
  scope (``while/body/dot_general``, ``exp``, ...), with the opcodes;
- OP lines: the ``--top`` largest instructions;
- AROUND: the unnamed instructions (copies, fast-memory prefetches) by
  opcode in every computation that also holds an instruction of the
  scope: what the compiler schedules around the layer.
"""
import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=.*?op_name=\"([^\"]*)\"")


def op_names(hlo_text: str) -> dict:
    """{instruction: op_name} (the first meaning of a repeated name)."""
    out = {}
    for line in hlo_text.splitlines():
        hit = _LINE.match(line)
        if hit:
            out.setdefault(hit.group(1), hit.group(2))
    return out


def tail_of(op_name: str, scope: str, exact: bool = False) -> str:
    """The name stack after the scope, transposes and jvps dropped; with
    ``exact`` after its last occurrence, the blocks' names dropped too."""
    if exact:
        tail = re.sub(r"layer\d+/", "", op_name.rsplit(scope, 1)[-1])
    else:
        tail = op_name.split(scope, 1)[-1]
    return re.sub(r"(transpose|jvp|checkpoint|rematted_computation)\(|\)",
                  "", tail.strip("/")) or "."


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--scope", default="kda/scan")
    parser.add_argument("--top", type=int, default=40)
    parser.add_argument("--exact", action="store_true")
    args = parser.parse_args(argv)

    import numpy as np
    from benchmark import run, trace_reduce
    from benchmark.cells import Registry
    from benchmark.tools.unscoped_ops import instructions_of
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
    trace_dir = os.path.join(ROOT, ".benchmark_cache", "scope_ops",
                             args.workload)
    run.traced_segments(cell, trainer, state, x, y, args.seed, trace_dir)
    del state
    trace = trace_reduce.reduce_trace(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)

    signature = layers.last_step_signature()
    table = trainer.step_layers(*signature)["ops"]
    text = trainer.train_step.lower(*signature).compile().as_text()
    where, names = instructions_of(text), op_names(text)
    steps = trace["steps"]
    ms = lambda seconds: 1e3 * seconds / steps

    if args.exact:
        under = lambda scope: not scope.replace(args.scope, "").strip("/")
    else:
        under = lambda scope: args.scope in scope
    inside, homes = [], set()
    for name, seconds in trace["by_op_s"].items():
        entry = table.get(name)
        if entry is not None and entry.scope and under(entry.scope):
            opcode, result, home = where.get(name, ("?", "?", "?"))
            inside.append((seconds, name, entry.pass_ or entry.direction,
                           opcode, result,
                           tail_of(names.get(name, ""), args.scope, args.exact)
                           if name in names else "(unnamed)"))
            homes.add(home)
    inside.sort(reverse=True)
    print("SCOPE " + json.dumps({
        "scope": args.scope, "steps": steps,
        "ms": ms(sum(r[0] for r in inside)),
        **{d + "_ms": ms(sum(r[0] for r in inside if r[2] == d))
           for d in layers.PASSES},
        "unnamed_ms": ms(sum(r[0] for r in inside if r[5] == "(unnamed)")),
        "instructions": len(inside)}))
    pieces = {}
    for seconds, _name, which, opcode, _result, tail in inside:
        rec = pieces.setdefault((which, tail), [0.0, 0, set()])
        rec[0] += seconds
        rec[1] += 1
        rec[2].add(opcode)
    for (which, tail), (seconds, count, opcodes) in sorted(
            pieces.items(), key=lambda kv: -kv[1][0])[:args.top]:
        print("PIECE " + json.dumps({
            "ms": ms(seconds), "count": count, "pass": which,
            "tail": tail, "opcodes": sorted(opcodes)}))
    for seconds, name, which, opcode, result, tail in inside[:args.top]:
        print("OP " + json.dumps({
            "ms": ms(seconds), "name": name, "pass": which,
            "opcode": opcode, "type": result, "tail": tail}))
    around = {}
    for name, seconds in trace["by_op_s"].items():
        opcode, _result, home = where.get(name, ("?", "?", "?"))
        if home in homes and home != "ENTRY" and name not in names:
            rec = around.setdefault((home, opcode), [0.0, 0])
            rec[0] += seconds
            rec[1] += 1
    for (home, opcode), (seconds, count) in sorted(
            around.items(), key=lambda kv: -kv[1][0])[:args.top]:
        print("AROUND " + json.dumps({
            "ms": ms(seconds), "count": count, "opcode": opcode,
            "in": home}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
