"""Time the elementwise halves of a grouped-query mixer on the chip: the
q / k norm with rotary, and the output gate, at the Trinity cell's shapes
or at those of ``--config <file>`` (a grouped-query decoder's
`benchmark/configs/*.json`: one sequence of its `sequence_length`, its
heads, and the eps and each layer kind's positions of the mixers its
family builds: Trinity's `rope_theta` on the window layers alone,
Mellum2's `rope_parameters` by layer type, YaRN's among them).

For each shape (``L:H:KV:d``, default one sequence of the cell's layer,
8,192 tokens of 32 query heads on 4 of 128), each layer kind (``window``:
rotary; ``global``: none, or the configuration's) and operand dtype, the
variants of each half:

- ``chain``: what `models/afmoe.GQAMixer` ran before PR 37
  (`decoder.RMSNorm` then `afmoe.rotary`; ``(o * sigmoid(logits))``),
  JAX's backward; not under a YaRN table, which it never ran;
- ``jnp``: the restated jnp form (`ops/gqa_elementwise.norm_rotary_ref`,
  `gated_ref`: a roll and a signed sine, one cast in and one out), JAX's
  backward;
- ``kernel``: the Pallas pair with its own backward
  (`ops/gqa_elementwise.norm_rotary`), where there is one: with rotary.
  The pairs PR 37 wrote for the norm alone and for the gate did not beat
  the jnp forms here and went (PERF.md section 6, PR 37, has their lines).

A call to the device costs the host ~0.6 ms whatever it does, and cold
operands read twice their in-step time (PERF.md, PR 28), so one jitted
program runs ``--calls`` calls and MAKES every call's operands (and, for
forward + backward, its cotangents) inside it, one elementwise pass each
behind an optimization barrier, as the products and the attention kernels
leave them in a step; the results end in a barrier too, so that nothing is
fused away.  The making, timed alone, is taken off.  (The operands are
made as ``[1, L, H, d]``, which the kernel's ``[1, L, H d]`` view pays a
relayout for that a step's products do not need: a call of the pair reads
0.51 / 0.76 ms forward / backward here and 0.24 / 0.33 in
`trinitymini-fsa-1c`'s step, PERF.md, PR 37.)  A line gives
milliseconds for ONE call, forward and forward + backward, the kernels'
plans, and each variant's largest gap (forward, then every gradient, each
over the oracle's largest magnitude) from float64 arithmetic on the host
over the first ``--check-tokens`` tokens, with the float32 tables and
scales taken as given.  Last, one ``Verdict`` line where a kernel ran:
a step runs a layer's forward twice (rematerialised) and its backward
once, so the kernel stays where ``fwd + fwd_bwd`` beats the jnp form's by
more than ``--noise`` (ROADMAP D3: a kernel that does not beat XLA's own
program at real sizes is deleted).

    python tools/gqa_proj_timing.py [8192:32:4:128] [--dtypes bfloat16]
        [--kinds window] [--halves norm_rotary] [--skip chain,jnp]
        [--set MAX_TILE=128]
    python tools/gqa_proj_timing.py \
        --config benchmark/configs/mellum2-12b-ep4.json --halves norm_rotary

``--interpret`` rehearses it on the CPU at a small shape
(``64:4:2:128``; no times).
"""
import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EPS, THETA = 1e-5, 10000.0


def median_ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def oracle_norm_rotary(q, k, q_scale, k_scale, eps, rope, gq, gk):
    """The chain in float64 on the host, rotate-half written plainly:
    (q', k'), (dq, dk, dq_scale, dk_scale).  The float32 frequencies and
    angles (`gqa_elementwise.rotary_frequencies`: a theta's, or YaRN's
    with its factor on cos and sin) are taken as given."""
    import numpy as np
    from geomx_tpu.ops import gqa_elementwise as ge
    f64 = lambda a: np.asarray(a, np.float64)
    length, d = q.shape[1], q.shape[-1]
    half = d // 2
    if rope is not None:
        inverse, factor = ge.rotary_frequencies(d, rope)
        angle = (np.arange(length, dtype=np.float32)[:, None]
                 * np.asarray(inverse, np.float32)[None])
        cos, sin = (factor * np.concatenate([f(f64(angle))] * 2, -1)[
            None, :, None] for f in (np.cos, np.sin))

    def one(x, scale, g):
        x, scale, g = f64(x), f64(scale), f64(g)
        r = 1.0 / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
        n = x * r
        out = n * scale
        if rope is not None:
            out = out * cos + np.concatenate(
                [-out[..., half:], out[..., :half]], -1) * sin
            gs = g * sin
            g = g * cos + np.concatenate([gs[..., half:], -gs[..., :half]],
                                         -1)
        dn = g * scale
        dx = r * (dn - n * np.mean(dn * n, -1, keepdims=True))
        return out, dx, np.sum(g * n, (0, 1, 2))

    qo, dq, dqs = one(q, q_scale, gq)
    ko, dk, dks = one(k, k_scale, gk)
    return (qo, ko), (dq, dk, dqs, dks)


def mixers(config: dict) -> dict:
    """{layer kind: the mixer the configuration's family builds}: its
    `rope` (None, a theta, a `gqa_elementwise.Yarn`) and `eps` are what
    the model's own layers run, whatever keys the file states them in."""
    from benchmark.cells import Registry, _load_module
    family = _load_module(
        Registry(ROOT).find("families", config["family"], ".py"),
        "benchmark_family_" + config["family"])
    cfg = family.build_model(config).cfg
    return {kind: cfg.make_mixer(kind, None) for kind in ("window", "global")}


def oracle_gated(o, logits, g):
    import numpy as np
    o, logits, g = (np.asarray(a, np.float64) for a in (o, logits, g))
    s = 1.0 / (1.0 + np.exp(-logits))
    return (o * s,), (g * s, g * o * s * (1.0 - s))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("shapes", nargs="*", default=None)
    parser.add_argument("--config", default=None,
                        help="a grouped-query decoder configuration's file: "
                             "the shape, eps and each kind's positions")
    parser.add_argument("--dtypes", default="bfloat16")
    parser.add_argument("--kinds", default="window,global")
    parser.add_argument("--calls", type=int, default=8)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--skip", default="",
                        help="comma-separated variants to leave out "
                             "(chain, jnp, kernel)")
    parser.add_argument("--check-tokens", type=int, default=256)
    parser.add_argument("--noise", type=float, default=0.03,
                        help="the share of the jnp form's time a kernel "
                             "has to win to stay")
    parser.add_argument("--halves", default="norm_rotary,gate")
    parser.add_argument("--set", action="append", default=[],
                        metavar="NAME=INT", help="a constant of "
                        "gqa_elementwise to try another plan with "
                        "(MAX_TILE, VMEM_BUDGET)")
    parser.add_argument("--interpret", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from geomx_tpu.models.afmoe import rotary
    from geomx_tpu.models.decoder import RMSNorm
    from geomx_tpu.ops import gqa_elementwise as ge

    for item in args.set:
        name, value = item.split("=")
        assert hasattr(ge, name), name
        setattr(ge, name, int(value))

    eps, ropes = EPS, {"window": THETA, "global": None}
    shapes = args.shapes or ["8192:32:4:128"]
    if args.config:
        with open(args.config) as fh:
            config = json.load(fh)
        built = mixers(config)
        eps = built["window"].eps
        ropes = {kind: mixer.rope for kind, mixer in built.items()}
        shapes = args.shapes or [":".join(str(config[key]) for key in (
            "sequence_length", "num_attention_heads", "num_key_value_heads",
            "head_dim"))]

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("not a TPU: a time from here is not a device time",
              file=sys.stderr)
        return 1
    skip = set(filter(None, args.skip.split(",")))

    def chain_norm_rotary(q, k, q_scale, k_scale, eps, theta):
        norm = RMSNorm(eps)
        q = norm.apply({"params": {"scale": q_scale}}, q)
        k = norm.apply({"params": {"scale": k_scale}}, k)
        if theta is not None:
            q, k = rotary(q, theta), rotary(k, theta)
        return q, k

    halves = {
        "norm_rotary": {
            "chain": chain_norm_rotary, "jnp": ge.norm_rotary_ref,
            "kernel": functools.partial(ge.norm_rotary,
                                        interpret=args.interpret)},
        "gate": {
            "chain": lambda o, l: ((o * jax.nn.sigmoid(l)).astype(o.dtype),),
            "jnp": lambda o, l: (ge.gated_ref(o, l),)},
    }

    def every_call(fn, count, made, fixed, backward):
        """One program: ``count`` times make the first ``made`` base arrays
        anew (the operands a call reads, and the cotangents where there
        are more) and run ``fn`` on the operands and the ``fixed`` ones
        (None: make them only), forward or forward + backward through
        every operand; a float32 that reads every result."""
        def one(c, acc, *base):
            step = 1.0 + c.astype(jnp.float32) * 2.0 ** -10
            new = jax.lax.optimization_barrier(tuple(
                (b.astype(jnp.float32) * step).astype(b.dtype)
                for b in base))
            if fn is None:
                outs = new
            elif backward:      # the results too, or the forward is dead
                out, transpose = jax.vjp(fn, *new[:made], *fixed)
                outs = (*out, *transpose(new[made:]))
            else:
                outs = fn(*new[:made], *fixed)
            outs = jax.lax.optimization_barrier(outs)
            return acc + sum(o.reshape(-1)[0].astype(jnp.float32)
                             for o in outs)

        return jax.jit(lambda *base: jax.lax.fori_loop(
            0, count, lambda c, acc: one(c, acc, *base), jnp.float32(0)))

    def gaps(got, want):
        assert len(got) == len(want)
        return [float(np.max(np.abs(np.asarray(g, np.float64) - w))
                      / np.max(np.abs(w))) for g, w in zip(got, want)]

    verdicts = []
    for shape in shapes:
        length, h, kv, d = (int(x) for x in shape.split(":"))
        check = min(args.check_tokens, length)
        for dtype in args.dtypes.split(","):
            dt = jnp.dtype(dtype)
            keys = jax.random.split(jax.random.PRNGKey(length + d), 9)
            normal = lambda key, shp, to=dt: jax.random.normal(
                key, shp, jnp.float32).astype(to)
            q, gq = (normal(key, (1, length, h, d)) for key in keys[:2])
            k, gk = (normal(key, (1, length, kv, d)) for key in keys[2:4])
            scales = tuple(1.0 + 0.1 * normal(key, (d,), jnp.float32)
                           for key in keys[4:6])
            o, go = (normal(key, (1, length, h * d)) for key in keys[6:8])
            logits = 2.0 * normal(keys[8], (1, length, h * d), jnp.float32)
            # half, layer kind, positions, operands, fixed operands,
            # cotangents
            cases = [("norm_rotary", kind, ropes[kind], (q, k), scales,
                      (gq, gk)) for kind in args.kinds.split(",")]
            cases.append(("gate", "any", None, (o, logits), (), (go,)))
            for half, kind, rope, operands, fixed, cots in cases:
                if half not in args.halves.split(","):
                    continue
                made, base = len(operands), operands + cots
                few = tuple(x[:, :check] for x in base)
                if half == "norm_rotary":
                    bind = lambda impl: (lambda *a: impl(*a, eps, rope))
                    want_out, want_grads = oracle_norm_rotary(
                        *few[:2], *fixed, eps, rope, *few[2:])
                else:
                    bind = lambda impl: impl
                    want_out, want_grads = oracle_gated(*few)
                line = {"half": half, "kind": kind, "rope": rope,
                        "dims": [length, h, kv, d], "dtype": dtype,
                        "calls": args.calls, "reps": args.reps,
                        "check_tokens": check, "set": args.set,
                        "device": jax.devices()[0].device_kind}
                variants = dict(halves[half])
                if isinstance(rope, ge.Yarn):   # the chain knew a theta only
                    variants.pop("chain", None)
                if rope is None:        # no kernel for the norm alone
                    variants.pop("kernel", None)
                else:
                    line["plans"] = [ge.norm_rotary_plan(
                        q.shape, k.shape, dt, back) for back in (False, True)]
                make = {}
                if on_chip:
                    for back, arrays in ((False, operands), (True, base)):
                        make[back] = median_ms(
                            every_call(None, args.calls, 0, (), back),
                            arrays, args.reps) / args.calls
                    line["make_ms"] = [make[False], make[True]]
                for name, impl in variants.items():
                    if name in skip:
                        continue
                    fn = bind(impl)
                    t0 = time.perf_counter()
                    out = jax.jit(fn)(*few[:made], *fixed)
                    grads = jax.jit(lambda *a: jax.vjp(
                        fn, *a[:made], *fixed)[1](a[made:]))(*few)
                    jax.block_until_ready((out, grads))
                    line[name + "_first_run_s"] = time.perf_counter() - t0
                    line[name + "_gap"] = gaps(out, want_out) + gaps(
                        grads, want_grads)
                    if not on_chip:      # rehearse the timed program too
                        jax.block_until_ready(every_call(
                            fn, 2, made, fixed, True)(*base))
                        continue
                    line[name + "_fwd_ms"] = median_ms(
                        every_call(fn, args.calls, made, fixed, False),
                        operands, args.reps) / args.calls - make[False]
                    line[name + "_fwd_bwd_ms"] = median_ms(
                        every_call(fn, args.calls, made, fixed, True),
                        base, args.reps) / args.calls - make[True]
                print(json.dumps(line), flush=True)
                if on_chip and {"jnp", "kernel"} <= variants.keys() - skip:
                    a_step = lambda name: (line[name + "_fwd_ms"]
                                           + line[name + "_fwd_bwd_ms"])
                    verdicts.append({
                        "half": half, "kind": kind, "dtype": dtype,
                        **{name + "_ms": a_step(name)
                           for name in variants if name not in skip},
                        "kernel": "stays" if a_step("kernel") < (
                            1.0 - args.noise) * a_step("jnp") else "deleted"})
    for verdict in verdicts:
        print("Verdict " + json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
