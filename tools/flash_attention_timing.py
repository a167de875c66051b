"""Time the flash-attention kernels on the chip, at the cells' shapes.

For each shape (``name`` or ``B:L:H:D:Dv:causal[:Hkv[:window]]``) and
dtype it makes
``calls`` sets of q on the device and times one jitted program that runs
all of them against one k, v (and one dO), ending in
``block_until_ready``.  A call to the device costs the host about 0.6 ms
whatever it does, so the calls share one program and the line gives
milliseconds per call, forward alone (with ``lse``) and forward +
backward.  Variants:

- ``this``: ``geomx_tpu/ops/flash_attention.py`` as it stands;
- ``other``: the same file of a git ref (``--ref``, read with
  ``git show <ref>:geomx_tpu/ops/flash_attention.py``) or at a path
  (``--other``: the chip tool's copy has no ``.git``, so write the file
  out first), loaded as a module of its own;
- ``xla``: the dense form in plain XLA, operands in the inputs' dtype,
  float32 scores and softmax, one sequence at a time where a batch's
  scores would pass ``--xla-score-bytes``; left out where one sequence's
  would (the latent shape at 8,192: 8.6 GB).

Named shapes: ``bert`` 16 x 512 x 16 x 64 (the BERT cells' layer),
``latent`` 1 x 8,192 x 32 x 192/128 causal (one sequence of the
Kimi cell's MLA layer), ``mid`` 16 x 2,048 x 16 x 64, ``latent256`` 1 x
16,384 x 20 x 256/256 causal (the GLM cell's one sequence: its heads,
widths and length read from ``benchmark/configs/glm-4.7-flash-ep8.json``),
``trinity-global`` 1 x 8,192 x 32 on 4 of 128 causal and ``mellum-global``
1 x 16,384 x 32 on 4 of 128 causal, ``trinity-window`` and
``mellum-window`` the same under a band of 2,048 and of 1,024 keys (the
grouped-query cells' layers, whose ONE backward kernel keeps the group of
eight's dq^T, 32 and 64 MiB, and asks Mosaic for 60.5 and 92.5 MiB in
all; ``--set ONE_KERNEL_VMEM=0`` times the two kernels at any shape, and
a larger or smaller bound is defended here), ``ouro-full`` 1 x 8,192 x
16 on 16 of 128 causal (the looped cell's layer, 24 applications a step:
heads, width and length read from
``benchmark/configs/ouro-2.6b-6of48.json``).

One JSON line a shape and dtype on stdout: the plan
(``attention_plan``: blocks, heads a step, ``backward_kernels`` one or
two, the VMEM bytes with ``resident_bytes``, the dq^T one backward kernel
keeps, apart), each variant's first-run seconds and ms per call,
and the largest difference of each variant's output and gradients from
the dense form in float32 at ``highest`` precision on the same inputs
(over ``--check-heads`` heads, relative to the reference's largest
magnitude).  ROADMAP D3: a kernel that does not beat XLA's own fusion at
real sizes is deleted with its flag; this is the measurement that rule
asks for (PERF.md).

    python tools/flash_attention_timing.py bert latent mid \
        --other _scratch/flash_attention_pr29.py
"""
import argparse
import functools
import importlib
import importlib.util
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# (B, L, H, D, Dv, causal[, key/value heads[, window]])
NAMED = {"bert": (16, 512, 16, 64, 64, False),
         "latent": (1, 8192, 32, 192, 128, True),
         "mid": (16, 2048, 16, 64, 64, False),
         "trinity-global": (1, 8192, 32, 128, 128, True, 4),
         "mellum-global": (1, 16384, 32, 128, 128, True, 4),
         "trinity-window": (1, 8192, 32, 128, 128, True, 4, 2048),
         "mellum-window": (1, 16384, 32, 128, 128, True, 4, 1024)}
CALLS = {"bert": 24, "latent": 2, "mid": 4, "latent256": 1, "ouro-full": 2,
         "trinity-global": 2, "mellum-global": 1,
         "trinity-window": 2, "mellum-window": 1}


def latent_shape(path):
    """(B, L, H, D, Dv, causal) of a configuration file's latent attention:
    `qk_nope_head_dim` + `qk_rope_head_dim` wide q and k, `v_head_dim`
    wide v, one sequence of `sequence_length`."""
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    return (1, config["sequence_length"], config["num_attention_heads"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"], True)


NAMED["latent256"] = latent_shape("benchmark/configs/glm-4.7-flash-ep8.json")


def full_shape(path):
    """(B, L, H, D, D, causal, key/value heads) of a configuration file's
    full attention layers: `num_attention_heads` on `num_key_value_heads`
    of `head_dim`, one sequence of `sequence_length`."""
    with open(os.path.join(ROOT, path)) as f:
        config = json.load(f)
    return (1, config["sequence_length"], config["num_attention_heads"],
            config["head_dim"], config["head_dim"], True,
            config["num_key_value_heads"])


NAMED["ouro-full"] = full_shape("benchmark/configs/ouro-2.6b-6of48.json")


def load_other(ref, path):
    """The module of another flash_attention.py, or None."""
    if ref:
        source = subprocess.run(
            ["git", "show", f"{ref}:geomx_tpu/ops/flash_attention.py"],
            cwd=ROOT, check=True, capture_output=True, text=True).stdout
        handle = tempfile.NamedTemporaryFile(
            "w", suffix=".py", prefix="flash_attention_other_", delete=False)
        handle.write(source)
        handle.close()
        path = handle.name
    if not path:
        return None
    spec = importlib.util.spec_from_file_location("other_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def dense(q, k, v, causal, window=None, precision=None):
    """Plain XLA: operands as given (k and v repeated to q's heads where
    they have fewer), float32 scores and softmax; `window`: the causal
    band's width in keys."""
    import jax
    import jax.numpy as jnp
    scale = q.shape[-1] ** -0.5
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
        seen = cols <= rows
        if window is not None:
            seen = seen & (rows - cols < window)
        s = jnp.where(seen, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      precision=precision,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def median_ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("shapes", nargs="+",
                        help="a name of NAMED | "
                             "B:L:H:D:Dv:causal(0/1)[:Hkv[:window]]")
    parser.add_argument("--dtypes", default="bfloat16,float32")
    parser.add_argument("--calls", type=int, default=None)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--ref", default=None, help="git ref of `other`")
    parser.add_argument("--other", default=None,
                        help="path of another flash_attention.py")
    parser.add_argument("--skip", default="",
                        help="comma-separated variants to leave out")
    parser.add_argument("--check-heads", type=int, default=2)
    parser.add_argument("--xla-score-bytes", type=float, default=1.5e9)
    parser.add_argument("--set", action="append", default=[],
                        metavar="NAME=INT", help="a constant of this "
                        "file's module to try another plan with "
                        "(VMEM_BUDGET, MAX_BLOCK, MAX_HEADS, "
                        "ONE_KERNEL_VMEM)")
    parser.add_argument("--interpret", action="store_true",
                        help="rehearse on the CPU: no times, kernels "
                             "interpreted")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    # `geomx_tpu.ops.flash_attention` the attribute is the function
    this = importlib.import_module("geomx_tpu.ops.flash_attention")
    for item in args.set:
        name, value = item.split("=")
        assert hasattr(this, name), name
        setattr(this, name, int(value))

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("not a TPU: a time from here is not a device time",
              file=sys.stderr)
        return 1
    modules = {"this": this}
    other = load_other(args.ref, args.other)
    if other is not None:
        modules["other"] = other
    skip = set(filter(None, args.skip.split(",")))

    def kernel_pair(module, causal, window):
        """(forward with lse, forward + backward) of one module."""
        given = dict(causal=causal, interpret=args.interpret)
        if window is not None:
            given["window"] = window
        fwd = functools.partial(module.flash_attention_with_lse, **given)

        def both(q, k, v, g):
            out, lse = fwd(q, k, v)
            return module.flash_attention_bwd(q, k, v, out, lse, g, **given)
        return (lambda q, k, v, g: fwd(q, k, v)[0]), both

    def xla_pair(causal, window, per_sequence):
        one = functools.partial(dense, causal=causal, window=window)
        if per_sequence:
            one = lambda q, k, v, inner=one: jax.lax.map(
                lambda x: inner(x[0][None], x[1][None], x[2][None])[0],
                (q, k, v))

        def both(q, k, v, g):
            return jax.vjp(one, q, k, v)[1](g)
        return (lambda q, k, v, g: one(q, k, v)), both

    def yardstick(q, k, v, g, causal, window):
        """(out, (dq, dk, dv)) of the dense form at `highest`, one query
        head at a time (a head's scores at 16,384 are 1 GB), dk and dv
        summed over the query heads that read a key/value head."""
        group = q.shape[2] // k.shape[2]
        heads_first = lambda x: jnp.moveaxis(x, 2, 0)[:, :, :, None]

        def one(x):
            out, pull = jax.vjp(functools.partial(
                dense, causal=causal, window=window, precision="highest"),
                *x[:3])
            return (out,) + pull(x[3])

        out, dq, dk, dv = (
            jnp.moveaxis(x[:, :, :, 0], 0, 2) for x in jax.lax.map(
                one, tuple(map(heads_first, (
                    q, jnp.repeat(k, group, axis=2),
                    jnp.repeat(v, group, axis=2), g)))))
        over = lambda x: x.reshape(*x.shape[:2], -1, group,
                                   x.shape[-1]).sum(3)
        return out, (dq, over(dk), over(dv))

    def split(x, d):
        """[..., L, H d] -> [..., L, H, d].  The operands are made as the
        models make them, heads side by side out of a product, and split
        inside the timed program: a layout change a variant needs is in
        its time, one it does not need is not."""
        return x.reshape(*x.shape[:-1], x.shape[-1] // d, d)

    def on_slabs(fn, d, dv):
        return jax.jit(lambda q, k, v, g: fn(
            split(q, d), split(k, d), split(v, dv), split(g, dv)))

    def every_call(fn, count, d, dv):
        """`fn` on each of `count` q's in one program.  The loop's carry
        is the last call's result and starts from zeros of its shape: a
        first call made outside the loop would be dead code (nothing reads
        it once the loop has run) and XLA would drop it."""
        def run(qs, k, v, g):
            k, v, g = split(k, d), split(v, dv), split(g, dv)
            one = lambda c: fn(split(qs[c], d), k, v, g)
            zeros = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(one, 0))
            return jax.lax.fori_loop(0, count, lambda c, _: one(c), zeros)
        return jax.jit(run)

    for shape in args.shapes:
        B, L, H, D, Dv, causal, *grouped = NAMED.get(shape) or (
            int(x) for x in shape.split(":"))
        causal, Hkv, window = bool(causal), *(grouped + [H, None][
            len(grouped):])
        group = H // Hkv
        count = args.calls or CALLS.get(shape, 4)
        for dtype in args.dtypes.split(","):
            dt = jnp.dtype(dtype)
            keys = jax.random.split(jax.random.PRNGKey(L + D), 4)
            draw = lambda key, *dims: jax.random.normal(
                key, dims, jnp.float32).astype(dt)
            qs = draw(keys[0], count, B, L, H * D)
            k = draw(keys[1], B, L, Hkv * D)
            v = draw(keys[2], B, L, Hkv * Dv)
            g = draw(keys[3], B, L, H * Dv)
            plan = this.attention_plan(L, L, H, D, Dv, dt, causal,
                                       kv_heads=Hkv)
            line = {"shape": shape, "dims": [B, L, H, D, Dv, Hkv],
                    "causal": causal, "window": window, "dtype": dtype,
                    "calls": count,
                    "plan": dict(plan._asdict(),
                                 backward_kernels=plan.backward_kernels),
                    "set": args.set,
                    "reps": args.reps,
                    "device": jax.devices()[0].device_kind}
            score_bytes = 4.0 * H * L * L
            variants = {name: kernel_pair(module, causal, window)
                        for name, module in modules.items()}
            if score_bytes <= args.xla_score_bytes:
                variants["xla"] = xla_pair(
                    causal, window, B * score_bytes > args.xla_score_bytes)
            else:
                line["xla"] = "left out: %.1f GB of scores a sequence" % (
                    score_bytes / 1e9)

            # the yardstick: float32 at `highest`, over a few key/value
            # heads and the query heads that read them
            ch = min(args.check_heads, Hkv) if group == 1 else 1
            cut = lambda x, e, n: split(x, e)[..., :n, :].astype(jnp.float32)
            few = (cut(qs[0], D, ch * group), cut(k, D, ch), cut(v, Dv, ch),
                   cut(g, Dv, ch * group))
            want_out, want = jax.jit(functools.partial(
                yardstick, causal=causal, window=window))(*few)

            def gap(got, ref):
                got = got[..., :ref.shape[-2], :].astype(jnp.float32)
                return float(jnp.max(jnp.abs(got - ref))
                             / jnp.max(jnp.abs(ref)))

            for name, (fwd, both) in variants.items():
                if name in skip:
                    continue
                t0 = time.perf_counter()
                out = jax.block_until_ready(
                    on_slabs(fwd, D, Dv)(qs[0], k, v, g))
                grads = jax.block_until_ready(
                    on_slabs(both, D, Dv)(qs[0], k, v, g))
                line[name + "_first_run_s"] = time.perf_counter() - t0
                line[name + "_gap"] = [gap(out, want_out)] + [
                    gap(a, b) for a, b in zip(grads, want)]
                del out, grads
                if not on_chip:      # rehearse the timed program too
                    jax.block_until_ready(every_call(both, count, D, Dv)(
                        qs, k, v, g))
                else:
                    line[name + "_fwd_ms"] = median_ms(
                        every_call(fwd, count, D, Dv), (qs, k, v, g),
                        args.reps) / count
                    line[name + "_fwd_bwd_ms"] = median_ms(
                        every_call(both, count, D, Dv), (qs, k, v, g),
                        args.reps) / count
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
