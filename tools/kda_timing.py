"""Time the KDA scan on the chip: the Pallas kernel pair
(`geomx_tpu/ops/kda_pallas.py`) against the jnp form
(`ops/kda.kda_chunked`, JAX's own backward), at the decoder cell's shape.

For each shape (``B:H:L:d``, default one sequence of the cell's layer,
1 x 32 x 8,192 x 128) and operand dtype it makes ``calls`` sets of q on
the device and times one jitted program that runs all of them against one
k, v, g, beta (and one dO), ending in ``block_until_ready``: a call to
the device costs the host ~0.6 ms whatever it does.  q, k, g and beta are
float32 and v is the operands' dtype, as `KDAMixer` makes them.  The line
gives milliseconds per call, forward alone and forward + backward (the
kernel's forward then writes the chunks' starting states, as its VJP
does), and the largest difference of each variant's output and five
gradients from `kda_chunked` in float32 at ``highest`` precision on the
same inputs (over ``--check-heads`` heads, relative to the oracle's
largest magnitude).  Beside them ``*_pieces_gap``: what a call of that
dtype keeps in float32 whatever its operands are (the score levels under
``sub`` tokens and the triangular inverse; :func:`chunk_pieces` for the
kernel, :func:`jnp_pieces` for the jnp form) against float64 on the
host, which the end-to-end gaps cannot see under the large products' bf16
rounding.  ROADMAP D3: a kernel that does not beat XLA's own
program at real sizes is deleted; this is the measurement that rule asks
for (PERF.md section 5).

    python tools/kda_timing.py [1:32:8192:128] [--sub 16,1]
        [--set MAX_HEADS=2] [--skip jnp]

``--interpret`` rehearses it on the CPU at a small size (no times).
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


def chunk_pieces(q, k, g, beta, chunk, sub, dtype, interpret=False):
    """One pack's chunk as the kernels compute it for a caller of
    ``dtype``: q, k, g [R, dk] float32 (R = pack x chunk rows, the heads
    stacked), beta [1, R] -> (A_qk below the diagonal, A_kk,
    X = (I + Diag(beta) A_kk)^-1), [R, R] float32 each."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from geomx_tpu.ops import kda_pallas as kp
    rows, dk = q.shape

    def body(q_ref, k_ref, g_ref, beta_ref, a_qk_ref, a_kk_ref, x_ref):
        masks = kp._masks(chunk, rows // chunk, dk)
        levels, _, _ = kp._decays(g_ref[...], masks)
        a, _ = kp._scores(q_ref[...], k_ref[...], levels, masks, sub, dtype)
        a_qk_ref[...], a_kk_ref[...] = a[:rows], a[rows:]
        x_ref[...] = kp._unit_lower_inverse(
            kp._to_col(beta_ref[...], masks.eye) * a[rows:], masks, dtype)

    return pl.pallas_call(
        body, out_shape=[jax.ShapeDtypeStruct((rows, rows), jnp.float32)] * 3,
        interpret=interpret)(q, k, g, beta)


def jnp_pieces(q, k, g, beta, chunk, sub, dtype):
    """:func:`chunk_pieces`' three matrices from the jnp form's own
    functions (`kda.chunk_scores`, `kda.unit_lower_inverse`), a head a
    block of the diagonal."""
    import jax
    import jax.numpy as jnp
    from geomx_tpu.ops import kda
    heads = lambda x: x.reshape(-1, chunk, x.shape[-1])
    a_qk, a_kk = kda.chunk_scores(
        heads(q), heads(k), jnp.cumsum(heads(g), axis=-2), sub, dtype)
    x = kda.unit_lower_inverse(heads(beta.T) * a_kk)
    return [jax.scipy.linalg.block_diag(*a) for a in (a_qk, a_kk, x)]


def pieces_gap(pieces, q, k, g, beta, chunk, sub):
    """Largest gaps of a form's ``pieces`` (A_qk, A_kk, X) from float64
    on the host, each over its own largest magnitude: the scores within
    aligned blocks of ``sub`` tokens (sum_d x_id k_jd exp(G_id - G_jd),
    j < i) and the inverse of the form's own ``I + Diag(beta) A_kk``."""
    import numpy as np
    a_qk, a_kk, x = (np.asarray(a, np.float64) for a in pieces)
    q, k, g, beta = (np.asarray(a, np.float64) for a in (q, k, g, beta))
    rows = q.shape[0]
    index = np.arange(rows)
    near = (index[:, None] // sub == index[None, :] // sub) & (
        index[None, :] < index[:, None])
    cum = np.concatenate([np.cumsum(part, 0) for part in
                          np.split(g, rows // chunk)])
    decay = np.exp(np.minimum(cum[:, None, :] - cum[None, :, :], 0.0))
    want = lambda x_: np.where(near, np.einsum("id,jd,ijd->ij", x_, k, decay),
                               0.0)
    rel = lambda got, ref: float(np.max(np.abs(got - ref))
                                 / np.max(np.abs(ref)))
    inverse = np.linalg.inv(np.eye(rows) + beta.T * a_kk)
    return {"scores_qk": rel(np.where(near, a_qk, 0.0), want(q)),
            "scores_kk": rel(np.where(near, a_kk, 0.0), want(k)),
            "inverse": rel(x, inverse)}


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
    parser.add_argument("shapes", nargs="*", default=["1:32:8192:128"])
    parser.add_argument("--dtypes", default="bfloat16,float32")
    parser.add_argument("--chunk", type=int, default=64)
    parser.add_argument("--sub", default="16",
                        help="comma-separated sub-block sizes to time")
    parser.add_argument("--calls", type=int, default=2)
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--skip", default="",
                        help="comma-separated variants to leave out "
                             "(kernel, jnp)")
    parser.add_argument("--check-heads", type=int, default=2)
    parser.add_argument("--set", action="append", default=[],
                        metavar="NAME=INT", help="a constant of "
                        "kda_pallas to try another plan with (STEP_ROWS, "
                        "MAX_HEADS, VMEM_BUDGET)")
    parser.add_argument("--interpret", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from geomx_tpu.ops import kda_pallas
    from geomx_tpu.ops.kda import kda_chunked
    for item in args.set:
        name, value = item.split("=")
        assert hasattr(kda_pallas, name), name
        setattr(kda_pallas, name, int(value))

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print("not a TPU: a time from here is not a device time",
              file=sys.stderr)
        return 1
    skip = set(filter(None, args.skip.split(",")))

    def kernel_pair(sub, dt):
        kw = dict(chunk=args.chunk, sub=sub, dtype=dt,
                  interpret=args.interpret)

        def both(q, k, v, g, beta, do):
            _, states = kda_pallas.kda_scan_fwd(q, k, v, g, beta,
                                                save_states=True, **kw)
            return kda_pallas.kda_scan_bwd(q, k, v, g, beta, states, do,
                                           **kw)
        return (lambda q, k, v, g, beta, do: kda_pallas.kda_scan_fwd(
            q, k, v, g, beta, **kw)), both

    def jnp_pair(sub, dt):
        run = functools.partial(kda_chunked, chunk=args.chunk, sub=sub,
                                dtype=dt)
        return (lambda q, k, v, g, beta, do: run(q, k, v, g, beta),
                lambda q, k, v, g, beta, do: jax.vjp(
                    run, q, k, v, g, beta)[1](do))

    def every_call(fn, count):
        """`fn` on each of `count` q's in one program; the carry starts
        from zeros of the result's shape (a first call outside the loop
        would be dead code)."""
        def run(qs, *rest):
            one = lambda c: fn(qs[c], *rest)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 jax.eval_shape(one, 0))
            return jax.lax.fori_loop(0, count, lambda c, _: one(c), zeros)
        return jax.jit(run)

    for shape in args.shapes:
        b, h, length, d = (int(x) for x in shape.split(":"))
        for dtype in args.dtypes.split(","):
            dt = jnp.dtype(dtype)
            keys = jax.random.split(jax.random.PRNGKey(length + d), 6)
            unit = lambda x: x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
            wide = lambda key, *lead: jax.random.normal(
                key, (*lead, b, h, length, d), jnp.float32)
            qs = unit(wide(keys[0], args.calls)) * d ** -0.5
            k = unit(wide(keys[1]))
            v = jax.nn.silu(wide(keys[2])).astype(dt)
            # a trained layer's decay, exp(-0.07) a token at the centre
            g = -0.14 * jax.random.uniform(keys[3], (b, h, length, d))
            beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, h, length)))
            do = wide(keys[5])
            rest = (k, v, g, beta, do)

            ch = min(args.check_heads, h)
            few = tuple(x[:, :ch] for x in (qs[0],) + rest)
            with jax.default_matmul_precision("highest"):
                oracle = jnp_pair(16, jnp.float32)
                f32 = lambda xs: tuple(x.astype(jnp.float32) for x in xs)
                want_out = jax.jit(oracle[0])(*f32(few))
                want = jax.jit(oracle[1])(*f32(few))

            def gap(got, ref):
                got = got[:, :ch].astype(jnp.float32)
                return float(jnp.max(jnp.abs(got - ref))
                             / jnp.max(jnp.abs(ref)))

            for sub in (int(s) for s in args.sub.split(",")):
                variants = {"kernel": kernel_pair(sub, dt),
                            "jnp": jnp_pair(sub, dt)}
                plan = kda_pallas.kda_plan(length, h, d, d, args.chunk, dt)
                line = {"dims": [b, h, length, d], "dtype": dtype,
                        "chunk": args.chunk, "sub": sub,
                        "calls": args.calls, "reps": args.reps,
                        "plan": plan._asdict(), "set": args.set,
                        "device": jax.devices()[0].device_kind}
                pack = kda_pallas._pack(plan.heads, args.chunk)
                stacked = lambda x: x[0, :pack, :args.chunk].reshape(
                    pack * args.chunk, -1)
                one = (stacked(qs[0]), stacked(k), stacked(g),
                       stacked(beta[..., None]).T, args.chunk, sub)
                if "kernel" not in skip:
                    line["kernel_pieces_gap"] = pieces_gap(
                        chunk_pieces(*one, dt, args.interpret), *one)
                if "jnp" not in skip:
                    line["jnp_pieces_gap"] = pieces_gap(
                        jax.jit(jnp_pieces, static_argnums=(4, 5, 6))(
                            *one, dt), *one)
                for name, (fwd, both) in variants.items():
                    if name in skip:
                        continue
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(jax.jit(fwd)(qs[0], *rest))
                    grads = jax.block_until_ready(
                        jax.jit(both)(qs[0], *rest))
                    line[name + "_first_run_s"] = time.perf_counter() - t0
                    line[name + "_gap"] = [gap(out, want_out)] + [
                        gap(a, r) for a, r in zip(grads, want)]
                    del out, grads
                    if not on_chip:      # rehearse the timed program too
                        jax.block_until_ready(
                            every_call(both, args.calls)(qs, *rest))
                        continue
                    line[name + "_fwd_ms"] = median_ms(
                        every_call(fwd, args.calls), (qs, *rest),
                        args.reps) / args.calls
                    line[name + "_fwd_bwd_ms"] = median_ms(
                        every_call(both, args.calls), (qs, *rest),
                        args.reps) / args.calls
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
