"""FLOP and byte counts of the benchmark's own functions against
hand-worked shapes, and the plain forms (bucket layout, boundary, Adam)
against small cases worked by hand."""
import json
import os

import numpy as np
import pytest

from bench_paths import ROOT

from benchmark.cells import Registry


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def family(name):
    return Registry(ROOT).cell(name)["family"]


def test_bertlarge_train_flops_by_hand():
    fam = family("bertlarge-fsa-1c")
    c = config("seqcls-bertlarge")
    d, length, layers = 1024, 512, 24
    per_token_layer = (2 * d * 3 * d) + (2 * d * d) + 2 * (2 * d * 4 * d) \
        + 2 * (2 * length * d)
    forward = length * layers * per_token_layer + 2 * d * 2
    assert fam.forward_flops_per_sample(c) == pytest.approx(forward)
    assert fam.train_flops_per_sample(c) == pytest.approx(3 * forward)
    assert fam.train_flops_per_sample(c) == pytest.approx(1.0060e12, rel=1e-3)


def test_resnet18_cifar_train_flops_by_hand():
    fam = family("resnet18-bsc-1c")
    c = config("resnet18-cifar")
    shapes = fam.conv_shapes(c)
    assert len(shapes) == 20            # stem, 16 3x3, 3 projections
    assert shapes[0] == (3, 3, 64, 32) and shapes[-1] == (3, 512, 512, 4)
    assert shapes[5] == (3, 64, 128, 16) and shapes[7] == (1, 64, 128, 16)
    stem = 2 * 9 * 3 * 64 * 32 * 32
    stage1 = 4 * 2 * 9 * 64 * 64 * 32 * 32
    later = sum(2 * 9 * ci * co * s * s + 3 * 2 * 9 * co * co * s * s
                + 2 * ci * co * s * s
                for ci, co, s in ((64, 128, 16), (128, 256, 8), (256, 512, 4)))
    forward = stem + stage1 + later + 2 * 512 * 10
    assert fam.forward_flops_per_sample(c) == pytest.approx(forward)
    assert fam.train_flops_per_sample(c) == pytest.approx(3.334e9, rel=1e-3)


def test_flash_attention_flops_and_select_pack_bytes_by_hand():
    readers = {m.NAME: m for m in Registry(ROOT).layer_metrics()}
    fa = readers["flash_attn_roofline_pct"]
    shape = {"batch": 2, "heads": 3, "length": 8, "head_dim": 4, "layers": 5}
    one_product = 2 * 2 * 3 * 8 * 8 * 4       # one L x L x e product, B H of them
    assert fa.flops_per_step(shape) == 5 * 6 * one_product
    sp = readers["select_pack_roofline_pct"]
    # leaves 1000 + 1500 -> one bucket padded to 2560; 3000 alone -> 3072;
    # 100 alone -> 128, under the sparse floor and not counted
    sizes = [1000, 1500, 3000, 100]
    expect = (20 * 2560 + 8 * 26) + (20 * 3072 + 8 * 31)
    assert sp.bytes_per_step(sizes, 4 * 2600, 0.01) == expect


def test_bucket_layout_k_and_boundary_by_hand():
    from benchmark.references import bisparse
    assert bisparse.bucket_layout([1000, 1500, 3000, 100], 4 * 2600) == [
        (0, 2, 2560), (2, 3, 3072), (3, 4, 128)]
    assert bisparse.bucket_layout([5], 4096) == [(0, 1, 128)]
    assert bisparse.k_for(2560, 0.01) == 26 and bisparse.k_for(10, 0.01) == 1
    # n <= 8192: every element is probed; the boundary sits at round(n - k)
    assert bisparse.boundary_position(2560, 26) == 2534
    # n > 8192: position round(8192 * (1 - k/n))
    assert bisparse.boundary_position(1 << 20, 10486) == round(8192 * (1 - 10486 / (1 << 20)))
    pos = bisparse.probe_positions(100000)
    assert len(pos) == 8192 and pos[1] == 2654435761 % 100000
    assert len(set(pos.tolist())) > 8000


def test_plain_select_against_a_loop():
    from benchmark.references import bisparse
    rng = np.random.default_rng(3)
    absv = np.abs(rng.normal(size=4000)).astype(np.float32)
    absv[[5, 900, 3100]] = absv[17]                 # ties at one value
    k = 40
    for thr in (np.sort(absv)[-30], absv[17], np.float32(0.0)):
        keep = np.asarray(bisparse.select(absv, thr, k))
        above = [i for i in range(4000) if absv[i] > thr]
        ties = [i for i in range(4000) if absv[i] == thr]
        want = (above + ties)[:k] if len(above) < k else above[:k]
        assert sorted(np.nonzero(keep)[0].tolist()) == sorted(want)


def test_plain_push_keeps_what_it_does_not_send():
    from benchmark.references import bisparse
    rng = np.random.default_rng(4)
    g = rng.normal(size=2560).astype(np.float32)
    u = rng.normal(size=2560).astype(np.float32) * 0.1
    v = rng.normal(size=2560).astype(np.float32) * 0.1
    sent, new_u, new_v = map(np.asarray, bisparse.push_bucket(g, u, v, ratio=0.01))
    acc_u = np.float32(0.9) * u + g
    acc_v = v + acc_u
    k = bisparse.k_for(2560, 0.01)
    assert 0 < np.count_nonzero(sent) <= k
    # to one rounding: XLA fuses the multiply-add, numpy does not
    np.testing.assert_allclose(sent + new_v, acc_v, rtol=2e-6, atol=1e-7)
    assert np.all(new_u[sent != 0] == 0)
    np.testing.assert_allclose(new_u[sent == 0], acc_u[sent == 0],
                               rtol=2e-6, atol=1e-7)
    assert np.abs(sent[sent != 0]).min() >= np.abs(new_v).max()
    facts = bisparse.payload_facts(sent, new_v, ratio=0.01)
    assert {k_: int(v_) for k_, v_ in facts.items()} == {
        "overlap": 0, "below": 0, "held": 0,
        "count": np.count_nonzero(sent), "k": k,
        "plain_count": np.count_nonzero(sent)}


def test_payload_facts_catch_a_broken_push():
    from benchmark.references import bisparse
    rng = np.random.default_rng(5)
    g = rng.normal(size=2560).astype(np.float32)
    zeros = np.zeros_like(g)
    sent, _, resid = map(np.asarray, bisparse.push_bucket(g, zeros, zeros, ratio=0.01))
    small = int(np.argmin(np.where(resid != 0, np.abs(resid), np.inf)))
    large = int(np.argmax(np.abs(sent)))

    def facts_of(a, b):
        return {k: int(v) for k, v in
                bisparse.payload_facts(a, b, ratio=0.01).items()}
    # a push that sends the smallest element in place of a large one
    bad_sent, bad_resid = sent.copy(), resid.copy()
    bad_sent[small], bad_resid[small] = resid[small], 0.0
    bad_resid[large], bad_sent[large] = sent[large], 0.0
    assert facts_of(bad_sent, bad_resid)["below"] == 1
    # a push that forgets a large element although a slot is free
    bad_sent, bad_resid = sent.copy(), resid.copy()
    bad_resid[large], bad_sent[large] = sent[large], 0.0
    facts = facts_of(bad_sent, bad_resid)
    assert facts["held"] == 1 and facts["count"] == facts["plain_count"] - 1
    # a push that does not zero what it sent
    facts = bisparse.payload_facts(sent, resid + sent, ratio=0.01)
    assert int(facts["overlap"]) == np.count_nonzero(sent)


def test_plain_adam_by_hand():
    import jax.numpy as jnp
    from benchmark.references.trainer import _adam
    p, g = jnp.asarray([1.0, -2.0]), jnp.asarray([0.5, -0.25])
    p1, m1, v1 = _adam({"w": p}, {"w": jnp.zeros(2)}, {"w": jnp.zeros(2)},
                       {"w": g}, 1.0, 0.1, 0.9, 0.999, 1e-8)
    np.testing.assert_allclose(m1["w"], 0.1 * np.asarray(g), rtol=1e-6)
    np.testing.assert_allclose(v1["w"], 0.001 * np.asarray(g) ** 2, rtol=1e-4)
    # first step: m_hat / sqrt(v_hat) = sign(g)
    np.testing.assert_allclose(p1["w"], [0.9, -1.9], rtol=1e-5)


def test_wire_bytes_per_sample_of_the_three_cells():
    """The static accounting the end-to-end metric reads, at the real
    shapes (abstract: nothing is allocated)."""
    import jax
    from benchmark import run
    reg = Registry(ROOT)
    for name, want in (("bertlarge-fsa-1c", None), ("resnet18-bsc-1c", None)):
        cell = reg.cell(name)
        model = cell["family"].build_model(cell["config"])
        x, _ = cell["family"].make_data(cell["config"],
                                        np.random.default_rng(0), 2)
        from geomx_tpu.train.step import _norm_input
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), _norm_input(x),
                               train=False))["params"]
        params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        wire = run.trainer_wire_bytes(cell, shapes)
        if name == "bertlarge-fsa-1c":
            # embeddings 31,254,528 + 524,288; 24 layers of 12,592,128
            # (LayerNorms 4,096, qkv 3,145,728, proj 1,048,576, MLP
            # 8,393,728); final LayerNorm 2,048; head 2,050
            assert params == 333_993_986
            assert wire >= 4 * params / 16          # dense fp32, padded buckets
            assert wire == pytest.approx(83_498_528, rel=1e-3)
        else:
            assert 11.1e6 < params < 11.3e6
            assert wire == pytest.approx(218.25, rel=0.02)
