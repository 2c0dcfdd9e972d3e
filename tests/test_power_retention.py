"""Gated, normalised power retention of degree 2 (``ops/power_retention.py``)
at small widths on the CPU (``d`` 16, 4 query heads on 2 key heads, chunks
of 8, documents that are no multiple of the chunk): the ``jnp`` chunked form
and the Pallas kernel, interpreted, against the pairs form; the state form
against the pairs form; the selection rule and the operation counts."""

import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.ops import power_retention as pr

# boundaries inside chunks of 8 and a one-token document; one document over
# many chunks; a chunk that starts a document on its first token
TEMPLATES = {
    "mixed": [13, 1, 30, 7, 21],
    "one_long": [75],
    "on_the_edge": [8, 16, 3, 5],
}
# the gate's range: a memory of a token or two, and one of hundreds
GATES = {"near_0": (1e-6, 0.05), "near_1": (0.98, 0.9999),
         "spread": (0.5, 0.999)}


def pairs(q, k, v, log_g, lengths, eps=pr.EPS):
    """The pairs form a document at a time, float64 on the host."""
    t, h, d = q.shape
    g = h // k.shape[1]
    out = np.zeros((t, h, d))
    start = 0
    for n in lengths:
        at = slice(start, start + n)
        start += n
        c = np.cumsum(log_g[at], 0)
        for head in range(h):
            kh = head // g
            decay = np.exp(np.minimum(c[:, None, kh] - c[None, :, kh], 0))
            w = np.tril((q[at, head] @ k[at, kh].T) ** 2 * decay)
            out[at, head] = w @ v[at, kh] / (w.sum(1, keepdims=True) + eps)
    return out


def state_form(q, k, v, log_g, lengths, eps=pr.EPS):
    """The recurrence token by token with ``phi`` formed whole, the state
    zeroed at a document's first token, float64 on the host."""
    t, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    a, b = np.triu_indices(d)
    scale = np.where(a == b, 1.0, np.sqrt(2.0))
    phi = lambda x: x[..., a] * x[..., b] * scale              # noqa: E731
    out = np.zeros((t, h, d))
    first = np.zeros(t, bool)
    first[np.cumsum([0] + lengths[:-1])] = True
    s = np.zeros((hkv, len(a), d))
    z = np.zeros((hkv, len(a)))
    for i in range(t):
        gamma = np.where(first[i], 0.0, np.exp(log_g[i]))
        fk = phi(k[i])
        s = gamma[:, None, None] * s + fk[:, :, None] * v[i][:, None, :]
        z = gamma[:, None] * z + fk
        fq = phi(q[i]).reshape(hkv, g, -1)
        out[i] = (np.einsum("kgD,kDe->kge", fq, s)
                  / (np.einsum("kgD,kD->kg", fq, z)[..., None] + eps)
                  ).reshape(h, d)
    return out


def inputs(lengths, gate, seed, h=4, hkv=2, d=16):
    """RMS-normed ``q``, ``k`` (as the model's QK-norm leaves them), ``v``,
    a log-gate drawn from ``gate``'s range, the document ids; float64."""
    t = sum(lengths)
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.sqrt((x * x).mean(-1, keepdims=True))  # noqa: E731
    q = unit(rng.normal(size=(t, h, d)))
    k = unit(rng.normal(size=(t, hkv, d)))
    v = rng.normal(size=(t, hkv, d))
    log_g = np.log(rng.uniform(*GATES[gate], size=(t, hkv)))
    seg = np.repeat(np.arange(len(lengths)), lengths)
    return q, k, v, log_g, seg


def f32(x):
    return jnp.asarray(x, jnp.float32)


@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_the_state_form_is_the_pairs_form(template, gate):
    lengths = TEMPLATES[template]
    q, k, v, log_g, _ = inputs(lengths, gate, seed=len(lengths))
    np.testing.assert_allclose(state_form(q, k, v, log_g, lengths),
                               pairs(q, k, v, log_g, lengths), atol=1e-9)


def test_phi_is_the_symmetric_square():
    rng = np.random.default_rng(1)
    x, y = rng.normal(size=(2, 7, 16))
    got = np.asarray(pr.phi(f32(x)) * pr.phi(f32(y))).sum(-1)
    np.testing.assert_allclose(got, (x * y).sum(-1) ** 2, rtol=1e-5)
    assert pr.phi(f32(x)).shape == (7, 136)


@pytest.mark.parametrize("gate", sorted(GATES))
@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_the_chunked_form_is_the_pairs_form(template, gate):
    lengths = TEMPLATES[template]
    q, k, v, log_g, seg = inputs(lengths, gate, seed=7 + len(lengths))
    got = pr._retention_jnp(f32(q), f32(k), f32(v), f32(log_g),
                            jnp.asarray(seg, jnp.int32), 8)
    np.testing.assert_allclose(np.asarray(got),
                               pairs(q, k, v, log_g, lengths), atol=2e-5)


@pytest.mark.parametrize("heads", [1, 2])
@pytest.mark.parametrize("gate", ["near_0", "near_1"])
@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_the_kernel_is_the_pairs_form(template, gate, heads):
    """The Pallas kernel, interpreted, one and two key heads a step: the
    state in its lane blocks (pairs of upper-triangle rows, the half row,
    the diagonal) and the sum of keys as ``Z``."""
    lengths = TEMPLATES[template]
    q, k, v, log_g, seg = inputs(lengths, gate, seed=11 + heads)
    got = pr._retention_kernel(f32(q), f32(k), f32(v), f32(log_g),
                               jnp.asarray(seg, jnp.int32), chunk=8,
                               heads=heads, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               pairs(q, k, v, log_g, lengths), atol=2e-5)


def test_the_kernel_and_the_chunked_form_hold_in_bfloat16():
    """``q, k, v`` in bfloat16 as the cell has them, against the pairs form
    of the same rounded inputs: a chunk's own pairs are exact products, the
    state's features ``q_a q_b`` are rounded to bfloat16 operands, and
    ``(q . k)^2`` out of ``d (d + 1) / 2`` rounded terms of both signs
    keeps about ``2^-9 sum_a q_a^2 k_a^2`` of error — at ``d = 16`` a
    tenth of a percent of the outputs' mean square, and more where a
    query meets little but the state."""
    lengths = TEMPLATES["mixed"]
    q, k, v, log_g, seg = inputs(lengths, "spread", seed=3)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)               # noqa: E731
    args = (bf(q), bf(k), bf(v), f32(log_g), jnp.asarray(seg, jnp.int32))
    kernel = pr._retention_kernel(*args, chunk=8, heads=2, interpret=True)
    plain = pr._retention_jnp(*args, 8)
    assert kernel.dtype == plain.dtype == jnp.bfloat16
    rounded = lambda x: np.asarray(bf(x), np.float64)          # noqa: E731
    want = pairs(rounded(q), rounded(k), rounded(v), log_g, lengths)
    for got in (kernel, plain):
        gap = np.abs(np.asarray(got, np.float64) - want)
        assert np.sqrt((gap ** 2).mean()) < 0.008 and gap.max() < 0.3


@pytest.mark.parametrize("h,hkv,d,dtype,fits", [
    (40, 8, 128, jnp.bfloat16, True), (40, 8, 128, jnp.float32, True),
    (4, 2, 16, jnp.float32, False), (4, 2, 64, jnp.bfloat16, False),
    (6, 4, 128, jnp.bfloat16, False)])
def test_the_kernel_rule_is_a_function_of_the_shapes(h, hkv, d, dtype, fits):
    q = jnp.zeros((8, h, d), dtype)
    k = jnp.zeros((8, hkv, d), dtype)
    assert pr._kernel_fits(q, k) == fits


def test_chunk_and_key_head_block_are_sized_from_vmem(monkeypatch):
    """At the published widths a step takes one key head and chunks of 256
    (its state alone is 4.3 MB); a tighter budget takes a smaller chunk —
    the kernel only where a chunk fills whole 128-lane rows — and none
    where not even the state fits."""
    q = jnp.zeros((8, 40, 128), jnp.bfloat16)
    k = jnp.zeros((8, 8, 128), jnp.bfloat16)
    assert pr._sizes(8, 5, 128, 2) == (256, 1)
    assert pr._step_bytes(256, 5, 128, 2) < pr._VMEM_BUDGET
    monkeypatch.setattr(pr, "_VMEM_BUDGET", 9 << 20)
    assert pr._sizes(8, 5, 128, 2) == (128, 1) and pr._kernel_fits(q, k)
    monkeypatch.setattr(pr, "_VMEM_BUDGET", 7 << 20)
    assert pr._sizes(8, 5, 128, 2) == (64, 1) and not pr._kernel_fits(q, k)
    monkeypatch.setattr(pr, "_VMEM_BUDGET", 4 << 20)
    assert pr._sizes(8, 5, 128, 2) is None and not pr._kernel_fits(q, k)


def test_eligible_shapes_take_the_jnp_form_on_the_cpu():
    lengths = [20, 9]
    q, k, v, log_g, seg = inputs(lengths, "spread", seed=5, h=2, hkv=1,
                                 d=128)
    o, fused, done = pr.power_retention_counted(
        f32(q), f32(k), f32(v), f32(log_g), jnp.asarray(seg, jnp.int32))
    assert int(fused) == 0
    # one chunk of 64: its pairs, the state's 8 256 features each way
    assert float(done) == pr.schedule_flops(29, 2, 1, 128, 64, 8256,
                                            2 * 8256)
    np.testing.assert_allclose(np.asarray(o), pairs(q, k, v, log_g, lengths),
                               atol=2e-4)


def test_the_needed_work_is_each_document_s_cheaper_form():
    """At the cell's lengths: the pairs for 2 185, 4 369 and 8 738 tokens
    (1.030 TFLOP a layer), the state for 17 476 (1.787); the crossover lies
    near 9 900 tokens."""
    lengths = jnp.asarray([2185, 4369, 8738, 17476])
    got = float(pr.needed_flops(lengths, 40, 8, 128))
    assert 2.815e12 < got < 2.819e12
    pairs_only = float(pr.needed_flops(lengths[:3], 40, 8, 128))
    assert 1.029e12 < pairs_only < 1.031e12
    state = float(pr.needed_flops(lengths[3:], 40, 8, 128))
    assert state == pytest.approx(8 * 17476 * 6 * (2 * 8256 * 128
                                                    + 2 * 8256), rel=1e-6)
    below, above = (float(pr.needed_flops(jnp.asarray([n]), 40, 8, 128))
                    for n in (9800, 10000))
    assert below < 8 * 9800 * 6 * (2 * 8256 * 128 + 2 * 8256)
    assert above == pytest.approx(8 * 10000 * 6 * (2 * 8256 * 128
                                                   + 2 * 8256), rel=1e-6)
