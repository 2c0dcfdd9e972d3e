"""``ops.selective_scan``: the chunked ``jnp`` formulation and the Pallas
kernel (interpreted on the CPU) against the recurrence token by token in
float64, with documents that straddle chunks and a one-token document."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.ops import selective_scan as ss

# documents inside and across the kernel's 256-token chunks, a one-token
# document, and a stream whose length is no whole chunk
LENGTHS = {"straddling": [255, 1, 300, 127, 341],
           "ragged_t": [70, 33, 129, 1, 90, 60, 200]}


def inputs(lengths, ch, n, seed):
    """``x``, ``dt`` (a step log-uniform in [1e-3, 1e-1], as Mamba
    initialises it), ``a`` (``-(n + 1)``, and noise), ``b``, ``c``, ``d``
    and the document ids, float64."""
    t = sum(lengths)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(t, ch))
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(t, ch)))
    a = -np.arange(1, n + 1)[None, :] * np.exp(0.2 * rng.normal(size=(ch, n)))
    b, c = rng.normal(size=(t, n)), rng.normal(size=(t, n))
    d = rng.normal(size=ch)
    seg = np.repeat(np.arange(len(lengths)), lengths)
    return x, dt, a, b, c, d, seg


def recurrence(x, dt, a, b, c, d, seg):
    """The scan as written, token by token, the state zero at every
    document's first token."""
    t, ch = x.shape
    y = np.zeros((t, ch))
    s = np.zeros(a.shape)
    for i in range(t):
        if i == 0 or seg[i] != seg[i - 1]:
            s[:] = 0.0
        s = np.exp(dt[i][:, None] * a) * s + (dt[i] * x[i])[:, None] * b[i]
        y[i] = s @ c[i] + d * x[i]
    return y


def as_args(x, dt, a, b, c, d, seg):
    f32 = lambda v: jnp.asarray(v, jnp.float32)               # noqa: E731
    return (f32(x), f32(dt), f32(a), f32(b), f32(c), f32(d),
            jnp.asarray(seg, jnp.int32))


@pytest.mark.parametrize("chunk", [16, 64, 256])
@pytest.mark.parametrize("template", sorted(LENGTHS))
def test_chunked_jnp_scan_is_the_recurrence(template, chunk):
    args = inputs(LENGTHS[template], 32, 4, seed=chunk)
    got = ss._scan_jnp(*as_args(*args), chunk)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), recurrence(*args),
                               atol=2e-5)


@pytest.mark.parametrize("n", [4, 16])
@pytest.mark.parametrize("template", sorted(LENGTHS))
def test_kernel_is_the_recurrence(template, n):
    """The kernel, interpreted, at one channel block of 1024 and two, the
    state in a chunk's registers and handed over in VMEM."""
    ch = 1024 if n == 16 else 2048
    args = inputs(LENGTHS[template], ch, n, seed=n)
    got = ss._scan_kernel(*as_args(*args), interpret=True)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), recurrence(*args),
                               atol=2e-5)


def test_kernel_and_jnp_agree_on_bfloat16_inputs():
    """``x`` in the activations' type: both cast it to float32 before the
    first product, so they differ by the order of their sums only."""
    args = as_args(*inputs(LENGTHS["straddling"], 1024, 16, seed=3))
    args = (args[0].astype(jnp.bfloat16),) + args[1:]
    got = np.asarray(ss._scan_kernel(*args, interpret=True))
    want = np.asarray(ss._scan_jnp(*args, 64))
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_every_document_starts_from_a_zero_state():
    """A document's output is the same behind another as alone."""
    x, dt, a, b, c, d, seg = inputs([300, 129], 1024, 16, seed=5)
    both = np.asarray(ss._scan_kernel(*as_args(x, dt, a, b, c, d, seg),
                                      interpret=True))
    tail = slice(300, 429)
    alone = np.asarray(ss._scan_kernel(*as_args(
        x[tail], dt[tail], a, b[tail], c[tail], d, seg[tail] - 1),
        interpret=True))
    np.testing.assert_allclose(both[tail], alone, atol=1e-6)
    # and the state does carry inside a document: the second half of the
    # first document differs from the same tokens started afresh
    mid = slice(150, 300)
    fresh = np.asarray(ss._scan_jnp(*as_args(
        x[mid], dt[mid], a, b[mid], c[mid], d, seg[mid]), 64))
    assert np.abs(both[mid] - fresh).max() > 1e-3


@pytest.mark.parametrize("t,ch,fits", [
    (256, 1024, True), (32768, 5120, True), (255, 1024, False),
    (4096, 512, False), (4096, 1000, False), (600, 3072, True)])
def test_kernel_rule_is_a_function_of_the_shapes(t, ch, fits):
    assert ss._kernel_fits(jnp.zeros((t, ch), jnp.bfloat16)) is fits


def test_eligible_shapes_take_the_jnp_path_on_the_cpu():
    """The kernel is chosen at lowering for a TPU: the CPU's program runs
    the ``jnp`` formulation and says so."""
    args = as_args(*inputs([300, 129], 1024, 16, seed=7))
    assert ss._kernel_fits(args[0])
    y, fused = jax.jit(ss.selective_scan_counted)(*args)
    assert int(fused) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        ss._scan_jnp(*args, ss._JNP_CHUNK)), atol=2e-6)
