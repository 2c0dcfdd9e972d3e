"""The experts' combine (``ops/moe.py``): the ``moe_combine`` kernel, run
interpreted, adds what the scatter adds — to the last bit, because a
token's addends arrive in the same order.  On a TPU the kernel is chosen at
lowering (``tests/test_tpu_compile.py`` holds it to the compiler); here the
layer's own call lowers to the scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.ops import moe

T = 96


def _rising(rng, n, among=T):
    return np.sort(rng.choice(among, n, replace=False))


def _tokens(case, block, rng):
    """(a block's tokens, how many are held): runs of rising tokens as the
    sort by (held expert, token) leaves them."""
    if case == "one_run":
        return _rising(rng, block), block
    if case == "two_runs":                       # tokens 90 and 95 in both
        sizes = [block // 2 - 3, block - block // 2 + 3]
        return np.concatenate([np.union1d(_rising(rng, s - 2, 90), [90, 95])
                               for s in sizes]), block
    if case == "three_runs":                     # token 95 in each
        sizes = [block // 4, block // 2, block - block // 4 - block // 2]
        return np.concatenate([np.union1d(_rising(rng, s - 1, 95), [95])
                               for s in sizes]), block
    if case == "last_partial":                   # rows behind go nowhere
        held = block // 3
        a = _rising(rng, held // 2)
        return np.concatenate([a, _rising(rng, held - len(a)),
                               rng.integers(0, T, block - held)]), held
    if case == "run_ends_with_the_block":        # the next row's is smaller
        return np.concatenate([_rising(rng, block - 1), [0]]), block - 1
    assert case == "none_held"
    return rng.integers(0, T, block), 0


CASES = ["one_run", "two_runs", "three_runs", "last_partial",
         "run_ends_with_the_block", "none_held"]


def _block(case, h, seed=0, block=64):
    """The sums, a block's weighted rows laid out as the sums are, their
    tokens, and how many are held."""
    rng = np.random.default_rng(seed)
    token, held = _tokens(case, block, rng)
    assert len(token) == block
    if case in ("two_runs", "three_runs"):
        assert len(set(token[:held])) < held     # a token stands twice
    out = jnp.asarray(rng.normal(size=(T, h // 128, 128)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(block, h // 128, 128)), jnp.float32)
    return out, y, jnp.asarray(token, jnp.int32), jnp.int32(held)


@pytest.mark.parametrize("h", [256, 384])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_adds_what_the_scatter_adds(case, h):
    args = _block(case, h, seed=len(case) * h)
    want, took = moe._combine(*args)
    assert int(took) == 0                        # the CPU: the scatter
    got = moe._combine_kernel(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "none_held":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(args[0]))
    else:
        assert not np.array_equal(np.asarray(got), np.asarray(args[0]))


@pytest.mark.parametrize("case", ["two_runs", "three_runs", "last_partial"])
def test_no_two_copies_of_one_sum_are_in_flight(case):
    """Under the TPU interpreter, which runs a copy only when it is awaited
    and notes every read and write of a buffer: no race between a run's
    copies back and the next run's copies in, and the scatter's sums."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    args = _block(case, 256, seed=len(case))
    want, _ = moe._combine(*args)
    got = moe._combine_kernel.__wrapped__(
        *args, interpret=pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait"))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not interpret_pallas_call.races.races_found


def test_a_block_in_several_kernel_steps(monkeypatch):
    """Where a block's tiles pass the VMEM budget the kernel takes it in
    steps; a run that crosses a step's end goes on in the next."""
    args = _block("two_runs", 256, seed=3)
    want, _ = moe._combine(*args)
    monkeypatch.setattr(moe, "_VMEM_BUDGET", 3 * 16 * 8 * 128 * 4)
    jax.clear_caches()                           # the budget is read once
    got = moe._combine_kernel(*args, interpret=True)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("h", [256, 384, 32])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_blocked_sum_through_the_kernel(dtype, h, monkeypatch):
    """The whole layer with the kernel in the scatter's place: five held
    experts' runs over blocks of 64 rows (most blocks cross an expert's
    end), a last partial block, padding tokens that reach no expert; ``h``
    of 32 has no 128 lanes and keeps the scatter."""
    rng = np.random.default_rng(h)
    t, i, k, held = 256, 16, 4, (0, 5)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(t, h)), dt)
    chosen = jnp.asarray(np.stack([rng.permutation(8)[:k]
                                   for _ in range(t)]), jnp.int32)
    # weights of eight bits: a product with an activation of as many is
    # exact, so a backend that contracts multiply and add changes nothing
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(t, k)),
                          jnp.bfloat16).astype(jnp.float32)
    live = jnp.asarray(np.arange(t) < 240)
    e_gu = jnp.asarray(rng.normal(size=(5, h, 2 * i)) / 6, dt)
    e_down = jnp.asarray(rng.normal(size=(5, i, h)) / 4, dt)

    def blocked(x, chosen, weights, live, e_gu, e_down):
        _, order, loads, w = moe._sorted_assignments(chosen, weights, live,
                                                     held)
        return moe._blocked_sum(x, order, loads, w, e_gu, e_down, 64)

    args = (x, chosen, weights, live, e_gu, e_down)
    want, rows, took = jax.jit(blocked)(*args)
    assert int(took) == 0 and int(rows) % 64 == 0
    scatter = moe._combine

    def combine(out, *rest):
        if out.shape[-1] != 128:
            return scatter(out, *rest)
        return moe._combine_kernel(out, *rest, interpret=True), jnp.int32(1)

    monkeypatch.setattr(moe, "_combine", combine)
    got, rows_k, took = jax.jit(lambda *a: blocked(*a))(*args)
    assert int(took) == (h % 128 == 0) and int(rows_k) == int(rows)
    assert np.asarray(got[240:] == 0).all()      # padding tokens: no sum
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
