"""Mamba's selective scan over packed documents: a per-channel, per-state
linear recurrence whose decay and input the token chooses.

A layer of ``C`` channels keeps a float32 state ``s [C, N]``, zero before a
document's first token:

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]

with ``A < 0``, so every decay lies in ``(0, 1]`` and nothing overflows.
No product of two matrices is in it: per token and channel it is ``N``
multiply-adds and ``N`` exponentials on the vector unit, and a whole
``[T, C, N]`` array of decays would move tens of GB a layer at the shapes
the benchmark runs (``[32768, 5120, 16]`` float32 is 10.7 GB).

**The ``jnp`` formulation** goes chunk by chunk: inside a chunk of
``chunk`` tokens a ``lax.associative_scan`` of the pairs ``(a, b)`` (decay,
input) over ``[chunk, C, N]``, ``(a1, b1) o (a2, b2) = (a1 a2, a2 b1 +
b2)``, then the state handed to the next chunk by a ``lax.scan``; a
document's first token has ``a = 0``, which cuts it from everything before.
It is what the CPU runs.

**On a TPU the scan is one Pallas kernel a call** (``selective_scan``),
chosen when the program is lowered (``lax.platform_dependent``).  A token's
channels are laid out to fill whole registers: ``x [T, C]`` is read as
``[T * 8, C / 8]``, so token ``t``'s channel ``r * C / 8 + l`` sits on
sublane ``r`` and lane ``l`` of rows ``8t .. 8t + 7``, and ``A`` as ``N``
tiles ``[8, C / 8]`` of the same layout.  The grid is (channel blocks of
``8 * 128`` channels, chunks of ``_CHUNK`` tokens), the chunk axis the
sequential one.  A step walks its chunk token by token with the ``N``
``[8, 128]`` float32 tiles of the state in registers (the loop's carry);
the state lies in VMEM scratch from chunk to chunk.  ``B`` and ``C`` of the
chunk, ``[chunk * N]`` each, and the reset row are in SMEM: a token's
``B_t[n]``, ``C_t[n]`` are scalars that multiply whole tiles.  A document's
first token has an infinite step in the decay only (``exp(inf * A) = 0``),
so the reset is one scalar add a token and not a select of every tile.
``D`` is added in the kernel; the gate ``y * silu(z)`` is the caller's.
Only ``y`` goes back to HBM.

``selective_scan_counted`` says which formulation ran.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_counted"]

F32 = jnp.float32
_CHUNK = 256                  # tokens a kernel step walks
_JNP_CHUNK = 64               # tokens a chunk of the ``jnp`` formulation
_LANES = 128                  # lanes of a channel block: 8 * 128 channels


def _starts(segments: jax.Array) -> jax.Array:
    """True at every document's first token (and at the stream's first)."""
    prev = jnp.concatenate([segments[:1] - 1, segments[:-1]])
    return segments != prev


def _scan_jnp(x, dt, a, b, c, d, segments, chunk):
    """Chunks of ``chunk`` tokens: an associative scan inside each, the
    state handed over by a ``lax.scan``."""
    t, ch = x.shape
    n = a.shape[1]
    pad = -t % chunk
    x, dt = x.astype(F32), dt.astype(F32)
    start = _starts(segments)
    if pad:
        zp = lambda y: jnp.pad(y, [(0, pad)] + [(0, 0)] * (y.ndim - 1))  # noqa: E731
        x, dt, b, c = zp(x), zp(dt), zp(b), zp(c)
        start = jnp.concatenate([start, jnp.ones((pad,), bool)])
    nc = x.shape[0] // chunk
    shaped = lambda y: y.reshape(nc, chunk, *y.shape[1:])     # noqa: E731
    xs = (shaped(x), shaped(dt), shaped(b.astype(F32)),
          shaped(c.astype(F32)), shaped(start))
    a = a.astype(F32)

    def one_chunk(s0, args):
        x_c, dt_c, b_c, c_c, start_c = args
        decay = jnp.where(start_c[:, None, None], 0.0,
                          jnp.exp(dt_c[:, :, None] * a[None]))
        inp = (dt_c * x_c)[:, :, None] * b_c[:, None, :]      # [L, C, N]

        def combine(left, right):
            return left[0] * right[0], right[0] * left[1] + right[1]

        cum_a, cum_b = lax.associative_scan(combine, (decay, inp))
        s = cum_a * s0[None] + cum_b
        y = jnp.einsum("lcn,ln->lc", s, c_c)
        return s[-1], y

    _, y = lax.scan(one_chunk, jnp.zeros((ch, n), F32), xs)
    y = y.reshape(-1, ch)[:t]
    return y + x[:t] * d.astype(F32)[None]


# -- the same recurrence as one Pallas TPU kernel ------------------------------

def _scan_kernel_body(reset_ref, b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref,
                      y_ref, s_ref):
    """One chunk of one channel block.  ``reset_ref [1, chunk]`` (SMEM) 0,
    or inf at a document's first token; ``b_ref``, ``c_ref [1, chunk *
    N]`` (SMEM); ``x_ref``, ``dt_ref``, ``y_ref [chunk * 8, 128]``: token ``t``
    the rows ``8t .. 8t + 7``; ``a_ref [N, 8, 128]``, ``d_ref [8, 128]``;
    ``s_ref [N, 8, 128]`` the state handed from chunk to chunk."""
    n = a_ref.shape[0]
    chunk = reset_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    d = d_ref[...]

    def one_token(t, s):
        rows = pl.ds(pl.multiple_of(t * 8, 8), 8)
        x = x_ref[rows, :]
        dt = dt_ref[rows, :]
        dtx = dt * x
        decay_dt = dt + reset_ref[0, t]
        y = d * x
        out = []
        for i in range(n):
            s_i = jnp.exp(decay_dt * a_ref[i]) * s[i] + dtx * b_ref[0, t * n + i]
            y = y + c_ref[0, t * n + i] * s_i
            out.append(s_i)
        y_ref[rows, :] = y
        return tuple(out)

    s = lax.fori_loop(0, chunk, one_token,
                      tuple(s_ref[i] for i in range(n)))
    for i in range(n):
        s_ref[i] = s[i]


def _kernel_fits(x: jax.Array) -> bool:
    """Channels whole blocks of ``8 * 128``; ``T`` a chunk at least."""
    t, ch = x.shape
    return ch % (8 * _LANES) == 0 and t >= _CHUNK


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_kernel(x, dt, a, b, c, d, segments, interpret=False):
    """``_scan_jnp``'s contract through the ``selective_scan`` kernel."""
    t, ch = x.shape
    n = a.shape[1]
    chunk = _CHUNK
    pad = -t % chunk
    reset = jnp.where(_starts(segments), jnp.inf, 0.0).astype(F32)
    x, dt = x.astype(F32), dt.astype(F32)
    b, c = b.astype(F32), c.astype(F32)
    if pad:
        zp = lambda y: jnp.pad(y, [(0, pad)] + [(0, 0)] * (y.ndim - 1))  # noqa: E731
        x, dt, b, c = zp(x), zp(dt), zp(b), zp(c)
        reset = jnp.concatenate([reset, jnp.full((pad,), jnp.inf, F32)])
    tp = x.shape[0]
    lanes = ch // 8
    rows = lambda y: y.reshape(tp * 8, lanes)                 # noqa: E731
    tile = pl.BlockSpec((chunk * 8, _LANES), lambda j, i: (i, j))
    smem = lambda size: pl.BlockSpec(                         # noqa: E731
        (None, 1, size), lambda j, i: (i, 0, 0), memory_space=pltpu.SMEM)
    y = pl.pallas_call(
        _scan_kernel_body,
        grid=(lanes // _LANES, tp // chunk),
        in_specs=[smem(chunk), smem(chunk * n), smem(chunk * n), tile, tile,
                  pl.BlockSpec((n, 8, _LANES), lambda j, i: (0, 0, j)),
                  pl.BlockSpec((8, _LANES), lambda j, i: (0, j))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((tp * 8, lanes), F32),
        scratch_shapes=[pltpu.VMEM((n, 8, _LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="selective_scan",
    )(reset.reshape(-1, 1, chunk), b.reshape(-1, 1, chunk * n),
      c.reshape(-1, 1, chunk * n), rows(x), rows(dt),
      a.astype(F32).T.reshape(n, 8, lanes), d.astype(F32).reshape(8, lanes))
    return y.reshape(tp, ch)[:t]


def selective_scan_counted(x: jax.Array, dt: jax.Array, a: jax.Array,
                           b: jax.Array, c: jax.Array, d: jax.Array,
                           segments: jax.Array):
    """``x [T, C]`` the scan's input (any float type), ``dt [T, C]`` the
    step (after its softplus), ``a [C, N]`` (negative), ``b, c [T, N]``,
    ``d [C]``, ``segments [T]`` document ids, non-decreasing.  Returns
    ``(y [T, C] float32, fused)``: ``fused`` is an int32 scalar, 1 where the
    program this was lowered into holds the kernel and 0 where the ``jnp``
    formulation runs."""
    args = (x, dt, a, b, c, d, segments)
    plain = lambda *z: (_scan_jnp(*z, _JNP_CHUNK),            # noqa: E731
                        jnp.int32(0))
    if _kernel_fits(x):
        return lax.platform_dependent(
            *args, default=plain,
            tpu=lambda *z: (_scan_kernel(*z), jnp.int32(1)))
    return plain(*args)

