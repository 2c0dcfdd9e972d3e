"""Plain reference of a ``brumby``-type document scorer (Qwen3's dense block
with every attention layer replaced by gated, normalised power retention of
degree 2): the layer equations in straightforward ``jax.numpy``, float32,
matmuls at ``highest`` precision.  Nothing imported from the program; no
kernel, no chunks, no state form, no cache.

Layers are numbered from 1; every layer retains.  RMSNorm (eps from the
architecture), ``SwiGLU(x) = W_d (silu(W_g x) * W_u x)``; ``H`` query heads
on ``H_kv`` key heads of ``d = head_dim`` (query head ``h`` reads key head
``g = h // (H / H_kv)``); ``p`` a token's position in its own document,
from 0:

* **In**: ``x = E[id]``.
* **Layer**: ``x <- x + Ret(N1(x))``; ``x <- x + SwiGLU(N2(x))``.
* **Retention**: ``q_h = R_p RMSNorm_d(W_q,h a)``, ``k_g = R_p
  RMSNorm_d(W_k,g a)`` (one norm weight each, over a head's ``d``
  columns), ``v_g = W_v,g a``, no bias; ``R_p x = x cos + rotate_half(x)
  sin``, the pairs ``(x_i, x_{i + d/2})`` turned by ``p theta^(-2i/d)``.
  The gate ``log gamma_{t,g} = logsigmoid(w_g . a_t + b_g)`` (``decay_up``,
  ``decay_rate``); ``Gamma_tj = prod_{l=j+1..t} gamma_l = exp(c_t - c_j)``
  with ``c`` the document's running sum of ``log gamma``;
  ``o_{t,h} = sum_{j<=t} Gamma_tj (q_t . k_j)^2 v_j / (sum_{j<=t} Gamma_tj
  (q_t . k_j)^2 + eps)``, one dense masked ``[n, n]`` weight matrix a head
  over the document's tokens.  Output ``W_o [o_1 .. o_H]``.
* **Score** of a document of ``n`` tokens:
  ``1/(n-1) sum_{t<n} log softmax(W_head RMSNorm(x_t))[x_{t+1}]`` over the
  ``vocab_rows`` columns held.

Departures from the published description, each forced by the cell:

* retention runs one document at a time (documents never see each other,
  and positions restart);
* the gate's bias ``b_g`` is a parameter of its own (zero would be the
  unbiased gate);
* on the chip the head's logits and the feed-forward sublayer are taken
  ``head_block`` tokens at a time and retention one head and one block of
  ``pad_to`` queries at a time, so that they fit, and a document is
  filled up with zero rows behind its last token to a multiple of
  ``pad_to`` (retention is causal, so nothing a real token sees changes)
  so that four lengths compile few programs; the numbers are the same.

``control`` puts the reference in the program's place with a fault planted:
``"fp8"`` rounds every weight, and the residual stream after every
sublayer, to an 8-bit float (e4m3), the nearest precision below the
configuration's bfloat16; ``"no_normaliser"`` leaves the division out (the
numerator alone); ``"no_gate"`` holds every ``gamma`` at 1;
``"no_rope"`` turns nothing; ``"bf16_state"`` computes retention in its
state form token by token — ``S_t = gamma_t S_{t-1} + phi(k_t) v_t^T`` and
``z_t = gamma_t z_{t-1} + phi(k_t)``, ``phi`` the symmetric square — with
``S`` and ``z`` held in bfloat16 (rounded after every token).
"""

from __future__ import annotations

import numpy as np

CONTROLS = (None, "fp8", "no_normaliser", "no_gate", "no_rope", "bf16_state")
EPS = 1e-6


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


class Reference:
    """The reference of one architecture (the configuration's file).
    Every method takes and returns float32 arrays."""

    def __init__(self, arch: dict, control: str | None = None,
                 pad_to: int = 1):
        if control not in CONTROLS:
            raise ValueError(f"control={control!r}")
        jax, _ = _jnp()
        layers = arch["num_hidden_layers"]
        if set(arch["layer_types"][:layers]) != {"power_retention"}:
            raise ValueError("a brumby-type reference computes "
                             "power_retention layers only")
        self.a = arch
        self.control = control
        self.pad_to = int(pad_to)
        self.eps = arch["rms_norm_eps"]
        d = arch["head_dim"]
        self.freqs = float(arch["rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)
        self._jit = {"mlp": jax.jit(self.mlp), "logp": jax.jit(self.logp),
                     "logits": jax.jit(self.logits),
                     "retention": jax.jit(self.retention,
                                          static_argnames="block")}

    # -- pieces -----------------------------------------------------------
    def w(self, x):
        """A weight as the reference uses it: float32 (through an 8-bit
        float under the ``fp8`` control)."""
        _, jnp = _jnp()
        return self.act(x.astype(jnp.float32))

    def act(self, x):
        _, jnp = _jnp()
        if self.control == "fp8":
            return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x

    def rms(self, x, w):
        _, jnp = _jnp()
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                            + self.eps) * self.w(w)

    def mlp(self, p, x):
        jax, jnp = _jnp()
        gate, up = jnp.split(x @ self.w(p["w_gu"]), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ self.w(p["w_down"])

    def rotate(self, x, positions):
        """``x [n, heads, d]`` as ``x cos + rotate_half(x) sin``."""
        _, jnp = _jnp()
        if self.control == "no_rope":
            return x
        angle = positions.astype(jnp.float32)[:, None] \
            * jnp.asarray(self.freqs, jnp.float32)[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None]
        half = x.shape[-1] // 2
        turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * cos + turned * sin

    def heads(self, p, x):
        """One document's ``q [n, H, d]``, ``k, v [n, H_kv, d]`` and the
        running sums ``c [n, H_kv]`` of ``log gamma``."""
        jax, jnp = _jnp()
        a = self.a
        n, nh, nkv, d = (x.shape[0], a["num_attention_heads"],
                         a["num_key_value_heads"], a["head_dim"])
        positions = jnp.arange(n)
        q = self.rotate(self.rms((x @ self.w(p["wq"])).reshape(n, nh, d),
                                 p["q_head_norm"]), positions)
        k = self.rotate(self.rms((x @ self.w(p["wk"])).reshape(n, nkv, d),
                                 p["k_head_norm"]), positions)
        v = (x @ self.w(p["wv"])).reshape(n, nkv, d)
        log_g = jax.nn.log_sigmoid(x @ self.w(p["decay_up"])
                                   + self.w(p["decay_rate"]))
        if self.control == "no_gate":
            log_g = jnp.zeros_like(log_g)
        return q, k, v, jnp.cumsum(log_g, 0)

    def retention(self, p, x, block: int):
        """One document ``x [n, hidden]`` (``n`` a multiple of ``block``)
        through the retention sublayer, the pairs of ``block`` queries at a
        time, one head at a time."""
        jax, jnp = _jnp()
        a = self.a
        n, nh = x.shape[0], a["num_attention_heads"]
        group = nh // a["num_key_value_heads"]
        q, k, v, c = self.heads(p, x)
        if self.control == "bf16_state":
            o = self.state_form(q, k, v, c)
            return o.reshape(n, -1) @ self.w(p["wo"])
        keys = jnp.arange(n)

        def head(h):
            g = h // group
            k_g, v_g, c_g = k[:, g], v[:, g], c[:, g]

            def queries(i):
                rows = i * block + jnp.arange(block)
                q_b = jax.lax.dynamic_slice_in_dim(q[:, h], i * block, block)
                c_b = jax.lax.dynamic_slice_in_dim(c_g, i * block, block)
                seen = keys[None, :] <= rows[:, None]
                decay = jnp.exp(jnp.where(seen, c_b[:, None] - c_g[None, :],
                                          -jnp.inf))
                weight = (q_b @ k_g.T) ** 2 * decay
                if self.control == "no_normaliser":
                    return weight @ v_g
                return (weight @ v_g) / (weight.sum(-1, keepdims=True)
                                         + EPS)

            return jax.lax.map(queries, jnp.arange(n // block)).reshape(n, -1)

        o = jax.lax.map(head, jnp.arange(nh))              # [H, n, d]
        return o.transpose(1, 0, 2).reshape(n, -1) @ self.w(p["wo"])

    def state_form(self, q, k, v, c):
        """The ``bf16_state`` control: the recurrence token by token with
        ``S [H_kv, D, d]`` and ``z [H_kv, D]`` rounded to bfloat16 after
        every token."""
        jax, jnp = _jnp()
        bf16 = jnp.bfloat16
        n, nh, d = q.shape
        nkv = k.shape[1]
        a_i, b_i = np.triu_indices(d)
        scale = jnp.asarray(np.where(a_i == b_i, 1.0, np.sqrt(2.0)),
                            jnp.float32)
        phi = lambda x: x[..., a_i] * x[..., b_i] * scale     # noqa: E731
        gamma = jnp.exp(jnp.diff(c, axis=0, prepend=0.0))     # [n, H_kv]

        def step(state, xs):
            s, z = state
            q_t, k_t, v_t, g_t = xs
            f = phi(k_t)                                      # [H_kv, D]
            s = (g_t[:, None, None] * s.astype(jnp.float32)
                 + f[:, :, None] * v_t[:, None, :]).astype(bf16)
            z = (g_t[:, None] * z.astype(jnp.float32) + f).astype(bf16)
            fq = phi(q_t).reshape(nkv, nh // nkv, -1)         # [H_kv, G, D]
            num = jnp.einsum("kgD,kDe->kge", fq, s.astype(jnp.float32))
            den = jnp.einsum("kgD,kD->kg", fq, z.astype(jnp.float32))
            return (s, z), (num / (den[..., None] + EPS)).reshape(nh, d)

        dd = d * (d + 1) // 2
        _, o = jax.lax.scan(step, (jnp.zeros((nkv, dd, d), bf16),
                                   jnp.zeros((nkv, dd), bf16)),
                            (q, k, v, gamma))
        return o

    def logits(self, hid, head):
        """``hid @ head`` over the held vocabulary; ``head`` as stored (its
        float32 copy, 3.1 GB at the published widths, exists inside this
        call only)."""
        return hid @ self.w(head)

    def logp(self, hid, head, target):
        """``log softmax(hid @ head)[target]`` a token."""
        jax, jnp = _jnp()
        return jnp.take_along_axis(
            jax.nn.log_softmax(self.logits(hid, head), axis=-1),
            target[:, None], 1)[:, 0]

    # -- one layer over the packed stream ---------------------------------
    def layer(self, p, x, bounds, step: int):
        """``x [T, hidden]`` through one layer; ``bounds`` the documents'
        ``(start, end)`` on the host; the feed-forward sublayer ``step``
        tokens at a time."""
        _, jnp = _jnp()
        y = self.rms(x, p["norm1"])

        def one_document(s, e):
            fill = -(e - s) % self.pad_to
            block = self.pad_to if self.pad_to > 1 else e - s
            return self._jit["retention"](
                p, jnp.pad(y[s:e], ((0, fill), (0, 0))), block=block)[:e - s]

        mixed = [one_document(s, e) for s, e in bounds if e > s]
        x = self.act(x + jnp.concatenate(mixed))
        y = self.rms(x, p["norm2"])
        out = jnp.concatenate([self._jit["mlp"](p, y[i:i + step])
                               for i in range(0, x.shape[0], step)])
        return self.act(x + out)

    def run(self, params, ids, row_ptr, positions=(), head_block=None):
        """Everything the comparison needs of one batch: ``scores`` (one a
        document), ``logits`` at stream ``positions`` over the held
        vocabulary; no layer chooses experts."""
        jax, jnp = _jnp()
        a = self.a
        row_ptr = np.asarray(row_ptr, np.int64)
        bounds = list(zip(row_ptr[:-1].tolist(), row_ptr[1:].tolist()))
        total = int(row_ptr[-1])
        ids = jnp.asarray(np.asarray(ids)[:total])
        step = head_block or max(total, 1)
        with jax.default_matmul_precision("highest"):
            x = self.act(self.w(params["embed"][ids]))
            for number in range(1, a["num_hidden_layers"] + 1):
                x = self.layer(params[f"layer_{number:02d}"], x, bounds, step)
            hid = self.rms(x, params["final_norm"])
            head = params["head"]
            nxt = jnp.roll(ids, -1)
            logp = np.concatenate([np.asarray(
                self._jit["logp"](hid[i:i + step], head, nxt[i:i + step]))
                for i in range(0, total, step)]) if total else np.zeros(0)
            scores = np.array([logp[s:e - 1].mean() if e - s > 1 else 0.0
                               for s, e in bounds], np.float64)
            logits = np.asarray(self._jit["logits"](
                hid[jnp.asarray(list(positions), jnp.int32)], head)) \
                if len(positions) else None
        return {"scores": scores, "logits": logits, "chosen": {},
                "margin": {}}
