"""Plain reference of an ``afmoe``-type document scorer (grouped-query
attention, a window on some layers and the whole document without positions
on the others, a gated output, QK-norm, sandwich norms, sigmoid routing):
the layer equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision.  Nothing imported from the program; no blocking of
attention, no grouped keys, no grouped products, no sort of assignments.

Layers are numbered from 1; layer ``n`` is of type ``layer_types[n - 1]``.
RMSNorm (eps from the architecture), ``SwiGLU(x) = W_d (silu(W_g x) *
W_u x)``; ``H`` query heads on ``H_kv`` key heads of ``d = head_dim``; ``W``
the window; ``p`` a token's position in its own document, from 0:

* **In**: ``x = sqrt(hidden_size) E[id]`` (``mup_enabled``).
* **Layer**: ``x <- x + N1'(Attn(N1(x)))``; ``x <- x + N2'(MLP(N2(x)))``:
  a norm before every sublayer and one on its output (four a layer).
* **Attention**: ``q_j = Nq(W_q,j a)`` a query head, ``k_g = Nk(W_k,g a)``,
  ``v_g = W_v,g a`` a key head; ``Nq``, ``Nk`` RMSNorm over the head's ``d``
  columns, one weight vector each.  On a ``sliding_attention`` layer
  ``q_j <- R_p q_j``, ``k_g <- R_p k_g``: ``R_p x = x cos + rotate_half(x)
  sin``, the pairs ``(x_i, x_{i + d/2})`` turned by ``p theta^(-2i/d)``; on
  a ``full_attention`` layer nothing turns.  Keys and values are repeated
  to ``H`` heads (head ``j`` reads ``g = j // (H / H_kv)``);
  ``softmax(q_j k_g^T d^-1/2)`` as one dense masked ``[n, n]`` matrix a head
  over the document's tokens ``s`` with ``0 <= t - s`` (full) and
  ``0 <= t - s < W`` (sliding: ``W`` keys, the query's own among them).
  Output ``W_o ([o_1 .. o_H] * sigmoid(W_g a))``.
* **Router**: ``s = sigmoid(W_r x)``; the ``k`` experts of largest ``s + b``
  (``b`` the bias, in the choice only); weights ``route_scale * s_e /
  (sum_chosen s + 1e-20)``.  A loop over the held experts, each run over
  every token and kept where it was chosen; plus the shared expert.
  Experts outside ``held`` add nothing.
* **Dense layers** (the first ``num_dense_layers``): one SwiGLU.
* **Score** of a document of ``n`` tokens:
  ``1/(n-1) sum_{t<n} log softmax(W_head RMSNorm(x_t))[x_{t+1}]`` over the
  ``vocab_rows`` columns held.

Departures from the published description, each forced by the cell:

* attention runs one document at a time (documents never see each other,
  and positions restart);
* ``held`` and ``vocab_rows`` cut experts and vocabulary to one holder's
  share, as the configuration states;
* on the chip the head's logits and the feed-forward sublayers are taken
  ``head_block`` tokens at a time and attention one head at a time, so that
  they fit, and a document is filled up with zero rows behind its last
  token to a multiple of ``pad_to`` (attention is causal, so nothing a real
  token sees changes) so that eight lengths compile few programs; the
  numbers are the same.

``control`` puts the reference in the program's place with a fault planted:
``"fp8"`` rounds every weight, and the residual stream after every
sublayer, to an 8-bit float (e4m3), the nearest precision below the
configuration's bfloat16; ``"half_experts"`` leaves out the upper half of
the held experts; ``"no_window"`` lets a sliding layer see its whole
document; ``"no_rope"`` applies no rotation; ``"no_gate"`` leaves the
attention's output ungated; ``"no_post_norm"`` adds a sublayer's output to
the residual as it is.
"""

from __future__ import annotations

import numpy as np

CONTROLS = (None, "fp8", "half_experts", "no_window", "no_rope", "no_gate",
            "no_post_norm")


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
        self.a = arch
        self.control = control
        self.pad_to = int(pad_to)
        self._jit = {f: jax.jit(getattr(self, f))
                     for f in ("moe", "swiglu", "logp")}
        self._jit["attention"] = jax.jit(self.attention,
                                         static_argnames="sliding")
        self.eps = arch["rms_norm_eps"]
        lo, hi = arch.get("held_experts", [0, arch["num_experts"]])
        if control == "half_experts":
            hi = lo + (hi - lo) // 2
        self.held = (lo, hi)
        self.first_held = arch.get("held_experts", [0])[0]
        d = arch["head_dim"]
        self.freqs = float(arch["rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)

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

    def post(self, out, w):
        """A sublayer's output through its own norm."""
        return out if self.control == "no_post_norm" else self.rms(out, w)

    def swiglu(self, x, w_gu, w_down):
        jax, jnp = _jnp()
        gate, up = jnp.split(x @ self.w(w_gu), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ self.w(w_down)

    def rotate(self, x, positions):
        """``x [n, heads, d]`` as ``x cos + rotate_half(x) sin``;
        ``positions [n]``."""
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

    def attention(self, p, x, sliding: bool):
        """One document ``x [n, hidden]`` through the attention sublayer;
        the token at row ``i`` has position ``i``."""
        jax, jnp = _jnp()
        a = self.a
        n, nh, nkv = (x.shape[0], a["num_attention_heads"],
                      a["num_key_value_heads"])
        d = a["head_dim"]
        q = self.rms((x @ self.w(p["wq"])).reshape(n, nh, d),
                     p["q_head_norm"])
        k = self.rms((x @ self.w(p["wk"])).reshape(n, nkv, d),
                     p["k_head_norm"])
        v = (x @ self.w(p["wv"])).reshape(n, nkv, d)
        if sliding:
            positions = jnp.arange(n)
            q, k = self.rotate(q, positions), self.rotate(k, positions)
        k, v = (jnp.repeat(y, nh // nkv, axis=1) for y in (k, v))
        behind = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
        seen = behind >= 0
        if sliding and self.control != "no_window":
            seen = seen & (behind < a["sliding_window"])

        def head(t):
            q_h, k_h, v_h = t
            s = jnp.where(seen, (q_h @ k_h.T) / np.sqrt(d), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v_h

        o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))
        o = o.transpose(1, 0, 2).reshape(n, nh * d)
        if self.control != "no_gate":
            o = o * jax.nn.sigmoid(x @ self.w(p["w_gate"]))
        return o @ self.w(p["wo"])

    def logp(self, hid, head, target):
        """``log softmax(hid @ head)[target]`` a token."""
        jax, jnp = _jnp()
        return jnp.take_along_axis(jax.nn.log_softmax(hid @ head, axis=-1),
                                   target[:, None], 1)[:, 0]

    def moe(self, p, x):
        """(the held experts' part + the shared expert, chosen ``[T, k]``,
        the gap between the k-th and the (k+1)-th ``s + b`` ``[T]``)."""
        jax, jnp = _jnp()
        k = self.a["num_experts_per_tok"]
        s = jax.nn.sigmoid(x @ self.w(p["router"]))
        top, chosen = jax.lax.top_k(s + self.w(p["router_bias"]), k + 1)
        chosen, margin = chosen[:, :k], top[:, k - 1] - top[:, k]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        weights = self.a["route_scale"] * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)

        def one_expert(e, y):
            w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            local = e - self.first_held       # the holder stores its own
            return y + w_e[:, None] * self.swiglu(x, p["e_gu"][local],
                                                  p["e_down"][local])

        y = jax.lax.fori_loop(self.held[0], self.held[1], one_expert,
                              jnp.zeros_like(x))
        return y + self.swiglu(x, p["s_gu"], p["s_down"]), chosen, margin

    # -- one layer over the packed stream ---------------------------------
    def layer(self, number: int, p, x, bounds, step: int):
        """``x [T, hidden]`` through layer ``number``; ``bounds`` the
        documents' ``(start, end)`` on the host; the feed-forward sublayer
        ``step`` tokens at a time.  Returns ``(x, chosen, margin)``, the
        last two None in a dense layer."""
        _, jnp = _jnp()
        sliding = self.a["layer_types"][number - 1] == "sliding_attention"
        y = self.rms(x, p["norm1"])

        def one_document(s, e):
            fill = -(e - s) % self.pad_to
            return self._jit["attention"](
                p, jnp.pad(y[s:e], ((0, fill), (0, 0))),
                sliding=sliding)[:e - s]

        mixed = [one_document(s, e) for s, e in bounds if e > s]
        x = self.act(x + self.post(jnp.concatenate(mixed), p["post_norm1"]))
        y = self.rms(x, p["norm2"])
        cuts = range(0, x.shape[0], step)
        if number <= self.a["num_dense_layers"]:
            out = jnp.concatenate([self._jit["swiglu"](
                y[i:i + step], p["w_gu"], p["w_down"]) for i in cuts])
            return self.act(x + self.post(out, p["post_norm2"])), None, None
        parts = [self._jit["moe"](p, y[i:i + step]) for i in cuts]
        out, chosen, margin = (jnp.concatenate([part[j] for part in parts])
                               for j in range(3))
        return self.act(x + self.post(out, p["post_norm2"])), chosen, margin

    def run(self, params, ids, row_ptr, positions=(), head_block=None):
        """Everything the comparison needs of one batch: ``scores`` (one a
        document), ``logits`` at stream ``positions`` over the held
        vocabulary, and per mixture layer ``chosen`` and ``margin``."""
        jax, jnp = _jnp()
        a = self.a
        row_ptr = np.asarray(row_ptr, np.int64)
        bounds = list(zip(row_ptr[:-1].tolist(), row_ptr[1:].tolist()))
        total = int(row_ptr[-1])
        ids = jnp.asarray(np.asarray(ids)[:total])
        step = head_block or max(total, 1)
        scale = np.sqrt(a["hidden_size"]) if a.get("mup_enabled") else 1.0
        with jax.default_matmul_precision("highest"):
            x = self.act(self.w(params["embed"])[ids] * scale)
            chosen, margin = {}, {}
            for number in range(1, a["num_hidden_layers"] + 1):
                name = f"layer_{number:02d}"
                x, c, m = self.layer(number, params[name], x, bounds, step)
                if c is not None:
                    chosen[name], margin[name] = np.asarray(c), np.asarray(m)
            hid = self.rms(x, params["final_norm"])
            head = self.w(params["head"])
            nxt = jnp.roll(ids, -1)
            logp = np.concatenate([np.asarray(
                self._jit["logp"](hid[i:i + step], head, nxt[i:i + step]))
                for i in range(0, total, step)]) if total else np.zeros(0)
            scores = np.array([logp[s:e - 1].mean() if e - s > 1 else 0.0
                               for s, e in bounds], np.float64)
            logits = np.asarray(hid[jnp.asarray(list(positions), jnp.int32)]
                                @ head) if len(positions) else None
        return {"scores": scores, "logits": logits, "chosen": chosen,
                "margin": margin}
