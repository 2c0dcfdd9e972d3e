"""Plain reference of the hybrid linear-attention / latent-attention
mixture-of-experts document scorer: the published layer equations in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision.
Nothing imported from the program; no chunking, no kernels, no cache.

Layers are numbered from 1.  Pre-norm residual blocks, RMSNorm (eps from
the architecture), ``SwiGLU(x) = W_d (silu(W_g x) * W_u x)``:

* **KDA** (layers in ``linear_attn_config.kda_layers``; ``H`` heads of
  ``d``): ``q, k, v = silu(Conv4(W x))``, a causal depthwise convolution
  that sees zeros before the document's first token; ``q, k`` L2-normalised
  per head, ``q`` scaled by ``d^-1/2``;
  ``a_t = exp(-exp(A_h) softplus(W_up W_down x_t + b))`` per channel,
  ``b_t = sigmoid(W_b x_t)`` per head; from ``S = 0`` at the document's
  start, token by token,
  ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; output
  ``W_o (RMSNorm_head(o_t) * sigmoid(W_gup W_gdown x_t))``.
* **MLA without positions** (the other layers): ``q = W_q x`` as heads of
  ``d_nope + d_rope``; ``[c ; k_r] = W_kva x``, ``c <- RMSNorm(c)``,
  ``[k_h ; v_h] = W_kvb,h c``, ``k_h <- [k_h ; k_r]`` with ``k_r`` shared
  by the heads and no rotation applied to anything;
  ``softmax(q_h k_h^T / sqrt(d_nope + d_rope))`` over the document's tokens
  up to the query, as one dense masked matrix a head.
* **Mixture**: ``s = sigmoid(W_r x)``; the ``k`` experts of largest
  ``s + b``; weights ``scale * s_e / sum_chosen s``; a loop over the held
  experts, each run over every token and kept where it was chosen; plus
  the shared expert.  Experts outside ``held`` add nothing.
* **Score** of a document of ``n`` tokens:
  ``1/(n-1) sum_{t<n} log softmax(W_head RMSNorm(x_t))[x_{t+1}]`` over the
  ``vocab_rows`` columns held.

Departures from the published description, each forced by the cell:

* the mixer runs one document at a time (documents never see each other);
* ``held`` and ``vocab_rows`` cut experts and vocabulary to one holder's
  share, as the configuration states;
* on the chip the head's logits are taken ``head_block`` tokens at a time
  and attention one head at a time, so that they fit, and a document is
  filled up with zero rows behind its last token to a multiple of
  ``pad_to`` (every mixer is causal, so nothing a real token sees changes)
  so that sixteen lengths compile eight programs; the numbers are the same.

``control`` puts the reference in the program's place with a fault planted:
``"fp8"`` rounds every weight, and the residual stream after every
sublayer, to an 8-bit float (e4m3) — the nearest precision below the
configuration's bfloat16; ``"half_experts"`` leaves out the upper half of
the held experts.
"""

from __future__ import annotations

import numpy as np


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


class Reference:
    """The reference of one architecture (the configuration's ``arch``
    group).  Every method takes and returns float32 arrays."""

    def __init__(self, arch: dict, control: str | None = None,
                 pad_to: int = 1):
        if control not in (None, "fp8", "half_experts"):
            raise ValueError(f"control={control!r}")
        jax, _ = _jnp()
        self.a = arch
        self.control = control
        self.pad_to = int(pad_to)
        self._jit = {f: jax.jit(getattr(self, f))
                     for f in ("kda", "mla", "moe", "swiglu", "logp")}
        lin = arch["linear_attn_config"]
        self.kda_layers = set(lin["kda_layers"])
        self.kh, self.kd = lin["num_heads"], lin["head_dim"]
        self.eps = arch["rms_norm_eps"]
        lo, hi = arch.get("held_experts", [0, arch["num_experts"]])
        if control == "half_experts":
            hi = lo + (hi - lo) // 2
        self.held = (lo, hi)
        self.first_held = arch.get("held_experts", [0])[0]

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

    def swiglu(self, x, w_gu, w_down):
        jax, jnp = _jnp()
        gate, up = jnp.split(x @ self.w(w_gu), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ self.w(w_down)

    def conv(self, x, taps):
        """``y_t = sum_j taps[j] x_{t-3+j}`` with zeros before the
        document."""
        _, jnp = _jnp()
        taps = self.w(taps)
        kk, n = taps.shape[0], x.shape[0]
        xp = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x])
        return sum(taps[j] * xp[j:j + n] for j in range(kk))

    def kda(self, p, x):
        """One document ``x [n, hidden]`` through the KDA sublayer."""
        jax, jnp = _jnp()
        n, h, d = x.shape[0], self.kh, self.kd

        def heads(w, taps):
            return jax.nn.silu(self.conv(x @ self.w(w), taps)).reshape(n, h, d)

        q, k = heads(p["wq"], p["conv_q"]), heads(p["wk"], p["conv_k"])
        v = heads(p["wv"], p["conv_v"])
        unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)  # noqa: E731
        q, k = unit(q) * d ** -0.5, unit(k)
        raw = (x @ self.w(p["decay_down"])) @ self.w(p["decay_up"])
        alpha = jnp.exp(-jnp.exp(self.w(p["decay_rate"]))[None, :, None]
                        * jax.nn.softplus(raw + self.w(p["decay_bias"])
                                          ).reshape(n, h, d))
        beta = jax.nn.sigmoid(x @ self.w(p["w_beta"]))

        def step(s, t):
            # sums, not matrix products: a [d] x [d, d] product a head is
            # the slowest thing the chip can be asked for
            q_t, k_t, v_t, a_t, b_t = t
            s = a_t[:, :, None] * s                        # Diag(a) S
            seen = jnp.sum(k_t[:, :, None] * s, axis=1)    # k^T (Diag(a) S)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None]
            return s, jnp.sum(s * q_t[:, :, None], axis=1)  # S^T q

        _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                            (q, k, v, alpha, beta))
        gate = jax.nn.sigmoid((x @ self.w(p["gate_down"]))
                              @ self.w(p["gate_up"])).reshape(n, h, d)
        return (self.rms(o, p["out_norm"]) * gate).reshape(n, h * d) \
            @ self.w(p["wo"])

    def mla(self, p, x):
        """One document through the latent-attention sublayer."""
        jax, jnp = _jnp()
        a = self.a
        n, nh = x.shape[0], a["num_attention_heads"]
        dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
        q = (x @ self.w(p["wq"])).reshape(n, nh, dn + dr)
        ckr = x @ self.w(p["wkv_a"])
        c, k_r = ckr[:, :a["kv_lora_rank"]], ckr[:, a["kv_lora_rank"]:]
        kv = (self.rms(c, p["kv_norm"]) @ self.w(p["wkv_b"])).reshape(
            n, nh, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (n, nh, dr))], -1)
        v = kv[..., dn:]
        causal = jnp.tril(jnp.ones((n, n), bool))

        def head(t):
            q_h, k_h, v_h = t
            s = jnp.where(causal, (q_h @ k_h.T) / np.sqrt(dn + dr), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v_h

        o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))
        return o.transpose(1, 0, 2).reshape(n, nh * dv) @ self.w(p["wo"])

    def logp(self, hid, head, target):
        """``log softmax(hid @ head)[target]`` a token."""
        jax, jnp = _jnp()
        return jnp.take_along_axis(jax.nn.log_softmax(hid @ head, axis=-1),
                                   target[:, None], 1)[:, 0]

    def moe(self, p, x):
        """(the held experts' part + the shared expert, chosen ``[T, k]``,
        the gap between the k-th and the (k+1)-th ``s + b`` ``[T]``)."""
        jax, jnp = _jnp()
        a = self.a
        k = a["num_experts_per_token"]
        s = jax.nn.sigmoid(x @ self.w(p["router"]))
        top, chosen = jax.lax.top_k(s + self.w(p["router_bias"]), k + 1)
        margin = top[:, k - 1] - top[:, k]
        chosen = chosen[:, :k]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        weights = a["routed_scaling_factor"] * picked / picked.sum(
            -1, keepdims=True)

        def one_expert(e, y):
            w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            local = e - self.first_held       # the holder stores its own
            return y + w_e[:, None] * self.swiglu(x, p["e_gu"][local],
                                                  p["e_down"][local])

        y = jax.lax.fori_loop(self.held[0], self.held[1], one_expert,
                              jnp.zeros_like(x))
        return y + self.swiglu(x, p["s_gu"], p["s_down"]), chosen, margin

    # -- one layer over the packed stream ---------------------------------
    def layer(self, number: int, p, x, bounds):
        """``x [T, hidden]`` through layer ``number``; ``bounds`` the
        documents' ``(start, end)`` on the host.  Returns ``(x, chosen,
        margin)``, the last two None in a dense layer."""
        jax, jnp = _jnp()
        mixer = self._jit["kda" if number in self.kda_layers else "mla"]
        y = self.rms(x, p["norm1"])

        def one_document(s, e):
            fill = -(e - s) % self.pad_to
            return mixer(p, jnp.pad(y[s:e], ((0, fill), (0, 0))))[:e - s]

        mixed = [one_document(s, e) for s, e in bounds if e > s]
        x = self.act(x + jnp.concatenate(mixed))
        y = self.rms(x, p["norm2"])
        if number <= self.a["first_k_dense_replace"]:
            out, chosen, margin = self._jit["swiglu"](
                y, p["w_gu"], p["w_down"]), None, None
        else:
            out, chosen, margin = self._jit["moe"](p, y)
        return self.act(x + out), chosen, margin

    def run(self, params, ids, row_ptr, positions=(), head_block=None):
        """Everything the comparison needs of one batch: ``scores`` (one a
        document), ``logits`` at stream ``positions`` over the held
        vocabulary, and per mixture layer ``chosen`` and ``margin``."""
        jax, jnp = _jnp()
        row_ptr = np.asarray(row_ptr, np.int64)
        bounds = list(zip(row_ptr[:-1].tolist(), row_ptr[1:].tolist()))
        total = int(row_ptr[-1])
        ids = jnp.asarray(np.asarray(ids)[:total])
        with jax.default_matmul_precision("highest"):
            x = self.act(self.w(params["embed"])[ids])
            chosen, margin = {}, {}
            for number in range(1, self.a["num_hidden_layers"] + 1):
                name = f"layer_{number:02d}"
                x, c, m = self.layer(number, params[name], x, bounds)
                if c is not None:
                    chosen[name], margin[name] = np.asarray(c), np.asarray(m)
            hid = self.rms(x, params["final_norm"])
            head = self.w(params["head"])
            step = head_block or max(total, 1)
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
