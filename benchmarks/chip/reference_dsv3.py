"""Plain reference of a ``deepseek_v3``-type document scorer (latent
attention with rotated positions and a low-rank query at every layer,
group-limited routing): the published layer equations in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision.  Nothing imported
from the program; no blocking of attention, no grouped products, no sort of
assignments.

Layers are numbered from 1.  Pre-norm residual blocks, RMSNorm (eps from
the architecture), ``SwiGLU(x) = W_d (silu(W_g x) * W_u x)``; ``H`` heads,
``d_n = qk_nope_head_dim``, ``d_r = qk_rope_head_dim``, ``d_v = v_head_dim``;
``p`` a token's position in its own document, from 0:

* **MLA**: ``c_q = RMSNorm(W_qa x)``, ``q_h = W_qb,h c_q = [q_n ; q_r]``;
  ``[c ; k_r] = W_kva x``, ``c <- RMSNorm(c)``, ``[k_n,h ; v_h] = W_kvb,h c``;
  ``q_r <- R_p q_r`` a head, ``k_r <- R_p k_r`` once, ``k_h = [k_n,h ; k_r]``.
  ``R_p`` multiplies the complex numbers ``x_2i + i x_2i+1`` by
  ``exp(i p f_i)``.  YaRN: ``e_i = theta^(-2i/d_r)``,
  ``dim(n) = d_r ln(L / (2 pi n)) / (2 ln theta)`` with ``L`` the original
  context, ``lo = floor(dim(beta_fast))``, ``hi = ceil(dim(beta_slow))``,
  ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``,
  ``f_i = e_i (1 - ramp_i) + (e_i / factor) ramp_i``; cos and sin carry
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 here).
  ``softmax(q_h k_h^T (d_n + d_r)^-1/2 m^2)`` over the document's tokens up
  to the query, ``m = 0.1 mscale_all_dim ln(factor) + 1``, as one dense
  masked matrix a head; output ``W_o [o_1 .. o_H]``.
* **Router**: ``s = sigmoid(W_r x)``, ``u = s + b``; the experts stand in
  ``n_group`` equal groups in order; a group ranks by the sum of its two
  largest ``u``; the ``topk_group`` best groups are kept (sort the groups,
  mask the rest), then the ``k`` experts of largest ``u`` inside them;
  weights ``scale * s_e / sum_chosen s``.  A loop over the held experts,
  each run over every token and kept where it was chosen; plus the shared
  expert.  Experts outside ``held`` add nothing.
* **Dense layers** (the first ``first_k_dense_replace``): one SwiGLU.
* **Score** of a document of ``n`` tokens:
  ``1/(n-1) sum_{t<n} log softmax(W_head RMSNorm(x_t))[x_{t+1}]`` over the
  ``vocab_rows`` columns held.

Not evaluated: the multi-token-prediction module
(``num_nextn_predict_layers``); a scorer reads the main model's next-token
log-probabilities.

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
the held experts; ``"no_rope"`` applies no rotation; ``"plain_rope"``
rotates by ``e_i`` with ``m = 1`` (no YaRN); ``"ungrouped"`` takes the ``k``
largest ``u`` over all experts.
"""

from __future__ import annotations

import math

import numpy as np

CONTROLS = (None, "fp8", "half_experts", "no_rope", "plain_rope", "ungrouped")


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def yarn(arch: dict):
    """(frequencies ``[d_r / 2]`` float64, the factor on cos and sin, the
    factor ``m`` whose square scales the softmax)."""
    d, theta = arch["qk_rope_head_dim"], float(arch["rope_theta"])
    e = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    sc = arch.get("rope_scaling")
    if not sc:
        return e, 1.0, 1.0
    assert sc.get("rope_type", sc.get("type")) == "yarn", sc
    factor = float(sc["factor"])
    original = float(sc["original_max_position_embeddings"])

    def dim(n):
        return d * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(sc["beta_fast"])), 0)
    hi = min(math.ceil(dim(sc["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / ((hi - lo) or 0.001), 0, 1)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

    return (e * (1 - ramp) + e / factor * ramp,
            mscale(sc.get("mscale", 1)) / mscale(sc.get("mscale_all_dim", 0)),
            mscale(sc.get("mscale_all_dim", 0)))


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
                     for f in ("mla", "moe", "swiglu", "logp")}
        self.eps = arch["rms_norm_eps"]
        lo, hi = arch.get("held_experts", [0, arch["n_routed_experts"]])
        if control == "half_experts":
            hi = lo + (hi - lo) // 2
        self.held = (lo, hi)
        self.first_held = arch.get("held_experts", [0])[0]
        plain = dict(arch, rope_scaling=None) if control == "plain_rope" \
            else arch
        self.freqs, self.cos_sin_factor, self.m = yarn(plain)

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

    def rotate(self, x, positions):
        """``x [n, ..., d_r]`` with the pairs ``(x_2i, x_2i+1)`` as complex
        numbers, times ``exp(i p f_i)``; ``positions [n]``."""
        jax, jnp = _jnp()
        if self.control == "no_rope":
            return x
        angle = positions.astype(jnp.float32)[:, None] \
            * jnp.asarray(self.freqs, jnp.float32)[None, :]
        turn = self.cos_sin_factor * jax.lax.complex(jnp.cos(angle),
                                                     jnp.sin(angle))
        turn = turn.reshape(turn.shape[:1] + (1,) * (x.ndim - 2)
                            + turn.shape[1:])
        pairs = x.reshape(x.shape[:-1] + (-1, 2))
        z = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
        return jnp.stack([z.real, z.imag], -1).reshape(x.shape)

    def mla(self, p, x):
        """One document ``x [n, hidden]`` through the latent-attention
        sublayer; the token at row ``i`` has position ``i``."""
        jax, jnp = _jnp()
        a = self.a
        n, nh = x.shape[0], a["num_attention_heads"]
        dn, dr, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
        positions = jnp.arange(n)
        c_q = self.rms(x @ self.w(p["wq_a"]), p["q_norm"])
        q = (c_q @ self.w(p["wq_b"])).reshape(n, nh, dn + dr)
        q = jnp.concatenate([q[..., :dn],
                             self.rotate(q[..., dn:], positions)], -1)
        ckr = x @ self.w(p["wkv_a"])
        c, k_r = ckr[:, :a["kv_lora_rank"]], ckr[:, a["kv_lora_rank"]:]
        k_r = self.rotate(k_r, positions)
        kv = (self.rms(c, p["kv_norm"]) @ self.w(p["wkv_b"])).reshape(
            n, nh, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (n, nh, dr))], -1)
        v = kv[..., dn:]
        causal = jnp.tril(jnp.ones((n, n), bool))
        scale = self.m ** 2 / np.sqrt(dn + dr)

        def head(t):
            q_h, k_h, v_h = t
            s = jnp.where(causal, (q_h @ k_h.T) * scale, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v_h

        o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))
        return o.transpose(1, 0, 2).reshape(n, nh * dv) @ self.w(p["wo"])

    def logp(self, hid, head, target):
        """``log softmax(hid @ head)[target]`` a token."""
        jax, jnp = _jnp()
        return jnp.take_along_axis(jax.nn.log_softmax(hid @ head, axis=-1),
                                   target[:, None], 1)[:, 0]

    def choose(self, s, bias):
        """(chosen ``[T, k]``, the smaller of the gap between the k-th and
        the (k+1)-th ``u`` inside the kept groups and the gap between the
        last group kept and the first left out ``[T]``)."""
        jax, jnp = _jnp()
        a = self.a
        k, groups, kept = (a["num_experts_per_tok"], a["n_group"],
                           a["topk_group"])
        u = s + bias
        gap = jnp.full(u.shape[:1], jnp.inf)
        if groups > 1 and self.control != "ungrouped":
            by_group = u.reshape(u.shape[0], groups, -1)
            rank = jnp.sort(by_group, -1)[..., -2:].sum(-1)   # [T, groups]
            best = jnp.sort(rank, -1)[:, ::-1]
            if kept < groups:
                gap = best[:, kept - 1] - best[:, kept]
            # a tie at the cut keeps every group of that rank
            u = jnp.where((rank >= best[:, kept - 1:kept])[:, :, None],
                          by_group, -jnp.inf).reshape(u.shape)
        top, chosen = jax.lax.top_k(u, k + 1)
        return chosen[:, :k], jnp.minimum(gap, top[:, k - 1] - top[:, k])

    def moe(self, p, x):
        """(the held experts' part + the shared expert, chosen ``[T, k]``,
        the margin of the choice ``[T]``)."""
        jax, jnp = _jnp()
        s = jax.nn.sigmoid(x @ self.w(p["router"]))
        chosen, margin = self.choose(s, self.w(p["router_bias"]))
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        weights = self.a["routed_scaling_factor"] * picked / picked.sum(
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
    def layer(self, number: int, p, x, bounds, step: int):
        """``x [T, hidden]`` through layer ``number``; ``bounds`` the
        documents' ``(start, end)`` on the host; the feed-forward sublayer
        ``step`` tokens at a time.  Returns ``(x, chosen, margin)``, the
        last two None in a dense layer."""
        _, jnp = _jnp()
        y = self.rms(x, p["norm1"])

        def one_document(s, e):
            fill = -(e - s) % self.pad_to
            return self._jit["mla"](
                p, jnp.pad(y[s:e], ((0, fill), (0, 0))))[:e - s]

        mixed = [one_document(s, e) for s, e in bounds if e > s]
        x = self.act(x + jnp.concatenate(mixed))
        y = self.rms(x, p["norm2"])
        cuts = range(0, x.shape[0], step)
        if number <= self.a["first_k_dense_replace"]:
            out = jnp.concatenate([self._jit["swiglu"](
                y[i:i + step], p["w_gu"], p["w_down"]) for i in cuts])
            return self.act(x + out), None, None
        parts = [self._jit["moe"](p, y[i:i + step]) for i in cuts]
        out, chosen, margin = (jnp.concatenate([part[j] for part in parts])
                               for j in range(3))
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
        step = head_block or max(total, 1)
        with jax.default_matmul_precision("highest"):
            x = self.act(self.w(params["embed"])[ids])
            chosen, margin = {}, {}
            for number in range(1, self.a["num_hidden_layers"] + 1):
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
