"""Plain reference of a ``mellum``-type document scorer (grouped-query
attention, plain RoPE and a window on some layers, YaRN and the whole
document on the others, softmax routing over experts that are all sparse):
the layer equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision.  Nothing imported from the program; no blocking of
attention, no grouped keys, no grouped products, no sort of assignments.

Layers are numbered from 1; layer ``n`` is of type ``layer_types[n - 1]``.
RMSNorm (eps from the architecture), ``SwiGLU(x) = W_d (silu(W_g x) *
W_u x)``; ``H`` query heads on ``H_kv`` key heads of ``d = head_dim``; ``W``
the window; ``p`` a token's position in its own document, from 0:

* **In**: ``x = E[id]``.
* **Layer**: ``x <- x + Attn(N1(x))``; ``x <- x + MoE(N2(x))``.
* **Attention**: ``q_j = W_q,j a`` a query head, ``k_g = W_k,g a``,
  ``v_g = W_v,g a`` a key head, no bias.  Both turn by the layer kind's
  entry of ``rope_parameters``: ``R_p x = c (x cos + rotate_half(x) sin)``,
  the pairs ``(x_i, x_{i + d/2})`` turned by ``p f_i``.  ``default``:
  ``f_i = theta^(-2i/d)``, ``c = 1``.  ``yarn`` (transformers'
  ``_compute_yarn_parameters``): with ``r(n) = d ln(L / (2 pi n)) /
  (2 ln theta)`` the column that turns ``n`` times over the original
  context ``L``, ``lo = floor(r(beta_fast))``, ``hi = ceil(r(beta_slow))``
  and the ramp ``m_i = clip((i - lo) / (hi - lo), 0, 1)``,
  ``f_i = theta^(-2i/d) ((1 - m_i) + m_i / factor)``; ``c =
  attention_factor``, so the logits grow by ``c^2``.  Keys and values are
  repeated to ``H`` heads (head ``j`` reads ``g = j // (H / H_kv)``);
  ``softmax(q_j k_g^T d^-1/2)`` as one dense masked ``[n, n]`` matrix a head
  over the document's tokens ``s`` with ``0 <= t - s`` (full) and
  ``0 <= t - s < W`` (sliding: ``W`` keys, the query's own among them).
  Output ``W_o [o_1 .. o_H]``.
* **Router**: ``s = softmax(W_r x)`` over all ``E`` experts; the ``k`` of
  largest ``s``; weights ``s_e / sum_chosen s`` (``norm_topk_prob``).  A
  loop over the held experts, each run over every token and kept where it
  was chosen.  Experts outside ``held`` add nothing; no shared expert.
* **Score** of a document of ``n`` tokens:
  ``1/(n-1) sum_{t<n} log softmax(W_head RMSNorm(x_t))[x_{t+1}]`` over the
  ``vocab_rows`` columns held.

Departures from the published description, each forced by the cell:

* attention runs one document at a time (documents never see each other,
  and positions restart);
* only the first ``num_hidden_layers`` of ``layer_types`` are computed;
* ``held_experts`` and ``vocab_rows``, where the configuration gives them,
  cut experts and vocabulary to one holder's share;
* on the chip the head's logits and the feed-forward sublayers are taken
  ``head_block`` tokens at a time and attention one head at a time, so that
  they fit, and a document is filled up with zero rows behind its last
  token to a multiple of ``pad_to`` (attention is causal, so nothing a real
  token sees changes) so that eight lengths compile few programs; the
  numbers are the same.

``control`` puts the reference in the program's place with a fault planted:
``"fp8"`` rounds every weight, and the residual stream after every
sublayer, to an 8-bit float (e4m3), the nearest precision below the
configuration's bfloat16; ``"plain_rope_full"`` turns the full layers by
plain RoPE at their ``rope_theta`` in place of YaRN; ``"no_attention_factor"``
keeps YaRN's frequencies and leaves cos and sin unscaled;
``"sigmoid_router"`` scores experts by a sigmoid a logit in place of the
softmax; ``"no_window"`` lets a sliding layer see its whole document.
"""

from __future__ import annotations

import math

import numpy as np

CONTROLS = (None, "fp8", "plain_rope_full", "no_attention_factor",
            "sigmoid_router", "no_window")
KINDS = ("sliding_attention", "full_attention")


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def rope(d: int, params: dict):
    """(frequencies ``[d / 2]`` float64, the factor on cos and sin) of one
    kind's entry of ``rope_parameters``."""
    theta = float(params["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    kind = params.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    factor = float(params["factor"])
    context = float(params["original_max_position_embeddings"])

    def column(turns):
        return d * math.log(context / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(column(params.get("beta_fast", 32))), 0)
    hi = min(math.ceil(column(params.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / ((hi - lo) or 0.001), 0, 1)
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return plain * (1 - ramp) + plain / factor * ramp, float(scale)


class Reference:
    """The reference of one architecture (the configuration's file).
    Every method takes and returns float32 arrays."""

    def __init__(self, arch: dict, control: str | None = None,
                 pad_to: int = 1):
        if control not in CONTROLS:
            raise ValueError(f"control={control!r}")
        jax, _ = _jnp()
        layers = arch["num_hidden_layers"]
        if set(arch["mlp_layer_types"][:layers]) != {"sparse"}:
            raise ValueError("a mellum-type reference computes sparse "
                             "layers only")
        self.a = arch
        self.control = control
        self.pad_to = int(pad_to)
        self._jit = {f: jax.jit(getattr(self, f)) for f in ("moe", "logp")}
        self._jit["attention"] = jax.jit(self.attention,
                                         static_argnames="sliding")
        self.eps = arch["rms_norm_eps"]
        lo, hi = arch.get("held_experts", [0, arch["num_experts"]])
        self.held = (lo, hi)
        d = arch["head_dim"]
        by_kind = dict(arch["rope_parameters"])
        if control == "plain_rope_full":
            by_kind[KINDS[1]] = {"rope_type": "default",
                                 "rope_theta": by_kind[KINDS[1]]["rope_theta"]}
        self.turns = {kind: rope(d, by_kind[kind]) for kind in KINDS}
        if control == "no_attention_factor":
            self.turns = {k: (f, 1.0) for k, (f, _) in self.turns.items()}

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

    def rotate(self, x, positions, freqs, scale):
        """``x [n, heads, d]`` as ``scale (x cos + rotate_half(x) sin)``;
        ``positions [n]``, ``freqs [d / 2]``."""
        _, jnp = _jnp()
        angle = positions.astype(jnp.float32)[:, None] \
            * freqs.astype(jnp.float32)[None, :]
        cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None] * scale
        sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None] * scale
        half = x.shape[-1] // 2
        turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * cos + turned * sin

    def attention(self, p, x, freqs, scale, sliding: bool):
        """One document ``x [n, hidden]`` through the attention sublayer;
        the token at row ``i`` has position ``i``."""
        jax, jnp = _jnp()
        a = self.a
        n, nh, nkv = (x.shape[0], a["num_attention_heads"],
                      a["num_key_value_heads"])
        d = a["head_dim"]
        positions = jnp.arange(n)
        q = self.rotate((x @ self.w(p["wq"])).reshape(n, nh, d), positions,
                        freqs, scale)
        k = self.rotate((x @ self.w(p["wk"])).reshape(n, nkv, d), positions,
                        freqs, scale)
        v = (x @ self.w(p["wv"])).reshape(n, nkv, d)
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
        return o.transpose(1, 0, 2).reshape(n, nh * d) @ self.w(p["wo"])

    def logp(self, hid, head, target):
        """``log softmax(hid @ head)[target]`` a token."""
        jax, jnp = _jnp()
        return jnp.take_along_axis(jax.nn.log_softmax(hid @ head, axis=-1),
                                   target[:, None], 1)[:, 0]

    def moe(self, p, x):
        """(the held experts' part, chosen ``[T, k]``, the gap between the
        k-th and the (k+1)-th score ``[T]``)."""
        jax, jnp = _jnp()
        k = self.a["num_experts_per_tok"]
        logits = x @ self.w(p["router"])
        s = jax.nn.sigmoid(logits) if self.control == "sigmoid_router" \
            else jax.nn.softmax(logits, axis=-1)
        top, chosen = jax.lax.top_k(s, k + 1)
        chosen, margin = chosen[:, :k], top[:, k - 1] - top[:, k]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        weights = picked / picked.sum(-1, keepdims=True)

        def one_expert(e, y):
            w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            local = e - self.held[0]          # the holder stores its own
            return y + w_e[:, None] * self.swiglu(x, p["e_gu"][local],
                                                  p["e_down"][local])

        y = jax.lax.fori_loop(self.held[0], self.held[1], one_expert,
                              jnp.zeros_like(x))
        return y, chosen, margin

    # -- one layer over the packed stream ---------------------------------
    def layer(self, number: int, p, x, bounds, step: int):
        """``x [T, hidden]`` through layer ``number``; ``bounds`` the
        documents' ``(start, end)`` on the host; the mixture ``step``
        tokens at a time.  Returns ``(x, chosen, margin)``."""
        _, jnp = _jnp()
        kind = self.a["layer_types"][number - 1]
        freqs, scale = self.turns[kind]
        y = self.rms(x, p["norm1"])

        def one_document(s, e):
            fill = -(e - s) % self.pad_to
            return self._jit["attention"](
                p, jnp.pad(y[s:e], ((0, fill), (0, 0))),
                jnp.asarray(freqs, jnp.float32), jnp.float32(scale),
                sliding=kind == KINDS[0])[:e - s]

        mixed = [one_document(s, e) for s, e in bounds if e > s]
        x = self.act(x + jnp.concatenate(mixed))
        y = self.rms(x, p["norm2"])
        parts = [self._jit["moe"](p, y[i:i + step])
                 for i in range(0, x.shape[0], step)]
        out, chosen, margin = (jnp.concatenate([part[j] for part in parts])
                               for j in range(3))
        return self.act(x + out), chosen, margin

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
        with jax.default_matmul_precision("highest"):
            x = self.act(self.w(params["embed"])[ids])
            chosen, margin = {}, {}
            for number in range(1, a["num_hidden_layers"] + 1):
                name = f"layer_{number:02d}"
                x, chosen[name], margin[name] = self.layer(
                    number, params[name], x, bounds, step)
                chosen[name] = np.asarray(chosen[name])
                margin[name] = np.asarray(margin[name])
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
