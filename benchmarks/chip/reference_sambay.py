"""Plain reference of a SambaY-type document scorer (``model_type``
``phi4flash``: Mamba and windowed differential attention in the
self-decoder, one whole-document attention layer whose keys and values,
and one Mamba layer whose scan output, every later layer reads; Gated
Memory Units and cross-attention in the cross-decoder): the layer
equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision.  Nothing imported from the program; no blocking of
attention, no chunks of the scan, no kernels.

Layers are numbered from 0, ``L`` of them; ``LN`` is LayerNorm with weight
and bias (eps ``layer_norm_eps``); ``a`` a sublayer's normalised input:

* **In**: ``x = E[id]``.  **Layer**: ``x <- x + M_l(LN1(x))``, then
  ``x <- x + W_d(silu(g) * u)``, ``[g, u] = LN2(x) W_gu``.
* **Which mixer**: ``l < L/2``: Mamba (even), windowed attention (odd);
  ``l = L/2``: Mamba, its ``y`` (before the gate) kept as ``m``;
  ``l = L/2 + 1``: whole-document attention, its ``k, v`` kept;
  ``l > L/2 + 1``: a GMU (even), cross-attention (odd).
* **Mamba**: ``[xi, z] = a W_in``; ``xc_t = silu(b + sum_j w_j
  xi_{t-3+j})``, zeros before the document; ``[delta, B, C] = xc W_x``;
  ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; token by token
  (``lax.scan``) ``s_t = exp(dt_t A) s_{t-1} + (dt_t xc_t) B_t^T``, ``s = 0``
  at the document's start, ``y_t = s_t C_t + D xc_t``; ``M = (y *
  silu(z)) W_out``.
* **Differential attention**: ``[q, k, v] = a W_qkv + b`` (a cross layer:
  ``q = a W_q + b`` and the kept ``k, v``), ``H`` query heads on ``H_kv``
  of ``d = hidden / H``; pair ``j`` is query heads ``2j``, ``2j + 1``, group
  ``g = j // (H / H_kv)`` key heads ``2g``, ``2g + 1`` and ``V_g = [v_2g |
  v_2g+1]``; ``A^i = softmax(q^i k^i^T / sqrt(d))`` as one dense masked
  ``[n, n]`` matrix over the document (``0 <= t - s``, and ``< W`` on a
  windowed layer); ``lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_j =
  RMSNorm((A^1 - lambda A^2) V_g) * subln * (1 - lambda_init)``; ``M =
  [o_j] W_o + b_o``.
* **GMU**: ``M = (m * silu(a W_g)) W_o``.
* **Score** of a document of ``n`` tokens: ``1/(n-1) sum_{t<n} log
  softmax(LN_f(x_t) E^T)[x_{t+1}]``.

Departures from the published description, each forced by the cell:
every mixer runs one document at a time (documents never see each other);
on the chip the head's logits and the feed-forward sublayers are taken
``head_block`` tokens at a time and attention one pair at a time, so that
they fit, and a document is filled up with zero rows behind its last token
to a multiple of ``pad_to`` (every mixer is causal and the scan's state is
frozen past the last real token, so nothing a real token sees changes);
the numbers are the same.

``control`` puts the reference in the program's place with a fault planted:
``"fp8"`` rounds every weight, and the residual stream after every
sublayer, to an 8-bit float (e4m3), the nearest precision below the
configuration's bfloat16; ``"no_reset"`` hands a Mamba layer's state from
one document of the batch to the next instead of starting each at zero;
``"no_lambda"`` takes ``lambda = 0``: the first map alone.
"""

from __future__ import annotations

import math

import numpy as np

CONTROLS = (None, "fp8", "no_reset", "no_lambda")


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
        self.eps = arch["layer_norm_eps"]
        h = arch["hidden_size"]
        self.heads = arch["num_attention_heads"]
        self.kv_heads = arch["num_key_value_heads"]
        self.d = h // self.heads
        self.n_state = arch.get("mamba_d_state", 16)
        rank = arch.get("mamba_dt_rank", "auto")
        self.rank = math.ceil(h / 16) if rank == "auto" else int(rank)
        self._jit = {f: jax.jit(getattr(self, f))
                     for f in ("ln", "mamba", "gmu", "swiglu", "logp")}
        self._jit["attention"] = jax.jit(self.attention,
                                         static_argnames="window")

    def kind(self, layer: int) -> str:
        half = self.a["num_hidden_layers"] // 2
        if layer < half:
            return "mamba" if layer % 2 == 0 else "swa"
        if layer == half:
            return "memory"
        if layer == half + 1:
            return "full"
        return "gmu" if layer % 2 == 0 else "cross"

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

    def ln(self, x, w, b):
        _, jnp = _jnp()
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + self.eps) * self.w(w) + self.w(b)

    def swiglu(self, x, w_gu, w_down):
        jax, jnp = _jnp()
        gate, up = jnp.split(x @ self.w(w_gu), 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ self.w(w_down)

    def mamba(self, p, x, s0, n):
        """One document ``x [m, hidden]`` (its first ``n`` rows real) through
        a Mamba mixer from state ``s0 [d_inner, N]``.  Returns ``(M, y, the
        state after the last real token)``."""
        jax, jnp = _jnp()
        r, ns = self.rank, self.n_state
        xi, z = jnp.split(x @ self.w(p["w_in"]), 2, axis=-1)
        taps = self.w(p["conv"])
        k = taps.shape[0]
        padded = jnp.pad(xi, ((k - 1, 0), (0, 0)))
        conv = sum(taps[j] * padded[j:j + xi.shape[0]] for j in range(k))
        xc = jax.nn.silu(conv + self.w(p["conv_bias"]))
        delta, b, c = jnp.split(xc @ self.w(p["w_x"]), [r, r + ns], axis=-1)
        dt = jax.nn.softplus(delta @ self.w(p["w_dt"]) + self.w(p["dt_bias"]))
        a = -jnp.exp(self.w(p["A_log"]))
        live = jnp.arange(x.shape[0]) < n

        def step(s, inputs):
            dt_t, x_t, b_t, c_t, live_t = inputs
            s_new = jnp.exp(dt_t[:, None] * a) * s \
                + (dt_t * x_t)[:, None] * b_t[None, :]
            s = jnp.where(live_t, s_new, s)
            return s, s_new @ c_t

        s, y = jax.lax.scan(step, s0, (dt, xc, b, c, live))
        y = y + self.w(p["D"]) * xc
        return (y * jax.nn.silu(z)) @ self.w(p["wo"]), y, s

    def attention(self, p, x, kv, init, window: int):
        """One document ``x [n, hidden]`` through a differential attention
        mixer; ``kv`` the kept ``(k, v)`` on a cross layer, else None;
        ``init`` the layer's ``lambda_init``; ``window`` 0 for the whole
        document.  Returns ``(M, (k, v))``."""
        jax, jnp = _jnp()
        n, nh, nkv, d = x.shape[0], self.heads, self.kv_heads, self.d
        if kv is None:
            qkv = x @ self.w(p["w_qkv"]) + self.w(p["b_qkv"])
            q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
            k, v = k.reshape(n, nkv, d), v.reshape(n, nkv, d)
        else:
            q = x @ self.w(p["w_q"]) + self.w(p["b_q"])
            k, v = kv
        q = q.reshape(n, nh, d)
        lam = jnp.exp(jnp.sum(self.w(p["lambda_q1"]) * self.w(p["lambda_k1"]))) \
            - jnp.exp(jnp.sum(self.w(p["lambda_q2"])
                              * self.w(p["lambda_k2"]))) + init
        if self.control == "no_lambda":
            lam = 0.0
        behind = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
        seen = behind >= 0
        if window:
            seen = seen & (behind < window)
        per = (nh // 2) // (nkv // 2)

        def softmax(q_h, k_h):
            s = jnp.where(seen, (q_h @ k_h.T) / np.sqrt(d), -jnp.inf)
            return jax.nn.softmax(s, axis=-1)

        def pair(j):
            g = j // per
            vg = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
            a1 = softmax(q[:, 2 * j], k[:, 2 * g])
            a2 = softmax(q[:, 2 * j + 1], k[:, 2 * g + 1])
            o = (a1 - lam * a2) @ vg
            return o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + self.eps)

        o = jax.lax.map(pair, jnp.arange(nh // 2))            # [P, n, 2d]
        o = o * self.w(p["subln"]) * (1.0 - init)
        o = o.transpose(1, 0, 2).reshape(n, nh * d)
        return o @ self.w(p["wo"]) + self.w(p["bo"]), (k, v)

    def gmu(self, p, x, m):
        jax, _ = _jnp()
        return (m * jax.nn.silu(x @ self.w(p["w_g"]))) @ self.w(p["wo"])

    def logp(self, hid, embed, target):
        """``log softmax(hid @ E^T)[target]`` a token."""
        jax, jnp = _jnp()
        logits = hid @ self.w(embed).T
        return jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   target[:, None], 1)[:, 0]

    # -- the whole forward ------------------------------------------------
    def run(self, params, ids, row_ptr, positions=(), head_block=None):
        """Everything the comparison needs of one batch: ``scores`` (one a
        document) and ``logits`` at stream ``positions``; ``chosen`` and
        ``margin`` empty (no experts)."""
        jax, jnp = _jnp()
        a = self.a
        row_ptr = np.asarray(row_ptr, np.int64)
        bounds = [(s, e) for s, e in zip(row_ptr[:-1].tolist(),
                                         row_ptr[1:].tolist()) if e > s]
        total = int(row_ptr[-1])
        ids = jnp.asarray(np.asarray(ids)[:total])
        step = head_block or max(total, 1)
        di = a.get("mamba_expand", 2) * a["hidden_size"]
        with jax.default_matmul_precision("highest"):
            x = self.act(params["embed"][ids].astype(jnp.float32))
            memory, shared = {}, {}
            for layer in range(a["num_hidden_layers"]):
                p = params[f"layer_{layer:02d}"]
                kind = self.kind(layer)
                y = self._jit["ln"](x, p["norm1"], p["norm1_bias"])
                state = jnp.zeros((di, self.n_state), jnp.float32)
                mixed = []
                for s, e in bounds:
                    fill = -(e - s) % self.pad_to
                    doc = jnp.pad(y[s:e], ((0, fill), (0, 0)))
                    if kind in ("mamba", "memory"):
                        if self.control != "no_reset":
                            state = jnp.zeros_like(state)
                        out, m, state = self._jit["mamba"](p, doc, state,
                                                           e - s)
                        if kind == "memory":
                            memory[s] = m
                    elif kind == "gmu":
                        out = self._jit["gmu"](p, doc, memory[s])
                    else:
                        window = a["sliding_window"] if kind == "swa" else 0
                        out, kv = self._jit["attention"](
                            p, doc, shared.get(s) if kind == "cross" else None,
                            0.8 - 0.6 * math.exp(-0.3 * layer),
                            window=window)
                        if kind == "full":
                            shared[s] = kv
                    mixed.append(out[:e - s])
                x = self.act(x + jnp.concatenate(mixed))
                y = self._jit["ln"](x, p["norm2"], p["norm2_bias"])
                out = jnp.concatenate([self._jit["swiglu"](
                    y[i:i + step], p["w_gu"], p["w_down"])
                    for i in range(0, total, step)])
                x = self.act(x + out)
            hid = self._jit["ln"](x, params["final_norm"],
                                  params["final_norm_bias"])
            nxt = jnp.roll(ids, -1)
            logp = np.concatenate([np.asarray(self._jit["logp"](
                hid[i:i + step], params["embed"], nxt[i:i + step]))
                for i in range(0, total, step)]) if total else np.zeros(0)
            scores = np.array([logp[s:e - 1].mean() if e - s > 1 else 0.0
                               for s, e in zip(row_ptr[:-1].tolist(),
                                               row_ptr[1:].tolist())],
                              np.float64)
            logits = np.asarray(
                hid[jnp.asarray(list(positions), jnp.int32)]
                @ self.w(params["embed"]).T) if len(positions) else None
        return {"scores": scores, "logits": logits, "chosen": {},
                "margin": {}}
