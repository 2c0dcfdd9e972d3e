"""A decoder-hybrid-decoder (SambaY, arXiv:2507.06607; ``model_type``
``phi4flash``) that scores packed documents, forward only.

The batch, its reading and the score are ``models.hybrid_lm``'s: a row is a
document, ``ids`` its tokens in order, and ``forward`` returns each
document's mean log-probability of its next tokens.  The architecture
arrives as the model's published ``config.json`` keys plus ``vocab_rows``
and the Mamba sizes the published file leaves to the code's defaults
(``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_expand`` 2,
``mamba_dt_rank`` ``ceil(hidden / 16)``).

Layers are numbered from 0, ``L`` of them, ``L / 2`` even.  Every layer is
pre-norm with LayerNorm (weight and bias): ``h = x + M_l(LN1(x))``, then
``x' = h + W_2(silu(g) * u)`` with ``[g, u] = LN2(h) W_1``; at the end
``LN_f`` and the tied embedding as the head (``logits = x E^T``).  No
positions anywhere.  The mixer ``M_l``:

* **self-decoder** (``l < L / 2``): Mamba on even layers, differential
  attention over the last ``sliding_window`` keys of the document (the
  query's own among them) on odd ones;
* ``l = L / 2``: Mamba whose scan output *before* its gate is kept as the
  memory ``m [T, d_inner]``;
* ``l = L / 2 + 1``: differential attention over the whole document, whose
  keys and values are kept as the shared KV;
* **cross-decoder** (``l >= L / 2 + 2``): a Gated Memory Unit on even
  layers, ``M = (m * silu(a W_g)) W_o``; on odd ones differential attention
  of the layer's own query over the shared keys and values, whole
  document.

**Mamba** (Mamba-1): ``[xi, z] = a W_in``; ``xc = silu(conv4(xi) + b)``, a
causal depthwise convolution that sees zeros before the document
(``hybrid_lm._doc_conv``); ``[delta, B, C] = xc W_x``; ``dt =
softplus(delta W_dt + b_dt)``; the selective scan with ``A = -exp(A_log)``
and ``D`` (``ops.selective_scan``), its state zero before each document;
``M = (y * silu(z)) W_out``.

**Differential attention** (arXiv:2410.05258): ``[q, k, v] = a W_qkv + b``
(a cross layer computes ``q`` only), ``H`` query heads on ``H_kv`` key
heads of ``d = hidden / H``, the heads taken in interleaved pairs (query
heads ``2j, 2j + 1`` are pair ``j``'s two maps, key heads ``2g, 2g + 1`` group
``g``'s, ``V_g = [v_2g | v_2g+1]``); ``lambda = exp(l_q1 . l_k1) - exp(l_q2
. l_k2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``;
``o_j = RMSNorm_2d((A1_j - lambda A2_j) V_g) (1 - lambda_init)``
(``ops.doc_attention.doc_diff_attention_counted``); ``M = [o_j] W_o + b_o``.

``dtype`` (bfloat16 as served) is the type of parameters and activations;
the scan's state, the step, every exponential, the softmax, lambda, every
norm's statistics, the log-softmax and every accumulation are float32.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..ops.doc_attention import doc_diff_attention_counted, walk_blocks
from ..ops.moe import swiglu
from ..ops.selective_scan import selective_scan_counted
from .hybrid_lm import HybridMoELM, _doc_conv, _mm, doc_scores, load_arch

__all__ = ["SambaYLM", "load_arch"]

Params = Dict[str, object]
F32 = jnp.float32


def _ln(x: jax.Array, w: jax.Array, b: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with float32 statistics, out in ``x``'s type."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(F32) + b.astype(F32)).astype(x.dtype)


def _affine(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    return (jnp.dot(x, w, preferred_element_type=F32)
            + b.astype(F32)).astype(x.dtype)


class SambaYLM:
    """Registered as ``sambay_lm``; see the module text."""

    def __init__(self, arch: dict):
        a = dict(arch)
        need = {"tie_word_embeddings": True, "hidden_act": "silu",
                "mb_per_layer": 2, "mlp_bias": False, "lm_head_bias": False}
        for key, want in need.items():
            if a.get(key, want) != want:
                raise ValueError(f"sambay_lm computes {key}={want!r} only, "
                                 f"the architecture says {a[key]!r}")
        self.dtype = jnp.dtype(a.get("dtype", "bfloat16"))
        self.hidden = h = int(a["hidden_size"])
        self.layers = int(a["num_hidden_layers"])
        if self.layers % 4:
            raise ValueError(f"sambay_lm needs the self-decoder's half of "
                             f"{self.layers} layers to end on a Mamba "
                             f"layer: a multiple of 4")
        self.eps = float(a["layer_norm_eps"])
        self.heads = int(a["num_attention_heads"])
        self.kv_heads = int(a["num_key_value_heads"])
        self.head_dim = h // self.heads
        if (self.heads % 2 or self.kv_heads % 2
                or self.heads % self.kv_heads):
            raise ValueError(f"{self.heads} query heads on {self.kv_heads} "
                             f"key heads are no whole pairs and groups")
        self.window = int(a["sliding_window"])
        self.dense_width = int(a["intermediate_size"])
        self.d_state = int(a.get("mamba_d_state", 16))
        self.d_conv = int(a.get("mamba_d_conv", 4))
        self.d_inner = int(a.get("mamba_expand", 2)) * h
        rank = a.get("mamba_dt_rank", "auto")
        self.dt_rank = math.ceil(h / 16) if rank == "auto" else int(rank)
        self.vocab = int(a.get("vocab_rows", a["vocab_size"]))

    def kind(self, layer: int) -> str:
        """``mamba``, ``swa``, ``memory`` (Mamba whose scan output is the
        memory), ``full`` (attention whose keys and values are shared),
        ``gmu`` or ``cross``; ``layer`` from 0."""
        half = self.layers // 2
        if layer < half:
            return "mamba" if layer % 2 == 0 else "swa"
        if layer == half:
            return "memory"
        if layer == half + 1:
            return "full"
        return "gmu" if layer % 2 == 0 else "cross"

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)

    # -- parameters -------------------------------------------------------
    def _layer_shapes(self, layer: int) -> Dict[str, tuple]:
        h, di, d = self.hidden, self.d_inner, self.head_dim
        s: Dict[str, tuple] = {
            "norm1": (h,), "norm1_bias": (h,), "norm2": (h,),
            "norm2_bias": (h,), "w_gu": (h, 2 * self.dense_width),
            "w_down": (self.dense_width, h)}
        kind = self.kind(layer)
        if kind in ("mamba", "memory"):
            s.update(w_in=(h, 2 * di), conv=(self.d_conv, di),
                     conv_bias=(di,),
                     w_x=(di, self.dt_rank + 2 * self.d_state),
                     w_dt=(self.dt_rank, di), dt_bias=(di,),
                     A_log=(di, self.d_state), D=(di,), wo=(di, h))
        elif kind == "gmu":
            s.update(w_g=(h, di), wo=(di, h))
        else:
            nq = self.heads * d
            if kind == "cross":
                s.update(w_q=(h, nq), b_q=(nq,))
            else:
                nqkv = (self.heads + 2 * self.kv_heads) * d
                s.update(w_qkv=(h, nqkv), b_qkv=(nqkv,))
            s.update(lambda_q1=(d,), lambda_k1=(d,), lambda_q2=(d,),
                     lambda_k2=(d,), subln=(2 * d,), wo=(nq, h), bo=(h,))
        return s

    def shapes(self) -> Dict[str, object]:
        out: Dict[str, object] = {"embed": (self.vocab, self.hidden),
                                  "final_norm": (self.hidden,),
                                  "final_norm_bias": (self.hidden,)}
        for layer in range(self.layers):
            out[f"layer_{layer:02d}"] = self._layer_shapes(layer)
        return out

    def init(self, rng: jax.Array) -> Params:
        """Norm weights 1 and every bias 0; ``A_log[c, n] = log(n + 1)``,
        ``D`` 1, ``dt_bias`` the ``softplus^-1`` of 0.01; ``lambda_*``
        ``N(0, 0.1^2)``; matrices and conv taps ``N(0, 1/fan_in)``; the
        embedding, which is also the head, ``N(0, 1/hidden)``."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes(), is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(rng, len(flat))
        leaves = []
        for key, (path, shape) in zip(keys, flat):
            name = path[-1].key
            x = jax.random.normal(key, shape, F32)
            if name.endswith("bias") or name in ("b_q", "b_qkv", "bo"):
                leaf = (jnp.full(shape, math.log(math.expm1(0.01)), F32)
                        if name == "dt_bias" else jnp.zeros(shape, F32))
            elif "norm" in name or name in ("subln", "D"):
                leaf = jnp.ones(shape, F32)
            elif name == "A_log":
                leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=F32)), shape)
            elif name.startswith("lambda"):
                leaf = 0.1 * x
            elif name == "embed":
                leaf = x * self.hidden ** -0.5
            else:
                leaf = x * shape[-2] ** -0.5
            leaves.append(leaf.astype(self.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- sublayers --------------------------------------------------------
    def _mamba(self, p, a, seg, pos):
        """(``M``, the scan's output before its gate, fused)."""
        n, r = self.d_state, self.dt_rank
        with jax.named_scope("mamba/in_proj"):
            xi, z = jnp.split(_mm(a, p["w_in"]), 2, axis=-1)
        with jax.named_scope("mamba/conv"):
            xc = jax.nn.silu(_doc_conv(xi, p["conv"], pos).astype(F32)
                             + p["conv_bias"].astype(F32)).astype(a.dtype)
        with jax.named_scope("mamba/x_proj"):
            delta, b, c = jnp.split(
                jnp.dot(xc, p["w_x"], preferred_element_type=F32),
                [r, r + n], axis=-1)
        with jax.named_scope("mamba/dt"):
            dt = jax.nn.softplus(
                jnp.dot(delta.astype(a.dtype), p["w_dt"],
                        preferred_element_type=F32)
                + p["dt_bias"].astype(F32))
        with jax.named_scope("mamba/scan"):
            y, fused = selective_scan_counted(
                xc, dt, -jnp.exp(p["A_log"].astype(F32)), b, c, p["D"], seg)
        with jax.named_scope("mamba/gate"):
            gated = (y * jax.nn.silu(z.astype(F32))).astype(a.dtype)
        with jax.named_scope("mamba/out"):
            return _mm(gated, p["wo"]), y.astype(a.dtype), fused

    def _attention(self, p, a, layer, seg, first_key, shared):
        """(``M``, this layer's ``(k, v)``, fused); ``shared`` the keys and
        values a cross layer reads, None elsewhere."""
        t = a.shape[0]
        nh, nkv, d = self.heads, self.kv_heads, self.head_dim
        scope = "cross" if shared is not None else "dattn"
        with jax.named_scope(f"{scope}/project"):
            if shared is None:
                qkv = _affine(a, p["w_qkv"], p["b_qkv"])
                q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
                k, v = k.reshape(t, nkv, d), v.reshape(t, nkv, d)
            else:
                q = _affine(a, p["w_q"], p["b_q"])
                k, v = shared
            q = (q.astype(F32) * d ** -0.5).astype(a.dtype).reshape(t, nh, d)
            dot = lambda x, y: jnp.sum(x.astype(F32) * y.astype(F32))  # noqa: E731
            init = self.lambda_init(layer)
            lam = (jnp.exp(dot(p["lambda_q1"], p["lambda_k1"]))
                   - jnp.exp(dot(p["lambda_q2"], p["lambda_k2"])) + init)
        with jax.named_scope(f"{scope}/attention"):
            o, fused = doc_diff_attention_counted(q, k, v, lam, seg,
                                                  first_key)
        with jax.named_scope(f"{scope}/combine"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + self.eps)
            o = (o * (p["subln"].astype(F32) * (1.0 - init))).astype(a.dtype)
        with jax.named_scope(f"{scope}/out"):
            return _affine(o.reshape(t, nh * d), p["wo"], p["bo"]), (k, v), \
                fused

    def _gmu(self, p, a, memory):
        with jax.named_scope("gmu/gate"):
            gated = (memory.astype(F32) * jax.nn.silu(jnp.dot(
                a, p["w_g"], preferred_element_type=F32))).astype(a.dtype)
        with jax.named_scope("gmu/out"):
            return _mm(gated, p["wo"])

    # -- the whole forward ------------------------------------------------
    def _hidden(self, params, batch):
        """Final-norm hidden states ``[T, H]`` and the counters."""
        ids, seg, row_ptr = batch["ids"], batch["segments"], batch["row_ptr"]
        rows = row_ptr.shape[0] - 1
        t = ids.shape[0]
        # a token's document start; padding is one document behind the last
        doc_start = row_ptr[jnp.minimum(seg, rows)]
        stream = jnp.arange(t, dtype=jnp.int32)
        pos = stream - doc_start
        in_window = jnp.maximum(doc_start, stream - (self.window - 1))
        with jax.named_scope("lm_embed"):
            x = params["embed"][ids]
        counters = {"ssm.fused_layers": jnp.int32(0),
                    "dattn.fused_layers": jnp.int32(0),
                    "attn.key_blocks_full": walk_blocks(doc_start),
                    "attn.key_blocks_window": walk_blocks(in_window)}
        memory = shared = None
        for layer in range(self.layers):
            p = params[f"layer_{layer:02d}"]
            kind = self.kind(layer)
            with jax.named_scope("layer_norm"):
                a = _ln(x, p["norm1"], p["norm1_bias"], self.eps)
            if kind in ("mamba", "memory"):
                out, y, fused = self._mamba(p, a, seg, pos)
                counters["ssm.fused_layers"] += fused
                if kind == "memory":
                    memory = y
            elif kind == "gmu":
                out = self._gmu(p, a, memory)
            else:
                first = in_window if kind == "swa" else doc_start
                out, kv, fused = self._attention(
                    p, a, layer, seg, first,
                    shared if kind == "cross" else None)
                counters["dattn.fused_layers"] += fused
                if kind == "full":
                    shared = kv
            x = x + out
            with jax.named_scope("layer_norm"):
                a = _ln(x, p["norm2"], p["norm2_bias"], self.eps)
            with jax.named_scope("dense_mlp"):
                x = x + swiglu(a, p["w_gu"], p["w_down"])
        with jax.named_scope("layer_norm"):
            hid = _ln(x, params["final_norm"], params["final_norm_bias"],
                      self.eps)
        return hid, counters

    def forward_counted(self, params: Params, batch: Dict[str, jax.Array]):
        """(scores ``[batch_rows]`` float32, counters): ``ssm.fused_layers``
        and ``dattn.fused_layers``, how many Mamba layers took the scan
        kernel and how many attention layers the differential kernel;
        ``attn.key_blocks_full`` and ``attn.key_blocks_window``, the key
        blocks a whole-document and a windowed layer's walk visit for this
        batch (``ops.doc_attention.walk_blocks``); the batch's tokens and
        documents."""
        hid, counters = self._hidden(params, batch)
        scores, counts = doc_scores(hid, batch, lambda h: jax.lax.dot_general(
            h, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=F32))
        counters.update(counts)
        return scores, counters

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return self.forward_counted(params, batch)[0]

    def probe(self, params: Params, batch: Dict[str, jax.Array],
              positions: jax.Array):
        """For checks, not for speed: the logits ``[P, vocab_rows]`` float32
        at stream ``positions`` through the same layers as ``forward``, and
        no routing choices (``{}``)."""
        hid, _ = self._hidden(params, batch)
        logits = jax.lax.dot_general(hid[positions], params["embed"],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=F32)
        return logits, {}

    counter_record = staticmethod(HybridMoELM.counter_record)
