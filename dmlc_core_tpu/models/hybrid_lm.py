"""A hybrid linear-attention / latent-attention / grouped-query-attention
mixture-of-experts language model that scores packed documents, forward
only.

The batch is the loader's own flat-CSR dict read another way: a row is a
**document**, ``ids[nnz_cap]`` are its **tokens in order** (repeats and
all), ``row_ptr`` / ``segments`` are the document boundaries of one packed
stream, ``vals`` and ``labels`` are ignored, and padding tokens
(``segments == batch_rows``) belong to no document.  ``forward`` returns one
score a row: the mean log-probability of each next token given the tokens
before it in the document, ``1/(n-1) sum_t log p(x_{t+1} | x_{<=t})``.

The architecture arrives as the model's published ``config.json`` keys, in
the ``kimi_linear``, the ``deepseek_v3`` or the ``afmoe`` spelling
(``canonical`` maps the others onto the first, the one the class reads),
plus two that say what this holder keeps of a layer shared between chips:
``held_experts = [lo, hi]`` and ``vocab_rows``.  Pre-norm residual blocks,
RMSNorm, untied embedding and head:

* layers in ``linear_attn_config.kda_layers`` (numbered from 1; none where
  the group is absent or the list empty) mix tokens with a gated delta
  rule with per-channel decay (``ops.kda``) behind a 4-tap causal depthwise
  convolution and SiLU, L2-normalised ``q``/``k``, a low-rank decay gate
  and a low-rank output gate (rank = the head size, as the published
  implementation has it);
* every other layer of an architecture with ``kv_lora_rank`` uses latent
  attention: keys and values expand from a
  normalised latent of ``kv_lora_rank``; the query is one matrix, or with
  ``q_lora_rank`` a normalised low-rank pair.  The ``qk_rope_head_dim``
  columns, the key's shared by all heads, are rotated by the token's
  position **in its document** — consecutive pairs, frequencies
  ``rope_theta^(-2i/d)``, under ``rope_scaling`` of type ``yarn`` blended
  towards ``1/factor`` of themselves below ``beta_fast`` rotations of the
  original context, the softmax scale times ``mscale^2`` — or, with
  ``mla_use_nope``, ride unrotated;
* an architecture with ``layer_types`` and no ``kv_lora_rank`` (``afmoe``)
  uses grouped-query attention at every layer: ``num_attention_heads``
  query heads on ``num_key_value_heads`` key heads of ``head_dim``, an
  RMSNorm over each head's columns of ``q`` and of ``k`` (one weight
  vector each), then on a ``sliding_attention`` layer the rotation of all
  ``head_dim`` columns by the token's position in its document (the halves
  paired, ``(x_i, x_{i + d/2})``, frequencies ``rope_theta^(-2i/d)``) and a
  view of the last ``sliding_window`` keys of the document, the query's
  own among them; on a ``full_attention`` layer no rotation and the whole
  document.  The output is gated a channel, ``o * sigmoid(W_g x)``, before
  ``W_o``.  Such an architecture also norms every sublayer's *output*
  before it joins the residual (``x + post_norm(f(norm(x)))``) and, with
  ``mup_enabled``, scales the embedding row by ``sqrt(hidden_size)``;
* the first ``first_k_dense_replace`` (``num_dense_layers``) layers have a
  dense SwiGLU, the rest a
  sigmoid-routed mixture plus shared experts (``ops.moe``), the choice
  limited to the ``topk_group`` best of ``num_expert_group`` groups where
  the architecture has groups.

``dtype`` (bfloat16 as published) is the type of parameters and
activations; router scores, group sums and the choice, the rotation,
softmax and log-softmax, RMSNorm statistics, the gates' sigmoid, the decay
(in log space), the recurrent state and every accumulation are float32.  The
``[T, vocab_rows]`` logits exist one block of tokens at a time.
"""

from __future__ import annotations

import json
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.doc_attention import doc_causal_attention_counted, walk_blocks
from ..ops.kda import kda_chunked_counted
from ..ops.moe import held_experts_sum, route, swiglu

__all__ = ["HybridMoELM", "canonical", "load_arch"]

Params = Dict[str, object]
F32 = jnp.float32
KDA_CHUNK = 64
HEAD_BLOCK = 1024


# what the class reads <- the same quantity as ``deepseek_v3`` spells it
SPELLINGS = {
    "num_experts": "n_routed_experts",
    "num_experts_per_token": "num_experts_per_tok",
    "num_shared_experts": "n_shared_experts",
    "moe_router_activation_func": "scoring_func",
    "moe_renormalize": "norm_topk_prob",
    "num_expert_group": "n_group",
}
# ... and as ``afmoe`` spells what the two above do not
SPELLINGS_AFMOE = {
    "first_k_dense_replace": "num_dense_layers",
    "routed_scaling_factor": "route_scale",
    "moe_router_activation_func": "score_func",
    "moe_renormalize": "route_norm",
    "num_expert_group": "num_expert_groups",
    "topk_group": "num_limited_groups",
}


def load_arch(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def canonical(arch: dict) -> dict:
    """``arch`` with every key of ``SPELLINGS`` and ``SPELLINGS_AFMOE``
    under the name the class reads; a file that gives two spellings has to
    give them alike."""
    a = dict(arch)
    for ours, theirs in (*SPELLINGS.items(), *SPELLINGS_AFMOE.items()):
        if theirs not in a:
            continue
        value = a.pop(theirs)
        if a.setdefault(ours, value) != value:
            raise ValueError(f"the architecture says {ours}={a[ours]!r} and "
                             f"{theirs}={value!r}")
    return a


def rope_frequencies(d_rope: int, theta: float, scaling) -> Tuple[list, float]:
    """(the ``d_rope / 2`` rotation frequencies, ``mscale``): plain RoPE
    without ``scaling``, YaRN's blend and softmax factor with it."""
    half = d_rope // 2
    freqs = [theta ** (-2.0 * i / d_rope) for i in range(half)]
    if not scaling:
        return freqs, 1.0
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind != "yarn":
        raise ValueError(f"hybrid_moe_lm computes rope_scaling of type "
                         f"'yarn' only, the architecture says {kind!r}")
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def turns_at(n):      # the column that makes n turns over the context
        return d_rope * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turns_at(float(scaling.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(turns_at(float(scaling.get("beta_slow", 1)))),
             d_rope - 1)
    width = (hi - lo) or 0.001
    ramp = [min(max((i - lo) / width, 0.0), 1.0) for i in range(half)]
    freqs = [f * (1.0 - r) + f / factor * r for f, r in zip(freqs, ramp)]

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0

    both = mscale(float(scaling.get("mscale", 1))) \
        / mscale(float(scaling.get("mscale_all_dim", 0)))
    if abs(both - 1.0) > 1e-12:
        raise ValueError("hybrid_moe_lm computes rope_scaling with mscale = "
                         "mscale_all_dim only (cos and sin unscaled)")
    return freqs, mscale(float(scaling.get("mscale_all_dim", 0)))


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The pairs ``(x_2i, x_2i+1)`` of the last axis turned by their angle,
    float32.  Comes back as ``[re_0 .. ; im_0 ..]``: queries and the key
    take the same order, and their product does not see it."""
    a, b = x[..., 0::2].astype(F32), x[..., 1::2].astype(F32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _rotate_halves(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The pairs ``(x_i, x_{i + d/2})`` of the last axis turned by their
    angle, float32, each where it was (``x cos + rotate_half(x) sin``)."""
    a, b = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)


def _doc_conv(x: jax.Array, taps: jax.Array, pos: jax.Array) -> jax.Array:
    """Causal depthwise convolution over the stream that sees zeros before
    a document's first token: ``y_t = sum_j taps[j] x_{t-(K-1)+j}``, a term
    kept only where that many tokens of the document lie behind ``t``."""
    kk = taps.shape[0]
    y = x.astype(F32) * taps[-1].astype(F32)
    for back in range(1, kk):
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:x.shape[0]]
        y = y + jnp.where((pos >= back)[:, None], shifted.astype(F32), 0.0) \
            * taps[kk - 1 - back].astype(F32)
    return y.astype(x.dtype)


class HybridMoELM:
    """Registered as ``hybrid_moe_lm``; see the module text."""

    def __init__(self, arch: dict):
        a = canonical(arch)
        need = {"moe_router_activation_func": "sigmoid",
                "moe_renormalize": True, "topk_method": "noaux_tc",
                "tie_word_embeddings": False, "attention_bias": False,
                "hidden_act": "silu", "moe_layer_freq": 1}
        for key, want in need.items():
            if a.get(key, want) != want:
                raise ValueError(f"hybrid_moe_lm computes {key}={want!r} "
                                 f"only, the architecture says {a[key]!r}")
        self.dtype = jnp.dtype(a.get("dtype", "bfloat16"))
        self.hidden = int(a["hidden_size"])
        self.layers = int(a["num_hidden_layers"])
        self.eps = float(a["rms_norm_eps"])
        lin = a.get("linear_attn_config") or {}
        self.kda_layers = {int(x) for x in lin.get("kda_layers", ())}
        if self.kda_layers:
            self.kda_heads = int(lin["num_heads"])
            self.kda_dim = int(lin["head_dim"])
            self.conv_taps = int(lin["short_conv_kernel_size"])
            self.gate_rank = self.kda_dim
        self.heads = int(a["num_attention_heads"])
        theta = float(a.get("rope_theta", 10000.0))
        # what the layers outside ``kda_layers`` mix tokens with
        self.attention = "gqa" if "layer_types" in a \
            and "kv_lora_rank" not in a else "mla"
        if self.attention == "gqa":
            self._init_gqa(a, theta)
        else:
            self.q_rank = a.get("q_lora_rank") and int(a["q_lora_rank"])
            self.kv_rank = int(a["kv_lora_rank"])
            self.d_nope = int(a["qk_nope_head_dim"])
            self.d_rope = int(a["qk_rope_head_dim"])
            self.d_v = int(a["v_head_dim"])
            self.rope_freqs, mscale = None, 1.0
            if not a.get("mla_use_nope", False):
                self.rope_freqs, mscale = rope_frequencies(
                    self.d_rope, theta, a.get("rope_scaling"))
            self.attn_scale = (self.d_nope + self.d_rope) ** -0.5 \
                * mscale ** 2
        # the norm on a sublayer's output and the embedding's multiplier
        self.sandwich = self.attention == "gqa"
        self.embed_scale = self.hidden ** 0.5 if a.get("mup_enabled") else 1.0
        self.dense_layers = int(a["first_k_dense_replace"])
        self.dense_width = int(a["intermediate_size"])
        self.expert_width = int(a["moe_intermediate_size"])
        self.experts = int(a["num_experts"])
        self.top_k = int(a["num_experts_per_token"])
        self.shared = int(a["num_shared_experts"])
        self.route_scale = float(a["routed_scaling_factor"])
        self.groups = int(a.get("num_expert_group", 1))
        self.groups_kept = int(a.get("topk_group", 1))
        per_group = self.experts // max(self.groups, 1)
        if (self.groups < 1 or per_group * self.groups != self.experts
                or not 1 <= self.groups_kept <= self.groups
                or self.groups_kept * per_group < self.top_k
                or (self.groups > 1 and per_group < 2)):
            raise ValueError(
                f"{self.experts} experts in {self.groups} groups of which "
                f"{self.groups_kept} are kept leave no choice of "
                f"{self.top_k}")
        lo, hi = a.get("held_experts", [0, self.experts])
        self.held: Tuple[int, int] = (int(lo), int(hi))
        if not 0 <= self.held[0] < self.held[1] <= self.experts:
            raise ValueError(f"held_experts {self.held} is no range of the "
                             f"{self.experts} experts")
        self.vocab = int(a.get("vocab_rows", a["vocab_size"]))

    def _init_gqa(self, a: dict, theta: float) -> None:
        self.kv_heads = int(a["num_key_value_heads"])
        self.head_dim = int(a["head_dim"])
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads on {self.kv_heads} "
                             f"key heads are no whole groups")
        types = list(a["layer_types"])[:self.layers]
        known = ("sliding_attention", "full_attention")
        if len(types) < self.layers or set(types) - set(known):
            raise ValueError(f"layer_types has to say one of {known} for "
                             f"each of the {self.layers} layers, it says "
                             f"{types}")
        self.sliding = {n for n, kind in enumerate(types, 1)
                        if kind == known[0]}
        self.window = int(a["sliding_window"])
        if self.window < 1:
            raise ValueError(f"sliding_window {self.window}")
        if a.get("rope_scaling"):
            raise ValueError("hybrid_moe_lm computes rope_scaling=None only "
                             "under grouped-query attention, the "
                             f"architecture says {a['rope_scaling']!r}")
        self.rope_freqs, _ = rope_frequencies(self.head_dim, theta, None)
        self.attn_scale = self.head_dim ** -0.5

    def mixer(self, layer: int) -> str:
        return "kda" if layer in self.kda_layers else self.attention

    # -- parameters -------------------------------------------------------
    def _layer_shapes(self, layer: int) -> Dict[str, tuple]:
        h = self.hidden
        s: Dict[str, tuple] = {"norm1": (h,), "norm2": (h,)}
        if self.mixer(layer) == "kda":
            n, r = self.kda_heads * self.kda_dim, self.gate_rank
            s.update(wq=(h, n), wk=(h, n), wv=(h, n),
                     conv_q=(self.conv_taps, n), conv_k=(self.conv_taps, n),
                     conv_v=(self.conv_taps, n),
                     decay_down=(h, r), decay_up=(r, n), decay_bias=(n,),
                     decay_rate=(self.kda_heads,),
                     w_beta=(h, self.kda_heads),
                     gate_down=(h, r), gate_up=(r, n),
                     out_norm=(self.kda_dim,), wo=(n, h))
        elif self.attention == "gqa":
            d = self.head_dim
            n, nkv = self.heads * d, self.kv_heads * d
            s.update(wq=(h, n), wk=(h, nkv), wv=(h, nkv), w_gate=(h, n),
                     q_head_norm=(d,), k_head_norm=(d,), wo=(n, h))
        else:
            dq = self.heads * (self.d_nope + self.d_rope)
            if self.q_rank:
                s.update(wq_a=(h, self.q_rank), q_norm=(self.q_rank,),
                         wq_b=(self.q_rank, dq))
            else:
                s.update(wq=(h, dq))
            s.update(wkv_a=(h, self.kv_rank + self.d_rope),
                     kv_norm=(self.kv_rank,),
                     wkv_b=(self.kv_rank,
                            self.heads * (self.d_nope + self.d_v)),
                     wo=(self.heads * self.d_v, h))
        if self.sandwich:
            s.update(post_norm1=(h,), post_norm2=(h,))
        if layer <= self.dense_layers:
            s.update(w_gu=(h, 2 * self.dense_width),
                     w_down=(self.dense_width, h))
        else:
            g, w = self.held[1] - self.held[0], self.expert_width
            s.update(router=(h, self.experts), router_bias=(self.experts,),
                     e_gu=(g, h, 2 * w), e_down=(g, w, h),
                     s_gu=(h, 2 * w * self.shared),
                     s_down=(w * self.shared, h))
        return s

    def shapes(self) -> Dict[str, object]:
        out: Dict[str, object] = {
            "embed": (self.vocab, self.hidden), "final_norm": (self.hidden,),
            "head": (self.hidden, self.vocab)}
        for layer in range(1, self.layers + 1):
            out[f"layer_{layer:02d}"] = self._layer_shapes(layer)
        return out

    def init(self, rng: jax.Array) -> Params:
        """Norm weights 1, ``router_bias`` 0, ``decay_rate`` (log of the
        per-head rate) 0, ``decay_bias`` -2; matrices ``N(0, 1/fan_in)``;
        the embedding ``N(0, 1)``."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.shapes(), is_leaf=lambda x: isinstance(x, tuple))
        keys = jax.random.split(rng, len(flat))
        leaves = []
        for key, (path, shape) in zip(keys, flat):
            name = path[-1].key
            if "norm" in name:
                leaf = jnp.ones(shape, F32)
            elif name in ("router_bias", "decay_rate"):
                leaf = jnp.zeros(shape, F32)
            elif name == "decay_bias":
                leaf = jnp.full(shape, -2.0, F32)
            elif name == "embed" or name.startswith("conv"):
                leaf = jax.random.normal(key, shape, F32)
            else:
                leaf = jax.random.normal(key, shape, F32) * shape[-2] ** -0.5
            leaves.append(leaf.astype(self.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # -- sublayers --------------------------------------------------------
    def _kda(self, p, x, seg, pos):
        t = x.shape[0]
        nh, d = self.kda_heads, self.kda_dim
        with jax.named_scope("kda/project"):
            q, k, v = _mm(x, p["wq"]), _mm(x, p["wk"]), _mm(x, p["wv"])
        with jax.named_scope("kda/conv"):
            heads = lambda y, taps: jax.nn.silu(                  # noqa: E731
                _doc_conv(y, taps, pos).astype(F32)).reshape(t, nh, d)
            q, k = heads(q, p["conv_q"]), heads(k, p["conv_k"])
            v = heads(v, p["conv_v"]).astype(x.dtype)
            unit = lambda y: y * jax.lax.rsqrt(                   # noqa: E731
                jnp.sum(y * y, -1, keepdims=True) + 1e-6)
            q = (unit(q) * d ** -0.5).astype(x.dtype)
            k = unit(k).astype(x.dtype)
        with jax.named_scope("kda/gates"):
            raw = jnp.dot(_mm(x, p["decay_down"]), p["decay_up"],
                          preferred_element_type=F32)
            rate = jnp.exp(p["decay_rate"].astype(F32))[None, :, None]
            g = -rate * jax.nn.softplus(
                raw + p["decay_bias"].astype(F32)).reshape(t, nh, d)
            beta = jax.nn.sigmoid(jnp.dot(x, p["w_beta"],
                                          preferred_element_type=F32))
            gate = jax.nn.sigmoid(jnp.dot(
                _mm(x, p["gate_down"]), p["gate_up"],
                preferred_element_type=F32)).reshape(t, nh, d)
        with jax.named_scope("kda/scan"):
            o, fused = kda_chunked_counted(q, k, v, g, beta, seg, KDA_CHUNK)
        with jax.named_scope("kda/out"):
            o = _rms(o, p["out_norm"], self.eps) * gate
            return _mm(o.astype(x.dtype).reshape(t, nh * d), p["wo"]), fused

    def _turn(self, pos):
        """(cos, sin) ``[T, d_rope / 2]`` of every token's rotation, or None
        where the architecture rotates nothing: the same for every layer
        that rotates."""
        if self.rope_freqs is None:
            return None
        with jax.named_scope(f"{self.attention}/rope"):
            angle = pos.astype(F32)[:, None] * jnp.asarray(
                self.rope_freqs, F32)[None, :]
            return jnp.cos(angle), jnp.sin(angle)

    def _mla(self, p, x, seg, doc_start, turn):
        t = x.shape[0]
        nh, dn, dr, dv = self.heads, self.d_nope, self.d_rope, self.d_v
        if self.q_rank:
            with jax.named_scope("mla/q_lora"):
                q = _mm(_rms(_mm(x, p["wq_a"]), p["q_norm"], self.eps),
                        p["wq_b"])
        with jax.named_scope("mla/project"):
            if not self.q_rank:
                q = _mm(x, p["wq"])
            q = q.reshape(t, nh, dn + dr)
            latent, k_shared = jnp.split(_mm(x, p["wkv_a"]),
                                         [self.kv_rank], axis=-1)
            kv = _mm(_rms(latent, p["kv_norm"], self.eps),
                     p["wkv_b"]).reshape(t, nh, dn + dv)
            if turn is None:
                q = (q.astype(F32) * self.attn_scale).astype(x.dtype)
        if turn is not None:
            with jax.named_scope("mla/rope"):
                cos, sin = turn
                q = (jnp.concatenate(
                    [q[..., :dn].astype(F32),
                     _rotate(q[..., dn:], cos[:, None], sin[:, None])], -1)
                    * self.attn_scale).astype(x.dtype)
                k_shared = _rotate(k_shared, cos, sin).astype(x.dtype)
        with jax.named_scope("mla/project"):
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_shared[:, None, :], (t, nh, dr))], -1)
            v = kv[..., dn:]
        with jax.named_scope("mla/attention"):
            o, fused = doc_causal_attention_counted(q, k, v, seg, doc_start)
        with jax.named_scope("mla/out"):
            return _mm(o.astype(x.dtype).reshape(t, nh * dv), p["wo"]), fused

    def _gqa(self, p, x, seg, first_key, turn):
        """``first_key [T]`` the first key each token sees in this layer;
        ``turn`` None on a layer that rotates nothing."""
        t = x.shape[0]
        nh, nkv, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("gqa/project"):
            q = _mm(x, p["wq"]).reshape(t, nh, d)
            k = _mm(x, p["wk"]).reshape(t, nkv, d)
            v = _mm(x, p["wv"]).reshape(t, nkv, d)
            gate = _mm(x, p["w_gate"])
        with jax.named_scope("gqa/qk_norm"):
            q = _rms(q, p["q_head_norm"], self.eps)
            k = _rms(k, p["k_head_norm"], self.eps)
            if turn is None:
                q = (q.astype(F32) * self.attn_scale).astype(x.dtype)
        if turn is not None:
            with jax.named_scope("gqa/rope"):
                cos, sin = turn[0][:, None], turn[1][:, None]
                q = (_rotate_halves(q, cos, sin)
                     * self.attn_scale).astype(x.dtype)
                k = _rotate_halves(k, cos, sin).astype(x.dtype)
        with jax.named_scope("gqa/attention"):
            o, fused = doc_causal_attention_counted(q, k, v, seg, first_key)
        with jax.named_scope("gqa/gate"):
            o = (o.reshape(t, nh * d)
                 * jax.nn.sigmoid(gate.astype(F32))).astype(x.dtype)
        with jax.named_scope("gqa/out"):
            return _mm(o, p["wo"]), fused

    def _post(self, p, name, out):
        """A sublayer's output as it joins the residual: through its own
        RMSNorm where the architecture has one."""
        if not self.sandwich:
            return out
        with jax.named_scope("post_norm"):
            return _rms(out, p[name], self.eps)

    def _moe(self, p, x, live):
        with jax.named_scope("moe/router"):
            chosen, weights = route(x, p["router"], p["router_bias"],
                                    self.top_k, self.route_scale,
                                    self.groups, self.groups_kept)
        routed, counters = held_experts_sum(
            x, chosen, weights, live, p["e_gu"], p["e_down"], self.held)
        with jax.named_scope("moe/shared"):
            shared = swiglu(x, p["s_gu"], p["s_down"])
        return routed + shared, counters, chosen

    # -- the whole forward ------------------------------------------------
    def _hidden(self, params, batch):
        """Final-norm hidden states ``[T, H]``, the per-layer counters and
        the experts each token chose in each mixture layer."""
        ids, seg, row_ptr = batch["ids"], batch["segments"], batch["row_ptr"]
        rows = row_ptr.shape[0] - 1
        t = ids.shape[0]
        live = seg < rows
        # a token's document start; padding is one document behind the last
        doc_start = row_ptr[jnp.minimum(seg, rows)]
        pos = jnp.arange(t, dtype=jnp.int32) - doc_start
        turn = self._turn(pos)
        with jax.named_scope("lm_embed"):
            x = params["embed"][ids]
            if self.embed_scale != 1.0:
                x = (x.astype(F32) * self.embed_scale).astype(x.dtype)
        counters = {"kda.fused_layers": jnp.int32(0),
                    "mla.fused_layers": jnp.int32(0),
                    "gqa.fused_layers": jnp.int32(0),
                    "moe.fused_combines": jnp.int32(0)}
        if self.attention == "gqa":
            # the first key a token sees: on a full layer its document's
            # start, on a sliding one no more than a window back
            in_window = jnp.maximum(
                doc_start, jnp.arange(t, dtype=jnp.int32) - (self.window - 1))
            counters["attn.key_blocks_full"] = walk_blocks(doc_start)
            counters["attn.key_blocks_window"] = walk_blocks(in_window)
        choices = {}
        for layer in range(1, self.layers + 1):
            name = f"layer_{layer:02d}"
            p = params[name]
            y = _rms(x, p["norm1"], self.eps)
            mixer = self.mixer(layer)
            if mixer == "kda":
                out, fused = self._kda(p, y, seg, pos)
            elif mixer == "mla":
                out, fused = self._mla(p, y, seg, doc_start, turn)
            elif layer in self.sliding:
                out, fused = self._gqa(p, y, seg, in_window, turn)
            else:
                out, fused = self._gqa(p, y, seg, doc_start, None)
            counters[f"{mixer}.fused_layers"] += fused
            x = x + self._post(p, "post_norm1", out)
            y = _rms(x, p["norm2"], self.eps)
            if layer <= self.dense_layers:
                with jax.named_scope("dense_mlp"):
                    out = swiglu(y, p["w_gu"], p["w_down"])
            else:
                out, counters[name], choices[name] = self._moe(p, y, live)
                counters["moe.fused_combines"] += counters[name].pop(
                    "fused_combine")
            x = x + self._post(p, "post_norm2", out)
        return _rms(x, params["final_norm"], self.eps), counters, choices

    def forward_counted(self, params: Params, batch: Dict[str, jax.Array]):
        """(scores ``[batch_rows]`` float32, counters): per mixture layer
        the assignments that reached held experts, the largest and the mean
        load of a held expert, the live tokens none of whose experts is
        held and the rows the dispatch gathered for them; the batch's
        tokens and documents; ``kda.fused_layers``, ``mla.fused_layers`` and
        ``gqa.fused_layers``, how many KDA layers of this program took the
        chunk kernel and how many layers of either attention the attention
        kernel, and ``moe.fused_combines``, how many mixture layers added
        their experts' rows by the combine kernel; under grouped-query
        attention ``attn.key_blocks_full`` and ``attn.key_blocks_window``,
        the key blocks one full and one sliding layer's walk visits for this
        batch (``ops.doc_attention.walk_blocks``)."""
        seg, row_ptr = batch["segments"], batch["row_ptr"]
        rows = row_ptr.shape[0] - 1
        t = seg.shape[0]
        hid, counters, _ = self._hidden(params, batch)
        with jax.named_scope("lm_head"):
            target = jnp.roll(batch["ids"], -1)
            pad = -t % HEAD_BLOCK
            blocks = lambda a: jnp.pad(                           # noqa: E731
                a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
                    (t + pad) // HEAD_BLOCK, HEAD_BLOCK, *a.shape[1:])

            def block_logp(args):
                h_b, target_b = args
                logits = jnp.dot(h_b, params["head"],
                                 preferred_element_type=F32)
                picked = jnp.take_along_axis(logits, target_b[:, None], 1)
                return picked[:, 0] - jax.nn.logsumexp(logits, axis=-1)

            logp = jax.lax.map(block_logp, (blocks(hid), blocks(target)))
            logp = logp.reshape(-1)[:t]
            # token t predicts t+1 where both lie in one document
            counted = (seg == jnp.roll(seg, -1)) & (seg < rows)
            counted = counted.at[-1].set(False)
            total = jax.ops.segment_sum(jnp.where(counted, logp, 0.0), seg,
                                        num_segments=rows + 1)[:rows]
            n = jnp.diff(row_ptr)
            scores = total / jnp.maximum(n - 1, 1).astype(F32)
        counters["tokens"] = row_ptr[rows]
        counters["documents"] = jnp.sum(n > 0)
        return scores, counters

    def forward(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        return self.forward_counted(params, batch)[0]

    def probe(self, params: Params, batch: Dict[str, jax.Array],
              positions: jax.Array):
        """For checks, not for speed: the logits ``[P, vocab_rows]`` float32
        at stream ``positions`` and each mixture layer's chosen experts
        ``{layer: [T, k]}``, through the same sublayers as ``forward``."""
        hid, _, choices = self._hidden(params, batch)
        logits = jnp.dot(hid[positions], params["head"],
                         preferred_element_type=F32)
        return logits, choices

    @staticmethod
    def counter_record(counters) -> dict:
        """One batch's counters, fetched to the host, as one flat record:
        what the scoring loop adds to the span ring as an ``lm.batch``
        event."""
        rec = {}
        for name, value in jax.device_get(counters).items():
            if isinstance(value, dict):
                rec.update({f"{name}.{k}": float(v)
                            for k, v in value.items()})
            else:
                rec[name] = float(value)
        return rec
