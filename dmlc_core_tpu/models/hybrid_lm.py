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
the ``kimi_linear``, the ``deepseek_v3``, the ``afmoe``, the ``mellum`` or
the ``brumby`` spelling (``canonical`` maps the others onto the first, the
one the class reads; a key the file leaves out means the plain block below),
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
* an architecture with ``layer_types`` and no ``kv_lora_rank`` (``afmoe``,
  ``mellum``) uses grouped-query attention at every layer:
  ``num_attention_heads`` query heads on ``num_key_value_heads`` key heads
  of ``head_dim``; a ``sliding_attention`` layer sees the last
  ``sliding_window`` keys of the document, the query's own among them, a
  ``full_attention`` layer the whole document.  A layer rotates all
  ``head_dim`` columns by the token's position in its document (the halves
  paired, ``(x_i, x_{i + d/2})``) as its kind's entry of ``rope_parameters``
  says — plain RoPE, frequencies ``rope_theta^(-2i/d)``, or YaRN's blend
  with cos and sin times ``attention_factor`` — and without that group
  (``afmoe``) a sliding layer by plain RoPE at ``rope_theta`` and a full
  layer not at all.  A file in ``afmoe``'s spelling (``AFMOE_BLOCK``) also
  norms each head's columns of ``q`` and of ``k`` (one weight vector each),
  gates the output a channel, ``o * sigmoid(W_g x)``, before ``W_o``, and
  norms every sublayer's *output* before it joins the residual
  (``x + post_norm(f(norm(x)))``); ``mup_enabled`` scales the embedding
  row by ``sqrt(hidden_size)``;
* a ``power_retention`` layer (``brumby``: Qwen3's block, QK-norm kept,
  ``RETENTION_BLOCK``) retains in place of attending (``ops.power_retention``).
  With ``x`` the layer's normed input, query head ``h`` on key head
  ``g = h // (H / H_kv)``: ``q_h = RoPE(RMSNorm_d(W_q,h x))``,
  ``k_g = RoPE(RMSNorm_d(W_k,g x))`` (plain RoPE at ``rope_theta``, the
  halves paired, at the position in the document), ``v_g = W_v,g x``; a
  gate a key head ``log gamma_t = logsigmoid(decay_up x_t + decay_rate)``
  and ``Gamma_tj = prod_{l=j+1..t} gamma_l`` inside one document;
  ``o_t = sum_{j<=t} Gamma_tj (q_t . k_j)^2 v_j / (sum_{j<=t} Gamma_tj
  (q_t . k_j)^2 + eps)``, the same as the state form ``S_t = gamma_t S_{t-1}
  + phi(k_t) v_t^T``, ``z_t = gamma_t z_{t-1} + phi(k_t)``, ``o_t =
  phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` with ``phi`` the symmetric
  square; then ``W_o [o_1 .. o_H]``;
* the first ``first_k_dense_replace`` (``num_dense_layers``, the leading
  ``dense`` entries of ``mlp_layer_types``; every layer where the file names
  no ``num_experts``) layers have a dense SwiGLU, the rest a mixture
  (``ops.moe``): sigmoid scores with a bias in the choice
  (``topk_method`` ``noaux_tc``), or, where the file names no scoring rule,
  the plain top ``k`` of the softmax over all experts (``greedy``), no bias;
  plus ``num_shared_experts`` shared experts (none where absent), weights
  times ``routed_scaling_factor`` (1 where absent), the choice limited to
  the ``topk_group`` best of ``num_expert_group`` groups where the
  architecture has groups.

``dtype`` (bfloat16 as published) is the type of parameters and
activations; router scores, group sums and the choice, the rotation,
softmax and log-softmax, RMSNorm statistics, the gates' sigmoid, the decay
(in log space), the recurrent state, retention's squares, numerator and
denominator and every accumulation are float32.  The
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
from ..ops.power_retention import needed_flops, power_retention_counted

__all__ = ["HybridMoELM", "canonical", "doc_scores", "load_arch"]

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
# the grouped-query block's parts that ``afmoe``'s code has and its
# config.json names no key for: a file in its spelling has all three
AFMOE_BLOCK = {"attention_output_gate": True, "qk_norm": True,
               "sublayer_post_norm": True}
# the layer kinds of ``layer_types``: windowed and whole-document softmax
# attention, and power retention (``brumby``: Qwen3's block, QK-norm kept)
KINDS = ("sliding_attention", "full_attention", "power_retention")
RETENTION_BLOCK = {"qk_norm": True}
# a router's scores -> the choice they go with: a bias beside sigmoid
# scores (``noaux_tc``), the plain top k of a softmax (``greedy``)
ROUTERS = {"sigmoid": "noaux_tc", "softmax": "greedy"}


def load_arch(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _leading_dense(arch: dict) -> int:
    """How many layers ``mlp_layer_types`` makes dense: its leading
    ``dense`` entries, every later one ``sparse``."""
    layers = int(arch["num_hidden_layers"])
    kinds = list(arch["mlp_layer_types"])[:layers]
    dense = next((n for n, kind in enumerate(kinds) if kind != "dense"),
                 len(kinds))
    if len(kinds) < layers or set(kinds[dense:]) - {"sparse"}:
        raise ValueError(f"hybrid_moe_lm computes mlp_layer_types of leading "
                         f"'dense' layers and 'sparse' ones after them, one "
                         f"a layer for {layers} layers; the architecture says "
                         f"{kinds}")
    return dense


def canonical(arch: dict) -> dict:
    """``arch`` with every key of ``SPELLINGS`` and ``SPELLINGS_AFMOE``
    under the name the class reads, ``mlp_layer_types`` as
    ``first_k_dense_replace`` and, in a file in ``afmoe``'s spelling, the
    ``AFMOE_BLOCK`` keys it does not give (``RETENTION_BLOCK``'s in a file
    with ``power_retention`` layers); a file that gives two spellings has
    to give them alike."""
    a = dict(arch)
    if set(SPELLINGS_AFMOE.values()) & set(a):
        for key, value in AFMOE_BLOCK.items():
            a.setdefault(key, value)
    if KINDS[2] in a.get("layer_types", ()):
        for key, value in RETENTION_BLOCK.items():
            a.setdefault(key, value)
    spelt = [*SPELLINGS.items(), *SPELLINGS_AFMOE.items()]
    if "mlp_layer_types" in a:
        a["mlp_layer_types"] = _leading_dense(a)
        spelt.append(("first_k_dense_replace", "mlp_layer_types"))
    for ours, theirs in spelt:
        if theirs not in a:
            continue
        value = a.pop(theirs)
        if a.setdefault(ours, value) != value:
            raise ValueError(f"the architecture says {ours}={a[ours]!r} and "
                             f"{theirs}={value!r}")
    return a


def _yarn(d_rope: int, theta: float, scaling) -> Tuple[list, float]:
    """(YaRN's ``d_rope / 2`` frequencies, its ``factor``): the plain ones
    blended towards ``1/factor`` of themselves on a ramp from the column
    that turns ``beta_fast`` times over the original context (floor) to
    the one that turns ``beta_slow`` times (ceil), as transformers'
    ``_compute_yarn_parameters`` has it."""
    half = d_rope // 2
    freqs = [theta ** (-2.0 * i / d_rope) for i in range(half)]
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
    return [f * (1.0 - r) + f / factor * r for f, r in zip(freqs, ramp)], \
        factor


def rope_frequencies(d_rope: int, theta: float, scaling) -> Tuple[list, float]:
    """(the ``d_rope / 2`` rotation frequencies, ``mscale``): plain RoPE
    without ``scaling``, YaRN's blend and softmax factor with it."""
    if not scaling:
        half = d_rope // 2
        return [theta ** (-2.0 * i / d_rope) for i in range(half)], 1.0
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind != "yarn":
        raise ValueError(f"hybrid_moe_lm computes rope_scaling of type "
                         f"'yarn' only, the architecture says {kind!r}")
    freqs, factor = _yarn(d_rope, theta, scaling)

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0

    both = mscale(float(scaling.get("mscale", 1))) \
        / mscale(float(scaling.get("mscale_all_dim", 0)))
    if abs(both - 1.0) > 1e-12:
        raise ValueError("hybrid_moe_lm computes rope_scaling with mscale = "
                         "mscale_all_dim only (cos and sin unscaled)")
    return freqs, mscale(float(scaling.get("mscale_all_dim", 0)))


def rope_of_kind(d: int, params: dict) -> Tuple[list, float]:
    """One kind of layer's entry of transformers' ``rope_parameters``: (the
    ``d / 2`` frequencies, the factor cos and sin are scaled by) — plain
    RoPE for ``rope_type`` ``default``, YaRN's blend for ``yarn`` with
    ``attention_factor`` (``0.1 ln(factor) + 1`` where absent)."""
    kind = params.get("rope_type", "default")
    theta = float(params["rope_theta"])
    if kind == "default":
        return rope_frequencies(d, theta, None)
    if kind != "yarn" or {"mscale", "mscale_all_dim"} & set(params):
        raise ValueError(f"hybrid_moe_lm computes rope_parameters of type "
                         f"'default' or 'yarn' (no mscale) only, the "
                         f"architecture says {params!r}")
    freqs, factor = _yarn(d, theta, params)
    return freqs, float(params.get("attention_factor",
                                   0.1 * math.log(factor) + 1.0))


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


def doc_scores(hid: jax.Array, batch: Dict[str, jax.Array], logits_of):
    """(scores ``[batch_rows]`` float32, ``{"tokens", "documents"}``): each
    document's mean log-probability of its next tokens from the final
    hidden states ``hid [T, H]``; ``logits_of(h [HEAD_BLOCK, H])`` gives a
    block's float32 logits over the vocabulary rows, so the ``[T, V]``
    logits exist one block of tokens at a time.  The head of every scorer
    in ``models``."""
    seg, row_ptr = batch["segments"], batch["row_ptr"]
    rows = row_ptr.shape[0] - 1
    t = seg.shape[0]
    with jax.named_scope("lm_head"):
        target = jnp.roll(batch["ids"], -1)
        pad = -t % HEAD_BLOCK
        blocks = lambda a: jnp.pad(                               # noqa: E731
            a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
                (t + pad) // HEAD_BLOCK, HEAD_BLOCK, *a.shape[1:])

        def block_logp(args):
            h_b, target_b = args
            logits = logits_of(h_b)
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
    return scores, {"tokens": row_ptr[rows], "documents": jnp.sum(n > 0)}


class HybridMoELM:
    """Registered as ``hybrid_moe_lm``; see the module text."""

    def __init__(self, arch: dict):
        a = canonical(arch)
        # no scoring rule: the plain top k of a softmax; a named one keeps
        # the choice it always had here, a bias beside its scores
        named = "moe_router_activation_func" in a
        self.scoring = a.get("moe_router_activation_func", "softmax")
        if self.scoring not in ROUTERS:
            raise ValueError(f"hybrid_moe_lm computes router scores of "
                             f"{sorted(ROUTERS)} only, the architecture says "
                             f"{self.scoring!r}")
        choice = a.get("topk_method", "noaux_tc" if named else "greedy")
        if choice != ROUTERS[self.scoring]:
            raise ValueError(f"hybrid_moe_lm computes {self.scoring} scores "
                             f"with topk_method={ROUTERS[self.scoring]!r} "
                             f"only, the architecture says {choice!r}")
        need = {"moe_renormalize": True, "tie_word_embeddings": False,
                "attention_bias": False, "hidden_act": "silu",
                "moe_layer_freq": 1}
        for key, want in need.items():
            if a.get(key, want) != want:
                raise ValueError(f"hybrid_moe_lm computes {key}={want!r} "
                                 f"only, the architecture says {a[key]!r}")
        # the bias that joins the scores in the choice
        self.router_bias = choice == "noaux_tc"
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
        self.retention_layers: set = set()
        theta = float(a.get("rope_theta", 10000.0))
        # what the layers outside ``kda_layers`` mix tokens with
        self.attention = "gqa" if "layer_types" in a \
            and "kv_lora_rank" not in a else "mla"
        # the grouped-query block's parts that only some files have
        self.gate = bool(a.get("attention_output_gate"))
        self.qk_norm = bool(a.get("qk_norm"))
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
        self.sandwich = bool(a.get("sublayer_post_norm"))
        self.embed_scale = self.hidden ** 0.5 if a.get("mup_enabled") else 1.0
        self.dense_width = int(a["intermediate_size"])
        # an architecture without experts is dense at every layer
        self.dense_layers = int(a["first_k_dense_replace"]) \
            if "num_experts" in a else self.layers
        if self.dense_layers < self.layers:
            self._init_experts(a)
        self.vocab = int(a.get("vocab_rows", a["vocab_size"]))

    def _init_experts(self, a: dict) -> None:
        self.expert_width = int(a["moe_intermediate_size"])
        self.experts = int(a["num_experts"])
        self.top_k = int(a["num_experts_per_token"])
        self.shared = int(a.get("num_shared_experts", 0))
        self.route_scale = float(a.get("routed_scaling_factor", 1.0))
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

    def _init_gqa(self, a: dict, theta: float) -> None:
        self.kv_heads = int(a["num_key_value_heads"])
        self.head_dim = int(a["head_dim"])
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads on {self.kv_heads} "
                             f"key heads are no whole groups")
        types = list(a["layer_types"])[:self.layers]
        if len(types) < self.layers or set(types) - set(KINDS):
            raise ValueError(f"layer_types has to say one of {KINDS} for "
                             f"each of the {self.layers} layers, it says "
                             f"{types}")
        self.sliding = {n for n, kind in enumerate(types, 1)
                        if kind == KINDS[0]}
        self.retention_layers = {n for n, kind in enumerate(types, 1)
                                 if kind == KINDS[2]}
        if self.sliding or a.get("sliding_window") is not None:
            self.window = int(a["sliding_window"])
            if self.window < 1:
                raise ValueError(f"sliding_window {self.window}")
        if a.get("rope_scaling"):
            raise ValueError("hybrid_moe_lm computes rope_scaling=None only "
                             "under grouped-query attention, the "
                             f"architecture says {a['rope_scaling']!r}")
        # a kind of layer -> (frequencies, the factor on cos and sin, the
        # scope a trace reads), or None where that kind rotates nothing
        by_kind = a.get("rope_parameters")
        if by_kind is None:
            self.rotations = {KINDS[0]: (*rope_frequencies(
                self.head_dim, theta, None), "gqa/rope"), KINDS[1]: None}
            if self.retention_layers:      # plain RoPE at rope_theta
                self.rotations[KINDS[2]] = (*rope_frequencies(
                    self.head_dim, theta, None), "retention/qk_norm_rope")
        else:
            if set(types) - set(by_kind):
                raise ValueError(f"rope_parameters has to give each of "
                                 f"{sorted(set(types))} its own entry, it "
                                 f"gives {sorted(by_kind)}")
            self.rotations = {kind: (*rope_of_kind(self.head_dim, rope), (
                "retention/qk_norm_rope" if kind == KINDS[2]
                else "gqa/rope_yarn" if rope.get("rope_type") == "yarn"
                else "gqa/rope")) for kind, rope in by_kind.items()
                if kind in types}
        self.yarn_layers = sum(turn is not None and turn[2] == "gqa/rope_yarn"
                               for turn in map(self.rotations.get, types))
        self.attn_scale = self.head_dim ** -0.5

    def mixer(self, layer: int) -> str:
        if layer in self.kda_layers:
            return "kda"
        return "retention" if layer in self.retention_layers \
            else self.attention

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
            s.update(wq=(h, n), wk=(h, nkv), wv=(h, nkv), wo=(n, h))
            if self.gate:
                s.update(w_gate=(h, n))
            if self.qk_norm:
                s.update(q_head_norm=(d,), k_head_norm=(d,))
            if self.mixer(layer) == "retention":
                # the gate's logit a key head: decay_up x + decay_rate
                s.update(decay_up=(h, self.kv_heads),
                         decay_rate=(self.kv_heads,))
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
            s.update(router=(h, self.experts), e_gu=(g, h, 2 * w),
                     e_down=(g, w, h))
            if self.router_bias:
                s.update(router_bias=(self.experts,))
            if self.shared:
                s.update(s_gu=(h, 2 * w * self.shared),
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

    def _turn(self, pos, freqs, factor=1.0, scope="mla/rope"):
        """(cos, sin) ``[T, len(freqs)]`` of every token's rotation by
        ``freqs``, both times ``factor``, computed under ``scope``."""
        with jax.named_scope(scope):
            angle = pos.astype(F32)[:, None] * jnp.asarray(
                freqs, F32)[None, :]
            cos, sin = jnp.cos(angle), jnp.sin(angle)
            if factor != 1.0:
                cos, sin = cos * factor, sin * factor
            return cos, sin

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

    def _heads(self, p, x, scope):
        """The grouped-query projections under ``<scope>/project``: ``q [T,
        H, d]``, ``k, v [T, H_kv, d]`` and the output gate's logits (None
        where the block has no gate)."""
        t = x.shape[0]
        nh, nkv, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope(f"{scope}/project"):
            q = _mm(x, p["wq"]).reshape(t, nh, d)
            k = _mm(x, p["wk"]).reshape(t, nkv, d)
            v = _mm(x, p["wv"]).reshape(t, nkv, d)
            gate = _mm(x, p["w_gate"]) if self.gate else None
        return q, k, v, gate

    def _qk_norm(self, p, q, k):
        """Each head's columns of ``q`` and of ``k`` through RMSNorm, one
        weight vector each, where the block norms them."""
        if not self.qk_norm:
            return q, k
        return (_rms(q, p["q_head_norm"], self.eps),
                _rms(k, p["k_head_norm"], self.eps))

    def _gqa(self, p, x, seg, first_key, turn):
        """``first_key [T]`` the first key each token sees in this layer;
        ``turn`` (cos, sin, scope) of the layer's kind, None on a layer that
        rotates nothing."""
        t = x.shape[0]
        nh, d = self.heads, self.head_dim
        q, k, v, gate = self._heads(p, x, "gqa")
        with jax.named_scope("gqa/qk_norm"):
            q, k = self._qk_norm(p, q, k)
            if turn is None:
                q = (q.astype(F32) * self.attn_scale).astype(x.dtype)
        if turn is not None:
            with jax.named_scope(turn[2]):
                cos, sin = turn[0][:, None], turn[1][:, None]
                q = (_rotate_halves(q, cos, sin)
                     * self.attn_scale).astype(x.dtype)
                k = _rotate_halves(k, cos, sin).astype(x.dtype)
        with jax.named_scope("gqa/attention"):
            o, fused = doc_causal_attention_counted(q, k, v, seg, first_key)
        o = o.reshape(t, nh * d)
        if self.gate:
            with jax.named_scope("gqa/gate"):
                o = o * jax.nn.sigmoid(gate.astype(F32))
        with jax.named_scope("gqa/out"):
            return _mm(o.astype(x.dtype), p["wo"]), fused

    def _retention(self, p, x, seg, turn):
        """Power retention in the grouped-query block's place: ``q`` and
        ``k`` normed a head and turned by ``turn`` (cos, sin, scope), the
        gate ``log gamma = logsigmoid(decay_up x + decay_rate)`` a key head,
        then ``ops.power_retention``.  Returns ``(out, fused, flops_done)``."""
        t = x.shape[0]
        q, k, v, _ = self._heads(p, x, "retention")
        with jax.named_scope(turn[2]):
            q, k = self._qk_norm(p, q, k)
            cos, sin = turn[0][:, None], turn[1][:, None]
            q = _rotate_halves(q, cos, sin).astype(x.dtype)
            k = _rotate_halves(k, cos, sin).astype(x.dtype)
        with jax.named_scope("retention/gate"):
            log_g = jax.nn.log_sigmoid(
                jnp.dot(x, p["decay_up"], preferred_element_type=F32)
                + p["decay_rate"].astype(F32))
        with jax.named_scope("retention/kernel"):
            o, fused, done = power_retention_counted(q, k, v, log_g, seg)
        with jax.named_scope("retention/out"):
            o = o.astype(x.dtype).reshape(t, self.heads * self.head_dim)
            return _mm(o, p["wo"]), fused, done

    def _post(self, p, name, out):
        """A sublayer's output as it joins the residual: through its own
        RMSNorm where the architecture has one."""
        if not self.sandwich:
            return out
        with jax.named_scope("post_norm"):
            return _rms(out, p[name], self.eps)

    def _moe(self, p, x, live):
        with jax.named_scope("moe/router"):
            chosen, weights = route(x, p["router"], p.get("router_bias"),
                                    self.top_k, self.route_scale,
                                    self.groups, self.groups_kept,
                                    self.scoring)
        routed, counters = held_experts_sum(
            x, chosen, weights, live, p["e_gu"], p["e_down"], self.held,
            self.experts)
        if not self.shared:
            return routed, counters, chosen
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
        if self.attention == "gqa":     # each kind of layer its own
            turns = {kind: rot and (*self._turn(pos, *rot), rot[2])
                     for kind, rot in self.rotations.items()}
        else:
            turn = self.rope_freqs and self._turn(pos, self.rope_freqs)
        with jax.named_scope("lm_embed"):
            x = params["embed"][ids]
            if self.embed_scale != 1.0:
                x = (x.astype(F32) * self.embed_scale).astype(x.dtype)
        counters = {"kda.fused_layers": jnp.int32(0),
                    "mla.fused_layers": jnp.int32(0),
                    "gqa.fused_layers": jnp.int32(0),
                    "moe.fused_combines": jnp.int32(0)}
        if self.retention_layers:
            counters["retention.fused_layers"] = jnp.int32(0)
            needed = needed_flops(jnp.diff(row_ptr), self.heads,
                                  self.kv_heads, self.head_dim)
        if any(self.mixer(n) == "gqa" for n in range(1, self.layers + 1)):
            # the first key a token sees: on a full layer its document's
            # start, on a sliding one no more than a window back
            in_window = jnp.maximum(
                doc_start, jnp.arange(t, dtype=jnp.int32) - (self.window - 1))
            counters["attn.key_blocks_full"] = walk_blocks(doc_start)
            counters["attn.key_blocks_window"] = walk_blocks(in_window)
            if self.yarn_layers:
                counters["gqa.yarn_layers"] = jnp.int32(self.yarn_layers)
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
            elif mixer == "retention":
                out, fused, done = self._retention(
                    p, y, seg, turns["power_retention"])
                counters[name] = {"retention.flops_done": done,
                                  "retention.flops_needed": needed}
            elif layer in self.sliding:
                out, fused = self._gqa(p, y, seg, in_window,
                                       turns["sliding_attention"])
            else:
                out, fused = self._gqa(p, y, seg, doc_start,
                                       turns["full_attention"])
            counters[f"{mixer}.fused_layers"] += fused
            x = x + self._post(p, "post_norm1", out)
            y = _rms(x, p["norm2"], self.eps)
            if layer <= self.dense_layers:
                with jax.named_scope("dense_mlp"):
                    out = swiglu(y, p["w_gu"], p["w_down"])
            else:
                out, mine, choices[name] = self._moe(p, y, live)
                counters[name] = {**counters.get(name, {}), **mine}
                counters["moe.fused_combines"] += counters[name].pop(
                    "fused_combine")
                for key, total in (("gather_combine", "moe.gather_combines"),
                                   ("dispatch_turns", "moe.dispatch_turns")):
                    if key in counters[name]:
                        counters[total] = counters.get(total, 0) + \
                            counters[name].pop(key)
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
        their experts' rows by the combine kernel; where a layer holds
        every expert ``moe.gather_combines``, how many mixture layers
        summed their rows by token (``ops.moe.held_experts_sum``), and
        ``moe.dispatch_turns``, the turns their loops took in all; under
        grouped-query attention ``attn.key_blocks_full`` and
        ``attn.key_blocks_window``, the key blocks one full and one sliding
        layer's walk visits for this batch
        (``ops.doc_attention.walk_blocks``), and where layers rotate by
        YaRN ``gqa.yarn_layers``, how many; where layers retain,
        ``retention.fused_layers``, how many took the retention kernel, and
        per such layer ``retention.flops_done``, the operations its
        schedule executed (masked pairs and padding included), and
        ``retention.flops_needed``, those of each document's cheaper exact
        form (``ops.power_retention.needed_flops``)."""
        hid, counters, _ = self._hidden(params, batch)
        scores, counts = doc_scores(
            hid, batch, lambda h: jnp.dot(h, params["head"],
                                          preferred_element_type=F32))
        counters.update(counts)
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
