"""Operations and bytes one forward of a SambaY-type document scorer
*needs*, from the configuration's shapes and a batch's document lengths,
by ``lm_work.py``'s rules.  Each function returns ``(flops, bytes)``; a
multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — a Mamba
layer's ``W_in``, ``W_x``, ``W_dt`` and ``W_out``; an attention layer's
``W_qkv`` (a cross layer's ``W_q``) and ``W_o``; a GMU's ``W_g`` and
``W_o``; every layer's SwiGLU; the tied head over the vocabulary rows.
Beside them differential attention's products over the pairs of query
and key a layer *may see* inside each document of ``n`` tokens — ``n (n +
1) / 2`` on a whole-document layer (the shared-KV layer and the cross
layers); on a windowed layer with window ``W`` the same up to ``n = W`` and
``W (W + 1) / 2 + (n - W) W`` beyond — at two maps of ``d`` (scores) and
``2 d`` (the value pair) multiply-adds a query-head pair: ``(H / 2) * 2 *
2 (d + 2 d)`` operations a visible pair, 15 360 at the published widths.
Not counted: norms, activations, the conv, softmax, lambda, the gates, and
the scan's own arithmetic (``scan_work``), which is vector work beside
the products.

Bytes: every resident parameter once (each is read at least once a
batch), the token ids, the scores.
"""

from __future__ import annotations

import math


def kinds(cfg: dict) -> list:
    """Each layer's mixer, as ``models/sambay_lm.py`` reads the file."""
    layers = cfg["num_hidden_layers"]
    half = layers // 2
    out = []
    for n in range(layers):
        if n < half:
            out.append("mamba" if n % 2 == 0 else "swa")
        elif n <= half + 1:
            out.append("mamba" if n == half else "full")
        else:
            out.append("gmu" if n % 2 == 0 else "cross")
    return out


def _widths(cfg: dict):
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    di = cfg.get("mamba_expand", 2) * h
    rank = cfg.get("mamba_dt_rank", math.ceil(h / 16))
    return h, d, di, rank, cfg.get("mamba_d_state", 16)


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets in one layer of each kind, and the
    head; ``resident`` everything a layer of that kind holds."""
    h, d, di, rank, n = _widths(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mlp = 3 * h * cfg["intermediate_size"]
    mamba = 2 * h * di + di * (rank + 2 * n) + rank * di + di * h
    attn = h * (nh + 2 * nkv) * d + nh * d * h
    cross = 2 * h * nh * d
    gmu = 2 * h * di
    norms = 4 * h
    return {
        "mlp": mlp, "mamba": mamba, "swa": attn, "full": attn,
        "cross": cross, "gmu": gmu,
        "resident": {
            "mamba": mlp + mamba + norms + di * (4 + 2 + n + 1),
            "swa": mlp + attn + norms + (nh + 2 * nkv) * d + h + 6 * d,
            "cross": mlp + cross + norms + nh * d + h + 6 * d,
            "gmu": mlp + gmu + norms},
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def pairs(cfg: dict, lengths) -> dict:
    """Pairs of query and key one windowed and one whole-document layer may
    see over documents of ``lengths`` tokens."""
    w = cfg["sliding_window"]
    return {
        "full": sum(n * (n + 1) // 2 for n in lengths),
        "window": sum(n * (n + 1) // 2 if n <= w
                      else w * (w + 1) // 2 + (n - w) * w for n in lengths)}


def attention_pairs(cfg: dict, lengths, act_bytes: int = 2):
    """The differential attention kernel's own needed work of one forward:
    both maps' two products over the visible pairs of every attention
    layer; ``q``, ``k``, ``v`` read once a layer in the activations' type
    (a cross layer reads the shared ones) and the float32 output written
    once."""
    _, d, _, _, _ = _widths(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    seen = pairs(cfg, lengths)
    layers = kinds(cfg)
    visible = sum(seen["window"] if k == "swa" else seen["full"]
                  for k in layers if k in ("swa", "full", "cross"))
    tokens = float(sum(lengths))
    count = sum(k in ("swa", "full", "cross") for k in layers)
    flops = visible * (nh // 2) * 2 * 2 * (d + 2 * d)
    bytes_ = count * tokens * ((nh + 2 * nkv) * d * act_bytes
                               + (nh // 2) * 2 * d * 4)
    return float(flops), float(bytes_)


def scan_work(cfg: dict, lengths, act_bytes: int = 2):
    """The selective-scan kernel's own needed work of one forward, every
    Mamba layer: per token, channel and state the decay's product and
    exponential, the state's multiply-add, the input's product and the
    output's multiply-add (seven operations), per token and channel ``dt
    x`` and ``D x`` (three); bytes: ``x`` read once in the activations'
    type, ``dt`` and ``B``, ``C`` once in float32, ``y`` written once in
    float32."""
    _, _, di, _, n = _widths(cfg)
    layers = sum(k == "mamba" for k in kinds(cfg))
    tokens = float(sum(lengths))
    flops = layers * tokens * di * (7 * n + 3)
    bytes_ = layers * tokens * (di * (act_bytes + 4 + 4) + 2 * n * 4)
    return float(flops), float(bytes_)


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    layers = kinds(cfg)
    met = sum(s[k] + s["mlp"] for k in layers)
    flops = 2 * tokens * (met + s["head"]) + attention_pairs(cfg, lengths)[0]
    resident = s["head"] + 2 * cfg["hidden_size"] + sum(
        s["resident"]["swa" if k == "full" else k] for k in layers)
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return float(flops), float(bytes_)
