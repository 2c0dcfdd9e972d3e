"""Operations and bytes one forward of an ``afmoe``-type document scorer
*needs*, from the configuration's shapes and a batch's document lengths, by
``lm_work.py``'s rules.  Each function returns ``(flops, bytes)``; a
multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — grouped-
query attention's five products (``q`` and the output gate over ``H`` heads,
``k`` and ``v`` over ``H_kv``, the output), the dense MLP or (router + shared
experts + the **held** experts a token meets on average, ``k * held / E``:
0.5 here, not the 4 it chooses and not the 32 that are resident), the head
over the vocabulary rows held.  Beside them attention's two products over
the pairs of query and key a layer *may see* inside each document of ``n``
tokens — ``n (n + 1) / 2`` on a ``full_attention`` layer; on a
``sliding_attention`` layer with window ``W`` the same up to ``n = W`` and
``W (W + 1) / 2 + (n - W) W`` beyond — at ``d_qk + d_v`` multiply-adds a
query head: ``2 H (128 + 128)`` operations a pair here.  Not counted: norms,
activations, the rotation, softmax, the gate's sigmoid, the sort of the
dispatch, and anything an implementation adds (masked blocks, padding).

Bytes: every resident parameter once (each is read at least once a batch),
the token ids, the scores.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets, and resident parameters, by part."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    experts = cfg["num_experts"]
    lo, hi = cfg.get("held_experts", [0, experts])
    met = cfg["num_experts_per_tok"] * (hi - lo) / experts
    return {
        "gqa": 3 * h * nh * d + 2 * h * nkv * d,
        "dense": 3 * h * cfg["intermediate_size"],
        "moe_met": h * experts + expert * (cfg["num_shared_experts"] + met),
        "moe_resident": h * experts
        + expert * (cfg["num_shared_experts"] + hi - lo),
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def pairs(cfg: dict, lengths) -> float:
    """Pairs of query and key the layers may see, all layers of one forward
    over documents of ``lengths`` tokens."""
    w = cfg["sliding_window"]
    full = sum(n * (n + 1) // 2 for n in lengths)
    sliding = sum(n * (n + 1) // 2 if n <= w
                  else w * (w + 1) // 2 + (n - w) * w for n in lengths)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return float(sum(sliding if kind == "sliding_attention" else full
                     for kind in kinds))


def attention_pairs(cfg: dict, lengths, act_bytes: int = 2):
    """The attention kernel's own needed work of one forward: the two
    products over the pairs; ``q``, ``k``, ``v`` read once a layer in the
    activations' type and the float32 output written once."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    tokens = float(sum(lengths))
    layers = cfg["num_hidden_layers"]
    flops = pairs(cfg, lengths) * 2 * nh * 2 * d
    bytes_ = layers * tokens * d * ((nh + 2 * nkv) * act_bytes + nh * 4)
    return flops, bytes_


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["num_dense_layers"], layers)
    flops = 2 * tokens * s["head"] + layers * 2 * tokens * s["gqa"] \
        + attention_pairs(cfg, lengths)[0] \
        + 2 * tokens * (dense * s["dense"] + (layers - dense) * s["moe_met"])
    resident = 2 * s["head"] + layers * s["gqa"] + dense * s["dense"] \
        + (layers - dense) * s["moe_resident"]
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return flops, bytes_
