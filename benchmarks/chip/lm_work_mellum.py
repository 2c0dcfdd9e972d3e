"""Operations and bytes one forward of a ``mellum``-type document scorer
*needs*, from the configuration's shapes and a batch's document lengths, by
``lm_work.py``'s rules.  Each function returns ``(flops, bytes)``; a
multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — grouped-
query attention's four products (``q`` and the output over ``H`` heads,
``k`` and ``v`` over ``H_kv``), the router, the **held** experts a token
meets on average (``k * held / E``: all 8 it chooses here, every expert
being held), the head over the vocabulary rows held.  Beside them
attention's two products over the pairs of query and key a layer *may see*
inside each document (``lm_work_afmoe``'s rule: the whole causal triangle
on a ``full_attention`` layer, the window's band on a ``sliding_attention``
one).  Not counted: norms, activations, the rotation, softmax, the sort of
the dispatch, and anything an implementation adds (masked blocks, padding,
rows carried past the last assignment).

Bytes: every resident parameter once (each is read at least once a batch),
the token ids, the scores.
"""

from __future__ import annotations

from lm_work_afmoe import attention_pairs, pairs  # noqa: F401  (the rule)


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets, and resident parameters, by part."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    experts = cfg["num_experts"]
    lo, hi = cfg.get("held_experts", [0, experts])
    return {
        "gqa": 2 * h * nh * d + 2 * h * nkv * d,
        "router": h * experts,
        "expert": expert,
        "met": cfg["num_experts_per_tok"] * (hi - lo) / experts,
        "held": hi - lo,
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    layers = cfg["num_hidden_layers"]
    flops = 2 * tokens * s["head"] + attention_pairs(cfg, lengths)[0] \
        + layers * 2 * tokens * (s["gqa"] + s["router"]
                                 + s["met"] * s["expert"])
    resident = 2 * s["head"] + layers * (
        s["gqa"] + s["router"] + s["held"] * s["expert"])
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return flops, bytes_


def expert_products(cfg: dict, lengths, act_bytes: int = 2):
    """The grouped products' own needed work in one forward: each served
    row through its expert's three matrices; the held experts' weights read
    once a layer and each served row read in and written out once in the
    activations' type."""
    s = sizes(cfg)
    h = cfg["hidden_size"]
    rows = float(sum(lengths)) * s["met"]
    layers = cfg["num_hidden_layers"]
    flops = layers * 2 * rows * s["expert"]
    bytes_ = layers * (s["held"] * s["expert"] * act_bytes
                       + 2 * rows * h * act_bytes)
    return flops, bytes_


def combine_rows(cfg: dict, lengths, act_bytes: int = 2):
    """The combine's own needed work in one forward: each served row
    weighed and added once (a multiply and an add a column), read once in
    the experts' output type; each token's sum written once in the layer's
    type."""
    s = sizes(cfg)
    h = cfg["hidden_size"]
    tokens = float(sum(lengths))
    rows = tokens * s["met"]
    layers = cfg["num_hidden_layers"]
    return (layers * 2 * rows * h,
            layers * (rows + tokens) * h * act_bytes)
