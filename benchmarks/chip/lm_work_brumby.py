"""Operations and bytes one forward of a ``brumby``-type document scorer
*needs*, from the configuration's shapes and a batch's document lengths, by
``lm_work.py``'s rules.  Each function returns ``(flops, bytes)``; a
multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — the four
projections of the retention block (``q`` and the output over ``H`` heads,
``k`` and ``v`` over ``H_kv``), the gate's ``[hidden, H_kv]``, the dense
SwiGLU's three matrices, the head over the vocabulary rows held.  Beside
them retention itself (``retention_work``): per document of ``L`` tokens
and key head the cheaper exact form, the pairs ``G L (L + 1) / 2`` of
``4 d + 2`` operations each (``q . k``, the square and the decay, the
product with ``v``, the normaliser's sum) or the state, ``L (G + 1)``
products of ``2 D d + 2 D`` with ``D = d (d + 1) / 2`` (each key into the
state and the sum of keys, each of the ``G`` query heads out of them),
``phi``'s own products left out.  Not counted: norms, activations, the
rotation, and anything an implementation adds (masked pairs, padding, a
state form where the pairs cost less, features past ``D``).

Bytes: every resident parameter once (each is read at least once a batch;
the norms left out), the token ids, the scores; for retention alone
``q, k, v`` and the output once each in bfloat16 and ``log gamma`` once in
float32.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets, by part."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "retention": 2 * h * nh * d + 2 * h * nkv * d,
        "gate": h * nkv,
        "mlp": 3 * h * cfg["intermediate_size"],
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def pairs_and_state(cfg: dict, length: int):
    """(the pairs form's, the state form's) operations for one document of
    ``length`` tokens and one key head."""
    d = cfg["head_dim"]
    g = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    dd = d * (d + 1) // 2
    n = float(length)
    return g * n * (n + 1) / 2 * (4 * d + 2), n * (g + 1) * (2 * dd * d
                                                             + 2 * dd)


def retention_work(cfg: dict, lengths, act_bytes: int = 2):
    """Retention's own needed work in one forward, every layer."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    flops = layers * nkv * sum(min(pairs_and_state(cfg, n))
                               for n in lengths)
    tokens = float(sum(lengths))
    bytes_ = layers * tokens * ((2 * nh + 2 * nkv) * d * act_bytes + 4 * nkv)
    return flops, bytes_


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    layers = cfg["num_hidden_layers"]
    per_layer = s["retention"] + s["gate"] + s["mlp"]
    flops = 2 * tokens * s["head"] + layers * 2 * tokens * per_layer \
        + retention_work(cfg, lengths)[0]
    resident = 2 * s["head"] + layers * per_layer
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return flops, bytes_
