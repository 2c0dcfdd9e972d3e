"""Operations and bytes one forward of the document scorer *needs*, from the
configuration's shapes and a batch's document lengths.  Each function
returns ``(flops, bytes)``; a multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — the
attention or KDA projections, the dense MLP or (router + shared expert +
the **held** experts a token meets on average, ``k * held / E``: 4 here,
not the 8 it chooses and not the 128 that are resident), the head over the
vocabulary rows held.  Beside them the mixers' own work: the gated delta
rule's state update and read-out as the recurrence states it (decay,
``k^T S``, the rank-one update, ``S^T q``: ``7 d_k d_v`` a token and head),
and attention's two products over the causal pairs *inside* each document
(``n (n + 1) / 2`` pairs of ``d_qk + d_v`` multiply-adds a head).  Not
counted: norms, activations, the convolution, softmax, the sort of the
dispatch, and anything an implementation adds (a chunk's inverse, masked
blocks, padding).

Bytes: every resident parameter once (each is read at least once a batch),
the token ids, the scores.
"""

from __future__ import annotations


def layer_plan(cfg: dict):
    """[(mixer, ffn)] for layers 1..num_hidden_layers."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [("kda" if n in kda else "mla",
             "dense" if n <= cfg["first_k_dense_replace"] else "moe")
            for n in range(1, cfg["num_hidden_layers"] + 1)]


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets, and resident parameters, by part."""
    h = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    n, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    kda = 3 * h * n + 2 * (h * r + r * n) + h * lin["num_heads"] + n * h
    nh = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = (h * nh * dqk + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
           + nh * cfg["v_head_dim"] * h)
    expert = 3 * h * cfg["moe_intermediate_size"]
    lo, hi = cfg.get("held_experts", [0, cfg["num_experts"]])
    met = cfg["num_experts_per_token"] * (hi - lo) / cfg["num_experts"]
    return {
        "kda": kda, "mla": mla, "dense": 3 * h * cfg["intermediate_size"],
        "moe_met": h * cfg["num_experts"]
        + expert * (cfg["num_shared_experts"] + met),
        "moe_resident": h * cfg["num_experts"]
        + expert * (cfg["num_shared_experts"] + hi - lo),
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    pairs = float(sum(n * (n + 1) // 2 for n in lengths))
    lin = cfg["linear_attn_config"]
    state = 7 * lin["num_heads"] * lin["head_dim"] ** 2
    attend = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    flops, resident = 2 * tokens * s["head"], 2 * s["head"]
    for mixer, ffn in layer_plan(cfg):
        flops += 2 * tokens * s[mixer]
        flops += tokens * state if mixer == "kda" else pairs * attend
        flops += 2 * tokens * (s["dense"] if ffn == "dense"
                               else s["moe_met"])
        resident += s[mixer] + (s["dense"] if ffn == "dense"
                                else s["moe_resident"])
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return flops, bytes_
