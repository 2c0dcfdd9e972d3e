"""Operations and bytes one forward of a ``deepseek_v3``-type document
scorer *needs*, from the configuration's shapes and a batch's document
lengths, by ``lm_work.py``'s rules.  Each function returns
``(flops, bytes)``; a multiply-add is two operations.

Counted per token: every matrix the token meets, twice its size — latent
attention's five products (the low-rank query's two, the latent's two, the
output), the dense MLP or (router + shared experts + the **held** experts a
token meets on average, ``k * held / E``: 0.5 here, not the 8 it chooses and
not the 16 that are resident), the head over the vocabulary rows held.
Beside them attention's two products over the causal pairs *inside* each
document (``n (n + 1) / 2`` pairs of ``d_qk + d_v`` multiply-adds a head:
``2 H (192 + 192)`` operations a pair here).  Not counted: norms,
activations, the rotation, softmax, the group step and the sort of the
dispatch, and anything an implementation adds (masked blocks, padding).

Bytes: every resident parameter once (each is read at least once a batch),
the token ids, the scores.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """Matrix parameters a token meets, and resident parameters, by part."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    q = (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * dqk
         if cfg.get("q_lora_rank") else h * nh * dqk)
    mla = (q + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
           + nh * cfg["v_head_dim"] * h)
    expert = 3 * h * cfg["moe_intermediate_size"]
    experts = cfg["n_routed_experts"]
    lo, hi = cfg.get("held_experts", [0, experts])
    met = cfg["num_experts_per_tok"] * (hi - lo) / experts
    return {
        "mla": mla, "dense": 3 * h * cfg["intermediate_size"],
        "moe_met": h * experts + expert * (cfg["n_shared_experts"] + met),
        "moe_resident": h * experts
        + expert * (cfg["n_shared_experts"] + hi - lo),
        "head": h * cfg.get("vocab_rows", cfg["vocab_size"]),
    }


def lm_forward(cfg: dict, lengths, param_bytes: int = 2):
    """One forward over documents of ``lengths`` tokens."""
    s = sizes(cfg)
    tokens = float(sum(lengths))
    pairs = float(sum(n * (n + 1) // 2 for n in lengths))
    attend = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    flops = 2 * tokens * s["head"] + layers * (
        2 * tokens * s["mla"] + pairs * attend) \
        + 2 * tokens * (dense * s["dense"] + (layers - dense) * s["moe_met"])
    resident = 2 * s["head"] + layers * s["mla"] + dense * s["dense"] \
        + (layers - dense) * s["moe_resident"]
    bytes_ = resident * param_bytes + 4 * tokens + 4 * len(lengths)
    return flops, bytes_
