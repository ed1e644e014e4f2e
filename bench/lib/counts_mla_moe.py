"""Operations the work of a DeepSeek-V2-style serving step needs, from
shapes alone, for one chip's share (``bench/configs/deepseek-v2-*.json``).

As ``counts.py`` counts: a multiply-add is 2 operations, causal
attention only below the diagonal, recomputation not at all.  The routed
experts are counted apart, per slot that lands on an expert held here,
since which slots do is the traffic's and the router's, not the shape's.
"""
from __future__ import annotations

from .counts import causal_pairs


def mla_moe_request_flops(config: dict, tokens_per_request: int) -> float:
    """Forward operations of one request without its routed experts:
    each layer's MLA (the q-LoRA, the compressed key and value and their
    up-projection, the rope key, the output, the scores and values over
    the causal pairs), the leading dense layers' SwiGLU, each MoE layer's
    router and shared experts, and the unembedding slice."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    ql, kvl = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd = config["v_head_dim"]
    s = tokens_per_request
    mla = (s * 2 * (d * ql + ql * h * (nope + rope)       # queries
                    + d * (kvl + rope)                    # c_kv, k_pe
                    + kvl * h * (nope + vd)               # k, v
                    + h * vd * d)                         # output
           + 2 * causal_pairs(s) * h * (nope + rope + vd))
    dense = s * 2 * 3 * d * config["intermediate_size"]
    moe = s * 2 * (d * config["deployment"]["router_experts"]
                   + 3 * d * config["n_shared_experts"]
                   * config["moe_intermediate_size"])
    n_dense = config["first_k_dense_replace"]
    n_moe = config["num_hidden_layers"] - n_dense
    return float(config["num_hidden_layers"] * mla + n_dense * dense
                 + n_moe * moe + s * 2 * d * config["vocab_size"])


def routed_slot_flops(config: dict) -> float:
    """Operations of one routed slot on a held expert: a SwiGLU of the
    expert width."""
    return float(2 * 3 * config["hidden_size"]
                 * config["moe_intermediate_size"])
