"""Operations and bytes the work needs, from shapes alone.

These count what a request or a step requires, whatever implements it:
a multiply-add is 2 operations, every expert is counted only for the
tokens routed to it, causal attention only below the diagonal, and
recomputation not at all.
"""
from __future__ import annotations


def causal_pairs(seq: int) -> int:
    """Query-key pairs of causal attention over ``seq`` positions."""
    return seq * (seq + 1) // 2


def serve_plane_flops(config: dict, tokens_per_request: int) -> float:
    """Forward operations of one request of the serving plane: attention
    projections and scores, the router, ``top_k`` SwiGLU experts per
    token, the unembedding."""
    d = config["hidden_size"]
    f = config["intermediate_size"]
    e = config["num_local_experts"]
    k = config["num_experts_per_tok"]
    v = config["vocab_size"]
    s = tokens_per_request
    per_layer = (s * 2 * 4 * d * d               # q, k, v, o
                 + 2 * 2 * causal_pairs(s) * d   # scores and values
                 + s * 2 * d * e                 # router
                 + s * k * 2 * 3 * d * f)        # experts
    return float(config["num_hidden_layers"] * per_layer + s * 2 * d * v)


def dense_lm_matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix product per token: the
    layers' projections and MLP, and the (tied) output head; the
    embedding lookup is not a product."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    hd = config.get("head_dim", d // h)
    f = config["intermediate_size"]
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 2 * d * f
    return config["num_hidden_layers"] * per_layer \
        + config["vocab_size"] * d


def dense_lm_train_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, and causal attention's scores and values (2 x 2 per
    query-key pair per head dimension forward, three times that with the
    backward pass)."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hd = config.get("head_dim", d // h)
    attn = 3 * 2 * 2 * causal_pairs(seq) * h * hd / seq
    return float(6 * dense_lm_matmul_params(config)
                 + config["num_hidden_layers"] * attn)


def gather_bytes(rows: int, width: int, distinct: int, itemsize: int = 4,
                 index_bytes: int = 4) -> float:
    """Least bytes of a gather of ``rows`` rows: the ids, each of the
    ``distinct`` rows it reads read once, each row written once."""
    return float(rows * index_bytes + (rows + distinct) * width * itemsize)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time on the chip: the larger of operations over peak
    operations and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])
