"""Operations and bytes the algorithm needs, from the configuration and the
rows' lengths, whatever implements them."""

from __future__ import annotations


def param_counts(cfg):
    h, m = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    v, hq = int(cfg["vocab_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or h // hq)
    kv = int(cfg["num_key_value_heads"]) * hd
    layers = int(cfg["num_hidden_layers"])
    per_layer = h * hq * hd * 2 + 2 * h * kv + 3 * h * m + 2 * h
    embed = v * h
    head = h * v
    return {"total": layers * per_layer + embed + head + h,
            "matmul": layers * (per_layer - 2 * h) + head,
            "embed": embed}


def train_flops_per_token(cfg, seq):
    """6 x N_matmul + 6 x L x seq x hidden (``bench.py``'s count: forward and
    backward of every matmul and of causal attention; gather excluded;
    recomputation not counted)."""
    n = param_counts(cfg)["matmul"]
    return 6.0 * n + 6.0 * int(cfg["num_hidden_layers"]) * seq * int(cfg["hidden_size"])


def attention_train_flops(cfg, batch, seq):
    """Causal attention alone, forward and backward, of one step: 6 x L x
    seq x hidden per token."""
    return 6.0 * int(cfg["num_hidden_layers"]) * seq * int(cfg["hidden_size"]) * batch * seq


def serve_flops(cfg, tokens, context_sum):
    """Forward FLOPs of ``tokens`` processed positions whose attention spans
    sum to ``context_sum`` keys: 2 x N_matmul per token plus 4 x hidden per
    key per layer (QK and PV)."""
    n = param_counts(cfg)["matmul"]
    hq = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // hq)
    return 2.0 * n * tokens + 4.0 * int(cfg["num_hidden_layers"]) * hq * hd * context_sum


def kv_bytes_per_token(cfg, itemsize=2):
    hq = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // hq)
    return 2 * int(cfg["num_hidden_layers"]) * int(cfg["num_key_value_heads"]) * hd * itemsize


def weight_bytes(cfg, itemsize=2):
    """Bytes one decode step must read of the weights: every matmul weight
    once (the embedding is gathered, a row per token)."""
    return param_counts(cfg)["matmul"] * itemsize
