"""Parameters and operations of the LFM2-24B-A2B configuration, from the keys
of its `model` block alone (benchmark/configs/lfm2_24b_a2b_fetchsgd.json): the
yardstick of `round_mfu` in its cell. Like counting.py, nothing here looks at
what the program compiled. The routers' bias is a buffer and is not counted."""

from __future__ import annotations


def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def short_conv_params(m: dict) -> int:
    """The projection to the three streams, the convolution's taps, the
    output projection."""
    D = m["hidden_size"]
    return D * 3 * D + m["conv_L_cache"] * D + D * D


def attention_projection_params(m: dict) -> int:
    """Query and output projections over every query head, key and value
    projections over the key/value heads."""
    D, hd = m["hidden_size"], head_dim(m)
    return 2 * D * m["num_attention_heads"] * hd + 2 * D * m["num_key_value_heads"] * hd


def attention_params(m: dict) -> int:
    """The projections and the query and key heads' norm weights."""
    return attention_projection_params(m) + 2 * head_dim(m)


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def routed_expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    """The router over all the published experts: there is no shared expert."""
    return m["hidden_size"] * m["router_num_experts"]


def layer_params(m: dict, layer: int) -> int:
    """Two norms, the mixer `layer_types` names, and the dense feed-forward
    of a leading layer or the router with the experts held."""
    attention = m["layer_types"][layer] == "full_attention"
    ffn = (dense_mlp_params(m) if layer < m["num_dense_layers"]
           else router_params(m) + m["num_experts"] * routed_expert_params(m))
    return (2 * m["hidden_size"] + (attention_params(m) if attention else short_conv_params(m))
            + ffn)


def params(m: dict) -> int:
    """d of the configuration as it is run: the layers, the embedding (the
    head is tied to it: counted once) and the final norm."""
    D = m["hidden_size"]
    return (sum(layer_params(m, i) for i in range(m["num_hidden_layers"]))
            + m["vocab_size"] * D + D)


def macs_per_token(m: dict, seq_len: int) -> dict:
    """Multiply-accumulates of one token's forward pass in a sequence of
    seq_len, by kind of block, summed over the layers: a short convolution's
    two projections and its taps (the gates are element-wise and left out),
    attention over the whole T x T square as it is computed (scores and
    values over every query head), the routed experts by the expected number
    of a token's choices that are held here under a uniform router
    (k * held / routed), the tied head."""
    layers = m["num_hidden_layers"]
    dense = min(m["num_dense_layers"], layers)
    attn = sum(kind == "full_attention" for kind in m["layer_types"])
    square = seq_len * m["num_attention_heads"] * 2 * head_dim(m)
    held = m["num_experts_per_tok"] * m["num_experts"] / m["router_num_experts"]
    return {"short_conv": (layers - attn) * short_conv_params(m),
            "gqa_attn": attn * (attention_projection_params(m) + square),
            "dense_mlp": dense * dense_mlp_params(m),
            "moe": (layers - dense) * (router_params(m) + held * routed_expert_params(m)),
            "lm_head": m["vocab_size"] * m["hidden_size"]}


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward: 2 FLOPs a MAC, the backward pass twice the forward.
    Norms, gates, activations, rotary positions and softmaxes are left out."""
    return 6.0 * float(sum(macs_per_token(m, seq_len).values()))
