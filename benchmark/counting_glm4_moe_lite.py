"""Parameters and operations of the GLM-4.7-Flash configuration, from the keys
of its `model` block alone (benchmark/configs/glm47_flash_fetchsgd.json): the
yardstick of `round_mfu` in its cells. Like counting.py, nothing here looks at
what the program compiled. The selection bias is a buffer and is not counted."""

from __future__ import annotations


def mla_projection_params(m: dict) -> int:
    """The five matrices of a latent-attention block: query down and up, the
    key/value latent with its one rotary key head, its up-projection to every
    head's keys and values, the output projection."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (D * m["q_lora_rank"] + m["q_lora_rank"] * H * qk
            + D * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * H * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * D)


def mla_params(m: dict) -> int:
    """The projections and the two latents' norm weights."""
    return mla_projection_params(m) + m["q_lora_rank"] + m["kv_lora_rank"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def routed_expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def moe_shared_params(m: dict) -> int:
    """The expert block outside its routed experts: the router over all the
    published experts and the shared expert."""
    return (m["hidden_size"] * m["router_num_experts"]
            + m["n_shared_experts"] * routed_expert_params(m))


def dense_layer_params(m: dict) -> int:
    return 2 * m["hidden_size"] + mla_params(m) + dense_mlp_params(m)


def expert_layer_params(m: dict) -> int:
    return (2 * m["hidden_size"] + mla_params(m) + moe_shared_params(m)
            + m["n_routed_experts"] * routed_expert_params(m))


def params(m: dict) -> int:
    """d of the configuration as it is run: first_k_dense_replace dense layers,
    then expert layers with n_routed_experts held in each, embedding, untied
    head, final norm."""
    dense = min(m["first_k_dense_replace"], m["num_hidden_layers"])
    D = m["hidden_size"]
    return (dense * dense_layer_params(m)
            + (m["num_hidden_layers"] - dense) * expert_layer_params(m)
            + 2 * m["vocab_size"] * D + D)


def macs_per_token(m: dict, seq_len: int) -> dict:
    """Multiply-accumulates of one token's forward pass in a sequence of
    seq_len, by kind of block, summed over the layers: attention over the
    whole T x T square as it is computed (scores over the key width nope +
    rope, values over v), the routed experts by the expected number of a
    token's choices that are held here under a uniform router
    (k * held / routed)."""
    layers = m["num_hidden_layers"]
    dense = min(m["first_k_dense_replace"], layers)
    H = m["num_attention_heads"]
    square = seq_len * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    held = m["num_experts_per_tok"] * m["n_routed_experts"] / m["router_num_experts"]
    return {"mla": layers * (mla_projection_params(m) + square),
            "dense_mlp": dense * dense_mlp_params(m),
            "moe": (layers - dense) * (moe_shared_params(m) + held * routed_expert_params(m)),
            "lm_head": m["vocab_size"] * m["hidden_size"]}


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward: 2 FLOPs a MAC, the backward pass twice the forward.
    Norms, activations, rotary positions and softmaxes are left out."""
    return 6.0 * float(sum(macs_per_token(m, seq_len).values()))
