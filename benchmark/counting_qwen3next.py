"""Parameters and operations of the Qwen3-Next configuration, from the keys of
its `model` block alone (benchmark/configs/qwen3next_80b_a3b_fetchsgd.json):
the yardstick of `round_mfu` in its cells. Like counting.py, nothing here looks
at what the program compiled."""

from __future__ import annotations


def _dims(m: dict) -> dict:
    key_dim = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    value_dim = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    return {"D": m["hidden_size"], "key_dim": key_dim, "value_dim": value_dim,
            "Hv": m["linear_num_value_heads"], "q_dim": m["num_attention_heads"] * m["head_dim"],
            "kv_dim": m["num_key_value_heads"] * m["head_dim"],
            "F": m["moe_intermediate_size"], "Fs": m["shared_expert_intermediate_size"]}


def delta_mixer_params(m: dict) -> int:
    """Fused q/k/v/z and b/a projections, the depthwise convolution over
    q, k, v, A_log, dt_bias, the gated norm's weight, the output projection."""
    s = _dims(m)
    return (s["D"] * (2 * s["key_dim"] + 2 * s["value_dim"]) + s["D"] * 2 * s["Hv"]
            + m["linear_conv_kernel_dim"] * (2 * s["key_dim"] + s["value_dim"])
            + 2 * s["Hv"] + m["linear_value_head_dim"] + s["value_dim"] * s["D"])


def attention_mixer_params(m: dict) -> int:
    """q (query and gate), k, v, o projections and the two per-head norms."""
    s = _dims(m)
    return (s["D"] * 2 * s["q_dim"] + 2 * s["D"] * s["kv_dim"] + s["q_dim"] * s["D"]
            + 2 * m["head_dim"])


def moe_shared_params(m: dict) -> int:
    """The expert block outside its routed experts: the router over all the
    published experts, the shared expert and its gate."""
    s = _dims(m)
    return s["D"] * m["router_num_experts"] + 3 * s["D"] * s["Fs"] + s["D"]


def routed_expert_params(m: dict) -> int:
    s = _dims(m)
    return 3 * s["D"] * s["F"]


def period_params_outside_experts(m: dict) -> int:
    """One period of the layer pattern (full_attention_interval layers, the last
    of them attention), two layer norms a layer, no routed expert."""
    n, D = m["full_attention_interval"], m["hidden_size"]
    return ((n - 1) * delta_mixer_params(m) + attention_mixer_params(m)
            + n * (moe_shared_params(m) + 2 * D))


def params(m: dict) -> int:
    """d of the configuration as it is run: num_hidden_layers layers with
    num_experts routed experts held in each, embedding, untied head, final norm."""
    layers, n = m["num_hidden_layers"], m["full_attention_interval"]
    attention = sum(1 for i in range(layers) if (i + 1) % n == 0)
    D = m["hidden_size"]
    return ((layers - attention) * delta_mixer_params(m) + attention * attention_mixer_params(m)
            + layers * (moe_shared_params(m) + 2 * D + m["num_experts"] * routed_expert_params(m))
            + 2 * m["vocab_size"] * D + D)


def macs_per_token(m: dict, seq_len: int) -> dict:
    """Multiply-accumulates of one token's forward pass in a sequence of
    seq_len, by kind of block, summed over the layers. The delta rule is
    counted as its recurrence states it (three dk x dv products a token and
    value head: S^T k, k u^T, S^T q), attention over the whole T x T square
    as it is computed, the routed experts by the expected number of a token's
    choices that are held here under a uniform router (k * held / routed)."""
    s = _dims(m)
    layers, n = m["num_hidden_layers"], m["full_attention_interval"]
    attention = sum(1 for i in range(layers) if (i + 1) % n == 0)
    rule = 3 * s["Hv"] * m["linear_key_head_dim"] * m["linear_value_head_dim"]
    gdn = delta_mixer_params(m) - 2 * s["Hv"] - m["linear_value_head_dim"] + rule
    attn = attention_mixer_params(m) - 2 * m["head_dim"] + 2 * seq_len * s["q_dim"]
    held = m["num_experts_per_tok"] * m["num_experts"] / m["router_num_experts"]
    return {"gdn": (layers - attention) * gdn, "gated_attn": attention * attn,
            "moe": layers * (moe_shared_params(m) + held * routed_expert_params(m)),
            "lm_head": m["vocab_size"] * s["D"]}


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward: 2 FLOPs a MAC, the backward pass twice the forward.
    Norms, activations, softmaxes and the convolution's SiLU are left out."""
    return 6.0 * float(sum(macs_per_token(m, seq_len).values()))
