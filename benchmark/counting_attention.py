"""Operations and bytes of the causal attention kernel pair
(commefficient_tpu/ops/attention.py) a round, from the configuration's `model`
block, its input's sequence length and the traffic's cohort alone: the
yardstick of `attention_kernel_roofline`. Like counting.py, nothing here looks
at what the program compiled.

A causal pair is a query and a key at or before it: T (T + 1) / 2 a sequence.
Per query head and pair the forward pass is 2 d_qk + 2 d_v FLOPs (scores,
values) and the backward pass 2 d_qk + 2 d_v + 2 d_v + 2 d_qk + 2 d_qk
(recomputed scores, dP, dV, dQ, dK). Nothing above the diagonal is counted, and
neither is any product a kernel repeats. Bytes: q, k, v and dO read as bf16,
o, dq, dk and dv as float32, and the log-sum-exp, each once."""

from __future__ import annotations


def attention_layers(m: dict) -> tuple[int, int, int, int, int]:
    """(attention layers, query heads, key/value heads, d_qk, d_v) of a model
    block; a family with no attention layer has none."""
    kind = m.get("model_type")
    if kind == "glm4_moe_lite":
        H = m["num_attention_heads"]
        return (m["num_hidden_layers"], H, H, m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
                m["v_head_dim"])
    if kind == "lfm2_moe":
        hd = m["hidden_size"] // m["num_attention_heads"]
        return (list(m["layer_types"]).count("full_attention"), m["num_attention_heads"],
                m["num_key_value_heads"], hd, hd)
    if kind == "nemotron_h":
        hd = m["head_dim"]
        return (m["hybrid_override_pattern"].count("*"), m["num_attention_heads"],
                m["num_key_value_heads"], hd, hd)
    if kind == "qwen3_next":
        hd = m["head_dim"]
        layers = sum((i + 1) % m["full_attention_interval"] == 0
                     for i in range(m["num_hidden_layers"]))
        return layers, m["num_attention_heads"], m["num_key_value_heads"], hd, hd
    return 0, 0, 0, 0, 0


def sequences(traffic: dict) -> int:
    """Sequences a round: the cohort's documents."""
    return traffic["cohort"] * traffic["examples_per_client"]


def calls_per_round(config: dict, traffic: dict) -> int:
    """Calls of each kernel (forward, backward) a round: one a layer and
    client chunk, the chunk's clients batched into one call
    (`--client_chunk` in the traffic's trainer flags; without it, or at 0,
    the cohort is one chunk)."""
    argv = traffic.get("argv", [])
    chunk = int(argv[argv.index("--client_chunk") + 1]) if "--client_chunk" in argv else 0
    cohort = traffic["cohort"]
    return attention_layers(config["model"])[0] * -(-cohort // (chunk or cohort))


def pairs(T: int) -> int:
    return T * (T + 1) // 2


def flops_per_round(config: dict, traffic: dict) -> float:
    layers, H, _, d_qk, d_v = attention_layers(config["model"])
    per_pair = (2 * d_qk + 2 * d_v) + (6 * d_qk + 4 * d_v)
    return float(sequences(traffic) * layers * H * pairs(config["input"]["seq_len"]) * per_pair)


def bytes_per_round(config: dict, traffic: dict) -> float:
    layers, H, KV, d_qk, d_v = attention_layers(config["model"])
    T = config["input"]["seq_len"]
    bf16 = 2 * T * (H * d_qk + KV * d_qk + KV * d_v + H * d_v)  # q, k, v, dO
    f32 = 4 * T * (H * d_v + H * d_qk + KV * d_qk + KV * d_v + H)  # o, dq, dk, dv, lse
    return float(sequences(traffic) * layers * (bf16 + f32))
