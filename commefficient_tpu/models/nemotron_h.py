"""NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
`model_type` nemotron_h) in flax: a decoder whose layer i is ONE mixer,
`x + mixer_i(norm_i(x))`, of the kind the character `hybrid_override_pattern[i]`
names: "M" a Mamba-2 state-space mixer, "E" an expert block, "*" grouped-query
softmax attention with no positions at all (no rotary embedding, no bias, no
norm on the heads). No layer has a second sublayer.

Mamba-2 (`ops/ssd.py`): [z | xBC | dt] = u W_in; a causal depthwise
convolution of `conv_kernel` taps with a bias, then SiLU, over xBC; [x | B | C]
= xBC with B and C shared by the heads of each of `n_groups` groups; Δ =
softplus(dt + dt_bias) with no clamp; A = -exp(A_log); the scan with a D skip;
then w * rms-by-group(y * silu(z)) (`norm_before_gate` false, groups of
H P / n_groups channels) and W_out. The expert block: the router scores by
sigmoid, chooses by score + a per-expert bias and weights by the unbiased
scores renormalised and scaled (GLM's rule, `glm4_moe_lite.biased_experts`),
with two-matrix squared-ReLU experts, down(relu(up x)^2), and one shared expert
of that kind that every token passes. Plain RMSNorm, an untied head. Written
from the public config.json and the family's published modelling code; the
plain restatement with every departure noted is
benchmark/reference/nemotron_h.py.

The expert block is one chip's share of an expert-parallel deployment
(`ops/moe.topk_moe_ffn`): the router scores all `router_num_experts`, this
model holds the `n_routed_experts` experts from `experts_held_first` on and
adds their part of the result. The bias (`e_score_correction_bias`) is a buffer
as GLM's is: in the `buffers` collection, outside d, and nothing here changes
it. The order of `in_proj`'s columns, [z | xBC | dt], is the family's own.

float32 throughout. `jax.named_scope`s name the blocks (`mamba2`, `gqa_attn`,
`moe_route`, `moe_experts`, `moe_shared`, `lm_head`: `obs/profiler.py`
`BLOCK_SCOPES`); each Mamba-2 mixer sows the chunks it scanned under
`metrics` (`ssm_chunks`), beside the expert counters GLM's block sows.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.attention import causal_attention
from .glm4_moe_lite import biased_experts, rms_norm
from .qwen3_next import _weight

# the blocks this model names with `jax.named_scope` (obs/profiler.BLOCK_SCOPES
# holds every model's)
SCOPES = ("mamba2", "gqa_attn", "moe_route", "moe_experts", "moe_shared", "lm_head")
KINDS = "ME*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The keys of the public config.json that shape the model, under their
    own names, and the chip's share of the experts."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = ssd.CHUNK
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    n_routed_experts: int = 128  # experts held here
    router_num_experts: int = 128  # experts the router scores (the published count)
    experts_held_first: int = 0
    layer_norm_epsilon: float = 1e-5

    @classmethod
    def from_model_block(cls, block: dict) -> "NemotronHConfig":
        """From the `model` block of a configuration file: its keys that are
        fields here; a model of another kind, or a key whose published value
        is the only one built, is refused."""
        if block.get("model_type") != "nemotron_h":
            raise ValueError(f"model_type {block.get('model_type')!r} is not nemotron_h")
        only = {"mamba_hidden_act": "silu", "mlp_hidden_act": "relu2", "use_conv_bias": True,
                "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
                "use_bias": False, "tie_word_embeddings": False, "norm_topk_prob": True,
                "n_group": 1, "topk_group": 1, "n_shared_experts": 1, "sliding_window": None}
        for key, value in only.items():
            if block.get(key, value) != value:
                raise ValueError(f"only {key} = {value!r} is built, not {block[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in block.items() if k in names})
        pattern = cfg.hybrid_override_pattern
        if len(pattern) != cfg.num_hidden_layers or set(pattern) - set(KINDS):
            raise ValueError(f"hybrid_override_pattern names each of the {cfg.num_hidden_layers} "
                             f"layers' mixer, one of {', '.join(KINDS)}")
        if cfg.num_attention_heads % cfg.num_key_value_heads or (
                cfg.mamba_num_heads % cfg.n_groups):
            raise ValueError("key/value heads divide the query heads, groups the Mamba-2 heads")
        if cfg.experts_held_first + cfg.n_routed_experts > cfg.router_num_experts:
            raise ValueError("the experts held lie outside the router's")
        return cfg

    def model_block(self) -> dict:
        """The block `from_model_block` reads this configuration back from."""
        return dict(dataclasses.asdict(self), model_type="nemotron_h")

    def kind(self, layer: int) -> str:
        return self.hybrid_override_pattern[layer]


TINY = NemotronHConfig(
    vocab_size=256, hidden_size=32, num_hidden_layers=5, hybrid_override_pattern="ME*EM",
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
    mamba_head_dim=8, n_groups=2, ssm_state_size=8, chunk_size=8, moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24, num_experts_per_tok=3, n_routed_experts=4,
    router_num_experts=8, experts_held_first=2)


def relu2_mlp(module, x, width: int, prefix: str = ""):
    """down(relu(up x)^2): the family's two-matrix feed-forward."""
    h = jnp.square(jax.nn.relu(x @ _weight(module, prefix + "up", (x.shape[-1], width))))
    return h @ _weight(module, prefix + "down", (width, x.shape[-1]))


class Mamba2(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        B, T, C = u.shape
        H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                         cfg.ssm_state_size, cfg.conv_kernel)
        inner, conv_dim = H * P, H * P + 2 * G * N
        # taps and bias uniform on +-K^-1/2, PyTorch's Conv1d default at fan-in K
        taps = lambda k, s, d: jax.random.uniform(k, s, d, -K ** -0.5, K ** -0.5)  # noqa: E731
        conv = _weight(self, "conv", (K, conv_dim), taps)
        conv_bias = _weight(self, "conv_bias", (conv_dim,), taps)
        a_log = _weight(self, "A_log", (H,), lambda k, s, d: jnp.log(jnp.arange(1, H + 1, dtype=d)))
        skip = _weight(self, "D", (H,), nn.initializers.ones)

        def dt_init(key, shape, dtype):
            # Δ log-uniform on [time_step_min, time_step_max], at least the floor
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                             cfg.time_step_floor)
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1

        dt_bias = _weight(self, "dt_bias", (H,), dt_init)
        norm = _weight(self, "norm", (inner,), nn.initializers.ones)

        z, xbc, dt = jnp.split(u @ _weight(self, "in_proj", (C, inner + conv_dim + H)),
                               [inner, inner + conv_dim], axis=-1)
        # causal depthwise convolution (left pad K - 1) as a sum of shifted
        # copies, as qwen3_next.GatedDeltaNet's, with its bias, then SiLU
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[:, j: j + T] * conv[j] for j in range(K)) + conv_bias)
        x, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        x = x.reshape(B, T, H, P)
        y = ssd.chunk_ssd(x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                          b.reshape(B, T, G, N), c.reshape(B, T, G, N), chunk=cfg.chunk_size)
        y = (y + x * skip[:, None]).reshape(B, T, inner) * jax.nn.silu(z)
        # the gated norm: each group of inner / G channels by its own rms
        y = y.reshape(B, T, G, inner // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.layer_norm_epsilon)
        self.sow("metrics", "ssm_chunks", jnp.float32(B * -(-T // cfg.chunk_size)))
        return (y.reshape(B, T, inner) * norm) @ _weight(self, "out_proj", (inner, C))


class Attention(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = (x @ _weight(self, "q_proj", (C, H * hd))).reshape(B, T, KV, H // KV, hd)
        k = (x @ _weight(self, "k_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        v = (x @ _weight(self, "v_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        y = causal_attention(q, k, v, scale=hd ** -0.5).reshape(B, T, H * hd)
        return y @ _weight(self, "o_proj", (H * hd, C))


class SparseMoE(nn.Module):
    """The routed experts held here (ops/moe.py) plus the shared expert."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        tokens = x.reshape(B * T, C)
        y = biased_experts(self, tokens, held=(cfg.experts_held_first, cfg.n_routed_experts),
                           width=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
                           router_experts=cfg.router_num_experts,
                           bias_name="e_score_correction_bias",
                           scale=cfg.routed_scaling_factor, gated=False)
        with jax.named_scope("moe_shared"):
            y = y + relu2_mlp(self, tokens, cfg.moe_shared_expert_intermediate_size, "shared_")
        return y.reshape(B, T, C)


class DecoderLayer(nn.Module):
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = rms_norm(x, _weight(self, "norm", (cfg.hidden_size,), nn.initializers.ones),
                     cfg.layer_norm_epsilon)
        if self.kind == "M":
            with jax.named_scope("mamba2"):
                return x + Mamba2(cfg, name="mixer")(h)
        if self.kind == "*":
            with jax.named_scope("gqa_attn"):
                return x + Attention(cfg, name="mixer")(h)
        return x + SparseMoE(cfg, name="mixer")(h)


class NemotronHLM(nn.Module):
    """Causal LM. `token_type_ids` (the dialog federation's speaker segments)
    is taken and ignored: the architecture has no segment embedding."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = True, token_type_ids=None):
        cfg = self.cfg
        x = _weight(self, "embed", (cfg.vocab_size, cfg.hidden_size))[input_ids]
        for i in range(cfg.num_hidden_layers):
            x = DecoderLayer(cfg, cfg.kind(i), name=f"layers_{i}")(x)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, _weight(self, "norm_f", (cfg.hidden_size,), nn.initializers.ones),
                         cfg.layer_norm_epsilon)
            return x @ _weight(self, "lm_head", (cfg.hidden_size, cfg.vocab_size))
