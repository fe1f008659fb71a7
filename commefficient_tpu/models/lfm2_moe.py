"""LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe) in flax: a
decoder whose layer i mixes tokens by what `layer_types[i]` names, a gated
short convolution ("conv": three streams B, C, x from one projection, a causal
depthwise convolution of width `conv_L_cache` over B * x, the output gate C)
or grouped-query softmax attention ("full_attention": an RMSNorm on every
query and key head, rotary positions on the whole head). The first
`num_dense_layers` feed-forwards are dense SwiGLU, the others a top-k expert
block with no shared expert, whose router scores by sigmoid, chooses by score
+ a per-expert bias and weights by the unbiased scores, renormalised over
their sum + 1e-6. Plain RMSNorm, no learned positions, the head tied to the
embedding. Written from the public config.json and the family's published
modelling code; the plain restatement with every departure noted is
benchmark/reference/lfm2_moe.py.

The expert block is one chip's share of an expert-parallel deployment
(`ops/moe.topk_moe_ffn`): the router scores all `router_num_experts`, this
model holds the `num_experts` experts from `experts_held_first` on and adds
their part of the result. The bias (`expert_bias`) is a buffer as GLM's is
(`glm4_moe_lite.biased_experts`): in the `buffers` collection, outside d, and
nothing here changes it. The order of `in_proj`'s three streams is this
file's own ([B | C | x]): it matters only for loading published weights,
which nothing here does.

float32 throughout. `jax.named_scope`s name the blocks (`short_conv`,
`gqa_attn`, `dense_mlp`, `moe_route`, `moe_experts`, `lm_head`:
`obs/profiler.py` `BLOCK_SCOPES`).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .glm4_moe_lite import biased_experts, rms_norm, swiglu
from .qwen3_next import _weight, grouped_causal_attention, rotary

# the blocks this model names with `jax.named_scope` (obs/profiler.BLOCK_SCOPES
# holds every model's)
SCOPES = ("short_conv", "gqa_attn", "dense_mlp", "moe_route", "moe_experts", "lm_head")
KINDS = ("conv", "full_attention")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The keys of the public config.json that shape the model, under their
    own names (`rope_theta` is `rope_parameters`'), and the chip's share of
    the experts."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: tuple[str, ...] = tuple(
        "full_attention" if i % 4 == 2 else "conv" for i in range(40))
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    moe_intermediate_size: int = 1536
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    num_experts: int = 64  # experts held here
    router_num_experts: int = 64  # experts the router scores (the published count)
    experts_held_first: int = 0
    norm_eps: float = 1e-5

    @classmethod
    def from_model_block(cls, block: dict) -> "Lfm2MoeConfig":
        """From the `model` block of a configuration file: its keys that are
        fields here; a model of another kind, or a key whose published value
        is the only one built, is refused."""
        if block.get("model_type") != "lfm2_moe":
            raise ValueError(f"model_type {block.get('model_type')!r} is not lfm2_moe")
        only = {"conv_bias": False, "use_expert_bias": True, "norm_topk_prob": True,
                "tie_embedding": True}
        for key, value in only.items():
            if block.get(key, value) != value:
                raise ValueError(f"only {key} = {value!r} is built, not {block[key]!r}")
        rope = block.get("rope_parameters", {})
        if rope.get("rope_type", "default") != "default":
            raise ValueError(f"only rope_type = 'default' is built, not {rope['rope_type']!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        given = {k: v for k, v in block.items() if k in names}
        if "rope_theta" in rope:
            given["rope_theta"] = rope["rope_theta"]
        if "layer_types" in given:
            given["layer_types"] = tuple(given["layer_types"])
        cfg = cls(**given)
        if len(cfg.layer_types) != cfg.num_hidden_layers or set(cfg.layer_types) - set(KINDS):
            raise ValueError(f"layer_types names each of the {cfg.num_hidden_layers} layers' "
                             f"mixer, one of {', '.join(KINDS)}")
        if cfg.hidden_size % cfg.num_attention_heads or (
                cfg.num_attention_heads % cfg.num_key_value_heads):
            raise ValueError("heads divide the hidden size, key/value heads the query heads")
        if cfg.experts_held_first + cfg.num_experts > cfg.router_num_experts:
            raise ValueError("the experts held lie outside the router's")
        return cfg

    def model_block(self) -> dict:
        """The block `from_model_block` reads this configuration back from."""
        block = dataclasses.asdict(self)
        block.update(model_type="lfm2_moe", layer_types=list(self.layer_types),
                     rope_parameters={"rope_theta": block.pop("rope_theta"),
                                      "rope_type": "default"})
        return block

    def is_attention(self, layer: int) -> bool:
        return self.layer_types[layer] == "full_attention"

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


TINY = Lfm2MoeConfig(
    vocab_size=256, hidden_size=32, num_hidden_layers=3,
    layer_types=("conv", "full_attention", "conv"), num_dense_layers=1, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, rope_theta=1e4, moe_intermediate_size=16,
    num_experts_per_tok=3, num_experts=4, router_num_experts=8, experts_held_first=2)


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        width = self.cfg.conv_L_cache
        bcx = x @ _weight(self, "in_proj", (C, 3 * C))
        conv = _weight(self, "conv", (width, C), nn.initializers.normal(width ** -0.5))
        b, c, u = jnp.split(bcx, 3, axis=-1)
        # causal depthwise convolution (left pad width - 1, no bias, no
        # activation) as a sum of shifted copies, as qwen3_next.GatedDeltaNet's
        padded = jnp.pad(b * u, ((0, 0), (width - 1, 0), (0, 0)))
        y = c * sum(padded[:, j: j + T] * conv[j] for j in range(width))
        return y @ _weight(self, "out_proj", (C, C))


class GroupedQueryAttention(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = (x @ _weight(self, "q_proj", (C, H * hd))).reshape(B, T, H, hd)
        k = (x @ _weight(self, "k_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        v = (x @ _weight(self, "v_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        ones = nn.initializers.ones
        q = rms_norm(q, _weight(self, "q_norm", (hd,), ones), cfg.norm_eps)
        k = rms_norm(k, _weight(self, "k_norm", (hd,), ones), cfg.norm_eps)
        q = rotary(q, cfg.rope_theta, hd).reshape(B, T, KV, H // KV, hd)
        k = rotary(k, cfg.rope_theta, hd)
        y = grouped_causal_attention(q, k, v).reshape(B, T, H * hd)
        return y @ _weight(self, "o_proj", (H * hd, C))


class SparseMoE(nn.Module):
    """The routed experts held here (ops/moe.py); there is no shared expert."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        y = biased_experts(self, x.reshape(B * T, C),
                           held=(cfg.experts_held_first, cfg.num_experts),
                           width=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
                           router_experts=cfg.router_num_experts, bias_name="expert_bias",
                           scale=cfg.routed_scaling_factor, eps=1e-6)
        return y.reshape(B, T, C)


class DenseMLP(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        return swiglu(self, x, self.cfg.intermediate_size)


class DecoderLayer(nn.Module):
    cfg: Lfm2MoeConfig
    attention: bool
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg, ones = self.cfg, nn.initializers.ones
        h = rms_norm(x, _weight(self, "norm_1", (cfg.hidden_size,), ones), cfg.norm_eps)
        if self.attention:
            with jax.named_scope("gqa_attn"):
                x = x + GroupedQueryAttention(cfg, name="mixer")(h)
        else:
            with jax.named_scope("short_conv"):
                x = x + ShortConv(cfg, name="mixer")(h)
        h = rms_norm(x, _weight(self, "norm_2", (cfg.hidden_size,), ones), cfg.norm_eps)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                return x + DenseMLP(cfg, name="mlp")(h)
        return x + SparseMoE(cfg, name="moe")(h)


class Lfm2MoeLM(nn.Module):
    """Causal LM. `token_type_ids` (the dialog federation's speaker segments)
    is taken and ignored: the architecture has no segment embedding."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = True, token_type_ids=None):
        cfg = self.cfg
        embed = _weight(self, "embed", (cfg.vocab_size, cfg.hidden_size))
        x = embed[input_ids]
        for i in range(cfg.num_hidden_layers):
            x = DecoderLayer(cfg, cfg.is_attention(i), cfg.is_dense(i), name=f"layers_{i}")(x)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, _weight(self, "norm_f", (cfg.hidden_size,), nn.initializers.ones),
                         cfg.norm_eps)
            return x @ embed.T  # the head is the embedding: one leaf, both gradients
