"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B) in flax: a decoder whose layer i mixes
tokens by a Gated DeltaNet (linear attention, arXiv:2412.06464) unless
(i + 1) % full_attention_interval == 0, when it is gated softmax attention
with grouped heads and partial rotary positions; every layer's feed-forward is
a top-k expert block with a shared expert. Zero-centred RMSNorm, no learned
positions, an untied head. Written from the public config.json and
`modeling_qwen3_next.py`; the plain restatement with every departure noted is
benchmark/reference/qwen3_next.py.

The expert block is one chip's share of an expert-parallel deployment
(`ops/moe.topk_moe_ffn`): the router scores all `router_num_experts`, this
model holds the `num_experts` experts from `experts_held_first` on and adds
their part of the result. The order of the fused projections' columns is this
file's own ([q | k | v | z] and [b | a], heads contiguous inside each): it
matters only for loading published weights, which nothing here does.

float32 throughout. `jax.named_scope`s name the blocks (`gdn`, `gated_attn`,
`moe_route`, `moe_experts`, `moe_shared`, `lm_head`: `obs/profiler.py`
`BLOCK_SCOPES`) so that a profiler capture can be reduced by kind of block;
the experts' choices are sown under `intermediates`, and MoE counters are sown under the `metrics` collection as
sums, which `losses.make_lm_loss(model_metrics=True)` hands to the engine.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import gated_delta, moe
from ..ops.attention import causal_attention

# the blocks this model names with `jax.named_scope` (obs/profiler.BLOCK_SCOPES
# holds every model's)
SCOPES = ("gdn", "gated_attn", "moe_route", "moe_experts", "moe_shared", "lm_head")

@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The keys of the public config.json that shape the model, under their
    own names, and the chip's share of the experts."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts_per_tok: int = 10
    num_experts: int = 512  # experts held here
    router_num_experts: int = 512  # experts the router scores (the published count)
    experts_held_first: int = 0
    rms_norm_eps: float = 1e-6
    gdn_chunk: int = gated_delta.CHUNK

    @classmethod
    def from_model_block(cls, block: dict) -> "Qwen3NextConfig":
        """From the `model` block of a configuration file: its keys that are
        fields here; a model of another kind, or one whose feed-forward is not
        sparse in every layer, is refused."""
        if block.get("model_type", "qwen3_next") != "qwen3_next":
            raise ValueError(f"model_type {block.get('model_type')!r} is not qwen3_next")
        if block.get("decoder_sparse_step", 1) != 1 or block.get("mlp_only_layers"):
            raise ValueError("only the published layout is built: an expert block in every layer")
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in block.items() if k in names})
        if cfg.experts_held_first + cfg.num_experts > cfg.router_num_experts:
            raise ValueError("the experts held lie outside the router's")
        return cfg

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


TINY = Qwen3NextConfig(
    vocab_size=256, hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, num_experts_per_tok=3,
    num_experts=4, router_num_experts=8, experts_held_first=2, gdn_chunk=8)

_normal = nn.initializers.normal(0.02)


def _weight(module, name, shape, init=_normal):
    return module.param(name, init, shape, jnp.float32)


def rms_norm(x, w, eps):
    """Zero-centred: the weight is stored as its distance from 1."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta: float, rotary_dim: int):
    """Rotate-half positions on the first `rotary_dim` dims of x [B, T, H, hd]."""
    T = x.shape[1]
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]  # [1, T, 1, rotary_dim]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


class GatedDeltaNet(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        key_dim, value_dim, width = Hk * dk, Hv * dv, cfg.linear_conv_kernel_dim
        w_qkvz = _weight(self, "in_proj_qkvz", (C, 2 * key_dim + 2 * value_dim))
        w_ba = _weight(self, "in_proj_ba", (C, 2 * Hv))
        conv = _weight(self, "conv", (width, 2 * key_dim + value_dim),
                       nn.initializers.normal(width ** -0.5))
        a_log = _weight(self, "A_log", (Hv,), lambda k, s, d: jnp.log(
            jax.random.uniform(k, s, d, 1.0, 16.0)))
        dt_bias = _weight(self, "dt_bias", (Hv,), lambda k, s, d: jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k, s, d, jnp.log(1e-3), jnp.log(1e-1))))))
        norm = _weight(self, "norm", (dv,), nn.initializers.ones)
        w_out = _weight(self, "out_proj", (value_dim, C))

        qkvz = x @ w_qkvz
        qkv, z = qkvz[..., : 2 * key_dim + value_dim], qkvz[..., 2 * key_dim + value_dim:]
        # causal depthwise convolution of width 4 (left pad 3, no bias), SiLU
        padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(padded[:, j: j + T] * conv[j] for j in range(width)))
        q = qkv[..., :key_dim].reshape(B, T, Hk, dk)
        k = qkv[..., key_dim: 2 * key_dim].reshape(B, T, Hk, dk)
        v = qkv[..., 2 * key_dim:].reshape(B, T, Hv, dv)
        ba = (x @ w_ba).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)

        # recomputed in the backward pass, but for the chunks' inverses: the
        # other intermediates are several times the rule's inputs and cost a
        # few percent of the layer, the inverses are small and a loop to make
        @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.save_only_these_names(
            gated_delta.INVERSE))
        def rule(q, k, v, g, beta):
            unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)  # noqa: E731
            # each key head serves Hv / Hk value heads
            q = jnp.repeat(unit(q) * dk ** -0.5, Hv // Hk, axis=2)
            k = jnp.repeat(unit(k), Hv // Hk, axis=2)
            return gated_delta.chunk_gated_delta_rule(q, k, v, g, beta, chunk=cfg.gdn_chunk)

        o = rule(q, k, v, g, beta)
        # gated norm per head: w * o / rms(o) * silu(z)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps) * norm
        o = o * jax.nn.silu(z.reshape(B, T, Hv, dv))
        return o.reshape(B, T, value_dim) @ w_out


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        qg = (x @ _weight(self, "q_proj", (C, 2 * H * hd))).reshape(B, T, H, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (x @ _weight(self, "k_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        v = (x @ _weight(self, "v_proj", (C, KV * hd))).reshape(B, T, KV, hd)
        zeros = nn.initializers.zeros
        q = rms_norm(q, _weight(self, "q_norm", (hd,), zeros), cfg.rms_norm_eps)
        k = rms_norm(k, _weight(self, "k_norm", (hd,), zeros), cfg.rms_norm_eps)
        q = rotary(q, cfg.rope_theta, cfg.rotary_dim).reshape(B, T, KV, H // KV, hd)
        k = rotary(k, cfg.rope_theta, cfg.rotary_dim)

        y = causal_attention(q, k, v, scale=hd ** -0.5).reshape(B, T, H, hd)
        y = (y * jax.nn.sigmoid(gate)).reshape(B, T, H * hd)
        return y @ _weight(self, "o_proj", (H * hd, C))


class SparseMoE(nn.Module):
    """The routed experts held here (ops/moe.py) plus the shared expert."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        G, F, Fs = cfg.num_experts, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
        router = _weight(self, "router", (C, cfg.router_num_experts))
        experts = {"gate": _weight(self, "experts_gate", (G, C, F)),
                   "up": _weight(self, "experts_up", (G, C, F)),
                   "down": _weight(self, "experts_down", (G, F, C))}
        tokens = x.reshape(B * T, C)
        y, counts = moe.topk_moe_ffn(tokens, router, experts,
                                     (cfg.experts_held_first, G), cfg.num_experts_per_tok,
                                     route=moe.topk_route)
        self.sow("metrics", "moe_assignments", counts["assignments"])
        self.sow("metrics", "moe_assignments_held", counts["assignments_held"])
        self.sow("metrics", "moe_load_max_sum", counts["expert_load_max"])
        self.sow("metrics", "moe_load_max_count", jnp.float32(1.0))
        self.sow("intermediates", "moe_choices", counts["experts"])
        with jax.named_scope("moe_shared"):
            h = jax.nn.silu(tokens @ _weight(self, "shared_gate", (C, Fs)))
            h = (h * (tokens @ _weight(self, "shared_up", (C, Fs)))) @ _weight(
                self, "shared_down", (Fs, C))
            y = y + jax.nn.sigmoid(tokens @ _weight(self, "shared_expert_gate", (C,)))[:, None] * h
        return y.reshape(B, T, C)


class DecoderLayer(nn.Module):
    cfg: Qwen3NextConfig
    attention: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        zeros = nn.initializers.zeros
        h = rms_norm(x, _weight(self, "norm_1", (cfg.hidden_size,), zeros), cfg.rms_norm_eps)
        if self.attention:
            with jax.named_scope("gated_attn"):
                x = x + GatedAttention(cfg, name="mixer")(h)
        else:
            with jax.named_scope("gdn"):
                x = x + GatedDeltaNet(cfg, name="mixer")(h)
        h = rms_norm(x, _weight(self, "norm_2", (cfg.hidden_size,), zeros), cfg.rms_norm_eps)
        return x + SparseMoE(cfg, name="moe")(h)


class Qwen3NextLM(nn.Module):
    """Causal LM. `token_type_ids` (the dialog federation's speaker segments)
    is taken and ignored: the architecture has no segment embedding."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = True, token_type_ids=None):
        cfg = self.cfg
        x = _weight(self, "embed", (cfg.vocab_size, cfg.hidden_size))[input_ids]
        for i in range(cfg.num_hidden_layers):
            x = DecoderLayer(cfg, cfg.is_attention(i), name=f"layers_{i}")(x)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, _weight(self, "norm_f", (cfg.hidden_size,), nn.initializers.zeros),
                         cfg.rms_norm_eps)
            return x @ _weight(self, "lm_head", (cfg.hidden_size, cfg.vocab_size))
