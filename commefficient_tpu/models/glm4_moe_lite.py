"""GLM-4.7-Flash (zai-org/GLM-4.7-Flash, `model_type` glm4_moe_lite) in flax: a
decoder whose every layer attends through low-rank latents (multi-head latent
attention: a query latent and a shared key/value latent, each RMS-normed, and
one rotary key head that all query heads share), whose first
`first_k_dense_replace` layers have a dense SwiGLU feed-forward and whose other
layers a top-k expert block with a shared expert. The router scores by sigmoid,
chooses by score + a per-expert selection bias and weights by the unbiased
scores, renormalised and scaled (`topk_method` noaux_tc, one group). Plain
RMSNorm, no learned positions, an untied head. Written from the public
config.json and the DeepSeek-V3 family's published modelling code, which
glm4_moe_lite follows; the plain restatement with every departure noted is
benchmark/reference/glm4_moe_lite.py.

The expert block is one chip's share of an expert-parallel deployment
(`ops/moe.topk_moe_ffn`): the router scores all `router_num_experts`, this
model holds the `n_routed_experts` experts from `experts_held_first` on and
adds their part of the result.

The selection bias (`e_score_correction_bias`) is a buffer, not a parameter:
it lives in the `buffers` collection (the session's `net_state`), no gradient
reaches it, weight decay and the sketch never see it, and nothing here changes
it (the pre-training rule that nudges it toward balance has no rate in the
config). Attention is computed in its expanded form, keys and values of every
head made from the latent: training has no cache to shrink.

float32 throughout. `jax.named_scope`s name the blocks (`mla`, `dense_mlp`,
`moe_route`, `moe_experts`, `moe_shared`, `lm_head`: `obs/profiler.py`
`BLOCK_SCOPES`); the experts' choices are sown under `intermediates`, and the
expert counters under `metrics` as sums, as `qwen3_next.SparseMoE` sows them,
with the number of tokens whose choice the bias changed beside them.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe
from .qwen3_next import _weight, rotary

# the blocks this model names with `jax.named_scope` (obs/profiler.BLOCK_SCOPES
# holds every model's)
SCOPES = ("mla", "dense_mlp", "moe_route", "moe_experts", "moe_shared", "lm_head")

@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """The keys of the public config.json that shape the model, under their
    own names, and the chip's share of the experts."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    n_routed_experts: int = 64  # experts held here
    router_num_experts: int = 64  # experts the router scores (the published count)
    experts_held_first: int = 0
    rms_norm_eps: float = 1e-5

    @classmethod
    def from_model_block(cls, block: dict) -> "Glm4MoeLiteConfig":
        """From the `model` block of a configuration file: its keys that are
        fields here; a model of another kind, or a key whose published value
        is the only one built, is refused."""
        if block.get("model_type") != "glm4_moe_lite":
            raise ValueError(f"model_type {block.get('model_type')!r} is not glm4_moe_lite")
        only = {"n_group": 1, "topk_group": 1, "norm_topk_prob": True, "topk_method": "noaux_tc",
                "rope_scaling": None, "partial_rotary_factor": 1, "attention_bias": False,
                "hidden_act": "silu", "tie_word_embeddings": False}
        for key, value in only.items():
            if block.get(key, value) != value:
                raise ValueError(f"only {key} = {value!r} is built, not {block[key]!r}")
        heads = block.get("num_attention_heads", cls.num_attention_heads)
        if block.get("num_key_value_heads", heads) != heads:
            raise ValueError("latent attention makes keys and values for every query head")
        names = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in block.items() if k in names})
        if cfg.experts_held_first + cfg.n_routed_experts > cfg.router_num_experts:
            raise ValueError("the experts held lie outside the router's")
        return cfg

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace


TINY = Glm4MoeLiteConfig(
    vocab_size=256, hidden_size=32, num_hidden_layers=2, intermediate_size=48,
    num_attention_heads=3, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e4, moe_intermediate_size=16,
    num_experts_per_tok=3, n_routed_experts=4, router_num_experts=8, experts_held_first=2)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(module, x, width: int, prefix: str = ""):
    C = x.shape[-1]
    h = jax.nn.silu(x @ _weight(module, prefix + "gate", (C, width)))
    return (h * (x @ _weight(module, prefix + "up", (C, width)))) @ _weight(
        module, prefix + "down", (width, C))


class LatentAttention(nn.Module):
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        rq, rkv, ones = cfg.q_lora_rank, cfg.kv_lora_rank, nn.initializers.ones
        c_q = rms_norm(x @ _weight(self, "q_a_proj", (C, rq)),
                       _weight(self, "q_a_norm", (rq,), ones), cfg.rms_norm_eps)
        q = (c_q @ _weight(self, "q_b_proj", (rq, H * (nope + rope)))).reshape(B, T, H, nope + rope)
        # one rotary key head beside the latent, shared by every query head
        kv_a = x @ _weight(self, "kv_a_proj", (C, rkv + rope))
        c_kv = rms_norm(kv_a[..., :rkv], _weight(self, "kv_a_norm", (rkv,), ones), cfg.rms_norm_eps)
        kv = (c_kv @ _weight(self, "kv_b_proj", (rkv, H * (nope + dv)))).reshape(B, T, H, nope + dv)
        q_rope = rotary(q[..., nope:], cfg.rope_theta, rope)
        k_rope = rotary(kv_a[..., None, rkv:], cfg.rope_theta, rope)[:, :, 0]

        # q . [k_nope | k_rope] as two products, so that the shared head is
        # never copied H times; unfused T x T scores, recomputed in the
        # backward pass (two copies of them are kept otherwise)
        @jax.checkpoint
        def attend(q_nope, q_rope, k_nope, k_rope, v):
            att = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                   + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) * (nope + rope) ** -0.5
            causal = jnp.tril(jnp.ones((T, T), bool))
            att = jax.nn.softmax(jnp.where(causal, att, jnp.finfo(att.dtype).min), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", att, v)

        y = attend(q[..., :nope], q_rope, kv[..., :nope], k_rope, kv[..., nope:])
        return y.reshape(B, T, H * dv) @ _weight(self, "o_proj", (H * dv, C))


def biased_experts(module, tokens, *, held: tuple[int, int], width: int, k: int,
                   router_experts: int, bias_name: str, scale: float, eps: float = 1e-20):
    """The routed experts `module` holds of a layer whose router chooses by
    sigmoid score + a selection bias (`ops/moe.sigmoid_topk_route`), for
    tokens [N, C]: the router and the experts' weights as the module's
    parameters, the bias as its buffer `bias_name`, the expert counters sown
    under `metrics` and the choices under `intermediates`. Returns the held
    experts' part of the result, [N, C]."""
    N, C = tokens.shape
    first, G = held
    router = _weight(module, "router", (C, router_experts))
    bias = module.variable("buffers", bias_name, lambda: 0.01 * jax.random.normal(
        module.make_rng("params"), (router_experts,), jnp.float32)).value
    experts = {"gate": _weight(module, "experts_gate", (G, C, width)),
               "up": _weight(module, "experts_up", (G, C, width)),
               "down": _weight(module, "experts_down", (G, width, C))}
    rule = functools.partial(moe.sigmoid_topk_route, bias=bias, scale=scale, eps=eps)
    y, counts = moe.topk_moe_ffn(tokens, router, experts, held, k, route=rule)
    with jax.named_scope("moe_route"):
        # the tokens whose chosen set the bias changed: the compiler
        # shares the router's product with the rule's own (pinned in
        # tests/test_tpu_compile.py), so this is one more top-k of
        # [T, E] and two sorts of [T, k]
        unbiased, _ = moe.sigmoid_topk_route(tokens, router, k)
        flips = (jnp.sort(unbiased, -1) != jnp.sort(counts["experts"], -1)).any(-1)
    module.sow("metrics", "moe_assignments", counts["assignments"])
    module.sow("metrics", "moe_assignments_held", counts["assignments_held"])
    module.sow("metrics", "moe_load_max_sum", counts["expert_load_max"])
    module.sow("metrics", "moe_load_max_count", jnp.float32(1.0))
    module.sow("metrics", "moe_bias_flips", flips.sum().astype(jnp.float32))
    module.sow("metrics", "moe_bias_tokens", jnp.float32(N))
    module.sow("intermediates", "moe_choices", counts["experts"])
    return y


class SparseMoE(nn.Module):
    """The routed experts held here (ops/moe.py) plus the shared expert."""

    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, C = x.shape
        F = cfg.moe_intermediate_size
        tokens = x.reshape(B * T, C)
        y = biased_experts(self, tokens, held=(cfg.experts_held_first, cfg.n_routed_experts),
                           width=F, k=cfg.num_experts_per_tok,
                           router_experts=cfg.router_num_experts,
                           bias_name="e_score_correction_bias",
                           scale=cfg.routed_scaling_factor)
        with jax.named_scope("moe_shared"):
            y = y + swiglu(self, tokens, F * cfg.n_shared_experts, "shared_")
        return y.reshape(B, T, C)


class DenseMLP(nn.Module):
    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, x):
        return swiglu(self, x, self.cfg.intermediate_size)


class DecoderLayer(nn.Module):
    cfg: Glm4MoeLiteConfig
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg, ones = self.cfg, nn.initializers.ones
        h = rms_norm(x, _weight(self, "norm_1", (cfg.hidden_size,), ones), cfg.rms_norm_eps)
        with jax.named_scope("mla"):
            x = x + LatentAttention(cfg, name="attn")(h)
        h = rms_norm(x, _weight(self, "norm_2", (cfg.hidden_size,), ones), cfg.rms_norm_eps)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                return x + DenseMLP(cfg, name="mlp")(h)
        return x + SparseMoE(cfg, name="moe")(h)


class Glm4MoeLiteLM(nn.Module):
    """Causal LM. `token_type_ids` (the dialog federation's speaker segments)
    is taken and ignored: the architecture has no segment embedding."""

    cfg: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = True, token_type_ids=None):
        cfg = self.cfg
        x = _weight(self, "embed", (cfg.vocab_size, cfg.hidden_size))[input_ids]
        for i in range(cfg.num_hidden_layers):
            x = DecoderLayer(cfg, cfg.is_dense(i), name=f"layers_{i}")(x)
        with jax.named_scope("lm_head"):
            x = rms_norm(x, _weight(self, "norm_f", (cfg.hidden_size,), nn.initializers.ones),
                         cfg.rms_norm_eps)
            return x @ _weight(self, "lm_head", (cfg.hidden_size, cfg.vocab_size))
