"""ops/attention.py: the Pallas kernel pair, run by the interpreter, against
the jnp form it replaces on the TPU (its oracle), for the output and the
gradients of q, k and v at the four layouts the expert models run (grouped
heads of 64, 128 and 256, and latent attention's 20 heads of 256 | 256 whose
one rotary key head is broadcast beside the latent keys), at T = 256 with one
block and with blocks of 128 (tiles below the diagonal and on it); under
`vmap`, as the engine calls it; and each expert family's loss and gradients
with its attention steered to the kernel, against the same model on the jnp
path.

Tolerances: the kernel rounds every product's operands to bf16, as JAX's
default precision does on the TPU's MXU, and the jnp form on the CPU rounds
none: 8 bits of mantissa are a relative rounding of up to 2^-9 an operand, and
the gaps below read 1.3e-3 to 3.8e-3. A wrong mask, scale, head mapping or
log-sum-exp reads 0.1 or more."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models.losses import make_lm_loss
from commefficient_tpu.ops import attention

T = 256
# (key/value heads, query heads a key/value head, d_qk, d_v): LFM2, Nemotron,
# Qwen3-Next and GLM (MLA, built from its parts in _mla_inputs)
LAYOUTS = {"gqa_64": (8, 4, 64, 64), "gqa_128": (2, 16, 128, 128),
           "gqa_256": (2, 8, 256, 256), "mla_256": (20, 1, 256, 256)}
NOPE, ROPE = 192, 64
# the relative norm gap of the output and of each gradient, kernel against
# oracle: bf16 operands against float32 ones (module docstring)
GAP = 1e-2


def _gap(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(attention, "implementation", lambda *a: "interpret")


def _inputs(name, B=1):
    KV, n, d_qk, d_v = LAYOUTS[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name) + KV), 4)
    w = jax.random.normal(ks[3], (B, T, KV, n, d_v))
    if name.startswith("mla"):
        # the parts the model has: per-head latent keys and ONE rotary key
        # head, broadcast to every head inside the function differentiated,
        # so that the rotary head's gradient sums over the heads
        parts = (jax.random.normal(ks[0], (B, T, KV, n, d_qk)),
                 jax.random.normal(ks[1], (B, T, KV, NOPE)),
                 jax.random.normal(ks[2], (B, T, ROPE)),
                 jax.random.normal(ks[0] + 1, (B, T, KV, d_v)))

        def keys(q, k_nope, k_rope, v):
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], k_nope.shape[:3] + (ROPE,))], -1)
            return q, k, v

        return parts, keys, w, (NOPE + ROPE) ** -0.5
    parts = (jax.random.normal(ks[0], (B, T, KV, n, d_qk)),
             jax.random.normal(ks[1], (B, T, KV, d_qk)),
             jax.random.normal(ks[2], (B, T, KV, d_v)))
    return parts, lambda *a: a, w, d_qk ** -0.5


def _value_and_grads(fn, parts, keys, w):
    loss = lambda *p: (fn(*keys(*p)) * w).sum()  # noqa: E731
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(len(parts)))))(*parts)


@pytest.mark.parametrize("blocks", [None, 128], ids=["one_block", "blocks_128"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_kernel_equals_the_jnp_form_output_and_gradients(name, blocks, interpret, monkeypatch):
    if blocks is not None:
        monkeypatch.setattr(attention, "BLOCK", blocks)
    parts, keys, w, scale = _inputs(name)
    kernel = lambda q, k, v: attention.causal_attention(q, k, v, scale=scale)  # noqa: E731
    oracle = lambda q, k, v: attention.reference_attention(q, k, v, scale)  # noqa: E731
    np.testing.assert_array_less(_gap(jax.jit(kernel)(*keys(*parts)),
                                      oracle(*keys(*parts))), GAP)
    (_, got), (_, want) = (_value_and_grads(f, parts, keys, w) for f in (kernel, oracle))
    gaps = [_gap(a, b) for a, b in zip(got, want)]
    assert max(gaps) < GAP, gaps


def test_a_planted_error_is_far_outside_the_tolerance(interpret):
    """The tolerance tells a right kernel from a wrong one: the oracle with
    the key width's scale in the place of the latent one (192^-0.5 for
    256^-0.5) reads a gap over ten times GAP from the kernel."""
    parts, keys, w, scale = _inputs("mla_256")
    got = attention.causal_attention(*keys(*parts), scale=scale)
    wrong = attention.reference_attention(*keys(*parts), NOPE ** -0.5)
    assert _gap(got, wrong) > 10 * GAP


def test_kernel_under_vmap_equals_one_call_a_client(interpret, monkeypatch):
    """The engine calls the model under `vmap` over clients: the kernels'
    batching adds a grid axis, each client's result its own."""
    monkeypatch.setattr(attention, "BLOCK", 128)
    parts, keys, w, scale = _inputs("gqa_128", B=2)
    split = [tuple(p[:, None] for p in parts), keys, w[:, None]]

    def loss(q, k, v, w):
        return (attention.causal_attention(q, k, v, scale=scale) * w).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    batched = jax.jit(jax.vmap(grad))(*split[0], split[2])
    for c in range(2):
        one = grad(*(p[c] for p in split[0]), split[2][c])
        for a, b in zip(batched, one):
            np.testing.assert_allclose(np.asarray(a[c]), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_the_choice_is_the_platform_and_the_shapes(monkeypatch):
    """On a CPU the jnp form; on a TPU the kernel where T is a multiple of
    every block, else the jnp form (no flag, variable or configuration key)."""
    assert attention.implementation(2048, 256, 256, 1) == "jnp"  # this backend
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert attention.implementation(2048, 256, 256, 1) == "pallas"
    assert attention.implementation(256, 64, 64, 4) == "pallas"
    assert attention.implementation(2000, 128, 128, 16) == "jnp"
    assert attention.implementation(40, 16, 16, 2) == "jnp"
    # a backward kernel whose whole-sequence blocks would not fit its VMEM
    assert attention.implementation(16384, 256, 256, 1) == "jnp"


def _family(module_name, T_model):
    module = importlib.import_module(f"commefficient_tpu.models.{module_name}")
    cfg = module.TINY
    model_cls = {"qwen3_next": "Qwen3NextLM", "glm4_moe_lite": "Glm4MoeLiteLM",
                 "lfm2_moe": "Lfm2MoeLM", "nemotron_h": "NemotronHLM"}[module_name]
    if module_name == "qwen3_next":
        cfg = dataclasses.replace(cfg, gdn_chunk=64)
    if module_name == "nemotron_h":
        cfg = dataclasses.replace(cfg, chunk_size=64)
    model = getattr(module, model_cls)(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, T_model), 0, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(4), ids, train=False)
    params = variables.pop("params")
    batch = {"input_ids": ids, "labels": ids, "token_type_ids": jnp.zeros_like(ids)}
    loss_fn = make_lm_loss(model, train=True)
    return jax.jit(jax.value_and_grad(lambda p: loss_fn(p, dict(variables), batch, None)[0]))(params)


# the attention layers' own projections
PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "q_a_proj", "q_b_proj", "kv_a_proj",
               "kv_b_proj")


@pytest.mark.parametrize("family", ["qwen3_next", "glm4_moe_lite", "lfm2_moe", "nemotron_h"])
def test_each_familys_loss_and_gradients_on_the_kernel_equal_the_jnp_path(family, monkeypatch):
    """The model's own call, steered to the kernel (interpreter, blocks of
    128 over 256 tokens) against the same model on the jnp path: the loss,
    the whole gradient and each attention projection's gradient by their
    relative norm gaps (bf16 operands in the attention layers against float32
    ones, module docstring; they read 2e-7 at most for the loss, 5e-5 to
    9.3e-4 for the whole gradient, under 4e-3 for a projection). Single
    leaves of an expert block are not compared: one rounding may flip a
    token's chosen experts, which moves a held expert's gradient by a few
    percent (LFM2's last layer reads up to 0.03)."""
    want_loss, want = _family(family, T)
    monkeypatch.setattr(attention, "implementation", lambda *a: "interpret")
    monkeypatch.setattr(attention, "BLOCK", 128)
    got_loss, got = _family(family, T)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat = lambda tree: jnp.concatenate([x.ravel() for x in jax.tree.leaves(tree)])  # noqa: E731
    assert _gap(flat(got), flat(want)) < GAP
    compared = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        if path[-1].key in PROJECTIONS:
            assert _gap(a, b) < GAP, jax.tree_util.keystr(path)
            compared += 1
    assert compared >= 4
