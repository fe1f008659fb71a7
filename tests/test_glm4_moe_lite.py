"""models/glm4_moe_lite.py: the trainer's GLM-4.7-Flash against the plain
reference (benchmark/reference/glm4_moe_lite.py) on seeded weights, logits,
loss and every gradient leaf; the published widths from the committed
configuration file; the share of every chip adding up to the uncut layer; the
selection bias as a buffer that no round changes; the blocks' named scopes;
the expert counters on their way to the registry; `gpt2_train.py
--model_config` and its dispatch on `model_type`."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counting_glm4_moe_lite as counting
from benchmark.reference import glm4_moe_lite as ref
from commefficient_tpu import models
from commefficient_tpu.models.glm4_moe_lite import TINY, Glm4MoeLiteConfig, Glm4MoeLiteLM, SparseMoE
from commefficient_tpu.models.losses import make_lm_loss
from commefficient_tpu.obs import profiler
from commefficient_tpu.obs import registry as obreg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
CONFIG = os.path.join(CONFIGS, "glm47_flash_fetchsgd.json")
T = 21
BIAS = ref.BIAS


@pytest.fixture(scope="module")
def tiny():
    """TINY is one dense layer and one expert layer. The bias is 30 times its
    seeded width, so that it decides most tokens' choices."""
    m = dataclasses.asdict(TINY)
    params = ref.init_params(jax.random.PRNGKey(1), ref.param_shapes(m))
    buffers = jax.tree.map(lambda b: 30.0 * b,
                           ref.init_buffers(jax.random.PRNGKey(1), ref.buffer_shapes(m)))
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 2, T), 0, TINY.vocab_size)
    batches = [{"input_ids": i, "labels": i, "token_type_ids": jnp.zeros_like(i)} for i in ids]
    return m, params, buffers, batches


def test_model_equals_reference_logits_loss_and_every_gradient_leaf(tiny):
    m, params, buffers, batches = tiny
    model = Glm4MoeLiteLM(TINY)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batches[0]["input_ids"], train=False))
    shape = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
    assert shape(params) == shape(want["params"]) and shape(buffers) == shape(want["buffers"])
    assert [TINY.is_dense(i) for i in range(2)] == [True, False]
    net_state = {"buffers": buffers}
    loss_fn = make_lm_loss(model, train=True, model_metrics=True)
    ids = batches[0]["input_ids"]
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params, **net_state}, ids)
        ref_logits = jax.vmap(lambda i: ref.sequence_logits(params, buffers, i, m))(ids)
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, net_state, batches[0], None), has_aux=True))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.client_loss(p, batches[0], m, buffers)[0]))(params)
    # float32 on both sides at highest precision: what is left is the order of
    # the sums (the experts' grouped products against the dense scan, the
    # scores as two products against one), a few ulps through two layers
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-6)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 2e-5, (jax.tree_util.keystr(path), gap)
    # the buffer comes back as it went in
    np.testing.assert_array_equal(np.asarray(aux["net_state"]["buffers"]["layers_1"]["moe"][BIAS]),
                                  np.asarray(buffers["layers_1"]["moe"][BIAS]))
    # discrete outcomes: every token chooses the same experts on both sides
    sown = model.apply({"params": params, **net_state}, ids, mutable=["intermediates"])[1]
    got_choice = sown["intermediates"]["layers_1"]["moe"]["moe_choices"][0]
    for b in range(2):
        np.testing.assert_array_equal(
            np.asarray(got_choice[b * T: (b + 1) * T]),
            np.asarray(ref.routing_choices(params, buffers, ids[b], m)[0]))
    # the counters leave as sums, with the counts that turn them into means
    got = {k: float(v) for k, v in aux["metrics"].items()}
    tokens = 2 * T
    assert got["moe_assignments"] == tokens * TINY.num_experts_per_tok
    assert 0 < got["moe_assignments_held"] < got["moe_assignments"]
    assert got["moe_load_max_count"] == 1 and got["moe_bias_tokens"] == tokens
    assert 0 < got["moe_bias_flips"] <= tokens
    assert got["count"] == 2 * (T - 1)


def test_the_bias_decides_the_choice_and_gets_no_gradient(tiny):
    m, params, buffers, batches = tiny
    model = Glm4MoeLiteLM(TINY)
    loss_fn = make_lm_loss(model, train=True, model_metrics=True)
    loss = lambda b: loss_fn(params, {"buffers": b}, batches[0], None)  # noqa: E731
    grad = jax.grad(lambda b: loss(b)[0])(buffers)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in jax.tree.leaves(grad))
    none = jax.tree.map(jnp.zeros_like, buffers)
    with_bias, without = loss(buffers), loss(none)
    assert float(without[1]["metrics"]["moe_bias_flips"]) == 0
    assert float(with_bias[0]) != float(without[0])


def test_client_chunk_scan_of_vmapped_gradients_equals_one_client_at_a_time(tiny):
    m, params, buffers, batches = tiny
    loss_fn = make_lm_loss(Glm4MoeLiteLM(TINY), train=True, model_metrics=True)
    client_grad = jax.jit(jax.grad(lambda p, b: loss_fn(p, {"buffers": buffers}, b, None)[0]))
    stacked = jax.tree.map(lambda *a: jnp.stack(a).reshape((2, 2) + a[0].shape), *batches)

    def body(acc, chunk):
        g = jax.vmap(lambda b: client_grad(params, b))(chunk)
        return jax.tree.map(lambda a, b: a + b.sum(0), acc, g), None

    got, _ = jax.jit(lambda xs: jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params), xs))(stacked)
    want = jax.tree.map(lambda *g: sum(g), *[client_grad(params, b) for b in batches])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(a - b)) <= 2e-5 * float(jnp.linalg.norm(b)), path


def test_the_expert_block_of_all_8_chips_adds_up_to_the_uncut_layer():
    """The guide's share test on the model's own block at the published
    counts: 64 experts, 4 a token, 8 chips of 8 experts each. The routed parts
    of the 8 shares, plus the shared expert once, against the reference's
    uncut layer (all 64 held)."""
    whole = dict(dataclasses.asdict(TINY), n_routed_experts=64, router_num_experts=64,
                 experts_held_first=0, num_experts_per_tok=4, num_hidden_layers=2)
    p = ref.init_params(jax.random.PRNGKey(3), ref.param_shapes(whole))["layers_1"]["moe"]
    p = dict(p, router=20.0 * p["router"])  # scores spread over (0, 1)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 33, TINY.hidden_size))
    want = ref._moe(p, bias, x[0], whole)
    no_experts = {k: (jnp.zeros_like(v) if k.startswith("experts_") else v) for k, v in p.items()}
    total = ref._moe(no_experts, bias, x[0], whole)  # the shared expert, once
    no_shared = dict(p, shared_down=jnp.zeros_like(p["shared_down"]))
    landed = 0.0
    for first in range(0, 64, 8):
        cfg = dataclasses.replace(TINY, n_routed_experts=8, router_num_experts=64,
                                  experts_held_first=first, num_experts_per_tok=4)
        share = {k: (v[first: first + 8] if k.startswith("experts_") else v)
                 for k, v in no_shared.items()}
        y, sown = SparseMoE(cfg).apply({"params": share, "buffers": {BIAS: bias}}, x,
                                       mutable=["metrics"])
        total = total + y[0]
        landed += float(sown["metrics"]["moe_assignments_held"][0])
    assert landed == 33 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_committed_configuration_has_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    cfg = Glm4MoeLiteConfig.from_model_block(config["model"])
    published = Glm4MoeLiteConfig()  # the defaults are the public config.json
    cut = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(config["reduced"]) == cut
    for f in dataclasses.fields(cfg):
        if f.name not in cut:
            assert getattr(cfg, f.name) == getattr(published, f.name), f.name
    assert (cfg.num_hidden_layers, cfg.n_routed_experts, cfg.vocab_size) == (5, 8, 19360)
    assert config["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64,
                                   "vocab_size": 154880}
    assert cfg.vocab_size * 8 == 154880 and cfg.router_num_experts == 64
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.rms_norm_eps, cfg.rope_theta) == (
        2048, 20, 768, 512, 192, 64, 256, 10240, 1536, 4, 1.8, 1e-5, 1e6)
    # every key of the model block that the file also states at its top level agrees
    assert all(config[k] == v for k, v in config["model"].items() if k in config)
    shapes = jax.eval_shape(lambda: Glm4MoeLiteLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))
    assert d == config["expect_d"] == counting.params(config["model"]) == 591_294_720
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    # the buffer: 64 a layer, four layers, outside d
    assert [s.shape for s in jax.tree.leaves(shapes["buffers"])] == [(64,)] * 4


@pytest.mark.parametrize("name", sorted(os.listdir(CONFIGS)))
def test_every_family_is_built_from_its_committed_model_block(name):
    """`gpt2_train.py --model_config` dispatches on `model_type`: each
    configuration file that names one builds (as shapes) to its expect_d."""
    with open(os.path.join(CONFIGS, name)) as f:
        config = json.load(f)
    block = config.get("model", {})
    if block.get("model_type") not in models.FAMILIES:
        with pytest.raises(ValueError, match="glm4_moe_lite, lfm2_moe, qwen3_next"):
            models.from_model_block(block)
        return
    cfg, model = models.from_model_block(block)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))["params"]
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == config["expect_d"]
    assert type(model).__name__ == models.FAMILIES[block["model_type"]][2]


@pytest.mark.parametrize("block, why", [
    ({"model_type": "qwen3_next"}, "not glm4_moe_lite"),
    ({"model_type": "lfm2_moe"}, "not glm4_moe_lite"),
    ({"model_type": "glm4_moe_lite", "n_group": 8}, "only n_group = 1"),
    ({"model_type": "glm4_moe_lite", "rope_scaling": {"type": "yarn"}}, "only rope_scaling"),
    ({"model_type": "glm4_moe_lite", "num_key_value_heads": 4}, "every query head"),
    ({"model_type": "glm4_moe_lite", "n_routed_experts": 16, "router_num_experts": 8},
     "outside the router"),
])
def test_a_model_block_of_another_kind_is_refused(block, why):
    with pytest.raises(ValueError, match=why):
        Glm4MoeLiteConfig.from_model_block(block)


def test_forward_and_backward_operations_carry_their_blocks_name(tiny):
    m, params, buffers, batches = tiny
    loss_fn = make_lm_loss(Glm4MoeLiteLM(TINY), train=True, model_metrics=True)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {"buffers": buffers}, batches[0], None)[0])).lower(
        params).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    from commefficient_tpu.models import glm4_moe_lite, lfm2_moe, qwen3_next

    # the profiler's second reduction knows every model's blocks and no other
    assert set(profiler.BLOCK_SCOPES) == (
        set(glm4_moe_lite.SCOPES) | set(qwen3_next.SCOPES) | set(lfm2_moe.SCOPES))
    assert len(set(profiler.BLOCK_SCOPES)) == len(profiler.BLOCK_SCOPES)
    for block in glm4_moe_lite.SCOPES:
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block for n in names), block
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block and "transpose" in n
                   for n in names), block


def test_gpt2_train_builds_the_model_and_no_round_changes_the_bias(tmp_path, capsys):
    """Three rounds under the sketch with weight decay on and a cohort of 3
    (a mean of three equal float32 copies need not be the copy): the buffer
    in the session's state is the seeded one, bit for bit; the counters and
    the gauge of the bias's flips reach the registry."""
    import gpt2_train

    block = dict(dataclasses.asdict(TINY), model_type="glm4_moe_lite", vocab_size=300)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({"model": block}))
    reg = obreg.default()
    before = reg.counter("model_moe_assignments_total").value
    argv = ["--model_config", str(path), "--num_clients", "9", "--num_workers", "3",
            "--client_chunk", "1", "--num_rounds", "3", "--eval_every", "3", "--mode", "sketch",
            "--num_cols", "4096", "--num_rows", "3", "--k", "200", "--weight_decay", "5e-4",
            "--seq_len", "24", "--local_batch_size", "1", "--lr_scale", "0.05", "--seed", "7",
            "--data_root", "/nonexistent"]
    session = gpt2_train.main(argv)
    out = capsys.readouterr().out
    assert "model: Glm4MoeLiteLM" in out and session.round == 3
    cfg, model = models.from_model_block(block)
    seeded = model.init(jax.random.PRNGKey(7), jnp.zeros((1, 24), jnp.int32), train=False)
    got = session.state["net_state"]["buffers"]["layers_1"]["moe"][BIAS]
    want = seeded["buffers"]["layers_1"]["moe"][BIAS]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(session.state["net_state"]) == {"buffers"}
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), session.state["params"],
                         seeded["params"])
    assert max(jax.tree.leaves(moved)) > 0  # the rounds did train
    # the buffer is outside d
    assert session.cfg.mode.d == sum(int(np.prod(x.shape)) for x in jax.tree.leaves(seeded["params"]))
    counted = reg.counter("model_moe_assignments_total").value - before
    assert counted >= 3 * 3 * 24 * cfg.num_experts_per_tok
    assert 0 <= reg.gauge("model_moe_bias_flips_share").value <= 1
    with pytest.raises(SystemExit, match="glm4_moe_lite, lfm2_moe, qwen3_next"):
        path.write_text(json.dumps({"model": dict(block, model_type="llama")}))
        gpt2_train.main(argv)
