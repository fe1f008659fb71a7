"""models/lfm2_moe.py: the trainer's LFM2-24B-A2B against the plain reference
(benchmark/reference/lfm2_moe.py) on seeded weights, logits, loss and every
gradient leaf (the tied leaf gets both contributions); the short convolution
causal and equal to a per-token loop; the published widths from the committed
configuration file; the share of every chip adding up to the uncut layer; the
routers' bias as a buffer that no round changes; the blocks' named scopes;
`gpt2_train.py --model_config`."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counting_lfm2_moe as counting
from benchmark.reference import lfm2_moe as ref
from commefficient_tpu import models
from commefficient_tpu.models.lfm2_moe import (TINY, Lfm2MoeConfig, Lfm2MoeLM, ShortConv,
                                               SparseMoE)
from commefficient_tpu.models.losses import make_lm_loss
from commefficient_tpu.obs import profiler
from commefficient_tpu.obs import registry as obreg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "lfm2_24b_a2b_fetchsgd.json")
T = 21
BIAS = ref.BIAS


@pytest.fixture(scope="module")
def tiny():
    """TINY is a convolution layer with a dense feed-forward, then an
    attention and a convolution layer with expert blocks. The bias is 30 times
    its seeded width, so that it decides most tokens' choices."""
    m = TINY.model_block()
    params = ref.init_params(jax.random.PRNGKey(1), ref.param_shapes(m))
    buffers = jax.tree.map(lambda b: 30.0 * b,
                           ref.init_buffers(jax.random.PRNGKey(1), ref.buffer_shapes(m)))
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 2, T), 0, TINY.vocab_size)
    batches = [{"input_ids": i, "labels": i, "token_type_ids": jnp.zeros_like(i)} for i in ids]
    return m, params, buffers, batches


def test_model_equals_reference_logits_loss_and_every_gradient_leaf(tiny):
    m, params, buffers, batches = tiny
    assert Lfm2MoeConfig.from_model_block(m) == TINY
    model = Lfm2MoeLM(TINY)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batches[0]["input_ids"], train=False))
    shape = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
    assert shape(params) == shape(want["params"]) and shape(buffers) == shape(want["buffers"])
    assert [TINY.is_dense(i) for i in range(3)] == [True, False, False]
    assert [TINY.is_attention(i) for i in range(3)] == [False, True, False]
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
    # float32 on both sides at highest precision, as in tests/test_glm4_moe_lite.py:
    # what is left is the order of the sums (the experts' grouped products
    # against the dense scan, grouped query heads against repeated keys), a
    # few ulps through three layers
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-6)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert "lm_head" not in grads  # tied: the embedding's leaf carries the head's gradient too
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 2e-5, (jax.tree_util.keystr(path), gap)
    # the buffer comes back as it went in
    np.testing.assert_array_equal(np.asarray(aux["net_state"]["buffers"]["layers_1"]["moe"][BIAS]),
                                  np.asarray(buffers["layers_1"]["moe"][BIAS]))
    # discrete outcomes: every token chooses the same experts on both sides, in both expert layers
    sown = model.apply({"params": params, **net_state}, ids, mutable=["intermediates"])[1]
    for layer, name in enumerate(("layers_1", "layers_2")):
        got_choice = sown["intermediates"][name]["moe"]["moe_choices"][0]
        for b in range(2):
            np.testing.assert_array_equal(
                np.asarray(got_choice[b * T: (b + 1) * T]),
                np.asarray(ref.routing_choices(params, buffers, ids[b], m)[layer]))
    # the counters leave as sums over the two expert layers, with the counts
    # that turn them into means
    got = {k: float(v) for k, v in aux["metrics"].items()}
    tokens = 2 * T
    assert got["moe_assignments"] == 2 * tokens * TINY.num_experts_per_tok
    assert 0 < got["moe_assignments_held"] < got["moe_assignments"]
    assert got["moe_load_max_count"] == 2 and got["moe_bias_tokens"] == 2 * tokens
    assert 0 < got["moe_bias_flips"] <= 2 * tokens
    assert got["count"] == 2 * (T - 1)


def test_the_tied_leaf_gets_the_embeddings_and_the_heads_gradient(tiny):
    """d(loss)/d(embed) of the tied model = the two gradients of the same
    model with its head untied and set to the embedding's transpose."""
    m, params, buffers, batches = tiny
    ids = batches[0]["input_ids"][0]

    def untied_loss(embed, head):
        # the reference's own layers up to the final norm, then `head`
        h = embed[ids]
        for i in range(m["num_hidden_layers"]):
            bias = None if ref.is_dense(m, i) else buffers[f"layers_{i}"]["moe"][BIAS]
            h = ref._layer(params[f"layers_{i}"], bias, h, m, ref.is_attention(m, i))
        logits = ref._rms(h, params["norm_f"], m["norm_eps"]) @ head
        logp = jax.nn.log_softmax(logits[:-1])
        return -jnp.take_along_axis(logp, ids[1:, None], axis=-1).mean()

    loss_fn = make_lm_loss(Lfm2MoeLM(TINY), train=True)
    one = {k: v[:1] for k, v in batches[0].items()}
    with jax.default_matmul_precision("highest"):
        tied = jax.grad(lambda p: loss_fn(p, {"buffers": buffers}, one, None)[0])(params)["embed"]
        as_embed, as_head = jax.grad(untied_loss, argnums=(0, 1))(params["embed"], params["embed"].T)
    assert float(jnp.linalg.norm(as_head)) > 0.1 * float(jnp.linalg.norm(as_embed)) > 0
    np.testing.assert_allclose(np.asarray(tied), np.asarray(as_embed + as_head.T),
                               rtol=1e-4, atol=1e-7)


def test_the_short_convolution_is_causal_and_equals_a_per_token_loop():
    C, L = TINY.hidden_size, TINY.conv_L_cache
    p = ref.init_params(jax.random.PRNGKey(7), {"in_proj": (C, 3 * C), "conv": (L, C),
                                                "out_proj": (C, C)})
    p = dict(p, in_proj=10.0 * p["in_proj"], out_proj=10.0 * p["out_proj"])
    u = jax.random.normal(jax.random.PRNGKey(8), (2, T, C))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ShortConv(TINY).apply({"params": p}, u))
        # token by token, as the published description states it
        want = np.zeros((2, T, C), np.float32)
        w = np.asarray(p["conv"])
        for b in range(2):
            bcx = np.asarray(u[b] @ p["in_proj"])
            z = bcx[:, :C] * bcx[:, 2 * C:]
            for t in range(T):
                c = sum(w[L - 1 - back] * z[t - back] for back in range(L) if t - back >= 0)
                want[b, t] = np.asarray((bcx[t, C: 2 * C] * c) @ p["out_proj"])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
        # changing token t moves no output before t, and does move t
        t = 9
        moved = np.asarray(ShortConv(TINY).apply({"params": p}, u.at[:, t].add(1.0)))
    np.testing.assert_array_equal(moved[:, :t], got[:, :t])
    assert np.abs(moved[:, t] - got[:, t]).max() > 1e-3
    assert np.abs(moved[:, t + L - 1] - got[:, t + L - 1]).max() > 1e-3
    np.testing.assert_array_equal(moved[:, t + L:], got[:, t + L:])  # three taps, no further


def test_the_bias_decides_the_choice_and_gets_no_gradient(tiny):
    m, params, buffers, batches = tiny
    model = Lfm2MoeLM(TINY)
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
    loss_fn = make_lm_loss(Lfm2MoeLM(TINY), train=True, model_metrics=True)
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
    counts: 64 experts, 4 a token, 8 chips of 8 experts each. The 8 shares
    against the reference's uncut layer (all 64 held); there is no shared
    expert to count once."""
    whole = dict(TINY.model_block(), num_experts=64, router_num_experts=64,
                 experts_held_first=0, num_experts_per_tok=4)
    p = ref.init_params(jax.random.PRNGKey(3), ref.param_shapes(whole))["layers_1"]["moe"]
    p = dict(p, router=20.0 * p["router"])  # scores spread over (0, 1)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (64,))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 33, TINY.hidden_size))
    want = ref._moe(p, bias, x[0], whole)
    total, landed = jnp.zeros_like(want), 0.0
    for first in range(0, 64, 8):
        cfg = dataclasses.replace(TINY, num_experts=8, router_num_experts=64,
                                  experts_held_first=first, num_experts_per_tok=4)
        share = {k: (v[first: first + 8] if k.startswith("experts_") else v)
                 for k, v in p.items()}
        y, sown = SparseMoE(cfg).apply({"params": share, "buffers": {BIAS: bias}}, x,
                                       mutable=["metrics"])
        total = total + y[0]
        landed += float(sown["metrics"]["moe_assignments_held"][0])
    assert landed == 33 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_committed_configuration_has_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    cfg = Lfm2MoeConfig.from_model_block(config["model"])
    published = Lfm2MoeConfig()  # the defaults are the public config.json
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    # the cut in depth takes layer_types and num_dense_layers with it
    cut = set(config["reduced"]) | {"layer_types", "num_dense_layers"}
    for f in dataclasses.fields(cfg):
        if f.name not in cut:
            assert getattr(cfg, f.name) == getattr(published, f.name), f.name
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.vocab_size) == (5, 8, 8192)
    assert config["published"] == {"num_hidden_layers": 40, "num_experts": 64, "vocab_size": 65536}
    assert cfg.vocab_size * 8 == 65536 and cfg.router_num_experts == 64
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.conv_L_cache, cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_eps, cfg.rope_theta) == (
        2048, 32, 8, 64, 3, 11776, 1536, 4, 1, 1e-5, 1e6)
    # the top level keeps the published list and count; the model block's are
    # what the layers kept had there: layer 0 and one whole period
    kept = config["model"]["layers_kept"]
    assert kept == [0, 2, 3, 4, 5] and tuple(config["layer_types"]) == published.layer_types
    assert config["num_dense_layers"] == published.num_dense_layers == 2
    assert list(cfg.layer_types) == [config["layer_types"][i] for i in kept] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [cfg.is_dense(i) for i in range(5)] == [i < 2 for i in kept]
    assert published.layer_types.count("full_attention") == 10  # three to one
    # every other key of the model block that the file also states at its top level agrees
    assert all(config[k] == v for k, v in config["model"].items()
               if k in config and k not in ("layer_types", "num_dense_layers"))
    shapes = jax.eval_shape(lambda: Lfm2MoeLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))
    assert d == config["expect_d"] == counting.params(config["model"]) == 469_284_992
    assert "lm_head" not in shapes["params"]
    # the buffer: 64 a layer, four layers, outside d
    assert [s.shape for s in jax.tree.leaves(shapes["buffers"])] == [(64,)] * 4


@pytest.mark.parametrize("change, why", [
    ({"model_type": "glm4_moe_lite"}, "not lfm2_moe"),
    ({"conv_bias": True}, "only conv_bias = False"),
    ({"use_expert_bias": False}, "only use_expert_bias"),
    ({"tie_embedding": False}, "only tie_embedding"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "only rope_type"),
    ({"layer_types": ["conv", "sliding_attention", "conv"]}, "one of conv, full_attention"),
    ({"layer_types": ["conv"]}, "each of the 3 layers"),
    ({"num_key_value_heads": 3}, "key/value heads the query heads"),
    ({"num_experts": 16}, "outside the router"),
])
def test_a_model_block_of_another_kind_is_refused(change, why):
    with pytest.raises(ValueError, match=why):
        Lfm2MoeConfig.from_model_block(dict(TINY.model_block(), **change))


def test_forward_and_backward_operations_carry_their_blocks_name(tiny):
    m, params, buffers, batches = tiny
    loss_fn = make_lm_loss(Lfm2MoeLM(TINY), train=True, model_metrics=True)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {"buffers": buffers}, batches[0], None)[0])).lower(
        params).as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    from commefficient_tpu.models import lfm2_moe

    assert set(lfm2_moe.SCOPES) <= set(profiler.BLOCK_SCOPES)
    assert "moe_shared" not in lfm2_moe.SCOPES  # there is none
    for block in lfm2_moe.SCOPES:
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block for n in names), block
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block and "transpose" in n
                   for n in names), block
    assert not any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == "moe_shared" for n in names)


def test_gpt2_train_builds_the_model_and_no_round_changes_the_bias(tmp_path, capsys):
    """Three rounds under the sketch with weight decay on and a cohort of 3:
    the buffer in the session's state is the seeded one, bit for bit; the
    counters and the gauge of the bias's flips reach the registry."""
    import gpt2_train

    block = dict(TINY.model_block(), vocab_size=300)
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
    assert "model: Lfm2MoeLM" in out and session.round == 3
    cfg, model = models.from_model_block(block)
    seeded = model.init(jax.random.PRNGKey(7), jnp.zeros((1, 24), jnp.int32), train=False)
    for layer in ("layers_1", "layers_2"):
        got = session.state["net_state"]["buffers"][layer]["moe"][BIAS]
        want = seeded["buffers"][layer]["moe"][BIAS]
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(session.state["net_state"]) == {"buffers"}
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), session.state["params"],
                         seeded["params"])
    assert max(jax.tree.leaves(moved)) > 0  # the rounds did train
    # the buffer is outside d, and the tied head is counted once
    assert session.cfg.mode.d == sum(int(np.prod(x.shape)) for x in jax.tree.leaves(seeded["params"]))
    assert session.cfg.mode.d == counting.params(block)
    counted = reg.counter("model_moe_assignments_total").value - before
    assert counted >= 3 * 3 * 24 * 2 * cfg.num_experts_per_tok
    assert 0 <= reg.gauge("model_moe_bias_flips_share").value <= 1
