"""models/qwen3_next.py: the trainer's Qwen3-Next against the plain reference
(benchmark/reference/qwen3_next.py) on seeded weights, loss and every gradient
leaf; the published widths from the committed configuration file; the blocks'
named scopes and the capture's reduction by them; the expert counters on their
way to the registry; `gpt2_train.py --model_config`."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counting_qwen3next
from benchmark.reference import qwen3_next as ref
from commefficient_tpu.models import qwen3_next
from commefficient_tpu.models.losses import make_lm_loss
from commefficient_tpu.models.qwen3_next import TINY, Qwen3NextConfig, Qwen3NextLM
from commefficient_tpu.obs import profiler
from commefficient_tpu.obs import registry as obreg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "qwen3next_80b_a3b_fetchsgd.json")
T = 21  # not a multiple of TINY's chunk of 8
# one Gated DeltaNet layer and one attention layer: half of TINY's compile time
TWO = dataclasses.replace(TINY, num_hidden_layers=2, full_attention_interval=2)


@pytest.fixture(scope="module")
def tiny():
    m = dataclasses.asdict(TWO)
    params = ref.init_params(jax.random.PRNGKey(1), ref.param_shapes(m))
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 2, T), 0, TINY.vocab_size)
    batches = [{"input_ids": i, "labels": i, "token_type_ids": jnp.zeros_like(i)} for i in ids]
    return m, params, batches


def test_model_equals_reference_loss_and_every_gradient_leaf(tiny):
    m, params, batches = tiny
    model = Qwen3NextLM(TWO)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), batches[0]["input_ids"], train=False))["params"]
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(lambda x: x.shape, want)
    loss_fn = make_lm_loss(model, train=True, model_metrics=True)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, {}, batches[0], None), has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.client_loss(p, batches[0], m)[0]))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 2e-5, (jax.tree_util.keystr(path), gap)
    # discrete outcomes: every token chooses the same experts on both sides
    sown = model.apply({"params": params}, batches[0]["input_ids"], mutable=["intermediates"])[1]
    for i in range(TWO.num_hidden_layers):
        got_choice = sown["intermediates"][f"layers_{i}"]["moe"]["moe_choices"][0]
        for b in range(2):
            np.testing.assert_array_equal(
                np.asarray(got_choice[b * T: (b + 1) * T]),
                np.asarray(ref.routing_choices(params, batches[0]["input_ids"][b], m)[i]))
    # the counters leave as sums, with the count that turns the maxima into a mean
    got = {k: float(v) for k, v in aux["metrics"].items()}
    layers, tokens = TWO.num_hidden_layers, 2 * T
    assert got["moe_assignments"] == layers * tokens * TINY.num_experts_per_tok
    assert 0 < got["moe_assignments_held"] < got["moe_assignments"]
    assert got["moe_load_max_count"] == layers
    assert got["moe_load_max_sum"] * TINY.num_experts >= got["moe_assignments_held"]
    assert got["count"] == 2 * (T - 1)


def test_client_chunk_scan_of_vmapped_gradients_equals_one_client_at_a_time(tiny):
    m, params, batches = tiny
    loss_fn = make_lm_loss(Qwen3NextLM(TWO), train=True, model_metrics=True)
    client_grad = jax.jit(jax.grad(lambda p, b: loss_fn(p, {}, b, None)[0]))
    stacked = jax.tree.map(lambda *a: jnp.stack(a).reshape((2, 2) + a[0].shape), *batches)

    def body(acc, chunk):
        g = jax.vmap(lambda b: client_grad(params, b))(chunk)
        return jax.tree.map(lambda a, b: a + b.sum(0), acc, g), None

    got, _ = jax.jit(lambda xs: jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params), xs))(stacked)
    want = jax.tree.map(lambda *g: sum(g), *[client_grad(params, b) for b in batches])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(a - b)) <= 2e-5 * float(jnp.linalg.norm(b)), path


def test_the_expert_block_of_every_chip_adds_up_to_the_uncut_layer(tiny):
    """The guide's share test on the model's own block: the routed parts of
    the two chips that hold experts 0-3 and 4-7, plus the shared expert once,
    against the reference's uncut layer (all 8 held)."""
    from commefficient_tpu.models.qwen3_next import SparseMoE

    m, _, _ = tiny
    whole = dict(m, num_experts=8, experts_held_first=0)
    p = ref.init_params(jax.random.PRNGKey(3), ref.param_shapes(dict(whole, num_hidden_layers=1)))
    p = p["layers_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 33, TINY.hidden_size))
    want = ref._moe(p, x[0], whole)
    no_shared = dict(p, shared_down=jnp.zeros_like(p["shared_down"]))
    total = ref._moe(dict(p, **{k: jnp.zeros_like(v) for k, v in p.items()
                                if k.startswith("experts_")}), x[0], whole)  # the shared expert, once
    for first in (0, 4):
        cfg = dataclasses.replace(TINY, num_experts=4, experts_held_first=first)
        share = {k: (v[first: first + 4] if k.startswith("experts_") else v)
                 for k, v in no_shared.items()}
        total = total + SparseMoE(cfg).apply({"params": share}, x)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_committed_configuration_has_the_published_widths():
    with open(CONFIG) as f:
        config = json.load(f)
    cfg = Qwen3NextConfig.from_model_block(config["model"])
    published = Qwen3NextConfig()  # the defaults are the public config.json
    cut = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(config["reduced"]) == cut
    for f in dataclasses.fields(cfg):
        if f.name not in cut:
            assert getattr(cfg, f.name) == getattr(published, f.name), f.name
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.vocab_size) == (4, 16, 18992)
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    assert cfg.vocab_size * 8 == 151936 and cfg.router_num_experts == 512
    shapes = jax.eval_shape(lambda: Qwen3NextLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))["params"]
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert d == config["expect_d"] == counting_qwen3next.params(config["model"]) == 424_340_544
    assert [cfg.is_attention(i) for i in range(4)] == [False, False, False, True]


@pytest.mark.parametrize("block, why", [
    ({"model_type": "gpt2"}, "not qwen3_next"),
    ({"mlp_only_layers": [0]}, "every layer"),
    ({"num_experts": 16, "router_num_experts": 8}, "outside the router"),
])
def test_a_model_block_of_another_kind_is_refused(block, why):
    with pytest.raises(ValueError, match=why):
        Qwen3NextConfig.from_model_block(block)


def _scoped_words(tiny):
    import re

    m, params, batches = tiny
    loss_fn = make_lm_loss(Qwen3NextLM(TWO), train=True, model_metrics=True)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, {}, batches[0], None)[0])).lower(
        params).as_text(debug_info=True)
    return re.findall(r'loc\("([^"]*)"', text)


def test_forward_and_backward_operations_carry_their_blocks_name(tiny):
    names = _scoped_words(tiny)
    for block in qwen3_next.SCOPES:  # BLOCK_SCOPES holds every model's
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block for n in names), block
        assert any(profiler.phase_of(n, profiler.BLOCK_SCOPES) == block and "transpose" in n
                   for n in names), block


def test_capture_is_reduced_a_second_time_by_block(tmp_path, monkeypatch, capsys):
    """One capture, two reductions: by round phase as before, and by kind of
    block inside client_grad. A capture that names no block publishes nothing
    and is no failure."""
    US = 1000
    scoped = [("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 0, 100 * US, ""), ("jit_step(1)", 100 * US, 1, "")]),
        ("XLA Ops", [
            ("%f1", 0, 30 * US, "jit(step)/client_grad/vmap(jvp(Qwen3NextLM))/layers_0/gdn/mixer/dot"),
            ("%f2", 30 * US, 20 * US,
             "jit(step)/client_grad/vmap(transpose(jvp(Qwen3NextLM)))/layers_0/transpose(jvp(gdn))/mixer/dot"),
            ("%rd", 50 * US, 10 * US, "jit(step)/client_grad/vmap(jvp(Qwen3NextLM))/layers_0/moe/moe_experts/while/body/ragged_dot"),
            ("%r", 60 * US, 4 * US, "jit(step)/client_grad/vmap(jvp(Qwen3NextLM))/layers_0/moe/moe_route/sort"),
            ("%l", 64 * US, 6 * US, "jit(step)/client_grad/vmap(jvp(Qwen3NextLM))/lm_head/dot"),
            ("%s", 70 * US, 20 * US, "jit(step)/server_topk/sort")])])]
    unscoped = [("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 0, 10 * US, "")]),
        ("XLA Ops", [("%f", 0, 10 * US, "jit(step)/client_grad/conv")])])]
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(profiler, "newest_capture", lambda d: d)
    reg = obreg.default()
    for planes, want in ((scoped, 2), (unscoped, 0)):
        monkeypatch.setattr(profiler, "load_capture", lambda path, planes=planes: (planes, [], {}))
        pw = profiler.ProfileWindow.parse("0:1", str(tmp_path), phases=("client_grad", "server_topk"))
        pw.on_dispatch(0)
        assert reg.gauge("profile_block_traced_rounds").value == 0
        pw.on_committed(2)
        err = capsys.readouterr().err
        assert reg.gauge("profile_block_traced_rounds").value == want
        assert ("by block" in err) == bool(want) and "no summary" not in err
    assert reg.gauge("profile_block_device_ms_gdn").value == pytest.approx(0.050 / 2)
    assert reg.gauge("profile_block_device_ms_moe_experts").value == pytest.approx(0.010 / 2)
    assert reg.gauge("profile_block_device_ms_moe_route").value == pytest.approx(0.004 / 2)
    assert reg.gauge("profile_block_device_ms_lm_head").value == pytest.approx(0.006 / 2)
    assert "profile_block_device_ms_other" not in reg.snapshot()
    reg.gauge("profile_traced_rounds").set(0)


def test_gpt2_train_builds_the_model_from_a_configuration_file(tmp_path, capsys):
    import gpt2_train

    block = dict(dataclasses.asdict(TWO), model_type="qwen3_next", vocab_size=300)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({"model": block}))
    reg = obreg.default()
    before = reg.counter("model_moe_assignments_total").value
    session = gpt2_train.main([
        "--model_config", str(path), "--num_clients", "8", "--num_workers", "4",
        "--client_chunk", "2", "--num_rounds", "2", "--eval_every", "2", "--mode", "uncompressed",
        "--seq_len", "24",
        "--local_batch_size", "1", "--lr_scale", "0.05", "--data_root", "/nonexistent"])
    out = capsys.readouterr().out
    assert "model: Qwen3Next" in out and session.round == 2
    counted = reg.counter("model_moe_assignments_total").value - before
    per_token = TWO.num_hidden_layers * TWO.num_experts_per_tok
    assert counted >= 2 * 4 * 24 * per_token and counted % (24 * per_token) == 0
    assert 0 < reg.counter("model_moe_assignments_held_total").value
    assert 1 <= reg.gauge("model_moe_expert_load_max").value <= 24
    with pytest.raises(SystemExit, match="--model_config builds its own"):
        gpt2_train.main(["--model_config", str(path), "--moe_experts", "4"])
