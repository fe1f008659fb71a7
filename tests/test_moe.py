"""Expert-parallel MoE tests: the dispatch/combine einsum path must match
the dense oracle when capacity is not binding, degrade to pass-through on
overflow, and run sharded over an 'expert' mesh axis with identical
results."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from commefficient_tpu.ops import moe

E, D, H = 8, 16, 32


def _expert_fn(p, h):
    return jnp.tanh(h @ p["wi"]) @ p["wo"]


def _params(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return (
        0.3 * jax.random.normal(k1, (D, E)),  # router
        {
            "wi": 0.3 * jax.random.normal(k2, (E, D, H)),
            "wo": 0.3 * jax.random.normal(k3, (E, H, D)),
        },
    )


def test_moe_matches_dense_oracle_when_capacity_ample():
    router, experts = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    # capacity_factor = E guarantees C >= T, so nothing is ever dropped
    y, aux = moe.moe_ffn(x, router, experts, _expert_fn, capacity_factor=float(E))
    want = moe.dense_oracle(x, router, experts, _expert_fn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(aux) > 0.0


def test_moe_overflow_passes_through():
    """capacity 1 token/expert: dropped tokens keep x (identity), kept ones
    get gate * expert_out + (1-gate) * x."""
    router, experts = _params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (64, D))
    y, _ = moe.moe_ffn(x, router, experts, _expert_fn, capacity_factor=E / 64.0)
    # with C = 1, at most E tokens are routed; everyone else is identity
    changed = (np.abs(np.asarray(y - x)) > 1e-6).any(axis=1).sum()
    assert changed <= E
    assert changed > 0


def test_moe_sharded_over_expert_axis_matches():
    mesh = Mesh(np.array(jax.devices()[:8]), ("expert",))
    router, experts = _params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (64, D))
    ref, aux_ref = jax.jit(
        lambda x, r, e: moe.moe_ffn(x, r, e, _expert_fn, capacity_factor=2.0)
    )(x, router, experts)

    experts_sharded = jax.device_put(experts, NamedSharding(mesh, P("expert")))
    x_repl = jax.device_put(x, NamedSharding(mesh, P()))
    got, aux = jax.jit(
        lambda x, r, e: moe.moe_ffn(x, r, e, _expert_fn, capacity_factor=2.0)
    )(x_repl, router, experts_sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)


def test_moe_grads_flow_to_router_and_experts():
    router, experts = _params(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (32, D))

    def loss(r, e):
        y, aux = moe.moe_ffn(x, r, e, _expert_fn, capacity_factor=2.0)
        return jnp.mean(y**2) + 0.01 * aux

    gr, ge = jax.grad(loss, argnums=(0, 1))(router, experts)
    assert float(jnp.abs(gr).sum()) > 0
    assert all(float(jnp.abs(g).sum()) > 0 for g in jax.tree.leaves(ge))


def test_moe_gpt2_trains_federated():
    """GPT-2 with MoE blocks (cfg.moe_experts) trains through the federated
    engine: loss falls and the sown load-balancing aux reaches the metrics."""
    import dataclasses

    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.federated import engine
    from commefficient_tpu.models.gpt2 import TINY, GPT2LMHead
    from commefficient_tpu.models.losses import make_lm_loss
    from commefficient_tpu.modes.config import ModeConfig

    T = 32
    cfg = dataclasses.replace(TINY, n_positions=T, moe_experts=4)
    model = GPT2LMHead(cfg)
    ids0 = jnp.zeros((1, T), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0, train=False)["params"]
    assert "moe_mlp" in params["h_1"] and "mlp" in params["h_0"]  # every 2nd
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(mode="uncompressed", d=d, momentum_type="virtual", error_type="none")
    ecfg = engine.EngineConfig(mode=mcfg)
    state = engine.init_server_state(ecfg, params, {})
    loss_fn = make_lm_loss(model, train=True, moe_aux_coef=0.01)
    step = jax.jit(engine.make_round_step(loss_fn, ecfg))

    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 2, T), 0, cfg.vocab_size)
    batch = {"input_ids": ids, "labels": ids, "mask": jnp.ones((4, 2, T))}
    first, best = None, float("inf")
    for rnd in range(14):
        state, _, m = step(state, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(rnd))
        nll = float(m["loss_sum"]) / float(m["count"])
        first = nll if first is None else first
        best = min(best, nll)
        # sum/count pair: the engine sums metrics over the W=4 clients
        assert float(m["moe_aux_sum"]) > 0.0
        assert float(m["moe_aux_count"]) == 4.0
    assert best < first * 0.9, (first, best)


def test_moe_checkpoint_roundtrip(tmp_path):
    """MoE params (router/wi/wo under moe_mlp) survive the orbax
    checkpoint/restore path bit-for-bit via the standard session flow."""
    import dataclasses

    import gpt2_train
    from commefficient_tpu.utils import checkpoint as ckpt
    from commefficient_tpu.utils.config import make_parser, resolve_defaults
    from jax.flatten_util import ravel_pytree

    argv = [
        "--model_size", "tiny", "--num_clients", "10", "--num_workers", "2",
        "--mode", "uncompressed", "--moe_experts", "4", "--seq_len", "32",
        "--local_batch_size", "2", "--data_root", "/nonexistent",
        "--checkpoint_dir", str(tmp_path),
    ]
    args = resolve_defaults(make_parser("gpt2").parse_args(argv))
    session = gpt2_train.build(args)[0]
    for _ in range(2):
        session.run_round(0.05)
    ckpt.save(str(tmp_path), session)
    want = np.asarray(ravel_pytree(session.state["params"])[0])

    session2 = gpt2_train.build(args)[0]
    ckpt.restore(ckpt.latest(str(tmp_path)), session2)
    got = np.asarray(ravel_pytree(session2.state["params"])[0])
    np.testing.assert_array_equal(got, want)
    assert session2.round == 2


# ------------------------------------------------------------------
# top-k routing over the experts held here (ops/moe.topk_moe_ffn)

TK_T, TK_D, TK_F, TK_E, TK_K = 48, 16, 8, 12, 3


def _topk_params(key, held_count):
    ks = jax.random.split(key, 4)
    experts = {"gate": 0.3 * jax.random.normal(ks[1], (held_count, TK_D, TK_F)),
               "up": 0.3 * jax.random.normal(ks[2], (held_count, TK_D, TK_F)),
               "down": 0.3 * jax.random.normal(ks[3], (held_count, TK_F, TK_D))}
    return jax.random.normal(ks[0], (TK_D, TK_E)), experts


@pytest.mark.parametrize("held, block_rows", [
    ((0, 4), 16), ((5, 4), 1024), ((8, 4), 40), ((0, 12), 16), ((11, 1), 8)])
def test_topk_moe_matches_dense_oracle_values_and_gradients(held, block_rows):
    """block_rows 16 / 40 / 8: the T * k = 144 sorted assignments take several
    blocks, the last one ragged, and groups straddle block edges."""
    router, experts = _topk_params(jax.random.PRNGKey(0), held[1])
    x = jax.random.normal(jax.random.PRNGKey(1), (TK_T, TK_D))
    y, counts = moe.topk_moe_ffn(x, router, experts, held, TK_K, block_rows)
    want = moe.topk_dense_oracle(x, router, experts, held, TK_K)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    chosen, _ = moe.topk_route(x, router, TK_K)
    here = (chosen >= held[0]) & (chosen < held[0] + held[1])
    assert float(counts["assignments"]) == TK_T * TK_K
    assert float(counts["assignments_held"]) == int(here.sum())
    assert float(counts["expert_load_max"]) == max(
        int((chosen == e).sum()) for e in range(held[0], held[0] + held[1]))

    def grads(fn):
        return jax.grad(lambda x, r, e: (fn(x, r, e) ** 2).sum(), argnums=(0, 1, 2))(
            x, router, experts)

    got = grads(lambda x, r, e: moe.topk_moe_ffn(x, r, e, held, TK_K, block_rows)[0])
    ref = grads(lambda x, r, e: moe.topk_dense_oracle(x, r, e, held, TK_K))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_topk_weights_are_renormalised_over_the_chosen():
    router, _ = _topk_params(jax.random.PRNGKey(2), 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (TK_T, TK_D))
    chosen, weights = moe.topk_route(x, router, TK_K)
    probs = jax.nn.softmax(x @ router, axis=-1)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(jax.lax.top_k(probs, TK_K)[1]))


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: three chips hold experts 0-3, 4-7
    and 8-11 of the same layer; their routed parts add up to what one chip
    holding all twelve computes."""
    router, all_experts = _topk_params(jax.random.PRNGKey(4), TK_E)
    x = jax.random.normal(jax.random.PRNGKey(5), (TK_T, TK_D))
    whole, counts = moe.topk_moe_ffn(x, router, all_experts, (0, TK_E), TK_K)
    assert float(counts["assignments_held"]) == TK_T * TK_K
    parts, landed = 0.0, 0.0
    for first in (0, 4, 8):
        share = jax.tree.map(lambda a: a[first: first + 4], all_experts)
        y, c = moe.topk_moe_ffn(x, router, share, (first, 4), TK_K)
        parts, landed = parts + y, landed + float(c["assignments_held"])
    assert landed == TK_T * TK_K
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_no_token_is_dropped_when_every_assignment_lands_on_one_held_expert():
    """The router sends every token to expert 6 (top-1): its group is all T
    sorted rows, three whole blocks of 16 where an even share is 4 rows, and
    every token gets its expert's output."""
    _, experts = _topk_params(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(jax.random.PRNGKey(7), (TK_T, TK_D))
    router = jnp.zeros((TK_D, TK_E)).at[:, 6].set(1e3 * jnp.sign(x.sum(0)))
    x = jnp.abs(x) * jnp.sign(x.sum(0))  # x . router[:, 6] > 0 for every token
    y, counts = moe.topk_moe_ffn(x, router, experts, (4, 4), 1, 16)
    assert float(counts["assignments_held"]) == TK_T == float(counts["expert_load_max"])
    one = jax.tree.map(lambda a: a[2], experts)
    want = (jax.nn.silu(x @ one["gate"]) * (x @ one["up"])) @ one["down"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(y).min(axis=1).max()) > 0  # no row passed through as zero


def test_topk_moe_under_vmap_over_clients_inside_a_scan():
    """engine._weighted_client_reduce with --client_chunk: per-client gradients
    by vmap (weights unbatched, their cotangents batched) inside lax.scan; the
    loops over blocks take the batch one client at a time, each for as many
    blocks as that client's assignments fill."""
    held, W, C = (5, 4), 6, 2
    router, experts = _topk_params(jax.random.PRNGKey(8), held[1])
    xs = jax.random.normal(jax.random.PRNGKey(9), (W, TK_T, TK_D))

    def client_grad(x):
        return jax.grad(lambda r, e: (moe.topk_moe_ffn(x, r, e, held, TK_K, 32)[0] ** 2).sum(),
                        argnums=(0, 1))(router, experts)

    def body(acc, xb):
        g = jax.vmap(client_grad)(xb)
        return jax.tree.map(lambda a, b: a + b.sum(0), acc, g), None

    init = jax.tree.map(jnp.zeros_like, (router, experts))
    got, _ = jax.jit(lambda xs: jax.lax.scan(body, init, xs))(xs.reshape(W // C, C, TK_T, TK_D))
    want = init
    for x in xs:
        want = jax.tree.map(jnp.add, want, client_grad(x))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_topk_moe_under_grad_of_a_vmap_over_clients_inside_a_scan():
    """engine._weighted_client_reduce's fused path with --client_chunk: ONE
    gradient of the masked sum of the clients' losses, the vmap over clients
    inside it (weights unbatched, so their cotangents come back summed over
    the chunk), inside lax.scan. The held experts' custom_vmap / custom_vjp
    leaves now sit under grad-of-vmap and not vmap-of-grad; a masked client's
    rows (NaN here) are zeroed before the forward pass and add nothing."""
    held, W, C = (5, 4), 6, 2
    router, experts = _topk_params(jax.random.PRNGKey(8), held[1])
    xs = jax.random.normal(jax.random.PRNGKey(9), (W, TK_T, TK_D))
    live = jnp.asarray([1., 1., 0., 1., 0., 1.])
    poisoned = xs.at[2].set(jnp.nan)

    def client_loss(x, r, e):
        return (moe.topk_moe_ffn(x, r, e, held, TK_K, 32)[0] ** 2).sum()

    def body(acc, chunk):
        xb, wb = chunk
        xb = jnp.where(wb[:, None, None] > 0, xb, 0)
        g = jax.grad(lambda r, e: jnp.where(
            wb > 0, jax.vmap(lambda x: client_loss(x, r, e))(xb), 0).sum(),
            argnums=(0, 1))(router, experts)
        return jax.tree.map(jnp.add, acc, g), None

    init = jax.tree.map(jnp.zeros_like, (router, experts))
    got, _ = jax.jit(lambda xs, w: jax.lax.scan(body, init, (xs, w)))(
        poisoned.reshape(W // C, C, TK_T, TK_D), live.reshape(W // C, C))
    want = init
    for x, w in zip(xs, live):
        if w > 0:
            want = jax.tree.map(jnp.add, want, jax.grad(
                lambda r, e: client_loss(x, r, e), argnums=(0, 1))(router, experts))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_grouped_rows_past_the_last_group_are_zero():
    x = jnp.ones((10, 4))
    w = jnp.stack([jnp.full((4, 3), 1.0), jnp.full((4, 3), 2.0)])
    out = moe._grouped(x, w, jnp.asarray([3, 4], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out[:, 0]), [4, 4, 4, 8, 8, 8, 8, 0, 0, 0])


# ------------------------------------------------------------------
# the routing rule as an argument (PR 31): sigmoid scores, a selection bias
# that chooses and does not weigh, a scale

import functools


def _sigmoid_rule(bias, scale=1.8):
    return functools.partial(moe.sigmoid_topk_route, bias=bias, scale=scale)


def test_sigmoid_rule_selects_by_biased_scores_and_weighs_by_unbiased():
    router, _ = _topk_params(jax.random.PRNGKey(10), 1)
    x = jax.random.normal(jax.random.PRNGKey(11), (TK_T, TK_D))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(12), (TK_E,))
    s = jax.nn.sigmoid(x @ router)
    chosen, weights = moe.sigmoid_topk_route(x, router, TK_K, bias=bias, scale=1.8)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(jax.lax.top_k(s + bias, TK_K)[1]))
    top = jnp.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(weights),
                               np.asarray(1.8 * top / (top.sum(-1, keepdims=True) + 1e-20)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.8, rtol=1e-6)
    # no bias, no scale: the k largest scores, weights summing to 1
    plain, w1 = moe.sigmoid_topk_route(x, router, TK_K)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(jax.lax.top_k(s, TK_K)[1]))
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 1.0, rtol=1e-6)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(chosen), -1)).any()


def test_a_planted_bias_changes_the_choice_and_not_the_weights_formula():
    """A bias of +10 on expert 7 puts it in every token's choice (scores lie
    in (0, 1)); its weight is still its own unbiased score's share."""
    router, _ = _topk_params(jax.random.PRNGKey(13), 1)
    x = jax.random.normal(jax.random.PRNGKey(14), (TK_T, TK_D))
    s = jax.nn.sigmoid(x @ router)
    before, _ = moe.sigmoid_topk_route(x, router, TK_K)
    assert not bool((before == 7).any(-1).all())
    planted = jnp.zeros((TK_E,)).at[7].set(10.0)
    chosen, weights = moe.sigmoid_topk_route(x, router, TK_K, bias=planted, scale=1.8)
    assert bool((chosen[:, 0] == 7).all())  # s + 10 is the largest of every row
    top = jnp.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(weights[:, 0]),
                               np.asarray(1.8 * s[:, 7] / top.sum(-1)), rtol=1e-6)
    # the other k - 1 are the best of the rest, as before the bias
    rest = jax.lax.top_k(s.at[:, 7].set(-1.0), TK_K - 1)[1]
    np.testing.assert_array_equal(np.asarray(chosen[:, 1:]), np.asarray(rest))
    # the bias gets no gradient: the choice is discrete and the weights do not read it
    g = jax.grad(lambda b: moe.sigmoid_topk_route(x, router, TK_K, bias=b, scale=1.8)[1].sum())(planted)
    assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("held, block_rows", [((0, 4), 16), ((5, 4), 1024), ((8, 4), 40)])
def test_topk_moe_matches_dense_oracle_under_the_sigmoid_rule(held, block_rows):
    router, experts = _topk_params(jax.random.PRNGKey(15), held[1])
    x = jax.random.normal(jax.random.PRNGKey(16), (TK_T, TK_D))
    rule = _sigmoid_rule(0.5 * jax.random.normal(jax.random.PRNGKey(17), (TK_E,)))
    y, counts = moe.topk_moe_ffn(x, router, experts, held, TK_K, block_rows, route=rule)
    want = moe.topk_dense_oracle(x, router, experts, held, TK_K, route=rule)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-5)
    chosen, _ = rule(x, router, TK_K)
    np.testing.assert_array_equal(np.asarray(counts["experts"]), np.asarray(chosen))
    here = (chosen >= held[0]) & (chosen < held[0] + held[1])
    assert float(counts["assignments_held"]) == int(here.sum())
    # another function than under the softmax rule
    assert float(jnp.abs(y - moe.topk_moe_ffn(x, router, experts, held, TK_K, block_rows)[0]).max()) > 1e-3

    def grads(fn):
        return jax.grad(lambda x, r, e: (fn(x, r, e) ** 2).sum(), argnums=(0, 1, 2))(
            x, router, experts)

    got = grads(lambda x, r, e: moe.topk_moe_ffn(x, r, e, held, TK_K, block_rows, route=rule)[0])
    ref = grads(lambda x, r, e: moe.topk_dense_oracle(x, r, e, held, TK_K, route=rule))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_sigmoid_rule_under_vmap_over_clients_inside_the_client_chunk_scan():
    held, W, C = (5, 4), 6, 2
    router, experts = _topk_params(jax.random.PRNGKey(18), held[1])
    xs = jax.random.normal(jax.random.PRNGKey(19), (W, TK_T, TK_D))
    rule = _sigmoid_rule(0.5 * jax.random.normal(jax.random.PRNGKey(20), (TK_E,)))

    def client_grad(x, fn):
        return jax.grad(lambda r, e: (fn(x, r, e, held, TK_K, route=rule) ** 2).sum(),
                        argnums=(0, 1))(router, experts)

    ffn = lambda *a, **kw: moe.topk_moe_ffn(*a[:5], 32, **kw)[0]  # noqa: E731

    def body(acc, xb):
        g = jax.vmap(lambda x: client_grad(x, ffn))(xb)
        return jax.tree.map(lambda a, b: a + b.sum(0), acc, g), None

    init = jax.tree.map(jnp.zeros_like, (router, experts))
    got, _ = jax.jit(lambda xs: jax.lax.scan(body, init, xs))(xs.reshape(W // C, C, TK_T, TK_D))
    want = init
    for x in xs:
        want = jax.tree.map(jnp.add, want, client_grad(x, moe.topk_dense_oracle))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def _parent_topk_moe_ffn(x, router_w, expert_params, held, k, block_rows=moe.BLOCK_ROWS):
    """ops/moe.topk_moe_ffn as it stood before the routing rule became an
    argument (PR 30's text, the softmax rule written in place)."""
    T, D = x.shape
    first, G = held
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        top, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        experts, weights = experts.astype(jnp.int32), top / top.sum(-1, keepdims=True)
        local = experts.reshape(-1) - first
        here = (local >= 0) & (local < G)
        key = jnp.where(here, local, G)
        order = jnp.argsort(key, stable=True)
        group_sizes = (key[:, None] == jnp.arange(G)[None, :]).sum(0).astype(jnp.int32)
        pad = (-T * k) % block_rows
        token = jnp.pad(order // k, (0, pad)).astype(jnp.int32)
        weight = jnp.pad(weights.reshape(-1).astype(x.dtype)[order], (0, pad))
    with jax.named_scope("moe_experts"):
        y = moe._held_experts(block_rows)(x, expert_params, token, weight, group_sizes)
    return y, {"assignments_held": group_sizes.sum().astype(jnp.float32)}


def test_qwen3_nexts_expert_layer_lowers_to_the_same_text_as_before():
    """Qwen3-Next passes no rule and gets the softmax rule: the layer and its
    gradient lower to the text they lowered to before `route` was an argument."""
    held = (5, 4)
    router, experts = _topk_params(jax.random.PRNGKey(21), held[1])
    x = jax.random.normal(jax.random.PRNGKey(22), (TK_T, TK_D))

    def lowered(ffn):
        def layer(x, r, e):
            return jax.value_and_grad(lambda x, r, e: (ffn(x, r, e, held, TK_K, 32)[0] ** 2).sum(),
                                      argnums=(0, 1, 2))(x, r, e)

        return jax.jit(layer).lower(x, router, experts).as_text()

    assert lowered(moe.topk_moe_ffn) == lowered(_parent_topk_moe_ffn)
    assert lowered(functools.partial(moe.topk_moe_ffn, route=moe.topk_route)) == lowered(
        _parent_topk_moe_ffn)


def test_sigmoid_rule_with_lfm2s_eps_and_scale_against_the_written_out_rule():
    """LFM2's rule: the chosen scores over (their sum + 1e-6), times 1. Beside
    scores near 1/2 the 1e-6 is a few ulps; where every score is tiny it is
    most of the divisor, and GLM's 1e-20 none of it."""
    router, _ = _topk_params(jax.random.PRNGKey(23), 1)
    x = jax.random.normal(jax.random.PRNGKey(24), (TK_T, TK_D))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(25), (TK_E,))
    for shift in (0.0, -14.0):  # scores near 1/2, then near 1e-6
        x_s = x.at[:, 0].set(1.0)
        r_s = router.at[0].set(shift)
        s = jax.nn.sigmoid(jnp.dot(x_s, r_s, precision="highest"))
        chosen, weights = moe.sigmoid_topk_route(x_s, r_s, TK_K, bias=bias, scale=1.0, eps=1e-6)
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(jax.lax.top_k(s + bias, TK_K)[1]))
        top = jnp.take_along_axis(s, chosen, axis=-1)
        np.testing.assert_allclose(np.asarray(weights),
                                   np.asarray(top / (top.sum(-1, keepdims=True) + 1e-6)), rtol=1e-6)
        _, glm = moe.sigmoid_topk_route(x_s, r_s, TK_K, bias=bias)
        np.testing.assert_allclose(np.asarray(glm.sum(-1)), 1.0, rtol=1e-6)
        if shift:
            assert float(top.sum(-1).min()) < 1e-5
            assert float(weights.sum(-1).min()) < 0.9  # the 1e-6 shows
        else:
            np.testing.assert_allclose(np.asarray(weights), np.asarray(glm), rtol=2e-6)


def _parent_sigmoid_topk_route(x, router_w, k: int, bias=None, scale: float = 1.0, eps=1e-20):
    """ops/moe.sigmoid_topk_route as it was before `eps` was an argument: the
    one value an older family may hand it is the constant it had."""
    assert eps == 1e-20
    scores = jax.nn.sigmoid(moe._router_logits(x, router_w))
    _, experts = jax.lax.top_k(scores if bias is None else scores + bias, k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts.astype(jnp.int32), top / (top.sum(-1, keepdims=True) + 1e-20) * scale


@pytest.mark.parametrize("family", ["glm4_moe_lite", "qwen3_next"])
def test_an_older_familys_expert_layer_lowers_to_the_text_it_lowered_to_before_eps(
        family, monkeypatch):
    """GLM-4.7-Flash passes no `eps` and Qwen3-Next no sigmoid rule at all:
    each model's own expert layer and its gradient lower to the same text with
    the rule as it was written before the argument put in its place."""
    import importlib

    module = importlib.import_module(f"commefficient_tpu.models.{family}")
    layer = module.SparseMoE(module.TINY)
    x = jax.random.normal(jax.random.PRNGKey(26), (2, TK_T, module.TINY.hidden_size))
    variables = layer.init(jax.random.PRNGKey(27), x)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def lowered():
        fn = jax.value_and_grad(lambda p, x: (layer.apply({"params": p, **rest}, x) ** 2).sum(),
                                argnums=(0, 1))
        return jax.jit(fn).lower(variables["params"], x).as_text()

    now, calls = lowered(), []

    def parent(*args, **kw):
        calls.append(kw)
        return _parent_sigmoid_topk_route(*args, **kw)

    monkeypatch.setattr(moe, "sigmoid_topk_route", parent)
    assert lowered() == now
    assert bool(calls) == (family == "glm4_moe_lite")  # the rule was in the layer's path
