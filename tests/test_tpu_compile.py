"""Ask the TPU compiler, without a chip: the sketch kernels, the fused one-chip
round and the four-device sharded round are compiled for a DESCRIBED v5e:2x2
topology (the installed libtpu compiles for a chip that is not attached).
This is what interpret mode (tests/test_pallas.py) cannot show — Mosaic's
own verdict on tiling and VMEM, and the SPMD partitioner's on a kernel call
under a multi-device jit.

Nothing runs, so nothing here is a result or a time; `chip_smoke.py` is the
run. On the CPU backend `pallas_kernels.eligible` takes the oracle branch, so
the tests steer it (monkeypatch) — never a switch of the program.

Everything that touches the topology lives in module-scoped fixtures of THIS
file (only one process at a time may load libtpu; the worker that is handed
this file is the one that loads it), and the persistent compile cache is off
around the compiles (a described-topology executable cannot be read back).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from commefficient_tpu.federated import engine
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.parallel import mesh as meshlib
from commefficient_tpu.sketch import CSVecSpec
from commefficient_tpu.sketch import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (d, c, r): the probe's layout, the flagship's (ResNet-9, cv_train's
# --num_cols 524288 --num_rows 5 — what chip_smoke.py trains with) and the
# language models' (GPT-2 small's d under 5 x 1,048,576: 119 slabs of 8,192
# sublanes, the layout the benchmark's claimed cells run)
LAYOUTS = {"probe": (2560, 1024, 3), "flagship": (6_573_130, 524_288, 5),
           "gpt2": (124_443_648, 1_048_576, 5)}


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_kernels_compile_for_v5e(one_chip, name):
    d, c, r = LAYOUTS[name]
    spec = CSVecSpec(d=d, c=c, r=r, seed=42, family="rotation")
    v = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((r, c), jnp.float32, sharding=one_chip)
    acc = jax.jit(lambda x: pk.sketch_vec(spec, x)).lower(v).compile()
    qry = jax.jit(lambda x: pk.query_all(spec, x)).lower(t).compile()
    assert acc.as_text().count("tpu_custom_call") == 1
    assert qry.as_text().count("tpu_custom_call") == 1


def _mlp_loss(params, net_state, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    logp = jax.nn.log_softmax(h @ params["w2"] + params["b2"])
    per_ex = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]
    loss_sum = (per_ex * batch["mask"]).sum()
    count = batch["mask"].sum()
    return loss_sum / jnp.maximum(count, 1.0), {
        "net_state": net_state,
        "metrics": {"loss_sum": loss_sum, "count": count},
    }


_DIN, _DH, _DOUT, _W, _B = 32, 64, 4, 8, 4
_SKETCH = dict(mode="sketch", k=64, num_rows=3, num_cols=1024,
               hash_family="rotation", momentum_type="virtual",
               error_type="virtual")


def _round_shapes(mode_kw, sharding, batch_sharding, **eng_kw):
    """(cfg, state, batch, lr, rng) of the small MLP round as shapes on a
    described device: small model, small supported layout — what these
    tests guard is where the kernels land in the program, not the width."""
    f32 = jnp.float32
    params = {"w1": jax.ShapeDtypeStruct((_DIN, _DH), f32),
              "b1": jax.ShapeDtypeStruct((_DH,), f32),
              "w2": jax.ShapeDtypeStruct((_DH, _DOUT), f32),
              "b2": jax.ShapeDtypeStruct((_DOUT,), f32)}
    d = sum(int(np.prod(p.shape)) for p in params.values())
    cfg = engine.EngineConfig(mode=ModeConfig(**{**mode_kw, "d": d}),
                              weight_decay=5e-4, **eng_kw)

    def on(tree, where):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where), tree)

    state = on(jax.eval_shape(
        lambda p: engine.init_server_state(cfg, p, {}), params), sharding)
    batch = on({"x": jax.ShapeDtypeStruct((_W, _B, _DIN), f32),
                "y": jax.ShapeDtypeStruct((_W, _B), jnp.int32),
                "mask": jax.ShapeDtypeStruct((_W, _B), f32)}, batch_sharding)
    lr = jax.ShapeDtypeStruct((), f32, sharding=sharding)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    return cfg, state, batch, lr, rng


@pytest.mark.parametrize("name, mode_kw, eng_kw, custom_calls", [
    ("sketch", _SKETCH, {}, 2),
    ("sketch_chunk2", _SKETCH, dict(client_chunk=2), 2),
    ("uncompressed", dict(mode="uncompressed", momentum_type="virtual",
                          error_type="none"), {}, 0),
])
def test_fused_round_compiles_on_one_chip_with_both_kernels_inlined(
        one_chip, monkeypatch, name, mode_kw, eng_kw, custom_calls):
    """The fused `make_round_step` as ONE program for one described v5e, with
    the accumulate and the query kernel inlined beside the vmapped (or
    chunk-scanned) client phase: the fact that retired `--split_compile`
    (PR 21 ran it on the chip, PR 29 deleted the two-program fork). Exactly
    one Mosaic call a kernel in the sketch round, none in the dense one."""
    monkeypatch.setattr(pk, "eligible", pk.supported)
    cfg, state, batch, lr, rng = _round_shapes(
        mode_kw, one_chip, one_chip, **eng_kw)
    step = jax.jit(engine.make_round_step(_mlp_loss, cfg))
    hlo = step.lower(state, batch, {}, lr, rng).compile().as_text()
    assert hlo.count("tpu_custom_call") == custom_calls


def test_sharded_round_partitions_kernels_on_four_chips(topo, monkeypatch):
    """The four-device sharded round with the kernels routed: the per-device
    partial sketch sits inside the client-phase shard_map, and the replicated
    server tail's query — at jit top level — must be wrapped the same way
    (engine._kernels_replicated), or lowering dies with 'Mosaic kernels
    cannot be automatically partitioned'."""
    monkeypatch.setattr(pk, "eligible", pk.supported)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (meshlib.CLIENT_AXIS,))
    cfg, state, batch, lr, rng = _round_shapes(
        _SKETCH, NamedSharding(mesh, P()), meshlib.client_sharding(mesh),
        client_shards=4)
    step = jax.jit(engine.make_sharded_round_step(_mlp_loss, cfg, mesh))
    hlo = step.lower(state, batch, {}, lr, rng).compile().as_text()
    # accumulate (per-device partial) + query (replicated tail), both Mosaic
    assert hlo.count("tpu_custom_call") >= 2
    # the ordered cross-device table merge
    assert "all-gather" in hlo


def test_approx_topk_compiles_for_v5e_to_a_partial_reduce_and_no_sort_of_its_output(
        one_chip):
    """impl="approx" above topk_abs's rule: the TPU's PartialReduce leaves m
    partial maxima, of which the last 1,019 slots hold no coordinate at this
    n (its last group of 16 tiles is 5 elements long), and the only sort in
    the compiled program is the one over the k selected. The aggregated
    call beside it shows what that guards against: a sort of all m pairs."""
    import re

    from commefficient_tpu.sketch import csvec

    n, k = 16 * 1024 * 400 + 5, 3000
    m = csvec.approx_select_size(n, k, 0.99)
    assert m == 401 * 1024
    v = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)

    def sorted_shapes(fn):
        text = jax.jit(fn).lower(v).compile().as_text()
        assert text.count('custom_call_target="PartialReduce"') == 1
        assert f"(f32[{m}]" in text
        return [re.search(r"= \(?(\w+\[\d+\])", line).group(1)
                for line in text.splitlines() if re.search(r" sort\(", line)]

    assert sorted_shapes(
        lambda x: csvec.topk_abs(x, k, impl="approx", recall=0.99)
    ) == [f"s32[{k}]"]
    assert sorted_shapes(
        lambda x: jax.lax.approx_max_k(jnp.abs(x), k, recall_target=0.99)[1]
    ) == [f"f32[{m}]"]


# --- Qwen3-Next's two new operations at the published widths (PR 27) ---


def test_held_experts_compile_to_the_ragged_dot_kernel_one_client_at_a_time(one_chip):
    """vmap over 2 clients of the gradient of the expert layer (2,048 tokens of
    2,048, top-10 of 512, 16 experts of width 512 held): the TPU's ragged-dot
    kernel takes no batch dimension, so every grouped product must have
    reached it unbatched (ops/moe._sequential_vmap) and none as a dense dot
    over all the experts."""
    from commefficient_tpu.ops import moe

    T, D, F, E, G, k = 2048, 2048, 512, 512, 16, 10
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    experts = {"gate": f32(G, D, F), "up": f32(G, D, F), "down": f32(G, F, D)}

    def client_grad(x, router, experts):
        return jax.grad(lambda r, e: moe.topk_moe_ffn(x, r, e, (0, G), k)[0].sum(),
                        argnums=(0, 1))(router, experts)

    text = jax.jit(jax.vmap(client_grad, in_axes=(0, None, None))).lower(
        f32(2, T, D), f32(D, E), experts).compile().as_text()
    # each grouped product sits once in the body of the loop over the clients:
    # the experts' three products, the rows' cotangents and the weights'
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and line.lstrip().startswith("%ragged-dot-none")]
    assert len(calls) >= 6, len(calls)
    assert not any(f"f32[2,{T * k}," in line for line in calls)  # no batch dimension
    assert f"f32[{G},{T * k},{D}]" not in text  # the dense fallback's expanded rows


def test_chunked_delta_rule_compiles_for_v5e_at_the_published_heads(one_chip):
    """One client's sequence of 2,048 tokens, 32 value heads of 128 x 128,
    chunk 64, forward and backward: the triangular solve and the scan over 32
    chunks as the TPU compiler takes them."""
    from commefficient_tpu.ops import gated_delta

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa: E731
    q = f32(1, 2048, 32, 128)
    grad = jax.grad(lambda *a: gated_delta.chunk_gated_delta_rule(*a).sum(), argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(grad).lower(q, q, q, f32(1, 2048, 32), f32(1, 2048, 32)).compile()
    need = compiled.memory_analysis().temp_size_in_bytes
    assert need < 2 * 1024 ** 3, need


# --- GLM-4.7-Flash's two blocks at the published widths (PR 31) ---


def _glm_block_shapes(module, x, one_chip):
    """(variables of `module` as shapes on the described chip, x likewise)."""
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    variables = jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros(x.shape)))
    return on({k: v for k, v in variables.items() if k in ("params", "buffers")}), on(x)


def test_latent_attention_compiles_for_v5e_at_the_published_widths(one_chip):
    """One client's sequence of 2,048 tokens through the latent-attention
    block (latents 768 and 512, 20 heads of 192 + 64 and 256), forward and
    backward: the T x T scores of 20 heads are 336 MB a copy, and with them
    recomputed the gradient keeps under four copies alive."""
    from commefficient_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig, LatentAttention

    cfg = Glm4MoeLiteConfig()
    block = LatentAttention(cfg)
    variables, x = _glm_block_shapes(block, jax.ShapeDtypeStruct((1, 2048, 2048), jnp.float32),
                                     one_chip)
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(variables)) == 21_759_232
    grad = jax.grad(lambda v, x: block.apply(v, x).sum(), argnums=(0, 1))
    compiled = jax.jit(grad).lower(variables, x).compile()
    scores = 20 * 2048 * 2048 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * scores


def test_glm_expert_layer_compiles_to_the_ragged_dot_kernel_under_the_sigmoid_rule(one_chip):
    """One client's 2,048 tokens through the expert block (sigmoid top-4 of
    64 with the selection bias, 8 experts of width 1,536 held, the shared
    expert), forward and backward under the engine's vmap: the grouped
    products reach the TPU's ragged-dot kernel unbatched, as Qwen3-Next's do,
    and no dense product over all the held experts' rows is made. The router's
    product (the one matmul at `highest` precision) is made once, though the
    block routes a second time without the bias for its `moe_bias_flips` sum."""
    from commefficient_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig, SparseMoE

    cfg = Glm4MoeLiteConfig(n_routed_experts=8)
    block = SparseMoE(cfg)
    variables, x = _glm_block_shapes(block, jax.ShapeDtypeStruct((1, 2048, 2048), jnp.float32),
                                     one_chip)
    assert variables["buffers"]["e_score_correction_bias"].shape == (64,)

    def client_grad(x, variables):
        return jax.grad(lambda p: block.apply({**variables, "params": p}, x[None]).sum())(
            variables["params"])

    text = jax.jit(jax.vmap(client_grad, in_axes=(0, None))).lower(x, variables).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and line.lstrip().startswith("%ragged-dot-none")]
    assert len(calls) >= 6, len(calls)
    assert f"f32[8,{2048 * 4},2048]" not in text  # the dense fallback's expanded rows
    # one block holds one and a half times the 1,024 assignments a uniform
    # router sends here, in whole units of 1,024 rows (moe.held_block_rows)
    assert any("f32[2048,1536]" in line for line in calls) and not any(
        "f32[1024,1536]" in line for line in calls)
    router = [line for line in text.splitlines()
              if "operand_precision={highest,highest}" in line and " f32[2048,64]" in line]
    assert len(router) == 1 and "moe_route" in router[0], router
