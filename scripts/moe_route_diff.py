"""How many of a round's routing choices differ between program and reference
(PERF.md section 6, PR 27 and PR 31): python scripts/moe_route_diff.py <cell> <seed>...
The cell's seeded weights and its first 8 users' documents through the
trainer's model (the choices it sows) and through the plain reference; the
family comes from the cell's configuration file (its `model_type` names the
trainer's model, its builder the reference). On a TPU the two differ where a
router input, already rounded differently by the mixers before it, puts
another expert last of the chosen; on the CPU they do not. A family with a
selection bias also prints the share of tokens whose choice the bias changed.
ROUTE_TINY=1 runs narrow layers for a rehearsal."""
import json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # scripts/ -> repo
sys.path.insert(0, ROOT); os.chdir(ROOT)
import functools, importlib
import numpy as np
import jax, jax.numpy as jnp
from benchmark import federation, harness
from commefficient_tpu import models

loaded = harness.load_cell(harness.load_manifest(), sys.argv[1])
config, traffic = loaded["config"], loaded["traffic"]
m, inp = config["model"], config["input"]
ref = importlib.import_module("benchmark.reference." + config["builder"])
if os.environ.get("ROUTE_TINY"):
    narrow = dict(hidden_size=64, vocab_size=512, head_dim=16, linear_key_head_dim=8, linear_value_head_dim=8,
                  moe_intermediate_size=16, shared_expert_intermediate_size=16, intermediate_size=96,
                  q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16)
    m = dict(m, **{k: v for k, v in narrow.items() if k in m})
    inp = dict(inp, vocab=512, seq_len=128)
_, model = models.from_model_block(m)
has_bias = hasattr(ref, "init_buffers")
prog = jax.jit(lambda p, b, ids: model.apply({"params": p, **b}, ids[None], mutable=["intermediates", "metrics"])[1])
refc = jax.jit(lambda p, b, ids: ref.routing_choices(p, b, ids, m) if has_bias else ref.routing_choices(p, ids, m))
for seed in [int(s) for s in sys.argv[2:]]:
    fed = federation.generate(inp, traffic, seed)
    key = jax.random.PRNGKey(seed % 2**32)
    params = jax.jit(functools.partial(ref.init_params, shapes=ref.param_shapes(m)))(key)
    buffers = ref.init_buffers(key, ref.buffer_shapes(m)) if has_bias else {}
    differ, total, flips, tokens = None, 0, 0.0, 0.0
    for client in range(8):
        ids = jnp.asarray(fed["arrays"]["input_ids"][fed["shards"][client][0]])
        sown = prog(params, {"buffers": buffers} if has_bias else {}, ids)
        layers = [v["moe"] for _, v in sorted(sown["intermediates"].items(), key=lambda kv: int(kv[0].split("_")[1]))]
        a = np.stack([np.asarray(layer["moe_choices"][0]) for layer in layers])
        b = np.asarray(refc(params, buffers, ids))
        same = (a[:, :, :, None] == b[:, :, None, :]).any(-1)  # program's choice also chosen by the reference
        differ = (~same).sum((1, 2)) + (0 if differ is None else differ)
        total += a.shape[1] * a.shape[2]
        for layer in sown["metrics"].values():
            flips += float(layer["moe"].get("moe_bias_flips", (0.0,))[0])
            tokens += float(layer["moe"].get("moe_bias_tokens", (0.0,))[0])
    line = {"cell": sys.argv[1], "seed": seed, "choices_per_layer": total, "differ_by_layer": differ.tolist()}
    if tokens:
        line["bias_flips_share"] = flips / tokens
    print(json.dumps(line), flush=True)
