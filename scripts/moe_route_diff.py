"""How many of a round's routing choices differ between program and reference
(PERF.md section 6, PR 27): python scripts/moe_route_diff.py <seed>...
The cell's seeded weights and its first 8 users' documents through the
trainer's model (the choices it sows) and through the plain reference. On a
TPU the two differ where a router input, already rounded differently by the
mixers before it, puts another expert 10th; on the CPU they do not.
ROUTE_TINY=1 runs narrow layers for a rehearsal."""
import json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # scripts/ -> repo
sys.path.insert(0, ROOT); os.chdir(ROOT)
import functools
import numpy as np
import jax, jax.numpy as jnp
from benchmark import federation, harness
from benchmark.reference import qwen3_next as ref
from commefficient_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM

loaded = harness.load_cell(harness.load_manifest(), "qwen3next_sketch_w8_t2048")
m, inp, traffic = loaded["config"]["model"], loaded["config"]["input"], loaded["traffic"]
if os.environ.get("ROUTE_TINY"):
    m = dict(m, hidden_size=64, vocab_size=512, head_dim=16, linear_key_head_dim=8, linear_value_head_dim=8,
             moe_intermediate_size=16, shared_expert_intermediate_size=16)
    inp = dict(inp, vocab=512, seq_len=128)
model = Qwen3NextLM(Qwen3NextConfig.from_model_block(m))
prog = jax.jit(lambda p, ids: model.apply({"params": p}, ids[None], mutable=["intermediates"])[1])
refc = jax.jit(lambda p, ids: ref.routing_choices(p, ids, m))
for seed in [int(s) for s in sys.argv[1:]]:
    fed = federation.generate(inp, traffic, seed)
    params = jax.jit(functools.partial(ref.init_params, shapes=ref.param_shapes(m)))(jax.random.PRNGKey(seed % 2**32))
    differ = np.zeros(m["num_hidden_layers"], np.int64)
    total = 0
    for client in range(8):
        ids = jnp.asarray(fed["arrays"]["input_ids"][fed["shards"][client][0]])
        sown = prog(params, ids)["intermediates"]
        a = np.stack([np.asarray(sown[f"layers_{i}"]["moe"]["moe_choices"][0]) for i in range(m["num_hidden_layers"])])
        b = np.asarray(refc(params, ids))
        for layer in range(a.shape[0]):
            same = (a[layer][:, :, None] == b[layer][:, None, :]).any(-1)  # program's choice also chosen by the reference
            differ[layer] += int((~same).sum())
        total += a.shape[1] * a.shape[2]
    print(json.dumps({"seed": seed, "choices_per_layer": total, "differ_by_layer": differ.tolist()}), flush=True)
