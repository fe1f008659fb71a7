"""The one traffic generator: a synthetic federation made from the seed and a
traffic file's parameters (benchmark/traffic/<traffic>.json):

    num_clients          virtual clients in the federation
    cohort               clients sampled a round (W)
    examples_per_client  what each client holds, all used every round
    label_skew           "one_class" (the paper's non-iid split) | "iid"
    schedule_epoch       where in the learning-rate schedule the window sits
    argv                 further trainer flags this traffic asks for

What a client holds depends on the configuration's `input`: images with a
class label, or token sequences. Every seed gives the same sizes; only the
contents and the order in which clients are drawn change.
"""

from __future__ import annotations

import numpy as np


def generate(input_spec: dict, traffic: dict, seed: int) -> dict:
    """{"arrays": {name: [N, ...]}, "shards": [num_clients][examples]}."""
    rng = np.random.default_rng(seed)
    clients, per = int(traffic["num_clients"]), int(traffic["examples_per_client"])
    n = clients * per
    kind = input_spec["kind"]
    if kind == "image":
        classes = int(input_spec["classes"])
        h, w, c = input_spec["shape"]
        if traffic.get("label_skew", "one_class") == "one_class":
            y = (np.arange(n, dtype=np.int64) * classes // n).astype(np.int32)
        else:
            y = rng.integers(0, classes, n, dtype=np.int32)
        # class prototypes in 4x4 blocks (they survive convolution and
        # pooling), unit-scale pixel noise on top
        low = rng.standard_normal((classes, h // 4, w // 4, c), dtype=np.float32)
        protos = low.repeat(4, axis=1).repeat(4, axis=2)
        x = rng.standard_normal((n, h, w, c), dtype=np.float32)
        x *= 0.5
        x += protos[y]
        arrays = {"x": x, "y": y}
    elif kind == "tokens":
        vocab, t = int(input_spec["vocab"]), int(input_spec["seq_len"])
        # a persona is a client: its dialogues favour a pool of its own words
        # drawn over the whole vocabulary, mixed with words anyone uses
        pool = int(input_spec.get("persona_pool", 512))
        pools = rng.integers(0, vocab, (clients, pool), dtype=np.int32)
        own = rng.integers(0, pool, (clients, per, t))
        ids = np.take_along_axis(pools[:, None, :].repeat(per, 1), own, axis=2)
        anyone = rng.integers(0, vocab, (clients, per, t), dtype=np.int32)
        ids = np.where(rng.random((clients, per, t)) < 0.5, ids, anyone).astype(np.int32)
        arrays = {"input_ids": ids.reshape(n, t)}
    else:
        raise ValueError(f"unknown input kind {kind!r}")
    shards = np.arange(n, dtype=np.int64).reshape(clients, per)
    return {"arrays": arrays, "shards": shards}


def cohort_rows(fed: dict, ids) -> dict:
    """The examples the clients `ids` hold, [W, examples, ...] per array."""
    rows = fed["shards"][np.asarray(ids, dtype=np.int64)]
    return {k: v[rows] for k, v in fed["arrays"].items()}
