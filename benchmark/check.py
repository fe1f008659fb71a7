"""The comparison that decides `correct`: what the timed session produced in
its first three rounds against the plain reference's three.

Numbers read; a cell compares those its limits file names
(benchmark/limits/<cell>.json, each with the readings its limit was set from). A
gap is |program - reference| over the reference, of a loss or of a norm:

  loss1_gap   the first round's loss: same weights on both sides, so the
              steadiest number there is
  loss_gap    the worst of the three rounds' losses. Read, and compared in no
              cell yet: rounds 2 and 3 start from weights that already differ
              by round-off, so it swings (PERF.md section 2)
  grad_gap    the first round's gradient as the optimiser gets it, worked out
              from the state after one round. A sketch cell's optimiser gets
              the r x c table, S = (Verror_1 + sketch(params_0 - params_1)) /
              lr_1: the worst row, its gap measured against the reference's
              norm of that row or of the median row, whichever is larger. A
              dense cell's optimiser gets one flat vector, Vvelocity_1: the
              gap of its norm.
  update_gap  the norm of params_3 - params_0 over the whole vector.

Not compared, and why (PERF.md section 2): the change by the worst single
leaf. In a sketch cell it reads 0.26-0.62 on sound runs, always on a leaf of
10 to 512 elements (a batch-norm vector, the classifier's bias) that gets a
handful of the k coordinates, one more or fewer on either side; `details`
keeps those norms for a look by hand.
"""

from __future__ import annotations

import json
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from benchmark.reference import fetchsgd, rounds

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(cell_name: str) -> dict:
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def worst_leaf_gap(prog_norms, ref_norms) -> float:
    prog_norms, ref_norms = np.asarray(prog_norms), np.asarray(ref_norms)
    # a leaf that neither side moved reads 0, not 0/0
    scale = np.maximum(np.maximum(ref_norms, np.median(ref_norms)), 1e-30)
    return float((np.abs(prog_norms - ref_norms) / scale).max())


def program_optimizer_input(recipe, snap0, snap1, lr1):
    """The first gradient as the program's optimiser got it, from its state
    after one round (host arrays in, host array out)."""
    if recipe.mode == "sketch":
        cs = fetchsgd.CountSketch(recipe.d, recipe.rows, recipe.cols, recipe.hash_seed)
        delta = ravel_pytree(snap0["params"])[0] - ravel_pytree(snap1["params"])[0]
        sk = jax.jit(cs.accumulate)(jnp.asarray(delta))
        return (np.asarray(snap1["Verror"]) + np.asarray(sk)) / np.float32(lr1)
    return np.asarray(snap1["Vvelocity"])


def _gap(program: float, reference: float) -> float:
    return abs(program - reference) / max(abs(reference), 1e-30)


def _norm(x) -> float:
    return float(np.linalg.norm(np.ravel(np.asarray(x, np.float32)).astype(np.float64)))


def _tree_norm(tree) -> float:
    return float(np.sqrt(sum(_norm(x) ** 2 for x in jax.tree.leaves(tree))))


def readings(recipe, program: dict, ref: dict, details: dict | None = None) -> dict:
    """program: losses[3], snaps {0, 1, 3} of host state, lr1. ref: what
    reference.rounds.follow returned. `details`, if given, is filled with
    the per-leaf norms behind the numbers, for a look by hand."""
    out = {}
    per_step = [_gap(p, r) for p, r in zip(program["losses"], ref["losses"])]
    out["loss1_gap"], out["loss_gap"] = per_step[0], max(per_step)
    got = program_optimizer_input(recipe, program["snaps"][0], program["snaps"][1],
                                  program["lr1"])
    want = ref["first"]["optimizer_input"]
    if recipe.mode == "sketch":
        pn, rn = np.linalg.norm(got, axis=1), np.linalg.norm(want, axis=1)
        out["grad_gap"] = worst_leaf_gap(pn, rn)
    else:
        pn, rn = np.asarray([_norm(got)]), np.asarray([_norm(want)])
        out["grad_gap"] = _gap(pn[0], rn[0])

    p0 = program["snaps"][0]["params"]
    moved = lambda p: jax.tree.map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32), p, p0)
    pm, rm = moved(program["snaps"][3]["params"]), moved(ref["snaps"][3]["params"])
    out["update_gap"] = _gap(_tree_norm(pm), _tree_norm(rm))
    if details is not None:
        details.update(
            losses=[program["losses"], ref["losses"]], grad_norms=[pn.tolist(), rn.tolist()],
            update_norms=[rounds.leaf_norms(pm).tolist(), rounds.leaf_norms(rm).tolist()],
            ref_grad_leaf_norms=ref["first"]["grad_leaf_norms"].tolist(),
            leaf_sizes=[int(np.size(x)) for x in jax.tree.leaves(p0)])
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell's
    limits file names; the others are read and not compared. A compared
    number that is missing or not finite fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = values.get(name, float("nan"))
        compared[name] = {"value": value, "limit": limit}
        if not np.isfinite(value) or value > limit:
            ok = False
    return ok, compared
