"""Faults planted under a built cell, for the tests that must see `correct`
come out false and for reading what each fault does to the compared numbers.
The benchmark's own runs never call these."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def state_unchanged(cell) -> None:
    """The round step runs and reports its metrics, and hands back the state
    it was given."""
    step = cell.session._step

    def broken(state, batch, rows, lr, rng):
        _, new_rows, metrics = step(jax.tree.map(jnp.copy, state), batch, rows, lr, rng)
        return state, new_rows, metrics

    cell.session._step = broken


def half_batch(cell) -> None:
    """Half of the cohort is left out, the mean taken over the rest."""
    load = cell.session._load_client_batch

    def broken(ids, rnd=None):
        batch, _ = load(ids, rnd)
        valid = np.ones(len(ids), np.float32)
        valid[len(ids) // 2:] = 0.0
        return batch, valid

    cell.session._load_client_batch = broken


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
