"""The measure every tolerance in the benchmark is written in. It
belongs to no architecture: the drivers take the reference's answer from
the cell's architecture file and hold the program's against it here."""

from __future__ import annotations

import jax.numpy as jnp


def max_rel_err(got, want) -> float:
    """Largest absolute error over the reference's largest magnitude."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
