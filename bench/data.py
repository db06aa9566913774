"""Datasets of the benchmark, made on the device from the run's seed.

Each configuration file under ``bench/configs`` names a loss, a row count,
a feature count and the l2 coefficient. The rows are Gaussian stand-ins
for the published datasets (the real features are not fetched), drawn in
one jitted call:

* ``logistic``: labels +-1 with equal odds, features N(0, 1) shifted by
  +-0.5 in the first coordinate (the class means one unit apart), rows
  i.i.d. so that any contiguous block is a fair sample;
* ``ridge``: features N(0, 1), ``b = A x_true + N(0, 1)``.

The distributions follow ``convex.make_logistic_data`` and
``convex.make_ridge_data`` of the program; the code is the benchmark's
own, so that the program under test never makes its own inputs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

SEED_MODULUS = 2**31 - 1


class Data(NamedTuple):
    A: jax.Array      # (rows, features) float32
    b: jax.Array      # (rows,) float32
    lam: float
    loss: str


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: the low 32 bits make the key,
    the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def solve_seed(seed: int, i: int) -> int:
    """The seed of the run's i-th solve (i = 0 is the warm-up solve)."""
    return (int(seed) + i) % SEED_MODULUS


@functools.partial(jax.jit, static_argnames=("loss", "rows", "features"))
def _draw(key, loss: str, rows: int, features: int):
    k1, k2, k3 = jax.random.split(key, 3)
    if loss == "logistic":
        b = jnp.where(jax.random.bernoulli(k1, 0.5, (rows,)), 1.0, -1.0)
        shift = jnp.zeros((features,)).at[0].set(0.5)
        A = jax.random.normal(k2, (rows, features)) + b[:, None] * shift
        return A, b
    if loss == "ridge":
        A = jax.random.normal(k1, (rows, features))
        x_true = jax.random.normal(k2, (features,))
        b = (jnp.dot(A, x_true, precision=jax.lax.Precision.HIGHEST)
             + jax.random.normal(k3, (rows,)))
        return A, b
    raise ValueError(f"unknown loss {loss!r}")


def make(config: dict, seed: int) -> Data:
    """The configuration's dataset for ``seed``, on the default device."""
    A, b = _draw(key_from_seed(seed), config["loss"], int(config["rows"]),
                 int(config["features"]))
    return Data(A, b, float(config["lam"]), config["loss"])
