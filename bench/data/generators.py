"""Seeded data of the deployments, made on the device in one jitted call.

Frozen copies of the generators in the program's ``data/synthetic.py``
(``hierarchical_cells``, ``mnist_like``), drawn from a JAX key instead of
NumPy's generator so the data never crosses from the host.  Rows come
out grouped by cluster, as in the originals.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_major",
                                             "minors_per_major"))
def hierarchical_cells(key, *, n: int, dim: int, n_major: int = 4,
                       minors_per_major: int = 4):
    """Transcriptomics stand-in: major types -> sub-types -> cells, with
    the original's scales (majors N(0, 8), sub-types +N(0, 2), cells
    +N(0, 0.5)) and equal cluster sizes."""
    n_minor = n_major * minors_per_major
    if n % n_minor:
        raise ValueError(f"n={n} is not a multiple of {n_minor} clusters")
    k1, k2, k3 = jax.random.split(key, 3)
    major = 8.0 * jax.random.normal(k1, (n_major, dim), jnp.float32)
    minor = jnp.repeat(major, minors_per_major, axis=0) \
        + 2.0 * jax.random.normal(k2, (n_minor, dim), jnp.float32)
    label = jnp.arange(n, dtype=jnp.int32) // (n // n_minor)
    return minor[label] + 0.5 * jax.random.normal(k3, (n, dim), jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_classes",
                                             "manifold_dim"))
def mnist_like(key, *, n: int, dim: int, n_classes: int = 10,
               manifold_dim: int = 3):
    """MNIST stand-in: per class a cubic 3-D manifold in a random subspace
    (centres N(0, 6), curve scale 3, noise N(0, 0.2)), as the original."""
    if n % n_classes:
        raise ValueError(f"n={n} is not a multiple of {n_classes} classes")
    per = n // n_classes
    k1, k2, k3, k4 = jax.random.split(key, 4)
    basis, _ = jnp.linalg.qr(jax.random.normal(
        k1, (n_classes, dim, manifold_dim), jnp.float32))
    center = 6.0 * jax.random.normal(k2, (n_classes, dim), jnp.float32)
    t = jax.random.uniform(k3, (n_classes, per, manifold_dim), jnp.float32,
                           -1.0, 1.0)
    X = center[:, None, :] + 3.0 * jnp.einsum(
        "cpm,cdm->cpd", t ** 3, basis,
        precision=jax.lax.Precision.HIGHEST)
    X = X + 0.2 * jax.random.normal(k4, X.shape, jnp.float32)
    return X.reshape(n, dim)


GENERATORS = {"hierarchical_cells": hierarchical_cells,
              "mnist_like": mnist_like}


def make(config: dict, key):
    """The configuration's data set, (n, dim_hd) float32 on the device."""
    gen = config["generator"]
    return GENERATORS[gen["name"]](key, n=config["n"], dim=config["dim_hd"],
                                   **gen.get("params", {}))
