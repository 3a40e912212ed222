"""Deployment data and queries, made on the device from the run's seed.

A configuration's twin is a clustered Gaussian mixture: ``clusters``
centres drawn N(0, 1)·``center_scale`` in d dimensions; each cluster
spreads along ``active_dims`` unit directions of its own (so the local
intrinsic dimensionality is about ``active_dims``); every point gets
``noise``·N(0, 1) of full-rank noise.  These are the numbers of the
Table-3 twins in ``benchmarks/datasets.py`` (``SPECS``), copied into
each configuration file so that the yardstick does not move with the
program.  Queries are further draws from the same mixture, held out
from the data.

Everything is made in one jitted call per array, on the device, from
``jax.random`` keys derived from the seed: the same seed gives the same
points on every run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_MASK32 = 0xFFFFFFFF


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & _MASK32)
    return jax.random.fold_in(key, (seed >> 32) & _MASK32)


def _mixture(key, clusters: int, active: int, d: int, center_scale: float):
    kc, kb = jax.random.split(key)
    centers = jax.random.normal(kc, (clusters, d), jnp.float32) * center_scale
    basis = jax.random.normal(kb, (clusters, active, d), jnp.float32)
    basis = basis / jnp.linalg.norm(basis, axis=-1, keepdims=True)
    return centers, basis


@partial(jax.jit, static_argnames=("n", "d", "clusters", "active",
                                   "center_scale", "noise"))
def _draw(mix_key, key, *, n: int, d: int, clusters: int, active: int,
          center_scale: float, noise: float) -> jax.Array:
    centers, basis = _mixture(mix_key, clusters, active, d, center_scale)
    ka, kc, kn = jax.random.split(key, 3)
    asg = jax.random.randint(ka, (n,), 0, clusters)
    coeff = jax.random.normal(kc, (n, active), jnp.float32)
    pts = centers[asg] + noise * jax.random.normal(kn, (n, d), jnp.float32)

    # one (n, d) gather of a direction per step: never the (n, active, d)
    # tensor, which would not fit the chip at n = 1M
    def add_direction(j, acc):
        return acc + coeff[:, j, None] * basis[asg, j]

    return jax.lax.fori_loop(0, active, add_direction, pts)


def draw(cfg: dict, seed: int, n: int, stream: int) -> jax.Array:
    """``n`` points of the configuration's mixture, on the device.

    ``stream`` 0 is the data; other streams are held-out queries.  The
    mixture itself (centres, directions) depends on the seed alone.
    """
    twin = cfg["twin"]
    root = root_key(seed)
    return _draw(jax.random.fold_in(root, 0),
                 jax.random.fold_in(root, 1 + stream),
                 n=int(n), d=int(cfg["d"]), clusters=int(twin["clusters"]),
                 active=int(twin["active_dims"]),
                 center_scale=float(twin["center_scale"]),
                 noise=float(twin["noise"]))


def dataset(cfg: dict, seed: int) -> jax.Array:
    """The configuration's (n, d) float32 point set."""
    return draw(cfg, seed, cfg["n"], stream=0)


def queries(cfg: dict, seed: int, count: int, stream: int = 1) -> jax.Array:
    """``count`` held-out query rows from the same mixture; each stream
    (1, 2, ...) is a different set."""
    if stream < 1:
        raise ValueError("stream 0 is the data")
    return draw(cfg, seed, count, stream=stream)
