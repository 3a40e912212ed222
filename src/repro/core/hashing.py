"""2-stable LSH hash families (paper §2.2, Eq. 1 and Eq. 3).

Two families are provided:

* :class:`ProjectionFamily` — the un-quantized projection ``h*(o) = a·o``
  (Eq. 3) used by PM-LSH itself (and SRS).  ``m`` independent functions
  stack into a single ``(d, m)`` Gaussian matrix; projecting a batch is
  one MXU matmul.
* :class:`BucketFamily` — the classic E2LSH quantized hash
  ``h(o) = floor((a·o + b) / w)`` (Eq. 1) used by the bucket-based
  baselines (Multi-Probe, LSB-tree) and QALSH (w/ per-function offsets).

Both are deterministic given a seed, cheap to serialize, and their
`project`/`hash` methods are jit-safe (pure jnp on static matrices).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ProjectionFamily", "BucketFamily"]


@dataclasses.dataclass(frozen=True)
class ProjectionFamily:
    """m un-quantized 2-stable hash functions h*_i(o) = a_i · o  (Eq. 3).

    Attributes:
      a: (d, m) float32 matrix; column i is the Gaussian vector of h*_i.
    """

    a: jax.Array  # (d, m)

    @property
    def d(self) -> int:
        return self.a.shape[0]

    @property
    def m(self) -> int:
        return self.a.shape[1]

    @staticmethod
    def create(d: int, m: int, seed: int = 0) -> "ProjectionFamily":
        key = jax.random.PRNGKey(seed)
        a = jax.random.normal(key, (d, m), dtype=jnp.float32)
        return ProjectionFamily(a=a)

    def project(self, x: jax.Array) -> jax.Array:
        """Project points (..., d) into the m-dim hash space: x @ a."""
        return jnp.dot(jnp.asarray(x, jnp.float32), self.a,
                       precision=jax.lax.Precision.HIGHEST)

    def __call__(self, x: jax.Array) -> jax.Array:  # alias
        return self.project(x)


@dataclasses.dataclass(frozen=True)
class BucketFamily:
    """m quantized 2-stable hash functions h_i(o) = ⌊(a_i·o + b_i)/w⌋ (Eq. 1)."""

    a: jax.Array  # (d, m)
    b: jax.Array  # (m,)
    w: float

    @property
    def d(self) -> int:
        return self.a.shape[0]

    @property
    def m(self) -> int:
        return self.a.shape[1]

    @staticmethod
    def create(d: int, m: int, w: float, seed: int = 0) -> "BucketFamily":
        ka, kb = jax.random.split(jax.random.PRNGKey(seed))
        a = jax.random.normal(ka, (d, m), dtype=jnp.float32)
        b = jax.random.uniform(kb, (m,), dtype=jnp.float32, maxval=w)
        return BucketFamily(a=a, b=b, w=float(w))

    def raw(self, x: jax.Array) -> jax.Array:
        """Un-floored hash value (a·x + b)/w, useful for probing sequences."""
        return (jnp.dot(jnp.asarray(x, jnp.float32), self.a,
                        precision=jax.lax.Precision.HIGHEST) + self.b) / self.w

    def hash(self, x: jax.Array) -> jax.Array:
        """Integer bucket coordinates, (..., m) int32."""
        return jnp.floor(self.raw(x)).astype(jnp.int32)

    def __call__(self, x: jax.Array) -> jax.Array:  # alias
        return self.hash(x)


@partial(jax.jit, static_argnames=())
def collision_probability(tau: jax.Array, w: float) -> jax.Array:
    """p(τ) of Eq. 2 — probability two points at distance τ share a bucket.

    Closed form (Datar et al. 2004):
        p(τ) = 1 - 2Φ(-w/τ) - (2τ/(√(2π) w)) (1 - exp(-w²/(2τ²)))
    """
    tau = jnp.maximum(jnp.asarray(tau, jnp.float32), 1e-20)
    t = w / tau
    phi = 0.5 * (1.0 + jax.scipy.special.erf(-t / jnp.sqrt(2.0)))
    return 1.0 - 2.0 * phi - (2.0 / (jnp.sqrt(2.0 * jnp.pi) * t)) * (
        1.0 - jnp.exp(-(t * t) / 2.0)
    )


def pstable_check(family: ProjectionFamily, n_samples: int = 4096, seed: int = 1):
    """Empirical sanity check of the 2-stable property (used by tests):

    for random o1, o2: (h*(o1)-h*(o2)) / ||o1-o2||  ~  N(0, 1).
    Returns the samples so tests can run normality checks.
    """
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    o1 = jax.random.normal(k1, (n_samples, family.d))
    o2 = jax.random.normal(k2, (n_samples, family.d))
    r = jnp.linalg.norm(o1 - o2, axis=-1, keepdims=True)
    rho = (family.project(o1) - family.project(o2)) / r
    return np.asarray(rho).ravel()
