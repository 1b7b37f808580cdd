"""planted_manifold: points on a random smooth ``intrinsic_dim``-manifold
(a degree-2 feature lift of Gaussian latents) projected into ``dim``
dimensions, plus isotropic noise, made on the device in one jitted call.

It follows the form of the program's own generator
(``data/synthetic.py``).  Real audio and text embeddings have a low
intrinsic dimension; a corpus of that kind is what makes the paper's recall
reachable without heavy refinement.  The benchmark keeps its own copy so that
no later change to the program can change the data it is judged on.

Base rows and query rows come from the same manifold (one projection), so
queries are in-distribution: the first ``n`` rows are the base, the next
``query_pool`` the queries."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import Corpus, key_from_seed


@functools.partial(jax.jit,
                   static_argnames=("n", "dim", "intrinsic_dim", "noise"))
def _manifold(key, *, n: int, dim: int, intrinsic_dim: int, noise: float):
    k = intrinsic_dim
    kz, kp, kn = jax.random.split(key, 3)
    z = jax.random.normal(kz, (n, k), jnp.float32)
    iu0, iu1 = np.triu_indices(k)
    phi = jnp.concatenate([z, z[:, iu0] * z[:, iu1]], axis=1)
    n_feat = phi.shape[1]
    proj = jax.random.normal(kp, (n_feat, dim), jnp.float32) / np.sqrt(n_feat)
    x = jnp.matmul(phi, proj, precision=jax.lax.Precision.HIGHEST)
    return x + noise * jax.random.normal(kn, (n, dim), jnp.float32)


def planted_manifold(seed: int, n: int, dim: int, intrinsic_dim: int,
                     noise: float) -> np.ndarray:
    """(n, dim) float32 rows on the host, made on the default device."""
    x = _manifold(key_from_seed(seed), n=n, dim=dim,
                  intrinsic_dim=intrinsic_dim, noise=float(noise))
    return np.asarray(x)


def make(cfg: dict, seed: int) -> Corpus:
    gen = cfg["generator"]
    rows = planted_manifold(gen.get("seed", seed),
                            cfg["n"] + cfg["query_pool"], cfg["dim"],
                            gen["intrinsic_dim"], gen["noise"])
    return Corpus(rows[: cfg["n"]], rows[cfg["n"]:])
