"""Seeded corpora for the benchmark, made by the generator a configuration
names.

A configuration's ``generator.kind`` names a file of its own,
``bench/generators/<kind>.py``, whose ``make(cfg, seed)`` returns a
:class:`Corpus`: the base rows, the query pool, and in ``meta`` whatever
else the generator makes for the traffic and the judge (tags per row, a
runbook of inserts and deletes).  A later change adds a generator as a new
file.  Everything is float32, as served.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import numpy as np

from bench import spec


@dataclasses.dataclass
class Corpus:
    base: np.ndarray
    pool: np.ndarray
    meta: dict = dataclasses.field(default_factory=dict)


def key_from_seed(seed: int):
    """A JAX key from any whole-number seed (also beyond 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.key(int(word))


def corpus(cfg: dict, seed: int, bench_dir: Path) -> Corpus:
    """The configuration's corpus, from ``seed``, or from the generator's
    own ``seed`` where the configuration fixes one (the run's seed then
    orders the work, and does not change it); the generator is the one in
    ``bench_dir``'s ``generators/``."""
    return spec.generator(cfg["generator"]["kind"], bench_dir).make(cfg, seed)


def make_corpus(cfg: dict, seed: int, bench_dir: Path
                ) -> tuple[np.ndarray, np.ndarray]:
    """(base rows, query pool) of :func:`corpus`."""
    c = corpus(cfg, seed, bench_dir)
    return c.base, c.pool
