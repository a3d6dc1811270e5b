"""Traffic from the seed: the client pool's label skew, the per-client batch
seeds and the cohort of every round. Independent of the model family.

One ``numpy.random.SeedSequence(seed)`` feeds every draw, so the same seed
gives the same world and any whole number is a valid seed.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

# a client's batch seed is ``client_seed * 99991 + round`` and goes to
# numpy's RandomState, which takes 32 bits: client seeds stay below this
MAX_CLIENT_SEED = 40_000


def streams(seed: int) -> Dict[str, np.random.SeedSequence]:
    """Independent seed streams for each part of the world."""
    names = ("weights", "images", "labels", "clients", "cohorts")
    return dict(zip(names, np.random.SeedSequence(seed).spawn(len(names))))


def jax_seed(ss: np.random.SeedSequence) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from a stream."""
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def label_skew(clients: int, per_client: int, classes: int, alpha: float,
               ss: np.random.SeedSequence) -> np.ndarray:
    """Labels of an equal-size pool, [clients, per_client] int32: client
    i's class shares ~ Dirichlet(alpha), its counts multinomial in
    ``per_client``, its samples in a random order."""
    rng = np.random.default_rng(ss)
    out = np.empty((clients, per_client), np.int32)
    for i in range(clients):
        counts = rng.multinomial(per_client, rng.dirichlet([alpha] * classes))
        out[i] = rng.permutation(np.repeat(np.arange(classes), counts))
    return out


def client_seeds(clients: int, ss: np.random.SeedSequence) -> List[int]:
    """Distinct per-client batch seeds."""
    rng = np.random.default_rng(ss)
    base = int(rng.integers(0, MAX_CLIENT_SEED - clients))
    return [base + i for i in range(clients)]


def cohorts(clients: int, k: int, ss: np.random.SeedSequence
            ) -> Iterator[List[int]]:
    """An endless stream of cohorts of ``k`` distinct client ids. Each pass
    shuffles the pool and deals it out in cohorts, so consecutive cohorts
    of one pass share no client; a pass ends when fewer than ``k`` remain."""
    rng = np.random.default_rng(ss)
    while True:
        order = rng.permutation(clients)
        for lo in range(0, clients - k + 1, k):
            yield sorted(int(c) for c in order[lo:lo + k])
