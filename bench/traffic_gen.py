"""The one traffic generator: turns a mix's parameters (a file under
``bench/traffic/``) and ``--seed`` into the inputs a driver feeds the
system. The same seed gives the same inputs.

* ``populations`` — user populations for control ticks, one independent
  stream per population index (edge and service popularity come from the
  mix, thresholds from the deployment).
* ``trial_seeds`` — the per-trial instance seeds of a Monte-Carlo grid.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference as ref


def stream(seed: int, *index: int) -> np.random.Generator:
    """An independent generator for ``(seed, *index)``; any whole seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**64] + [int(i) for i in index]))


def populations(fleet: ref.Fleet, deployment: Dict, traffic: Dict,
                seed: int, first: int, count: int) -> List[ref.Users]:
    """Populations ``first .. first + count - 1`` of ``deployment``'s
    user count under ``traffic``'s popularity."""
    n = int(deployment["users"]["per_tick"])
    return [ref.draw_users(stream(seed, 0, i), fleet, n, deployment, traffic)
            for i in range(first, first + count)]


def trial_seeds(seed: int, trials: int) -> List[int]:
    """Seeds of a grid's trials (each a whole §VI-B instance)."""
    state = np.random.SeedSequence([int(seed) % 2**64, 1]).generate_state(
        trials, dtype=np.uint32)
    return [int(s) for s in state]
