"""The general traffic generator: one data file per mix, under ``traffic/``.

A mix file is a JSON object:

``arrivals``   ``"back_to_back"``: a closed loop of one client, each call
               issued when the previous one has returned.
``pool``       how many distinct right-hand sides the mix holds.
``pool_seed``  the seed they are drawn from, the same in every run, so
               that every run does the same work where that work depends
               on the right-hand side (a solve to a tolerance); ``--seed``
               then only sets the order in which a pass takes them.
               Without it, ``--seed`` draws them too: for mixes whose work
               does not depend on them (a fixed number of iterations).
``tol``        the solve tolerance, a number or ``"config"`` (the
               configuration's own).  0 runs exactly ``maxiter`` iterations.
``maxiter``    iterations allowed per call, a number or ``"config"``.
``why``        one line on what the mix exercises.

The window runs whole passes over the pool (``harness.window``).
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

ARRIVALS = ("back_to_back",)
KEYS = {"arrivals", "pool", "tol", "maxiter"}


@dataclasses.dataclass(frozen=True)
class Traffic:
    B: np.ndarray           # (pool, n) float32 right-hand sides
    order: np.ndarray       # the pool's slots in the order of one pass
    tol: float
    maxiter: int

    @property
    def fixed_iterations(self) -> bool:
        """Every call runs ``maxiter`` iterations (tol 0): the answer is the
        iterate after them, not a solution to a tolerance."""
        return self.tol == 0


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    missing = KEYS - set(mix)
    if missing:
        raise ValueError(f"{path}: mix lacks {sorted(missing)}")
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"{path}: arrivals {mix['arrivals']!r} not in "
                         f"{ARRIVALS}")
    return mix


def make_traffic(mix: dict, config: dict, n_rows: int, seed: int) -> Traffic:
    """The mix's pool of right-hand sides, in the order ``seed`` (any whole
    number) gives it."""
    tol = config["tol"] if mix["tol"] == "config" else mix["tol"]
    maxiter = config["maxiter"] if mix["maxiter"] == "config" else mix["maxiter"]
    pool = int(mix["pool"])
    rng = np.random.default_rng(int(seed) % 2**64)
    order = rng.permutation(pool)
    if "pool_seed" in mix:
        rng = np.random.default_rng(int(mix["pool_seed"]))
    B = rng.standard_normal((pool, n_rows), dtype=np.float32)
    return Traffic(B=B, order=order, tol=float(tol), maxiter=int(maxiter))
