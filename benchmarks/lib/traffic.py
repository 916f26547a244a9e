"""The one traffic generator: every mix is a file of parameters under
``traffic/``, read here.  The same seed gives the same traffic.

Flow sets (``"flows"``): ``count`` pairs of host indices drawn
uniformly from ``base_seed`` (config #4's seed 42, as
tools/scale_proof.py draws them), ``bytes`` each, in an order drawn
from the run's seed.  Every seed gives the program the same amount of
work, flow for flow, under another numbering of variables and
constraints, so seeds spread no wider than repeats of one seed.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def draw_pairs(n_hosts: int, count: int, seed: int) -> np.ndarray:
    """``count`` (src, dst) host indices from ``default_rng(seed)``; a
    pair that drew src == dst sends to the next host
    (tools/scale_proof.py's rule)."""
    pairs = np.random.default_rng(int(seed)).integers(
        0, n_hosts, size=(count, 2))
    same = pairs[:, 0] == pairs[:, 1]
    pairs[same, 1] = (pairs[same, 1] + 1) % n_hosts
    return pairs


def flow_pairs(flows: Dict[str, Any], n_hosts: int, seed: int
               ) -> np.ndarray:
    """The mix's flow set, reordered by the run's seed (seeds run past
    2**31: SeedSequence takes any non-negative integer)."""
    base = draw_pairs(n_hosts, flows["count"], flows["base_seed"])
    order = np.random.default_rng([int(seed), 1]).permutation(len(base))
    return base[order]
