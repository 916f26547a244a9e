"""The plain reference of the logical-ring allreduce: numpy only,
float64, nothing of the program.

``MPI_Allreduce`` as SimGrid's OpenMPI selector stages it for a
message of at least 10,000 bytes with more elements than ranks
(``smpi_openmpi_selector.cpp`` -> ``allreduce-lr.cpp``) among R ranks:
each rank first copies its own chunk with a ``sendrecv`` to itself,
then does 2 (R - 1) ring steps (reduce-scatter, then all-gather), and
in each it ``sendrecv``s one chunk to rank r + 1 and one from rank
r - 1 (mod R).  This module builds the dependency graph of the
self-copy and the first ``steps`` ring steps ITSELF (``ring_dag``);
the LV08 max-min system of its messages on the dragonfly, their delays
and the drain are ``dragonfly_lv08_dag``'s beside it, which take any
``Dag`` (``dragonfly_routes`` gives a host's route to itself as its
router link up and back down, the way back the same two links):

* rank r's self-copy waits for nothing;
* its step-1 message waits for the self-copies of r and r + 1 (the
  receiver posted its receive once its own copy was done);
* its step-k message (k > 1) waits for step k - 1's messages of
  r - 1, r and r + 1: what r received and sent, and what r + 1 sent;
* a message is on the wire ``latency-factor`` x the sum of its route's
  link latencies after its last predecessor, at penalty 1 and with no
  window bound.

Flow f is message (r, k) with f = r x (steps + 1) + k, k = 0 the
self-copy; ``ring_step`` names each flow's step, so the comparison
goes by (sender, receiver, step): the pair (r, r + 1) recurs every
step.

``precision="bf16"`` is the control (see ``dragonfly_lv08``).
"""

from __future__ import annotations

import numpy as np

from .dragonfly_lv08_dag import (LATENCY_FACTOR, Dag,  # noqa: F401
                                 dag_system, drain)


def ring_dag(ranks: int, steps: int) -> Dag:
    R, S = int(ranks), int(steps)
    if R < 3 or not 1 <= S <= 2 * (R - 1):
        raise ValueError(f"a ring among {ranks} ranks has 1 to "
                         f"{2 * (R - 1)} steps (and 3 ranks or more), "
                         f"not {steps}")
    r, k = np.divmod(np.arange(R * (S + 1)), S + 1)

    def flow(rank, step):
        return rank % R * (S + 1) + step

    before = np.maximum(k - 1, 0)
    preds = np.stack([flow(r - 1, before), flow(r, before),
                      flow(r + 1, before)], axis=1)
    preds[:, 0] = np.where(k > 1, preds[:, 0], -1)   # step 1: no r - 1
    preds = np.where((k > 0)[:, None], preds, -1)
    preds = np.concatenate([preds, np.full((len(r), 1), -1)], axis=1)
    return Dag(r, np.where(k == 0, r, (r + 1) % R), preds)


def ring_step(ranks: int, steps: int) -> np.ndarray:
    """Each flow's step in ``ring_dag(ranks, steps)`` (0: the
    self-copy)."""
    return np.arange(int(ranks) * (int(steps) + 1)) % (int(steps) + 1)
