"""The plain reference of the recursive-doubling allreduce: numpy only,
float64, nothing of the program.

``MPI_Allreduce`` as SimGrid's OpenMPI selector stages it for messages
under 10,000 bytes (``smpi_openmpi_selector.cpp`` ->
``allreduce-rdb.cpp``) among R = 2^n ranks: n steps, and in step k
(0 <= k < n) rank r does one ``sendrecv`` with rank r xor 2^k, the
whole message each way.  This module builds that dependency graph
ITSELF (``rdb_dag``); the LV08 max-min system of its R x n messages on
the dragonfly, their delays and the drain are ``dragonfly_lv08_dag``'s
beside it (routes, constants and the solver from ``dragonfly_lv08``),
which take any ``Dag``:

* a message is posted when both its ranks have finished step k - 1,
  the send AND the receive of each (four messages; none for step 0);
* it is on the wire ``latency-factor`` x the sum of its route's link
  latencies later, at penalty 1 and with no window bound, and shares
  the links max-min fairly with whatever else is on the wire then.

Flow f is message (r, k) with f = r x n + k; ``Dag`` names its ranks,
so the comparison goes by (sender, receiver) and the two sides need not
number their flows alike.  A rank count that is no power of two has a
fold-in step this reference does not build: refused.

``precision="bf16"`` is the control (see ``dragonfly_lv08``).
"""

from __future__ import annotations

import numpy as np

from .dragonfly_lv08_dag import (LATENCY_FACTOR, Dag,  # noqa: F401
                                 dag_system, drain)


def rdb_dag(ranks: int) -> Dag:
    R = int(ranks)
    n = R.bit_length() - 1
    if R < 2 or R != 1 << n:
        raise ValueError(f"recursive doubling among {ranks} ranks folds "
                         f"the ranks over a power of two in first: not "
                         f"this reference's graph")
    r, k = np.divmod(np.arange(R * n), n)

    def flow(rank, step):
        return rank * n + step

    peer = r ^ (1 << k)
    before = 1 << np.maximum(k - 1, 0)
    # the sender's send and receive of step k - 1, then the receiver's
    preds = np.stack([flow(r, k - 1), flow(r ^ before, k - 1),
                      flow(peer, k - 1), flow(peer ^ before, k - 1)],
                     axis=1)
    return Dag(r, peer, np.where((k > 0)[:, None], preds, -1))
