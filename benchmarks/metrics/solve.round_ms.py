"""Device time of the solve programs in the trace over the rounds
``opstats`` counted (``fixpoint_rounds``)."""

from lib.readers import program_round_ms

#: the compiled chunk programs of lmm_jax.solve_arrays, as XLA names them
NEEDLE = "jit__solve_kernel_chunk"


def read(run):
    return program_round_ms(run, NEEDLE)
