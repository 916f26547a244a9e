"""Device time of the superstep programs in the trace over the rounds
``opstats`` counted inside them."""

from lib.readers import program_round_ms

#: lmm_drain's superstep programs, as XLA names them
NEEDLE = "jit__superstep_program"


def read(run):
    return program_round_ms(run, NEEDLE)
