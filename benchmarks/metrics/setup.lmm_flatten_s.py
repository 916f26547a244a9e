"""Host seconds of set-up inside ``lmm_jax.flatten`` (the program's
``lmm.flatten`` span): the live host system walked into COO arrays."""

from lib.scopes import setup_span_s


def read(run):
    return setup_span_s(run, "lmm.flatten")
