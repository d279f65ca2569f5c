"""Trace, lower and compile seconds of every function but the step that
JAX compiled while the program's set-up record was open (the
constructor's small programs: a copy a parameter shape, the optimizer's
``init``); what was compiled inside another function's trace is part of
that trace and left out."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "s", "trainer", "setup_s", "program_span"


def read(run):
    return setup.other_compile_s()
