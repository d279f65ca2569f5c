"""Host seconds of the trainer's constructor: the program's
``bps.setup.init`` span in its own set-up record (the parameters' copy
onto the mesh, the optimizer's state, the jit objects; the small programs
JAX compiles for them are inside it and are ``trainer.other_compile_s``)."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "s", "trainer", "setup_s", "program_span"


def read(run):
    return setup.span_s("bps.setup.init")
