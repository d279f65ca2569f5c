"""Times the trainer's step function went from a jaxpr to an MLIR module
before the program's set-up record closed: 1 where the lowering somebody
asked for before the first step is the one the first step runs, 2 where
the step is lowered twice (ROADMAP A6)."""
from benchmark.trace import setup

UNIT, LAYER, MOVES, SOURCE = "count", "trainer", "setup_s", "program_counter"


def read(run):
    return setup.step_lowerings()
