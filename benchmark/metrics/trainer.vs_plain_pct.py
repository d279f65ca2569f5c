"""What the trainer and its exchange cost over a plain-JAX step of the
same model: (trainer step - plain step) / plain step, each arm
``ARM_STEPS`` steps timed together with the profiler off, one arm after
the other in the same process."""

UNIT, LAYER, MOVES, SOURCE = "%", "trainer", "tokens_per_s_chip", "host_clock"
NEEDS = ("plain_arm",)


def read(run):
    if not run.plain_step_s:
        return None
    return 100.0 * (run.trainer_step_s - run.plain_step_s) / run.plain_step_s
