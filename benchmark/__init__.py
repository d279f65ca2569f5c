"""The benchmark of byteps_tpu: harness, yardstick and plain references.

Nothing here is imported by the program. ``run.py`` is the one command
``BENCHMARK.json`` names; everything that belongs to one configuration,
one traffic mix or one per-layer metric is a file of its own, found by
the name ``BENCHMARK.json`` gives it.
"""
