"""From the profiler's ``.xplane.pb`` to what the per-layer metrics read."""
