"""Layer ``xla-compile``: backend compiles plus persistent-cache reads inside
the measured window, from the benchmark's ``jax.monitoring`` listener."""


def read(reading):
    return reading.counters.get("compiles_in_window")
