"""Layer ``xla-compile``: host clock of the first call: jax trace, lower, XLA
compile or persistent-cache read, first run."""


def read(reading):
    return reading.spans.get("compile_first_call_s")
