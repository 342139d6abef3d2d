"""Layer ``trace-claim``: seconds the program spent tracing, transforming and
claiming. The job takes it from the program's own compile-phase spans where
it has them (``thunder_tpu.jit``) and from the host clock around
``build_train_step`` where it has none."""


def read(reading):
    return reading.spans.get("trace_claim_s")
