"""Layer ``attention``: the operations the chunked form of the recurrence
performs at the chunk the program published, over the operations the recurrence
requires (``counters["ssm_chunk_ops"]``, which the job reckons from
``perfbench/flops_ssm.py`` and the program's own chunk). 1.0 would compute
nothing beyond the recurrence; a kernel that carries the state in fast memory
moves it by its choice of chunk. A program that publishes no chunk, or a job
that keeps no such counter, reads as nothing."""


def read(reading):
    count = reading.counters.get("ssm_chunk_ops")
    ops = count() if callable(count) else None
    return ops[0] / ops[1] if ops else None
