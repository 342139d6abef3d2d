"""Layer ``experts``: the share, in %, of the (token, choice) pairs of the traced
units that fell to a zero-compute expert, counted by the program's own routers
(``counters["zero_expert_choices"]``, a function the job hands out): 33.3 when
the router is even over 256 of 768 outputs; 0 means the mechanism is idle. A
program whose router has no such experts, or a job that keeps no such counter,
reads as nothing."""


def read(reading):
    count = reading.counters.get("zero_expert_choices")
    share = count() if callable(count) else None
    return None if share is None else 100.0 * share
