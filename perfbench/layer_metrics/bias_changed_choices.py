"""Layer ``experts``: the share, in %, of the (token, choice) pairs of the
traced units whose expert the router would not have chosen without its bias,
counted by the program's own router (``counters["bias_changed_choices"]``, a
function the job hands out and runs after the windows). 0 means the bias is
idle and the cell measures less than it says. A program whose router has no
bias, or a job that keeps no such counter, reads as nothing."""


def read(reading):
    count = reading.counters.get("bias_changed_choices")
    share = count() if callable(count) else None
    return None if share is None else 100.0 * share
