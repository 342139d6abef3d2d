"""Layer ``entry``: share, in %, of the untraced window's time that the units
took beyond the median time between two completions: 100 * (1 - rate / rate by
the median interval). ``tokens_per_s`` counts every stall; this says how much
of it stalls took."""


def read(reading):
    share = reading.window.stall_share()
    return None if share is None else 100.0 * share
