"""Layer ``attention``: over the traced units, sparse layers, key-value heads
and tiles of 128 consecutive queries, the mean count of distinct blocks a
tile's queries chose between them, over ``topk``, counted by the program's own
selection (``counters["sparse_tile_union"]``, a function the job hands out and
runs after the windows). 1.0 means neighbouring queries agree; a high reading
is the worst case for a kernel that shares blocks across a tile. A program
without the selection, or a job that keeps no such counter, reads as nothing."""


def read(reading):
    count = reading.counters.get("sparse_tile_union")
    return count() if callable(count) else None
