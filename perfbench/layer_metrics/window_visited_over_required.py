"""Layer ``attention``: the score elements of the tiles the claimed kernel's
mask visits in a window layer over the pairs the equations require there
(``counters["window_tiles"]``, which the job reads from the program's own mask).
1.0 computes nothing outside the window; tiles of 1024 under a window of 2048
visit three key tiles a query tile, about 1.5. A program without the window's
claim, or a job that keeps no such counter, reads as nothing."""


def read(reading):
    count = reading.counters.get("window_tiles")
    tiles = count() if callable(count) else None
    return tiles[0] / tiles[1] if tiles else None
