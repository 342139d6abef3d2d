"""Shared by the ``dispatch_*_ms`` readers: the program's own record of each
``thunder_tpu.jit`` call (``thunder_tpu.dispatch_phases()``: one record a call,
``at`` on the host clock the window's ``started_at`` and ``ended_at`` are on).
Only the untraced window counts: the same calls ``dispatch_ms`` times from
outside."""

import statistics


def median_ms(reading, field):
    """Median of ``field`` in ms over the calls that returned inside the
    window; ``None`` where the program keeps no such records (a commit before
    ``dispatch_phases``) or made no call there."""
    try:
        from thunder_tpu import dispatch_phases
    except ImportError:
        return None
    window = reading.window
    values = [r[field] for r in dispatch_phases() if window.started_at <= r["at"] <= window.ended_at]
    return 1e3 * statistics.median(values) if values else None
