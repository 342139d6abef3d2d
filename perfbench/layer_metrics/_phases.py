"""Shared by the ``phase_*_s`` readers: the program's own compile-phase spans
(``thunder_tpu.compile_phases()``: one record a phase a compile, on the host
clock the harness's spans and ``window.started_at`` are on), summed over the
named phases. Only set-up counts: a record that ended after the measured
window started (the lowering ``job.compiled()`` asks for, the check's step) is
left out."""


def seconds(reading, *phases):
    """Seconds set-up spent in ``phases``; ``None`` where the program keeps no
    such spans (a commit before PR 24) or recorded none of these."""
    try:
        from thunder_tpu import compile_phases
    except ImportError:
        return None
    spans = [r["s"] for r in compile_phases()
             if r["phase"] in phases and r["at"] < reading.window.started_at]
    return sum(spans) if spans else None
