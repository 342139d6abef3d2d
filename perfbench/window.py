"""The measured window: one thread keeps ``in_flight`` units of work (training
steps, forward calls) enqueued and notes the host time at which each becomes
ready. A fixed amount of work per unit, drawn from the seed.

The rate is the units between the first and the last completion over the time
between those two: a window of 40 steps is not quantised to 2.5%, and nothing
is pruned, so a stall counts. The median time between two completions is kept
beside it, and ``stall_share`` says how much of the window the units took
beyond it: on a closed loop the host is inside every interval, and that is
where a hiccup of the host shows.

Each iteration is wrapped in the profiler's own annotations, which cost
nothing while no trace is being taken and put the host's spans on the device
trace's clock while one is: ``perfbench.batch`` (making the inputs),
``perfbench.call`` (the call into the program, which returns before the device
finishes) and ``perfbench.wait`` (blocking on the oldest unit in flight).
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
import time


@dataclasses.dataclass
class WindowResult:
    attempted: int = 0
    failed: int = 0
    error: str | None = None
    done_at: list = dataclasses.field(default_factory=list)  # host clock at each completion
    dispatch_s: list = dataclasses.field(default_factory=list)  # host time for each call to return
    started_at: float = 0.0
    ended_at: float = 0.0

    def intervals(self) -> list:
        return [b - a for a, b in zip(self.done_at, self.done_at[1:])]

    def units_per_s(self) -> float | None:
        """Units between the first and the last completion over the time
        between those two: every stall counts."""
        gaps = self.intervals()
        return len(gaps) / sum(gaps) if gaps else None

    def median_units_per_s(self) -> float | None:
        """One unit over the median time between two completions."""
        gaps = self.intervals()
        return 1.0 / statistics.median(gaps) if gaps else None

    def stall_share(self) -> float | None:
        """Share of the time between the first and the last completion that the
        units took beyond the median interval."""
        gaps = self.intervals()
        return 1.0 - statistics.median(gaps) * len(gaps) / sum(gaps) if gaps else None


def run_window(job, *, in_flight: int, seconds: float | None = None, units: int | None = None,
               clock=time.perf_counter) -> WindowResult:
    """Issue units for ``seconds`` (or exactly ``units`` of them), then drain.

    ``job.make_batch()`` makes one unit's inputs on the host, ``job.issue(batch)``
    calls the program and returns a handle without waiting, ``job.wait(handle)``
    blocks until that unit is ready. What a unit returned is judged after the
    window (``job.failed_units()``), so nothing is read back inside it that the
    caller of the traffic file would not read. A unit that raises ends the
    window: the state it was given is gone."""
    import jax

    res = WindowResult(started_at=clock())
    pending = collections.deque()

    def finish_oldest():
        with jax.profiler.TraceAnnotation("perfbench.wait"):
            job.wait(pending.popleft())
        res.done_at.append(clock())

    try:
        while (units is None or res.attempted < units) and \
                (seconds is None or clock() - res.started_at < seconds):
            with jax.profiler.StepTraceAnnotation("perfbench.unit", step_num=res.attempted):
                with jax.profiler.TraceAnnotation("perfbench.batch"):
                    batch = job.make_batch()
                res.attempted += 1
                with jax.profiler.TraceAnnotation("perfbench.call"):
                    t0 = clock()
                    handle = job.issue(batch)
                    res.dispatch_s.append(clock() - t0)
                pending.append(handle)
                if len(pending) >= in_flight:
                    finish_oldest()
        while pending:
            finish_oldest()
    except Exception as e:  # noqa: BLE001 - the boundary: reported in the result, fails the run
        import traceback

        traceback.print_exc()
        res.error = f"{type(e).__name__}: {e}"[:500]
        res.failed = 1 + len(pending)
    res.ended_at = clock()
    return res
