#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Any platform but ``tpu``, or fewer chips than the
cell asks for, exits non-zero with no result. The last line of stdout is one
JSON object with the keys ``correct``, ``attempted``, ``failed``, ``metrics``
and ``device`` (and with ``--trace 1`` ``breakdown``); everything else a run
has to say goes on earlier lines.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` runs the
same window, untraced, for the host-side per-layer numbers, then traces a few
more units of work with the profiler and reports the cell's per-layer metrics.

``--rehearse`` (an explicit argument, never a default) runs the same code at
the tiny stand-in sizes the configuration and traffic files name, on virtual
CPU devices with the kernels interpreted: for the tests and for debugging
before a chip call. Its ``device`` says ``cpu`` and it proves nothing about
speed.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)


def log(*parts) -> None:
    print(*parts, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse", action="store_true",
                   help="stand-in sizes on virtual CPU devices; never proof of the chip")
    return p.parse_args(argv)


def place_compile_cache() -> str:
    """jax's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at ``<checkout>/.jax_cache``: a fixed path inside the checkout
    (the path is part of the cache's key). The program finds a directory
    already set and leaves it."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        path = os.path.join(CHECKOUT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return jax.config.jax_compilation_cache_dir


def executable_needs(compiled) -> tuple[int, dict]:
    """(bytes a device needs for the executable by the compiler's own account,
    arguments + outputs + temporaries - aliased; the sizes it is made of)."""
    ma = compiled.memory_analysis()
    analysis = {k: getattr(ma, k) for k in ("argument_size_in_bytes", "output_size_in_bytes",
                                            "temp_size_in_bytes", "alias_size_in_bytes",
                                            "generated_code_size_in_bytes", "peak_memory_in_bytes")
                if hasattr(ma, k)}
    return (analysis["argument_size_in_bytes"] + analysis["output_size_in_bytes"]
            + analysis["temp_size_in_bytes"] - analysis["alias_size_in_bytes"]), analysis


def memory_report(compiled, devices) -> dict:
    """``peak_hbm_bytes`` is ``executable_needs``. The allocator's readings
    are printed beside it: on the TPU it counts a program's temporaries under
    ``bytes_reserved``, not ``bytes_in_use``."""
    needs, analysis = executable_needs(compiled)
    stats = [d.memory_stats() or {} for d in devices]
    allocator_peak = max((s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats),
                         default=0)
    return {"peak_hbm_bytes": needs, "memory_analysis": analysis, "allocator_peak_bytes": allocator_peak,
            "allocator": [{k: s.get(k) for k in ("peak_bytes_in_use", "peak_bytes_reserved",
                                                 "bytes_in_use", "bytes_limit")} for s in stats]}


class Collections:
    """Python's collector, watched: seconds spent collecting, by generation.

    Set-up leaves some 335,000 tracked objects behind (what the imports made,
    the program's traces, jax's caches), and one full collection of them takes
    0.11 s of the v5e's host (PR 22). When the next one falls due depends on
    the allocations before it; the closed loop lost one interval of 0.10 to
    0.11 s, once, in 3 runs of 8 before this was here (PERF.md, PR 22). So
    set-up's garbage is collected in set-up, and what survives is frozen
    (``gc.freeze``) until the windows are over. The collector stays on: what
    a unit of work allocates is collected inside the window, at its cost, and
    the ``window:`` line shows it."""

    def __init__(self):
        self.seconds: dict[int, list] = {}
        self._started = 0.0
        gc.callbacks.append(self._on_event)

    def _on_event(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._started = time.perf_counter()
        else:
            self.seconds.setdefault(info["generation"], []).append(time.perf_counter() - self._started)

    def settle(self) -> dict:
        """The end of set-up: collect what it left, freeze what survives."""
        counts, t0 = gc.get_count(), time.perf_counter()
        gc.collect()
        gc.freeze()
        self.seconds.clear()
        return {"counts_before": counts, "frozen": gc.get_freeze_count(), "collect_s": time.perf_counter() - t0}

    def summary(self) -> dict:
        """{generation: [collections, seconds in all, longest]} since ``settle``."""
        return {g: [len(v), sum(v), max(v)] for g, v in sorted(self.seconds.items())}


def traced_units(job, cell, in_flight: int):
    """A few more units of work under the profiler; the reduced trace."""
    import jax

    from perfbench import window, xplane

    log_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's own annotations are enough
        options.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            res = window.run_window(job, in_flight=in_flight, units=cell.traffic["trace_units"])
        finally:
            jax.profiler.stop_trace()
        return res, xplane.load(xplane.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    from perfbench import manifest

    cell = manifest.load_cell(args.workload)

    if args.rehearse:  # before jax is imported; the kernels then run interpreted
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8").strip()
        os.environ["THUNDER_FLASH_FORCE"] = "1"

    import jax

    # The device gate, before anything of the program is imported.
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device} jax={jax.__version__}")
    want = "cpu" if args.rehearse else "tpu"
    if device["platform"] != want or len(devices) < cell.chips:
        print(f"perfbench: jax reports {device}; {cell.name} needs {cell.chips} device(s) of platform "
              f"{want!r}. Nothing was run.", file=sys.stderr)
        return 2
    used = devices[: cell.chips]

    from perfbench import compile_events, peaks, reading, window

    log(f"compile cache: {place_compile_cache()}")
    events = compile_events.CompileEvents()
    peak_table = peaks.peaks_for(device["kind"]) if device["platform"] == "tpu" else None

    # Set-up: weights from the seed, trace and claim, compile or cache read,
    # first unit, warm-up of the cell's one shape.
    job = importlib.import_module(f"perfbench.jobs.{cell.traffic['job']}").Job(
        cell, seed=args.seed, platform=want, rehearse=args.rehearse)
    collector = Collections()
    job.setup()
    settled = collector.settle()
    setup_s = time.perf_counter() - PROCESS_START
    setup_events = events.snapshot()
    log(f"set-up: {setup_s:.2f}s spans={json.dumps(job.spans)} compile_events={json.dumps(setup_events)} "
        f"heap={json.dumps(settled)}")

    # The measured window.
    in_flight = cell.traffic["in_flight"]
    win = window.run_window(job, in_flight=in_flight, seconds=args.seconds)
    in_window = events.since(setup_events)
    job.counters["compiles_in_window"] = in_window["backend_compiles"] + in_window["cache_reads"]
    units_per_s = win.units_per_s()
    tokens_per_s = units_per_s * job.tokens_per_unit if units_per_s else None
    gaps = sorted(win.intervals())
    log(f"window: attempted={win.attempted} completed={len(win.done_at)} "
        f"seconds={win.ended_at - win.started_at:.3f} units_per_s={units_per_s} "
        f"(by the median interval {win.median_units_per_s()}) tokens_per_s={tokens_per_s} "
        f"interval_s min/median/p95/max="
        f"{[gaps[0], gaps[len(gaps) // 2], gaps[(len(gaps) * 95) // 100], gaps[-1]] if gaps else None} "
        f"collections_in_window={json.dumps(collector.summary())} "
        f"compile_events_in_window={json.dumps(in_window)} error={win.error}")

    trace = trace_res = None
    if args.trace and not win.error:
        trace_res, trace = traced_units(job, cell, in_flight)
        log(f"traced units: {trace_res.attempted}, units_per_s under the profiler {trace_res.units_per_s()} "
            f"(untraced {units_per_s}) error={trace_res.error}")
    gc.unfreeze()  # the windows are over: what is released below can be collected again
    error = win.error or (trace_res.error if trace_res is not None else None)
    if error:
        # The state a raising unit was given is gone: nothing more of the
        # program can be asked. What was measured is reported, as not correct.
        log(f"NOT CORRECT: a unit of work raised: {error}")
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}} if not args.trace else {}
        log(json.dumps({"correct": False, "attempted": win.attempted, "failed": win.failed or trace_res.failed,
                        "metrics": metrics, "device": device}))
        return 0

    failed = job.failed_units()
    memory = memory_report(job.compiled(), used)
    log(f"memory: {json.dumps(memory)}")
    problems = job.validity()
    if job.counters["compiles_in_window"]:
        problems.append(f"{job.counters['compiles_in_window']} compile(s) or cache read(s) inside the window")

    # Correctness, outside the window and after the memory was read: the
    # system's state goes first, so that the reference has the device.
    job.release()
    t0, before_check = time.perf_counter(), events.snapshot()
    reference = importlib.import_module(f"perfbench.reference.{cell.config['reference']}")
    verdict = job.check(reference)
    log(f"check: {time.perf_counter() - t0:.2f}s {json.dumps(verdict)} "
        f"compile_events={json.dumps(events.since(before_check))}")
    if not verdict["ok"]:
        problems.append("the system disagrees with the reference beyond the tolerance")
    if failed:
        problems.append(f"{failed} unit(s) of work failed or were not finite")
    for p in problems:
        log(f"NOT CORRECT: {p}")

    measured = {"setup_s": setup_s, "tokens_per_s": tokens_per_s,
                "peak_hbm_gb": memory["peak_hbm_bytes"] / 1e9}
    metrics = {}
    if args.trace:
        r = reading.Reading(
            cell=cell, spans=job.spans, counters=job.counters, window=win, tokens_per_s=tokens_per_s,
            flops_per_token=job.flops_per_token(),
            peaks=peak_table, trace=trace, traced_units=trace_res.attempted)
        log(f"kernel families: {json.dumps(reading.kernel_family_table(r))}")
        for m in cell.per_layer:
            value = reading.read_metric(m["name"], r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if measured.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}

    device["memory_peak_bytes"] = memory["allocator_peak_bytes"] or memory["peak_hbm_bytes"]
    result = {"correct": not problems, "attempted": win.attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        from perfbench import xplane

        # A trace of a CPU (--rehearse) has no device plane: not measured.
        device["busy_s"], device["window_s"] = xplane.busy_and_window(trace) if trace.devices else (None, None)
        result["breakdown"] = reading.breakdown(r)
    log(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
