#!/usr/bin/env python
"""Fleet soak: sustained mixed-fault abuse with a committed goodput number.

The falsifiable half of ISSUE 11: run the FSDP×TP training workload (plus a
sidecar thunder-jit dispatch standing in for serving traffic) on the
virtual 8-device mesh for hundreds of steps under a **seeded random chaos
schedule** — host_loss, collective_hang, sdc, oom, preempt, ckpt_io,
interleaved and occasionally overlapping — with the fleet autopilot
(``resilience/autopilot.py``) deciding every recovery. The run must end
with ZERO unrecovered faults and ZERO unactuated decisions (the replay
correlation rules), and its headline is **goodput**:

    goodput = (useful_tokens / wall_s) × (1 − resilience_overhead_pct/100)

where ``useful_tokens`` counts each of the N steps once (re-executed steps
after a restore are waste, paid in ``wall_s``), ``wall_s`` is the whole
soak wall clock including every recovery/rebuild/restore, and the overhead
pct is the directly-measured steady-state cost of the watchdog + SDC
guard. One number that
only improves if speed AND resilience hold simultaneously.

Output: one JSON line (the committed ``SOAK_r*.json`` series), gated by
``scripts/perf_report.py --history SOAK_r*.json --gate`` with soak-sized
noise floors. ``scripts/lint_traces.py --soak`` runs a short deterministic
smoke of this driver in CI.

Usage::

    python scripts/soak_fleet.py                          # 200 steps, seed 1
    python scripts/soak_fleet.py --steps 200 --faults 14 \
        --seed 1 --out SOAK_r01.json
    python scripts/soak_fleet.py --smoke                  # CI-sized run
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr)


# =============================================================================
# The seeded chaos schedule
# =============================================================================

# Every required seam appears at least once so each autopilot policy class
# is exercised on any seed: host_loss/collective_hang -> elastic_resume,
# sdc -> quarantine_rerun, oom -> deopt_escalate, preempt ->
# checkpoint_halt, ckpt_io -> the manager's own retry; the tiered-
# checkpoint seams (ISSUE 14) -> the snapshot pipeline degrades one tier
# and keeps going (torn/slow flush -> a later commit; corrupt replica ->
# the restore ladder's checksum fall-through); straggler (ISSUE 15) -> a
# sub-timeout slowdown the STREAMING DETECTORS must flag (anomaly event,
# positive detection lead) before any watchdog timeout would.
REQUIRED_SEAMS = ("host_loss", "collective_hang", "sdc", "oom", "ckpt_io",
                  "preempt", "snap_torn", "snap_corrupt", "snap_slow",
                  "straggler")

# Fault classes a streaming detector covers (ISSUE 15): the soak gate
# requires >=1 anomaly of the mapped kinds whenever the class was injected
# (perf_report checks soak_undetected_detector_classes == 0).
DETECTED_FAULT_CLASSES = {
    "straggler": ("step_time_drift", "goodput_drop", "host_spread"),
    "oom": ("recompile_storm",),
}
# The filler pool excludes preempt: each preempt is a full
# checkpoint-and-halt + process-restart cycle, and one per soak is the
# scenario; a schedule of mostly restarts would measure restart latency,
# not goodput under churn. It also excludes the snap seams: they are
# near-free by design, and padding the schedule with them would flatter
# the per-fault recovery number instead of stressing the heavy actuators.
FILLER_SEAMS = ("host_loss", "collective_hang", "sdc", "oom", "ckpt_io")
# Seams that fire lazily at a later seam visit (a background flush, a
# tiered restore) rather than at their trigger step.
_LAZY_SNAP_SEAMS = ("snap_torn", "snap_slow")


@dataclass
class ScheduledFault:
    """One schedule entry: ``seam`` is armed at the end of ``step`` (so it
    fires on step+1's boundary/dispatch). Entries sharing a ``step`` are an
    overlapping pair — both armed before either recovery runs. ``target``
    carries a seam-specific target clause (the snap_corrupt tier)."""

    step: int
    seam: str
    target: str = None


def make_schedule(seed: int, n_steps: int, n_faults: int,
                  overlap_pairs: int = 2) -> list[ScheduledFault]:
    """Deterministic mixed-fault schedule: ``n_faults`` events over
    ``n_steps`` steps, covering every REQUIRED_SEAMS kind, with
    ``overlap_pairs`` of them sharing a trigger step (arriving before the
    prior fault's recovery has run). Same seed → same schedule.

    Tiered-checkpoint seams get special placement: ``snap_torn``/
    ``snap_slow`` fire at the NEXT background flush, so they are pinned
    into the early third of the run (armed at the tail they would never
    see a flush and never inject); ``snap_corrupt`` fires at the next
    tiered restore, so it is co-scheduled onto an elastic-driving fault's
    step (host_loss/collective_hang — whose recovery IS a restore) and
    targets the local tier, forcing the ladder through the buddy
    replica."""
    if n_faults < len(REQUIRED_SEAMS):
        raise ValueError(
            f"need at least {len(REQUIRED_SEAMS)} faults to cover every seam"
        )
    rng = random.Random(seed)
    seams = list(REQUIRED_SEAMS)
    while len(seams) < n_faults:
        pick = rng.choice(FILLER_SEAMS)
        # The de-opt ladder is 3 levels deep and sticky per function: a 4th
        # oom would exhaust it and (correctly) kill the run — cap the
        # schedule at what the ladder can absorb.
        if pick == "oom" and seams.count("oom") >= 3:
            continue
        seams.append(pick)
    # The recompile-storm detector needs >=2 recompiles inside its window
    # (ISSUE 15): with any filler slots at all, guarantee a second oom so
    # the storm anomaly is deterministic on every seed.
    if len(seams) > len(REQUIRED_SEAMS) and seams.count("oom") < 2:
        seams[len(REQUIRED_SEAMS)] = "oom"
    rng.shuffle(seams)
    # The preempt goes late: everything after it replays in the "restarted
    # process", and a very early halt would leave most faults untested
    # before the restart. It must land in the SLOT region (the first
    # n_slots seams get their own trigger step) — in the overlap tail it
    # would be co-scheduled onto another fault's step, whose recovery
    # would then fire in no process after the halt.
    n_slots = n_faults - overlap_pairs
    seams.remove("preempt")
    seams.insert(min(int(len(seams) * 0.6), max(0, n_slots - 1)), "preempt")
    lo, hi = 3, max(4, n_steps - 4)
    spacing = max(3, (hi - lo) // max(1, n_slots))
    slots = []
    for i in range(n_slots):
        base = lo + i * spacing
        slots.append(min(hi, base + rng.randrange(max(1, spacing - 2))))
    schedule = [ScheduledFault(step, seam) for step, seam in zip(slots, seams)]
    # Overlapping pairs: the remaining seams land ON an existing slot.
    # A preempt never overlaps (its recovery is a process exit — the pair's
    # second fault would fire in nobody's process).
    candidates = [f for f in schedule if f.seam != "preempt"]
    for seam in seams[n_slots:]:
        host = rng.choice(candidates)
        schedule.append(ScheduledFault(host.step, seam))
    # Tiered-checkpoint seam placement (docstring): torn/slow flush seams
    # must still have a flush ahead of them; a corrupted replica must have
    # a restore ahead of it.
    preempt_steps = {f.step for f in schedule if f.seam == "preempt"}
    early_hi = lo + max(3, (hi - lo) // 3)
    for f in schedule:
        if f.seam in _LAZY_SNAP_SEAMS and f.step > early_hi:
            step = lo + rng.randrange(max(1, early_hi - lo))
            while step in preempt_steps:
                step = lo + rng.randrange(max(1, early_hi - lo))
            f.step = step
    # Straggler placement (ISSUE 15): late enough that the step-time
    # detectors have a baseline (min_samples of clean steps), and with at
    # least one elastic-driving fault still AHEAD of it — the anomaly must
    # precede a hang/host-loss decision for detection lead to be positive
    # and measurable.
    straggler_step = None
    for f in schedule:
        if f.seam == "straggler":
            f.step = min(10 + rng.randrange(4), hi)
            while f.step in preempt_steps:
                f.step += 1
            straggler_step = f.step
    elastic_hosts = [f for f in schedule
                     if f.seam in ("host_loss", "collective_hang")]
    if straggler_step is not None and elastic_hosts and not any(
            f.step > straggler_step + 2 for f in elastic_hosts):
        # Every hang/host-loss landed before the straggler window: push the
        # latest one past it so its decision can cite the anomaly.
        latest = max(elastic_hosts, key=lambda f: f.step)
        latest.step = min(straggler_step + 4 + rng.randrange(3), hi)
        while latest.step in preempt_steps:
            latest.step += 1
    # snap_corrupt co-schedules AFTER the adjustments above so the restore
    # that must follow it really does (the host it rides may have moved).
    for f in schedule:
        if f.seam == "snap_corrupt" and elastic_hosts:
            f.step = rng.choice(elastic_hosts).step
            f.target = "local"
    # Re-pinning (lazy snap seams, the straggler, the elastic adjustment)
    # can strand an overlap-tail entry alone on its step: repair by
    # co-scheduling movable mid-weight seams (armed-at-step, position-
    # insensitive) until the requested pairs are back.
    def _pairs() -> int:
        by_step: dict[int, int] = {}
        for f in schedule:
            by_step[f.step] = by_step.get(f.step, 0) + 1
        return sum(n - 1 for n in by_step.values() if n > 1)

    while _pairs() < overlap_pairs:
        counts: dict[int, int] = {}
        for f in schedule:
            counts[f.step] = counts.get(f.step, 0) + 1
        movable = [f for f in schedule
                   if f.seam in ("sdc", "ckpt_io", "oom")
                   and counts[f.step] == 1]
        targets = [f for f in schedule
                   if f.seam not in ("preempt", "straggler")
                   and f.step not in preempt_steps]
        if not movable:
            break
        mover = movable[-1]
        choices = [f for f in targets
                   if f is not mover and f.step != mover.step]
        if not choices:
            break
        mover.step = rng.choice(choices).step
    schedule.sort(key=lambda f: (f.step, f.seam))
    return schedule


def overlapping_pairs(schedule: list[ScheduledFault]) -> int:
    by_step: dict[int, int] = {}
    for f in schedule:
        by_step[f.step] = by_step.get(f.step, 0) + 1
    return sum(n - 1 for n in by_step.values() if n > 1)


def arm_fault(cfg, fault: ScheduledFault, *, hang_delay_s: float) -> None:
    """Append ``fault``'s FaultRule to the LIVE chaos config — the soak's
    step callback arms each scheduled fault at its trigger step, which is
    what lets two entries overlap deterministically (both rules armed
    before either recovery runs)."""
    from thunder_tpu.resilience.chaos import FaultRule

    seam = fault.seam
    if seam in ("host_loss", "preempt"):
        # Step-targeted: fires at the NEXT step's boundary check.
        cfg.rules.append(FaultRule(seam, target=str(fault.step + 1)))
    elif seam == "collective_hang":
        cfg.rules.append(FaultRule(seam, delay_s=hang_delay_s))
    elif seam == "snap_slow":
        # A slow flush must be slow relative to the flush cadence so the
        # single-in-flight backpressure actually coalesces behind it, but
        # must not dwarf the recovery budget it rides in.
        cfg.rules.append(FaultRule(seam, delay_s=min(1.0, hang_delay_s / 4)))
    elif seam == "snap_corrupt":
        # Fires at the next tiered restore; the target picks the tier(s).
        cfg.rules.append(FaultRule(seam, target=fault.target or "local"))
    elif seam == "straggler":
        # Sub-timeout slowdown over several consecutive guarded steps
        # (target "step" fires inside watchdog.guard_call, never on the
        # sidecar): big vs the ms-scale CPU-mesh step, far below the
        # watchdog timeout — only the streaming detectors can see it.
        cfg.rules.append(FaultRule(seam, target="step", count=5,
                                   delay_s=hang_delay_s / 200.0))
    else:  # sdc, oom, ckpt_io, snap_torn: fire at their next seam visit
        cfg.rules.append(FaultRule(seam))


# =============================================================================
# The soak run
# =============================================================================


def _build_workload(args):
    """The FSDP×TP training workload + per-mesh builders (the
    lint_traces --chaos-multihost idiom) and the sidecar thunder-jit
    dispatch (the 'serving traffic' that owns the oom/de-opt seam)."""
    import numpy as np

    import thunder_tpu as ttpu
    import thunder_tpu.torch as ttorch
    from thunder_tpu.core import dtypes
    from thunder_tpu.models import gpt as m
    from thunder_tpu.parallel import build_train_step, make_mesh
    from thunder_tpu.parallel.sharding import gpt_param_specs
    from thunder_tpu.parallel.train import opt_state_specs

    cfg = m.name_to_config(args.model)
    params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
    rng = np.random.RandomState(args.seed)
    idx = rng.randint(0, cfg.vocab_size, (args.batch, args.seq)).astype(np.int32)
    tgt = np.roll(idx, -1, axis=1).astype(np.int32)

    from thunder_tpu.resilience.elastic import mesh_shape

    step_cache: dict = {}

    def build_for_mesh(mesh):
        key = tuple(sorted((mesh_shape(mesh) or {}).items()))
        if key in step_cache:
            return step_cache[key]
        specs = gpt_param_specs(cfg, mesh)
        step, _ = build_train_step(
            cfg, params, idx, tgt, mesh=mesh, param_specs=specs, lr=1e-2,
            executors=["jax"], donate=False,
        )

        def step_fn(state):
            p, o = state
            p, o, loss = step(p, o, idx, tgt)
            return (p, o), float(np.asarray(loss))

        step_cache[key] = step_fn
        return step_fn

    def specs_for_mesh(mesh):
        p_specs = gpt_param_specs(cfg, mesh)
        return (p_specs, opt_state_specs(p_specs))

    mesh = make_mesh(fsdp=args.devices // 2, tp=2)
    # Build the opt state once on the full mesh.
    specs = gpt_param_specs(cfg, mesh)
    _, opt0 = build_train_step(
        cfg, params, idx, tgt, mesh=mesh, param_specs=specs, lr=1e-2,
        executors=["jax"], donate=False,
    )

    # Sidecar "serving" dispatch: a thunder-jit function whose dispatches
    # run through api._run_entry — the seam where oom fires and the de-opt
    # ladder (deopt_escalate decisions) recovers.
    xa = rng.randn(4, 8).astype(np.float32)
    wa = rng.randn(6, 8).astype(np.float32)
    sidecar = ttpu.jit(
        lambda a, w: ttorch.sum(ttorch.gelu(ttorch.linear(a, w))),
        executors=["jax"],
    )

    tokens_per_step = args.batch * args.seq
    return (mesh, (params, opt0), build_for_mesh, specs_for_mesh,
            lambda: sidecar(xa, wa), tokens_per_step)


def _measure_overheads(step_fn, state, mesh, n: int = 6):
    """(ideal tokens-per-step denominator, resilience_overhead_pct):
    median clean step,
    median SDC checksum, median watchdog spawn, overhead measured directly
    (loop-vs-loop deltas drown in CPU-mesh jitter)."""
    from thunder_tpu.resilience.watchdog import SDCGuard, guard_call

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    guard = SDCGuard(check_every=1)
    steps, checks = [], []
    for _ in range(max(4, n)):
        t0 = time.perf_counter()
        state, _ = step_fn(state)
        t1 = time.perf_counter()
        steps.append(t1 - t0)
        guard.check_state(state)
        checks.append(time.perf_counter() - t1)
    spawns = []
    noop = lambda: None  # noqa: E731
    for _ in range(20):
        t0 = time.perf_counter()
        guard_call(noop, (), fn_name="noop", timeout_s=60.0)
        spawns.append(time.perf_counter() - t0)
    step_s, check_s, spawn_s = med(steps), med(checks), med(spawns)
    overhead_pct = ((check_s + spawn_s) / step_s * 100.0) if step_s else 0.0
    return step_s, overhead_pct, state


def run_soak(args) -> dict:
    import thunder_tpu.monitor as monitor
    from thunder_tpu.analysis import Severity
    from thunder_tpu.analysis.events import format_replay, replay_events
    from thunder_tpu.observability import metrics as obsm
    from thunder_tpu.resilience import autopilot as ap_mod
    from thunder_tpu.resilience import chaos
    from thunder_tpu.resilience.chaos import ChaosConfig
    from thunder_tpu.resilience.preemption import CheckpointManager

    import tempfile

    tmp = args.workdir or tempfile.mkdtemp(prefix="ttpu_soak_")
    log = os.path.join(tmp, "events.jsonl")
    monitor.set_event_log(log)

    # The schedule is built FIRST (deterministic per seed) so the detector
    # config below can be sized to what it will actually inject.
    schedule = make_schedule(args.seed, args.steps, args.faults,
                             overlap_pairs=args.overlap_pairs)
    n_ooms = sum(1 for f in schedule if f.seam == "oom")

    # Live ops plane (ISSUE 15): the soak runs scrapeable — per-host
    # /metrics + /healthz on an ephemeral port, the flight recorder dumping
    # on every timeout/SDC/halt, and the streaming detectors (tuned to the
    # soak's compressed timescale) feeding anomalies into the autopilot.
    plane = None
    flightrec_dir = os.path.join(tmp, "flightrec")
    if args.ops_plane:
        from thunder_tpu.observability import opsplane
        from thunder_tpu.observability.detect import DetectorConfig

        plane = opsplane.enable(
            port=0, serve=True,
            flightrec_dir=flightrec_dir, flightrec_keep=64,
            detectors=DetectorConfig(
                min_samples=6, cooldown=20, goodput_consecutive=3,
                # N recompiles inside the run = a storm at soak scale,
                # sized to the schedule's oom count (>=2 whenever it has a
                # filler slot; a minimum-size schedule carries one oom and
                # the gate must stay deterministic, not hope for
                # incidental recompiles).
                recompile_threshold=min(2, max(1, n_ooms)),
                recompile_window_s=3600.0,
            ),
        )
        _log(f"ops plane: http://127.0.0.1:{plane.port} "
             f"(/metrics /healthz /debug/state); flight recorder -> "
             f"{flightrec_dir}")

    (mesh, state0, build_for_mesh, specs_for_mesh, sidecar,
     tokens_per_step) = _build_workload(args)
    from thunder_tpu.resilience.elastic import mesh_shape

    _log(f"workload: {args.model} B={args.batch} T={args.seq} "
         f"mesh={mesh_shape(mesh)}")

    # Warm the full-mesh step + sidecar, then measure the ideal step and
    # the resilience overhead OUTSIDE the soak wall clock.
    step_fn = build_for_mesh(mesh)
    state, _ = step_fn(state0)
    sidecar()
    ideal_step_s, overhead_pct, _ = _measure_overheads(step_fn, state, mesh)
    ideal_tps = tokens_per_step / ideal_step_s if ideal_step_s else 0.0
    _log(f"ideal step {ideal_step_s * 1e3:.1f}ms -> {ideal_tps:.0f} tok/s; "
         f"resilience overhead {overhead_pct:.2f}%")

    n_overlap = overlapping_pairs(schedule)
    by_seam: dict[str, int] = {}
    for f in schedule:
        by_seam[f.seam] = by_seam.get(f.seam, 0) + 1
    _log(f"schedule (seed={args.seed}): "
         + ", ".join(f"{f.seam}@{f.step}" for f in schedule)
         + f" ({n_overlap} overlapping pair(s))")

    by_step: dict[int, list] = {}
    for f in schedule:
        by_step.setdefault(f.step, []).append(f)

    cfg = ChaosConfig(rules=[], seed=args.seed)
    # Hysteresis windows sized to the soak's compressed timescale: the
    # production defaults (minutes) span the entire CPU-mesh run, which
    # would make every repeated fault look like flapping.
    policies = ap_mod.default_policies()
    for pol in policies.values():
        pol.window_s = min(pol.window_s, args.hysteresis_window_s)
    autopilot = ap_mod.Autopilot(policies=policies)

    def fresh_manager():
        # Tiered checkpointing (ISSUE 14): a local RAM ring buddy-paired
        # with a peer store (the virtual-mesh stand-in for replicating
        # shards to another host) + the async background disk writer. A
        # restart gets a FRESH pair — the next allocation's RAM starts
        # empty, disk is the only tier that survives a process death.
        from thunder_tpu.resilience.snapshot import SnapshotStore

        store = SnapshotStore(host=0, ring=args.snapshot_ring)
        buddy = SnapshotStore(host=1, ring=args.snapshot_ring)
        SnapshotStore.pair(store, buddy)
        return CheckpointManager(os.path.join(tmp, "ckpt"), keep=3,
                                 backoff_s=0.01, store=store,
                                 async_flush=True)

    mgr = fresh_manager()

    armed: set = set()

    def on_step(step, loss):
        # Sidecar dispatch first (an armed oom fires here), then arm
        # whatever the schedule planted at this step. Each entry arms at
        # most once — steps re-executed after a restore must not re-plant
        # faults that already fired (that would turn one scheduled hang
        # into an unbounded thrash loop).
        sidecar()
        for fault in by_step.get(step, ()):  # same step = overlapping
            if id(fault) in armed:
                continue
            armed.add(id(fault))
            arm_fault(cfg, fault, hang_delay_s=args.watchdog_timeout_s * 6)

    halts = 0
    losses: list = [None] * args.steps
    reports = []
    wall0 = time.perf_counter()
    with chaos.chaos_scope(cfg):
        while True:
            try:
                state, report = ap_mod.run_autopiloted_training(
                    autopilot, build_for_mesh, state0, args.steps,
                    manager=mgr, mesh=mesh, specs_for_mesh=specs_for_mesh,
                    sdc_guard=True,
                    watchdog_timeout_s=args.watchdog_timeout_s,
                    save_every=args.save_every,
                    snapshot_every=args.snapshot_every, on_step=on_step,
                    regrow_after=args.regrow_after,
                )
                reports.append(report)
                break
            except ap_mod.AutopilotHalt as e:
                # A checkpoint_halt landed (preemption or exhausted ladder):
                # the durable checkpoint exists; "the next allocation"
                # resumes — same process, fresh driver call with EMPTY RAM
                # tiers (only disk survives a process death; the restart's
                # first restore is the soak's disk-tier coverage).
                if e.report is not None:
                    reports.append(e.report)
                halts += 1
                mgr.close()
                mgr = fresh_manager()
                _log(f"halt #{halts}: {e} — restarting from the checkpoint")
                if halts > args.max_restarts:
                    raise RuntimeError(
                        f"soak exceeded {args.max_restarts} restarts"
                    ) from e
    mgr.close()  # drain the background writer: every flush event must land
    wall_s = time.perf_counter() - wall0
    for report in reports:
        for i, v in enumerate(report.losses):
            if v is not None:
                losses[i] = v
    steps_executed = sum(r.steps_executed for r in reports)

    ops_healthz = None
    ops_port = plane.port if plane is not None else None
    if plane is not None:
        # One end-of-run scrape proves the endpoints served a real run.
        try:
            import urllib.error
            import urllib.request

            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{plane.port}/healthz", timeout=5) as r:
                    body = r.read().decode()
            except urllib.error.HTTPError as e:
                body = e.read().decode()  # 503 = a served "critical" verdict
            ops_healthz = json.loads(body).get("status")
        except Exception as e:
            ops_healthz = f"unreachable: {e}"

    monitor.set_event_log(None)
    summary, diags = replay_events(log, storm_threshold=64)
    errors = [d for d in diags if d.severity >= Severity.ERROR]
    for line in format_replay(summary, diags).splitlines():
        _log(line)

    # Ops-plane accounting (ISSUE 15), all from durable artifacts: anomaly
    # counts from the replayed log; detection lead from decisions whose
    # evidence cites a detector anomaly (decision ts − anomaly ts > 0 means
    # the detectors saw the fault coming); flight-recorder dumps validated
    # file by file against the same schema + correlation rules.
    anomalies = dict(summary.get("anomalies") or {})
    leads: list = []
    cited = 0
    with open(log) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") != "autopilot_decision":
                continue
            ev = rec.get("evidence")
            an = ev.get("anomaly") if isinstance(ev, dict) else None
            if not an:
                continue
            cited += 1
            try:
                leads.append(float(rec["ts"]) - float(an["ts"]))
            except (KeyError, TypeError, ValueError):
                pass
    positive_leads = [l for l in leads if l > 0]
    detection_lead = round(max(positive_leads), 3) if positive_leads else 0.0
    undetected = sorted(
        seam for seam, kinds in DETECTED_FAULT_CLASSES.items()
        if by_seam.get(seam) and not any(anomalies.get(k) for k in kinds)
    )
    import glob as _glob

    dump_paths = sorted(_glob.glob(
        os.path.join(flightrec_dir, "flightrec-*.jsonl")))
    n_invalid = 0
    dump_reasons: dict = {}
    for p in dump_paths:
        dsum, ddiags = replay_events(p)
        if any(d.severity >= Severity.ERROR for d in ddiags):
            n_invalid += 1
        with open(p) as f:
            last = f.readlines()[-1]
        try:
            reason = str(json.loads(last).get("reason"))
        except ValueError:
            reason = "?"
        dump_reasons[reason] = dump_reasons.get(reason, 0) + 1
    timeouts = int(summary.get("kinds", {}).get("collective_timeout") or 0)
    dumps_missing = (
        max(0, timeouts - dump_reasons.get("collective_timeout", 0))
        + max(0, halts - dump_reasons.get("autopilot_halt", 0))
    ) if plane is not None else 0
    if plane is not None:
        from thunder_tpu.observability import opsplane

        opsplane.disable()

    useful_tokens = args.steps * tokens_per_step
    tps = useful_tokens / wall_s if wall_s else 0.0
    goodput = tps * (1.0 - overhead_pct / 100.0)
    ratio = goodput / ideal_tps if ideal_tps else 0.0
    # Wall time not spent on ideal-speed useful steps, charged per fault:
    # the machine-portable cost-of-a-fault number (the goodput RATIO swings
    # with the machine's ideal step time, which the CPU mesh cannot hold
    # steady run to run).
    n_faults = len(summary.get("faults_injected") or []) or 1
    recovery_per_fault_s = max(0.0, wall_s - args.steps * ideal_step_s) / n_faults
    if obsm.enabled():
        obsm.SOAK_GOODPUT.set(goodput)
    # The goodput record goes to the log AFTER replay on purpose: the
    # summary it carries (unrecovered/unactuated) is the replay's verdict.
    monitor.set_event_log(log)
    from thunder_tpu.observability.events import emit_event

    emit_event(
        "goodput", goodput_tokens_per_sec=round(goodput, 1),
        tokens_per_sec=round(tps, 1), useful_tokens=useful_tokens,
        wall_s=round(wall_s, 2), overhead_pct=round(overhead_pct, 2),
        steps=args.steps,
    )
    monitor.set_event_log(None)

    result = {
        "metric": "soak_goodput",
        "value": round(goodput, 1),
        "unit": "tokens/s",
        "seed": args.seed,
        "n_devices": args.devices,
        "mesh": mesh_shape(mesh),
        "model": args.model,
        "batch": args.batch,
        "seq": args.seq,
        "steps": args.steps,
        "soak_goodput_tokens_per_sec": round(goodput, 1),
        "soak_tokens_per_sec": round(tps, 1),
        "soak_ideal_tokens_per_sec": round(ideal_tps, 1),
        "soak_goodput_ratio": round(ratio, 4),
        "resilience_overhead_pct": round(overhead_pct, 2),
        "soak_wall_s": round(wall_s, 2),
        "soak_recovery_per_fault_s": round(recovery_per_fault_s, 2),
        "soak_faults_injected": len(summary.get("faults_injected") or []),
        "soak_fault_seams": by_seam,
        "soak_overlapping_pairs": n_overlap,
        "soak_decisions": summary.get("autopilot_decisions") or {},
        "soak_unrecovered": len(summary.get("unrecovered_faults") or []),
        "soak_unactuated": len(summary.get("unactuated_decisions") or []),
        "soak_replay_errors": len(errors),
        "soak_restarts": halts,
        "soak_steps_executed": steps_executed,
        "soak_final_loss": losses[-1],
        # Tiered checkpointing (ISSUE 14), all derived from the replayed
        # event log: the amortized hot-path stall of the snapshot cadence,
        # where restores landed on the tier ladder, and how many fell
        # through an invalid tier (the chaos seams' visible recovery).
        "checkpoint_stall_ms_per_step": round(
            float(summary.get("snapshot_stall_ms_total") or 0.0) / args.steps, 3),
        "snapshot_every": args.snapshot_every,
        "soak_snapshots": summary.get("snapshots") or 0,
        "soak_restore_tiers": summary.get("restore_tiers") or {},
        "soak_restore_fallthroughs": summary.get("restore_fallthroughs") or 0,
        # Live ops plane (ISSUE 15): streaming-detector anomalies, the
        # detection lead (max positive decision-ts − cited-anomaly-ts: >0
        # means a detector flagged the fault before the autopilot had to
        # act on it), detector coverage per fault class, and the flight
        # recorder's per-fault black-box dumps (validated against the event
        # schema + correlation rules, one by one).
        "soak_ops_port": ops_port,
        "soak_ops_healthz": ops_healthz,
        "soak_anomalies": anomalies,
        "soak_anomalies_total": sum(anomalies.values()),
        "soak_detection_lead": detection_lead,
        "soak_decisions_citing_anomaly": cited,
        "soak_undetected_detector_classes": len(undetected),
        "soak_detector_classes_missed": undetected,
        "soak_flightrec_dumps": len(dump_paths),
        "soak_flightrec_by_reason": dump_reasons,
        "soak_flightrec_invalid": n_invalid,
        "soak_flightrec_missing": dumps_missing,
        "events_log": log,
    }
    _log(f"goodput {goodput:.0f} tok/s ({ratio * 100:.1f}% of ideal "
         f"{ideal_tps:.0f}) over {wall_s:.1f}s wall; "
         f"{result['soak_faults_injected']} faults, "
         f"{sum(result['soak_decisions'].values())} decisions, "
         f"{halts} restart(s), unrecovered={result['soak_unrecovered']}, "
         f"unactuated={result['soak_unactuated']}")
    _log(f"tiers: {result['soak_snapshots']} snapshots "
         f"(stall {result['checkpoint_stall_ms_per_step']:.2f} ms/step), "
         f"restores "
         + (", ".join(f"{t}×{n}" for t, n in
                      sorted(result['soak_restore_tiers'].items())) or "none")
         + f", {result['soak_restore_fallthroughs']} fall-through(s)")
    if plane is not None:
        _log(f"ops: anomalies "
             + (", ".join(f"{k}×{n}" for k, n in sorted(anomalies.items()))
                or "none")
             + f"; detection lead {detection_lead:.2f}s over {cited} cited "
             f"decision(s); dumps "
             + (", ".join(f"{r}×{n}" for r, n in sorted(dump_reasons.items()))
                or "none")
             + f" ({n_invalid} invalid, {dumps_missing} missing); "
             f"healthz={ops_healthz}")
    return result


# =============================================================================
# Driver
# =============================================================================


def soak_ok(result: dict) -> bool:
    """The soak's pass condition (the acceptance gate): nothing unrecovered,
    nothing unactuated, no replay errors, a finite final loss — and, with
    the ops plane on (ISSUE 15), every detector-covered fault class raised
    an anomaly, detection lead is positive, and every timeout/halt produced
    a schema-valid flight-recorder dump."""
    loss = result.get("soak_final_loss")
    ok = (
        result.get("soak_unrecovered") == 0
        and result.get("soak_unactuated") == 0
        and result.get("soak_replay_errors") == 0
        and loss is not None and loss == loss  # not NaN
    )
    if ok and result.get("soak_ops_port") is not None:
        ok = (
            result.get("soak_undetected_detector_classes") == 0
            and result.get("soak_detection_lead", 0) > 0
            and result.get("soak_flightrec_invalid") == 0
            and result.get("soak_flightrec_missing") == 0
        )
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="soak_fleet.py",
        description="Goodput-gated chaos soak on the virtual mesh (SOAK series)",
    )
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--model", default="gpt-tiny")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--faults", type=int, default=14)
    p.add_argument("--overlap-pairs", type=int, default=2)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--save-every", type=int, default=10)
    p.add_argument("--snapshot-every", type=int, default=3,
                   help="RAM-snapshot cadence in steps (ISSUE 14: a fault "
                        "loses at most this many steps instead of "
                        "save-every)")
    p.add_argument("--snapshot-ring", type=int, default=4,
                   help="snapshots kept per RAM tier (local ring and buddy "
                        "replica ring)")
    p.add_argument("--watchdog-timeout-s", type=float, default=2.0)
    p.add_argument("--hysteresis-window-s", type=float, default=15.0,
                   help="cap on every policy's hysteresis window (the "
                        "production defaults span the whole CPU-mesh run)")
    p.add_argument("--regrow-after", type=int, default=15,
                   help="healthy steps on a shrunk mesh before resharding "
                        "back up to the full mesh (0 disables)")
    p.add_argument("--max-restarts", type=int, default=8)
    p.add_argument("--ops-plane", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="live ops plane (ISSUE 15): /metrics + /healthz on "
                        "an ephemeral port, flight-recorder dumps per "
                        "fault, streaming detectors feeding the autopilot")
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized run: 40 steps, 11 faults (lint_traces --soak)")
    p.add_argument("--workdir", default=None)
    p.add_argument("--out", default=None, help="also write the JSON here")
    p.add_argument("--_subprocess", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.smoke:
        # 11 faults = every required seam + one filler slot, which the
        # schedule turns into the second oom the recompile-storm detector
        # needs (ISSUE 15).
        args.steps, args.faults, args.save_every = 40, 11, 5
        args.snapshot_every = 2
        args.regrow_after = 10
    if not args.regrow_after:
        args.regrow_after = None

    import jax

    if len(jax.devices()) < args.devices and not args._subprocess:
        # Backend already initialized with fewer devices: re-exec on the
        # virtual CPU mesh.
        import subprocess

        env = {
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", "/root"),
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={args.devices}",
            "THUNDER_TPU_RETRY_BACKOFF_S": "0",
        }
        cmd = [sys.executable, os.path.abspath(__file__), "--_subprocess"] + [
            a for a in (argv if argv is not None else sys.argv[1:])
        ]
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=3000)
        sys.stderr.write(r.stderr[-8000:] if len(r.stderr) > 8000 else r.stderr)
        if r.returncode != 0:
            print(f"soak_fleet subprocess failed:\n{r.stdout[-2000:]}",
                  file=sys.stderr)
            return r.returncode
        line = r.stdout.strip().splitlines()[-1]
        json.loads(line)  # malformed output must fail loudly
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0

    os.environ.setdefault("THUNDER_TPU_RETRY_BACKOFF_S", "0")
    result = run_soak(args)
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if soak_ok(result) else 1


if __name__ == "__main__":
    raise SystemExit(main())
