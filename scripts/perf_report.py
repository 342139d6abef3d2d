#!/usr/bin/env python
"""Performance attribution reports and the bench regression gate.

Two modes:

**History / regression gate** — build the perf trajectory across committed
bench rounds and flag per-metric deltas beyond thresholds::

    python scripts/perf_report.py --history ROOFLINE_r0*.json
    python scripts/perf_report.py --history ROOFLINE_r0*.json --gate   # CI: exit 1
                                                                       # on un-acked regressions
    python scripts/perf_report.py --history SOAK_r*.json --gate

Each series (``SOAK_r*.json`` from ``scripts/soak_fleet.py`` — headline
``value`` is goodput tokens/sec, gated UP-good — or ``SOAK_POD_r*.json``
and ``CRITPATH_r*.json`` from ``scripts/soak_pod.py``) is gated
separately — one invocation per glob — with the same direction-aware
deltas, noise floors, and ack semantics. The driver's ``PERF_LEDGER.jsonl``
is the record of speed on the chip; this script does not read it.

Metric direction is inferred from the name (times/counts: lower is better;
MFU/throughput/ratios-vs-baseline: higher is better); sub-noise-floor
deltas on second-scale trace/compile timings are ignored. Known, accepted
regressions live in ``BENCH_ACK.json`` at the repo root (``--ack`` to point
elsewhere) so the gate stays green on history while failing loudly on new
regressions — the committed file acknowledges the r4→r5
``train_xla_compile_s`` 20.7s→43.3s jump this tool was built to catch.
``scripts/lint_traces.py`` runs the gate over the committed history.

**Attribution** — the measured/roofline report over a profile directory
(``thunder_tpu.profile()`` run under ``THUNDER_TPU_ANNOTATE_TRACES=1``)::

    python scripts/perf_report.py --trace-dir /tmp/prof --steps 3
    python scripts/perf_report.py --trace-dir /tmp/prof --model gpt-tiny \
        --batch 2 --seq 16        # join the static cost model → roofline/MFU

See docs/performance.md for the full profile → perf_report → roofline
workflow.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Any, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# =============================================================================
# History / regression gate
# =============================================================================

# Direction inference: higher-is-better substrings win first (an MFU ratio
# name like train_synced_mfu_vs_ref_mfu must not fall through to the "_s"
# time suffix), then lower-is-better time/count shapes. Unmatched metrics are
# reported in the trajectory but never gated.
_HIGHER_SUBSTRINGS = ("mfu", "vs_baseline", "tokens_per_sec", "dots_passed",
                      "goodput", "achieved_frac", "coverage_pct")
_LOWER_SUFFIXES = ("_s", "_us", "_ms", "_pct", "_pct_static", "_seconds", "_ms_per_step")
_LOWER_EXACT = {"value", "recompile_count"}

# Absolute-delta floors (same units as the metric): second-scale pipeline
# timings jitter ±0.3s run to run; a 0.2s→0.3s "+50%" is noise, a
# 20.7s→43.3s "+109%" is not.
_NOISE_FLOORS = (
    ("trace_claim_s", 1.0),
    ("xla_compile_s", 2.0),
    ("lookup_us", 5.0),
    ("dispatch_us", 20.0),
    ("overhead_pct", 0.5),
    ("exposed_pct", 5.0),
)

# Series-aware floors for the MULTICHIP_BENCH rounds (headline metric name
# starts with "multichip"): tiny-model steps on an emulated 8-device CPU
# mesh jitter tens of ms — and MFU/tokens track the same measurement — so
# the floors are sized to that jitter WITHOUT weakening the single-host
# BENCH gate, whose metrics share these names. Checked before the generic
# table; "value" is the multichip headline (iter seconds).
_MULTICHIP_NOISE_FLOORS = (
    ("value", 0.02),
    ("iter_s", 0.02),
    ("synced_s", 0.02),
    ("strict_sync_s", 0.02),
    ("mfu", 5e-4),
    # tokens/sec is 256/iter_s: at the r03+ ~11ms step, the same ±1.5ms
    # scheduler jitter the iter floors absorb swings tokens by ±3000 —
    # the old 2000 floor (sized at r02's ~8k tok/s) gated pure noise.
    ("tokens_per_sec", 4000.0),
    # resilience_overhead_pct is a RATIO of two jittery tiny-step timings:
    # single-digit swings are measurement noise on the CPU mesh.
    ("overhead_pct", 5.0),
    # The snapshot stall is a host gather of a tiny model on a contended
    # CPU — a few ms of scheduler jitter is noise (ISSUE 14).
    ("stall_ms_per_step", 3.0),
    # Static exposed-collective % from the HLO auditor (ISSUE 16) is
    # deterministic given the HLO, but XLA fusion decisions wobble a little
    # across versions/flags; a couple of points is not a scheduling
    # regression.
    ("exposed_pct_static", 2.0),
)

# SOAK_r* rounds (headline metric "soak_goodput"): goodput on the emulated
# CPU mesh inherits the tiny-step jitter TWICE (ideal step AND soak wall
# clock share the scheduler), and the recovery path lengths vary with
# host load — the floors are sized to that, per the committed r01 noise
# measurement, without touching the bench series.
_SOAK_NOISE_FLOORS = (
    ("value", 800.0),              # goodput tokens/s
    ("tokens_per_sec", 800.0),
    ("goodput_ratio", 0.15),
    ("overhead_pct", 5.0),
    # Recovery seconds charged per fault: sized to r01's 3.61 s/fault scale
    # when committed; re-sized to the tiered-checkpoint era (ISSUE 14,
    # r02 ≈ 1.x s/fault) so the comparator keeps teeth.
    ("per_fault_s", 1.5),
    ("stall_ms_per_step", 3.0),    # snapshot stall under CPU-mesh jitter
    ("wall_s", 60.0),
    ("_s", 60.0),                  # any other second-scale soak timing
)

# SOAK_POD_r* rounds (headline "soak_pod_goodput", from scripts/soak_pod.py
# — ISSUE 18): same CPU-mesh jitter story as the fleet soak, plus the
# degraded-window split whose tokens/s rides on a handful of accum-rescaled
# steps. Checked BEFORE the generic soak table ("soak_pod" startswith
# "soak"); anything not listed here falls through to the soak floors.
_SOAK_POD_NOISE_FLOORS = (
    ("degraded_tokens_per_sec", 600.0),  # ~15-step window, double jitter
    ("goodput_ratio", 0.05),
    ("shrink_latency_s", 0.05),    # sub-second controller latencies: gate
    ("regrow_to_full_s", 2.0),     # on scale changes, not scheduler noise
)


# ROOFLINE_r* rounds (headline metric "roofline_*" — ISSUE 19): the per-op
# ``op_<line>_<sym>_us`` / ``_achieved_frac`` series. Per-op microsecond timings are the noisiest
# numbers the gate sees (single-op, single-probe, tens of µs on the CPU
# round) — the floors absorb scheduler jitter while still catching an op
# that genuinely doubled; achieved fraction is a ratio of the same
# measurement, floored absolutely.
_ROOFLINE_NOISE_FLOORS = (
    ("achieved_frac", 0.05),
    ("_us", 40.0),
    ("coverage_pct", 10.0),
    ("value", 0.2),                # total device-busy ms/step
)


# CRITPATH_r* rounds (headline "critpath_exposed_pct", from soak_pod.py's
# --critpath-out — ISSUE 20): the measured exposed-collective share is
# static-wire-priced against the MEASURED ideal step, so it inherits the
# CPU-mesh step jitter; skew recovery error is µs-scale in practice but
# rides two time.time() reads per barrier. The structural invariants
# (class coverage, host attribution, detector/citation joins) are gated
# absolutely in _critpath_failures, not by deltas.
_CRITPATH_NOISE_FLOORS = (
    ("value", 5.0),                # measured exposed %
    ("exposed_pct", 5.0),
    ("_pct", 5.0),
    ("recovery_err_ms", 10.0),
    ("_ms", 10.0),
    ("_s", 60.0),
)


def metric_direction(name: str, series: str = "") -> Optional[int]:
    """+1 = higher is better, -1 = lower is better, None = not gated.
    ``series`` (the round's headline ``metric`` name) resolves the fields
    whose direction follows the series: the SOAK rounds' headline ``value``
    is goodput tokens/sec (up-good), where every other series' ``value`` is
    a time (down-good)."""
    low = name.lower()
    if series.lower().startswith("soak") and low == "value":
        return 1
    if any(s in low for s in _HIGHER_SUBSTRINGS):
        return 1
    if low in _LOWER_EXACT or low.endswith(_LOWER_SUFFIXES):
        return -1
    return None


def mfu_comparable(name: str, *rounds: dict) -> bool:
    """MFU against the ``cpu`` fallback spec is meaningless (the "peak
    FLOP/s" is a made-up host number — MULTICHIP_BENCH r02's 0.001) and
    would trip direction-aware gating the first time it wiggles: an MFU
    metric is only gated when every round that reports it ran on a real
    device spec."""
    if "mfu" not in name.lower():
        return True
    return all(m.get("_device_spec") != "cpu" for m in rounds)


def noise_floor(name: str, series: str = "") -> float:
    """Minimum absolute delta for ``name`` to gate; ``series`` is the
    round's headline ``metric`` name, selecting the multichip/soak floor
    tables for those rounds (the series share metric names)."""
    low = name.lower()
    if series.lower().startswith("multichip"):
        for suffix, floor in _MULTICHIP_NOISE_FLOORS:
            if low.endswith(suffix):
                return floor
    if series.lower().startswith("soak_pod"):
        for suffix, floor in _SOAK_POD_NOISE_FLOORS:
            if low.endswith(suffix):
                return floor
    if series.lower().startswith("soak"):
        for suffix, floor in _SOAK_NOISE_FLOORS:
            if low.endswith(suffix):
                return floor
    if series.lower().startswith("roofline"):
        for suffix, floor in _ROOFLINE_NOISE_FLOORS:
            if low.endswith(suffix):
                return floor
    if series.lower().startswith("critpath"):
        for suffix, floor in _CRITPATH_NOISE_FLOORS:
            if low.endswith(suffix):
                return floor
    for suffix, floor in _NOISE_FLOORS:
        if low.endswith(suffix):
            return floor
    return 0.0


# Headline fields whose meaning follows the round's "metric" name (r01's
# headline was the forward bench, r02+ the training bench): only comparable
# when consecutive rounds benched the same thing.
_HEADLINE_KEYS = {"value", "vs_baseline", "tokens_per_sec", "mfu", "baseline_mfu_a100"}


def load_round(path: str) -> tuple[str, dict[str, float]]:
    """(round label, numeric metrics) from one committed bench JSON — the
    driver's ``{"n", "cmd", "rc", "tail", "parsed": {...}}`` wrapper or a
    bare JSON line. The round's headline ``metric`` name is kept
    under ``_metric_name`` for the comparability check."""
    with open(path) as f:
        doc = json.load(f)
    metrics = doc.get("parsed", doc) if isinstance(doc, dict) else {}
    if not isinstance(metrics, dict):
        metrics = {}
    m = re.search(r"r(\d+)", os.path.basename(path))
    label = f"r{int(m.group(1)):02d}" if m else os.path.basename(path)
    out = {
        k: float(v)
        for k, v in metrics.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }
    if isinstance(metrics.get("metric"), str):
        out["_metric_name"] = metrics["metric"]  # type: ignore[assignment]
    if isinstance(metrics.get("device_spec"), str):
        out["_device_spec"] = metrics["device_spec"]  # type: ignore[assignment]
    return label, out


@dataclass
class Regression:
    metric: str
    frm: str
    to: str
    prev: float
    cur: float
    pct: float  # signed relative change
    acked: bool = False
    reason: str = ""

    @property
    def key(self) -> str:
        return f"{self.frm}->{self.to}:{self.metric}"

    def format(self) -> str:
        tag = "acked" if self.acked else "REGRESSION"
        note = f" ({self.reason})" if self.reason else ""
        return (
            f"{tag}: {self.metric} {self.prev:g} -> {self.cur:g} "
            f"({self.pct * 100:+.1f}%) over {self.frm}->{self.to}{note}"
        )


def load_ack(path: Optional[str]) -> dict[str, str]:
    """``{transition:metric -> reason}`` from a BENCH_ACK.json file."""
    if not path or not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    out: dict[str, str] = {}
    for entry in doc.get("acknowledged", []):
        out[f"{entry['transition']}:{entry['metric']}"] = entry.get("reason", "")
    return out


def analyze_history(
    rounds: list[tuple[str, dict[str, float]]],
    *,
    threshold: float = 0.10,
    ack: Optional[dict[str, str]] = None,
) -> list[Regression]:
    """Regressions across every consecutive round pair: a gated metric whose
    relative change exceeds ``threshold`` in the bad direction AND whose
    absolute delta clears the metric's noise floor."""
    ack = ack or {}
    out: list[Regression] = []
    for (l0, m0), (l1, m1) in zip(rounds, rounds[1:]):
        same_headline = m0.get("_metric_name") == m1.get("_metric_name")
        series = str(m0.get("_metric_name") or m1.get("_metric_name") or "")
        for name in sorted(set(m0) & set(m1)):
            direction = metric_direction(name, series)
            if direction is None:
                continue
            if name in _HEADLINE_KEYS and not same_headline:
                continue  # the rounds benched different headline workloads
            if not mfu_comparable(name, m0, m1):
                continue  # cpu-fallback MFU is not a real utilization number
            prev, cur = m0[name], m1[name]
            if prev == 0:
                continue
            pct = (cur - prev) / abs(prev)
            bad = pct > threshold if direction < 0 else pct < -threshold
            if not bad or abs(cur - prev) <= noise_floor(name, series):
                continue
            r = Regression(metric=name, frm=l0, to=l1, prev=prev, cur=cur, pct=pct)
            if r.key in ack:
                r.acked, r.reason = True, ack[r.key]
            out.append(r)
    return out


def compare_rounds(
    prev: dict[str, float], cur: dict[str, float], *, threshold: float = 0.10,
) -> tuple[dict[str, float], list[str]]:
    """One-transition comparison against the newest committed round: ``(deltas, regressions)`` where ``deltas`` maps each
    gated metric to its signed relative change and ``regressions`` holds
    human-readable strings for changes beyond ``threshold`` in the bad
    direction (noise floors applied)."""
    same_headline = prev.get("_metric_name") == cur.get("_metric_name")
    series = str(prev.get("_metric_name") or cur.get("_metric_name") or "")
    deltas: dict[str, float] = {}
    regs: list[str] = []
    for name in sorted(set(prev) & set(cur)):
        direction = metric_direction(name, series)
        if direction is None:
            continue
        if name in _HEADLINE_KEYS and not same_headline:
            continue
        if not mfu_comparable(name, prev, cur):
            continue
        p, c = prev[name], cur[name]
        if not isinstance(p, (int, float)) or not isinstance(c, (int, float)) or p == 0:
            continue
        pct = (c - p) / abs(p)
        deltas[name] = round(pct, 4)
        bad = pct > threshold if direction < 0 else pct < -threshold
        if bad and abs(c - p) > noise_floor(name, series):
            regs.append(f"{name} {p:g} -> {c:g} ({pct * 100:+.1f}%)")
    return deltas, regs


def format_history(rounds: list[tuple[str, dict[str, float]]],
                   regressions: list[Regression]) -> str:
    labels = [l for l, _ in rounds]
    series = str(next((m.get("_metric_name") for _, m in rounds
                       if m.get("_metric_name")), ""))
    names = sorted({n for _, m in rounds for n in m
                    if metric_direction(n, series) is not None})
    w = max((len(n) for n in names), default=10)
    lines = ["bench history: " + " -> ".join(labels),
             f"  {'metric':<{w}} " + " ".join(f"{l:>10}" for l in labels)]
    for n in names:
        cells = []
        for _, m in rounds:
            v = m.get(n)
            cells.append(f"{v:>10.4g}" if v is not None else f"{'-':>10}")
        arrow = {1: "^", -1: "v"}[metric_direction(n, series)]
        note = "" if mfu_comparable(n, *[m for _, m in rounds]) else \
            " (cpu spec: not comparable, not gated)"
        lines.append(f"  {n:<{w}} " + " ".join(cells) + f"  [{arrow}]{note}")
    if regressions:
        lines.append("")
        for r in regressions:
            lines.append("  " + r.format())
    else:
        lines.append("  no regressions beyond threshold")
    return "\n".join(lines)


def run_history_gate(
    paths: list[str],
    *,
    threshold: float = 0.10,
    ack_path: Optional[str] = None,
    gate: bool = False,
    out=sys.stdout,
) -> int:
    """The CI entry (also called by scripts/lint_traces.py): print the
    trajectory + flags; exit 1 only under ``--gate`` with un-acked
    regressions."""
    rounds = [load_round(p) for p in sorted(paths)]
    rounds = [(l, m) for l, m in rounds if m]
    if not rounds:
        print("perf_report --history: no rounds with metrics", file=out)
        return 0
    if len(rounds) < 2:
        # No trajectory to diff — but the newest round's ABSOLUTE
        # acceptance invariants (ops plane, pod federation) still gate:
        # the SOAK_POD series ships with a single committed round and its
        # pass/fail proofs must hold from r01 onward.
        print("perf_report --history: need at least two rounds with metrics "
              "to diff; checking absolute invariants only", file=out)
        failures = (_ops_plane_failures(rounds[-1]) + _pod_failures(rounds[-1])
                    + _roofline_failures(rounds[-1])
                    + _critpath_failures(rounds[-1]))
        if failures:
            print("\nperf_report: acceptance failed on the newest round: "
                  + ", ".join(failures), file=out)
        return 1 if (gate and failures) else 0
    if ack_path is None:
        repo_ack = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_ACK.json")
        ack_path = repo_ack
    regs = analyze_history(rounds, threshold=threshold, ack=load_ack(ack_path))
    print(format_history(rounds, regs), file=out)
    fresh = [r for r in regs if not r.acked]
    if fresh:
        print(
            f"\nperf_report: {len(fresh)} un-acknowledged regression(s) "
            f"(threshold {threshold * 100:.0f}%); acknowledge deliberate ones in "
            f"{os.path.basename(ack_path or 'BENCH_ACK.json')}",
            file=out,
        )
    ops_failures = (_ops_plane_failures(rounds[-1]) + _pod_failures(rounds[-1])
                    + _roofline_failures(rounds[-1])
                    + _critpath_failures(rounds[-1]))
    if ops_failures:
        print(
            "\nperf_report: acceptance failed on the newest "
            "round: " + ", ".join(ops_failures), file=out,
        )
    return 1 if (gate and (fresh or ops_failures)) else 0


def _ops_plane_failures(newest: tuple) -> list[str]:
    """Absolute ops-plane checks on the newest SOAK round (ISSUE 15) —
    unlike the direction-aware deltas, these are pass/fail invariants:
    every soak fault class with a streaming detector must have raised at
    least one anomaly, detection lead must be positive, and every
    timeout/halt must have produced a schema-valid flight-recorder dump.
    Rounds predating the ops plane (no soak_ops keys) are exempt."""
    label, m = newest
    if not str(m.get("_metric_name", "")).startswith("soak"):
        return []
    if "soak_undetected_detector_classes" not in m:
        return []  # pre-ops-plane round
    out = []
    for key in ("soak_undetected_detector_classes", "soak_flightrec_invalid",
                "soak_flightrec_missing"):
        v = m.get(key)
        if v:
            out.append(f"{label}: {key}={v:g}")
    lead = m.get("soak_detection_lead")
    if lead is not None and lead <= 0:
        out.append(f"{label}: soak_detection_lead={lead:g} (need > 0: an "
                   f"anomaly must precede the decision citing it)")
    return out


def _pod_failures(newest: tuple) -> list[str]:
    """Absolute federation checks on the newest SOAK_POD round (ISSUE 18)
    — the elastic shrink/regrow acceptance invariants, pass/fail
    regardless of how many rounds exist:

    - zero unrecovered faults, unactuated decisions, replay errors, and
      process restarts;
    - the fleet actually shrank (min width < full width, degraded steps
      ran) AND regrew to full DP width (final == full), with shrink and
      regrow decision counts equal — a flapping slice may not buy extra
      shrinks;
    - every slice-loss recovery restored from the cross-slice buddy's
      peer-RAM tier (nonpeer count 0) and disk served nothing after the
      step-0 anchor;
    - when the schedule carried the flap seam, its cooldown->lost
      re-failure edge is in the ledger (refailures >= 1); when it carried
      the slow-slice window, the DCN-tier spread detector raised at least
      one slice_spread anomaly."""
    label, m = newest
    if not str(m.get("_metric_name", "")).startswith("soak_pod"):
        return []
    out = []
    for key in ("soak_pod_unrecovered", "soak_pod_unactuated",
                "soak_pod_replay_errors", "soak_pod_restarts",
                "soak_pod_slice_loss_nonpeer_restores",
                "soak_pod_disk_restores_after_anchor"):
        v = m.get(key)
        if v:
            out.append(f"{label}: {key}={v:g}")
    full, final = m.get("soak_pod_full_width"), m.get("soak_pod_final_width")
    if full is not None and final != full:
        out.append(f"{label}: final_width={final:g} != full_width={full:g} "
                   f"(fleet did not regrow)")
    if full is not None and not (m.get("soak_pod_min_width", full) < full
                                 and m.get("soak_pod_degraded_steps", 0) > 0):
        out.append(f"{label}: no degraded window (the soak never actually "
                   f"lost a slice)")
    shrinks, regrows = m.get("soak_pod_shrinks"), m.get("soak_pod_regrows")
    if shrinks is not None and not (shrinks == regrows and shrinks > 0):
        out.append(f"{label}: shrinks={shrinks:g} regrows={regrows:g} "
                   f"(need equal and > 0)")
    if not m.get("soak_pod_slice_loss_restores"):
        out.append(f"{label}: soak_pod_slice_loss_restores=0 (no peer-tier "
                   f"recovery was proven)")
    if m.get("soak_pod_flap_injected") and \
            not m.get("soak_pod_flap_refailures"):
        out.append(f"{label}: flap injected but no cooldown->lost re-failure "
                   f"edge in the ledger")
    if m.get("soak_pod_slow_injected") and \
            not m.get("soak_pod_slice_spread_anomalies"):
        out.append(f"{label}: slow slice injected but no slice_spread "
                   f"anomaly was raised")
    return out


def _critpath_failures(newest: tuple) -> list[str]:
    """Absolute checks on the newest CRITPATH round (ISSUE 20) — the fleet
    critical-path ledger's acceptance invariants, pass/fail regardless of
    how many rounds exist:

    - the ledger folded a real run (>= 5 steps) and the per-step breakdown
      carried >= 5 distinct nonzero time classes, summing to ~1;
    - clock alignment is falsifiable and passed: the estimator recovered
      the run's injected per-slice offsets within 25 ms, with confidence
      >= 0.5 and no spurious outlier hosts (the soak injects clean skews);
    - straggler-wait is attributed to the seeded slow slice;
    - the detectors saw the shift (>= 1 bottleneck_shift anomaly) AND the
      autopilot cited it in >= 1 decision's evidence;
    - the static-vs-measured exposed-collective cross-check agrees within
      the 10-point noise band (on the emulated fleet the wire classes are
      static-priced, so a larger gap means the plumbing broke)."""
    label, m = newest
    if not str(m.get("_metric_name", "")).startswith("critpath"):
        return []
    out = []
    steps = m.get("critpath_steps", 0)
    if steps < 5:
        out.append(f"{label}: critpath_steps={steps:g} (need >= 5)")
    ncls = m.get("critpath_nonzero_classes", 0)
    if ncls < 5:
        out.append(f"{label}: critpath_nonzero_classes={ncls:g} "
                   f"(need >= 5 distinct time classes)")
    fsum = m.get("critpath_frac_sum")
    if fsum is not None and abs(fsum - 1.0) > 0.02:
        out.append(f"{label}: critpath_frac_sum={fsum:g} (breakdown must "
                   f"sum to ~1)")
    err = m.get("critpath_skew_recovery_err_ms")
    if err is None or not (err == err) or err > 25.0:
        out.append(f"{label}: critpath_skew_recovery_err_ms={err} "
                   f"(injected offsets not recovered within 25 ms)")
    conf = m.get("critpath_skew_min_confidence", 0.0)
    if conf < 0.5:
        out.append(f"{label}: critpath_skew_min_confidence={conf:g} "
                   f"(need >= 0.5)")
    if m.get("critpath_skew_outlier_hosts"):
        out.append(f"{label}: critpath_skew_outlier_hosts="
                   f"{m.get('critpath_skew_outlier_hosts'):g} (clean "
                   f"injected skews must not flag outliers)")
    if not m.get("critpath_straggler_host_match"):
        out.append(f"{label}: straggler-wait not attributed to the seeded "
                   f"slow slice")
    if not m.get("critpath_bottleneck_shift_anomalies"):
        out.append(f"{label}: no bottleneck_shift anomaly was raised")
    if not m.get("critpath_cited_decisions"):
        out.append(f"{label}: no autopilot decision cited bottleneck_shift")
    delta = m.get("critpath_delta_static_pct")
    if delta is None or abs(delta) > 10.0:
        out.append(f"{label}: critpath_delta_static_pct={delta} "
                   f"(static-vs-measured exposed pct disagree)")
    return out


def _roofline_failures(newest: tuple) -> list[str]:
    """Absolute checks on the newest ROOFLINE round (ISSUE 19) — the
    committed per-op series must stay a usable baseline regardless of how
    many rounds exist: at least 10 per-op rows, every row in the
    observability/roofline.py ``ROW_FIELDS`` schema (bench stamps
    ``roofline_schema_ok``), and at least 10 flattened
    ``op_*_achieved_frac`` keys so the per-op direction gate has ops to
    hold onto."""
    label, m = newest
    if not str(m.get("_metric_name", "")).startswith("roofline"):
        return []
    out = []
    rows = m.get("roofline_rows", 0)
    if rows < 10:
        out.append(f"{label}: roofline_rows={rows:g} (need >= 10 per-op rows)")
    if not m.get("roofline_schema_ok"):
        out.append(f"{label}: roofline_schema_ok="
                   f"{m.get('roofline_schema_ok', 0):g} (rows violate the "
                   f"ledger ROW_FIELDS schema)")
    n_flat = sum(1 for k in m
                 if k.startswith("op_") and k.endswith("_achieved_frac"))
    if n_flat < 10:
        out.append(f"{label}: only {n_flat} flattened op_*_achieved_frac "
                   f"key(s) (need >= 10 for the per-op gate)")
    return out


# =============================================================================
# Attribution mode
# =============================================================================


def run_attribution(
    trace_dir: str,
    *,
    steps: int = 1,
    top_k: int = 10,
    device: Optional[str] = None,
    hlo_path: Optional[str] = None,
    model: Optional[str] = None,
    batch: int = 2,
    seq: int = 16,
    out=sys.stdout,
) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from thunder_tpu.analysis.cost import cost_report
    from thunder_tpu.observability.attribution import attribute, join_cost_attribution

    hlo_text = None
    if hlo_path:
        with open(hlo_path) as f:
            hlo_text = f.read()
    try:
        attr = attribute(trace_dir, hlo_text=hlo_text)
    except FileNotFoundError as e:
        print(f"perf_report: {e}", file=sys.stderr)
        return 2

    cost = None
    if model:
        from thunder_tpu.core import dtypes
        from thunder_tpu.models import gpt as m

        cfg = m.name_to_config(model)
        params = m.init_params(cfg, dtype=dtypes.float32, seed=0)
        idx = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        cost = cost_report(lambda p, i: m.forward(p, i, cfg), params, idx,
                           executors=["jax"], device=device)
    join = join_cost_attribution(attr, cost, steps=steps)
    print(join.format(top_k), file=out)
    if attr.coverage < 0.9 and attr.device_busy_us:
        print(
            f"\nperf_report: only {attr.coverage * 100:.1f}% of device time "
            "attributed — profile with THUNDER_TPU_ANNOTATE_TRACES=1, or pass "
            "--hlo <compiled.txt> to join raw HLO op names",
            file=out,
        )
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="perf_report.py",
        description="Bench-history regression gate and profile attribution reports",
    )
    p.add_argument("--history", nargs="+", metavar="BENCH.json",
                   help="committed bench rounds to diff (BENCH_r*.json)")
    p.add_argument("--threshold", type=float, default=0.10,
                   help="relative regression threshold (default 0.10)")
    p.add_argument("--ack", default=None,
                   help="acknowledgment file (default: repo-root BENCH_ACK.json)")
    p.add_argument("--gate", action="store_true",
                   help="exit 1 on un-acknowledged regressions (CI mode)")
    p.add_argument("--trace-dir", default=None,
                   help="profile dir (or one trace-events JSON) to attribute")
    p.add_argument("--steps", type=int, default=1,
                   help="steps the profile bracketed (scales totals per step)")
    p.add_argument("--top", type=int, default=10, help="rows in the top-k table")
    p.add_argument("--device", default=None,
                   help="device spec name for the cost model (v5e/v5p/v4/a100/cpu)")
    p.add_argument("--hlo", default=None,
                   help="compiled-HLO text file to map raw hlo_op names to scopes")
    p.add_argument("--model", default=None,
                   help="GPT config name to build the cost model from (e.g. gpt-tiny)")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=16)
    args = p.parse_args(argv)

    if args.history:
        return run_history_gate(
            args.history, threshold=args.threshold, ack_path=args.ack, gate=args.gate
        )
    if args.trace_dir:
        return run_attribution(
            args.trace_dir, steps=args.steps, top_k=args.top, device=args.device,
            hlo_path=args.hlo, model=args.model, batch=args.batch, seq=args.seq,
        )
    p.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
