"""Slice-granular failure domains (ISSUE 18): federated mesh, hierarchical
collective lowering + DCN cost class, chaos slice seams, the membership
ledger + shrink/regrow controller, and the federated driver end-to-end on
the 8-device virtual CPU mesh (two emulated slices).

The acceptance invariants proven here:

- whole-slice loss restores from the cross-slice buddy's PEER-RAM tier —
  the disk tier is never touched in a slice-loss recovery;
- a flapping slice degrades the fleet exactly ONCE: one ``shrink_dp``, one
  deferred ``regrow_dp``, proven by replaying the autopilot event ledger;
- the rejoin backoff + hysteresis hold a recovered slice out until the
  window clears (fake-clock controller tests);
- chaos per-process seeds derive from ``(seed, slice, host)`` so two
  hosts — or two slices — never replay each other's schedule.
"""

import json
import os
import tempfile

import numpy as np
import pytest

import thunder_tpu.monitor as monitor
from thunder_tpu.resilience import chaos
from thunder_tpu.resilience.autopilot import Autopilot, AutopilotHalt, Signal
from thunder_tpu.resilience.federation import (
    FederationLedger,
    FleetController,
    current_ledger,
    install_ledger,
    run_federated_training,
)
from thunder_tpu.resilience.preemption import CheckpointManager
from thunder_tpu.resilience.snapshot import SnapshotStore


def _events(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


# =============================================================================
# Federated mesh + hierarchical lowering + DCN cost class
# =============================================================================


class TestFederatedMesh:
    def test_shape_and_axes(self):
        from thunder_tpu.parallel import make_federated_mesh
        from thunder_tpu.parallel.mesh import DCN_AXIS, is_federated

        mesh, topo = make_federated_mesh(2, dp=2, tp=2)
        assert mesh.axis_names[0] == DCN_AXIS
        assert mesh.devices.shape[0] == 2
        assert topo.n_slices == 2 and topo.devices_per_slice == 4
        assert is_federated(mesh)

    def test_slice_blocks_are_contiguous(self):
        from thunder_tpu.parallel import make_federated_mesh

        _, topo = make_federated_mesh(2, dp=4)
        assert list(topo.device_indices(0)) == list(range(4))
        assert list(topo.device_indices(1)) == list(range(4, 8))
        assert topo.slice_of_device(3) == 0
        assert topo.slice_of_device(4) == 1

    def test_plain_mesh_not_federated(self):
        from thunder_tpu.parallel import make_mesh
        from thunder_tpu.parallel.mesh import is_federated, slice_axis_size

        mesh = make_mesh(dp=4)
        assert not is_federated(mesh)
        assert slice_axis_size(mesh) == 1

    def test_slice_axis_size(self):
        from thunder_tpu.parallel import make_federated_mesh
        from thunder_tpu.parallel.mesh import slice_axis_size

        mesh, _ = make_federated_mesh(2, dp=2)
        assert slice_axis_size(mesh) == 2

    def test_too_many_devices_raises(self):
        from thunder_tpu.parallel import make_federated_mesh

        with pytest.raises(ValueError):
            make_federated_mesh(4, dp=4)  # 16 > the 8 virtual devices


class TestHierAllReduceLowering:
    def _extrace(self, fn, *args):
        from thunder_tpu.api import trace_program
        from thunder_tpu.executors.passes import transform_for_execution
        from thunder_tpu.extend import resolve_executors
        from thunder_tpu.transforms.common import cse, dce

        _, comp = trace_program(fn, args, {})
        return transform_for_execution(
            cse(dce(comp)), resolve_executors(["jax"]))

    def test_hier_wire_cost_golden(self):
        """8x8 f32 (256 B), in-slice group 4, 2 slices: reduce-scatter +
        all-gather move 2*(3/4)*256 = 384 B on ICI; the cross-slice psum of
        the 1/4 shard moves 2*(1/2)*64 = 64 B on DCN — 448 total."""
        from thunder_tpu.analysis.cost import trace_cost
        from thunder_tpu.distributed import prims as dp

        def fn(a):
            return dp.hier_all_reduce(a, "dp", "dcn", 4, 2)

        tr = self._extrace(fn, np.zeros((8, 8), np.float32))
        tc = trace_cost(tr, "v5e")
        assert tc.total_comm_bytes == 448.0
        assert tc.total_dcn_bytes == 64.0

    def test_flat_all_reduce_on_dcn_axis_prices_dcn(self):
        from thunder_tpu.analysis.cost import trace_cost
        from thunder_tpu.distributed import prims as dp

        def fn(a):
            return dp.all_reduce(a, "dcn", 2)

        tr = self._extrace(fn, np.zeros((8, 8), np.float32))
        tc = trace_cost(tr, "v5e")
        assert tc.total_dcn_bytes == tc.total_comm_bytes > 0

    def test_ici_collective_has_zero_dcn_bytes(self):
        from thunder_tpu.analysis.cost import trace_cost
        from thunder_tpu.distributed import prims as dp

        def fn(a):
            return dp.all_reduce(a, "dp", 4)

        tr = self._extrace(fn, np.zeros((8, 8), np.float32))
        tc = trace_cost(tr, "v5e")
        assert tc.total_comm_bytes > 0
        assert tc.total_dcn_bytes == 0.0

    def test_dcn_bytes_slower_than_ici(self):
        """Same bytes cost MORE wall time on the DCN tier: comm_s prices
        the two bandwidth classes separately."""
        from thunder_tpu.analysis.cost import DEVICE_SPECS, TraceCost

        dev = DEVICE_SPECS["v5e"]
        assert dev.dcn_bw_or_ici < dev.ici_bw
        ici = TraceCost(device=dev, total_comm_bytes=1e9, total_dcn_bytes=0.0)
        dcn = TraceCost(device=dev, total_comm_bytes=1e9, total_dcn_bytes=1e9)
        assert dcn.comm_s > ici.comm_s

    def test_hier_numerics_match_flat(self):
        """Executed on the virtual mesh: hierarchical == flat two-axis psum."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from thunder_tpu.parallel import make_federated_mesh

        mesh, _ = make_federated_mesh(2, dp=4)
        x = np.arange(64, dtype=np.float32).reshape(8, 8)

        def hier(a):
            part = jax.lax.psum_scatter(a, "dp", scatter_dimension=0,
                                        tiled=True)
            part = jax.lax.psum(part, "dcn")
            return jax.lax.all_gather(part, "dp", axis=0, tiled=True)

        def flat(a):
            return jax.lax.psum(a, ("dcn", "dp"))

        from jax import shard_map

        kw = dict(mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        got = shard_map(hier, **kw)(x)
        want = shard_map(flat, **kw)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


# =============================================================================
# Chaos: slice seams + per-(slice, host) seed derivation
# =============================================================================


class TestChaosSliceSeams:
    def test_parse_slice_clause(self):
        rules = chaos.parse_spec("slice_loss@3,slice=1").rules
        assert rules[0].seam == "slice_loss"
        assert rules[0].target == "3" and rules[0].slice == 1

    def test_slice_loss_fires_exactly_at_step(self):
        with chaos.chaos_scope("slice_loss@3,slice=1;seed=5"):
            assert chaos.slice_loss_at_step(2) is None
            assert chaos.slice_loss_at_step(3) == 1
            assert chaos.slice_loss_at_step(3) is None  # count exhausted
            assert chaos.slice_loss_at_step(4) is None

    def test_slice_flap_default_slice_zero(self):
        with chaos.chaos_scope("slice_flap@2;seed=5"):
            assert chaos.slice_flap_at_step(2) == 0

    def test_dcn_partition_carries_heal_delay(self):
        with chaos.chaos_scope("dcn_partition@4~3.0;seed=5"):
            assert chaos.dcn_partition_at_step(3) is None
            rule = chaos.dcn_partition_at_step(4)
            assert rule is not None and rule.delay_s == 3.0

    def test_slice_slow_targets_one_slice(self):
        with chaos.chaos_scope("slice_slow@slice=1~0.25;seed=5"):
            assert chaos.slice_slow_delay(0) == 0.0
            assert chaos.slice_slow_delay(1) == 0.25

    def test_seam_fires_emit_fault_events(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        monitor.set_event_log(log)
        try:
            with chaos.chaos_scope("slice_loss@1,slice=1;seed=5"):
                chaos.slice_loss_at_step(1)
        finally:
            monitor.set_event_log(None)
        rec = next(r for r in _events(log) if r["kind"] == "fault_injected")
        assert rec["seam"] == "slice_loss"
        assert rec["target"] == "step1:slice1"

    def test_seed_derivation_is_stable_and_distinct(self):
        a = chaos._derive_seed(7, 0, 0)
        assert a == chaos._derive_seed(7, 0, 0)  # replayable across runs
        # Distinct per coordinate: a renumbered host/slice never inherits
        # another's schedule (the bug `seed + host` arithmetic had).
        assert len({chaos._derive_seed(7, s, h)
                    for s in range(4) for h in range(4)}) == 16

    def test_rng_keyed_by_slice_env(self, monkeypatch):
        monkeypatch.setenv("THUNDER_TPU_SLICE_ID", "0")
        r0 = chaos.parse_spec("kernel_raise%0.5;seed=11").rng.random()
        monkeypatch.setenv("THUNDER_TPU_SLICE_ID", "1")
        r1 = chaos.parse_spec("kernel_raise%0.5;seed=11").rng.random()
        assert r0 != r1

    def test_slice_id_default_zero(self, monkeypatch):
        monkeypatch.delenv("THUNDER_TPU_SLICE_ID", raising=False)
        assert chaos.slice_id() == 0


# =============================================================================
# Snapshot ring: cross-slice buddy replication + DCN partition
# =============================================================================


class TestSnapshotRing:
    def _stores(self, n=2):
        stores = [SnapshotStore(host=i, ring=4) for i in range(n)]
        SnapshotStore.make_ring(stores)
        return stores

    def test_ring_buddy_wiring(self):
        s = self._stores(3)
        assert s[0].buddy is s[1] and s[1].buddy is s[2]
        assert s[2].buddy is s[0]

    def test_ring_needs_two(self):
        with pytest.raises(ValueError):
            SnapshotStore.make_ring([SnapshotStore(host=0)])

    def _put(self, store, step):
        from thunder_tpu.resilience.snapshot import Snapshot, pytree_crc32

        state = {"w": np.full(4, float(step), np.float32)}
        snap = Snapshot(step=step, state=state, crcs=pytree_crc32(state))
        store.put(snap)
        return snap

    def test_put_replicates_to_buddy(self):
        """A put on slice 0 is fetchable back from its buddy across the
        DCN boundary — where a replacement process reads after losing RAM."""
        s0, s1 = self._stores()
        self._put(s0, 3)
        assert [p.step for p in s0.peer_snapshots()] == [3]

    def test_partition_severs_replication_both_ways(self):
        s0, s1 = self._stores()
        self._put(s0, 1)
        s1.partitioned = True
        self._put(s0, 2)  # buddy partitioned: not replicated
        assert [p.step for p in s0.peer_snapshots()] == []  # reads severed too
        s1.partitioned = False
        self._put(s0, 3)  # healed: replication resumes
        assert sorted(p.step for p in s0.peer_snapshots()) == [1, 3]

    def test_local_partition_severs_own_put(self):
        s0, s1 = self._stores()
        s0.partitioned = True
        self._put(s0, 1)
        s0.partitioned = False
        assert [p.step for p in s0.peer_snapshots()] == []


# =============================================================================
# Orphan-tmp sweep on restore (satellite: died-mid-flush writers)
# =============================================================================


class TestTmpSweep:
    def test_restore_sweeps_stale_tmps(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save({"w": np.ones(4, np.float32)}, 5)
        # A writer that died mid-flush leaves an orphan .tmp dir behind.
        stale = os.path.join(mgr.directory, "step_3.tmp")
        os.makedirs(stale)
        with open(os.path.join(stale, "junk"), "w") as f:
            f.write("torn")
        monitor.set_event_log(log)
        try:
            state, meta = mgr.restore()
        finally:
            monitor.set_event_log(None)
        assert meta["step"] == 5
        assert not os.path.exists(stale)
        rec = next(r for r in _events(log) if r["kind"] == "ckpt_tmp_sweep")
        assert rec["count"] == 1 and rec["steps"] == [3]

    def test_restore_no_tmps_no_event(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save({"w": np.ones(4, np.float32)}, 5)
        monitor.set_event_log(log)
        try:
            mgr.restore()
        finally:
            monitor.set_event_log(None)
        assert not any(r["kind"] == "ckpt_tmp_sweep" for r in _events(log))


# =============================================================================
# Ledger + controller state machine (fake clock: no sleeps)
# =============================================================================


class TestFederationLedger:
    def test_initial_state(self):
        led = FederationLedger(3)
        assert led.width() == 3
        assert led.active_slices() == [0, 1, 2]

    def test_legal_cycle(self):
        led = FederationLedger(2)
        led.mark_lost(1)
        assert led.state_of(1) == "lost" and led.width() == 1
        led.mark_cooldown(1)
        led.promote(1)
        assert led.width() == 2
        assert [(s, f, t) for s, f, t, _ in led.transitions] == [
            (1, "active", "lost"), (1, "lost", "cooldown"),
            (1, "cooldown", "active")]

    def test_illegal_edges_raise(self):
        led = FederationLedger(2)
        with pytest.raises(ValueError):
            led.promote(1)  # active -> active
        led.mark_lost(1)
        with pytest.raises(ValueError):
            led.promote(1)  # lost -> active skips cooldown

    def test_transitions_emit_slice_state_events(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        monitor.set_event_log(log)
        try:
            led = FederationLedger(2)
            led.mark_lost(1, reason="chaos")
        finally:
            monitor.set_event_log(None)
        rec = next(r for r in _events(log) if r["kind"] == "slice_state")
        assert rec["slice"] == 1 and rec["from"] == "active"
        assert rec["to"] == "lost" and rec["reason"] == "chaos"

    def test_debug_state_shape(self):
        led = FederationLedger(2)
        led.mark_lost(0)
        st = led.debug_state()
        assert st["n_slices"] == 2 and st["width"] == 1
        assert st["slices"][0]["state"] == "lost"
        assert st["transitions"][-1]["to"] == "lost"


class TestFleetController:
    def _controller(self, n=2, backoff=10.0, hysteresis=10.0):
        t = [0.0]
        led = FederationLedger(n, clock=lambda: t[0])
        fc = FleetController(led, Autopilot(), rejoin_backoff_s=backoff,
                             hysteresis_s=hysteresis, clock=lambda: t[0])
        return fc, led, t

    def test_loss_decides_shrink(self):
        fc, led, _ = self._controller()
        d = fc.on_slice_loss(1, step=3)
        assert d is not None and d.actuator == "shrink_dp"
        assert led.state_of(1) == "lost"

    def test_duplicate_loss_is_noop(self):
        fc, _, _ = self._controller()
        assert fc.on_slice_loss(1) is not None
        assert fc.on_slice_loss(1) is None

    def test_backoff_holds_slice_out_until_hysteresis_clears(self):
        """The flap guarantee: a recovered slice stays in cooldown until
        max(rejoin_backoff, hysteresis) of STABLE time has passed; a
        re-failure inside the window restarts it and costs no second
        shrink."""
        fc, led, t = self._controller(backoff=5.0, hysteresis=8.0)
        fc.on_slice_loss(1, step=1)
        t[0] = 10.0
        fc.on_slice_recovered(1, step=2)
        assert led.state_of(1) == "cooldown"
        t[0] = 12.0
        assert fc.poll(step=3) is None        # 2s stable < 8s window
        t[0] = 17.0
        assert fc.poll(step=4) is None        # 7s stable: backoff cleared,
        # hysteresis (the max) not yet
        # re-failure inside the window: NO second shrink, window restarts
        assert fc.on_slice_loss(1, step=5) is None
        t[0] = 20.0
        fc.on_slice_recovered(1, step=6)
        t[0] = 27.0
        assert fc.poll(step=7) is None        # only 7s since the re-recovery
        t[0] = 28.5
        d = fc.poll(step=8)
        assert d is not None and d.actuator == "regrow_dp"
        assert led.state_of(1) == "active"

    def test_poll_promotes_one_slice_at_a_time(self):
        fc, led, t = self._controller(n=3, backoff=1.0, hysteresis=1.0)
        fc.on_slice_loss(1)
        fc.on_slice_loss(2)
        t[0] = 5.0
        fc.on_slice_recovered(1)
        fc.on_slice_recovered(2)
        t[0] = 10.0
        assert fc.poll() is not None
        assert led.width() == 2
        assert fc.poll() is not None
        assert led.width() == 3
        assert fc.poll() is None

    def test_grad_accum_rescales_loss_equivalently(self):
        fc, led, _ = self._controller(n=4)
        assert fc.grad_accum_for(2) == 2     # full width: unchanged
        fc.on_slice_loss(3)
        assert fc.grad_accum_for(2) == 3     # ceil(2*4/3)
        fc.on_slice_loss(2)
        assert fc.grad_accum_for(2) == 4     # 2*4/2
        fc.on_slice_loss(1)
        assert fc.grad_accum_for(2) == 8     # 2*4/1

    def test_all_slices_lost_halts(self):
        fc, _, _ = self._controller()
        fc.on_slice_loss(0)
        fc.on_slice_loss(1)
        with pytest.raises(AutopilotHalt):
            fc.grad_accum_for(1)

    def test_controller_installs_ledger_for_ops_plane(self):
        try:
            fc, led, _ = self._controller()
            assert current_ledger() is led
        finally:
            install_ledger(None)


# =============================================================================
# Cross-slice spread detector -> autopilot strike ledger
# =============================================================================


class TestSliceSpreadDetector:
    def _bank(self):
        from thunder_tpu.observability.detect import (
            DetectorBank, DetectorConfig)

        return DetectorBank(DetectorConfig(
            spread_min_steps=2, spread_consecutive=2))

    def test_slow_slice_flagged(self):
        bank = self._bank()
        for _ in range(8):
            bank.note_slice_step(0, 0.10)
            bank.note_slice_step(1, 0.30)
        hits = [a for a in bank.anomalies if a.kind == "slice_spread"]
        assert hits and hits[0].suspect_host == "slice1"
        state = bank.slice_spread_state()
        assert state["slow_slices"] == [1]

    def test_even_fleet_quiet(self):
        bank = self._bank()
        for _ in range(8):
            bank.note_slice_step(0, 0.10)
            bank.note_slice_step(1, 0.11)
        assert not [a for a in bank.anomalies if a.kind == "slice_spread"]

    def test_anomaly_strikes_autopilot_ledger(self):
        ap = Autopilot()
        bank = self._bank()
        with ap.installed():
            for _ in range(16):
                bank.note_slice_step(0, 0.10)
                bank.note_slice_step(1, 0.30)
        assert any(h == "slice1" for h in ap._anomaly_strikes)

    def test_slice_loss_signal_cites_slice_spread(self):
        ap = Autopilot()
        ap.note_anomaly({"anomaly": "slice_spread", "severity": "warn",
                         "value": 2.0, "baseline": 1.3,
                         "suspect_host": "slice1"})
        d = ap.decide(Signal("slice_loss", step=3, suspect_host="slice1"))
        assert d.actuator == "shrink_dp"
        assert d.signal.evidence.get("anomaly", {}).get("anomaly") == \
            "slice_spread"


# =============================================================================
# Decision replay: shrink_dp / regrow_dp correlation rules
# =============================================================================


class TestFederationReplay:
    def _replay(self, recs, **kw):
        from thunder_tpu.analysis.events import replay_events

        path = os.path.join(tempfile.mkdtemp(), "log.jsonl")
        with open(path, "w") as f:
            for i, r in enumerate(recs):
                base = {"v": 1, "ts": float(i), "seq": i, "pid": 1, "host": 0}
                base.update(r)
                f.write(json.dumps(base) + "\n")
        return replay_events(path, **kw)

    def _decision(self, actuator, signal="slice_loss"):
        return {"kind": "autopilot_decision", "decision_id": 1,
                "signal": signal, "actuator": actuator}

    _RESUME = {"kind": "elastic_resume", "step": 3, "from_mesh": {"dp": 4},
               "to_mesh": {"dp": 2}, "resharded": True, "tier": "peer"}
    _SLICE_STATE = {"kind": "slice_state", "slice": 1, "from": "active",
                    "to": "lost", "reason": "slice_loss"}

    def test_new_kinds_validate(self):
        _, diags = self._replay([
            self._SLICE_STATE,
            {"kind": "ckpt_tmp_sweep", "count": 2, "steps": [1, 2]},
        ])
        assert not diags

    def test_shrink_dp_requires_elastic_resume(self):
        summary, _ = self._replay([self._decision("shrink_dp")])
        assert summary["unactuated_decisions"] == ["shrink_dp<-slice_loss"]
        summary, _ = self._replay([self._decision("shrink_dp"), self._RESUME])
        assert summary["unactuated_decisions"] == []

    def test_regrow_dp_requires_elastic_resume(self):
        summary, _ = self._replay(
            [self._decision("regrow_dp", "slice_recovered")])
        assert summary["unactuated_decisions"] == \
            ["regrow_dp<-slice_recovered"]
        summary, _ = self._replay(
            [self._decision("regrow_dp", "slice_recovered"), self._RESUME])
        assert summary["unactuated_decisions"] == []

    def test_slice_loss_fault_requires_resume(self):
        fault = {"kind": "fault_injected", "seam": "slice_loss",
                 "target": "step3:slice1", "n": 1}
        summary, _ = self._replay([fault])
        assert summary["unrecovered_faults"] == ["slice_loss@step3:slice1"]
        summary, _ = self._replay([fault, self._RESUME])
        assert summary["unrecovered_faults"] == []

    def test_slice_flap_recovered_by_slice_state(self):
        fault = {"kind": "fault_injected", "seam": "slice_flap",
                 "target": "step3:slice1", "n": 1}
        summary, _ = self._replay([fault])
        assert summary["unrecovered_faults"] == ["slice_flap@step3:slice1"]
        summary, _ = self._replay([fault, self._SLICE_STATE])
        assert summary["unrecovered_faults"] == []


# =============================================================================
# The federated driver end-to-end (2 emulated slices on the virtual mesh)
# =============================================================================


def _toy_step(mesh, width, accum):
    import jax.numpy as jnp

    def step_fn(state):
        w = state["w"]
        loss = float(np.asarray(jnp.sum(w * w)))
        return {"w": w - 0.01 * w}, loss

    return step_fn


class TestFederatedDriver:
    N_SLICES = 2
    DP_PER = 2

    def _run(self, tmp_path, spec, n=20, name="ck", **kw):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from thunder_tpu.parallel import make_mesh

        def mesh_for_width(w):
            return make_mesh(dp=self.DP_PER * w), {"w": P()}

        led = FederationLedger(self.N_SLICES)
        ap = Autopilot()
        fc = FleetController(led, ap, rejoin_backoff_s=0.02,
                             hysteresis_s=0.02)
        stores = [SnapshotStore(host=i, ring=4)
                  for i in range(self.N_SLICES)]
        SnapshotStore.make_ring(stores)
        mgr = CheckpointManager(str(tmp_path / name), store=stores[0])
        init = {"w": jnp.ones((8,), jnp.float32)}
        kw.setdefault("on_step",
                      lambda step, loss, width: __import__("time")
                      .sleep(0.004))
        try:
            with chaos.chaos_scope(spec):
                state, report = run_federated_training(
                    fc, _toy_step, init, n, manager=mgr,
                    mesh_for_width=mesh_for_width, stores=stores,
                    snapshot_every=2, **kw)
        finally:
            install_ledger(None)
        return state, report, led, ap

    def test_slice_loss_shrinks_then_regrows(self, tmp_path):
        log = str(tmp_path / "ev.jsonl")
        monitor.set_event_log(log)
        try:
            _, report, led, _ = self._run(
                tmp_path, "slice_loss@6,slice=1;seed=3", recover_after=4)
        finally:
            monitor.set_event_log(None)
        assert report.halted is None
        assert report.shrinks == 1 and report.regrows == 1
        assert report.degraded_steps > 0
        assert report.final_width == report.full_width == 2
        assert report.steps_executed == 20
        recs = _events(log)
        # The acceptance invariant: the slice-loss restore came from the
        # cross-slice buddy's RAM — tier="peer", disk never touched after
        # the initial anchor resume.
        tiers = [r["tier"] for r in recs
                 if r["kind"] == "restore" and r.get("ok")]
        assert tiers.count("peer") == 1
        assert "disk" not in tiers[1:]
        decisions = [r["actuator"] for r in recs
                     if r["kind"] == "autopilot_decision"]
        assert decisions == ["shrink_dp", "regrow_dp"]
        from thunder_tpu.analysis.events import replay_events

        summary, diags = replay_events(log, storm_threshold=64)
        assert summary["unrecovered_faults"] == []
        assert summary["unactuated_decisions"] == []

    def test_flap_degrades_once(self, tmp_path):
        """The flapping-slice headline: fail/recover/fail/recover faster
        than the hysteresis window costs ONE shrink and ONE (deferred)
        regrow — proven on the replayed autopilot event ledger."""
        log = str(tmp_path / "ev.jsonl")
        monitor.set_event_log(log)
        try:
            _, report, _, _ = self._run(
                tmp_path, "slice_flap@4,slice=1;seed=3")
        finally:
            monitor.set_event_log(None)
        assert report.halted is None
        assert report.shrinks == 1 and report.regrows == 1
        recs = _events(log)
        decisions = [r["actuator"] for r in recs
                     if r["kind"] == "autopilot_decision"]
        assert decisions == ["shrink_dp", "regrow_dp"]
        # the ledger saw the flap: a cooldown -> lost re-failure edge
        edges = [(r["from"], r["to"]) for r in recs
                 if r["kind"] == "slice_state"]
        assert ("cooldown", "lost") in edges
        from thunder_tpu.analysis.events import replay_events

        summary, _ = replay_events(log, storm_threshold=64)
        assert summary["unrecovered_faults"] == []
        assert summary["unactuated_decisions"] == []

    def test_dcn_partition_defers_replication(self, tmp_path):
        _, report, _, _ = self._run(
            tmp_path, "dcn_partition@4~3.0;seed=3", n=14)
        assert report.halted is None
        assert report.partitioned_steps > 0
        assert report.shrinks == 0  # training continued in-slice

    def test_slow_slice_inflates_degraded_signal(self, tmp_path):
        from thunder_tpu.observability.detect import (
            DetectorBank, DetectorConfig)

        bank = DetectorBank(DetectorConfig(
            spread_min_steps=2, spread_consecutive=2))
        _, report, _, _ = self._run(
            tmp_path, "slice_slow@slice=1~0.05;seed=3", n=10,
            slice_step_time=bank.note_slice_step)
        assert report.halted is None and report.shrinks == 0
        hits = [a for a in bank.anomalies if a.kind == "slice_spread"]
        assert hits and hits[0].suspect_host == "slice1"

    def test_losses_stay_finite_through_episode(self, tmp_path):
        _, report, _, _ = self._run(
            tmp_path, "slice_loss@6,slice=1;seed=3", recover_after=4)
        assert all(np.isfinite(loss) for loss in report.losses)
