"""The ``dispatch_*_ms`` readers on records made by hand and a window made by
hand: the median of one field of the records that the program wrote while the
untraced window ran. In process; no run of ``run.py``."""

import collections

import pytest
from pb_helpers import MANIFEST

from perfbench import reading, window

FIELDS = {"dispatch_in_program_ms": "s", "dispatch_lookup_ms": "lookup_s",
          "dispatch_prepare_ms": "prepare_s", "dispatch_launch_ms": "launch_s"}
FORWARD_CELLS = ["pythia-410m.fwd", "a.x-k1.fwd", "lfm2-8b-a1b.fwd", "minicpm-sala.fwd-t32k",
                 "trinity-mini.fwd-t32k", "longcat-flash-omni.fwd-t16k", "granite-4.0-h-micro.fwd-t16k"]


def hand_made(started_at: float, ended_at: float) -> reading.Reading:
    return reading.Reading(cell=None, spans={}, counters={},
                           window=window.WindowResult(started_at=started_at, ended_at=ended_at),
                           tokens_per_s=None, flops_per_token=0.0, peaks=None)


def record(at: float, s: float) -> dict:
    return {"fn": "forward", "hit": "fast", "at": at, "s": s,
            "lookup_s": s / 2, "prepare_s": s / 4, "launch_s": s / 8}


@pytest.fixture
def records(monkeypatch):
    from thunder_tpu import api

    mine = collections.deque(maxlen=4096)
    monkeypatch.setattr(api, "_dispatch_records", mine)
    return mine


@pytest.mark.parametrize("name", sorted(FIELDS))
def test_the_median_of_the_calls_in_the_window(records, name):
    # Set-up's calls before the window, the traced units' and the check's after it.
    records.extend([record(9.0, 1.0), record(9.99, 1.0)])
    records.extend(record(10.0 + i, s) for i, s in enumerate([0.004, 0.002, 0.003, 0.001]))
    records.extend([record(20.01, 1.0), record(25.0, 1.0)])
    scale = {"s": 1, "lookup_s": 1 / 2, "prepare_s": 1 / 4, "launch_s": 1 / 8}[FIELDS[name]]
    assert reading.read_metric(name, hand_made(10.0, 20.0)) == pytest.approx(2.5 * scale, rel=1e-12)
    assert reading.read_metric(name, hand_made(10.0, 12.5)) == pytest.approx(3.0 * scale, rel=1e-12)


@pytest.mark.parametrize("name", sorted(FIELDS))
def test_no_call_in_the_window_gives_no_metric(records, name):
    assert reading.read_metric(name, hand_made(10.0, 20.0)) is None
    records.extend([record(9.0, 1.0), record(21.0, 1.0)])
    assert reading.read_metric(name, hand_made(10.0, 20.0)) is None


@pytest.mark.parametrize("name", sorted(FIELDS))
def test_a_program_without_the_record_gives_no_metric_and_does_not_raise(monkeypatch, records, name):
    """An older program with these files laid over its checkout has no
    ``dispatch_phases``: the result line then leaves the metrics out."""
    import thunder_tpu

    records.append(record(15.0, 1.0))
    monkeypatch.delattr(thunder_tpu, "dispatch_phases")
    assert reading.read_metric(name, hand_made(10.0, 20.0)) is None


def test_the_manifest_lists_the_seven_forward_cells_for_each():
    entries = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in FIELDS}
    assert set(entries) == set(FIELDS)
    for m in entries.values():
        assert m["workloads"] == FORWARD_CELLS
        assert (m["layer"], m["source"], m["moves"], m["unit"], m["better"]) == (
            "entry", "program_span", "tokens_per_s", "ms", "lower")
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert all(cells[c]["traffic"].startswith("fwd") for c in FORWARD_CELLS)
    assert {c for c, w in cells.items() if w["traffic"].startswith("fwd")} == set(FORWARD_CELLS)
