"""``BENCHMARK.json`` and the data files it names.

A cell names a configuration and a traffic mix; each is a JSON file of its own
under ``perfbench/``, found by the name in the manifest, so a later PR adds a
file and an entry and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names does not say what a run needs."""


def _load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"{path}: {e}") from e


def load_manifest(checkout: str = CHECKOUT) -> dict:
    return _load_json(os.path.join(checkout, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names read in."""

    name: str
    chips: int
    config_name: str
    config: dict  # perfbench/configs/<config>.json
    traffic_name: str
    traffic: dict  # perfbench/traffic/<traffic>.json
    end_to_end: tuple  # the manifest's metric entries that apply to this cell
    per_layer: tuple


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, checkout: str = CHECKOUT) -> Cell:
    manifest = load_manifest(checkout)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {name!r} names configuration {w['config']!r}, "
                            f"which BENCHMARK.json does not list")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=_load_json(os.path.join(checkout, configs[w["config"]]["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(checkout, "perfbench", "traffic", w["traffic"] + ".json")),
        end_to_end=tuple(m for m in manifest["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in manifest["per_layer"] if _applies(m, name)),
    )


def published(cell: Cell, rehearse: bool = False) -> dict:
    """The configuration file as this run uses it: where the file gives a key
    by the cell's ``chips`` (``<key>_at_chips``, the depth a cut model has on
    one chip and on four) that value, and with ``--rehearse`` the file's small
    stand-in sizes on top."""
    keys = dict(cell.config)
    for by_chips in [k for k in keys if k.endswith("_at_chips")]:
        keys[by_chips[: -len("_at_chips")]] = keys[by_chips][str(cell.chips)]
    return {**keys, **keys["stand_in"]} if rehearse else keys
