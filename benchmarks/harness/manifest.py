"""``BENCHMARK.json`` and the per-cell data files, found by name.

A cell is one ``workloads`` entry: a configuration under a traffic mix. What
belongs to one of them sits in a file of its own::

    benchmarks/configs/<config>.json     sizes, TrainConfig fields of model/optimizer, data block
    benchmarks/traffic/<mix>.json        chips, loader, TrainConfig fields of the layout
    benchmarks/workloads/<cell>.json     why, predictions (optional)
    benchmarks/models/<reference>.py     analytic operations, plain float32 reference
    benchmarks/layer_metrics/<name>.py   one reader per per-layer metric

so a later PR adds files and one ``workloads`` entry and edits nothing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
BENCH_DIR = "benchmarks"


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a cell's file breaks the contract."""


class RefusedError(RuntimeError):
    """The run cannot be made here (no TPU, too few chips, unknown chip):
    the command exits non-zero and prints no result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one run needs, read from the cell's files."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]     # benchmarks/configs/<config>.json
    traffic: Dict[str, Any]    # benchmarks/traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]   # this cell's metrics, manifest entries
    per_layer: List[Dict[str, Any]]

    @property
    def batch_per_chip(self) -> int:
        return int(
            self.traffic.get("batch_per_chip")
            or self.config["data"]["batch_per_chip"]
        )

    @property
    def global_batch(self) -> int:
        return self.batch_per_chip * self.chips

    @property
    def reference_check(self) -> Dict[str, Any]:
        """The configuration's tolerances; a traffic mix may override how
        much of the first batch the comparison takes."""
        return {**self.config["reference_check"], **self.traffic.get("reference_check", {})}

    @property
    def fused(self) -> bool:
        return self.traffic.get("loader", "stream") == "fused"

    @property
    def n_train(self) -> int:
        data = self.config["data"]
        if data.get("n_train"):
            return int(data["n_train"])
        return int(data["epoch_steps"]) * self.global_batch


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise ManifestError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path}: not JSON ({e})") from e


def load_manifest(root: str) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: Dict[str, Any], cell_name: str) -> bool:
    only = metric.get("workloads")
    return only is None or cell_name in only


def load_cell(root: str, name: str) -> Cell:
    man = load_manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        have = ", ".join(w["name"] for w in man["workloads"])
        raise ManifestError(f"unknown workload {name!r}; BENCHMARK.json has: {have}")
    cfg_entry = next(
        (c for c in man["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise ManifestError(f"workload {name!r} names unknown config {entry['config']!r}")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(
        os.path.join(root, BENCH_DIR, "traffic", entry["traffic"] + ".json")
    )
    if int(traffic.get("chips", entry["chips"])) != int(entry["chips"]):
        raise ManifestError(
            f"workload {name!r} asks for {entry['chips']} chip(s), its traffic "
            f"file {entry['traffic']!r} for {traffic['chips']}"
        )
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        traffic_name=entry["traffic"], config=config, traffic=traffic,
        end_to_end=[m for m in man["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in man["per_layer"] if _reports(m, name)],
    )


def train_config_fields(cell: Cell, seed: int) -> Dict[str, Any]:
    """``TrainConfig`` = defaults (+) config file (+) traffic file. The batch
    and the seed come from the cell; a field the dataclass lacks is an error,
    and so is one that both the harness and a file would set."""
    from tpu_dist.config.config import TrainConfig  # noqa: PLC0415

    known = {f.name for f in dataclasses.fields(TrainConfig)}
    owned = {"batch_size", "seed", "fused_epoch", "dataset", "synthetic_n"}
    out: Dict[str, Any] = {}
    for origin, fields in (
        (f"configs/{cell.config_name}.json", cell.config.get("train_config", {})),
        (f"traffic/{cell.traffic_name}.json", cell.traffic.get("train_config", {})),
    ):
        for key, value in fields.items():
            if key not in known:
                raise ManifestError(f"{origin}: TrainConfig has no field {key!r}")
            if key in owned:
                raise ManifestError(
                    f"{origin}: {key!r} is set by the harness from the cell "
                    "(batch_per_chip, loader, --seed, the data block)"
                )
            out[key] = tuple(value) if isinstance(value, list) else value
    out.update(
        batch_size=cell.global_batch, seed=int(seed), fused_epoch=cell.fused,
        dataset="synthetic",
        # the Trainer's own data set is replaced by the benchmark's seeded
        # arrays (adapter.py), so it only has to exist
        synthetic_n=max(cell.global_batch, 512),
    )
    return out


def load_module(root: str, kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (models, layer_metrics)."""
    if not NAME_RE.match(name):
        raise ManifestError(f"bad {kind} name {name!r}")
    path = os.path.join(root, BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(root: str, device_kind: str) -> Dict[str, float]:
    table = _load_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    row = table.get(device_kind)
    if not isinstance(row, dict):
        kinds = sorted(k for k in table if not k.startswith("_"))
        raise RefusedError(
            f"device_kind {device_kind!r} has no row in benchmarks/peaks.json "
            f"(rows: {kinds}); an unknown chip is an error, not a default"
        )
    return row


# -- the contract's checks that need no chip (tests/benchmark runs them) -----

def check_manifest(root: str) -> List[str]:
    """Every breach of the contract this file can see, as text; [] if none."""
    man = load_manifest(root)
    bad: List[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(man) != want:
        bad.append(f"keys {sorted(man)} != {sorted(want)}")
        return bad

    def name_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            bad.append(f"{what}: bad name {value!r}")

    def line_ok(what: str, value: Any) -> None:
        if (not isinstance(value, str) or not 1 <= len(value) <= 200
                or "\n" in value or "\t" in value):
            bad.append(f"{what}: not one line of 1 to 200 characters")

    if not 1 <= int(man["run_seconds"]) <= 51:
        bad.append("run_seconds outside 1..51")
    paths = man["paths"]
    for word in man["command"]:
        line_ok("command", word)
    config_names = [c["name"] for c in man["configs"]]
    files = [c["file"] for c in man["configs"]]
    if len(set(files)) != len(files):
        bad.append("two configs share a file")
    for c in man["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        for key in c["reduced"]:
            name_ok(f"config {c['name']} reduced", key)
        if not any(c["file"].startswith(p + "/") for p in paths):
            bad.append(f"config {c['name']}: file {c['file']} outside paths")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: file {c['file']} missing")
    cells = man["workloads"]
    if not 2 <= len(cells) <= 24:
        bad.append("workloads: need 2 to 24 cells")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    if len(set(pairs)) != len(pairs):
        bad.append("a (config, traffic) pair appears twice")
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
        name_ok("workload", w["name"])
        name_ok(f"workload {w['name']} traffic", w["traffic"])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["config"] not in config_names:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
    for c in config_names:
        if not any(w["config"] == c for w in cells):
            bad.append(f"config {c} has no cell")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} four-chip cells of {len(cells)}: over a quarter")
    names = [w["name"] for w in cells] + config_names
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    for group in (names, metric_names):
        if len(set(group)) != len(group):
            bad.append(f"duplicate name in {sorted(group)}")
    cell_names = {w["name"] for w in cells}
    for m in man["end_to_end"] + man["per_layer"]:
        name_ok("metric", m["name"])
        if not UNIT_RE.match(str(m.get("unit", ""))):
            bad.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for wl in m.get("workloads", []):
            if wl not in cell_names:
                bad.append(f"metric {m['name']}: unknown workload {wl}")
    for m in man["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad.append(f"end_to_end {m['name']}: keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end_to_end {m['name']}: source {m['source']}")
        if not 0.01 <= float(m["bound"]) <= 0.1:
            bad.append(f"end_to_end {m['name']}: bound {m['bound']}")
    if "setup_s" not in [m["name"] for m in man["end_to_end"]]:
        bad.append("no setup_s")
    for m in man["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            bad.append(f"per_layer {m['name']}: keys {sorted(m)}")
        line_ok(f"per_layer {m['name']} layer", m.get("layer"))
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for w in cells:
        mine = [m for m in man["end_to_end"] if _reports(m, w["name"])]
        if "setup_s" not in [m["name"] for m in mine] or len(mine) < 2:
            bad.append(f"cell {w['name']}: needs setup_s and one more end-to-end metric")
        layer = [m for m in man["per_layer"] if _reports(m, w["name"])]
        if not layer:
            bad.append(f"cell {w['name']}: no per-layer metric")
        for m in layer:
            moved = e2e.get(m["moves"])
            if moved is None or not _reports(moved, w["name"]):
                bad.append(
                    f"cell {w['name']}: {m['name']} moves {m['moves']}, "
                    "which the cell does not report"
                )
    return bad
