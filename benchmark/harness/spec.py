"""Find a cell and everything it names, by name, from data files.

``BENCHMARK.json`` at the checkout root lists the cells.  Each cell names
a configuration (``benchmark/configs/<config>.json``), a traffic mix
(``benchmark/traffic/<traffic>.json``) and, through the metrics that list
it, per-layer readers (``benchmark/metrics/<metric>.py``).  Nothing here
knows a cell, a configuration or a metric by name, so a cell that only
adds data files runs without a code change.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple[str, ...] | None
    moves: str | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    answers: dict
    bench_dir: str

    def reader(self, metric: Metric):
        """The ``read(ctx)`` function of a per-layer metric's own file."""
        path = os.path.join(self.bench_dir, "metrics", metric.name + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.name.replace(".", "_"), path
        )
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries, cell: str) -> tuple[Metric, ...]:
    out = []
    for m in entries:
        wl = m.get("workloads")
        if wl is not None and cell not in wl:
            continue
        out.append(Metric(
            name=m["name"], unit=m["unit"],
            workloads=tuple(wl) if wl else None, moves=m.get("moves"),
        ))
    return tuple(out)


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its data."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(
        os.path.join(bench_dir, "traffic", w["traffic"] + ".json")
    )
    answers = _load_json(
        os.path.join(bench_dir, "reference", "answers", w["config"] + ".json")
    )
    if answers.get("constants") != config["constants"]:
        raise ValueError(
            f"reference answers for {w['config']} were computed for other "
            "constants; regenerate them (benchmark/reference/ref.py)"
        )
    e2e = _metrics(spec["end_to_end"], name)
    # a per-layer metric without a workloads list belongs to every cell
    # that reports the end-to-end metric it moves
    e2e_names = {m.name for m in e2e}
    per_layer = tuple(
        m for m in _metrics(spec["per_layer"], name)
        if m.workloads is not None or m.moves in e2e_names
    )
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer, answers=answers,
        bench_dir=bench_dir,
    )
