"""Resolve a cell by name. ``BENCHMARK.json`` lists cells, metrics and
configurations; everything that belongs to one of them is a file of its
own that is found by the name in that list, so a later PR adds a cell by
adding files and entries and edits nothing:

* ``benchmark/configs/<config>.json`` is named by the entry's ``file``;
* ``benchmark/traffic/<traffic>.json`` is found beside it by name;
* ``benchmark/drivers/<kind>.py`` by the traffic file's ``kind``;
* ``benchmark/layer_metrics/<metric>.py`` by the metric's name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmark"


class BenchmarkError(Exception):
    """The benchmark cannot run or cannot vouch for its result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file, as it is run
    traffic_name: str
    traffic: dict         # the traffic mix's parameters
    end_to_end: tuple[dict, ...]   # metric entries this cell reports
    per_layer: tuple[dict, ...]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _metrics_for(entries: list[dict], cell: str) -> tuple[dict, ...]:
    """A metric belongs to every cell unless it lists its cells."""
    return tuple(m for m in entries
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {name!r} names configuration "
                             f"{entry['config']!r}, which is not listed")
    traffic_path = (root / "benchmark" / "traffic"
                    / f"{entry['traffic']}.json")
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"],
                config=load_json(root / cfg_entry["file"]),
                traffic_name=entry["traffic"],
                traffic=load_json(traffic_path),
                end_to_end=_metrics_for(bench["end_to_end"], name),
                per_layer=_metrics_for(bench["per_layer"], name))


def _load_module(path: Path, label: str):
    if not path.exists():
        raise BenchmarkError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{label}_{path.stem}".replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(kind: str, root: Path = ROOT):
    """The driver of one traffic kind: a module with
    ``run(cell, args, rt) -> dict``."""
    return _load_module(root / "benchmark" / "drivers" / f"{kind}.py",
                        "driver")


def load_reader(metric: str, root: Path = ROOT):
    """The reader of one per-layer metric: a module with
    ``read(ctx) -> float | None``."""
    return _load_module(root / "benchmark" / "layer_metrics"
                        / f"{metric}.py", "reader")


# -- the configuration file → the sections handed to the program ----------

def model_section(config: dict) -> dict:
    """The program's ``model`` section from the configuration's source
    keys. Only sizes: the choice of attention implementation, dtype and
    recomputation policy stay at the program's defaults."""
    d, ffn = config["hidden_size"], config["ffn_dim"]
    if ffn != 4 * d:
        raise BenchmarkError(
            f"ffn_dim {ffn} is not 4 x hidden_size {d}: the repo's block "
            "fixes the FFN width at 4·d and cannot run this shape")
    if config.get("word_embed_proj_dim", d) != d:
        raise BenchmarkError("word_embed_proj_dim differs from hidden_size: "
                             "the repo's block has no embedding projection")
    return {"name": "transformer", "model_dim": d,
            "num_heads": config["num_attention_heads"],
            "num_layers": config["num_hidden_layers"],
            "seq_len": config["max_position_embeddings"],
            "vocab_size": config["vocab_size"],
            **config.get("model_assumed", {})}
