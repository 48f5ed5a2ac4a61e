"""``BENCHMARK.json`` against the contract it is written to, every name
in it resolved to the file it stands for, and the proof that a later PR
can add a cell, a configuration, a traffic mix and a per-layer metric by
new files and new entries alone."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import cell as cell_lib

ROOT = cell_lib.ROOT
BENCH = cell_lib.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_the_file_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200
               for e in BENCH["configs"] + BENCH["workloads"])


def test_cells_and_chips():
    assert 2 <= len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_metrics_are_well_formed():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["source"] in SOURCES
        assert LAYER.match(m["layer"]) and m["moves"] in e2e
        assert m["unit"] and m["better"] in ("higher", "lower")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_that_exist(name):
    cell = cell_lib.load_cell(name)
    assert cell.kind and (ROOT / "benchmark" / "drivers"
                          / f"{cell.kind}.py").exists()
    assert hasattr(cell_lib.load_driver(cell.kind), "run")
    # setup_s, one more end-to-end metric, one per-layer metric at least
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell_lib.load_reader(m["name"]).read)
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations_carry_the_published_widths(entry):
    cfg = cell_lib.load_json(ROOT / entry["file"])
    assert entry["file"].startswith("benchmark/configs/")
    published = {
        "opt-6.7b": (4096, 32, 16384, 32),
        "opt-1.3b": (2048, 32, 8192, 24)}[entry["name"]]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["ffn_dim"]) == published[:3]
    assert cfg["vocab_size"] == 50272
    assert cfg["max_position_embeddings"] == 2048
    if "num_hidden_layers" in entry["reduced"]:
        assert cfg["num_hidden_layers"] < published[3]
        assert "num_hidden_layers" in cfg["reduced"]
    else:
        assert cfg["num_hidden_layers"] == published[3]
    # reduced never names a width
    assert not any(re.search(r"(size|dim|rank|heads)", k)
                   for k in entry["reduced"])
    assert cfg["departures"] and cfg["deployment"]
    # sizes, never a choice between duplicate paths (ROADMAP D4)
    text = json.dumps(cfg)
    for switch in ("attention_kernel", "attention_impl", "remat_policy",
                   "use_native_pipeline", "sp_attention", "comm_buckets",
                   "resident_sharded", "async_snapshot", "swap_policy",
                   "pipeline_schedule"):
        assert f'"{switch}"' not in text, switch
    model = cell_lib.model_section(cfg)
    assert model["model_dim"] // model["num_heads"] in (64, 128)


def test_a_block_the_repo_cannot_run_is_refused():
    with pytest.raises(cell_lib.BenchmarkError):
        cell_lib.model_section({"hidden_size": 64, "ffn_dim": 100,
                                "num_attention_heads": 4,
                                "num_hidden_layers": 1, "vocab_size": 8,
                                "max_position_embeddings": 8})
    with pytest.raises(cell_lib.BenchmarkError):
        cell_lib.load_cell("no.such_cell")


def test_the_open_cells_rate_is_a_number_below_its_knee():
    arrivals = cell_lib.load_cell("opt-1.3b.serve_chat_open").traffic[
        "arrivals"]
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"], rel=0.05)


# -- added by files alone ---------------------------------------------------

def _copy_of_the_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    # a configuration, a traffic mix of a NEW kind with its driver, and a
    # per-layer metric with its reader: four new files
    (root / "benchmark/configs/toy.json").write_text(json.dumps(
        {"hidden_size": 8, "ffn_dim": 32, "num_attention_heads": 2,
         "num_hidden_layers": 1, "vocab_size": 16,
         "max_position_embeddings": 8}))
    (root / "benchmark/traffic/toy_echo.json").write_text(json.dumps(
        {"kind": "echo", "value": 3.5}))
    (root / "benchmark/drivers/echo.py").write_text(
        "def run(cell, rt):\n"
        "    rt.window_opens()\n"
        "    rt.window_closes()\n"
        "    return {'correct': True, 'attempted': 1, 'failed': 0,\n"
        "            'values': {'echo_rate': cell.traffic['value']},\n"
        "            'counters': {'echoes': 2}}\n")
    (root / "benchmark/layer_metrics/echoes_per_op.py").write_text(
        "def read(trace, counters):\n"
        "    return counters['echoes'] / len(trace['device_ops'])\n")
    bench = cell_lib.load_json(root / "BENCHMARK.json")
    bench["configs"].append({"name": "toy", "source": "none",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.echo", "config": "toy",
                               "traffic": "toy_echo", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "echo_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["toy.echo"]})
    bench["per_layer"].append({"name": "echoes_per_op", "unit": "1",
                               "better": "higher",
                               "source": "program_counter", "layer": "toy",
                               "moves": "echo_rate",
                               "workloads": ["toy.echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cell_lib.load_cell("toy.echo", root=root)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "echo_rate"]
    # metrics without a list of cells reach the new cell too
    assert [m["name"] for m in cell.per_layer] == ["compile_or_load_s",
                                                   "echoes_per_op"]
    from bench_toy import ToyRuntime
    rt = ToyRuntime(cell, 0, 1.0, False, 0.0,
                    {"platform": "cpu", "kind": "toy", "count": 1},
                    {"bf16_flops_per_s": 1.0}, work_root=tmp_path / "work")
    result = run_mod.measure(cell, rt, root=root)
    assert result["metrics"]["echo_rate"] == {"value": 3.5, "unit": "1/s"}
    layer = run_mod.per_layer_metrics(
        cell, {"device_ops": [["a", 1.0], ["b", 1.0]]},
        {"echoes": 2, "setup_compile_s": 0.25}, root=root)
    assert layer == {"compile_or_load_s": {"value": 0.25, "unit": "s"},
                     "echoes_per_op": {"value": 1.0, "unit": "1"}}
    # and not one file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())
    # the cells that were there still resolve
    assert cell_lib.load_cell(CELLS[0], root=root).name == CELLS[0]


def test_a_listed_metric_without_a_reading_is_an_error_not_a_zero(tmp_path):
    cell = cell_lib.load_cell("opt-1.3b.serve_chat_open")
    with pytest.raises(cell_lib.BenchmarkError, match="found nothing"):
        run_mod.per_layer_metrics(
            cell, {"busy_s": 1.0, "window_s": 2.0, "pallas_s": 0.1,
                   "modules": {"jit_x": {"starts_ms": [0, 1, 2, 3],
                                         "durations_ms": [1, 1, 1, 1]}}},
            {"setup_compile_s": 1.0})   # no weights_ready_s counter


# -- refusing to run --------------------------------------------------------

def _run(cwd: Path, *extra_env: tuple[str, str]):
    import os
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **dict(extra_env)}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_runner_refuses_without_a_tpu():
    got = _run(ROOT)
    assert got.returncode != 0 and got.stdout == ""
    assert "needs a TPU" in got.stderr


def test_the_runner_refuses_without_the_program(tmp_path):
    got = _run(_copy_of_the_benchmark(tmp_path), ("PYTHONPATH", ""))
    assert got.returncode != 0 and got.stdout == ""
    assert "the program is not here" in got.stderr
