"""``BENCHMARK.json`` against the contract it is written to, every name
in it resolved to the file it stands for, and the proof that a later PR
can add a cell, a configuration of another architecture, a traffic mix
and a per-layer metric by new files and new entries alone."""

import dataclasses
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import toy_routed_model
from benchmark import run as run_mod
from benchmark.lib import cell as cell_lib, compare, serving

ROOT = cell_lib.ROOT
BENCH = cell_lib.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells(bench: dict) -> list[str]:
    return [w["name"] for w in bench["workloads"]]


CELLS = _cells(BENCH)


# What the file, every cell and every configuration is held to is a
# function of (the file, the checkout's root): the tests below run each
# over this tree, and the proof that a cell is added by new files alone
# runs EVERY one of them over its copy (``check_everything``), so a
# check that names what only this tree has cannot come back unseen.

def check_the_file(bench: dict, root: Path = ROOT) -> None:
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (root / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    names = ([c["name"] for c in bench["configs"]] + _cells(bench)
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200
               for e in bench["configs"] + bench["workloads"])


def check_cells_and_chips(bench: dict) -> None:
    cells = _cells(bench)
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def check_metrics(bench: dict) -> None:
    cells = _cells(bench)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in bench["per_layer"]:
        assert "bound" not in m and m["source"] in SOURCES
        assert LAYER.match(m["layer"]) and m["moves"] in e2e
        assert m["unit"] and m["better"] in ("higher", "lower")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)


def check_cell(name: str, root: Path = ROOT) -> None:
    cell = cell_lib.load_cell(name, root=root)
    assert cell.kind and (root / "benchmark" / "drivers"
                          / f"{cell.kind}.py").exists()
    assert hasattr(cell_lib.load_driver(cell.kind, root), "run")
    # setup_s, one more end-to-end metric, one per-layer metric at least
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell_lib.load_reader(m["name"], root).read)
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_the_file_has_exactly_the_contracts_keys():
    check_the_file(BENCH)


def test_cells_and_chips():
    check_cells_and_chips(BENCH)
    assert "opt-1.3b.serve_chat_open" in KNEE_CELLS


def test_metrics_are_well_formed():
    check_metrics(BENCH)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_that_exist(name):
    check_cell(name)


#: sizes, never a choice between duplicate paths (ROADMAP D4)
SWITCHES = ("attention_kernel", "attention_impl", "remat_policy",
            "use_native_pipeline", "sp_attention", "comm_buckets",
            "resident_sharded", "async_snapshot", "swap_policy",
            "pipeline_schedule")
#: What ``reduced`` may name is an allow-list by name: a key that
#: matches neither pattern below is refused, whatever it is. (The
#: `model-configs` guide, section 4: a width is never cut: a hidden,
#: intermediate, latent, state or projection size, a head size, a
#: window, an expansion factor, the number of experts a token takes or
#: of groups it takes them from; nor a shared expert, which every chip
#: computes alike.) A depth: the number of layers, or of leading dense
#: ones
DEPTH = re.compile(r"^(num|n)_(\w+_)?layers?$|^(\w+_)?layers$|^depth$"
                   r"|^first_k_dense_replace$")
#: what a chip may hold its share of, where a stated deployment divides
#: a layer over several chips (same section): the rows of the
#: vocabulary, the heads, the routed experts
COUNT = re.compile(r"^vocab_size$|^(num|n)_(\w+_)?heads?$"
                   r"|^(num|n)_(routed_|local_)?experts$|^(moe_)?num_experts$")
#: and no spelling of the two that also spells a width (a belt to the
#: allow-list: ``num_experts_per_tok``, ``n_shared_experts``,
#: ``num_selected_experts``, ``n_group`` match neither pattern above)
WIDTH = re.compile(r"size|dim|rank|per_tok|top_?k|expan|shared|select|activ"
                   r"|group|window|state")
#: the guide's floors for what is left of a model
MIN_VOCABULARY_SHARE = 8      # at least an eighth of the vocabulary
MIN_ROUTED_EXPERTS = 8


def is_count(key: str) -> bool:
    return bool(COUNT.match(key)) and (
        key == "vocab_size" or not WIDTH.search(key))


def is_depth(key: str) -> bool:
    return bool(DEPTH.match(key)) and not WIDTH.search(key)


def check_reduced(reduced: list[str], cfg: dict) -> None:
    """``reduced`` names depths and a chip's share of counts, by an
    allow-list of names: any other key is a width, or unknown, and
    refused. Every key it names runs smaller than published, with its
    reason in the file. A count (vocabulary, heads, experts) is the
    share one of ``n`` chips holds of a layer: the published value is
    ``n`` times what is held, and the key's reason and the file's
    ``deployment`` both say over how many chips a layer is shared
    ("<n> chips"; the shares may differ from key to key)."""
    published = cfg["published"]
    assert set(reduced) <= set(published)
    for key in reduced:
        held, whole, why = cfg[key], published[key], cfg["reduced"][key]
        assert held < whole and why, key
        if is_count(key):
            chips, rest = divmod(whole, held)
            assert rest == 0, f"{key}: {whole} is no multiple of {held}"
            assert re.search(rf"\b{chips} chips\b", why), (
                f"{key}: its reason does not say that {chips} chips "
                "share a layer")
            assert re.search(r"\b\d+ chips\b", cfg["deployment"]), (
                "the deployment does not say how many chips share a layer")
            if key == "vocab_size":
                assert chips <= MIN_VOCABULARY_SHARE, key
            if "experts" in key:
                assert held >= MIN_ROUTED_EXPERTS, key
        else:
            assert is_depth(key), (
                f"{key} is neither a depth nor a count that `reduced` "
                "may name: a width, or a key this lint does not know")


def check_configuration(entry: dict, root: Path = ROOT) -> tuple[dict, dict]:
    """What every listed configuration is held to, whatever its
    architecture: the file brings its source's shape keys verbatim under
    ``published`` and runs each of them as published, but for the keys
    the entry lists under ``reduced``, which are smaller and explained
    in the file (:func:`check_reduced`); it names an architecture that a
    file under ``benchmark/archs/`` answers to and that takes its
    shapes. Returns the file and the program's ``model`` section.

    ``published`` is copied by hand from the ``config.json`` that
    ``source`` names, and NO test verifies it against that source (there
    is no network here): a width cut in both places passes this lint.
    Only the two OPT rows are pinned, below. For any other configuration
    the reviewer of the PR that adds it compares ``published`` with the
    source."""
    assert entry["file"].startswith("benchmark/configs/")
    cfg = cell_lib.load_json(root / entry["file"])
    published = cfg["published"]
    assert published
    for key in ("source", "departures", "deployment"):
        assert cfg.get(key), key
    check_reduced(entry["reduced"], cfg)
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cfg[key] == value, key
    text = json.dumps(cfg)
    for switch in SWITCHES:
        assert f'"{switch}"' not in text, switch
    return cfg, cell_lib.load_arch(cfg, root).model_section(cfg)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configurations_carry_the_published_widths(entry):
    check_configuration(entry)


@pytest.mark.parametrize("name, widths", [
    ("opt-6.7b", (4096, 32, 16384, 32)),
    ("opt-1.3b", (2048, 32, 8192, 24))])
def test_the_opt_rows_are_the_published_ones(name, widths):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    # what is run equals what is published but for ``reduced``: the lint
    cfg, model = check_configuration(entry)
    published = cfg["published"]
    assert cfg["arch"] == "opt"
    assert (published["hidden_size"], published["num_attention_heads"],
            published["ffn_dim"], published["num_hidden_layers"]) == widths
    assert published["vocab_size"] == 50272
    assert published["max_position_embeddings"] == 2048
    assert set(entry["reduced"]) <= {"num_hidden_layers"}
    assert model["model_dim"] // model["num_heads"] in (64, 128)


def test_a_block_the_repo_cannot_run_is_refused():
    opt = cell_lib.load_arch({"arch": "opt"})
    with pytest.raises(cell_lib.BenchmarkError):
        opt.model_section({"hidden_size": 64, "ffn_dim": 100,
                           "num_attention_heads": 4,
                           "num_hidden_layers": 1, "vocab_size": 8,
                           "max_position_embeddings": 8})
    with pytest.raises(cell_lib.BenchmarkError):
        cell_lib.load_cell("no.such_cell")


def test_a_configuration_names_its_architecture():
    # no default: a file without the key is refused, as is a name that
    # no file under benchmark/archs/ answers to. That each listed file
    # names one that resolves is check_configuration's, for every entry
    with pytest.raises(cell_lib.BenchmarkError, match="arch"):
        cell_lib.load_arch({"hidden_size": 64})
    with pytest.raises(cell_lib.BenchmarkError, match="no file"):
        cell_lib.load_arch({"arch": "no_such_block"})


def knee_cells(bench: dict, root: Path = ROOT) -> list[str]:
    """The cells whose traffic offers load at a share of a swept knee."""
    return [w["name"] for w in bench["workloads"]
            if "knee_per_s" in cell_lib.load_json(
                root / "benchmark" / "traffic"
                / f"{w['traffic']}.json").get("arrivals", {})]


KNEE_CELLS = knee_cells(BENCH)


def check_knee(name: str, root: Path = ROOT) -> None:
    arrivals = cell_lib.load_cell(name, root=root).traffic["arrivals"]
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"], rel=0.05)


@pytest.mark.parametrize("name", KNEE_CELLS)
def test_the_open_cells_rate_is_a_number_below_its_knee(name):
    check_knee(name)


def check_everything(root: Path) -> None:
    """Every check above that runs over the file, a cell or a
    configuration, over the checkout at ``root``."""
    bench = cell_lib.load_json(root / "BENCHMARK.json")
    check_the_file(bench, root)
    check_cells_and_chips(bench)
    check_metrics(bench)
    for name in _cells(bench):
        check_cell(name, root)
    for entry in bench["configs"]:
        check_configuration(entry, root)
    for name in knee_cells(bench, root):
        check_knee(name, root)


# -- added by files alone ---------------------------------------------------

def _copy_of_the_benchmark(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


#: a second architecture, as a later PR would bring it: its source names
#: its sizes by other keys than OPT's, and its file maps them onto the
#: repo's block (the only one the program has; a real one writes its own
#: equations where this toy borrows OPT's)
TOY_ARCH = '''
from benchmark.lib.cell import load_arch

_opt = load_arch({"arch": "opt"})


def _shapes(c):
    return {"hidden_size": c["d_model"], "ffn_dim": c["intermediate_size"],
            "num_attention_heads": c["n_heads"],
            "num_hidden_layers": c["n_layers"],
            "vocab_size": c["vocab_size"],
            "max_position_embeddings": c["n_positions"]}


def model_section(c):
    return _opt.model_section(_shapes(c))


def logits(params, tokens, c, last=None):
    return _opt.logits(params, tokens, _shapes(c), last=last)


def loss(params, tokens, c):
    return _opt.loss(params, tokens, _shapes(c))


def train_flops_per_token(c, seq_len):
    return _opt.train_flops_per_token(_shapes(c), seq_len)


def attention_train_flops_per_token(c, seq_len):
    return _opt.attention_train_flops_per_token(_shapes(c), seq_len)


def decode_bytes_per_step(c, contexts, weight_bytes=2, kv_bytes=2):
    return _opt.decode_bytes_per_step(_shapes(c), contexts, weight_bytes,
                                      kv_bytes)
'''

TOY_CONFIG = {
    "arch": "toy_block", "source": {"config_json": "none: a test"},
    "published": {"d_model": 8, "intermediate_size": 32, "n_heads": 2,
                  "n_layers": 4, "vocab_size": 16, "n_positions": 8},
    "d_model": 8, "intermediate_size": 32, "n_heads": 2, "n_layers": 1,
    "vocab_size": 16, "n_positions": 8,
    "reduced": {"n_layers": "4 -> 1: a test"},
    "departures": ["the repo's block"], "deployment": "a test",
    "train": {"sequences_per_step_per_chip": 2,
              "optim": {"name": "momentum", "momentum": 0.9,
                        "initial_learning_rate": 0.05,
                        "learning_rate_decay_factor": 1.0}},
    "serve": {"precision": {}, "replica": {"queue_depth": 8},
              "decode": {"decode_slots": 2, "block_size": 4,
                         "num_blocks": 5, "max_prompt_len": 4,
                         "max_new_tokens": 4, "eos_token": -1}}}


#: a routed configuration as a later PR would bring it (the architecture
#: file is ``tests/benchmark/toy_routed.py``, copied in): depth cut, and
#: a chip's share of the vocabulary and of the routed experts, each with
#: the number of chips that share a layer
_ROUTED_SHAPES = toy_routed_model.toy_config(64)
TOY_ROUTED_CONFIG = {
    **_ROUTED_SHAPES, "source": {"config_json": "none"},
    "published": {**{k: v for k, v in _ROUTED_SHAPES.items()
                     if isinstance(v, int) and not isinstance(v, bool)},
                  "num_hidden_layers": 12, "vocab_size": 4096,
                  "n_routed_experts": 256},
    "reduced": {
        "num_hidden_layers": "12 -> 3: a test",
        "vocab_size": "4096 -> 512: embedding and head over 8 chips",
        "n_routed_experts": "256 -> 64: each expert layer over 4 chips"},
    "departures": ["none"],
    "deployment": "a test: 8 chips share embedding and head, 4 chips "
                  "each expert layer",
    "train": TOY_CONFIG["train"], "serve": TOY_CONFIG["serve"]}


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    # a configuration of a SECOND architecture with its architecture
    # file, a traffic mix of a NEW kind with its driver, a per-layer
    # metric with its reader, and a mix of each kind that is there:
    # eight new files; and a ROUTED architecture (its file has
    # ``routing_slack``, ``lib/cell.py``) with a configuration that
    # holds a chip's share of its vocabulary and experts: two more
    (root / "benchmark/configs/toy.json").write_text(json.dumps(TOY_CONFIG))
    (root / "benchmark/configs/toy_routed.json").write_text(
        json.dumps(TOY_ROUTED_CONFIG))
    shutil.copy(ROOT / "tests/benchmark/toy_routed.py",
                root / "benchmark/archs/toy_routed.py")
    (root / "benchmark/archs/toy_block.py").write_text(TOY_ARCH)
    (root / "benchmark/traffic/toy_echo.json").write_text(json.dumps(
        {"kind": "echo", "value": 3.5}))
    (root / "benchmark/traffic/toy_train.json").write_text(json.dumps(
        {"kind": "train", "sync": {"mode": "sync"}}))
    (root / "benchmark/traffic/toy_serve.json").write_text(json.dumps(
        {"kind": "serve_closed"}))
    (root / "benchmark/traffic/toy_open.json").write_text(json.dumps(
        {"kind": "serve_open",
         "arrivals": {"rate_per_s": 4.0, "knee_per_s": 5.0}}))
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
    toy_entry = {"name": "toy", "source": "none",
                 "file": "benchmark/configs/toy.json",
                 "reduced": ["n_layers"], "why": "test"}
    routed_entry = {"name": "toy_routed", "source": "none",
                    "file": "benchmark/configs/toy_routed.json",
                    "reduced": ["num_hidden_layers", "vocab_size",
                                "n_routed_experts"], "why": "test"}
    bench["configs"] += [toy_entry, routed_entry]
    bench["workloads"] += [
        {"name": f"toy.{traffic}", "config": "toy",
         "traffic": f"toy_{traffic}", "chips": 1, "why": "test"}
        for traffic in ("echo", "train", "serve", "open")] + [
        {"name": f"toy_routed.{traffic}", "config": "toy_routed",
         "traffic": f"toy_{traffic}", "chips": 1, "why": "test"}
        for traffic in ("train", "serve")]
    # each new cell of a kind that is there reports what a cell of that
    # kind reports: its name joins those metrics' lists of cells
    for metric in bench["end_to_end"] + bench["per_layer"]:
        metric.get("workloads", []).extend(
            toy for like, toy in (
                ("opt-6.7b.train_sync_1chip", "toy.train"),
                ("opt-1.3b.serve_decode_closed", "toy.serve"),
                ("opt-1.3b.serve_chat_open", "toy.open"),
                ("opt-6.7b.train_sync_1chip", "toy_routed.train"),
                ("opt-1.3b.serve_decode_closed", "toy_routed.serve"))
            if like in metric.get("workloads", []))
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

    # the copy, with what was added, passes EVERY check this file runs
    # over the file, a cell or a configuration: no check names what
    # only the tree as it stands has. The new configuration passes the
    # lint the listed ones pass by its own architecture file: OPT's
    # would not find its keys
    check_everything(root)
    cfg, model = check_configuration(toy_entry, root)
    assert model["model_dim"] == 8 and model["num_layers"] == 1
    with pytest.raises(KeyError):
        cell_lib.load_arch({"arch": "opt"}, root).model_section(cfg)
    # the routed one too, by the file copied in: the drivers will follow
    # its program's expert choices, OPT's cells are compared as before
    _, routed_model = check_configuration(routed_entry, root)
    assert routed_model["num_experts"] == 64
    routed = [cell_lib.load_cell(f"toy_routed.{t}", root=root)
              for t in ("train", "serve")]
    assert all(compare.is_routed(c.arch) for c in routed)
    assert not compare.is_routed(
        cell_lib.load_cell(CELLS[0], root=root).arch)

    cell = cell_lib.load_cell("toy.echo", root=root)
    assert cell.arch.__file__ == str(root / "benchmark/archs/toy_block.py")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "echo_rate"]
    # metrics without a list of cells reach the new cell too
    assert {"compile_or_load_s", "echoes_per_op"} <= {
        m["name"] for m in cell.per_layer}
    from bench_toy import ToyRuntime
    rt = ToyRuntime(cell, 0, 1.0, False, 0.0,
                    {"platform": "cpu", "kind": "toy", "count": 1},
                    {"bf16_flops_per_s": 1.0}, work_root=tmp_path / "work")
    result = run_mod.measure(cell, rt, root=root)
    assert result["metrics"]["echo_rate"] == {"value": 3.5, "unit": "1/s"}
    # the new reader and one that was there, on a trace made for them
    # (a later PR's metric for every cell wants a trace of its own)
    known = dataclasses.replace(cell, per_layer=tuple(
        m for m in cell.per_layer
        if m["name"] in ("compile_or_load_s", "echoes_per_op")))
    layer = run_mod.per_layer_metrics(
        known, {"device_ops": [["a", 1.0], ["b", 1.0]]},
        {"echoes": 2, "setup_compile_s": 0.25}, root=root)
    assert layer == {"compile_or_load_s": {"value": 0.25, "unit": "s"},
                     "echoes_per_op": {"value": 1.0, "unit": "1"}}

    # both drivers that are there build the program's configuration
    # from it, and the program takes it
    from distributedmnist_tpu.core.config import ExperimentConfig
    train = cell_lib.load_cell("toy.train", root=root)
    serve = cell_lib.load_cell("toy.serve", root=root)
    for experiment in (
            cell_lib.load_driver(train.kind, root).experiment(train, rt),
            serving.experiment(serve, rt)):
        assert experiment["model"] == {**model, "init_seed": 0}
        assert ExperimentConfig.from_dict(experiment).model.model_dim == 8
    for experiment in (
            cell_lib.load_driver("train", root).experiment(routed[0], rt),
            serving.experiment(routed[1], rt)):
        assert experiment["model"] == {**routed_model, "init_seed": 0}
        assert ExperimentConfig.from_dict(
            experiment).model.moe_router_top_k == 4

    # and not one file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())
    # the cells that were there still resolve
    assert cell_lib.load_cell(CELLS[0], root=root).name == CELLS[0]


def test_a_seventh_cell_and_a_new_last_metric_are_entries_alone(tmp_path):
    """What a ``model_config`` or ``perf_opt`` PR does to the file as it
    stands: one more cell (here an accepted configuration under an
    accepted traffic file, a pair no cell has), the cell appended to
    the ``workloads`` of the end-to-end metric it reports, and one
    per-layer entry appended AFTER the last, with its reader as a new
    file. Nothing that was there is edited or moved, every check of
    this file holds on the copy as on the tree, and the cell resolves."""
    root = _copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    bench = cell_lib.load_json(root / "BENCHMARK.json")
    was = json.loads(json.dumps(bench))
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    config, traffic = next(
        (c["name"], t.stem) for c in bench["configs"]
        for t in sorted((root / "benchmark/traffic").glob("serve_*.json"))
        if "serve" in cell_lib.load_json(root / c["file"])
        and (c["name"], t.stem) not in pairs)
    name = f"{config}.{traffic}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "a rehearsal"})
    gap = next(m for m in bench["end_to_end"] if m["name"] == "itl_ms_p90")
    gap["workloads"].append(name)
    (root / "benchmark/layer_metrics/rehearsed_steps.py").write_text(
        "def read(trace, counters):\n"
        "    return float(counters['decode_steps'])\n")
    bench["per_layer"].append({
        "name": "rehearsed_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "decode_loop",
        "moves": "itl_ms_p90", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    check_everything(root)
    check_everything(ROOT)
    cell = cell_lib.load_cell(name, root=root)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        config, traffic, 1)
    assert [m["name"] for m in cell.end_to_end] == ["itl_ms_p90", "setup_s"]
    # its own metric, and those that list no cells and so reach every one
    listed = [m["name"] for m in cell.per_layer]
    assert listed[-1] == "rehearsed_steps"
    assert set(listed[:-1]) == {m["name"] for m in was["per_layer"]
                                if "workloads" not in m}
    assert run_mod.per_layer_metrics(
        dataclasses.replace(cell, per_layer=cell.per_layer[-1:]), {},
        {"decode_steps": 3}, root=root) == {
            "rehearsed_steps": {"value": 3.0, "unit": "steps"}}
    # appended: every list begins with what it held, entry for entry
    # (the driver compares the accepted entries by position); of the
    # end-to-end metrics one list of cells grew by the new cell
    after = cell_lib.load_json(root / "BENCHMARK.json")
    for key in ("configs", "workloads", "per_layer"):
        assert after[key][:len(was[key])] == was[key]
    assert (len(after["workloads"]), len(after["per_layer"])) == (
        len(was["workloads"]) + 1, len(was["per_layer"]) + 1)
    assert gap["workloads"].pop() == name
    assert bench["end_to_end"] == was["end_to_end"]
    # every cell that was there reports what it reported
    for cell_name in _cells(was):
        there = cell_lib.load_cell(cell_name, root=root)
        here = cell_lib.load_cell(cell_name)
        for mine in ("end_to_end", "per_layer"):
            assert [m["name"] for m in getattr(there, mine)] == [
                m["name"] for m in getattr(here, mine)]
    assert all(p.read_bytes() == data for p, data in before.items())


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
