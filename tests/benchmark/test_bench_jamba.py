"""The configuration ``ai21-jamba2-3b`` and its cell: the file is the
catalog's row with nothing reduced, 3.03 B parameters by its own count;
the cell resolves and runs end to end at a toy size through the serving
driver (the replica's own stores, its session in the check); the four
faults of a state that is a sequence's are refused by the check (the
nearest lower precision reported); the widest decode step and the longest
prefill compile for a described v5e under a stated ceiling; each new
reader reads a recorded trace; the step's roofline counts the state both
ways."""

import dataclasses
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench_toy import TOY_TRAFFIC, toy_cell, toy_runtime  # noqa: F401
from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, program_trace, serving,
                           ssm_controls, ssm_scopes, trace_reduce)
from distributedmnist_tpu.core.config import (DecodeConfig, ExperimentConfig,
                                              ModelConfig,
                                              effective_model_config)
from distributedmnist_tpu.models.registry import get_model

from test_bench_contract import BENCH, check_configuration
from test_bench_rehearsal import (GB, HBM_USABLE, _topology, _total,
                                  for_the_chip)  # noqa: F401

CELL = "ai21-jamba2-3b.serve_reason_long_closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
DATA = Path(__file__).parent / "data"

TOY = {
    "arch": "jamba", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 4, "vocab_size": 512,
    "intermediate_size": 96, "mamba_d_state": 8, "mamba_expand": 2,
    "mamba_d_conv": 4, "mamba_dt_rank": 6, "attn_layer_period": 4,
    "attn_layer_offset": 1, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": True, "num_experts": 1, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "sliding_window": None,
    "assumed": {"seq_len": 128},
    "model_assumed": {"compute_dtype": "float32"},
    "serve": {"precision": {}, "replica": {"queue_depth": 64},
              "decode": {"decode_slots": 4, "block_size": 16,
                         "num_blocks": 33, "max_prompt_len": 64,
                         "max_new_tokens": 32, "eos_token": -1}}}


# -- the configuration's file ------------------------------------------------

def test_the_entry_is_the_catalogs_row_with_nothing_reduced():
    entry = next(c for c in BENCH["configs"] if c["name"] == "ai21-jamba2-3b")
    cfg, model = check_configuration(entry)
    assert entry["reduced"] == [] and cfg["reduced"] == {}
    assert cfg["arch"] == "jamba"
    assert (model["model_dim"], model["num_heads"], model["kv_heads"],
            model["num_layers"], model["ffn_dim"], model["vocab_size"]) == (
                2560, 20, 1, 28, 8192, 65536)
    assert (model["ssm_state_dim"], model["ssm_expand"], model["ssm_conv"],
            model["ssm_dt_rank"], model["attn_layer_period"],
            model["attn_layer_offset"], model["norm_eps"]) == (
                16, 2, 4, 160, 14, 7, 1e-6)
    assert {"layer_order", "state_dtype", "seq_len", "init",
            "decode.decode_slots", "decode.block_size",
            "decode.num_blocks"} <= set(cfg["assumed"])
    assert "whole model on one chip" in cfg["deployment"]
    d = cfg["serve"]["decode"]
    assert (d["decode_slots"], d["block_size"], d["max_prompt_len"],
            d["max_new_tokens"]) == (128, 128, 2048, 4096)
    assert d["num_blocks"] == (d["decode_slots"]
                               * (d["max_prompt_len"] + d["max_new_tokens"])
                               // d["block_size"] + 1)
    # a page's DMA is at least 32 KB: block_size tokens of one head's 128
    assert d["block_size"] * 128 * 2 >= 32 * 1024
    assert cfg["serve"]["replica"]["queue_depth"] == 512
    assert cfg["assumed"]["seq_len"] == 2048 + 4096
    if not CATALOG.exists():
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "AI21-Jamba2-3B")
    assert cfg["published"] == row["config"]
    assert entry["source"] == row["source_url"]


def test_the_counts_are_the_issues():
    cell = cell_lib.load_cell(CELL)
    arch, c = cell.arch, cell.config
    assert arch.layer_counts(c) == (2, 26)
    assert [i for i in range(28) if arch.attends(c, i)] == [7, 21]
    assert arch.mamba_mixer_params(c) == 41_241_792          # 41.24 M
    assert arch.unit_params(c) == 62_914_560                 # 62.91 M
    assert arch.attention_params(c) == 13_762_560            # 13.76 M
    assert arch.param_count(c) == 3_029_337_472              # 3.03 B
    # by the program's own tree, shapes only
    model = get_model(ModelConfig(**arch.model_section(c)))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == arch.param_count(c)
    assert model.decode_cache_shape == (2, 1, 128)
    assert model.decode_state_shape == (26, 16, 5120, 3)
    # a sequence's state: 26 x (327,680 + 30,720) B
    assert arch.state_bytes_per_step(c, 1) == 2 * 26 * (327_680 + 30_720)
    # the step's roofline counts the state both ways, the weights once,
    # a cached token's 2 layers x 2 x 128 x 2 B
    idle = arch.decode_bytes_per_step(c, [])
    assert idle == pytest.approx(2 * (arch.param_count(c) - 57 * 2560),
                                 rel=1e-6)
    one = arch.decode_bytes_per_step(c, [1000]) - idle
    assert one == 2560 * 2 + 1000 * 1024 + arch.state_bytes_per_step(c, 1)
    full = arch.decode_bytes_per_step(c, [3000] * 128)
    assert full / 1e9 == pytest.approx(6.06 + 2.39 + 0.39, abs=0.02)


def test_the_cell_resolves_with_the_issues_traffic():
    cell = cell_lib.load_cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed"
    t = cell.traffic
    assert (t["clients_per_slot"], t["requests_per_client"], t["warmup_s"],
            t["stagger_first_wave"], t["deadline_ms"]) == (
                2, 4, 20, True, 600000)
    assert isinstance(t["sizes_seed"], int)
    assert (t["prompt_len"], t["max_tokens"]) == (
        {"dist": "loguniform", "lo": 512, "hi": 2048},
        {"dist": "loguniform", "lo": 2048, "hi": 4096})
    ends = {m["name"] for m in cell.end_to_end}
    assert {"itl_ms_p90", "setup_s"} <= ends <= {
        "itl_ms_p90", "setup_s", "serve_tokens_per_s"}
    layers = {m["name"] for m in cell.per_layer}
    assert set(ssm_scopes.READERS) <= layers
    assert {"decode_step_roofline", "decode_step_device_ms",
            "decode_iter_ms_p50", "decode_attention_ms_per_step",
            "prefill_ms_p50", "decode_slots_live_p50",
            "serve_device_idle_share", "weights_ready_s", "itl_ms_p50",
            "itl_ms_p99", "loadgen_late_ms_p99",
            "compile_or_load_s"} <= layers
    # an accepted test holds the lists of PR 40's eleven readers (the
    # gap's split, the kernel's and the write's time) to the three cells
    # PR 42 listed, and one holds `decode_table_blocks_p50` whole:
    # `python3 benchmark/lib/host_gaps.py` prints the eleven for this cell
    assert "decode_table_blocks_p50" not in layers
    assert "decode_gap_ms_p90" not in layers
    # the four new entries come after everything PR 42 had, in the order
    # this PR appended them
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-4:] == list(ssm_scopes.READERS) or set(
        ssm_scopes.READERS) <= set(names)
    for name in ssm_scopes.READERS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["moves"] == "itl_ms_p90" and CELL in entry["workloads"]


def test_a_program_without_the_mechanism_is_refused_cleanly(monkeypatch):
    """What the parent commit answers, given this PR's files."""
    cell = cell_lib.load_cell(CELL)
    monkeypatch.setattr(cell.arch, "_program_model_keys",
                        lambda: {"name", "model_dim", "num_heads",
                                 "num_layers", "seq_len", "vocab_size",
                                 "ffn_dim", "norm_eps"})
    with pytest.raises(cell_lib.BenchmarkError, match="ssm_state_dim"):
        cell.arch.model_section(cell.config)
    with pytest.raises(cell_lib.BenchmarkError, match="something else"):
        cell.arch.model_section({**cell.config, "num_experts": 16})


# -- the cell's path at a toy size -------------------------------------------

def _toy_cell():
    cell = toy_cell("serve_closed", config=TOY,
                    arch=cell_lib.load_arch(TOY))
    return dataclasses.replace(
        cell, per_layer=tuple(m for m in cell_lib.load_cell(CELL).per_layer))


def test_the_driver_runs_the_hybrid_end_to_end_at_a_toy_size(
        toy_runtime, capsys):  # noqa: F811
    cell = _toy_cell()
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.5))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    events = {e["event"]: e for e in map(json.loads, (
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")))}
    check = events["reference_check"]
    assert check["ok"] and check["decode_logits_max_rel_err"] < 1e-4
    # the record's own session over the replica's own stores
    assert check["session"]["session"] == "slot_state"
    assert check["session"]["state_arrays"] == [[4, 8, 128], [3, 4, 128]]
    assert check["session"]["state_layers"] == 3
    assert all(events["serve_window"]["checks"].values())
    assert events["serve_window"]["compiles_in_window"] == 0


def _toy_check(control: str, seed: int, dtype: str = "bfloat16") -> dict:
    cell = _toy_cell()
    cfg = ExperimentConfig.from_dict(serving.experiment(
        dataclasses.replace(cell, config={
            **TOY, "model_assumed": {"compute_dtype": dtype,
                                     "attention_impl": "dense"}}),
        types.SimpleNamespace(seed=seed, workdir=cell_lib.ROOT)))
    model_cfg = effective_model_config(cfg, serving=True)
    params = get_model(model_cfg).init(jax.random.PRNGKey(seed))
    # the program starts every matrix at 0.02, which is the inverse root
    # of the PUBLISHED width (2560^-1/2 = 0.0198); at this toy's 64 a
    # mixer would add a fortieth of what it adds there and no fault of it
    # could show. Weights are data: the toy's matrices at the inverse
    # root of ITS width
    up = (2560 / TOY["hidden_size"]) ** 0.5
    params["blocks"] = [
        {k: v * up if k.startswith("w") and k != "w_dt" else v
         for k, v in blk.items()} for blk in params["blocks"]]
    return ssm_controls.check_control(control, model_cfg, params, cfg.decode,
                                      cell, seed, get_model)


@pytest.mark.parametrize("control", ["state_not_advanced",
                                     "conv_tail_dropped",
                                     "stale_slot_state"])
def test_a_fault_of_the_slot_state_is_refused(control):
    sound = _toy_check("sound", 5)
    assert sound["ok"] and sound["failed_by"] == []
    assert sound["session"]["session"] == "slot_state"
    assert sound["decode_logits_max_rel_err"] < serving.DECODE_LOGITS_TOL / 3
    row = _toy_check(control, 5)
    assert not row["ok"]
    assert row["failed_by"] == ["decode_logits_max_rel_err"]
    assert row["decode_logits_max_rel_err"] > 2 * serving.DECODE_LOGITS_TOL


def test_the_state_in_the_nearest_lower_precision_is_reported():
    """Whichever way it falls at the cell's size; at this toy's, a
    float32 program with a bfloat16 state leaves float32's error far
    behind."""
    sound = _toy_check("sound", 6, "float32")
    row = _toy_check("bfloat16_state", 6, "float32")
    assert row["session"]["state_arrays"][0] == [4, 8, 128]
    assert sound["decode_logits_max_rel_err"] < 1e-5
    assert row["decode_logits_max_rel_err"] > 20 * max(
        sound["decode_logits_max_rel_err"], 1e-6)


# -- compiled for a described v5e --------------------------------------------

def _abstract(cell, dev):
    model = get_model(ModelConfig(**{**cell.arch.model_section(cell.config),
                                     "compute_dtype": "bfloat16"}))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=dev)
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    return model, params, sds


def test_the_widest_step_and_the_longest_prefill_compile_for_the_v5e(
        for_the_chip, monkeypatch):  # noqa: F811
    import functools
    import re
    dev = SingleDeviceSharding(_topology("v5e:1x1").devices[0])
    # the arm a TPU's step takes (the program asks jax.devices())
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu")])
    cell = cell_lib.load_cell(CELL)
    model, params, sds = _abstract(cell, dev)
    d = DecodeConfig(**cell.config["serve"]["decode"])
    slots, width = d.decode_slots, d.max_blocks_per_seq()
    assert width == 48
    layers, n, e, taps = model.decode_state_shape
    cache = sds((2, d.num_blocks, d.block_size, 1, 128), jnp.bfloat16)
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=d.block_size),
                   donate_argnums=(3, 4, 7, 8))
    compiled = step.lower(
        params, sds((slots,), jnp.int32), sds((slots,), jnp.int32), cache,
        cache, sds((slots, width), jnp.int32), sds((slots,), jnp.int32),
        (sds((slots, n, e), jnp.float32),) * layers,
        (sds((taps, slots, e), jnp.bfloat16),) * layers).compile()
    # at PR 43: 8.13 GB: 8.06 of arguments (6.06 weights, 0.81 rows, 1.19
    # state), 0.03 of logits and 0.03 of temporaries; the issue's ceiling
    # for 128 slots is 15.5
    print(f"decode step: {_total(compiled):.0f} bytes")
    assert _total(compiled) / GB <= 8.4
    assert _total(compiled) + 1 * GB < HBM_USABLE
    m = compiled.memory_analysis()
    # the cache and both state arrays are written where they lie
    assert m.alias_size_in_bytes / GB == pytest.approx(0.81 + 1.19, abs=0.02)
    assert m.temp_size_in_bytes / GB < 0.2
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    assert len(re.findall(r" while\(", text)) == 0

    prefill = jax.jit(model.decode_prefill).lower(
        params, sds((1, d.max_prompt_len), jnp.int32),
        sds((1,), jnp.int32)).compile()
    pm = prefill.memory_analysis()
    # one position's logits, not a bucket's: 0.5 GB less
    assert pm.output_size_in_bytes / GB < 0.05
    # 0.71 GB at PR 43 (the flash call's copies of the one key-value head,
    # a chunk's two [64, 16, 5120] float32 arrays)
    assert pm.temp_size_in_bytes / GB < 0.9
    # beside a replica's stores: weights, rows, state, the step's logits
    assert (_total(compiled) + pm.temp_size_in_bytes
            + pm.output_size_in_bytes) < HBM_USABLE - 2 * GB


# -- the readers --------------------------------------------------------------

READ = lambda m, c=None: cell_lib.load_reader(m).read({}, c or {})  # noqa: E731


def test_the_scope_readers_read_the_decode_steps_table(monkeypatch):
    table = {"by_scope": {
        ("attention", "forward"): 0.5, ("attention/cache_write", "forward"): 0.1,
        ("ssm", "forward"): 3.0, ("ssm/ssm_conv", "forward"): 0.25,
        ("ssm/state_update", "forward"): 4.0, ("ssm", "unnamed"): 0.5,
        ("ffn", "forward"): 5.0, ("head", "forward"): 0.5}}
    run = {"trace": {}, "workdir": Path("/nowhere") / CELL}
    monkeypatch.setattr(program_trace, "this_run", lambda reduced: run)
    monkeypatch.setattr(program_trace, "executions",
                        lambda trace, program: ([("x", 0, 1)], {1}))
    monkeypatch.setattr(program_trace, "scope_table",
                        lambda trace, program: table)
    assert READ("decode_ssm_ms_per_step") == 7.75
    assert READ("decode_state_update_ms_per_step") == 4.0
    # 128 live slots, the state alone: 2 x 128 x 26 x 327,680 B = 2.181 GB
    # at 819 GB/s is 2.663 ms; over 4 ms
    monkeypatch.setattr(program_trace, "spans_by_thread", lambda trace: {
        "loop": [(program_trace.SPAN_DISPATCH, 0, 1, 0, {"live": 128})] * 3})
    share = READ("decode_state_update_roofline",
                 {"peak_hbm_bytes_per_s": 819e9})
    assert share == pytest.approx(100 * 2.6631 / 4.0, rel=1e-3)
    assert share < 100
    # a program with no such layer opens no such scope: nothing, no error
    plain = {"by_scope": {("attention", "forward"): 3.0,
                          ("ffn", "forward"): 1.0}}
    monkeypatch.setattr(program_trace, "scope_table",
                        lambda trace, program: plain)
    for name in ssm_scopes.READERS[:3]:
        assert READ(name, {"peak_hbm_bytes_per_s": 819e9}) is None
    # nor a run with no execution of the step
    monkeypatch.setattr(program_trace, "executions",
                        lambda trace, program: ([], set()))
    for name in ssm_scopes.READERS:
        assert READ(name, {"peak_hbm_bytes_per_s": 819e9}) is None
    was = program_trace.SCOPES
    with ssm_scopes._also(ssm_scopes.SSM_SCOPES):
        assert program_trace.scope_path(
            "jit(decode_step)/ssm/state_update/mul") == ("ssm",
                                                         "state_update")
    assert program_trace.SCOPES == was


def test_the_readers_on_a_recorded_trace(monkeypatch):
    """A trace of this PR's own chip run of the cell from the committed
    files alone (seed 2147481888), cut to a few decode steps around one
    prefill. The decode steps are whole: their scopes read what the run's
    own readers read (13.9 ms a step, the state's update 3.26 at 81.6%).
    The prefill's ~10^5 operations (a loop over tokens a layer) are folded
    into one operation a distinct ``op_name``, laid end to end, each as
    long as the self times it stands for: every scope's time is what it
    was (this prompt's scan 118.6 ms; the run's median over seven
    prefills was 72.1), the order inside the prefill is not kept."""
    path = DATA / "v5e_jamba_steps_and_prefills.json.gz"
    trace = program_trace.load(str(path))
    run = {"trace": trace, "workdir": Path("/nowhere") / CELL}
    monkeypatch.setattr(program_trace, "this_run", lambda reduced: run)
    assert len(program_trace.executions(
        trace, program_trace.DECODE_STEP)[0]) >= 2
    assert len(program_trace.executions(trace, ssm_scopes.PREFILL)[0]) >= 1
    ssm = READ("decode_ssm_ms_per_step")
    update = READ("decode_state_update_ms_per_step")
    assert 6.0 < ssm < 7.5 and 3.0 < update < 3.6
    share = READ("decode_state_update_roofline",
                 {"peak_hbm_bytes_per_s": 819e9})
    assert 70 < share < 90
    assert READ("prefill_scan_ms_p50") == pytest.approx(118.58, abs=0.1)
