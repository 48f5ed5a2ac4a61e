"""The configuration ``ling-3.0-flash`` and its cell: the file is the
catalog's row but for the five keys ``reduced`` names, 3.26 B parameters
by its own count and by the program's tree; the cell resolves and runs end
to end at a toy size through the serving driver (the replica's own stores,
its session in the routed check); the six faults of
``lib/kda_controls.py`` are refused by the check at a small size; the
widest decode step and the longest prefill compile for a described v5e
under a stated ceiling; each new reader reads the decode step's scope
table (no recorded trace of the cell was cut in PR 45: no chip was free
for it; PERF.md §7); a program without the mechanism refuses the cell
cleanly."""

import copy
import dataclasses
import functools
import json
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench_toy import TOY_TRAFFIC, toy_cell, toy_runtime  # noqa: F401
from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, kda_controls, kda_scopes,
                           program_trace, serving)
from distributedmnist_tpu.core.config import (DecodeConfig, ExperimentConfig,
                                              ModelConfig,
                                              effective_model_config)
from distributedmnist_tpu.models.registry import get_model

from test_bench_contract import BENCH, check_configuration
from test_bench_rehearsal import (GB, HBM_USABLE, _topology, _total,
                                  for_the_chip)  # noqa: F401

CELL = "ling-3.0-flash.serve_reason_long_closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size", "num_nextn_predict_layers"]


def small_config(**over) -> dict:
    """The configuration file with every width cut to a toy's: 4 heads of
    16 (KDA's state 16 x 16 a head), a 32-wide latent with 8 rotated
    columns, 4 of 32 experts held (half of group 0 of 8), 4 a token from
    4 groups, 7 layers in periods of 3 (latent attention at 2 and 5)."""
    c = copy.deepcopy(cell_lib.load_cell(CELL).config)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
             num_hidden_layers=7, layer_group_size=3,
             first_k_dense_replace=1, num_experts=4, num_experts_per_tok=4,
             vocab_size=512)
    c["published"] = dict(c["published"], num_experts=32)
    c["assumed"] = dict(c["assumed"], seq_len=128)
    c["model_assumed"] = {"compute_dtype": "float32"}
    c["serve"] = {"precision": {}, "replica": {"queue_depth": 64},
                  "decode": {"decode_slots": 4, "block_size": 16,
                             "num_blocks": 33, "max_prompt_len": 64,
                             "max_new_tokens": 32, "eos_token": -1}}
    c.update(over)
    return c


# -- the configuration's file ------------------------------------------------

def test_the_entry_is_the_catalogs_row_but_for_the_five_reduced_keys():
    entry = next(c for c in BENCH["configs"] if c["name"] == "ling-3.0-flash")
    cfg, model = check_configuration(entry)
    assert entry["reduced"] == REDUCED and list(cfg["reduced"]) == REDUCED
    assert cfg["arch"] == "bailing_hybrid"
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (13, 1, 32, 19648, 0)
    assert (model["model_dim"], model["num_heads"], model["num_layers"],
            model["ffn_dim"], model["vocab_size"]) == (
                2560, 32, 13, 6144, 19648)
    assert (model["kda_head_dim"], model["kda_conv"],
            model["kda_lower_bound"], model["attn_layer_period"],
            model["attn_layer_offset"]) == (128, 4, -5.0, 6, 5)
    assert (model["q_latent_dim"], model["kv_latent_dim"],
            model["qk_nope_dim"], model["qk_rope_dim"], model["v_head_dim"],
            model["rope_theta"], model["attn_head_gate"]) == (
                0, 512, 128, 64, 128, 6e6, True)
    assert (model["routed_experts"], model["held_experts"],
            model["first_held_expert"], model["experts_per_token"],
            model["shared_experts"], model["expert_ffn_dim"],
            model["routed_scaling"], model["router_groups"],
            model["router_topk_groups"], model["dense_layers"]) == (
                512, 32, 0, 8, 1, 768, 2.5, 8, 4, 1)
    assert {"layer_order", "kda_gate", "kda_output_gate", "state_dtype",
            "seq_len", "init", "decode.decode_slots", "decode.block_size",
            "decode.num_blocks", "serve.queue_depth"} <= set(cfg["assumed"])
    assert "16 chips" in cfg["deployment"]
    d = cfg["serve"]["decode"]
    assert (d["decode_slots"], d["block_size"], d["max_prompt_len"],
            d["max_new_tokens"]) == (128, 128, 2048, 4096)
    assert d["num_blocks"] == (d["decode_slots"]
                               * (d["max_prompt_len"] + d["max_new_tokens"])
                               // d["block_size"] + 1)
    assert cfg["serve"]["replica"]["queue_depth"] == 512
    assert cfg["assumed"]["seq_len"] == 2048 + 4096
    # nothing of the clamp in a layer the cut keeps
    assert not any(cfg["expert_swiglu_limit_list"][:13])
    assert not any(cfg["share_expert_swiglu_limit_list"][:13])
    if not CATALOG.exists():
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Ling-3.0-flash")
    assert cfg["published"] == row["config"]
    assert entry["source"] == row["source_url"]


def test_the_counts_are_the_issues():
    cell = cell_lib.load_cell(CELL)
    arch, c = cell.arch, cell.config
    assert arch.layer_counts(c) == (2, 11)
    assert [i for i in range(13) if arch.attends(c, i)] == [5, 11]
    assert arch.kda_params(c) == 63_049_888                  # 63.0 M
    assert arch.latent_params(c) == 31_965_696               # 32.0 M
    assert arch.unit_params(c) == 5_898_240                  # 5.90 M
    assert arch.param_count(c) == 3_256_770_784              # 3.26 B
    # by the program's own tree, shapes only
    model = get_model(ModelConfig(**arch.model_section(c)))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == arch.param_count(c)
    assert model.decode_cache_shape == (2, 1, (512, 64))
    assert model.decode_state_shape == (11, (32, 128), 128, 3, 12288)
    assert model.decode_counts and hasattr(model, "decode_session")
    # a sequence's state: 11 x 32 x 128 x 128 x 4 B, both ways
    assert arch.kda_state_bytes_per_step(c, 1) == 2 * 11 * 2_097_152
    assert arch.kda_state_bytes_per_step(c, 128) / 1e9 == pytest.approx(
        5.906, abs=1e-3)
    one = (arch.decode_bytes_per_step(c, [1000])
           - arch.decode_bytes_per_step(c, []))
    touched = arch.expected_experts_touched(c, 1)
    assert touched == pytest.approx(32 * 8 / 512)
    assert one == pytest.approx(
        2560 * 2 + 1000 * 2 * 576 * 2 + arch.kda_state_bytes_per_step(c, 1)
        + 2 * 11 * 3 * 12288 * 2 + 12 * touched * 5_898_240 * 2)
    full = arch.decode_bytes_per_step(c, [3000] * 128)
    assert 12.0 < full / 1e9 < 13.5


def test_the_cell_resolves_with_the_issues_traffic():
    cell = cell_lib.load_cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed"
    jamba = cell_lib.load_cell("ai21-jamba2-3b.serve_reason_long_closed")
    assert cell.traffic == jamba.traffic        # one load, two states
    ends = {m["name"] for m in cell.end_to_end}
    assert {"itl_ms_p90", "setup_s"} <= ends <= {
        "itl_ms_p90", "setup_s", "serve_tokens_per_s"}
    layers = {m["name"] for m in cell.per_layer}
    assert set(kda_scopes.READERS) <= layers
    assert {"decode_step_roofline", "decode_step_device_ms",
            "decode_iter_ms_p50", "decode_attention_ms_per_step",
            "decode_absorb_ms_per_step", "decode_moe_ms_per_step",
            "decode_experts_touched_p50",
            "decode_pairs_per_touched_expert_p50", "prefill_ms_p50",
            "decode_slots_live_p50", "serve_device_idle_share",
            "weights_ready_s", "itl_ms_p50", "itl_ms_p99",
            "loadgen_late_ms_p99", "compile_or_load_s"} <= layers
    # accepted tests pin these lists to the cells PR 42 listed
    assert "decode_table_blocks_p50" not in layers
    assert "decode_gap_ms_p90" not in layers
    # a state-space layer's readers are not this cell's
    assert "decode_ssm_ms_per_step" not in layers
    for name in kda_scopes.READERS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["moves"] == "itl_ms_p90" and entry["workloads"] == [CELL]
        assert "ms" in entry["unit"] or entry["unit"] == "%"


def test_a_program_without_the_mechanism_is_refused_cleanly(monkeypatch):
    """What the parent commit answers, given this PR's files."""
    cell = cell_lib.load_cell(CELL)
    monkeypatch.setattr(cell.arch, "_program_model_keys", lambda: {
        f.name for f in dataclasses.fields(ModelConfig)} - {
            "kda_head_dim", "kda_conv", "kda_lower_bound", "attn_head_gate",
            "router_groups", "router_topk_groups"})
    with pytest.raises(cell_lib.BenchmarkError, match="kda_head_dim"):
        cell.arch.model_section(cell.config)


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("tie_word_embeddings", True),
    ("num_nextn_predict_layers", 1), ("topk_method", "greedy"),
    ("num_kv_heads_for_linear_attn", 8), ("kda_safe_gate", False),
    ("gated_attention_proj_granularity_type", "elementwise"),
    ("num_hidden_layers", 36)])
def test_a_configuration_the_program_cannot_serve_is_refused(key, value):
    """The last: a cut that keeps a layer whose gated unit is clamped."""
    cell = cell_lib.load_cell(CELL)
    with pytest.raises(cell_lib.BenchmarkError, match="something else"):
        cell.arch.model_section({**cell.config, key: value})


# -- the cell's path at a toy size -------------------------------------------

def _toy_cell(config=None):
    config = config or small_config()
    cell = toy_cell("serve_closed", config=config,
                    arch=cell_lib.load_arch(config))
    return dataclasses.replace(
        cell, per_layer=tuple(m for m in cell_lib.load_cell(CELL).per_layer))


def test_the_driver_runs_the_cell_end_to_end_at_a_toy_size(
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
    assert check["routing_ok"] and check["routing_flag_diff"] == 0.0
    assert check["routing_agreement"] > 0.99
    # the record's own session over the replica's own three stores
    assert check["session"]["session"] == "slot_state"
    assert check["session"]["state_arrays"] == [[4, 4, 16, 16], [3, 4, 192]]
    assert check["session"]["cache_arrays"] == [[2, 33, 16, 32],
                                                [2, 33, 16, 8]]
    assert check["session"]["state_layers"] == 5
    assert all(events["serve_window"]["checks"].values())
    assert events["serve_window"]["compiles_in_window"] == 0
    assert set(result["compared"]) >= {
        "decode_logits_max_rel_err", "routing_slack_max",
        "routing_agreement", "routing_flag_diff"}


def _toy_check(control: str, seed: int, dtype: str = "bfloat16") -> dict:
    config = small_config(model_assumed={"compute_dtype": dtype,
                                         "attention_impl": "dense"})
    cell = _toy_cell(config)
    cfg = ExperimentConfig.from_dict(serving.experiment(
        cell, types.SimpleNamespace(seed=seed, workdir=cell_lib.ROOT)))
    model_cfg = effective_model_config(cfg, serving=True)
    params = get_model(model_cfg).init(jax.random.PRNGKey(seed))
    # the program starts every matrix at 0.02, which is the inverse root
    # of the PUBLISHED width (2560^-1/2 = 0.0198); at this toy's 64 a
    # sublayer would add a fortieth of what it adds there and no fault of
    # it could show. Weights are data: the toy's matrices at the inverse
    # root of ITS width (the embedding and the convolutions as they are)
    up = (2560 / config["hidden_size"]) ** 0.5
    params["blocks"] = jax.tree_util.tree_map_with_path(
        lambda path, a: a * up if a.ndim >= 2 and getattr(
            path[-1], "key", None) != "conv_w" else a, params["blocks"])
    return kda_controls.check_control(control, model_cfg, params, cfg.decode,
                                      cell, seed, get_model)


@pytest.fixture(scope="module")
def sound():
    return _toy_check("sound", 5)


def test_the_sound_program_passes_the_routed_check(sound):
    assert sound["ok"] and sound["failed_by"] == []
    assert sound["session"]["session"] == "slot_state"
    assert sound["decode_logits_max_rel_err"] < serving.DECODE_LOGITS_TOL
    assert sound["routing_flag_diff"] == 0.0
    assert set(kda_controls._controls()) == set(kda_controls.CONTROLS)


@pytest.mark.parametrize("control, by", [
    ("state_not_advanced", "decode_logits_max_rel_err"),
    ("decay_dropped", "decode_logits_max_rel_err"),
    ("delta_term_dropped", "decode_logits_max_rel_err"),
    ("conv_tail_dropped", "decode_logits_max_rel_err"),
    ("stale_slot_state", "decode_logits_max_rel_err"),
    ("group_limit_dropped", "routing_agreement")])
def test_a_fault_of_the_mechanism_is_refused(control, by, sound):
    row = _toy_check(control, 5)
    assert not row["ok"], row
    assert by in row["failed_by"]
    if by == "decode_logits_max_rel_err":
        assert row["decode_logits_max_rel_err"] > 2 * max(
            sound["decode_logits_max_rel_err"], serving.DECODE_LOGITS_TOL)
    else:
        # top 4 of all 32 lies inside the 4 best of 8 groups at few
        # positions: the sets agree far below the floor, and the worst
        # group chosen lies far below the fourth best
        assert row["routing_agreement"] < 0.6
        assert "routing_slack_max" in row["failed_by"]


# -- compiled for a described v5e --------------------------------------------

def test_the_widest_step_and_the_longest_prefill_compile_for_the_v5e(
        for_the_chip, monkeypatch):  # noqa: F811
    dev = SingleDeviceSharding(_topology("v5e:1x1").devices[0])
    # the arm a TPU's step takes (the program asks jax.devices())
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu")])
    cell = cell_lib.load_cell(CELL)
    model = get_model(ModelConfig(**{**cell.arch.model_section(cell.config),
                                     "compute_dtype": "bfloat16"}))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=dev)
    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    d = DecodeConfig(**cell.config["serve"]["decode"])
    slots, width = d.decode_slots, d.max_blocks_per_seq()
    assert width == 48
    layers, (heads, dim), dv, taps, wide = model.decode_state_shape
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=d.block_size,
                                     return_counts=True),
                   donate_argnums=(3, 4, 7, 8))
    compiled = step.lower(
        params, sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        # the two row widths as the device stores them whole
        sds((2, d.num_blocks, d.block_size, 512), jnp.bfloat16),
        sds((2, d.num_blocks, d.block_size, 128), jnp.bfloat16),
        sds((slots, width), jnp.int32), sds((slots,), jnp.int32),
        (sds((slots, heads, dim, dv), jnp.float32),) * layers,
        (sds((taps, slots, wide), jnp.bfloat16),) * layers).compile()
    # at PR 45: 13.34 GB: 11.58 of arguments (6.51 weights, 2.01 rows,
    # 3.05 state and tails), 1.75 of temporaries (a latent layer's half of
    # the cache copied out before its gather, 0.81; the gathered rows);
    # the issue's ceiling for 128 slots is 15
    print(f"decode step: {_total(compiled):.0f} bytes")
    assert _total(compiled) / GB <= 13.8
    assert _total(compiled) + 1 * GB < HBM_USABLE
    m = compiled.memory_analysis()
    # the cache and both state arrays are written where they lie
    assert m.alias_size_in_bytes / GB == pytest.approx(2.01 + 3.06, abs=0.03)
    assert m.temp_size_in_bytes / GB < 2.0
    text = compiled.as_text()
    # the token's u is broadcast inside the update's fusion: nothing of
    # the state's size is written out beside the state
    assert not re.search(
        r"^\s*%?broadcast[.\d]* = f32\[128,32,128,128\]", text[text.index(
            "\nENTRY"):], re.M)

    prefill = jax.jit(model.decode_prefill).lower(
        params, sds((1, d.max_prompt_len), jnp.int32),
        sds((1,), jnp.int32)).compile()
    pm = prefill.memory_analysis()
    # one position's logits, not a bucket's
    assert pm.output_size_in_bytes / GB < 0.05
    # 0.62 GB at PR 45
    assert pm.temp_size_in_bytes / GB < 0.8
    # beside a replica's stores: weights, rows, state, the step's logits
    assert (_total(compiled) + pm.temp_size_in_bytes
            + pm.output_size_in_bytes) < HBM_USABLE - 1 * GB
    # the two latent layers' prompts through the flash kernel
    assert prefill.as_text().count("tpu_custom_call") == 2


# -- the readers --------------------------------------------------------------

READ = lambda m, c=None: cell_lib.load_reader(m).read({}, c or {})  # noqa: E731


def test_the_scope_readers_read_the_decode_steps_table(monkeypatch):
    table = {"by_scope": {
        ("attention", "forward"): 0.5,
        ("attention/cache_gather", "forward"): 1.5,
        ("kda", "forward"): 3.0, ("kda/kda_conv", "forward"): 0.25,
        ("kda/kda_gate", "forward"): 0.25,
        ("kda/kda_state", "forward"): 12.0, ("kda", "unnamed"): 0.5,
        ("ffn/moe", "forward"): 5.0, ("head", "forward"): 0.5}}
    run = {"trace": {}, "workdir": Path("/nowhere") / CELL}
    monkeypatch.setattr(program_trace, "this_run", lambda reduced: run)
    monkeypatch.setattr(program_trace, "executions",
                        lambda trace, program: ([("x", 0, 1)], {1}))
    monkeypatch.setattr(program_trace, "scope_table",
                        lambda trace, program: table)
    assert READ("decode_kda_ms_per_step") == 16.0
    assert READ("decode_kda_state_ms_per_step") == 12.0
    # 128 live slots: 2 x 128 x 11 x 2,097,152 B = 5.906 GB at 819 GB/s is
    # 7.211 ms; over 12 ms
    monkeypatch.setattr(program_trace, "spans_by_thread", lambda trace: {
        "loop": [(program_trace.SPAN_DISPATCH, 0, 1, 0, {"live": 128})] * 3})
    share = READ("decode_kda_state_roofline",
                 {"peak_hbm_bytes_per_s": 819e9})
    assert share == pytest.approx(100 * 7.2107 / 12.0, rel=1e-3)
    assert 0 < share < 100
    # a program with no such layer opens no such scope: nothing, no error
    plain = {"by_scope": {("attention", "forward"): 3.0,
                          ("ssm/state_update", "forward"): 1.0}}
    monkeypatch.setattr(program_trace, "scope_table",
                        lambda trace, program: plain)
    for name in kda_scopes.READERS[:3]:
        assert READ(name, {"peak_hbm_bytes_per_s": 819e9}) is None
    # nor a run with no execution of the step
    monkeypatch.setattr(program_trace, "executions",
                        lambda trace, program: ([], set()))
    for name in kda_scopes.READERS:
        assert READ(name, {"peak_hbm_bytes_per_s": 819e9}) is None
    was = program_trace.SCOPES
    with kda_scopes._also(kda_scopes.KDA_SCOPES):
        assert program_trace.scope_path(
            "jit(decode_step)/kda/kda_state/mul") == ("kda", "kda_state")
    assert program_trace.SCOPES == was
