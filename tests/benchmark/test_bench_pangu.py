"""Configuration ``openpangu-ultra-moe-718b``: the served program against
the plain reference of ``benchmark/archs/pangu_ultra_moe.py`` in float32
at a small size on the CPU (prefill and eight decode steps through the
paged latent cache, by the harness's own check), its bfloat16 control,
the pieces on their own (sixteen shares of a routed layer add up to the
uncut layer), the entry against the catalog's row, the architecture's
counts, the configuration's four readers on fixtures, what the new block
costs the accepted cells (nothing: their programs compile to the parent's
text), and the widest decode step at the real widths compiled for a
described v5e under a memory ceiling."""

import copy
import dataclasses
import functools
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.lib import (cell as cell_lib, compare, decode_controls,
                           decode_scopes, serving)
from distributedmnist_tpu.core.config import DecodeConfig, ModelConfig
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops import moe
from distributedmnist_tpu.servesvc.kv_cache import (cache_shapes,
                                                    stored_head_dim)

from test_bench_contract import BENCH, check_configuration
from test_bench_rehearsal import (GB, HBM_USABLE, _compile_train_step,
                                  _topology, _total,
                                  for_the_chip)  # noqa: F401
from test_bench_xing4 import _normalised

CELL = "openpangu-ultra-moe-718b.serve_reason_closed"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: float32 against float32 at ``highest``: what the order of a sum moves
F32_TOL = 2e-5


def small_config(**over) -> dict:
    """The configuration file with every width cut to a toy's: 4 heads
    of 16 + 8 (value 16) over a 16-wide latent, 4 of 16 experts held, 2
    a token, 3 layers."""
    c = copy.deepcopy(cell_lib.load_cell(CELL).config)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             first_k_dense_replace=1, n_routed_experts=4,
             num_experts_per_tok=2, vocab_size=128)
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["assumed"] = dict(c["assumed"], seq_len=64)
    c.update(over)
    return c


def build(config: dict, dtype: str = "float32"):
    arch = cell_lib.load_arch(config)
    section = {**arch.model_section(config), "compute_dtype": dtype,
               "attention_impl": "dense"}
    model = get_model(ModelConfig(**section))
    params = model.init(jax.random.PRNGKey(3))
    # norm scales away from one: a norm left out, or its epsilon, shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(path)), a.shape)
        if getattr(path[-1], "key", None) == "scale" else a, params)
    return arch, model, params


def harness_check(config: dict, dtype: str = "float32", seed: int = 5,
                  control: str = "sound"):
    """``lib/serving.py``'s own check of the decode path, as a run makes
    it on the chip: a seeded prompt of 32, eight teacher-forced steps
    through a scratch paged cache of the replica's geometry, against the
    reference's full forward under the program's expert choices. Under
    ``control``, the program with that fault
    (``lib/decode_controls.py``)."""
    arch, model, params = build(config, dtype)
    cell = dataclasses.replace(cell_lib.load_cell(CELL), config=config,
                               arch=arch)
    dcfg = DecodeConfig(decode_slots=3, block_size=4, num_blocks=33,
                        max_prompt_len=32, max_new_tokens=16)
    section = {**arch.model_section(config), "compute_dtype": dtype,
               "attention_impl": "dense"}
    with jax.default_matmul_precision("highest"):
        return decode_controls.check_control(
            control, ModelConfig(**section), params, dcfg, cell, seed,
            get_model)


def test_the_served_program_is_the_reference_in_float32():
    check = harness_check(small_config())
    assert check["positions"] == 9
    assert check["decode_logits_max_rel_err"] <= F32_TOL
    # the routing export: every position's ids valid, the flag changing
    # no logit, the choices the reference's own
    assert check["routing_ok"] and check["routing_flag_diff"] == 0.0
    assert check["routing_ids_valid"] == 1.0
    assert check["routing_agreement"] == 1.0
    assert check["ok"]


def test_the_bfloat16_program_fails_the_float32_tolerance():
    check = harness_check(small_config(), "bfloat16")
    assert F32_TOL * 10 < check["decode_logits_max_rel_err"]
    assert check["decode_logits_max_rel_err"] < serving.DECODE_LOGITS_TOL
    assert check["routing_flag_diff"] == 0.0


@pytest.mark.parametrize("control, by", [
    ("attention_sublayer_dropped", "decode_logits_max_rel_err"),
    ("held_experts_dropped", "decode_logits_max_rel_err")])
def test_a_fault_in_one_sublayer_fails_the_harness_comparison(control, by):
    """The comparison that decides ``correct`` on the chip, at the
    harness's own limits and in the precision the cell serves in: a
    program that drops one attention sublayer, or one layer's held
    experts, is refused."""
    check = harness_check(small_config(), "bfloat16", control=control)
    assert not check["ok"] and by in check["failed_by"]


def test_the_sound_bfloat16_program_passes_it_and_the_residual_shows():
    sound = harness_check(small_config(), "bfloat16")
    assert sound["ok"] and sound["failed_by"] == []
    # the nearest precision below: a residual in bfloat16
    lower = harness_check(small_config(), "bfloat16",
                          control="bfloat16_residual")
    assert (lower["decode_logits_max_rel_err"]
            > sound["decode_logits_max_rel_err"])
    assert transformer.FLOAT32 is not transformer.PLAIN     # put back
    # one token of a context left out of the mask: seven times the sound
    # program's error and still at the limit's edge (a token of 33 to 40
    # under near-uniform weights): the limit is not made for it
    masked = harness_check(small_config(), "bfloat16",
                           control="masked_newest_token")
    assert (masked["decode_logits_max_rel_err"]
            > 3 * sound["decode_logits_max_rel_err"])
    assert set(decode_controls._controls()) == set(decode_controls.CONTROLS)


def test_whole_forward_and_loss_are_the_references():
    config = small_config()
    arch, model, params = build(config)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 128)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, tokens, train=False)
    assert compare.max_rel_err(got, arch.logits(params, tokens, config)) \
        <= F32_TOL
    assert compare.max_rel_err(model.loss(got, tokens),
                               arch.loss(params, tokens, config)) <= F32_TOL
    # and the reference without its output norms is another function
    bare = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.ones_like(a) * 3.0
        if any(getattr(k, "key", "") in ("ln1_out", "ln2_out")
               for k in path) else a, params)
    assert compare.max_rel_err(arch.logits(bare, tokens, config), got) > 1e-2


def test_sixteen_shares_of_sixteen_experts_add_up_to_the_uncut_layer():
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    z = transformer.Sizes(routed_experts=256, held=(0, 256),
                          shared_experts=1, expert_ffn_dim=32,
                          router_bias_init=0.0)
    blk = transformer._init_sized_block(keys[0], 64, 4, z, routed=True)
    blk["router"] = blk["router"] * 10     # scores wide enough apart
    h = jax.random.normal(keys[1], (2, 48, 64))
    config = small_config(n_routed_experts=256, num_experts_per_tok=8)
    config["published"]["n_routed_experts"] = 256
    arch = cell_lib.load_arch(config)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([arch._routed(seq, blk, config, None)[0]
                          for seq in h])
        shared = moe.gated_unit(h, **blk["shared"])
        total, pairs = shared, 0
        for first in range(0, 256, 16):
            held = jax.tree.map(lambda w: w[first:first + 16],
                                blk["experts"])
            out, _, counts, _ = moe.routed_ffn(
                h, blk["router"], blk["router_bias"], held, blk["shared"],
                total=256, held=(first, 16), top_k=8, scaling=2.5)
            # the shared expert is in every share: counted once
            total, pairs = total + (out - shared), pairs + int(counts.sum())
    assert pairs == 2 * 48 * 8                      # every pair, once
    assert compare.max_rel_err(total, want) <= F32_TOL


def test_a_configuration_the_program_cannot_serve_is_refused():
    arch = cell_lib.load_arch(small_config())
    for key, value in (("sandwich_norm", False), ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("rope_scaling", {"type": "yarn", "factor": 4})):
        with pytest.raises(cell_lib.BenchmarkError):
            arch.model_section(small_config(**{key: value}))


def test_the_entry_is_the_catalogs_row_but_for_what_reduced_names():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "openpangu-ultra-moe-718b")
    cfg, model = check_configuration(entry)
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 16, 19200, 0)
    assert "16 chips share each layer" in cfg["deployment"]
    assert (model["routed_experts"], model["held_experts"],
            model["experts_per_token"], model["sandwich_norm"],
            model["norm_eps"], model["rope_theta"]) == (
                256, 16, 8, True, 1e-5, 25.6e6)
    assert {"scoring", "seq_len", "first_held_expert"} <= set(cfg["assumed"])
    d = cfg["serve"]["decode"]
    assert d["num_blocks"] == (d["decode_slots"]
                               * (d["max_prompt_len"] + d["max_new_tokens"])
                               // d["block_size"] + 1)
    if not CATALOG.exists():
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "openPangu-Ultra-MoE-718B")
    assert cfg["published"] == row["config"]
    assert entry["source"] == row["source_url"]


def test_the_counts_are_the_issues():
    cell = cell_lib.load_cell(CELL)
    arch, c = cell.arch, cell.config
    assert arch._attention_matmul_params(c) / 1e6 == pytest.approx(196.58,
                                                                   abs=0.01)
    assert arch._unit_params(c) / 1e6 == pytest.approx(47.19, abs=0.01)
    # 64 live slots touch 13.9 of the 16 held experts a layer, if evenly
    assert arch.expected_experts_touched(c, 64) == pytest.approx(
        16 * (1 - (1 - 8 / 256) ** 64))
    assert arch.expected_experts_touched(c, 0) == 0.0
    empty = arch.decode_bytes_per_step(c, [])
    outside = (5 * 196.58e6 + 3 * 7680 * 18432
               + 4 * (7680 * 256 + 47.19e6) + 7680 * 19200) * 2
    assert empty == pytest.approx(outside, rel=1e-4)
    # 1,152 B a cached token a layer, a row of the embedding a sequence
    contexts = [4096] * 64
    full = arch.decode_bytes_per_step(c, contexts)
    experts = 4 * arch.expected_experts_touched(c, 64) * 47.19e6 * 2
    assert full - empty == pytest.approx(
        64 * 4096 * 5 * 1152 + 64 * 7680 * 2 + experts, rel=1e-4)
    # 3.50 GB outside the experts, 5.25 of experts, 1.51 of cache
    assert full / GB == pytest.approx(10.26, abs=0.05)
    assert arch.routed_experts(c) == 256 and arch.routed_layers(c) == 4
    assert arch.train_flops_per_token(c, 4096) > 0


# -- the configuration's four readers ---------------------------------------

READ = lambda m: cell_lib.load_reader(m).read({}, {})  # noqa: E731


def test_the_scope_readers_read_the_decode_steps_table(monkeypatch):
    table = {"by_scope": {
        ("attention", "forward"): 3.0,
        ("attention/latent_absorb", "forward"): 2.0,
        ("attention/cache_gather", "forward"): 4.0,
        ("ffn", "forward"): 1.0, ("ffn/moe", "forward"): 5.0,
        ("head", "forward"): 0.5}}
    monkeypatch.setattr(decode_scopes, "table", lambda reduced: table)
    assert READ("decode_absorb_ms_per_step") == 2.0
    assert READ("decode_moe_ms_per_step") == 5.0
    # the plain block's step opens neither scope: nothing, and no error
    plain = {"by_scope": {("attention", "forward"): 3.0,
                          ("attention/cache_gather", "forward"): 4.0,
                          ("ffn", "forward"): 1.0}}
    monkeypatch.setattr(decode_scopes, "table", lambda reduced: plain)
    for name in decode_scopes.READERS[:2]:
        assert READ(name) is None
    # as is a run with no execution of the step at all
    monkeypatch.setattr(decode_scopes, "table", lambda reduced: None)
    assert READ("decode_moe_ms_per_step") is None
    was = decode_scopes.program_trace.SCOPES
    with decode_scopes._also(decode_scopes.DECODE_SCOPES):
        assert decode_scopes.program_trace.scope_path(
            "jit(decode_step)/attention/latent_absorb/dot_general") == (
                "attention", "latent_absorb")
    assert decode_scopes.program_trace.SCOPES == was


def test_the_routing_readers_read_the_heartbeats(tmp_path, monkeypatch):
    workdir = tmp_path / CELL
    (workdir / "serve").mkdir(parents=True)
    (workdir / "load.json").write_text(json.dumps(
        {"window_start": 100.0, "window_end": 140.0}))
    beat = lambda t, pairs, touched: {  # noqa: E731
        "event": "heartbeat", "step": int(t), "time": t,
        "expert_pairs_held": pairs, "experts_touched": touched}
    beats = [beat(90.0, 999, 1),                # before the window
             beat(101.0, 30, 15), beat(110.0, 36, 12), beat(120.0, 28, 14),
             {"event": "heartbeat", "step": 7, "time": 125.0},
             beat(150.0, 999, 1)]               # after it
    (workdir / "serve" / "train_log.jsonl").write_text(
        "".join(json.dumps(b) + "\n" for b in beats))
    monkeypatch.setattr(decode_scopes.program_trace, "this_run",
                        lambda reduced: {"workdir": workdir, "trace": {}})
    assert READ("decode_experts_touched_p50") == 14.0
    assert READ("decode_pairs_per_touched_expert_p50") == 2.0
    # a replica that routes nothing writes no such field
    (workdir / "serve" / "train_log.jsonl").write_text(
        json.dumps({"event": "heartbeat", "step": 1, "time": 105.0}) + "\n")
    assert READ("decode_experts_touched_p50") is None
    assert READ("decode_pairs_per_touched_expert_p50") is None
    (workdir / "serve" / "train_log.jsonl").unlink()
    assert READ("decode_experts_touched_p50") is None


def test_the_cell_reports_the_accepted_serving_metrics():
    cell = cell_lib.load_cell(CELL)
    assert cell.chips == 1 and cell.kind == "serve_closed"
    # the gap alone: one run in four stops for a third of its window
    # (PERF.md §6, PR 34), which tokens a second cannot carry at 2%
    assert {m["name"] for m in cell.end_to_end} == {"itl_ms_p90", "setup_s"}
    # the thirteen accepted at PR 34 stay; a later PR may list the cell
    # under a metric it appends
    assert {m["name"] for m in cell.per_layer} >= {
        "compile_or_load_s", "weights_ready_s", "decode_iter_ms_p50",
        "decode_step_device_ms", "decode_step_roofline",
        "serve_device_idle_share", "loadgen_late_ms_p99", "itl_ms_p50",
        "itl_ms_p99",
        # what the account of the gap's spread rests on (PERF.md §6):
        # the step's attention half, the prefills a window holds and
        # their scatter, the slots live
        "decode_attention_ms_per_step", "prefill_ms_p50",
        "decode_slots_live_p50", "prefill_cache_write_share_of_busy"}
    # the cell is one of the file's, however many there are, and the
    # rule on four-chip cells is the contract's own
    assert CELL in [w["name"] for w in BENCH["workloads"]]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    t = cell.traffic
    assert (t["clients_per_slot"], t["requests_per_client"],
            t["warmup_s"]) == (2, 12, 12)
    assert (t["prompt_len"]["lo"], t["prompt_len"]["hi"],
            t["max_tokens"]["lo"], t["max_tokens"]["hi"]) == (
                512, 2048, 1024, 2048)


# -- compiled for a described v5e -------------------------------------------

def _sds(dev):
    return lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=dev)


def _abstract_params(model, dev, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype, sharding=dev),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))


def test_the_widest_decode_step_compiles_for_the_v5e_under_its_ceiling(
        for_the_chip):  # noqa: F811
    cell = cell_lib.load_cell(CELL)
    dev = SingleDeviceSharding(_topology("v5e:1x1").devices[0])
    sds = _sds(dev)
    model = get_model(ModelConfig(**cell.arch.model_section(cell.config)))
    params = _abstract_params(model, dev)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert weights / GB == pytest.approx(9.84, abs=0.01)    # 4.92 B x 2
    d = cell.config["serve"]["decode"]
    assert (d["decode_slots"], d["num_blocks"], d["block_size"]) == (
        64, 16385, 16)
    width = -(-(d["max_prompt_len"] + d["max_new_tokens"])
              // d["block_size"])
    layers, heads, widths = model.decode_cache_shape
    assert (layers, heads, widths, width) == (5, 1, (512, 64), 256)
    # as DecodeReplica builds the cache: each array's rows as wide as the
    # device keeps them whole, the positions of a block second-minor
    shapes = cache_shapes(layers, d["num_blocks"], d["block_size"], heads,
                          widths)
    stored = tuple(stored_head_dim(s, jnp.bfloat16, dev) for s in shapes)
    assert stored == (512, 128)
    placed = []
    for shape, wide in zip(shapes, stored):
        zeros = jax.jit(lambda s=(*shape[:-1], wide): jnp.zeros(
            s, jnp.bfloat16), out_shardings=dev).lower().compile()
        assert tuple(zeros.output_formats.layout.major_to_minor) == (
            0, 1, 2, 3)
        placed.append(zeros.memory_analysis().output_size_in_bytes)
    # 102,400 B a block over 5 layers: nothing padded eightfold
    assert sum(placed) == pytest.approx(16385 * 102400, rel=0.01)
    assert sum(placed) / GB == pytest.approx(1.68, abs=0.017)
    slots = d["decode_slots"]
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=d["block_size"],
                                     return_counts=True),
                   donate_argnums=(3, 4))
    compiled = step.lower(
        params, sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        *(sds((*shape[:-1], wide), jnp.bfloat16)
          for shape, wide in zip(shapes, stored)),
        sds((slots, width), jnp.int32), sds((slots,), jnp.int32)).compile()
    # a ceiling, so that a step that needs less passes. At PR 34: 12.11 GB,
    # 11.52 of arguments (weights and the cache) and 0.59 of temporaries
    print(f"decode step: {_total(compiled):.0f} bytes")
    assert _total(compiled) / GB <= 12.3
    # the replica holds no restore template beside it; 1 GB to spare
    assert _total(compiled) + 1 * GB < HBM_USABLE
    text = compiled.as_text()
    # the step takes both arrays as they lie
    assert "bf16[5,16385,16,512]{3,2,1,0:T(8,128)(2,1)} copy(" not in text
    assert "bf16[5,16385,16,128]{3,2,1,0:T(8,128)(2,1)} copy(" not in text


def _opt_decode_step(dev):
    cell = cell_lib.load_cell("opt-1.3b.serve_decode_closed")
    sds = _sds(dev)
    model = get_model(ModelConfig(**cell.arch.model_section(cell.config)))
    d = cell.config["serve"]["decode"]
    layers, heads, hd = model.decode_cache_shape
    wide = stored_head_dim((layers, d["num_blocks"], d["block_size"], heads,
                            hd), jnp.bfloat16, dev)
    cache = sds((layers, d["num_blocks"], d["block_size"], heads, wide),
                jnp.bfloat16)
    slots = d["decode_slots"]
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=d["block_size"]),
                   donate_argnums=(3, 4))
    return step.lower(
        _abstract_params(model, dev), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32), cache, cache, sds((slots, 28), jnp.int32),
        sds((slots,), jnp.int32)).compile()


def _xing4_train_step(dev):
    del dev
    return _compile_train_step(
        cell_lib.load_cell("xing4.0-29b-a4b.train_sync_1chip"),
        _topology("v5e:1x1").devices)


@pytest.mark.parametrize("program, parent", [
    (_opt_decode_step,
     "8affac7bb256e5b5bd6eb7f9b380f412557d9d802be2ba0987a336848c427a37"),
    (_xing4_train_step,
     "ef1e8bdfe797364dd0f2ea772443a36c55e75ab9dad8519b731fcbda2a5c0350")],
    ids=["opt-1.3b.decode_step", "xing4.0-29b-a4b.train_step"])
def test_the_accepted_programs_compile_to_the_parents_text(
        program, parent, for_the_chip):  # noqa: F811
    """The output norms, the block's decode attention, the latent
    rows a prefill hands over and the grouped product's tile cost the
    accepted cells nothing: ``opt-1.3b``'s decode step (at the replica's
    stored width, 28 blocks of table) and ``xing4.0-29b-a4b``'s train
    step compile for a described v5e to the text the parent commit
    (e875a74) compiled to, hashed there. ``opt-6.7b``'s two train steps
    are held to theirs by ``test_bench_xing4.py``."""
    dev = SingleDeviceSharding(_topology("v5e:1x1").devices[0])
    text = _normalised(program(dev).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == parent
