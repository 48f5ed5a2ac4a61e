"""Configuration ``xing4.0-29b-a4b``: the program against the plain
reference of ``benchmark/archs/xing4.py`` in float32 at a small size on
the CPU, its bfloat16 control, the pieces on their own (the shares of a
routed layer add up to the uncut layer; the Sinkhorn iteration; no pair
dropped), what the new block costs the OPT cells (nothing: their train
steps compile to the parent's text), and the train step at the real
widths compiled for a described v5e under a memory ceiling."""

import copy
import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import block_scopes, cell as cell_lib, compare
from distributedmnist_tpu.core.config import ModelConfig
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops import moe

from test_bench_contract import BENCH, check_configuration
from test_bench_rehearsal import (GB, _compile_train_step, _topology, _total,
                                  for_the_chip)  # noqa: F401

CELL = "xing4.0-29b-a4b.train_sync_1chip"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
#: float32 against float32 at ``highest``: what the order of a sum moves
F32_TOL = 2e-5


def small_config(**over) -> dict:
    """The configuration file with every width cut to a toy's: 4 heads
    of 16 + 8 (value 16), 4 of 16 experts held, 3 layers and the module."""
    c = copy.deepcopy(cell_lib.load_cell(CELL).config)
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             first_k_dense_replace=1, n_routed_experts=4,
             num_experts_per_tok=2, vocab_size=128)
    c["published"] = dict(c["published"], n_routed_experts=16)
    c["rope_scaling"] = dict(c["rope_scaling"], factor=4,
                             original_max_position_embeddings=16)
    c["assumed"] = dict(c["assumed"], seq_len=32)
    c.update(over)
    return c


def build(config: dict, dtype: str = "float32"):
    arch = cell_lib.load_arch(config)
    section = {**arch.model_section(config), "compute_dtype": dtype,
               "attention_impl": "dense"}
    model = get_model(ModelConfig(**section))
    params = model.init(jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0,
                                config["vocab_size"])
    return arch, model, params, tokens


@pytest.fixture(scope="module")
def small():
    config = small_config()
    return (config, *build(config))


def program_train_loss(model, params, tokens):
    logits, aux = model.apply(params, tokens, train=True, return_aux=True)
    return model.loss(logits, tokens) + aux["loss"]


@pytest.mark.parametrize("what", ["logits", "loss", "train_loss"])
def test_the_program_is_the_reference_in_float32(small, what):
    config, arch, model, params, tokens = small
    if what == "logits":
        got = model.apply(params, tokens, train=False)
        want = arch.logits(params, tokens, config)
    elif what == "loss":
        got = model.loss(model.apply(params, tokens, train=False), tokens)
        want = arch.loss(params, tokens, config)
    else:
        got = program_train_loss(model, params, tokens)
        want = arch.train_loss(params, tokens, config)
        # the module's term is there, at its weight
        assert float(got) > float(arch.loss(params, tokens, config)) + 1.0
    assert compare.max_rel_err(got, want) <= F32_TOL


def test_the_gradients_of_one_step_are_the_references(small):
    config, arch, model, params, tokens = small
    got = jax.grad(lambda p: program_train_loss(model, p, tokens))(params)
    want = jax.grad(lambda p: arch.train_loss(p, tokens, config))(params)
    # the loss's gradient reaches no selection bias, on either side; the
    # program's moves by load: half the sign of each expert's excess
    _, aux = model.apply(params, tokens, train=True, return_aux=True)
    blocks = [*got["blocks"][1:], got["nextn"]["block"]]
    for blk, ids in zip(blocks, np.asarray(aux["routing"])):
        load = np.bincount(ids.reshape(-1), minlength=16)
        assert np.array_equal(np.asarray(blk.pop("router_bias")),
                              0.5 * np.sign(load - ids.size / 16))
    for blk in [*want["blocks"][1:], want["nextn"]["block"]]:
        assert not jnp.any(blk.pop("router_bias"))
    worst = max(jax.tree.leaves(jax.tree.map(compare.max_rel_err, got, want)))
    assert worst <= 1e-3


def test_the_bfloat16_program_fails_the_float32_tolerance(small):
    config, arch, _, params, tokens = small
    _, model, _, _ = build(config, "bfloat16")
    logits, aux = model.apply(params, tokens, train=False, return_aux=True)
    forced = arch.logits(params, tokens, config, routing=aux["routing"])
    err = compare.max_rel_err(logits, forced)
    assert F32_TOL * 10 < err < 2e-2


def test_the_routing_export_adds_an_output_and_nothing_else(small):
    config, arch, model, params, tokens = small
    plain = model.apply(params, tokens, train=False)
    logits, aux = model.apply(params, tokens, train=False, return_aux=True)
    assert jnp.array_equal(plain, logits)
    routing = aux["routing"]
    assert routing.shape == (2, 2, 32, 2) and routing.dtype == jnp.int32
    slack = arch.routing_slack(params, tokens, config, routing)
    verdict = compare.routing_verdict(
        slack, routing, compare.routed_experts(arch, config), 0.0)
    assert verdict["routing_ok"] and verdict["routing_agreement"] == 1.0
    # counts: the pairs that landed on the four experts held
    held = np.asarray((routing >= 0) & (routing < 4)).sum(axis=(1, 2, 3))
    assert np.array_equal(np.asarray(aux["counts"]).sum(axis=1), held)
    # training runs the module's routed layer too, and says so last
    _, aux = model.apply(params, tokens, train=True, return_aux=True)
    assert aux["routing"].shape[0] == aux["counts"].shape[0] == 3


def _layer_and_input(width=64, experts=64, ffn=32, tokens=96):
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    z = transformer.Sizes(routed_experts=experts, held=(0, experts),
                          shared_experts=1, expert_ffn_dim=ffn)
    blk = transformer._init_sized_block(keys[0], width, 4, z, routed=True)
    # scores wide enough apart to route unevenly
    blk["router"] = blk["router"] * 10
    return blk, jax.random.normal(keys[1], (2, tokens // 2, width))


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    blk, h = _layer_and_input()
    config = small_config(hidden_size=64, n_routed_experts=64,
                          num_experts_per_tok=4)
    config["published"]["n_routed_experts"] = 64
    arch = cell_lib.load_arch(config)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([arch._routed(seq, blk, config, None)[0]
                          for seq in h])
        shared = moe.gated_unit(h, **blk["shared"])
        total, pairs = shared, 0
        for first in range(0, 64, 8):
            held = jax.tree.map(lambda w: w[first:first + 8], blk["experts"])
            out, _, counts, _ = moe.routed_ffn(
                h, blk["router"], blk["router_bias"], held, blk["shared"],
                total=64, held=(first, 8), top_k=4, scaling=2.0)
            total, pairs = total + (out - shared), pairs + int(counts.sum())
    assert pairs == h.shape[0] * h.shape[1] * 4     # every pair, once
    assert compare.max_rel_err(total, want) <= F32_TOL


def test_no_pair_is_dropped_when_every_token_takes_one_held_expert():
    blk, h = _layer_and_input(experts=16, tokens=1400)
    # expert 5 wins every token's first place by its selection bias
    blk["router_bias"] = jnp.zeros(16).at[5].set(10.0)
    held = jax.tree.map(lambda w: w[4:8], blk["experts"])
    with jax.default_matmul_precision("highest"):
        out, ids, counts, _ = moe.routed_ffn(
            h, blk["router"], blk["router_bias"], held, None, total=16,
            held=(4, 4), top_k=2, scaling=1.0)
        assert int(counts[1]) == 1400 > moe.TILE_ROWS   # more than a tile
        assert bool(jnp.all(jnp.any(ids == 5, axis=-1)))
        flat = h.reshape(-1, 64)
        _, gates = moe.route_tokens(flat, blk["router"], blk["router_bias"],
                                    2, 1.0)
        want = sum(
            jnp.sum(jnp.where(ids.reshape(-1, 2) == 4 + e, gates, 0.0),
                    axis=-1, keepdims=True)
            * moe.gated_unit(flat, *(held[k][e] for k in
                                     ("w_gate", "w_up", "w_down")))
            for e in range(4))
    assert compare.max_rel_err(out.reshape(-1, 64), want) <= F32_TOL


@pytest.mark.parametrize("name", ["start", "ends_diagonal", "all_high",
                                  "all_low", "random"])
def test_sinkhorn_rows_and_columns_sum_to_one(name):
    n, clamp = 4, 30.0
    r = {"start": 8.0 * jnp.eye(n),
         "ends_diagonal": clamp * (2 * jnp.eye(n) - 1),
         "all_high": jnp.full((n, n), 5 * clamp),     # clipped to the end
         "all_low": jnp.full((n, n), -5 * clamp),
         # what the maps make a little off their start
         "random": 8.0 * jnp.eye(n) + 0.3 * jax.random.normal(
             jax.random.PRNGKey(0), (n, n)),
         }[name]
    m = transformer._sinkhorn(r[:, :, None], 20, 1e-6, clamp)[:, :, 0]
    assert bool(jnp.all(m > 0))
    # the columns are divided last. Near the identity the iteration
    # converges slowly (its rate is the limit's second singular value,
    # here nearly 1): 20 rounds, the published count, leave the rows of
    # the perturbed start 6e-4 off
    assert float(jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1))) <= 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1))) <= (
        2e-3 if name == "random" else 1e-4)
    config = small_config()
    ref = cell_lib.load_arch(config).sinkhorn(r, 20, 1e-6, clamp)
    assert compare.max_rel_err(m, ref) <= 1e-6


@pytest.mark.parametrize("forward", ["prefill_with_kv", "apply_pp",
                                     "apply_pp_1f1b"])
def test_a_forward_that_carries_one_stream_refuses_four(forward):
    block = transformer.make_block(
        num_heads=4, residual=transformer.stream_residual(
            4, iters=2, eps=1e-6, clamp=30.0))
    kwargs = {"apply_pp": dict(stage_axis="s", num_microbatches=1),
              "apply_pp_1f1b": dict(stage_axis="s", num_microbatches=1,
                                    num_chunks=1)}.get(forward, {})
    with pytest.raises(NotImplementedError, match="one residual stream"):
        getattr(transformer, forward)({}, jnp.zeros((1, 8), jnp.int32),
                                      block=block, **kwargs)


def test_the_record_has_no_decode_export_and_no_sharded_layout(small):
    model = small[2]
    assert model.decode_step is None and model.decode_prefill is None
    with pytest.raises(NotImplementedError, match="unsharded"):
        model.sharded_apply_factory(None, "model")


def test_a_configuration_the_program_cannot_run_is_refused():
    arch = cell_lib.load_arch(small_config())
    for key, value in (("scoring_func", "softmax"), ("n_group", 2),
                       ("tie_word_embeddings", True)):
        with pytest.raises(cell_lib.BenchmarkError):
            arch.model_section(small_config(**{key: value}))


def test_the_entry_is_the_catalogs_row_but_for_what_reduced_names():
    entry = next(c for c in BENCH["configs"] if c["name"] == "xing4.0-29b-a4b")
    cfg, model = check_configuration(entry)
    assert set(entry["reduced"]) == {"num_hidden_layers", "vocab_size",
                                     "first_k_dense_replace",
                                     "n_routed_experts"}
    assert (model["routed_experts"], model["held_experts"],
            model["experts_per_token"], model["residual_streams"],
            model["sinkhorn_iters"]) == (64, 8, 4, 4, 20)
    if not CATALOG.exists():
        pytest.skip("no catalog beside the guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["published"] == row["config"]
    assert entry["source"] == row["source_url"]


def test_the_counts_are_the_issues(small):
    cell = cell_lib.load_cell(CELL)
    forward = cell.arch.train_flops_per_token(cell.config, 4096) / 3
    assert forward / 1e6 == pytest.approx(1253, abs=2)    # MFLOP a token
    attention = cell.arch.attention_train_flops_per_token(cell.config, 4096)
    # six layers, 32 heads, 192 for the scores and 128 for the values
    assert attention == 3 * 6 * 2 * 2048.5 * 32 * (192 + 128)


def _fake_run(tmp_path, monkeypatch, records):
    workdir = tmp_path / CELL
    (workdir / "train").mkdir(parents=True, exist_ok=True)
    (workdir / "train" / "train_log.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in records))
    monkeypatch.setattr(block_scopes.program_trace, "this_run",
                        lambda reduced: {"workdir": workdir, "trace": {}})


def test_the_routing_counters_read_the_step_records(tmp_path, monkeypatch):
    step = {"event": "step", "expert_counts": [[512] * 8, [1024] + [0] * 7]}
    _fake_run(tmp_path, monkeypatch, [step, {"event": "save"}, step])
    counters = {"tokens_per_step": 8192}
    held = cell_lib.load_reader("moe_pairs_held_share").read({}, counters)
    assert held == pytest.approx(100 * (4096 + 1024) / (2 * 8192 * 4))
    load = cell_lib.load_reader("moe_expert_load_max_over_mean").read(
        {}, counters)
    assert load == pytest.approx((1 + 8) / 2)
    # a program that logs no counts: nothing to read, and no error
    _fake_run(tmp_path, monkeypatch, [{"event": "step"}])
    assert cell_lib.load_reader("moe_pairs_held_share").read(
        {}, counters) is None


def test_a_scope_reader_finds_nothing_in_a_program_without_the_scope(
        monkeypatch):
    table = {"by_scope": {("attention", "forward"): 3.0,
                          ("attention/residual_mix", "backward"): 2.0,
                          ("mtp/attention", "forward"): 1.0,
                          ("ffn", "forward"): 5.0}}
    monkeypatch.setattr(block_scopes, "table", lambda reduced: table)
    read = lambda m: cell_lib.load_reader(m).read({}, {})  # noqa: E731
    assert read("latent_attention_ms_per_step") == 4.0
    assert read("residual_mix_ms_per_step") == 2.0
    assert read("mtp_ms_per_step") == 1.0
    assert read("moe_ms_per_step") is None
    was = block_scopes.program_trace.SCOPES
    with block_scopes._also(block_scopes.BLOCK_SCOPES):
        assert block_scopes.program_trace.scope_path(
            "jit(f)/transpose(jvp(mtp))/ffn/moe/while") == ("mtp", "ffn",
                                                             "moe")
    assert block_scopes.program_trace.SCOPES == was


def _normalised(text: str) -> str:
    """Compiled text less what names the checkout: operation metadata,
    the header's source tables and the kernels' serialized bodies."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"backend_config=.*", "backend_config=<kernel>", text)
    out, skip = [], False
    for line in text.split("\n"):
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)",
                    line):
            skip = True
        elif skip:
            skip = bool(line.strip())
        else:
            out.append(line)
    return "\n".join(out)


@pytest.mark.parametrize("workload, topology, parent", [
    ("opt-6.7b.train_sync_1chip", "v5e:1x1",
     "3d299f682553e7a8a8ed912509c19e64399cd3ef4e6752ddc02490d867540543"),
    ("opt-6.7b.train_quorum3of4_4chip", "v5e:2x2", "4e3d3d6fb9f2e8edc683fb1f6436f7c9a5654af91daf346bad9206ee122498fa")])
def test_the_opt_train_steps_compile_to_the_parents_text(
        workload, topology, parent, for_the_chip):  # noqa: F811
    """The residual rule, the projections and the per-layer block cost
    OPT nothing: its train steps compile for a described v5e to the text
    the parent commit (775e99d) compiled to, hashed there."""
    compiled = _compile_train_step(cell_lib.load_cell(workload),
                                   _topology(topology).devices)
    text = _normalised(compiled.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == parent


def test_the_train_step_compiles_for_the_v5e_under_its_ceiling(
        for_the_chip):  # noqa: F811
    cell = cell_lib.load_cell(CELL)
    assert cell.config["train"]["sequences_per_step_per_chip"] * 4096 == 8192
    compiled = _compile_train_step(cell, _topology("v5e:1x1").devices)
    text = compiled.as_text()
    # 7.31 GB of f32 weights and momentum, about 8 of temporaries
    print(f"train step: {_total(compiled):.0f} bytes")
    assert _total(compiled) / GB <= 15.5
    # six layers' flash forward, forward again, and two backward kernels:
    # the step's Mosaic calls are the flash kernels alone
    assert text.count("tpu_custom_call") == 4 * 6
    assert "all-reduce" not in text
