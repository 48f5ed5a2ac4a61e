"""How the decode step takes the paged KV cache
(servesvc/kv_cache.py::stored_head_dim, models/transformer.py::
decode_step, servesvc/decode.py).

Two halves. At ``opt-1.3b``'s geometry, compiled for a v5e that is
described and not attached (rehearsal 3 of the on-chip-measurement
guide: nothing runs, nothing here is a time; skipped where the
installation cannot describe the chip): with the head stored as wide as
``stored_head_dim`` answers, the step and the prompt's scatter copy no
whole cache array; the step a TPU runs reads the cache through one
paged kernel a layer at each of the replica's four table widths, with
no gathered copy of the context and no float32 view of one, and the
gather arm, still there by name, keeps its sizes; which arm a step
takes is decided by what it is handed; the step a TPU runs writes the
new token's rows inside that kernel, so it holds no loop; a latent
block's step at the latent cell's shapes reads its two arrays through
its own kernel, a call a layer, with no slice of a layer and no gather
of the table's width. And on the
CPU test mesh: a cache with wider rows decodes to the bit what one with
the head's own width decodes, through the model's step, the prompt's
scatter and a replica."""

import functools
import json
import os
import re
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.models.transformer import decode_attention_arm
from distributedmnist_tpu.servesvc import decode as decode_mod
from distributedmnist_tpu.servesvc.kv_cache import (PagedKVCache,
                                                    stored_head_dim,
                                                    write_prompt_kv)

GB = 1e9
#: opt-1.3b as served (benchmark/configs/opt-1.3b.json): 24 layers, 32
#: heads of 64, bf16; 16 slots, 769 blocks of 16, tables of 28..112
OPT_1_3B = {"name": "transformer", "model_dim": 2048, "num_heads": 32,
            "num_layers": 24, "seq_len": 2048, "vocab_size": 50272}
SLOTS, BLOCKS, BLOCK = 16, 769, 16
CACHE_SHAPE = (24, BLOCKS, BLOCK, 32, 64)
WIDTHS = [28, 56, 84, 112]      # DecodeReplica._table_widths of 112
GATHER_WIDTHS = [28, 112]
MB = 1e6
#: the latent cell's decode state (openpangu-ultra-moe-718b as served:
#: 5 layers' latents 512 wide and rotated keys stored 128 wide, 16,385
#: pages of 16, 64 slots, 128 heads, the widest table) on a block
#: narrow enough to compile in seconds: nothing here counts its matrices
LATENT_CELL = {"name": "transformer", "model_dim": 1024, "num_heads": 128,
               "num_layers": 5, "seq_len": 4096, "vocab_size": 1024,
               "q_latent_dim": 256, "kv_latent_dim": 512,
               "qk_nope_dim": 128, "qk_rope_dim": 64, "v_head_dim": 128,
               "ffn_dim": 1024, "compute_dtype": "bfloat16"}
LATENT_SLOTS, LATENT_TABLE = 64, 256
LATENT_SHAPES = ((5, 16385, 16, 512), (5, 16385, 16, 128))


def _total(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _count(compiled, shape, op: str) -> int:
    """Instructions ``op`` whose result has ``shape``, any layout."""
    dims = re.escape("[" + ",".join(map(str, shape)) + "]")
    return len(re.findall(rf"= \w+{dims}\{{[^}}]*\}} {op}\(",
                          compiled.as_text()))


def _as_on_a_tpu():
    """What the program asks of its devices and its backend answers as
    on the chip, so that the step takes the arm
    (``decode_attention_arm`` asks ``jax.devices()``) and the kernel
    lowers for Mosaic (it asks ``jax.default_backend()``): a described
    device leaves both the CPU's."""
    tpu = types.SimpleNamespace(platform="tpu")
    return mock.patch.multiple(jax, devices=lambda *a: [tpu],
                               default_backend=lambda: "tpu")


@pytest.fixture(scope="module")
def for_the_chip():
    """``model.decode_step`` jitted as the replica jits it (both caches
    donated) at its four table widths, as a TPU runs it, the gather arm
    by name at two, and the prompt's scatter, on a cache as wide as
    ``stored_head_dim`` answers for the chip, for a described
    ``v5e:1x1``; these compiles kept out of the persistent cache
    (written without a chip they cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model

    for name, value in (("TPU_LOG_DIR", "disabled"),
                        ("TPU_ACCELERATOR_TYPE", "v5litepod-1"),
                        ("TPU_WORKER_HOSTNAMES", "localhost")):
        os.environ.setdefault(name, value)
    try:
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chip_config_name="default", chips_per_host_bounds=(1, 1, 1),
            num_slices=1).devices[0]
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe v5e:1x1: {type(e).__name__}: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        on_chip = SingleDeviceSharding(device)
        sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=on_chip)
        model = get_model(ModelConfig(**OPT_1_3B))
        params = jax.tree.map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
        wide = stored_head_dim(CACHE_SHAPE, jnp.bfloat16, on_chip)

        def compiled(width, head_dim, **how):
            cache = sds((*CACHE_SHAPE[:-1], head_dim), jnp.bfloat16)
            return step_of(model, params, cache, cache, SLOTS, width, **how)

        def step_of(model, params, k, v, slots, width, **how):
            step = jax.jit(functools.partial(model.decode_step,
                                             block_size=BLOCK, **how),
                           donate_argnums=(3, 4))
            with _as_on_a_tpu():
                return step.lower(
                    params, sds((slots,), jnp.int32),
                    sds((slots,), jnp.int32), k, v,
                    sds((slots, width), jnp.int32),
                    sds((slots,), jnp.int32)).compile()

        latent = get_model(ModelConfig(**LATENT_CELL))
        latent_params = jax.tree.map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(lambda: latent.init(jax.random.PRNGKey(0))))

        cache = sds((*CACHE_SHAPE[:-1], wide), jnp.bfloat16)
        prompt = sds((24, 256, 32, 64), jnp.bfloat16)
        yield {"stored_head_dim": wide, "on_chip": on_chip,
               "stored_wide": {w: compiled(w, wide) for w in WIDTHS},
               "gather": {w: compiled(w, wide, attention_kernel="dense")
                          for w in GATHER_WIDTHS},
               "latent": step_of(
                   latent, latent_params,
                   *(sds(shape, jnp.bfloat16) for shape in LATENT_SHAPES),
                   LATENT_SLOTS, LATENT_TABLE),
               "write": jax.jit(
                   write_prompt_kv, static_argnames="block_size",
                   donate_argnums=(0, 1)).lower(
                       cache, cache, prompt, prompt,
                       sds((WIDTHS[-1],), jnp.int32), sds((), jnp.int32),
                       block_size=BLOCK).compile()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_the_chip_keeps_a_128_wide_heads_rows_whole(for_the_chip):
    """The v5e's default layout of ``[24, 769, 16, 32, 64]`` makes the
    block index minor; of ``[..., 128]`` the head."""
    on_chip = for_the_chip["on_chip"]
    assert for_the_chip["stored_head_dim"] == 128
    assert stored_head_dim((*CACHE_SHAPE[:-1], 128), jnp.bfloat16,
                           on_chip) == 128
    assert stored_head_dim((*CACHE_SHAPE[:-1], 160), jnp.bfloat16,
                           on_chip) == 256


def _takes_the_cache_as_it_lies(step, shape):
    assert _count(step, shape, "copy") == 0
    layouts = {f.layout for f in (*step.input_formats[0][3:5],
                                  *step.output_formats[1:])}
    assert [tuple(at.major_to_minor) for at in layouts] == [(0, 1, 2, 3, 4)]


@pytest.mark.parametrize("width, temporaries_gb",
                         zip(GATHER_WIDTHS, (0.5, 1.0)))
def test_the_step_on_whole_rows_copies_no_cache_array(
        for_the_chip, width, temporaries_gb):
    """The gather arm, by name (what every token took before the paged
    kernel, and still the oracle): no ``copy`` of the cache's shape, the
    cache arrays taken and returned in one layout, and the memory that
    leaves (12.73 GB at the head's own width, 7.27 of it temporaries:
    ``tests/benchmark/test_bench_rehearsal.py``)."""
    step = for_the_chip["gather"][width]
    shape = (*CACHE_SHAPE[:-1], for_the_chip["stored_head_dim"])
    _takes_the_cache_as_it_lies(step, shape)
    assert step.memory_analysis().temp_size_in_bytes / GB <= temporaries_gb
    assert _total(step) / GB <= 8.4
    assert "paged_decode" not in step.as_text()


@pytest.mark.parametrize("width", WIDTHS)
def test_the_step_a_tpu_runs_reads_the_cache_where_it_lies(
        for_the_chip, width):
    """What the replica runs on the chip, at each of its four widths:
    one Mosaic call of the paged kernel a layer; no array of a gathered
    context (``[16, w, 16, 32, *]``) in float32 or bfloat16; no
    ``copy`` or ``slice`` of a cache array or of a layer of one; and
    temporaries of 24 MB where the gather's are 134 / 390 / 582 / 741
    MB (PR 38's ``decode_start``), whatever the width."""
    step = for_the_chip["stored_wide"][width]
    text = step.as_text()
    shape = (*CACHE_SHAPE[:-1], for_the_chip["stored_head_dim"])
    _takes_the_cache_as_it_lies(step, shape)
    calls = re.findall(r"%paged_decode[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == OPT_1_3B["num_layers"]
    assert not re.findall(rf"= (?:f32|bf16)\[{SLOTS},{width},{BLOCK},32,\d+\]",
                          text)
    for dims in (shape, shape[1:]):
        for op in ("copy", "slice", "dynamic-slice", "fusion"):
            assert _count(step, dims, op) == 0, (dims, op)
    assert step.memory_analysis().temp_size_in_bytes / MB <= 64
    assert _total(step) / GB <= 7.6


@pytest.mark.parametrize("width", WIDTHS)
def test_the_step_a_tpu_runs_writes_the_new_rows_inside_the_kernel(
        for_the_chip, width):
    """The paged arm hands the token's rows to the kernel, which takes
    each cache array as input and output in one buffer: the compiled
    step holds no ``while`` (a scatter of the rows is two loops a
    layer: what the gather arm, by name, still compiles to), no
    ``dynamic-update-slice`` and no ``scatter`` at all, every one of its
    24 calls aliases outputs 1 and 2 to the cache operands (the test
    above holds that no copy is made of them and what the step needs
    beside its arguments)."""
    text = for_the_chip["stored_wide"][width].as_text()
    assert decode_mod.while_loops(text) == 0
    for op in ("dynamic-update-slice", "scatter"):
        assert not re.findall(rf" {op}\(", text), op
    calls = re.findall(r"%paged_decode[.\d]* = [^\n]*", text)
    assert len(calls) == OPT_1_3B["num_layers"]
    assert all("output_to_operand_aliasing={{1}: (9, {}), {2}: (10, {})}"
               in call for call in calls)
    if width in GATHER_WIDTHS:
        gather = for_the_chip["gather"][width].as_text()
        assert decode_mod.while_loops(gather) == 2 * OPT_1_3B["num_layers"]


def test_a_step_takes_the_arm_its_input_decides():
    """``decode.attention_kernel = auto``: rows stored in whole lanes on
    a TPU go through the kernel, keys and values a head and a latent's
    one row a token for all heads alike; the head's own width, a latent
    row that fills no whole lane or a latent page that is no whole tile,
    and a CPU through the gather. A name is an arm whatever the input."""
    wide, own = (24, 769, 16, 32, 128), (24, 769, 16, 32, 64)
    latent = (5, 16385, 16, 512)
    assert decode_attention_arm("auto", latent) == "gather"    # a CPU
    assert jax.devices()[0].platform == "cpu"
    assert decode_attention_arm("auto", wide) == "gather"
    # the kernels' own question alone (what the accepted compile tests
    # of tests/benchmark patch) moves no arm
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert decode_attention_arm("auto", wide) == "gather"
    with _as_on_a_tpu():
        assert decode_attention_arm("auto", wide) == "paged"
        assert decode_attention_arm("auto", (2, 40, 4, 4, 256)) == "paged"
        assert decode_attention_arm("auto", own) == "gather"
        assert decode_attention_arm("auto", (2, 40, 4, 4, 192)) == "gather"
        assert decode_attention_arm("auto", latent) == "paged"
        assert decode_attention_arm("auto", (5, 16385, 16, 128)) == "paged"
        assert decode_attention_arm("auto", (5, 16385, 16, 64)) == "gather"
        assert decode_attention_arm("auto", (3, 16, 4, 16)) == "gather"
        # both arrays are asked, and a page has to be whole tiles too:
        # the kernel writes the token's row through its tile
        assert decode_attention_arm("auto", latent, (5, 16385, 16, 128)) \
            == "paged"
        assert decode_attention_arm("auto", latent, (5, 16385, 16, 64)) \
            == "gather"
        assert decode_attention_arm("auto", (5, 32769, 8, 512),
                                    (5, 32769, 8, 128)) == "gather"
        assert decode_attention_arm("dense", latent) == "gather"
        assert decode_attention_arm("dense", wide) == "gather"
        assert decode_attention_arm("paged", own) == "paged"
    assert decode_attention_arm("paged", wide) == "paged"
    assert decode_attention_arm("paged", (3, 16, 4, 16)) == "paged"
    assert decode_attention_arm("dense", own) == "gather"
    with pytest.raises(ValueError, match="attention_kernel"):
        decode_attention_arm("flash", wide)


def test_a_latent_blocks_step_calls_its_kernel_on_a_tpu():
    """A latent block reads its cache by the arm its arrays decide:
    lowered for a TPU with both rows in whole lanes, its step under
    ``auto`` calls the latent kernel once a layer (one body,
    ``paged_latent_decode``, the layer an argument); under ``dense`` it
    holds none and is, to the letter, the text the gather compiled to
    before there was such a kernel; with the rotated key stored at its
    own width ``auto`` is that text too."""
    import hashlib

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model

    model = get_model(ModelConfig(
        name="transformer", model_dim=64, num_heads=4, num_layers=2,
        seq_len=64, vocab_size=96, q_latent_dim=24, kv_latent_dim=128,
        qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, ffn_dim=96,
        compute_dtype="bfloat16", attention_impl="dense"))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    sds = jax.ShapeDtypeStruct

    def lowered(rope_row=128, **how):
        args = (params, sds((3,), jnp.int32), sds((3,), jnp.int32),
                sds((2, 9, 16, 128), jnp.bfloat16),
                sds((2, 9, 16, rope_row), jnp.bfloat16),
                sds((3, 4), jnp.int32), sds((3,), jnp.int32))
        with _as_on_a_tpu():
            return jax.jit(functools.partial(
                model.decode_step, block_size=16, **how)).trace(
                    *args).lower(lowering_platforms=("tpu",)).as_text()

    auto, dense = lowered(), lowered(attention_kernel="dense")
    assert len(re.findall(r"call @paged_latent_attention_write\(",
                          auto)) == 2
    assert auto.count('kernel_name = "paged_latent_decode"') == 1
    assert "paged_latent_decode" not in dense
    assert "tpu_custom_call" not in dense
    # (the parent commit's text of this toy, block 16, 9 blocks)
    assert hashlib.sha256(dense.encode()).hexdigest() == (
        "5ac4814521b907c31704efbd21038bb3ca7974e83a71a455c312356dbde0b6b7")
    narrow = lowered(rope_row=8)
    assert "tpu_custom_call" not in narrow
    assert narrow == lowered(rope_row=8, attention_kernel="dense")


def test_the_latent_step_a_tpu_runs_reads_its_cache_where_it_lies(
        for_the_chip):
    """The latent cell's decode state compiled for a described v5e at
    its widest table: one Mosaic call of the latent kernel a layer, each
    with the two cache arrays aliased in and out (operands 10 and 11),
    no ``copy`` of either; and neither of the two things the ledger's
    ``device_ops`` showed of the gather: no operation whose output is a
    layer's ``[16385, 16, width]`` slice of a cache array, no gather of
    the table's ``256 · 16`` rows a slot; no loop and no scatter (the
    token's row is written inside the call)."""
    step = for_the_chip["latent"]
    text = step.as_text()
    layers = LATENT_CELL["num_layers"]
    calls = re.findall(r"%paged_latent_decode[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"[^\n]*",
                       text)
    assert len(calls) == layers
    assert all("output_to_operand_aliasing={{1}: (10, {}), {2}: (11, {})}"
               in call for call in calls)
    rows = LATENT_TABLE * BLOCK
    for shape in LATENT_SHAPES:
        for op in ("copy", "slice", "dynamic-slice", "fusion", "bitcast"):
            assert _count(step, shape[1:], op) == 0, (shape, op)
        assert _count(step, shape, "copy") == 0
        width = shape[-1]
        assert not re.findall(
            rf"= (?:f32|bf16)\[(?:{LATENT_SLOTS},{LATENT_TABLE},{BLOCK}"
            rf"|{LATENT_SLOTS},{rows}|{LATENT_SLOTS * rows}"
            rf"|{LATENT_SLOTS * LATENT_TABLE},{BLOCK}),{width}\]", text)
    assert not re.findall(
        rf"= f32\[{LATENT_SLOTS},{LATENT_CELL['num_heads']},{rows}\]", text)
    assert decode_mod.while_loops(text) == 0
    for op in ("dynamic-update-slice", "scatter"):
        assert not re.findall(rf" {op}\(", text), op
    # (what gathers are left are the embedding's and the work items')
    gathered = re.findall(r"= \w+\[(\d+)[\],][^=]* gather\(", text)
    assert all(int(n) <= LATENT_SLOTS * LATENT_TABLE for n in gathered)
    layouts = {f.layout for f in (*step.input_formats[0][3:5],
                                  *step.output_formats[1:])}
    assert [tuple(at.major_to_minor) for at in layouts] == [(0, 1, 2, 3)]
    assert step.memory_analysis().temp_size_in_bytes / MB <= 64


@pytest.mark.parametrize("slots, heads, d, dv", [(4, 16, 128, 128),
                                                 (2, 3, 64, 256)])
def test_a_delta_rule_layers_donated_state_goes_through_one_mosaic_call(
        for_the_chip, slots, heads, d, dv):
    """``ops/kda.py::state_step`` compiled for the described chip: Mosaic
    takes the body at a block smaller than the heads and at three heads
    in one, the call is counted by the name ``decode_start`` counts it
    by, and a donated state is advanced in its own buffer."""
    from distributedmnist_tpu.ops import kda
    sds = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=for_the_chip["on_chip"])
    compiled = jax.jit(lambda *a: kda.state_step(*a, interpret=False),
                       donate_argnums=5).lower(
        sds(slots, heads, d), sds(slots, heads, d), sds(slots, heads, dv),
        sds(slots, heads, d), sds(slots, heads), sds(slots, heads, d, dv),
        sds(slots, dt=jnp.bool_)).compile()
    text = compiled.as_text()
    assert decode_mod.mosaic_calls(text, "kda_state_step") == 1
    assert decode_mod.mosaic_calls(text, "paged_(?:latent_)?decode") == 0
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == slots * heads * d * dv * 4
    assert m.temp_size_in_bytes < 1 << 20


def test_the_prompts_scatter_on_whole_rows_copies_no_cache_array(
        for_the_chip):
    write = for_the_chip["write"]
    shape = (*CACHE_SHAPE[:-1], for_the_chip["stored_head_dim"])
    assert _count(write, shape, "copy") == 0
    assert write.memory_analysis().temp_size_in_bytes / GB <= 0.1


# -- the CPU test mesh ------------------------------------------------------

@pytest.mark.parametrize("shape, dtype", [
    ((2, 40, 4, 4, 16), jnp.float32), ((24, 97, 16, 32, 64), jnp.bfloat16),
    ((1, 5, 16, 1, 128), jnp.bfloat16)])
def test_a_cpu_keeps_every_heads_rows_whole(shape, dtype):
    """Where the compiler's answer is the default, nothing is widened."""
    assert stored_head_dim(shape, dtype) == shape[-1]
    assert stored_head_dim(shape, dtype, jax.sharding.SingleDeviceSharding(
        jax.devices()[0])) == shape[-1]


LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}
GEOMETRY = {"decode_slots": 3, "block_size": 4, "num_blocks": 40,
            "max_prompt_len": 16, "max_new_tokens": 16}


@pytest.fixture(scope="module")
def lm():
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    model = get_model(ModelConfig(**LM_MODEL))
    return model, model.init(jax.random.PRNGKey(3))


def _cache(model, head_dim=None, blocks=12):
    layers, heads, hd = model.decode_cache_shape
    return PagedKVCache(layers, 40, 4, heads, head_dim or hd,
                        max_blocks_per_seq=blocks, dtype=jnp.float32)


def test_a_cache_built_with_todays_arguments_is_placed_as_before(lm):
    """Every caller but the replica: the head's own width, the default
    layout, committed to no device."""
    cache = _cache(lm[0])
    plain = jnp.zeros((2, 40, 4, 4, 16), jnp.float32)
    for a in (cache.k, cache.v):
        assert a.shape == plain.shape and a.dtype == plain.dtype
        assert a.format.layout == plain.format.layout
        assert not a.committed


@pytest.mark.parametrize("kernel", ["dense", "paged"])
@pytest.mark.parametrize("stored, width", [(24, 4), (32, 6), (128, 12)])
def test_wider_rows_decode_to_the_bit_what_the_heads_own_width_decodes(
        lm, kernel, stored, width):
    """A written prompt and six greedy steps for two slots of three
    (the third idle) through ``write_prompt`` and the model's step, at
    table widths 4, 6 and 12 blocks: logits equal to the bit, the same
    elements in the head's part of every row, zeros beside it."""
    model, params = lm
    hd = model.decode_cache_shape[2]
    prefill = jax.jit(model.decode_prefill)
    step = jax.jit(functools.partial(model.decode_step, block_size=4,
                                     attention_kernel=kernel))
    prompts = [[1, 2, 3, 4, 5], list(range(1, 9))]

    def decode(head_dim):
        cache = _cache(model, head_dim, blocks=width)
        tables = np.zeros((3, width), np.int32)
        toks, rows = [], []
        for slot, prompt in enumerate(prompts):
            padded = np.zeros((1, 8), np.int32)
            padded[0, :len(prompt)] = prompt
            logits, ks, vs = prefill(params, jnp.asarray(padded))
            tables[slot] = cache.alloc_sequence(len(prompt) + 6)
            cache.write_prompt(tables[slot], ks[:, 0], vs[:, 0], len(prompt))
            toks.append(int(jnp.argmax(logits[0, len(prompt) - 1])))
        for i in range(6):
            pos = [len(p) + i for p in prompts]
            out, cache.k, cache.v = step(
                params, jnp.asarray([*toks, 0], jnp.int32),
                jnp.asarray([*pos, 0], jnp.int32), cache.k, cache.v,
                jnp.asarray(tables),
                jnp.asarray([pos[0] + 1, pos[1] + 1, 0], jnp.int32))
            rows.append(np.asarray(out[:2]))
            toks = [int(t) for t in rows[-1].argmax(-1)]
        return np.stack(rows), np.asarray(cache.k), np.asarray(cache.v)

    want, want_k, want_v = decode(hd)
    got, got_k, got_v = decode(stored)
    np.testing.assert_array_equal(got, want)
    for got_a, want_a in ((got_k, want_k), (got_v, want_v)):
        assert got_a.shape[-1] == stored and want_a.any()
        np.testing.assert_array_equal(got_a[..., :hd], want_a)
        assert not got_a[..., hd:].any()


@pytest.mark.parametrize("stored", [None, 48])
def test_a_replica_decodes_what_the_full_context_forward_decodes(
        tmp_path, monkeypatch, stored):
    """Through every table width a toy replica has (2, 4, 6, 8 blocks),
    at the head's own width (what a CPU answers) and with rows three
    times as wide as the 16-wide head; and ``decode_start`` says how the
    cache lies and what each width's step does with it."""
    from distributedmnist_tpu.core.config import (DecodeConfig,
                                                  ExperimentConfig,
                                                  ServeConfig)
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.obsv.schema import validate_event
    from distributedmnist_tpu.parallel.api import init_train_state
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.train.checkpoint import save_checkpoint

    if stored:
        monkeypatch.setattr(decode_mod, "stored_head_dim",
                            lambda shape, dtype, sharding: stored)
    train_dir = tmp_path / "published"
    cfg = ExperimentConfig.from_dict({
        "model": dict(LM_MODEL), "train": {"train_dir": str(train_dir)}})
    model = get_model(cfg.model)
    state = init_train_state(model, cfg)
    save_checkpoint(train_dir, state, 0, extra={"config": cfg.to_dict()})
    rep = decode_mod.DecodeReplica(
        train_dir, serve_dir=tmp_path / "replica",
        scfg=ServeConfig(poll_secs=0.05), dcfg=DecodeConfig(**GEOMETRY),
        cfg=cfg)
    head_dim = stored or model.decode_cache_shape[2]
    assert rep.cache.k.shape == rep.cache.v.shape == (2, 40, 4, 4, head_dim)
    prompts = [[1, 2, 3, 4, 5], list(range(1, 17)), [7]]
    rep.start()
    try:
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=60.0)
        outs = [client.generate(p, max_tokens=12) for p in prompts]
    finally:
        rep.stop()
    apply = jax.jit(lambda p, t: model.apply(p, t, train=False))
    for prompt, out in zip(prompts, outs):
        assert out["status"] == "ok" and len(out["tokens"]) == 12
        seq = list(prompt)
        for _ in range(12):
            logits = apply(state.params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert out["tokens"] == seq[len(prompt):]
    journal = (rep.serve_dir / "serve_log.jsonl").read_text().splitlines()
    [started] = [r for r in map(json.loads, journal)
                 if r.get("action") == "decode_start"]
    assert validate_event(started) == []
    assert started["table_widths"] == [2, 4, 6, 8]
    assert started["cache_layout"] == "major_to_minor=(0, 1, 2, 3, 4) tiling=()"
    assert started["cache_device_bytes"] == 2 * 2 * 40 * 4 * 4 * head_dim * 4
    assert len(started["step_temp_bytes"]) == 4
    assert all(isinstance(n, int) and n > 0
               for n in started["step_temp_bytes"])
    assert len(started["whole_cache_copies"]) == 4
    # on a CPU, whatever the rows' width: the gather, and no Mosaic call
    assert started["attention_arm"] == ["gather"] * 4
    assert started["paged_calls"] == [0] * 4
    # whatever the gather arm's scatter compiles to here: a count a width
    assert len(started["step_while_loops"]) == 4
    assert all(isinstance(n, int) and n >= 0
               for n in started["step_while_loops"])
