"""Rehearsal 3 of the on-chip-measurement guide, kept as tests: the train
step and the decode step of the benchmark's cells compiled at their
published widths for a v5e that is described and not attached. Nothing
runs, so nothing here is a time; what the chip's compiler refuses, or
what no longer fits its memory, shows here at no chip time.

The numbers pinned are the ``memory_analysis`` figures that decided
``num_hidden_layers`` and the tokens per step of ``opt-6.7b`` and the
``num_blocks`` of ``opt-1.3b`` (their configuration files quote them).
Skipped where the TPU compiler cannot describe the topology."""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from benchmark.lib import cell as cell_lib, peaks

GB = 1e9
#: what ``memory_stats()["bytes_limit"]`` reports on the v5e (PR 22)
HBM_USABLE = 16_909_336_064


def _topology(name: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-1")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    bounds = (1, 1, 1) if name.endswith("1x1") else (2, 2, 1)
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=name, chip_config_name="default",
            chips_per_host_bounds=bounds, num_slices=1)
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe {name}: {type(e).__name__}: {e}")


@pytest.fixture()
def for_the_chip(monkeypatch):
    """Lower the Pallas kernels for Mosaic (the program asks
    ``jax.default_backend()``), and keep these compiles out of the
    persistent cache: written without a chip they cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _total(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _compile_train_step(cell, devices):
    from distributedmnist_tpu.core.config import (ExperimentConfig,
                                                  effective_model_config)
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import (build_train_step,
                                                   init_train_state,
                                                   state_partition_specs)
    from distributedmnist_tpu.train.lr_schedule import constant

    class _Rt:
        seed, workdir = 0, cell_lib.ROOT
    cfg = ExperimentConfig.from_dict(
        cell_lib.load_driver("train").experiment(cell, _Rt))
    model = get_model(effective_model_config(cfg))
    topo = make_topology(cfg.mesh, devices=devices)
    step = build_train_step(model, cfg, topo, constant(
        cfg.optim.initial_learning_rate))
    specs = state_partition_specs(model, cfg, topo)
    abstract = jax.eval_shape(lambda: init_train_state(model, cfg, topo))
    spec_leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    placed = [jax.tree.map(
        lambda a, s=s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(topo.mesh, s)), sub)
        for sub, s in zip(treedef.flatten_up_to(abstract), spec_leaves)]
    state = jax.tree.unflatten(treedef, placed)
    rows = NamedSharding(topo.mesh, P(topo.replica_axis))
    b, s = cfg.data.batch_size, cfg.model.seq_len
    batch = {"image": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rows),
             "label": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rows),
             "weight": jax.ShapeDtypeStruct((b,), jnp.float32, sharding=rows)}
    measured = jax.ShapeDtypeStruct((len(devices),), jnp.float32,
                                    sharding=rows)
    discipline = jax.ShapeDtypeStruct(
        (3,), jnp.float32, sharding=NamedSharding(topo.mesh, P()))
    return step.jitted.lower(state, batch, measured, discipline).compile()


@pytest.mark.parametrize("workload, topology, chips", [
    # the one-chip step is the four-chip step without its all-reduces:
    # 16 s of compile that tier-1's time limit cannot spare (-m slow)
    pytest.param("opt-6.7b.train_sync_1chip", "v5e:1x1", 1,
                 marks=pytest.mark.slow),
    ("opt-6.7b.train_quorum3of4_4chip", "v5e:2x2", 4)])
def test_train_step_compiles_for_the_v5e(workload, topology, chips,
                                         for_the_chip):
    cell = cell_lib.load_cell(workload)
    devices = _topology(topology).devices
    assert cell.chips == chips == len(devices)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.config["train"]["sequences_per_step_per_chip"] * 2048 == 8192
    compiled = _compile_train_step(cell, devices)
    text = compiled.as_text()
    # per chip: 6.55 GB of f32 weights and momentum, 4.50 GB temporaries
    assert _total(compiled) / GB == pytest.approx(11.05, abs=0.15)
    assert _total(compiled) + 1 * GB < HBM_USABLE
    # flash forward, the forward again under remat, two backward kernels
    assert text.count("tpu_custom_call") == 4 * 3
    if chips == 1:
        assert "all-reduce" not in text
    else:
        assert "all-reduce" in text      # the masked psum of the gradient


def test_decode_step_compiles_for_the_v5e_with_room_for_the_template(
        for_the_chip):
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    cell = cell_lib.load_cell("opt-1.3b.serve_decode_closed")
    dev = SingleDeviceSharding(_topology("v5e:1x1").devices[0])
    model = get_model(ModelConfig(**cell.arch.model_section(cell.config)))
    stored = jnp.dtype(cell.config["serve"]["precision"]["param_dtype"])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=dev)
    params = jax.tree.map(
        lambda a: sds(a.shape, stored),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    template = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert template / GB == pytest.approx(2.63, abs=0.01)   # 1.315 B x 2
    d = cell.config["serve"]["decode"]
    assert (d["decode_slots"], d["num_blocks"], d["block_size"]) == (
        16, 769, 16)
    width = -(-(d["max_prompt_len"] + d["max_new_tokens"])
              // d["block_size"])
    layers, heads, head_dim = model.decode_cache_shape
    assert (layers, heads, head_dim, width) == (24, 32, 64, 112)

    def caches():
        # as DecodeReplica builds them, whatever their layout; traced
        # by eval_shape and so never allocated
        cache = PagedKVCache(layers, d["num_blocks"], d["block_size"],
                             heads, head_dim, width, dtype=jnp.bfloat16)
        return cache.k, cache.v

    k_cache, v_cache = (sds(a.shape, a.dtype)
                        for a in jax.eval_shape(caches))
    slots = d["decode_slots"]
    # built as DecodeReplica builds it, the kernel choice left at the
    # program's default
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=d["block_size"]),
                   donate_argnums=(3, 4))
    compiled = step.lower(
        params, sds((slots,), jnp.int32), sds((slots,), jnp.int32), k_cache,
        v_cache, sds((slots, width), jnp.int32),
        sds((slots,), jnp.int32)).compile()
    # a ceiling, so that a step that needs less passes. At PR 22 it
    # needed 12.73 GB: 5.45 of arguments (weights and both caches) and
    # 7.28 of temporaries, three times the caches, which the step copies
    # into a lane-padded layout because the heads are 64 wide
    print(f"decode step: {_total(compiled):.0f} bytes")
    assert _total(compiled) / GB <= 12.88
    # beside it the replica holds its restore template; 1 GB to spare
    assert _total(compiled) + template + 1 * GB < HBM_USABLE
    assert HBM_USABLE < peaks.peaks_for("TPU v5 lite")["hbm_bytes"]
