"""The gradient's all-reduces beside the weight-gradient products:
``build_train_step``'s ``precompile`` compiles the step with
``ASYNC_ALL_REDUCE_OPTIONS`` where the mesh's devices are TPUs and the
replica axis holds more than one, and nowhere else.

The first two cases compile for a v5e that is described and not
attached (rehearsal 3 of the on-chip-measurement guide): nothing runs,
nothing here is a time. They show the schedule the compiler makes and
that it still fits the chip. Skipped where the TPU compiler cannot
describe the topology. The others run on the CPU mesh, or on no device."""

import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import base_config
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.parallel import api
from distributedmnist_tpu.train.lr_schedule import constant

GB = 1e9
#: what ``memory_stats()["bytes_limit"]`` reports on the v5e (PERF.md)
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


@pytest.fixture()
def compiles(monkeypatch):
    """The ``compiler_options`` of every ``Lowered.compile`` call."""
    seen = []
    real = jax.stages.Lowered.compile

    def spy(self, compiler_options=None, **kw):
        seen.append(compiler_options)
        return real(self, compiler_options=compiler_options, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", spy)
    return seen


def _cell_step(workload: str, devices, **model_overrides):
    """A benchmark cell's train step for described devices, with the
    abstract arguments its ``precompile`` takes: what the trainer builds
    on the chip, from the cell's own configuration."""
    from benchmark.lib import cell as cell_lib
    from distributedmnist_tpu.core.config import (ExperimentConfig,
                                                  effective_model_config)
    from distributedmnist_tpu.core.mesh import make_topology

    class _Rt:
        seed, workdir = 0, cell_lib.ROOT
    cell = cell_lib.load_cell(workload)
    assert cell.chips == len(devices)
    exp = cell_lib.load_driver("train").experiment(cell, _Rt)
    exp["model"].update(model_overrides)
    cfg = ExperimentConfig.from_dict(exp)
    model = get_model(effective_model_config(cfg))
    topo = make_topology(cfg.mesh, devices=devices)
    step = api.build_train_step(model, cfg, topo, constant(
        cfg.optim.initial_learning_rate))
    specs = api.state_partition_specs(model, cfg, topo)
    abstract = jax.eval_shape(lambda: api.init_train_state(model, cfg, topo))
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
    return step, (state, batch, measured, discipline)


def _total(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_four_chip_step_is_compiled_with_its_all_reduces_in_flight(
        for_the_chip, compiles):
    step, args = _cell_step("opt-6.7b.train_quorum3of4_4chip",
                            _topology("v5e:2x2").devices)
    record = step.precompile(*args)
    assert compiles == [api.ASYNC_ALL_REDUCE_OPTIONS]
    assert record["source"] == "compiled"
    assert record["compiler_options"] == sorted(api.ASYNC_ALL_REDUCE_OPTIONS)
    # one a matrix of the three layers but the first to finish; the
    # embedding's and the quorum's flags stay synchronous
    assert record["async_collectives"] >= 10
    exe = step.executable()
    text = exe.as_text()
    dones = re.findall(r"^\s*%?async-collective-done[\w.]* = ", text, re.M)
    assert len(dones) == record["async_collectives"]
    assert "all-reduce(" in text
    # flash forward, the forward again under remat, two backward kernels
    assert text.count("tpu_custom_call") == 4 * 3
    # the fusions do not reduce in place: 13.19 GB where the synchronous
    # program holds 11.05, and still a gigabyte of room on the chip
    assert _total(exe) + 1 * GB < HBM_USABLE


def test_one_chip_step_is_the_program_it_was(for_the_chip, compiles):
    """One replica: no option, and the executable that ``jit`` alone
    makes. One layer of the three, so that two compiles fit tier-1."""
    step, args = _cell_step("opt-6.7b.train_sync_1chip",
                            _topology("v5e:1x1").devices, num_layers=1)
    record = step.precompile(*args)
    assert compiles == [None]
    assert record["compiler_options"] == []
    assert record["async_collectives"] == 0
    plain = step.jitted.lower(*args).compile()
    text = step.executable().as_text()
    assert text == plain.as_text()
    assert "all-reduce" not in text and "tpu_custom_call" in text


def _cpu_step(topo8):
    cfg = base_config(model={"dropout_rate": 0.0},
                      sync={"mode": "quorum", "num_replicas_to_aggregate": 6})
    model = get_model(cfg.model)
    step = api.build_train_step(model, cfg, topo8, constant(0.05))

    def fresh_state():
        return topo8.device_put_state(
            api.init_train_state(model, cfg, topo8),
            api.state_partition_specs(model, cfg, topo8))

    rng = np.random.default_rng(0)
    batch = topo8.device_put_batch(
        {"image": rng.normal(size=(64, 28, 28, 1)).astype(np.float32),
         "label": rng.integers(0, 10, size=(64,)).astype(np.int32)})
    return step, fresh_state, batch


def _bits(tree):
    return [np.asarray(leaf).tobytes() for leaf in jax.tree.leaves(tree)]


def test_cpu_mesh_gets_no_option_and_the_inline_path_s_values(
        topo8, compiles):
    step, fresh_state, batch = _cpu_step(topo8)
    record = step.precompile(fresh_state(), batch)
    assert compiles == [None]
    assert record == {"compile_s": record["compile_s"], "source": "compiled",
                      "compiler_options": [], "async_collectives": 0}
    assert step.executable() is not None
    got = step(fresh_state(), batch)             # the compiled fast path
    want = step.jitted(fresh_state(), batch, topo8.zeros_measured(),
                       step.default_discipline())
    assert _bits(got) == _bits(want)
    assert int(got[1]["num_contributors"]) == 6


def test_a_compiler_that_refuses_the_options_costs_a_warning_not_the_job(
        topo8, compiles, monkeypatch, caplog):
    """Eight replicas believed to be TPUs: the CPU's compiler is the one
    that refuses TPU options, and the step is compiled without them."""
    monkeypatch.setattr(api, "_async_options",
                        lambda mesh, axis: api.ASYNC_ALL_REDUCE_OPTIONS)
    step, fresh_state, batch = _cpu_step(topo8)
    logger = logging.getLogger("distributedmnist_tpu.parallel")
    logger.addHandler(caplog.handler)     # the package's root propagates nothing
    try:
        record = step.precompile(fresh_state(), batch)
    finally:
        logger.removeHandler(caplog.handler)
    assert compiles == [api.ASYNC_ALL_REDUCE_OPTIONS, None]
    warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert len(warnings) == 1
    assert "xla_enable_async_all_reduce" in warnings[0].getMessage()
    assert record["source"] == "compiled"
    assert record["compiler_options"] == []
    assert record["async_collectives"] == 0
    # and the job goes on, on the plain program
    state, metrics = step(fresh_state(), batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("platform, replicas, expected", [
    ("tpu", 4, api.ASYNC_ALL_REDUCE_OPTIONS),
    ("tpu", 1, {}),
    ("cpu", 8, {}),
])
def test_the_condition_reads_the_mesh(platform, replicas, expected):
    class _Device:
        pass
    device = _Device()
    device.platform = platform

    class _Mesh:
        devices = np.array([device] * replicas, dtype=object)
        shape = {"replica": replicas}
    assert api._async_options(_Mesh, "replica") == expected


def test_count_async_collectives_reads_the_entry_computation_only():
    text = """HloModule jit_shard_fn, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %async-collective-start.9 = f32[8]{0} parameter(0)
}

ENTRY %main.1_spmd (param: f32[8]) -> f32[8] {
  %async-collective-start = (f32[8]{0}, u32[]) fusion(%param), kind=kCustom
  %gte = f32[8]{0} get-tuple-element(%async-collective-start), index=0
  %async-collective-done = f32[8]{0} fusion(%gte), kind=kCustom
  %async-collective-start.1 = (f32[8]{0}, u32[]) fusion(%param), kind=kCustom
  %all-reduce-start.2 = f32[8]{0} all-reduce-start(%param), to_apply=%add
  ROOT %all-reduce.3 = f32[8]{0} all-reduce(%param), to_apply=%add
}
"""
    assert api.count_async_collectives(text) == 3
    assert api.count_async_collectives("") == 0
