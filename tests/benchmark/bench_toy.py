"""Toy sizes for the benchmark's own tests (a helper module, not a
conftest: tests elsewhere import ``conftest`` by name, so this directory
must not have one): the drivers and the harness
run end to end on the CPU test mesh (Pallas interpreted), at shapes
passed in from here. Nothing timed in these tests is a device metric."""

import time

import pytest

from benchmark.lib import cell as cell_lib
from benchmark.lib.runtime import Runtime

TOY_SHAPES = {"arch": "opt", "hidden_size": 64, "ffn_dim": 256,
              "num_attention_heads": 4, "num_hidden_layers": 2,
              "vocab_size": 512, "max_position_embeddings": 128,
              "word_embed_proj_dim": 64}

TOY_TRAIN_CONFIG = {
    **TOY_SHAPES,
    "model_assumed": {"remat": True, "compute_dtype": "float32"},
    "train": {"sequences_per_step_per_chip": 2,
              "optim": {"name": "momentum", "momentum": 0.9,
                        "initial_learning_rate": 0.05,
                        "learning_rate_decay_factor": 1.0}}}

TOY_SERVE_CONFIG = {
    **TOY_SHAPES,
    "model_assumed": {"compute_dtype": "float32"},
    "serve": {"precision": {}, "replica": {"queue_depth": 64},
              "decode": {"decode_slots": 4, "block_size": 16,
                         "num_blocks": 33, "max_prompt_len": 64,
                         "max_new_tokens": 32, "eos_token": -1}}}

_DATA = {"sequences_per_chip": 64, "zipf_exponent": 1.1, "bigram_share": 0.5}

TOY_TRAFFIC = {
    "train_sync": {"kind": "train", "sync": {"mode": "sync"}, "data": _DATA,
                   "warmup_log_windows": 1},
    "train_quorum": {"kind": "train",
                     "sync": {"mode": "quorum",
                              "num_replicas_to_aggregate": 3,
                              "straggler_profile": "lognormal"},
                     "data": _DATA, "warmup_log_windows": 1},
    "serve_closed": {
        "kind": "serve_closed", "clients_per_slot": 2,
        "requests_per_client": 400,
        "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 32},
        "max_tokens": {"dist": "loguniform", "lo": 8, "hi": 32},
        "stagger_first_wave": True, "deadline_ms": 300000,
        "warmup_s": 0.5, "warmup_request_timeout_s": 300},
    "serve_open": {
        "kind": "serve_open",
        "arrivals": {"process": "poisson", "rate_per_s": 4.0,
                     "knee_per_s": 5.0},
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.7,
                       "lo": 8, "hi": 64},
        "max_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                       "lo": 4, "hi": 32},
        "deadline_ms": 30000, "warmup_s": 1, "grace_s": 5,
        "warmup_request_timeout_s": 300},
}

#: which real cell lends its metric lists to which toy traffic
REAL_CELL = {"train_sync": "opt-6.7b.train_sync_1chip",
             "train_quorum": "opt-6.7b.train_quorum3of4_4chip",
             "serve_closed": "opt-1.3b.serve_decode_closed",
             "serve_open": "opt-1.3b.serve_chat_open"}


class ToyRuntime(Runtime):
    """The runtime without a chip: the CPU reports no memory peak."""

    def memory_peak_bytes(self) -> int:
        return 1


def toy_cell(traffic_name: str, config: dict | None = None,
             arch=None) -> cell_lib.Cell:
    """The toy cell of one traffic mix: OPT's block, or ``config`` with
    the architecture file ``arch`` (a module)."""
    real = cell_lib.load_cell(REAL_CELL[traffic_name])
    if config is None:
        config = (TOY_TRAIN_CONFIG if traffic_name.startswith("train")
                  else TOY_SERVE_CONFIG)
    return cell_lib.Cell(
        name=f"toy.{traffic_name}", chips=real.chips, config_name="toy",
        config=config, arch=arch or cell_lib.load_arch(config),
        traffic_name=traffic_name,
        traffic=TOY_TRAFFIC[traffic_name], end_to_end=real.end_to_end,
        per_layer=real.per_layer)


def routed_toy_cell(traffic_name: str, experts: int = 64) -> cell_lib.Cell:
    """The same cell over the routed toy architecture
    (``toy_routed.py``), run by its bfloat16 stand-in
    (``toy_routed_model.py``) through the program's registry."""
    import toy_routed
    import toy_routed_model
    toy_routed_model.register()
    return toy_cell(traffic_name, arch=toy_routed, config={
        **toy_routed_model.toy_config(experts),
        "model_assumed": {"compute_dtype": "bfloat16"},
        "train": TOY_TRAIN_CONFIG["train"],
        "serve": TOY_SERVE_CONFIG["serve"]})


@pytest.fixture(autouse=True)
def routed_toy_unregistered():
    """After a test that built a routed toy cell the stand-in leaves the
    program's registry again: the registry is the process's, and a test
    elsewhere that walks every registered model
    (``tests/test_models.py``) would meet it, or not, by the order a
    worker was handed its files in. Autouse in the modules that import
    it beside :func:`routed_toy_cell`."""
    yield
    from distributedmnist_tpu.models import registry
    registry._REGISTRY.pop("toy_routed", None)


@pytest.fixture()
def toy_runtime(tmp_path):
    def make(cell, seconds=2.0, seed=7, trace=False):
        return ToyRuntime(cell, seed, seconds, trace, time.time(),
                          {"platform": "cpu", "kind": "toy",
                           "count": cell.chips},
                          {"bf16_flops_per_s": 1e12},
                          work_root=tmp_path / "work")
    return make
