"""Test harness: 8 virtual CPU devices for SPMD semantics.

This is the mock distributed backend the reference never had
(SURVEY §4): quorum masks, psum semantics, interval windows, and
checkpoint round-trips are all exercised against a simulated 8-device
mesh on one CPU host. Platform setup MUST happen before any test
import initializes the XLA backend.
"""

import os

# Journal-schema enforcement ON for every test run: records the AST
# pass (distributedmnist_tpu.analysis, "graftcheck") can't see as
# literal dicts still get checked against obsv/schema.py at write time
# (core/log.py JsonlSink). Set before anything writes — the sink
# samples the gate on its FIRST write and freezes it for the process
# (hot path); per-call toggling only affects schema.maybe_check_event.
os.environ.setdefault("DMT_VALIDATE_EVENTS", "1")

# The jaxlib 0.9.0 XLA:CPU fusion emitters contract mul+add into FMA
# per fusion, so two programs with the SAME arithmetic but a different
# fusion structure differ in the last bit (measured: 2/32 elements of a
# bias leaf, 7e-9, between the ZeRO-1 layouts; gone with the emitters
# off or with --xla_cpu_max_isa=SSE4_2). The suite's bitwise-parity
# tests compare exactly such pairs, so the CPU test backend runs the
# classic emitters. Before the backend initializes, like the mesh.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_cpu_use_fusion_emitters=false").strip()

from distributedmnist_tpu.core.mesh import simulate_devices  # noqa: E402

simulate_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def topo8():
    from distributedmnist_tpu.core.mesh import make_topology
    assert len(jax.devices()) == 8, "conftest failed to create 8 CPU devices"
    return make_topology()


@pytest.fixture()
def tmp_train_dir(tmp_path):
    return str(tmp_path / "train")


@pytest.fixture(scope="session")
def synthetic_datasets():
    from distributedmnist_tpu.data.datasets import make_synthetic
    return make_synthetic(num_train=2048, num_test=512)


# ---- the gold-parity tests' tolerances (moe/pp/tp sharded vs dense) ----

LOSS_TOL = dict(rtol=2e-5, atol=2e-5)


def assert_update_parity(got, want, rtol=3e-4, atol=3e-5):
    """Leaf-wise sharded-vs-dense post-update parameter comparison."""
    import numpy as np
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


def base_config(**overrides):
    """Small fast config for tests; sections overridable via dicts."""
    from distributedmnist_tpu.core.config import ExperimentConfig
    d = {
        "data": {"dataset": "synthetic", "batch_size": 64,
                 "synthetic_train_size": 1024, "synthetic_test_size": 256,
                 "use_native_pipeline": False},
        "model": {"compute_dtype": "float32"},
        "train": {"max_steps": 10, "log_every_steps": 5,
                  "save_interval_steps": 0, "save_results_period": 0},
    }
    for k, v in overrides.items():
        if isinstance(v, dict) and k in d:
            d[k].update(v)
        else:
            d[k] = v
    return ExperimentConfig.from_dict(d)
