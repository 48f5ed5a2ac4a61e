"""chip_smoke.py on the CPU: the script refuses without a TPU, its phase
functions pass their numeric checks at tiny sizes on the 8-device test
mesh (Pallas interpreted), and a failing phase fails the run. The TPU
gate and the Mosaic-call assertions sit in ``main()``, which a CPU can
only watch refuse."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

TINY_MODEL = {"name": "transformer", "model_dim": 64, "num_layers": 2,
              "num_heads": 4, "seq_len": 64, "vocab_size": 32,
              "attention_impl": "flash", "compute_dtype": "float32"}


def _run_script(args, cwd, tmp_path, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), **env})


def test_refuses_without_a_tpu(tmp_path):
    out = _run_script(["chip_smoke.py"], REPO, tmp_path)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "platform='cpu'" in out.stderr  # names what it found
    assert out.stdout == ""  # no result, not even a phase line


def test_refuses_outside_a_checkout(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the
    repo: non-zero exit, no result."""
    shutil.copy2(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(["chip_smoke.py"], tmp_path, tmp_path, PYTHONPATH="")
    assert out.returncode == 3, out.stderr[-2000:]
    assert "not here" in out.stderr and out.stdout == ""


def test_a_failing_phase_fails_the_run(tmp_path):
    """Past the gate (faked: a CPU cannot pass it), a phase that raises
    ends the script non-zero with no summary line."""
    script = (
        "import chip_smoke as cs\n"
        "cs._gate = lambda: {'device': {'platform': 'tpu', 'kind': 'fake',"
        " 'count': 1}, 'versions': {}, 'env': {}}\n"
        "def boom(**kw):\n"
        "    raise RuntimeError('phase failed')\n"
        "cs.phase_train = boom\n"
        "cs.main()\n")
    out = _run_script(["-c", script], REPO, tmp_path)
    assert out.returncode not in (0, 2, 3), out.stderr[-2000:]
    assert "phase failed" in out.stderr
    lines = [json.loads(l) for l in out.stdout.splitlines()]
    assert [l.get("phase") for l in lines] == ["gate"]
    assert not any(l.get("ok") for l in lines)


def test_last_line_is_the_result_and_nothing_else(tmp_path):
    """With the gate and the phases faked to pass, main() ends its
    stdout with ``{"ok": true, "device": {platform, kind, count}}`` —
    exactly those keys — after the ``"claim": null`` summary line."""
    script = (
        "import chip_smoke as cs\n"
        "dev = {'platform': 'tpu', 'kind': 'fake', 'count': 1}\n"
        "cs._gate = lambda: {'device': dev, 'versions': {}, 'env': {}}\n"
        "n = {'total': 8, 'forward': 4, 'backward': 4}\n"
        "z = {'total': 0, 'forward': 0, 'backward': 0}\n"
        "arm = lambda step: {'prefill_buckets': [8, 16],"
        " 'prefill_mosaic_calls': n, 'step_mosaic_calls': step}\n"
        "cs.phase_train = lambda **kw: {'mosaic_calls': n, 'train_dir': '.',"
        " 'first_loss': 2.0, 'last_loss': 1.0}\n"
        "cs.phase_cnn_quorum = lambda **kw: {}\n"
        "cs.phase_serve = lambda **kw: {'token_agreement': 1.0,"
        " 'arms': {'dense': arm(z), 'paged': arm(n)}}\n"
        "cs.phase_kernels = lambda **kw: {'flash_mosaic_calls': n,"
        " 'paged_mosaic_calls': n, 'latent_decode':"
        " {'auto_arm': 'paged', 'paged_calls': 2}}\n"
        "cs.main()\n")
    out = _run_script(["-c", script], REPO, tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()]
    assert [l.get("phase") for l in lines] == [
        "gate", "train", "cnn_quorum", "serve", "kernels", "summary", None]
    assert lines[-2]["claim"] is None
    assert lines[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "fake", "count": 1}}


@pytest.fixture(scope="module")
def scratch_cache(tmp_path_factory):
    """The phases run the CLI's bring-up, which applies the compile-cache
    rule to this process: keep it off the checkout's real cache, and
    hand the next test module a process that never enabled one. (jax
    read the variable at import, so nothing is cached at all.)"""
    from distributedmnist_tpu.core import compile_cache as cc
    mp = pytest.MonkeyPatch()
    mp.setenv(cc.CACHE_DIR_ENV, str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()
    cc._applied, cc._enabled_dir = False, None


def test_phases_pass_tiny_on_the_cpu_mesh(tmp_path, scratch_cache):
    train = chip_smoke.phase_train(tmp_path, model=TINY_MODEL,
                                   per_device_batch=2, steps=8, lr=0.3)
    assert train["steps"] == 8 and train["global_batch"] == 16
    assert train["last_loss"] < train["first_loss"]
    assert train["compile_record"]["source"] == "compiled"
    assert train["layout"] == {"param_leaf_devices": 8, "batch_shards": 8,
                               "rows_per_shard": 2}
    assert train["loader"] == "BatchIterator"  # the Python pipeline
    # interpreted here: exactly what main()'s assertion refuses on a chip
    assert train["mosaic_calls"]["total"] == 0

    cnn = chip_smoke.phase_cnn_quorum(tmp_path, per_device_batch=8,
                                      steps=2, compute_dtype="float32")
    assert cnn["quorum_k"] == 7 and cnn["num_contributors"] == 7
    assert sum(cnn["flags_last_step"]) == 7

    serve = chip_smoke.phase_serve(
        Path(train["train_dir"]), tmp_path, prompt_lens=[3, 11, 6],
        max_new_tokens=4, max_prompt_len=16, block_size=8, num_blocks=32,
        decode_slots=2, concurrency=2)
    assert serve["requests"] == 6
    assert serve["arms"]["dense"]["prefill_buckets"] == [4, 8, 16]
    assert serve["arms"]["paged"]["tokens_streamed"] == 12
    assert serve["identical"] and serve["token_agreement"] == 1.0  # f32

    kern = chip_smoke.phase_kernels(model=TINY_MODEL, batch=1, block_size=8,
                                    context=20, tol=1e-4,
                                    prompt_lens=(3, 11, 6), steps=6)
    assert kern["decode_step_argmax_equal"]
    assert kern["paged_vs_dense"] <= 1e-4
    # a CPU stores every head's rows whole: the arm proves its plumbing
    wide = kern["wide_cache_rows"]
    assert wide["stored_head_dim"] == wide["head_dim"] == 64
    assert wide["logits_bit_equal"] and wide["cache_bytes_equal"]
    # the paged arm's step writes the token's rows inside the kernel: what
    # the scatter leaves, to the bit, and across a block's edge (3 + 6 > 8)
    written = kern["rows_written_in_the_kernel"]
    assert set(written["paged_equals_scattered_to_the_bit"].values()) == {
        True}
    assert written["null_block_untouched"]
    assert written["dense_first_layer_rows_equal_to_the_bit"]
    assert written["dense_token_agreement"] == 1.0  # f32
    assert written["slots"] == 5 and written["steps"] == 6
    assert (written["head_dim"], written["stored_head_dim"]) == (64, 128)
    assert written["rows_beside_the_head_are_zero"]
    # the absorbed decode step against the expanded forward, float32
    latent = kern["latent_decode"]
    assert latent["steps"] == 8 and latent["absorbed_vs_expanded"] <= 1e-4
    assert latent["cache_arrays"] == [[2, 33, 8, 128], [2, 33, 8, 8]]
    # the latent paged kernel, interpreted here, against the gather; on
    # a chip ``auto`` takes it and main() counts its Mosaic calls
    assert latent["paged_vs_dense"] <= 1e-4 and latent["layer0_rows_equal"]
    assert (latent["auto_arm"], latent["paged_calls"]) == ("gather", 0)


def test_phase_check_failure_raises(tmp_path, scratch_cache):
    """The checks are not decoration: a learning rate that makes the
    loss rise fails the train phase."""
    with pytest.raises(chip_smoke.SmokeFailure, match="loss did not fall"):
        chip_smoke.phase_train(tmp_path, model=TINY_MODEL,
                               per_device_batch=2, steps=3, lr=-0.3)


def test_simulated_mesh_config_is_refused(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="simulate_devices"):
        chip_smoke._run_trainer(
            tmp_path, "x", per_device_batch=1, steps=1, log_every=1,
            train_batches=1, data={}, mesh={"simulate_devices": 8})
    assert not any(tmp_path.iterdir())  # refused before anything is built
