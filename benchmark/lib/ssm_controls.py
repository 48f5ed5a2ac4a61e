#!/usr/bin/env python3
"""What a serving cell's ``correct`` can see of state that is a
SEQUENCE's, asked at the cell's own size: the check a run makes before
its window (``lib/serving.py::check_decode_against_reference``, through
the model record's own decode session) on the sound program and on the
same program with one fault each of the mechanism a state-space layer
brings (``lib/decode_controls.py`` has the faults of weights and of the
attention step; its fault in the step is the default session's alone, so
the PR that brings a session brings these). The reference's side is left
as it is. A control that comes out ``ok`` is a fault the check is blind
to at this configuration's weights; ``PERF.md`` keeps the readings. From
the root of a checkout, on the machine with the chip:

    python3 benchmark/lib/ssm_controls.py --workload <cell> --seed <n> [<n> ...]

One line a (seed, control): the check's numbers, ``ok`` and
``failed_by``. Nothing here is timed.

The controls (each a patch of the PROGRAM while the session is built and
driven; the program has no option that does any of this):

* ``state_not_advanced``: the decode step returns what a slot keeps of
  its sequence as it was given it, the recurrent state and the
  convolution's tail (``ops/ssm.py::mixer_step``; what a loop that does
  not take the step's arrays back serves): every token after the prompt
  is computed soundly from the prompt's end state, and from no token
  decoded since.
* ``conv_tail_dropped``: the prefill's convolution tail is not kept (the
  slot is written zeros for it): the first three decoded tokens convolve
  with nothing before them.
* ``stale_slot_state``: a prefill ADDS its end state to what the slot's
  last occupant left (``kv_cache.write_slot_state``); the session's slot
  is first used by another prompt of the longest length the replica
  admits, as a replica's slots are by the sequences before.
* ``bfloat16_state``: the nearest precision below the one the
  configuration states for the recurrent state (float32): the slot state
  kept in bfloat16, read and rounded at every step. Reported whichever
  way it falls.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import cell as cell_lib, serving  # noqa: E402
from benchmark.lib.decode_controls import _patched, failed_by  # noqa: E402

CONTROLS = ("sound", "state_not_advanced", "conv_tail_dropped",
            "stale_slot_state", "bfloat16_state")


def _state_not_advanced():
    from distributedmnist_tpu.ops import ssm
    sound = ssm.mixer_step

    def stuck(h, blk, s, tail, live, *, norm):
        out, _, _ = sound(h, blk, s, tail, live, norm=norm)
        return out, s, tail
    return _patched(ssm, "mixer_step", stuck)


def _conv_tail_dropped():
    import jax.numpy as jnp

    from distributedmnist_tpu.servesvc import decode
    sound = decode.store_prompt

    def dropped(cache, state, slot, table, outs, plen):
        sound(cache, state, slot, table,
              (*outs[:4], jnp.zeros_like(outs[4])), plen)
    return _patched(decode, "store_prompt", dropped)


def _stale_slot_state():
    import jax

    from distributedmnist_tpu.servesvc import kv_cache

    sound = kv_cache.write_slot_state

    def added(state, tail, new_state, new_tail, slot, *, row=0):
        held = jax.numpy.stack([jax.lax.dynamic_slice_in_dim(s, slot, 1, 0)
                                for s in state])
        return sound(state, tail,
                     new_state[:, row:row + 1] + held.astype(new_state.dtype),
                     new_tail[:, :, row:row + 1], slot)
    return _patched(kv_cache, "write_slot_state", added)


def _bfloat16_state():
    import jax.numpy as jnp

    from distributedmnist_tpu.servesvc import decode
    return _patched(decode, "SlotState", functools.partial(
        decode.SlotState, state_dtype=jnp.bfloat16))


def _used_before(model):
    """The record with a session whose slot another prompt has been
    through, as a replica's slots have."""
    import numpy as np

    def session(params, dcfg, cache_dtype):
        ses = model.decode_session(params, dcfg, cache_dtype)
        ses.prefill(np.arange(1, dcfg.max_prompt_len + 1, dtype=np.int32)
                    % 251)
        return ses
    return dataclasses.replace(model, decode_session=session)


def _controls() -> dict:
    """name → (what is patched while the session is built and driven,
    what is done to the model record)."""
    same = lambda model: model  # noqa: E731
    return {"sound": (contextlib.nullcontext, _used_before),
            "state_not_advanced": (_state_not_advanced, same),
            "conv_tail_dropped": (_conv_tail_dropped, same),
            "stale_slot_state": (_stale_slot_state, _used_before),
            "bfloat16_state": (_bfloat16_state, same)}


def check_control(name: str, model_cfg, params, dcfg, cell, seed: int,
                  get_model) -> dict:
    import jax.numpy as jnp
    patch, edit = _controls()[name]
    with patch():
        model = edit(get_model(model_cfg))
        if getattr(model, "decode_session", None) is None:
            raise cell_lib.BenchmarkError(
                f"{cell.name}: the model record brings no decode session; "
                "these controls fault a state that is a sequence's")
        said: dict = {}
        check = serving.check_decode_against_reference(
            model, params, dcfg, jnp.dtype(model_cfg.compute_dtype),
            model_cfg.vocab_size, cell, seed, said=said)
    return {"control": name, "seed": seed, **check,
            "failed_by": failed_by(check), "session": said}


def run(workload: str, seeds: list[int], controls=CONTROLS) -> list[dict]:
    import jax
    from distributedmnist_tpu.core.compile_cache import \
        enable_persistent_cache
    from distributedmnist_tpu.core.config import (ExperimentConfig,
                                                  effective_model_config)
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import resolved_param_dtype

    enable_persistent_cache()
    cell = cell_lib.load_cell(workload)
    rows = []
    for seed in seeds:
        # the weights a run of this seed serves (lib/serving.py)
        cfg = ExperimentConfig.from_dict(serving.experiment(
            cell, SimpleNamespace(seed=seed, workdir=Path("unused"))))
        model_cfg = effective_model_config(cfg, serving=True)
        stored = resolved_param_dtype(cfg)
        params = jax.jit(lambda key: jax.tree.map(
            lambda p: p.astype(stored), get_model(model_cfg).init(key)))(
                jax.random.PRNGKey(seed))
        for name in controls:
            row = check_control(name, model_cfg, params, cfg.decode, cell,
                                seed, get_model)
            print(json.dumps(row), flush=True)
            rows.append(row)
        del params
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--control", nargs="+", default=list(CONTROLS),
                    choices=CONTROLS)
    args = ap.parse_args()
    rows = run(args.workload, args.seed, args.control)
    blind = sorted({r["control"] for r in rows
                    if r["control"] != "sound" and r["ok"]})
    unsound = [r["seed"] for r in rows if r["control"] == "sound"
               and not r["ok"]]
    print(json.dumps({"event": "ssm_controls", "workload": args.workload,
                      "seeds": args.seed, "sound_refused_at": unsound,
                      "controls_passed": blind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
