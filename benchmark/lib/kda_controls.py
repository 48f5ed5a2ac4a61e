#!/usr/bin/env python3
"""What a serving cell's ``correct`` can see of a delta-rule layer's
matrix state and of a group-limited router, asked at the cell's own size:
the check a run makes before its window
(``lib/serving.py::check_decode_against_reference``, through the model
record's own decode session) on the sound program and on the same program
with one fault each of the mechanisms this configuration brings
(``lib/ssm_controls.py`` has a state-space layer's, ``lib/decode_controls.py``
the faults of weights and of the attention step). The reference's side is
left as it is. A control that comes out ``ok`` is a fault the check is
blind to at this configuration's weights; ``PERF.md`` keeps the readings.
From the root of a checkout, on the machine with the chip:

    python3 benchmark/lib/kda_controls.py --workload <cell> --seed <n> [<n> ...]

One line a (seed, control): the check's numbers, ``ok`` and
``failed_by``. Nothing here is timed.

The controls (each a patch of the PROGRAM while the session is built and
driven; the program has no option that does any of this):

* ``state_not_advanced``: the decode step returns what a slot keeps of
  its sequence as it was given it, the matrix state and the convolutions'
  tail (``ops/kda.py::mixer_step``): every token after the prompt is
  computed soundly from the prompt's end state, and from no token decoded
  since.
* ``decay_dropped``: ``e^g = 1`` for every channel at every token
  (``ops/kda.py::gate`` answers zeros), prefill and step alike: a state
  that never forgets.
* ``delta_term_dropped``: ``S_t = Diag(e^g) S_{t-1} + beta k v^T`` without
  ``- k k^T S``: plain gated linear attention under this model's name,
  prefill (token by token) and step alike.
* ``conv_tail_dropped``: the prefill's convolution tail is not kept (the
  slot is written zeros for it): the first three decoded tokens convolve
  with nothing before them.
* ``stale_slot_state``: a prefill ADDS its end state to what the slot's
  last occupant left (``kv_cache.write_slot_state``); the session's slot
  is first used by another prompt of the longest length the replica
  admits, as a replica's slots are by the sequences before.
* ``group_limit_dropped``: the router takes its 8 best of all 512 experts
  (``ops/moe.py::_group_limited`` masks nothing).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import cell as cell_lib, serving  # noqa: E402
from benchmark.lib.decode_controls import _patched, failed_by  # noqa: E402
from benchmark.lib.ssm_controls import (_conv_tail_dropped,  # noqa: E402
                                        _stale_slot_state, _used_before)

CONTROLS = ("sound", "state_not_advanced", "decay_dropped",
            "delta_term_dropped", "conv_tail_dropped", "stale_slot_state",
            "group_limit_dropped")


def _state_not_advanced():
    from distributedmnist_tpu.ops import kda
    sound = kda.mixer_step

    def stuck(h, blk, s, tail, live, **how):
        out, _, _ = sound(h, blk, s, tail, live, **how)
        return out, s, tail
    return _patched(kda, "mixer_step", stuck)


def _decay_dropped():
    import jax.numpy as jnp

    from distributedmnist_tpu.ops import kda
    sound = kda.gate
    return _patched(kda, "gate", lambda *a, **k: jnp.zeros_like(
        sound(*a, **k)))


@contextlib.contextmanager
def _delta_term_dropped():
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops import kda

    def step(q, k, v, g, beta, s):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        q, k, v, g, beta = map(f32, (q, k, v, g, beta))
        new = (jnp.exp(g)[..., None] * f32(s)
               + k[..., None] * (beta[..., None] * v)[..., None, :])
        o = jnp.sum(q[..., None] * new, axis=-2) * q.shape[-1] ** -0.5
        return o, new.astype(s.dtype)

    def over_a_prompt(q, k, v, g, beta, s0=None, lengths=None, *, chunk=0):
        del chunk
        g, beta = kda._masked(g.astype(jnp.float32),
                              beta.astype(jnp.float32), lengths)
        b, _, h, d = q.shape
        if s0 is None:
            s0 = jnp.zeros((b, h, d, v.shape[-1]), jnp.float32)
        time_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
        s_end, o = jax.lax.scan(
            lambda s, x: step(*x, s)[::-1], s0,
            tuple(map(time_first, (q, k, v, g, beta))))
        return jnp.moveaxis(o, 0, 1), s_end

    with _patched(kda, "step", step), _patched(kda, "chunked",
                                               over_a_prompt):
        yield


def _group_limit_dropped():
    from distributedmnist_tpu.ops import moe
    return _patched(moe, "_group_limited", lambda biased, *_: biased)


def _controls() -> dict:
    """name → (what is patched while the session is built and driven,
    what is done to the model record)."""
    same = lambda model: model  # noqa: E731
    return {"sound": (contextlib.nullcontext, _used_before),
            "state_not_advanced": (_state_not_advanced, same),
            "decay_dropped": (_decay_dropped, same),
            "delta_term_dropped": (_delta_term_dropped, same),
            "conv_tail_dropped": (_conv_tail_dropped, same),
            "stale_slot_state": (_stale_slot_state, _used_before),
            "group_limit_dropped": (_group_limit_dropped, same)}


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
    print(json.dumps({"event": "kda_controls", "workload": args.workload,
                      "seeds": args.seed, "sound_refused_at": unsound,
                      "controls_passed": blind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
