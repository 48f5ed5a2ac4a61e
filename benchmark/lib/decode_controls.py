#!/usr/bin/env python3
"""What a serving cell's ``correct`` can see, asked at the cell's own
size: ``lib/serving.py::check_decode_against_reference`` (the comparison
a run makes before its window, through the decode session a run drives:
``lib/cell.py``) on the sound program and on the same program with one
fault each, the reference's side left as it is. A
control that comes out ``ok`` is a fault the cell's check is blind to at
this configuration's weights; ``PERF.md`` keeps the readings. A fault in
the weights is data: the session is the sound one, handed a tree with
one leaf of zeros, and the reference reads the sound tree
(``reference_params``; the two trees share every other buffer), so it
faults a record's own ``decode_session`` as it faults the default one. A
fault in the step (``masked_newest_token``) wraps the record's
``decode_step`` export, which only the default session calls: a record
that brings its own session brings that control with it, and is refused
here. A fault written INTO a program (a
leaf times zero under the jit) lets the compiler fold the export with and
without its routing output into two float graphs, and the flag's
difference then reads a router's flipped near tie (0.02-0.39 on the chip,
PR 34) and not the fault. From the root of a checkout, on the machine with the chip:

    python3 benchmark/lib/decode_controls.py --workload <cell> --seed <n> [<n> ...]

One line a (seed, control): the check's numbers, ``ok``, and ``failed_by``
(which of the cell's limits refused it). Nothing here is timed.

The controls:

* ``bfloat16_residual``: the nearest precision below what the program
  computes in: the residual between sublayers in the compute dtype where
  the block keeps it in float32 (``transformer.FLOAT32``; the router's
  product is float32 inside ``ops/moe.py`` and has no switch to pull). A
  program with no float32 residual runs unchanged under it.
* ``masked_newest_token``: the decode step attends to the cache without
  the token it has just written (``lengths - 1``): one wrong mask in the
  cache-side attention of every layer, the prefill sound.
* ``attention_sublayer_dropped``: the program's middle layer adds nothing
  of its attention to the residual (its output norm's scale zeros in the
  weights the PROGRAM is handed; a block without output norms: ``wo``):
  one sublayer at fault, prefill and step alike.
* ``held_experts_dropped``: the program's last routed layer adds nothing
  of the experts it holds (their ``w_down`` zeros in the weights the
  program is handed), the shared expert and the router sound: what the cell's
  ``correct`` sees of the grouped product alone. A model that routes
  nothing runs unchanged.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import cell as cell_lib, compare, serving  # noqa: E402


@contextlib.contextmanager
def _patched(obj, name: str, value):
    was = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, was)


def _with_leaf(params, at: int, path: tuple, leaf):
    """``params`` with ``blocks[at][path...]`` replaced by ``leaf``; every
    other buffer shared."""
    def put(tree, keys):
        return leaf if not keys else {
            **tree, keys[0]: put(tree[keys[0]], keys[1:])}
    blocks = list(params["blocks"])
    blocks[at] = put(blocks[at], path)
    return {**params, "blocks": type(params["blocks"])(blocks)}


def _drop_middle_attention(params):
    import jax.numpy as jnp
    at = len(params["blocks"]) // 2
    path = (("ln1_out", "scale") if "ln1_out" in params["blocks"][at]
            else ("wo",))
    leaf = params["blocks"][at]
    for key in path:
        leaf = leaf[key]
    return _with_leaf(params, at, path, jnp.zeros_like(leaf))


def _drop_last_held_experts(params):
    import jax.numpy as jnp
    at = max((i for i, b in enumerate(params["blocks"]) if "experts" in b),
             default=None)
    if at is None:
        return params
    return _with_leaf(params, at, ("experts", "w_down"), jnp.zeros_like(
        params["blocks"][at]["experts"]["w_down"]))


def _mask_newest(model):
    if getattr(model, "decode_session", None) is not None:
        raise cell_lib.BenchmarkError(
            "masked_newest_token wraps the record's decode_step, which a "
            "decode_session of the record's own does not call: the PR "
            "that brings the session brings this control")

    def step(params, tokens, positions, k, v, tables, lengths, **kw):
        return model.decode_step(params, tokens, positions, k, v, tables,
                                 (lengths - 1).clip(0), **kw)
    return dataclasses.replace(model, decode_step=step)


def _controls() -> dict:
    """name → (what is patched while the model is built and checked, what
    is done to the model record, what to the weights the program is
    handed)."""
    from distributedmnist_tpu.models import transformer as t
    same = lambda x: x  # noqa: E731
    nothing = contextlib.nullcontext
    return {
        "sound": (nothing, same, None),
        "bfloat16_residual":
            (lambda: _patched(t, "FLOAT32", t.PLAIN), same, None),
        "masked_newest_token": (nothing, _mask_newest, None),
        "attention_sublayer_dropped": (nothing, same, _drop_middle_attention),
        "held_experts_dropped": (nothing, same, _drop_last_held_experts),
    }


CONTROLS = ("sound", "bfloat16_residual", "masked_newest_token",
            "attention_sublayer_dropped", "held_experts_dropped")


def failed_by(check: dict) -> list[str]:
    """The cell's limits that refused ``check``, by the names a run's
    ``compared`` uses."""
    out = []
    if not check["decode_logits_max_rel_err"] <= serving.DECODE_LOGITS_TOL:
        out.append("decode_logits_max_rel_err")
    if "routing_ok" in check:
        if not check["routing_slack_max"] <= compare.ROUTING_SLACK_MAX:
            out.append("routing_slack_max")
        if not check["routing_agreement"] >= compare.ROUTING_AGREEMENT_MIN:
            out.append("routing_agreement")
        if check["routing_ids_valid"] != 1.0:
            out.append("routing_ids_valid")
        if check["routing_flag_diff"] != 0.0:
            out.append("routing_flag_diff")
    return out


def check_control(name: str, model_cfg, params, dcfg, cell, seed: int,
                  get_model) -> dict:
    """``check_decode_against_reference`` of the model ``model_cfg``
    names, built and checked under control ``name``."""
    import jax.numpy as jnp
    patch, edit, fault = _controls()[name]
    with patch():
        model = edit(get_model(model_cfg))
        said: dict = {}
        check = serving.check_decode_against_reference(
            model, params if fault is None else fault(params), dcfg,
            jnp.dtype(model_cfg.compute_dtype), model_cfg.vocab_size, cell,
            seed, reference_params=params, said=said)
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
    print(json.dumps({"event": "decode_controls", "workload": args.workload,
                      "seeds": args.seed, "sound_refused_at": unsound,
                      "controls_passed": blind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
