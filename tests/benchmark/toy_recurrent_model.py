"""The stand-in program for ``toy_recurrent.py``: a model record whose
only decode export is ``decode_session`` (``benchmark/lib/cell.py`` has
the contract). Its state is one ``[layers, d]`` float32 row a slot, held
whole however long the sequence: allocated with the session, reset by a
prefill, advanced by a step; no cache of rows a token, no block table,
no ``decode_cache_shape``. Written from the equations, importing nothing
of the reference."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp


def _advance(params, dtype, state, tokens):
    """``state [layers, d]`` float32 through ``tokens [n]``: the new
    state and the last position's logits."""
    cast = lambda a: a.astype(dtype)  # noqa: E731
    x = cast(params["embed"])[tokens]
    out = []
    for li, layer in enumerate(params["layers"]):
        keep = jax.nn.sigmoid(layer["decay"].astype(jnp.float32))
        u = (x @ cast(layer["w_in"])).astype(jnp.float32)

        def one(h, u_t):
            h = keep * h + u_t
            return h, h
        last, hs = jax.lax.scan(one, state[li], u)
        out.append(last)
        x = x + cast(jnp.tanh(hs)) @ cast(layer["w_out"])
    return jnp.stack(out), (x[-1] @ cast(params["embed"]).T).astype(
        jnp.float32)


class Session:
    """One sequence in slot 0 of ``[slots, layers, d]``. ``forgetful``:
    the control, a step that starts from an empty state (what a manager
    that frees or resets a slot's arrays between two steps serves)."""

    def __init__(self, params, dcfg, cache_dtype, forgetful: bool = False):
        self.params, self.forgetful = params, forgetful
        layers, d = len(params["layers"]), params["embed"].shape[1]
        self.state = jnp.zeros((dcfg.decode_slots, layers, d), jnp.float32)
        self._run = jax.jit(
            lambda p, s, t: _advance(p, jnp.dtype(cache_dtype), s, t))
        self._stepped = None         # (position, the state it began from)
        self.said = {"session": "toy_recurrent",
                     "state_arrays": [list(self.state.shape)]}

    def prefill(self, prompt, return_routing: bool = False):
        assert not return_routing, "nothing is routed here"
        state, row = self._run(self.params, jnp.zeros_like(self.state[0]),
                               jnp.asarray(prompt, jnp.int32))
        self.state = self.state.at[0].set(state)
        return row

    def step(self, token: int, position: int, return_routing: bool = False):
        assert not return_routing, "nothing is routed here"
        # asked again at the position just stepped: from the same state
        before = (self._stepped[1] if self._stepped
                  and self._stepped[0] == position else self.state[0])
        self._stepped = (position, before)
        if self.forgetful:
            before = jnp.zeros_like(before)
        state, row = self._run(self.params, before,
                               jnp.asarray([token], jnp.int32))
        self.state = self.state.at[0].set(state)
        return row


def record(forgetful: bool = False):
    """The model record as far as the serving check reads one."""
    return types.SimpleNamespace(
        name="toy_recurrent", decode_prefill=None, decode_step=None,
        decode_cache_shape=None,
        decode_session=lambda params, dcfg, cache_dtype: Session(
            params, dcfg, cache_dtype, forgetful))
