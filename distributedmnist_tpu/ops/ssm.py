"""The selective state-space mixer (Mamba, arXiv:2312.00752 §3; with the
three norms Jamba adds, arXiv:2403.19887): a layer with no attention,
whose state is a SEQUENCE's and not a token's.

For a token ``t`` of a sequence, ``E`` channels, a state of ``N`` a
channel, a convolution ``K`` taps wide::

    [u, z] = h W_in                                   (d -> 2E)
    u_t   <- silu(b_c + sum_j w_c[j] * u_{t-K+1+j})   (depth-wise, causal)
    [dt, B, C] = u W_x                                (E -> R + N + N)
    dt, B, C each through an RMSNorm of its own width
    D_t   = softplus(dt W_dt + b_dt)                  (R -> E)
    S_t   = exp(D_t (x) A) * S_{t-1} + (D_t * u_t) (x) B_t      [N, E]
    y_t   = C_t . S_t + D * u_t
    out   = (y * silu(z)) W_out                       (E -> d)

``A = -exp(a_log)``. The state ``S`` is kept ``[N, E]``, the channels in
the lanes (the equations' ``[E, N]`` transposed: one layout, no other
difference), in float32 whatever the products are computed in: a
recurrence over thousands of steps. What a decode replica keeps of a
sequence is ``S`` after its last token and the ``K - 1`` inputs of the
convolution before its next one (the *tail*).

Three functions carry the recurrence, plain XLA:

* :func:`causal_conv`: the convolution over a sequence that starts from
  a tail (zeros: the start of a sequence);
* :func:`selective_scan`: the recurrence over a sequence, chunked over
  time: a chunk's ``exp(D (x) A)`` and ``(D u) (x) B`` are made at once,
  ``[chunk, N, E]`` each, and a loop over the chunk's tokens carries
  ``S``; ``[T, N, E]`` is never held whole (671 MB at T = 2,048 and the
  published widths). A position at or past ``lengths`` leaves ``S`` as
  it is: a prompt padded to a bucket hands on the state of its last
  real token;
* :func:`selective_step`: one token a slot, ``S`` advanced where it
  lies (the caller donates it).

:func:`mixer` and :func:`mixer_step` are the whole sublayer body over a
block's leaves (``w_in`` [d, 2, E], ``conv_w`` [K, E], ``conv_b``,
``w_x`` [E, R + 2N], ``dt_norm``/``b_norm``/``c_norm``, ``w_dt`` [R, E],
``b_dt``, ``a_log`` [N, E], ``d_skip``, ``w_out`` [E, d]), under the
device scopes ``ssm`` > ``ssm_conv``, ``ssm_scan`` (a sequence) and
``state_update`` (a token): obsv/spans.py names their readers. (Not
``scan`` and ``conv``: jax names a ``lax.scan``'s operations ``.../scan/...``,
so a scope of that name would claim every loop of every program.)
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

Params = dict[str, Any]

#: tokens a chunk of :func:`selective_scan`: two float32 ``[chunk, N, E]``
#: arrays a sequence (21 MB each at the published widths)
SCAN_CHUNK = 64


def causal_conv(u: jax.Array, w: jax.Array, b: jax.Array,
                tail: jax.Array | None = None) -> jax.Array:
    """``silu(b + sum_j w[j] * u_{t-K+1+j})`` over ``u`` [batch, T, E],
    depth-wise; ``w`` [K, E], ``b`` [E]; ``tail`` [batch, K - 1, E] is
    what came before position 0 (None: zeros, the start of a sequence).
    In float32, returned in ``u``'s dtype."""
    taps = w.shape[0]
    if tail is None:
        tail = jnp.zeros((u.shape[0], taps - 1, u.shape[2]), u.dtype)
    seq = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    t = u.shape[1]
    out = b.astype(jnp.float32) + sum(
        w[j].astype(jnp.float32) * seq[:, j:j + t].astype(jnp.float32)
        for j in range(taps))
    return jax.nn.silu(out).astype(u.dtype)


def conv_tail(u: jax.Array, lengths: jax.Array, taps: int) -> jax.Array:
    """The ``taps - 1`` inputs of the convolution that end at position
    ``lengths - 1`` of ``u`` [batch, T, E] (zeros before position 0):
    what the next token's convolution reads, whatever ``u`` was padded
    to. [batch, taps - 1, E]."""
    at = lengths[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]
    rows = jnp.take_along_axis(u, jnp.maximum(at, 0)[:, :, None], axis=1)
    return jnp.where((at >= 0)[:, :, None], rows, jnp.zeros_like(rows))


def selective_scan(u: jax.Array, delta: jax.Array, a: jax.Array,
                   b: jax.Array, c: jax.Array, d: jax.Array,
                   s0: jax.Array | None = None,
                   lengths: jax.Array | None = None, *,
                   chunk: int = SCAN_CHUNK) -> tuple[jax.Array, jax.Array]:
    """The recurrence over a sequence. ``u``, ``delta`` [batch, T, E];
    ``a`` [N, E] (negative); ``b``, ``c`` [batch, T, N]; ``d`` [E];
    ``s0`` [batch, N, E] float32 (None: zeros); ``lengths`` [batch]
    (None: T). Returns ``y`` [batch, T, E] float32 and the state after
    position ``lengths - 1``, float32."""
    batch, t, e = u.shape
    n = a.shape[0]
    if s0 is None:
        s0 = jnp.zeros((batch, n, e), jnp.float32)
    if lengths is None:
        lengths = jnp.full((batch,), t, jnp.int32)
    chunk = min(chunk, t)
    pad = -t % chunk
    f32 = lambda x: jnp.pad(  # noqa: E731
        x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    # [chunks, chunk, batch, width]: time outermost for the two loops
    split = lambda x: f32(x).reshape(  # noqa: E731
        batch, -1, chunk, x.shape[-1]).transpose(1, 2, 0, 3)
    uf, df, bf, cf = split(u), split(delta), split(b), split(c)
    starts = jnp.arange(uf.shape[0]) * chunk
    af = a.astype(jnp.float32)

    @jax.checkpoint
    def one_chunk(s, xs):
        uc, dc, bc, cc, start = xs
        live = ((start + jnp.arange(chunk))[:, None]
                < lengths[None, :])[:, :, None, None]
        decay = jnp.where(live, jnp.exp(dc[:, :, None, :] * af), 1.0)
        drive = jnp.where(live, (dc * uc)[:, :, None, :] * bc[..., None],
                          0.0)

        def one_token(s, x):
            decay_t, drive_t, c_t = x
            s = decay_t * s + drive_t
            return s, jnp.sum(s * c_t[..., None], axis=1)

        return lax.scan(one_token, s, (decay, drive, cc))

    longest = jnp.max(lengths)

    def chunk_or_nothing(s, xs):
        # a chunk wholly past every sequence's length (a prompt's bucket
        # beyond the prompt) changes nothing and is not computed
        return lax.cond(xs[-1] < longest, one_chunk,
                        lambda s, xs: (s, jnp.zeros((chunk, batch, e),
                                                    jnp.float32)), s, xs)

    s_end, y = lax.scan(chunk_or_nothing, s0.astype(jnp.float32),
                        (uf, df, bf, cf, starts))
    y = y.reshape(-1, batch, e).transpose(1, 0, 2)[:, :t]
    return y + d.astype(jnp.float32) * u.astype(jnp.float32), s_end


def selective_step(u: jax.Array, delta: jax.Array, a: jax.Array,
                   b: jax.Array, c: jax.Array, d: jax.Array,
                   s: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot: ``u``, ``delta`` [slots, E]; ``b``, ``c``
    [slots, N]; ``s`` [slots, N, E]. Returns ``y`` [slots, E] float32
    and the new state in ``s``'s dtype (the products in float32)."""
    uf, df = u.astype(jnp.float32), delta.astype(jnp.float32)
    new = (jnp.exp(df[:, None, :] * a.astype(jnp.float32))
           * s.astype(jnp.float32)
           + (df * uf)[:, None, :] * b.astype(jnp.float32)[:, :, None])
    # what is read back is what is kept: a state stored narrower than
    # float32 answers from the stored values, as its next step will
    new = new.astype(s.dtype)
    y = jnp.sum(new.astype(jnp.float32)
                * c.astype(jnp.float32)[:, :, None], axis=1)
    return y + d.astype(jnp.float32) * uf, new


def _selection(u: jax.Array, blk: Params, norm: Callable):
    """``u`` [..., E] after the convolution -> (delta [..., E] float32,
    B [..., N], C [..., N])."""
    n = blk["a_log"].shape[0]
    rank = blk["w_dt"].shape[0]
    x = u @ blk["w_x"]
    dt = norm(x[..., :rank], blk["dt_norm"])
    b = norm(x[..., rank:rank + n], blk["b_norm"])
    c = norm(x[..., rank + n:], blk["c_norm"])
    delta = jax.nn.softplus((dt @ blk["w_dt"]).astype(jnp.float32)
                            + blk["b_dt"].astype(jnp.float32))
    return delta, b, c


def _a(blk: Params) -> jax.Array:
    return -jnp.exp(blk["a_log"].astype(jnp.float32))


@jax.named_scope("ssm")
def mixer(h: jax.Array, blk: Params, *, norm: Callable,
          lengths: jax.Array | None = None, return_state: bool = False):
    """The mixer over sequences ``h`` [batch, T, d] (normed) from an
    empty state: the sublayer's output [batch, T, d], and with
    ``return_state`` the state after position ``lengths - 1`` [batch, N,
    E] float32 and the convolution's tail there [batch, K - 1, E]."""
    uz = jnp.einsum("bsd,dte->bste", h, blk["w_in"])
    u_in, z = uz[:, :, 0], uz[:, :, 1]
    with jax.named_scope("ssm_conv"):
        u = causal_conv(u_in, blk["conv_w"], blk["conv_b"])
    delta, b, c = _selection(u, blk, norm)
    with jax.named_scope("ssm_scan"):
        y, s_end = selective_scan(u, delta, _a(blk), b, c, blk["d_skip"],
                                  lengths=lengths)
    out = (y.astype(h.dtype) * jax.nn.silu(z)) @ blk["w_out"]
    if not return_state:
        return out
    if lengths is None:
        lengths = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
    return out, s_end, conv_tail(u_in, lengths, blk["conv_w"].shape[0])


@jax.named_scope("ssm")
def mixer_step(h: jax.Array, blk: Params, s: jax.Array, tail: jax.Array,
               live: jax.Array, *, norm: Callable):
    """The mixer for one token a slot: ``h`` [slots, d] (normed), ``s``
    [slots, N, E], ``tail`` [K - 1, slots, E] (oldest first), ``live``
    [slots] bool. Returns the sublayer's output [slots, d] and the two
    arrays advanced; a slot that is not live keeps both as they are."""
    uz = jnp.einsum("sd,dte->ste", h, blk["w_in"])
    u_in, z = uz[:, 0], uz[:, 1]
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([tail.astype(u_in.dtype), u_in[None]])
        u = jax.nn.silu(
            blk["conv_b"].astype(jnp.float32)
            + jnp.sum(blk["conv_w"].astype(jnp.float32)[:, None, :]
                      * window.astype(jnp.float32), axis=0)
        ).astype(u_in.dtype)
        new_tail = jnp.where(live[None, :, None], window[1:].astype(
            tail.dtype), tail)
    delta, b, c = _selection(u, blk, norm)
    with jax.named_scope("state_update"):
        y, new_s = selective_step(u, delta, _a(blk), b, c, blk["d_skip"], s)
        new_s = jnp.where(live[:, None, None], new_s, s)
    out = (y.astype(h.dtype) * jax.nn.silu(z)) @ blk["w_out"]
    return out, new_s, new_tail
