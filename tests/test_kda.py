"""Kimi Delta Attention's three forms of one recurrence (ops/kda.py): the
chunk form the prefill runs, the one-token step the decode loop runs and
the token recurrence as written agree to float32 rounding, for several
chunk sizes, ragged lengths in one bucket and from a given state; a chunk
past the prompt is skipped; the gate stays in its bounds; the mixer's
prefill hands over what its steps continue from; an idle slot's state is
untouched; the initialisation spreads the decays; and the kernel that
holds a head's state in VMEM across a token's update (``state_step``,
interpreted here) is the step: a token, 512 tokens against the recurrence,
a ragged ``live`` mask, a given state, and the shapes it is not asked for."""

import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.ops import kda

B, T, H, D = 2, 50, 3, 16
F32 = 5e-6          # float32 rounding over fifty tokens of a delta rule


def _inputs(seed=0, t=T, dv=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda._l2(jax.random.normal(ks[0], (B, t, H, D)))
    k = kda._l2(jax.random.normal(ks[1], (B, t, H, D)))
    v = jax.random.normal(ks[2], (B, t, H, dv))
    g = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, t, H, D)) * 2 - 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, t, H)))
    return q, k, v, g, beta


def _live(lengths, t=T):
    return (jnp.arange(t)[None] < lengths[:, None])[..., None, None]


@pytest.mark.parametrize("chunk", [1, 4, 16, 32, 64])
def test_the_chunk_form_is_the_token_recurrence(chunk):
    x = _inputs()
    want_o, want_s = kda.recurrence(*x)
    got_o, got_s = kda.chunked(*x, chunk=chunk)
    assert float(jnp.abs(got_o - want_o).max()) < F32
    assert float(jnp.abs(got_s - want_s).max()) < F32
    assert float(jnp.abs(want_o).max()) > 0.1       # and says something


@pytest.mark.parametrize("chunk", [5, 16, 32])
def test_ragged_lengths_in_one_bucket_leave_each_state_at_its_length(chunk):
    x = _inputs(1)
    lengths = jnp.array([T, 23])
    want_o, want_s = kda.recurrence(*x, lengths=lengths)
    got_o, got_s = kda.chunked(*x, lengths=lengths, chunk=chunk)
    live = _live(lengths)
    assert float(jnp.abs(jnp.where(live, got_o - want_o, 0)).max()) < F32
    assert float(jnp.abs(got_s - want_s).max()) < F32
    # the shorter sequence's state is that of its first 23 tokens alone
    alone = kda.recurrence(*(a[1:, :23] for a in x))[1]
    assert float(jnp.abs(got_s[1:] - alone).max()) < F32
    assert bool(jnp.isfinite(got_o).all())


def test_a_value_wider_than_the_key_and_a_given_state():
    x = _inputs(2, dv=24)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (B, H, D, 24)) * 0.3
    want_o, want_s = kda.recurrence(*x, s0=s0)
    got_o, got_s = kda.chunked(*x, s0=s0, chunk=16)
    assert got_s.shape == (B, H, D, 24)
    assert float(jnp.abs(got_o - want_o).max()) < F32
    assert float(jnp.abs(got_s - want_s).max()) < F32


def test_a_chunk_past_the_prompt_is_skipped():
    """Sixteen tokens in a bucket of 64, chunks of 16: the loop's body
    holds a conditional, and the three chunks past the prompt answer
    zeros without their decays ever being made (NaNs planted there do
    not reach the state)."""
    q, k, v, g, beta = _inputs(3, t=64)
    lengths = jnp.array([16, 9])
    poisoned = v.at[:, 16:].set(jnp.nan)
    o, s = kda.chunked(q, k, poisoned, g, beta, lengths=lengths, chunk=16)
    want = kda.recurrence(q[:, :16], k[:, :16], v[:, :16], g[:, :16],
                          beta[:, :16], lengths=lengths)[1]
    assert bool(jnp.isfinite(s).all())
    assert float(jnp.abs(s - want).max()) < F32
    assert float(jnp.abs(o[:, 16:]).max()) == 0.0
    text = jax.jit(lambda *a: kda.chunked(*a, lengths=lengths, chunk=16)
                   ).lower(q, k, v, g, beta).as_text()
    assert "cond" in text or "case" in text


def test_the_step_over_tokens_is_the_recurrence():
    x = _inputs(4)
    lengths = jnp.array([T, 31])
    want_o, want_s = kda.recurrence(*x, lengths=lengths)
    s = jnp.zeros((B, H, D, D))
    outs = []
    for t in range(T):
        o, new = kda.step(*(a[:, t] for a in x), s)
        s = jnp.where((t < lengths)[:, None, None, None], new, s)
        outs.append(o)
    live = _live(lengths)
    assert float(jnp.abs(jnp.where(
        live, jnp.stack(outs, 1) - want_o, 0)).max()) < F32
    assert float(jnp.abs(s - want_s).max()) < F32


def test_the_gate_stays_in_its_bounds():
    f = jax.random.normal(jax.random.PRNGKey(5), (7, H * D)) * 30
    a_log = jnp.log(jnp.array([0.5, 1.0, 4.0]))
    g = kda.gate(f, a_log, jnp.zeros((H * D,)), -5.0)
    assert g.shape == (7, H, D) and g.dtype == jnp.float32
    assert float(g.max()) <= 0.0 and float(g.min()) >= -5.0
    # strictly inside wherever the logit is moderate
    inner = kda.gate(f / 30, a_log, jnp.zeros((H * D,)), -5.0)
    assert -5.0 < float(inner.min()) and float(inner.max()) < 0.0
    # a head's a_log scales its logit: at f + bias = 1, g = -5 sigmoid(a)
    one = kda.gate(jnp.ones((1, H * D)), a_log, jnp.zeros((H * D,)), -5.0)
    np.testing.assert_allclose(np.asarray(one[0, :, 0]),
                               -5 / (1 + np.exp(-np.array([0.5, 1., 4.]))),
                               rtol=1e-6)


def _block(seed=0, d=48):
    z = transformer.Sizes(kda_head_dim=D, ffn_dim=64, attn_period=2,
                          attn_offset=1)
    blk = transformer._init_sized_block(jax.random.PRNGKey(seed), d, H, z,
                                        routed=False, attends=False)
    # (matrices at the inverse root of this toy's width, not the
    # published one's)
    return {k: v * 5 if k.startswith("w") and k != "w_f" else v
            for k, v in blk.items()}, z


def _norm(x, p):
    return transformer._rms_norm(x, p)


@pytest.mark.parametrize("bucket", [16, 32])
def test_a_padded_prompt_hands_over_what_its_steps_continue_from(bucket):
    """The mixer over a prompt of 11 padded to a bucket, then five tokens
    one a step from the state and the tail it handed over: the outputs of
    the mixer over all sixteen."""
    blk, _ = _block()
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 16, 48))
    whole = kda.mixer(h, blk, lower_bound=-5.0, norm=_norm)
    padded = jnp.pad(h[:, :11], ((0, 0), (0, bucket - 11), (0, 0)),
                     constant_values=7.0)
    out, s, tail = kda.mixer(padded, blk, lower_bound=-5.0, norm=_norm,
                             lengths=jnp.array([11]), return_state=True,
                             chunk=8)
    assert s.shape == (1, H, D, D) and tail.shape == (1, 3, 3 * H * D)
    assert float(jnp.abs(out[:, :11] - whole[:, :11]).max()) < 1e-5
    tail = tail.transpose(1, 0, 2)                  # [K - 1, slots, W]
    live = jnp.array([True])
    for t in range(11, 16):
        o, s, tail = kda.mixer_step(h[:, t], blk, s, tail, live,
                                    lower_bound=-5.0, norm=_norm)
        assert float(jnp.abs(o - whole[:, t]).max()) < 1e-5


def test_an_idle_slots_state_is_untouched():
    blk, _ = _block(1)
    h = jax.random.normal(jax.random.PRNGKey(7), (3, 48))
    s = jax.random.normal(jax.random.PRNGKey(8), (3, H, D, D))
    tail = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 3 * H * D))
    live = jnp.array([True, False, True])
    _, new_s, new_tail = kda.mixer_step(h, blk, s, tail, live,
                                        lower_bound=-5.0, norm=_norm)
    assert float(jnp.abs(new_s[1] - s[1]).max()) == 0.0
    assert float(jnp.abs(new_tail[:, 1] - tail[:, 1]).max()) == 0.0
    assert float(jnp.abs(new_s[0] - s[0]).max()) > 1e-3
    assert float(jnp.abs(new_tail[-1, 2] - tail[-1, 2]).max()) > 1e-3
    # the tail moved up by one: its oldest input gone, the newest last
    assert float(jnp.abs(new_tail[:2, 0] - tail[1:, 0]).max()) == 0.0


def test_the_initialisation_spreads_the_decays():
    """What a scale cannot stand in for: a channel keeps between e^-0.5
    and 0.999 of itself a token before the input moves it; beta's
    projection and the three streams at the matrices' scale."""
    z = transformer.Sizes(kda_head_dim=128)
    blk = transformer._init_kda(jax.random.PRNGKey(0), 256, 8, z)
    assert blk["w_qkv"].shape == (256, 3 * 1024)
    assert blk["conv_w"].shape == (4, 3 * 1024)
    assert blk["a_log"].shape == (8,) and blk["dt_bias"].shape == (1024,)
    assert blk["o_norm"]["scale"].shape == (128,)
    keep = jnp.exp(kda.gate(jnp.zeros((1024,)), blk["a_log"],
                            blk["dt_bias"], -5.0))
    assert math.exp(-0.5) - 1e-3 < float(keep.min()) < 0.7
    assert 0.995 < float(keep.max()) <= 0.9991
    # spread over the range, not heaped at an end
    assert 0.2 < float(jnp.mean(keep < 0.95)) < 0.5
    assert float(jnp.std(blk["w_f"])) == pytest.approx(0.005, rel=0.2)
    assert float(jnp.abs(blk["conv_w"]).max()) <= 0.5
    assert "dt_bias" in transformer._F32_LEAVES


# -- the state held in VMEM across a token's update (state_step) ---------------

def _token(seed, slots=B, heads=H, d=D, dv=D, s_scale=0.3):
    """One token a slot and a state to advance."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2(jax.random.normal(ks[0], (slots, heads, d)))
    k = kda._l2(jax.random.normal(ks[1], (slots, heads, d)))
    v = jax.random.normal(ks[2], (slots, heads, dv))
    g = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], (slots, heads, d)) * 2
                            - 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (slots, heads)))
    s = jax.random.normal(ks[5], (slots, heads, d, dv)) * s_scale
    return q, k, v, g, beta, s


@pytest.mark.parametrize("heads, head_block, dv, s_scale", [
    (4, 2, D, 0.3),          # a block smaller than the heads
    (4, 4, D, 0.3),          # all of them in one item
    (H, None, D, 0.0),       # from an empty state, three heads in one block
    (32, None, 24, 1.0),     # two of the module's HEAD_BLOCK, a wider value
    (16, 8, 128, 0.3),       # the tiles Mosaic takes, two blocks a slot
    (24, 24, D, 0.3)])       # three groups of eight walked by the loop
def test_a_token_through_the_kernel_is_the_step(heads, head_block, dv,
                                                s_scale):
    x = _token(10, slots=3, heads=heads, dv=dv, s_scale=s_scale)
    live = jnp.ones((3,), bool)
    want_o, want_s = kda.step(*x)
    got_o, got_s = kda.state_step(*x, live, head_block=head_block)
    assert got_o.dtype == got_s.dtype == jnp.float32
    assert got_s.shape == x[-1].shape and got_o.shape == x[2].shape
    assert float(jnp.abs(got_o - want_o).max()) < 1e-6
    assert float(jnp.abs(got_s - want_s).max()) < 1e-6
    assert float(jnp.abs(want_o).max()) > 0.1       # and says something


def test_512_tokens_through_the_kernel_are_the_recurrence():
    """What a serving check of 136 positions cannot see: the error that
    compounds. State and outputs after 512 tokens of the kernel, from a
    given state, against the token recurrence."""
    t = 512
    x = _inputs(11, t=t)
    s0 = jax.random.normal(jax.random.PRNGKey(12), (B, H, D, D)) * 0.3
    want_o, want_s = kda.recurrence(*x, s0=s0)
    live = jnp.ones((B,), bool)

    def one_token(s, token):
        o, s = kda.state_step(*token, s, live)
        return s, o

    s_end, o = jax.jit(lambda s, x: jax.lax.scan(one_token, s, tuple(
        jnp.moveaxis(a, 1, 0) for a in x)))(s0, x)
    # float32 rounding over ten times the tokens of F32's fifty
    assert float(jnp.abs(jnp.moveaxis(o, 0, 1) - want_o).max()) < 4 * F32
    assert float(jnp.abs(s_end - want_s).max()) < 4 * F32
    assert float(jnp.abs(want_s).max()) > 0.1


def test_a_ragged_live_mask_keeps_idle_states_to_the_bit():
    x = _token(13, slots=5, heads=4)
    live = jnp.array([True, False, True, False, False])
    _, want_s = kda.step(*x)
    o, s = kda.state_step(*x, live, head_block=2)
    assert bool((s[~live] == x[-1][~live]).all())
    assert not bool(o[~live].any())                 # an idle slot: zeros
    assert float(jnp.abs(s[live] - want_s[live]).max()) < 1e-6
    assert float(jnp.abs(s[live] - x[-1][live]).max()) > 1e-3


def _as_on_a_tpu():
    tpu = mock.Mock(platform="tpu")
    return mock.patch.multiple(jax, devices=lambda *a: [tpu])


@pytest.mark.parametrize("shape, dtype, why", [
    ((4, 3, 16, 128), jnp.float32, None),
    ((4, 32, 128, 128), jnp.float32, None),
    ((4, 3, 12, 128), jnp.float32, "a toy D: no whole sublanes"),
    ((4, 3, 16, 16), jnp.float32, "a toy Dv: no whole lanes"),
    ((4, 3, 128, 192), jnp.float32, "Dv not a multiple of 128"),
    ((4, 3, 128, 128), jnp.bfloat16, "a state that is rounded"),
    ((4, 16, 5120), jnp.float32, "a state-space layer's rows")])
def test_the_arm_is_asked_of_the_devices_the_shape_and_the_dtype(shape, dtype,
                                                                 why):
    assert kda.state_in_vmem(shape, dtype) == (why is None)
    # a CPU runs the step whatever the shape; a patched default_backend
    # is not what is asked
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert kda.state_arm(shape, dtype) == "xla"
    with _as_on_a_tpu():
        assert kda.state_arm(shape, dtype) == (
            "kernel" if why is None else "xla")


@pytest.mark.parametrize("d, dv, dtype", [
    (12, 128, jnp.float32), (16, 192, jnp.float32), (16, 128, jnp.bfloat16)])
def test_compiled_for_a_state_mosaic_does_not_take_the_kernel_raises(
        d, dv, dtype):
    *x, s = _token(14, d=d, dv=dv)
    s = s.astype(dtype)
    live = jnp.ones((B,), bool)
    with pytest.raises(ValueError, match="ops.kda.step advances this one"):
        kda.state_step(*x, s, live, interpret=False)
    # and mixer_step's own question sends it to the step, whose text it is
    with _as_on_a_tpu():
        assert kda.state_arm(s.shape, s.dtype) == "xla"


def test_mixer_step_takes_the_arm_it_is_told(monkeypatch):
    """The sublayer through the kernel (the asking function patched, the
    kernel interpreted) is the sublayer through the step, an idle slot
    untouched; asked of a CPU the step's program holds no kernel."""
    blk, _ = _block(2)
    h = jax.random.normal(jax.random.PRNGKey(15), (3, 48))
    s = jax.random.normal(jax.random.PRNGKey(16), (3, H, D, D))
    tail = jax.random.normal(jax.random.PRNGKey(17), (3, 3, 3 * H * D))
    live = jnp.array([True, False, True])
    run = lambda: kda.mixer_step(h, blk, s, tail, live,  # noqa: E731
                                 lower_bound=-5.0, norm=_norm)
    want = run()
    asked = []
    monkeypatch.setattr(kda, "state_arm",
                        lambda *a: asked.append(a) or "kernel")
    got = run()
    assert asked == [((3, H, D, D), jnp.float32)]
    assert bool((got[1][1] == s[1]).all())
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b)[jnp.array([0, 2])].max()) < 1e-5
    assert bool((got[2] == want[2]).all())

