"""ops/ssm.py at a small size on the CPU: the chunked scan against a
token-by-token loop, the one-token step against the scan, the
convolution from a tail, a prompt padded to a bucket, and autodiff
through a toy hybrid's ``apply``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.core.config import ModelConfig
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops import ssm

B, T, E, N, K = 2, 37, 24, 4, 4


def _inputs(seed=0, t=T):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    return {"u": f(B, t, E), "delta": jax.nn.softplus(f(B, t, E) - 2.0),
            "a": -jnp.exp(f(N, E) * 0.5), "b": f(B, t, N), "c": f(B, t, N),
            "d": f(E), "s0": f(B, N, E) * 0.1}


def _loop(x, lengths=None):
    """The recurrence written out, one token at a time."""
    s = np.asarray(x["s0"], np.float64)
    u, delta, a, b, c, d = (np.asarray(x[k], np.float64)
                            for k in ("u", "delta", "a", "b", "c", "d"))
    ys = np.zeros(u.shape)
    for t in range(u.shape[1]):
        for i in range(B):
            if lengths is not None and t >= lengths[i]:
                continue
            s[i] = (np.exp(delta[i, t][None, :] * a) * s[i]
                    + (delta[i, t] * u[i, t])[None, :] * b[i, t][:, None])
            ys[i, t] = c[i, t] @ s[i] + d * u[i, t]
    return ys, s


@pytest.mark.parametrize("chunk", [1, 8, 64])
def test_the_chunked_scan_is_the_token_by_token_loop(chunk):
    x = _inputs()
    y, s_end = ssm.selective_scan(x["u"], x["delta"], x["a"], x["b"],
                                  x["c"], x["d"], x["s0"], chunk=chunk)
    want_y, want_s = _loop(x)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_end), want_s, rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("chunk", [5, 16])
def test_a_position_past_its_length_leaves_the_state(chunk):
    x = _inputs(1)
    lengths = np.array([T, 11], np.int32)
    y, s_end = ssm.selective_scan(x["u"], x["delta"], x["a"], x["b"],
                                  x["c"], x["d"], x["s0"],
                                  jnp.asarray(lengths), chunk=chunk)
    want_y, want_s = _loop(x, lengths)
    np.testing.assert_allclose(np.asarray(s_end), want_s, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(y[1, :11]), want_y[1, :11],
                               rtol=2e-5, atol=2e-5)


def test_the_step_over_tokens_is_the_scan():
    x = _inputs(2)
    want_y, want_s = ssm.selective_scan(x["u"], x["delta"], x["a"], x["b"],
                                        x["c"], x["d"], x["s0"])
    s, ys = x["s0"], []
    for t in range(T):
        y, s = ssm.selective_step(x["u"][:, t], x["delta"][:, t], x["a"],
                                  x["b"][:, t], x["c"][:, t], x["d"], s)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(want_y), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               rtol=2e-5, atol=2e-5)


def test_a_narrower_state_answers_from_what_it_keeps():
    """A state stored in bfloat16 reads out what it stored: the next
    step starts from the same values."""
    x = _inputs(3)
    y, s = ssm.selective_step(x["u"][:, 0], x["delta"][:, 0], x["a"],
                              x["b"][:, 0], x["c"][:, 0], x["d"],
                              x["s0"].astype(jnp.bfloat16))
    assert s.dtype == jnp.bfloat16
    want = (jnp.sum(s.astype(jnp.float32) * x["c"][:, 0][:, :, None], 1)
            + x["d"] * x["u"][:, 0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6)


def test_the_convolution_from_a_tail_is_the_convolution_of_the_whole():
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((B, T, E)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, E)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((E,)), jnp.float32)
    whole = ssm.causal_conv(u, w, b)
    cut = 13
    head = ssm.causal_conv(u[:, :cut], w, b)
    tail = ssm.conv_tail(u[:, :cut], jnp.full((B,), cut), K)
    np.testing.assert_array_equal(np.asarray(tail),
                                  np.asarray(u[:, cut - K + 1:cut]))
    rest = ssm.causal_conv(u[:, cut:], w, b, tail)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([head, rest], 1)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)
    # a sequence shorter than the taps: zeros before its start
    short = ssm.conv_tail(u, jnp.asarray([2, 1]), K)
    assert float(jnp.abs(short[0, 0]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(short[0, 1:]),
                                  np.asarray(u[0, :2]))
    assert float(jnp.abs(short[1, :2]).max()) == 0.0


HYBRID = ModelConfig(name="transformer", model_dim=32, num_heads=4,
                     kv_heads=1, num_layers=4, vocab_size=53, seq_len=32,
                     ffn_dim=48, ssm_state_dim=4, ssm_dt_rank=4,
                     attn_layer_period=4, attn_layer_offset=2,
                     attention_impl="dense", compute_dtype="float32")


@pytest.mark.parametrize("bucket", [16, 32])
def test_a_padded_prompt_hands_over_the_state_of_its_last_token(bucket):
    """Whatever the bucket: the state after token ``plen - 1``, the
    convolution's inputs before ``plen``, the logits of ``plen - 1``."""
    model = get_model(HYBRID)
    params = model.init(jax.random.PRNGKey(0))
    plen = 11
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, plen), 0, 53)
    exact = model.decode_prefill(params, prompt, jnp.asarray([plen]))
    padded = jnp.zeros((1, bucket), jnp.int32).at[:, :plen].set(prompt)
    # the padding is token 0 and then anything: it must not matter
    padded = padded.at[:, plen:].set(7)
    got = model.decode_prefill(params, padded, jnp.asarray([plen]))
    assert got[0].shape == (1, 1, 53)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(exact[0]),
                               rtol=1e-5, atol=1e-5)
    for mine, theirs in zip(got[3:], exact[3:]):       # state, tail
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                   rtol=1e-5, atol=1e-6)
    # rows of the real positions (the padding's are never read)
    np.testing.assert_allclose(np.asarray(got[1][:, :, :plen]),
                               np.asarray(exact[1]), rtol=1e-5, atol=1e-5)
    assert got[3].shape == (3, 1, 4, 64) and got[4].shape == (3, 3, 1, 64)


def test_gradients_are_finite_and_a_toy_hybrids_loss_falls():
    model = get_model(HYBRID)
    params = model.init(jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 24), 0, 53)

    def loss(p):
        return transformer.loss_fn(model.apply(p, tokens, train=True),
                                   tokens)

    step = jax.jit(jax.value_and_grad(loss))
    losses = []
    for _ in range(4):
        value, grads = step(params)
        assert all(bool(jnp.isfinite(g).all())
                   for g in jax.tree.leaves(grads))
        # every leaf of a mixer gets a gradient
        assert all(float(jnp.abs(g).max()) > 0
                   for g in jax.tree.leaves(grads["blocks"][0]))
        losses.append(float(value))
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
    assert losses[3] < losses[2] < losses[1] < losses[0]


def test_mamba_initialises_what_a_scale_cannot_stand_in_for():
    params = get_model(HYBRID).init(jax.random.PRNGKey(5))
    blk = params["blocks"][0]
    np.testing.assert_allclose(np.asarray(jnp.exp(blk["a_log"][:, 0])),
                               [1, 2, 3, 4], rtol=1e-6)
    assert float(blk["d_skip"].min()) == 1.0
    step = jax.nn.softplus(blk["b_dt"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert "pos" not in params and "head" not in params
    assert "wqkv" in params["blocks"][2] and "a_log" not in params["blocks"][2]
    assert params["blocks"][2]["wqkv"].shape == (32, (4 + 2) * 8)
