"""Ring attention correctness: the sharded ring must match the
single-device oracle exactly (sequence-parallel path, SURVEY §5.7 —
a capability the reference lacks entirely but this framework treats
as first-class)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributedmnist_tpu.core.mesh import make_seq_topology
from distributedmnist_tpu.ops.ring_attention import (local_self_attention,
                                                     ring_self_attention)


def _qkv(key, b=2, h=2, s=32, d=8):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, h, s, d), jnp.float32) for k in ks)


def _run_ring(q, k, v, causal):
    topo = make_seq_topology(8)
    axis = topo.seq_axis

    def fn(q, k, v):
        return ring_self_attention(q, k, v, axis, causal=causal)

    spec = P(None, None, axis, None)  # shard the sequence dim
    sharded = jax.jit(jax.shard_map(fn, mesh=topo.mesh,
                                    in_specs=(spec, spec, spec),
                                    out_specs=spec))
    return sharded(q, k, v)


def test_ring_matches_local_causal():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    want = local_self_attention(q, k, v, causal=True)
    got = _run_ring(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_matches_local_full():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    want = local_self_attention(q, k, v, causal=False)
    got = _run_ring(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_grads_match_local():
    q, k, v = _qkv(jax.random.PRNGKey(2))

    def local_obj(qkv):
        return jnp.sum(local_self_attention(*qkv, causal=True) ** 2)

    def ring_obj(qkv):
        topo = make_seq_topology(8)
        axis = topo.seq_axis
        spec = P(None, None, axis, None)

        def fn(q, k, v):
            return ring_self_attention(q, k, v, axis, causal=True)

        out = jax.shard_map(fn, mesh=topo.mesh, in_specs=(spec,) * 3,
                            out_specs=spec)(*qkv)
        return jnp.sum(out ** 2)

    g_local = jax.grad(local_obj)((q, k, v))
    g_ring = jax.grad(ring_obj)((q, k, v))
    for a, b in zip(jax.tree.leaves(g_ring), jax.tree.leaves(g_local)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_transformer_with_ring_attention_matches_local():
    """Full model equivalence: sequence-sharded forward == local forward."""
    from distributedmnist_tpu.models import transformer
    params = transformer.init(jax.random.PRNGKey(0), vocab_size=31,
                              model_dim=16, num_heads=2, num_layers=2,
                              max_seq_len=64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 31)
    want = transformer.apply(params, toks,
                             block=transformer.make_block(num_heads=2),
                             compute_dtype=jnp.float32)

    topo = make_seq_topology(8)
    axis = topo.seq_axis

    def fn(params, toks, positions):
        def ring_attn(q, k, v):
            return ring_self_attention(q, k, v, axis, causal=True)
        block = transformer.make_block(num_heads=2, attention_fn=ring_attn)
        return transformer.apply(params, toks, block=block,
                                 positions=positions,
                                 compute_dtype=jnp.float32)

    positions = jnp.arange(64)
    sharded = jax.jit(jax.shard_map(
        fn, mesh=topo.mesh,
        in_specs=(P(), P(None, axis), P(axis)),
        out_specs=P(None, axis, None)))
    got = sharded(params, toks, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
