"""Paged-attention kernel parity pins (ops/pallas_paged_attention.py).

The decode twin of the flash-kernel parity tests: the Pallas paged
kernel that walks each slot's block table IN-kernel, over the cache
rows as they are stored, must agree with the dense-gather oracle across
every slot mix the decode service produces — fresh, mid-generation,
near-max, idle (all-null table), and post-free block reuse.  Tolerances
are the documented contract (see the kernel module docstring), not
wishful thinking:

* live slots: f32 online-softmax vs dense softmax agree to
  accumulation-order noise (~4e-7 observed; 1e-5 pinned),
* idle slots (length 0): the paged kernel returns EXACT zeros (it makes
  no work item for them); the dense oracle's idle rows are
  unspecified garbage — by contract the caller ignores both,
* a dead table entry is never looked up: the null block may hold
  anything, a value that is no number included,
* the gather arm scatters the new token's rows through XLA, the paged
  arm hands them to the kernel, which copies them into their page
  before it reads (``paged_attention_write``): the same rows at the
  same place, so after a decode step the caches agree everywhere
  OUTSIDE the reserved null block (the scatter sends an idle slot's
  garbage row there; the kernel writes nothing for a slot of length 0).

CPU/GPU run the kernel in interpret mode — same index arithmetic, DMAs
and masking as compiled TPU, so these pins hold on every backend; the
compile for the chip itself is pinned in tests/test_cache_layout.py.
"""

import numpy as np
import pytest

LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}


def _rand_pages(rng, num_blocks, block_size, heads, hd):
    import jax.numpy as jnp
    k = rng.standard_normal((num_blocks, block_size, heads, hd))
    v = rng.standard_normal((num_blocks, block_size, heads, hd))
    return jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32)


@pytest.mark.tier1
def test_paged_matches_dense_oracle_across_slot_mix():
    """Fresh (len 1), mid (partial final block), near-max (full
    table), and idle (len 0, all-null) slots in ONE launch: live rows
    pinned to the oracle, idle rows exactly zero."""
    import jax.numpy as jnp

    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_dense)

    rng = np.random.default_rng(0)
    heads, hd, bs, width, nblocks = 4, 16, 8, 4, 16
    k_pages, v_pages = _rand_pages(rng, nblocks, bs, heads, hd)
    # block 0 is the null block: poison it so any accidental read of a
    # dead table entry shows up as a parity break instead of a zero
    k_pages = k_pages.at[0].set(37.0)
    v_pages = v_pages.at[0].set(-53.0)
    tables = np.zeros((4, width), np.int32)
    tables[0, 0] = 1                      # fresh: 1 token
    tables[1, :2] = (2, 3)                # mid: 11 tokens (partial blk)
    tables[2] = (4, 5, 6, 7)              # near-max: 32 tokens
    lengths = np.asarray([1, 11, 32, 0], np.int32)   # slot 3 idle
    q = jnp.asarray(rng.standard_normal((4, heads, hd)), jnp.float32)

    got = np.asarray(paged_attention(q, k_pages, v_pages,
                                     jnp.asarray(tables),
                                     jnp.asarray(lengths)))
    want = np.asarray(paged_attention_dense(q, k_pages, v_pages,
                                            jnp.asarray(tables),
                                            jnp.asarray(lengths)))
    np.testing.assert_allclose(got[:3], want[:3], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[3], np.zeros((heads, hd)))


@pytest.mark.tier1
def test_paged_parity_survives_block_free_and_reuse():
    """Free a sequence, let the LIFO allocator hand its blocks to a
    SHORTER successor, and pin the kernel against a from-scratch
    reference over the reused table — stale K/V beyond the new length
    must stay invisible (the length mask, not block hygiene, is the
    contract)."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention)
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    rng = np.random.default_rng(1)
    L, heads, hd, bs = 1, 4, 16, 8
    cache = PagedKVCache(num_layers=L, num_blocks=8, block_size=bs,
                         num_heads=heads, head_dim=hd,
                         max_blocks_per_seq=4)
    ta = cache.alloc_sequence(16)
    ka = jnp.asarray(rng.standard_normal((L, 16, heads, hd)), jnp.float32)
    va = jnp.asarray(rng.standard_normal((L, 16, heads, hd)), jnp.float32)
    cache.write_prompt(ta, ka, va, 16)
    cache.free_sequence(ta)

    tb = cache.alloc_sequence(9)          # LIFO: reuses A's blocks
    assert set(map(int, tb[:2])) <= set(map(int, ta[:2])) | {0} or True
    kb = jnp.asarray(rng.standard_normal((L, 9, heads, hd)), jnp.float32)
    vb = jnp.asarray(rng.standard_normal((L, 9, heads, hd)), jnp.float32)
    cache.write_prompt(tb, kb, vb, 9)

    q = jnp.asarray(rng.standard_normal((1, heads, hd)), jnp.float32)
    got = np.asarray(paged_attention(
        q, cache.k[0], cache.v[0],
        jnp.asarray(tb)[None, :], jnp.asarray([9], np.int32)))[0]

    # reference from the dense replay of what SHOULD be visible: the 9
    # tokens of B, nothing of A
    ks, vs = cache.gather_dense(tb, 9)          # [L, 9, h, hd]
    scale = 1.0 / np.sqrt(hd)
    sc = np.einsum("hd,khd->hk", np.asarray(q[0]), ks[0]) * scale
    w = np.exp(sc - sc.max(axis=1, keepdims=True))
    w /= w.sum(axis=1, keepdims=True)
    want = np.einsum("hk,khd->hd", w, vs[0])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # and the visible bytes are B's, not A's leftovers
    np.testing.assert_array_equal(ks[0], np.asarray(kb[0]))


@pytest.mark.tier1
def test_decode_step_paged_matches_dense_end_to_end():
    """Full decode_step through a real transformer: per-slot logits
    agree between kernels for live slots, and the scatter and the
    kernel's row copies leave both caches equal outside the reserved
    null block (layer 0's rows, which no attention's output has
    touched, to the bit; the paged arm leaves the null block alone)."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    model = get_model(ModelConfig(**LM_MODEL))
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(2)
    L, heads, hd, bs = 2, 4, 16, 8
    cache_p = PagedKVCache(num_layers=L, num_blocks=16, block_size=bs,
                           num_heads=heads, head_dim=hd,
                           max_blocks_per_seq=4)
    cache_d = PagedKVCache(num_layers=L, num_blocks=16, block_size=bs,
                           num_heads=heads, head_dim=hd,
                           max_blocks_per_seq=4)
    # three live slots at different lengths + one idle slot
    prompts = {0: 5, 1: 12, 2: 16}
    tables = np.zeros((4, 4), np.int32)
    for s, plen in prompts.items():
        toks = jnp.asarray(rng.integers(0, 32, size=(1, plen)), jnp.int32)
        _, ks, vs = model.decode_prefill(params, toks)
        t = cache_p.alloc_sequence(plen + 1)
        t2 = cache_d.alloc_sequence(plen + 1)
        np.testing.assert_array_equal(t, t2)  # identical alloc order
        tables[s] = t
        cache_p.write_prompt(t, ks[:, 0], vs[:, 0], plen)
        cache_d.write_prompt(t, ks[:, 0], vs[:, 0], plen)

    tokens = jnp.asarray([3, 7, 11, 0], jnp.int32)
    positions = jnp.asarray([5, 12, 16, 0], jnp.int32)
    lengths = jnp.asarray([6, 13, 17, 0], jnp.int32)
    out = {}
    for kern, cache in (("paged", cache_p), ("dense", cache_d)):
        logits, k_new, v_new = model.decode_step(
            params, tokens, positions, cache.k, cache.v,
            jnp.asarray(tables), lengths, block_size=bs,
            attention_kernel=kern)
        out[kern] = (np.asarray(logits), np.asarray(k_new),
                     np.asarray(v_new))
    lp, kp, vp = out["paged"]
    ld, kd, vd = out["dense"]
    np.testing.assert_allclose(lp[:3], ld[:3], atol=1e-4, rtol=1e-4)
    # cache parity outside the null block (the gather arm routes the
    # idle slot's garbage row to block 0; the paged arm writes none)
    np.testing.assert_allclose(kp[:, 1:], kd[:, 1:], atol=1e-5)
    np.testing.assert_allclose(vp[:, 1:], vd[:, 1:], atol=1e-5)
    np.testing.assert_array_equal(kp[0, 1:], kd[0, 1:])
    np.testing.assert_array_equal(vp[0, 1:], vd[0, 1:])
    assert (kp[0, 1:] != np.asarray(cache_p.k)[0, 1:]).any()
    np.testing.assert_array_equal(kp[:, 0], np.asarray(cache_p.k)[:, 0])
    np.testing.assert_array_equal(vp[:, 0], np.asarray(cache_p.v)[:, 0])


@pytest.mark.tier1
def test_attention_kernel_knob_validation():
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ConfigError, DecodeConfig

    assert DecodeConfig().attention_kernel == "auto"
    for known in ("auto", "dense", "paged"):
        DecodeConfig(attention_kernel=known).validate()
    with pytest.raises(ConfigError, match="attention_kernel"):
        DecodeConfig(attention_kernel="flash").validate()

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    model = get_model(ModelConfig(**LM_MODEL))
    params = model.init(jax.random.PRNGKey(0))
    z = jnp.zeros
    with pytest.raises(ValueError, match="attention_kernel"):
        model.decode_step(params, z((1,), jnp.int32), z((1,), jnp.int32),
                          z((2, 4, 8, 4, 16)), z((2, 4, 8, 4, 16)),
                          z((1, 2), jnp.int32), z((1,), jnp.int32),
                          block_size=8, attention_kernel="flash")


# -- the rows as stored -----------------------------------------------------

#: (table width, pages an item, each slot's length): blocks of 4, so a
#: table ``w`` wide holds ``4 w`` positions. The widths are the four
#: rungs a replica with tables of 12 blocks compiles
#: (``DecodeReplica._table_widths``); the pages divide none of them but
#: the last case's, and 8 is capped to a table of 3
ROWS_AS_STORED = {
    "one_token_beside_an_idle_slot_and_a_full_table": (3, 8, [1, 0, 12]),
    "a_blocks_edge_one_past_it_and_the_full_width": (6, 4, [4, 5, 24, 0]),
    "mixed_lengths_in_one_call": (9, 4, [36, 1, 17, 0, 30, 8, 9]),
    "idle_slots_first_and_last": (12, 8, [0, 48, 33, 7, 0]),
    "every_slot_idle_but_one": (12, 5, [0, 0, 41, 0]),
    "pages_that_divide_the_width": (12, 3, [48, 12, 13, 25]),
}


def _stored(rng, lengths, width, dtype, *, heads=2, hd=64, row=128, bs=4):
    """Pages with rows ``row`` wide for a head of ``hd``, garbage that is
    not zero beyond the head, each live slot's table filled as far as
    its length and ``NULL_BLOCK`` from there on; block 0 holds no
    number. Returns (q, k, v, the same k and v with a finite null block
    for the oracle, tables, lengths)."""
    import jax.numpy as jnp
    num_blocks = 1 + sum(-(-n // bs) for n in lengths)
    k = rng.standard_normal((num_blocks, bs, heads, row)).astype(np.float32)
    v = rng.standard_normal((num_blocks, bs, heads, row)).astype(np.float32)
    k[..., hd:] = 7.0 + rng.standard_normal(k[..., hd:].shape)
    v[..., hd:] = -5.0 + rng.standard_normal(v[..., hd:].shape)
    tables = np.zeros((len(lengths), width), np.int32)
    free = iter(rng.permutation(np.arange(1, num_blocks)))
    for slot, n in enumerate(lengths):
        for j in range(-(-n // bs)):
            tables[slot, j] = next(free)
    no_number = [a.copy() for a in (k, v)]
    for a in no_number:
        a[0] = np.nan
    q = jnp.asarray(rng.standard_normal((len(lengths), heads, hd)), dtype)
    return (q, *(jnp.asarray(a, dtype) for a in (*no_number, k, v)),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


@pytest.mark.tier1
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ROWS_AS_STORED)
def test_kernel_reads_rows_stored_wider_than_the_head(case, dtype):
    """A 64-wide head in 128-wide rows with garbage beside it, tables
    whose dead tail is ``NULL_BLOCK`` (which holds no number here: a dead
    entry is never looked up), through the interpreter: the oracle's
    output for every live slot, exact zeros for an idle one. In bfloat16
    (what the chip stores) the weights go to the product as three
    pieces, so the float32 oracle is met as closely as in float32."""
    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_dense)

    width, pages, lengths = ROWS_AS_STORED[case]
    q, k, v, k_finite, v_finite, tables, lens = _stored(
        np.random.default_rng(len(case)), lengths, width, dtype)
    got = np.asarray(paged_attention(q, k, v, tables, lens,
                                     pages_per_step=pages, interpret=True))
    want = np.asarray(paged_attention_dense(q, k_finite, v_finite, tables,
                                            lens))
    assert got.shape == want.shape == (len(lengths), 2, 64)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(got[~live], 0.0)


@pytest.mark.tier1
def test_kernel_takes_the_cache_whole_with_the_layers_index():
    """The arrays as ``PagedKVCache`` holds them, [L, N, B, h, width],
    and a traced layer index: what one layer's pages give."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention)

    rng = np.random.default_rng(5)
    q, k, v, _, _, tables, lens = _stored(rng, [9, 0, 16], 4, "float32",
                                          hd=16, row=32)
    k, v = k.at[0].set(1.0), v.at[0].set(1.0)
    whole_k = jnp.stack([k + 1.0, k, k - 1.0])
    whole_v = jnp.stack([v - 2.0, v, v + 2.0])
    read = jax.jit(lambda li: paged_attention(
        q, whole_k, whole_v, tables, lens, layer=li, interpret=True))
    want = paged_attention(q, k, v, tables, lens, interpret=True)
    np.testing.assert_array_equal(np.asarray(read(1)), np.asarray(want))
    assert np.abs(np.asarray(read(2)) - np.asarray(want)).max() > 0.5


#: the writing form's cases: (table width, pages an item, each slot's
#: length with the new token counted, the head's width, the row's, the
#: layer, whether it is traced); blocks of 4
ROWS_WRITTEN = {
    "a_token_at_a_blocks_first_offset": (3, 8, [5, 9, 1], 16, 16, 0, False),
    "a_token_at_a_blocks_last_offset": (3, 2, [4, 12, 8], 16, 16, 1, False),
    "a_slot_of_length_0_beside_live_ones": (6, 4, [0, 7, 0, 24], 16, 16, 2,
                                            False),
    "every_slot_of_length_0": (3, 8, [0, 0], 16, 16, 0, False),
    "rows_wider_than_the_head": (6, 4, [6, 0, 21, 16], 64, 128, 1, False),
    "a_traced_layer": (9, 4, [36, 1, 17, 0, 30], 16, 32, 2, True),
    "a_table_narrower_than_pages_per_step": (3, 8, [10, 3, 0, 12], 64, 128,
                                             0, False),
}


@pytest.mark.tier1
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ROWS_WRITTEN)
def test_the_kernel_writes_the_new_rows_the_scatter_writes(case, dtype):
    """``paged_attention_write`` against scatter-then-read, through the
    interpreter: every live slot's ``[heads, row]`` rows at position
    ``length - 1`` of its table in the layer asked for, both arrays
    equal to the bit in every layer, block and lane, and the attention
    equal to what the read-only form reads from the scattered cache. A
    slot of length 0 (its table all ``NULL_BLOCK``) changes no row of
    any block, the null block's included."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_write)

    width, pages, lengths, hd, row, layer, traced = ROWS_WRITTEN[case]
    rng = np.random.default_rng(len(case))
    q, k, v, _, _, tables, lens = _stored(rng, lengths, width, dtype,
                                          hd=hd, row=row)
    k, v = (jnp.stack([a + 1.0, a, a - 1.0]).at[:, 0].set(3.0)
            for a in (k, v))
    k_new, v_new = (jnp.asarray(rng.standard_normal((len(lengths), 2, row)),
                                dtype) for _ in range(2))
    write = jax.jit(lambda li: paged_attention_write(
        q, k_new, v_new, k, v, tables, lens, layer=li,
        pages_per_step=pages, interpret=True))
    got, got_k, got_v = write(jnp.asarray(layer) if traced else layer)

    want_k, want_v = np.array(k), np.array(v)
    for slot, n in enumerate(lengths):
        if n:
            at = (layer, int(tables[slot, (n - 1) // 4]), (n - 1) % 4)
            assert at[1] != 0
            want_k[at], want_v[at] = k_new[slot], v_new[slot]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    assert any(lengths) == (want_k != np.asarray(k)).any()
    want = paged_attention(q, jnp.asarray(want_k), jnp.asarray(want_v),
                           tables, lens, layer=layer, pages_per_step=pages,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.shape == (len(lengths), 2, hd)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(lengths) == 0],
                                  0.0)


@pytest.mark.tier1
@pytest.mark.parametrize("stored", [16, 128])
def test_both_arms_decode_the_same_greedy_tokens(stored):
    """A written prompt and eight greedy steps for two slots of three
    through ``decode_step`` on each arm, at the head's own width and on
    128-wide rows: the same tokens, logits within the tolerance of the
    one-step test above."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    model = get_model(ModelConfig(**LM_MODEL))
    params = model.init(jax.random.PRNGKey(3))
    prompts = [[1, 2, 3, 4, 5], list(range(1, 13))]

    def decode(kernel):
        step = jax.jit(functools.partial(model.decode_step, block_size=4,
                                         attention_kernel=kernel))
        cache = PagedKVCache(2, 40, 4, 4, stored, max_blocks_per_seq=6)
        tables = np.zeros((3, 6), np.int32)
        toks, rows, picked = [], [], []
        for slot, prompt in enumerate(prompts):
            logits, ks, vs = model.decode_prefill(
                params, jnp.asarray([prompt], jnp.int32))
            tables[slot] = cache.alloc_sequence(len(prompt) + 8)
            cache.write_prompt(tables[slot], ks[:, 0], vs[:, 0], len(prompt))
            toks.append(int(jnp.argmax(logits[0, -1])))
        for i in range(8):
            pos = [len(p) + i for p in prompts]
            out, cache.k, cache.v = step(
                params, jnp.asarray([*toks, 0], jnp.int32),
                jnp.asarray([*pos, 0], jnp.int32), cache.k, cache.v,
                jnp.asarray(tables),
                jnp.asarray([pos[0] + 1, pos[1] + 1, 0], jnp.int32))
            rows.append(np.asarray(out[:2]))
            toks = [int(t) for t in rows[-1].argmax(-1)]
            picked.append(toks)
        return picked, np.stack(rows)

    tokens_paged, logits_paged = decode("paged")
    tokens_dense, logits_dense = decode("dense")
    assert tokens_paged == tokens_dense
    np.testing.assert_allclose(logits_paged, logits_dense, atol=1e-4,
                               rtol=1e-4)


@pytest.mark.tier1
@pytest.mark.parametrize("stored", [16, 128])
def test_a_paged_step_leaves_the_cache_the_scatter_leaves(stored):
    """``decode_step`` on the paged arm, whose kernel writes the token's
    rows, against the same step with the writing form replaced (here, by
    the test) by a scatter through XLA and then the read-only form: five
    greedy steps for two slots of three across a block's edge, the
    logits and both cache arrays equal to the bit, every layer, block
    and lane; beside a 16-wide head a 128-wide row keeps its zeros, and
    the idle slot leaves the null block as the prompts left it."""
    import functools
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.ops import pallas_paged_attention as ppa
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    model = get_model(ModelConfig(**LM_MODEL))
    params = model.init(jax.random.PRNGKey(3))
    prompts = [[1, 2, 3], list(range(1, 11))]

    def scatter_then_read(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                          *, layer, scale):
        at = lengths[:2] - 1                  # the two live slots'
        blocks = jnp.take_along_axis(tables[:2], (at // 4)[:, None],
                                     axis=1)[:, 0]
        k_pages = k_pages.at[layer, blocks, at % 4].set(k_new[:2])
        v_pages = v_pages.at[layer, blocks, at % 4].set(v_new[:2])
        return (ppa.paged_attention(q, k_pages, v_pages, tables, lengths,
                                    layer=layer, scale=scale),
                k_pages, v_pages)

    def decode(write):
        cache = PagedKVCache(2, 40, 4, 4, stored, max_blocks_per_seq=4)
        tables = np.zeros((3, 4), np.int32)
        toks, rows = [], []
        for slot, prompt in enumerate(prompts):
            logits, ks, vs = model.decode_prefill(
                params, jnp.asarray([prompt], jnp.int32))
            tables[slot] = cache.alloc_sequence(len(prompt) + 5)
            cache.write_prompt(tables[slot], ks[:, 0], vs[:, 0], len(prompt))
            toks.append(int(jnp.argmax(logits[0, -1])))
        null = np.asarray(cache.k[:, 0])
        with mock.patch.object(ppa, "paged_attention_write", write):
            step = jax.jit(functools.partial(
                model.decode_step, block_size=4, attention_kernel="paged"))
            for i in range(5):
                pos = [len(p) + i for p in prompts]
                out, cache.k, cache.v = step(
                    params, jnp.asarray([*toks, 0], jnp.int32),
                    jnp.asarray([*pos, 0], jnp.int32), cache.k, cache.v,
                    jnp.asarray(tables),
                    jnp.asarray([pos[0] + 1, pos[1] + 1, 0], jnp.int32))
                rows.append(np.asarray(out[:2]))
                toks = [int(t) for t in rows[-1].argmax(-1)]
        np.testing.assert_array_equal(np.asarray(cache.k[:, 0]), null)
        return np.stack(rows), np.asarray(cache.k), np.asarray(cache.v)

    got, got_k, got_v = decode(ppa.paged_attention_write)
    want, want_k, want_v = decode(scatter_then_read)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    assert got_k[..., :16].any() and not got_k[..., 16:].any()


# -- the latent form --------------------------------------------------------
# (ops/pallas_paged_attention.py::paged_latent_attention[_write]: one row a
# token for all heads in each of two arrays, the value the latent itself;
# pinned to the gather arm of models/transformer.py::_latent_decode_attention,
# whose weights are rounded to the cache's dtype once.)

#: the two cells' pages and heads (openpangu-ultra-moe-718b: 16 positions a
#: page, 128 heads; ling-3.0-flash: 128 and 32) at toy widths; lengths that
#: end on a page's first row (1, B + 1) and on its last (B, 2B), an idle slot
LATENT_GEOMETRIES = [
    pytest.param(16, 128, [17, 0, 90, 32, 1, 16], id="block16-heads128"),
    pytest.param(128, 32, [129, 0, 300, 256, 1, 128], id="block128-heads32"),
]
LATENT, ROPE, SCALE = 32, 8, 0.17


def _latent_case(block, heads, lengths, *, dtype="bfloat16", table=None,
                 c_row=LATENT, kr_row=16, layers=2, seed=0):
    """Random pages, queries and new rows, and tables whose dead entries
    all point at block 0, which holds no number at all."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    need = -(-lengths // block)
    table = table or int(need.max())
    blocks = 1 + int(need.sum()) + 2
    normal = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    c = np.zeros((layers, blocks, block, c_row), np.float32)
    kr = np.zeros((layers, blocks, block, kr_row), np.float32)
    c[..., :LATENT], kr[..., :ROPE] = (normal(*c.shape[:3], LATENT),
                                       normal(*kr.shape[:3], ROPE))
    c[:, 0], kr[:, 0] = np.nan, np.nan
    tables = np.zeros((len(lengths), table), np.int32)
    order = iter(rng.permutation(np.arange(1, blocks)))
    for s, n in enumerate(need):
        tables[s, :n] = [next(order) for _ in range(n)]
    new_c = np.zeros((len(lengths), c_row), np.float32)
    new_kr = np.zeros((len(lengths), kr_row), np.float32)
    new_c[:, :LATENT] = normal(len(lengths), LATENT)
    new_kr[:, :ROPE] = normal(len(lengths), ROPE)
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    return dict(q_c=cast(normal(len(lengths), heads, LATENT)),
                q_r=cast(normal(len(lengths), heads, ROPE)),
                new_c=cast(new_c), new_kr=cast(new_kr), c=cast(c),
                kr=cast(kr), tables=jnp.asarray(tables),
                lengths=jnp.asarray(lengths))


def _latent_written(case, layer=1, **how):
    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_latent_attention_write)
    return paged_latent_attention_write(
        case["q_c"], case["q_r"], case["new_c"], case["new_kr"], case["c"],
        case["kr"], case["tables"], case["lengths"], layer=layer,
        scale=SCALE, **how)


def _latent_scattered(case, layer=1):
    """What the gather arm's two scatters leave: the live slots' rows at
    position ``length - 1``, nothing for an idle slot."""
    import jax.numpy as jnp
    lengths = np.asarray(case["lengths"])
    block = case["c"].shape[2]
    at = lengths - 1
    out = []
    for pages, new, width in ((case["c"], case["new_c"], LATENT),
                              (case["kr"], case["new_kr"], ROPE)):
        for s in np.flatnonzero(lengths > 0):
            pages = pages.at[layer, case["tables"][s, at[s] // block],
                             at[s] % block, :width].set(new[s, :width])
        out.append(pages)
    return out


def _latent_oracle(case, c, kr, layer=1):
    """The gather arm over one layer (it gathers the dead entries too,
    at weight 0: its null block holds zeros)."""
    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_latent_attention_dense)
    return paged_latent_attention_dense(
        case["q_c"], case["q_r"], c[layer].at[0].set(0.0),
        kr[layer].at[0].set(0.0), case["tables"], case["lengths"],
        scale=SCALE)


def _bits(a):
    """An array's bytes, so that rows that hold no number compare."""
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("block, heads, lengths", LATENT_GEOMETRIES)
def test_the_latent_kernel_reads_what_the_gather_arm_reads(block, heads,
                                                           lengths):
    """Ragged lengths in one call, an idle slot among them, lengths that
    end on a page's first and on its last row: every live slot within
    bfloat16's rounding of the gather arm (both round the weights once;
    the kernel before the division by their sum), the idle slot exact
    zeros. The token's own row is read through the cache."""
    case = _latent_case(block, heads, lengths)
    got, c, kr = _latent_written(case)
    want = np.asarray(_latent_oracle(case, *_latent_scattered(case)))
    got, live = np.asarray(got), np.asarray(case["lengths"]) > 0
    assert got.shape == (len(lengths), heads, LATENT)
    assert got.dtype == np.float32
    assert np.abs(got[live] - want[live]).max() <= 2e-2 * np.abs(want).max()
    np.testing.assert_array_equal(got[~live], 0.0)
    # without its own row a slot of length 1 would have nothing to read
    assert np.abs(got[4]).max() > 0


@pytest.mark.parametrize("block, heads, lengths", LATENT_GEOMETRIES)
def test_the_latent_write_leaves_every_other_row_as_it_was(block, heads,
                                                           lengths):
    """The writing form against the scatter, bit for bit over both
    arrays whole (both layers, the block that holds no number, the
    lanes beside a row's values): the written row is the scatter's,
    every other row untouched, an idle slot writes nothing; and the
    reading form over the written arrays answers the same to the bit."""
    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_latent_attention)
    case = _latent_case(block, heads, lengths)
    got, c, kr = _latent_written(case)
    want_c, want_kr = _latent_scattered(case)
    np.testing.assert_array_equal(_bits(c), _bits(want_c))
    np.testing.assert_array_equal(_bits(kr), _bits(want_kr))
    assert (_bits(c) != _bits(case["c"])).any(axis=-1).sum() == 5
    read = paged_latent_attention(case["q_c"], case["q_r"], c, kr,
                                  case["tables"], case["lengths"], layer=1,
                                  scale=SCALE)
    np.testing.assert_array_equal(np.asarray(read), np.asarray(got))


@pytest.mark.parametrize("block, heads, lengths", LATENT_GEOMETRIES)
def test_a_latent_tables_dead_tail_is_never_looked_up(block, heads, lengths):
    """A table three times as wide as the longest context, its dead
    entries at the block that holds no number: the answer is the narrow
    table's to the bit, at every size of an item."""
    narrow = _latent_case(block, heads, lengths)
    width = 3 * narrow["tables"].shape[1]
    wide = _latent_case(block, heads, lengths, table=width)
    assert wide["tables"].shape[1] == width
    want = np.asarray(_latent_written(narrow)[0])
    assert np.isfinite(want).all()
    for target_rows in (block, 1024, 1 << 20):
        got = np.asarray(_latent_written(wide, target_rows=target_rows)[0])
        if target_rows == 1024:
            np.testing.assert_array_equal(got, want)
        else:       # another order of the online softmax's sums
            np.testing.assert_allclose(got, want, atol=2e-2)


@pytest.mark.parametrize("block, heads, lengths", LATENT_GEOMETRIES)
def test_a_rotated_key_stored_wider_reads_the_same(block, heads, lengths):
    """The replica stores the rotated key as wide as the device's lanes
    (and may the latent): rows 128 and 64 wide answer what rows of the
    values' own widths answer (to the order of a product's sums: the
    zeros beside the values are summed too), and the lanes beside the
    values stay zeros."""
    own = _latent_case(block, heads, lengths, kr_row=ROPE)
    wide = _latent_case(block, heads, lengths, kr_row=128, c_row=64)
    want, _, _ = _latent_written(own)
    got, c, kr = _latent_written(wide)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert not np.asarray(kr[:, 1:, :, ROPE:], np.float32).any()
    assert not np.asarray(c[:, 1:, :, LATENT:], np.float32).any()


@pytest.mark.parametrize("block", [4, 8])
def test_the_latent_kernel_in_float32_is_the_gather_arm(block):
    """float32 pages round no weight: the kernel is the oracle to
    accumulation order, through both ways a row gets into its page (8
    positions a page are whole float32 tiles, so the row goes through
    its tile; 4 are none, and interpreted the row is copied as it is)."""
    from distributedmnist_tpu.ops import pallas_paged_attention as ppa
    case = _latent_case(block, 4, [5, 0, 24, 13, 8], dtype="float32")
    assert ppa._latent_rows_tile(case["c"]) == (8 if block == 8 else None)
    got, c, kr = _latent_written(case, target_rows=8)
    want_c, want_kr = _latent_scattered(case)
    np.testing.assert_array_equal(_bits(c), _bits(want_c))
    np.testing.assert_array_equal(_bits(kr), _bits(want_kr))
    want = np.asarray(_latent_oracle(case, want_c, want_kr))
    live = np.asarray(case["lengths"]) > 0
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=1e-5,
                               rtol=1e-5)


AS_THEY_LIE = [
    # shapes, writes, itemsize, whether Mosaic takes them
    pytest.param([(5, 16385, 16, 512), (5, 16385, 16, 128)], True, 2, True,
                 id="the-latent-cell"),
    pytest.param([(2, 6145, 128, 512), (2, 6145, 128, 128)], True, 2, True,
                 id="the-hybrid-cell"),
    pytest.param([(5, 16385, 16, 512), (5, 16385, 16, 64)], True, 2, False,
                 id="a-key-of-half-a-lane"),
    pytest.param([(2, 65, 8, 128), (2, 65, 8, 128)], True, 2, False,
                 id="bfloat16-pages-of-8-written"),
    pytest.param([(2, 65, 8, 128), (2, 65, 8, 128)], False, 2, True,
                 id="bfloat16-pages-of-8-read"),
    pytest.param([(2, 65, 8, 128), (2, 65, 8, 128)], True, 4, True,
                 id="float32-pages-of-8-written"),
]


@pytest.mark.parametrize("shapes, writes, itemsize, taken", AS_THEY_LIE)
def test_the_compiled_latent_forms_say_what_they_take(shapes, writes,
                                                      itemsize, taken):
    """One question for the step's arm and for the compiled entry
    points: every array's rows whole lanes and, to write, a page whole
    tiles. Compiled for anything else the entry points raise (no scatter
    and gather inside them: the gather arm is the caller's); the reading
    form is not held to the write's tile."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops import pallas_paged_attention as ppa
    assert ppa.latent_rows_as_they_lie(*shapes, writes=writes,
                                       itemsize=itemsize) is taken
    if taken:
        return
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    c, kr = (jax.ShapeDtypeStruct((2, 9, *shape[2:]), dtype)
             for shape in shapes)
    q_c, q_r = (jax.ShapeDtypeStruct((3, 4, a.shape[-1]), dtype)
                for a in (c, kr))
    new_c, new_kr = (jax.ShapeDtypeStruct((3, a.shape[-1]), dtype)
                     for a in (c, kr))
    tables = jax.ShapeDtypeStruct((3, 2), jnp.int32)
    lengths = jax.ShapeDtypeStruct((3,), jnp.int32)
    with pytest.raises(ValueError, match="whole lanes"):
        jax.eval_shape(
            lambda *a: ppa.paged_latent_attention_write(
                *a, scale=SCALE, interpret=False),
            q_c, q_r, new_c, new_kr, c, kr, tables, lengths)
    if not ppa.latent_rows_as_they_lie(*shapes, writes=False):
        with pytest.raises(ValueError, match="whole lanes"):
            jax.eval_shape(
                lambda *a: ppa.paged_latent_attention(
                    *a, scale=SCALE, interpret=False),
                q_c, q_r, c, kr, tables, lengths)


@pytest.mark.parametrize("unroll, turns_of", [(1, 1), (8, 6), (64, 12)])
def test_the_page_loop_turns_by_a_divisor_of_the_items_pages(unroll,
                                                             turns_of):
    """``PAGE_UNROLL`` pages' DMAs a turn of the loop, or the largest
    divisor of the item's pages under it (12 entries of table: 6 a turn
    at 8, all 12 at 64): the same DMAs in the same order, so the answer
    and both arrays are the one-a-turn loop's to the bit."""
    from unittest import mock

    import jax

    from distributedmnist_tpu.ops import pallas_paged_attention as ppa
    case = _latent_case(16, 4, [17, 0, 90, 190], table=12)
    seen = []
    real = ppa._latent_kernel

    def spy(*refs, unroll, **how):
        seen.append(unroll)
        return real(*refs, unroll=unroll, **how)

    def written():
        jax.clear_caches()
        return [np.asarray(a) for a in _latent_written(case)]

    with mock.patch.object(ppa, "PAGE_UNROLL", 1):
        want = written()
    with mock.patch.object(ppa, "PAGE_UNROLL", unroll), \
            mock.patch.object(ppa, "_latent_kernel", spy):
        got = written()
    assert seen == [turns_of]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def test_an_items_rows_follow_the_pages_size():
    """``TARGET_ROWS`` is rows, not pages: 16 positions a page make an
    item of 64 entries, 128 of 8, a table narrower than that of its own
    width, and a page larger than the target of one."""
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.ops import pallas_paged_attention as ppa

    def pages_of(block, table, **how):
        seen = []
        real = ppa._work_items

        def spy(lengths, block_size, table_width, pages):
            seen.append(pages)
            return real(lengths, block_size, table_width, pages)

        case = _latent_case(block, 4, [3, 0])
        tables = jnp.zeros((2, table), jnp.int32).at[0, 0].set(1)
        with mock.patch.object(ppa, "_work_items", spy):
            jax.eval_shape(lambda: ppa.paged_latent_attention.__wrapped__(
                case["q_c"], case["q_r"], case["c"], case["kr"], tables,
                case["lengths"], scale=SCALE, interpret=True, **how))
        return seen[0]

    assert ppa.TARGET_ROWS == 1024
    assert pages_of(16, 256) == 64 and pages_of(128, 48) == 8
    assert pages_of(16, 12) == 12 and pages_of(2048, 6) == 1
    assert pages_of(16, 256, target_rows=256) == 16
