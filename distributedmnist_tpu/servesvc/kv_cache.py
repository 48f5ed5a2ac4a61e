"""Paged KV cache for continuous-batching autoregressive decode.

The decode service's memory manager: K/V for every in-flight sequence
live in ONE pair of device arrays shaped ``[layers, num_blocks,
block_size, heads, head_dim]``, carved into fixed-size blocks a
free-list allocator hands out. Each sequence owns a **block table** —
a fixed-width ``[max_blocks_per_seq]`` int32 map from its position
range to blocks — so the compiled decode step reads any mix of
sequence lengths through one gather, and finishing a 7-token sequence
returns its blocks to the pool the same step a 90-token neighbor keeps
generating. This is what lets wildly different lengths share a
compiled decode shape instead of bucket-padding rounds. The dense
gather reads every position of the table it is handed, so the decode
loop hands the step the first ``w`` entries of every table, ``w`` the
narrowest of four widths (the quarters of ``max_blocks_per_seq``) that
holds its longest live sequence: four compiled shapes, not one, and
the full width only for a sequence that long (servesvc/decode.py).

A TPU's default layout of that shape, with a head under 128 wide, makes
the BLOCK index minor, so each program that takes the cache transposes
both arrays in and back out. The programs touch a row's first
``head_dim`` elements however wide it is stored, so an owner that runs
them a token (the decode replica) builds the cache as wide as
:func:`stored_head_dim` answers (ROADMAP S1).

A model that attends through a latent keeps, in place of keys and values
a head, ONE row a token a layer for all heads in each array: the latent
in ``k`` and the rotated key in ``v``, ``[layers, num_blocks, block_size,
width]`` (:func:`cache_shapes`; its ``decode_cache_shape`` carries the
pair of widths). The allocator, the tables and the scatter are the same.

Block 0 is the **reserved null block**: idle decode slots point their
whole table (and, where the step scatters the new rows, their writes)
at it, so the fixed-shape step never needs a branch — garbage lands in
a block no sequence owns. (The paged arm's kernel writes a live slot's
rows itself and none for a slot of length 0.)

Invariants the allocator maintains (property-tested in
tests/test_kv_cache.py): a block is never assigned to two live
sequences, alloc+free conserves the pool exactly, and reading a
sequence back through its block table reproduces a dense reference
cache byte-for-byte.

Allocation policy: admission reserves EVERY block a sequence can need
(prompt + max_new_tokens) up front, so an admitted sequence always
runs to completion — block pressure defers admission (the request
waits, bounded by its deadline), it never kills a running generation.

**State that is a sequence's and not a token's** lives beside the paged
arrays in :class:`SlotState`: a model with mixer layers (state-space,
ops/ssm.py; delta-rule, ops/kda.py) keeps of a sequence, a such layer,
one recurrent state and the last inputs of its convolution, the same
bytes however long the sequence. No block table: a decode slot owns its
slice of both arrays whole from admission to finish. The paged cache then
holds the rows of the layers that attend, and those only (keys and values
a head, or a latent model's pair of rows a token).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NULL_BLOCK = 0


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size blocks.

    Block 0 (:data:`NULL_BLOCK`) is reserved and never handed out.
    ``alloc`` is all-or-nothing: a request the pool cannot satisfy
    returns None and takes nothing (the caller defers admission)."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (one is the reserved null block), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed blocks are re-used first
        # (their cache lines are the warmest)
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._in_use: set[int] = set()

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> frozenset[int]:
        return frozenset(self._in_use)

    def alloc(self, n: int) -> tuple[int, ...] | None:
        """n blocks, or None (and no change) when the pool is short."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            return None
        got = tuple(self._free.pop() for _ in range(n))
        self._in_use.update(got)
        return got

    def free(self, blocks) -> None:
        for b in blocks:
            if b not in self._in_use:
                raise ValueError(
                    f"double free / foreign block {b} (in_use="
                    f"{sorted(self._in_use)})")
            self._in_use.remove(b)
            self._free.append(b)


def cache_shapes(num_layers: int, num_blocks: int, block_size: int,
                 num_heads: int, head_dim) -> tuple[tuple[int, ...], ...]:
    """The shapes of the cache's two arrays from a model's
    ``decode_cache_shape``: keys and values a head, ``[layers,
    num_blocks, block_size, heads, head_dim]`` both; or, where
    ``head_dim`` is a pair of widths, one row a token for all heads in
    each (a latent beside a rotated key), ``[layers, num_blocks,
    block_size, width]``: a block's positions are then the second-minor
    dimension, which a tiled layout pads to eight rows and would pad a
    dimension of one head to as well."""
    if isinstance(head_dim, (tuple, list)):
        return tuple((num_layers, num_blocks, block_size, int(w))
                     for w in head_dim)
    return ((num_layers, num_blocks, block_size, num_heads, head_dim),) * 2


def stored_head_dim(shape: tuple[int, ...], dtype,
                    sharding: jax.sharding.Sharding | None = None) -> int:
    """The row width at which a cache array of ``shape`` (one of
    :func:`cache_shapes`', the row's width last) is stored with its rows
    whole. The compiler is asked how the device lays the shape out (a
    program that returns zeros of it, compiled and not run): where the
    last dimension stays minor, ``head_dim`` itself (a CPU; a 128-wide
    head on a TPU); where another dimension would be minor, ``head_dim``
    rounded up to the layout's lanes, if the device keeps that shape's
    rows whole."""
    def layout(shape):
        zeros = jax.jit(lambda: jnp.zeros(shape, dtype),
                        out_shardings=sharding)
        return zeros.lower().compile().output_formats.layout

    def rows_whole(at):
        return tuple(at.major_to_minor) == tuple(range(len(shape)))

    head_dim = shape[-1]
    asked = layout(shape)
    if rows_whole(asked) or not asked.tiling:
        return head_dim
    lanes = asked.tiling[0][-1]
    wide = -(-head_dim // lanes) * lanes
    return wide if rows_whole(layout((*shape[:-1], wide))) else head_dim


@jax.named_scope("cache_write")
def write_prompt_kv(k_cache: jax.Array, v_cache: jax.Array,
                    ks: jax.Array, vs: jax.Array,
                    block_table: jax.Array, length: jax.Array, *,
                    block_size: int) -> tuple[jax.Array, jax.Array]:
    """Scatter one sequence's prefill K/V into its blocks.

    ``ks``/``vs`` [L, s_pad, h, hd] (the prefill export for ONE
    sequence, padded to its prompt bucket; [L, s_pad, width] each where
    the cache keeps one row a token) go into the first elements of the
    cache's rows; positions ``< length`` land
    at ``block_table[pos // block_size]`` offset ``pos % block_size``,
    padding positions are routed to the null block. jit this once per
    prompt bucket shape."""
    s_pad = ks.shape[1]
    pos = jnp.arange(s_pad)
    blk_ids = jnp.where(pos < length,
                        block_table[pos // block_size], NULL_BLOCK)
    offs = pos % block_size
    # a row may be stored wider (stored_head_dim)
    k_cache = k_cache.at[:, blk_ids, offs, ..., :ks.shape[-1]].set(
        ks.astype(k_cache.dtype))
    v_cache = v_cache.at[:, blk_ids, offs, ..., :vs.shape[-1]].set(
        vs.astype(v_cache.dtype))
    return k_cache, v_cache


class PagedKVCache:
    """The device arrays + allocator + block-table bookkeeping.

    ``k``/``v`` are functional jax arrays — every write goes through a
    jitted scatter that returns the new arrays and is reassigned here
    (single-writer: the decode loop thread)."""

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 num_heads: int, head_dim: "int | tuple[int, int]",
                 max_blocks_per_seq: int, dtype=jnp.float32):
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.allocator = BlockAllocator(num_blocks)
        k_shape, v_shape = cache_shapes(num_layers, num_blocks, block_size,
                                        num_heads, head_dim)
        self.k = jnp.zeros(k_shape, dtype)
        self.v = jnp.zeros(v_shape, dtype)
        # write_prompt's caller rebinds self.k/self.v to the outputs —
        # donate the cache operands so the scatter updates in place.
        # The function itself with a static argument, not a partial of
        # it: a profiler trace calls the program `jit_write_prompt_kv`
        self._write = jax.jit(write_prompt_kv, static_argnames="block_size",
                              donate_argnums=(0, 1))

    def alloc_sequence(self, total_len: int) -> np.ndarray | None:
        """Reserve blocks for a sequence of up to ``total_len`` tokens;
        returns its fixed-width block table (padded with the null
        block) or None under block pressure (nothing taken)."""
        need = -(-total_len // self.block_size)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"{total_len} tokens need {need} blocks > "
                f"max_blocks_per_seq={self.max_blocks_per_seq}")
        got = self.allocator.alloc(need)
        if got is None:
            return None
        table = np.full((self.max_blocks_per_seq,), NULL_BLOCK,
                        dtype=np.int32)
        table[:need] = got
        return table

    def free_sequence(self, block_table: np.ndarray) -> None:
        self.allocator.free(int(b) for b in block_table
                            if int(b) != NULL_BLOCK)

    def write_prompt(self, block_table: np.ndarray, ks, vs,
                     length: int) -> None:
        """Install one sequence's prefill K/V (``ks``/``vs``
        [L, s_pad, h, hd])."""
        self.k, self.v = self._write(self.k, self.v, ks, vs,
                                     jnp.asarray(block_table),
                                     jnp.asarray(length),
                                     block_size=self.block_size)

    def gather_dense(self, block_table: np.ndarray,
                     length: int) -> tuple[np.ndarray, np.ndarray]:
        """Read a sequence back as dense [L, length, h, hd] arrays —
        the reference view the property tests compare against (host
        path, not used by the decode step)."""
        k = np.asarray(jax.device_get(self.k))
        v = np.asarray(jax.device_get(self.v))
        ks, vs = [], []
        for pos in range(length):
            b = int(block_table[pos // self.block_size])
            o = pos % self.block_size
            ks.append(k[:, b, o])
            vs.append(v[:, b, o])
        return np.stack(ks, axis=1), np.stack(vs, axis=1)


@jax.named_scope("state_write")
def write_slot_state(state: tuple, tail: tuple, new_state: jax.Array,
                     new_tail: jax.Array, slot: jax.Array, *,
                     row: int = 0) -> tuple[tuple, tuple]:
    """One slot's slice of every layer's two arrays replaced: ``state`` a
    layer ``[slots, N, E]``, ``tail`` a layer ``[K - 1, slots, W]``;
    ``new_state`` [layers, batch, N, E] and ``new_tail`` [layers, K - 1,
    batch, W] as a prefill hands them over, of which sequence ``row`` is
    taken. Nothing of what the slot held is read."""
    state = tuple(jax.lax.dynamic_update_slice(
        s, new_state[i, row][None].astype(s.dtype),
        (slot,) + (0,) * (s.ndim - 1))
        for i, s in enumerate(state))
    tail = tuple(jax.lax.dynamic_update_slice(
        t, new_tail[i, :, row][:, None].astype(t.dtype), (0, slot, 0))
        for i, t in enumerate(tail))
    return state, tail


class SlotState:
    """The arrays a decode slot owns whole, beside the paged cache, ONE
    PAIR A LAYER that has such a state: ``state[l]`` [slots, N, E]
    float32, a mixer layer's recurrent state a slot, and ``tail[l]`` [K -
    1, slots, W] in the compute dtype, the inputs of its convolution
    before the slot's next token (oldest first). By kind of mixer:

    * state-space (ops/ssm.py): ``N`` the state's size a channel, ``E``
      the channels, and the tail as wide as the channels (``W = E``): at
      the published Jamba widths ``[slots, 16, 5120]`` and ``[3, slots,
      5120]``;
    * delta-rule (ops/kda.py): a matrix a head: ``N = (heads, D)``, a pair
      (``state_dim`` may be a tuple: the heads apart, the rank the update
      is one elementwise pass at), ``D`` key channels a head, ``E = D``
      value channels, and the tail as wide as the three convolved
      streams, ``W = 3 x heads x D`` (``tail_channels``): at 32 heads of
      128, ``[slots, 32, 128, 128]`` (the bytes and tiles of ``[slots,
      4096, 128]``) and ``[3, slots, 12288]``.

    The minor dimension of both is what a step's elementwise update runs
    along. An array a layer, not one stacked over the layers: a
    layer's update is then one elementwise pass that writes where it
    reads (the arrays donated), where a slice of a stacked array written
    back costs a second pass over the state (measured: PERF.md, PR 43).
    Float32 for the state whatever the model computes in: a recurrence
    over thousands of steps.

    A slot's life: :meth:`alloc` at admission (it holds zeros), then
    :meth:`write` with what the prefill hands over, the step advancing it
    in place (the arrays donated and rebound by the caller, as the cache's
    are), :meth:`free` at finish. :meth:`reset` zeroes a slot: at free,
    and before a restart's re-prefill. Functional arrays, single writer
    (the decode loop thread)."""

    def __init__(self, layers: int, slots: int,
                 state_dim: "int | tuple[int, ...]", channels: int,
                 taps_before: int, tail_channels: int | None = None,
                 dtype=jnp.float32, state_dtype=jnp.float32):
        wide = channels if tail_channels is None else tail_channels
        dims = state_dim if isinstance(state_dim, tuple) else (state_dim,)
        self.state = tuple(jnp.zeros((slots, *dims, channels),
                                     state_dtype) for _ in range(layers))
        self.tail = tuple(jnp.zeros((taps_before, slots, wide), dtype)
                          for _ in range(layers))
        self._zeros = (jnp.zeros((layers, 1, *dims, channels),
                                 state_dtype),
                       jnp.zeros((layers, taps_before, 1, wide), dtype))
        self._owned: set[int] = set()
        self.resets = 0
        # the function itself, so that a trace calls the program
        # `jit_write_slot_state`; the arrays donated: a slot is written
        # where it lies
        self._write = jax.jit(write_slot_state, donate_argnums=(0, 1),
                              static_argnames="row")

    @property
    def arrays(self) -> tuple[tuple, tuple]:
        return self.state, self.tail

    def place(self, put) -> None:
        """Every array (and the zeros a reset writes) through ``put``,
        once: where the weights are, so that no later call is compiled
        again for an argument placed differently."""
        self.state, self.tail, self._zeros = put(
            (self.state, self.tail, self._zeros))

    def slot_bytes(self) -> int:
        """Bytes one sequence's state takes, all layers."""
        return sum(a.size * a.dtype.itemsize for a in self._zeros)

    def device_bytes(self) -> int:
        return sum(a.on_device_size_in_bytes()
                   for a in (*self.state, *self.tail))

    def alloc(self, slot: int) -> None:
        if slot in self._owned:
            raise ValueError(f"slot {slot} already owns its state")
        self._owned.add(slot)

    def write(self, slot: int, state: jax.Array, tail: jax.Array,
              row: int = 0) -> None:
        """Sequence ``row`` of a prefill's end state into the slot:
        ``state`` [layers, batch, N, E], ``tail`` [layers, K - 1, batch,
        E]."""
        if slot not in self._owned:
            raise ValueError(f"slot {slot} was not allocated")
        self.state, self.tail = self._write(
            self.state, self.tail, state, tail, jnp.asarray(slot, jnp.int32),
            row=row)

    def reset(self, slot: int) -> None:
        self.state, self.tail = self._write(
            self.state, self.tail, *self._zeros,
            jnp.asarray(slot, jnp.int32))
        self.resets += 1

    def free(self, slot: int) -> None:
        if slot not in self._owned:
            raise ValueError(f"slot {slot} owns no state to free")
        self.reset(slot)
        self._owned.remove(slot)
