"""Single-query paged attention as a Pallas TPU kernel.

The decode twin of :mod:`ops.pallas_attention`, and on a TPU the read
the plain block's decode step makes of the paged cache
(:mod:`servesvc.kv_cache`: ``[layers, num_blocks, block_size, heads,
width]`` arrays, ``width`` the head's size or wider). The gather arm
(``models/transformer.py::_decode_attn``, and
:func:`paged_attention_dense` here) copies EVERY entry of the table it
is handed into a ``[slots, context, heads, head_dim]`` array and makes
a float32 view of that before it attends: a 10-token sequence pays what
a 1k-token one pays, several times over.

This kernel reads the rows where they lie. The cache arrays stay in HBM
and are passed whole, with the layer's index; one call serves every
slot of one layer:

* outside the kernel the live pages are cut into **work items**, one a
  (slot, chunk of ``pages_per_step`` table entries): an idle slot and a
  table's dead tail make none, so they are neither fetched nor
  computed (their entries, all
  :data:`servesvc.kv_cache.NULL_BLOCK`, are never looked up);
* inside, one loop walks the items: the pages of item ``i + 1`` are in
  flight (one DMA a page into the other half of a double buffer, across
  the edge between two slots too) while item ``i`` is computed;
* an item's scores are ONE product on the matrix unit: the slot's query
  ``[heads, width]`` against the chunk's rows as stored, ``[pages ·
  block_size · heads, width]``, every head against every row, of which
  a mask keeps a head's own rows (the unit has the room: a product a
  head would be ``heads`` one-row products a page); the weighted sum of
  values is one product the same way. The operands go in as stored
  (bfloat16 on the chip) and accumulate in float32.

The decode step has one more thing to do with the cache: put this
token's keys and values into it before it attends (position
``length - 1`` reads itself through the cache). A scatter through XLA
of ``[slots, heads, head_dim]`` into rows stored wider compiles on a
TPU to a loop a layer an array, an iteration a slot, whatever the slots
hold. :func:`paged_attention_write` is the same call with the rows
given: the cache arrays are its inputs AND its outputs in one buffer
(``input_output_aliases``), and before the first page is fetched the
kernel copies each live slot's ``[heads, width]`` rows, one run of a
page, from VMEM to where the table says (a DMA an array a slot, all in
flight together under the mask's set-up, then waited for). A slot of
length 0 writes nothing.

Numeric semantics are pinned to the gather arm (and its parity tests):
scores and softmax in float32, scale ``1/sqrt(head_dim)``, masked
positions get the finite ``-1e30`` (whose exp underflows to exactly 0.0
in float32), an online softmax over chunks, and the weighted sum of
values accumulated in float32 **from float32 weights**: against
bfloat16 values the weights go to the matrix unit as three bfloat16
pieces (``w = w1 + w2 + w3`` to float32's last bit), never rounded to
one. The ONE documented divergence: an idle slot (``length == 0``)
returns exact zeros here, while the gather softmaxes a fully-masked row
into a uniform average of cache garbage — both are
unspecified-by-contract (the decode loop never reads idle rows), and
the parity tests compare live slots only.

**The latent form** (:func:`paged_latent_attention`,
:func:`paged_latent_attention_write`; its own body and its own call,
``paged_latent_decode``, sharing the work items and the tile rule): a
latent block's cache (``models/transformer.py::
_latent_decode_attention``) keeps ONE row a token for all heads in each
of two arrays of different widths, ``[layers, num_blocks, block_size,
latent]`` and ``[.., rope]``, and the value is the latent itself. It
takes the absorbed queries ``q_c`` [slots, heads, latent] and ``q_r``
[slots, heads, rope], both arrays whole with the layer's index, the
tables and the lengths, and with the token's two rows writes them
first. An item is ``TARGET_ROWS`` cached positions whatever a page
holds; its scores are two products over one fetch of its pages, every
head against every row with no mask of heads, and the latent page is
fetched once for both the scores and the weighted sum. Queries and
outputs lie in HBM and move a slot at a time (a slot's ``[heads,
width]`` of a model with 128 heads is no whole-array block of VMEM).
One row a token is less than a tile, so the written row always goes
through the tile that holds it. Its numerics are pinned to ITS gather
arm, not to the above: the weights are rounded to the cache's dtype
ONCE before the product with the latents (``w.astype(cs.dtype)`` there),
no three pieces. Compiled it takes rows of whole lanes in both arrays
and, to write, pages of whole tiles (:func:`latent_rows_as_they_lie`,
which ``decode_attention_arm`` asks before it answers ``paged``), and
RAISES on anything else: no scatter and gather inside it.

Rows are taken as stored: a query padded with zeros beyond ``head_dim``
adds nothing to a score whatever the lanes beyond it hold, and the
output's lanes beyond ``head_dim`` are dropped. Compiled for a TPU the
row has to fill whole lanes (``width % 128 == 0``) and a block's rows
whole tiles (``block_size · heads % 16 == 0`` in bfloat16): what
``kv_cache.stored_head_dim`` makes of a real model's cache. Anything
else runs interpreted (``interpret=None`` picks the interpreter off the
TPU, same as the training kernel) or, the plain form, through the
gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # finite: matches decode_step's mask, exp -> exact 0.0

_LANE = 128

#: table entries an item holds: their DMAs are in flight together, and
#: an item's fixed cost (the loop, the products' fill and drain, the
#: softmax's bookkeeping) is paid once for them; a slot's last item
#: computes its dead pages too, masked (PERF.md has the chip's readings)
PAGES_PER_STEP = 8


def _paged_kernel(tables_ref, lengths_ref, slot_ref, chunk_ref, count_ref,
                  layer_ref, q_ref, *refs,
                  scale: float, num_heads: int, kv_heads: int,
                  block_size: int, pages: int, table_width: int,
                  writes: bool, group: int = 1, tile_rows: int = 0):
    """Every work item of one layer, in one loop; with ``writes``, each
    live slot's new rows put into the cache first.

    ``tables_ref`` .. ``layer_ref`` are the scalar-prefetch operands
    (SMEM): the block tables, the lengths, each item's slot and chunk,
    the number of items, the layer. ``k_hbm``/``v_hbm`` are the cache
    arrays where they lie, ``[layers, num_blocks, block_size · heads,
    width]``; ``k_buf``/``v_buf`` two chunks of rows each. With
    ``writes`` the step's new rows ``k_new``/``v_new`` ``[slots, heads,
    width]`` come in beside the query, and the cache arrays are the
    call's outputs too, aliased to its inputs: written and read through
    the output's name, which off the chip (interpreted) is the one that
    holds what was written.

    ``num_heads`` query heads read ``kv_heads`` heads' rows, ``group``
    queries in a row each their own head's (a page is ``block_size *
    kv_heads`` rows; a query head beyond ``group * kv_heads`` owns no
    row): with one key-value head the mask keeps every row for every
    query and an item's scores are the plain product.

    ``tile_rows`` > 0: a token's ``kv_heads`` rows are fewer than the
    rows of one tile of the cache as the device stores it (one bfloat16
    row is half a 32-bit sublane: no DMA can address it). Each live
    slot's tile of ``tile_rows`` rows that holds the token's position
    then comes into ``k_rmw``/``v_rmw`` [slots, tile_rows, width] whole,
    takes the new rows there, and goes back whole: two rounds of copies,
    each all in flight together. No two slots share a page."""
    if writes and tile_rows:
        (k_new, v_new, _, _, o_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
         bias_ref, pos_ref, m_ref, l_ref, acc_ref, write_sem,
         k_rmw, v_rmw) = refs
    elif writes:
        (k_new, v_new, _, _, o_ref, k_hbm, v_hbm, k_buf, v_buf, sem,
         bias_ref, pos_ref, m_ref, l_ref, acc_ref, write_sem) = refs
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
         bias_ref, pos_ref, m_ref, l_ref, acc_ref) = refs
    page_rows = block_size * kv_heads
    rows = pages * page_rows
    layer, num_items = layer_ref[0], count_ref[0]

    def row_copies(slot):
        """The two DMAs of a slot's new rows: ``kv_heads`` rows in one
        run, at offset ``(length - 1) % block_size`` of the page that
        holds position ``length - 1``."""
        at = lengths_ref[slot] - 1
        block = tables_ref[slot, at // block_size]
        if tile_rows:
            return tile_copies(slot, block, at, back=True)
        run = pl.ds(pl.multiple_of((at % block_size) * kv_heads, kv_heads),
                    kv_heads)
        return (pltpu.make_async_copy(k_new.at[slot],
                                      k_hbm.at[layer, block, run],
                                      write_sem.at[0]),
                pltpu.make_async_copy(v_new.at[slot],
                                      v_hbm.at[layer, block, run],
                                      write_sem.at[1]))

    def tile_of(at):
        """The first row of the tile that holds position ``at``'s rows."""
        first = (at % block_size) * kv_heads
        return pl.multiple_of(first // tile_rows * tile_rows, tile_rows)

    def tile_copies(slot, block, at, back: bool):
        """The two DMAs of a slot's tile, from the cache or back to it."""
        run = pl.ds(tile_of(at), tile_rows)
        pairs = ((k_hbm.at[layer, block, run], k_rmw.at[slot]),
                 (v_hbm.at[layer, block, run], v_rmw.at[slot]))
        return tuple(pltpu.make_async_copy(*(pair[::-1] if back else pair),
                                           write_sem.at[i])
                     for i, pair in enumerate(pairs))

    def tiles_in(slot):
        at = lengths_ref[slot] - 1
        return tile_copies(slot, tables_ref[slot, at // block_size], at,
                           back=False)

    def put_new_rows(slot, _):
        """The slot's new rows into its tile, in VMEM: a select a row."""
        at = lengths_ref[slot] - 1
        first = (at % block_size) * kv_heads - tile_of(at)
        row_id = jax.lax.broadcasted_iota(jnp.int32, k_rmw.shape[1:], 0)
        for new, tile in ((k_new, k_rmw), (v_new, v_rmw)):
            rows, held = new[slot], tile[slot]
            for j in range(kv_heads):
                held = jnp.where(row_id == first + j, rows[j:j + 1], held)
            tile[slot] = held

    def each_written_row(copies_of, act):
        """``act`` on the DMAs ``copies_of(slot)`` of every slot that has
        a row to write: a slot of length 0 writes nothing (its table is
        never looked up), nor does a position beyond the table."""
        def one(slot, _):
            n = lengths_ref[slot]

            @pl.when((n > 0) & (n <= table_width * block_size))
            def _():
                for dma in copies_of(slot):
                    act(dma)
        jax.lax.fori_loop(0, lengths_ref.shape[0], one, None)

    if writes and tile_rows:
        each_written_row(tiles_in, lambda dma: dma.start())
        each_written_row(tiles_in, lambda dma: dma.wait())
        # (a slot with nothing to write selects into a tile nobody reads)
        jax.lax.fori_loop(0, lengths_ref.shape[0], put_new_rows, None)
    if writes:
        # in flight under the set-up below, landed before the first
        # page is fetched: position length - 1 is read through the cache
        each_written_row(row_copies, lambda dma: dma.start())

    def live_pages(slot):
        return jnp.minimum(pl.cdiv(lengths_ref[slot], block_size),
                           table_width)

    def is_live(item, p):
        return chunk_ref[item] * pages + p < live_pages(slot_ref[item])

    def page_rows_of(p):
        return pl.ds(p * page_rows, page_rows)

    def copies(item, half, p):
        """The two DMAs of page ``p`` of the item's chunk, made for a
        live page only: a dead entry of the table is not looked up."""
        block = tables_ref[slot_ref[item], chunk_ref[item] * pages + p]
        return (pltpu.make_async_copy(k_hbm.at[layer, block],
                                      k_buf.at[half, page_rows_of(p)],
                                      sem.at[half, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, block],
                                      v_buf.at[half, page_rows_of(p)],
                                      sem.at[half, 1]))

    def start(item, half):
        for p in range(pages):
            @pl.when(is_live(item, p))
            def _():
                for dma in copies(item, half, p):
                    dma.start()

    def wait(item, half):
        """One wait a start; a page never fetched holds what the buffer
        held, and a weight of exactly 0 times a value that is no number
        is no number: its values are zeroed."""
        for p in range(pages):
            live = is_live(item, p)

            @pl.when(live)
            def _():
                for dma in copies(item, half, p):
                    dma.wait()

            @pl.when(jnp.logical_not(live))
            def _():
                v_buf[half, page_rows_of(p)] = jnp.zeros(
                    (page_rows, v_buf.shape[-1]), v_buf.dtype)

    # column c of an item's scores is row c of its chunk: position
    # c // kv_heads of the chunk, head c % kv_heads. A query head keeps
    # its own key-value head's rows; the rest gets the mask's value
    # through this addend
    col = jax.lax.broadcasted_iota(jnp.int32, (num_heads, rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (num_heads, rows), 0)
    owner = head if group == 1 else head // group
    bias_ref[...] = jnp.where(col % kv_heads == owner, 0.0, _NEG_INF)
    pos_ref[...] = jax.lax.broadcasted_iota(
        jnp.int32, pos_ref.shape, 1) // kv_heads
    o_ref[...] = jnp.zeros_like(o_ref)  # an idle slot's rows
    if writes:
        each_written_row(row_copies, lambda dma: dma.wait())

    @pl.when(num_items > 0)
    def _():
        start(0, 0)

    def item_step(item, _):
        half = item % 2

        @pl.when(item + 1 < num_items)
        def _():
            start(item + 1, 1 - half)

        slot, chunk = slot_ref[item], chunk_ref[item]
        length = lengths_ref[slot]
        num_chunks = pl.cdiv(live_pages(slot), pages)

        @pl.when(chunk == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        wait(item, half)
        q = q_ref[slot]                                   # [h, width]
        k = k_buf[half]                                   # [rows, width]
        v = v_buf[half]
        exact = (jax.lax.Precision.HIGHEST
                 if k.dtype == jnp.float32 else None)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32)           # [h, rows]
        sc = sc * scale + bias_ref[...]
        left = length - chunk * (pages * block_size)
        sc = jnp.where(pos_ref[...] < left, sc, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        w = jnp.exp(sc - m_new)                           # [h, rows] f32
        corr = jnp.exp(m_prev - m_new)                    # [h, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(w, axis=1, keepdims=True)
        if v.dtype == jnp.bfloat16:
            # float32 weights against bfloat16 values, on a unit that
            # multiplies bfloat16: three pieces that sum to the weight,
            # one product, three row groups summed
            w1 = w.astype(jnp.bfloat16)
            r1 = w - w1.astype(jnp.float32)
            w2 = r1.astype(jnp.bfloat16)
            w3 = (r1 - w2.astype(jnp.float32)).astype(jnp.bfloat16)
            parts = jnp.dot(jnp.concatenate([w1, w2, w3], axis=0), v,
                            preferred_element_type=jnp.float32)
            wv = (parts[:num_heads] + parts[num_heads:2 * num_heads]
                  + parts[2 * num_heads:])
        else:
            wv = jnp.dot(w, v.astype(jnp.float32), precision=exact,
                         preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + wv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(chunk == num_chunks - 1)
        def _():
            o_ref[slot] = acc_ref[...] / l_ref[:, :1]

    jax.lax.fori_loop(0, num_items, item_step, None)


def _work_items(lengths: jax.Array, block_size: int, table_width: int,
                pages: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The live pages cut into items: (each item's slot, its chunk of
    the slot's table, the number of items), the first two as long as
    the most a call can hold."""
    num_slots = lengths.shape[0]
    live_pages = jnp.minimum(-(-lengths // block_size), table_width)
    chunks = -(-live_pages // pages)                      # [slots]
    ends = jnp.cumsum(chunks)
    item = jnp.arange(num_slots * -(-table_width // pages),
                      dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1),
                       num_slots - 1).astype(jnp.int32)
    chunk = item - (ends - chunks)[slot]
    return slot, chunk.astype(jnp.int32), ends[-1:].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    layer: jax.Array | int = 0, scale: float | None = None,
                    pages_per_step: int = PAGES_PER_STEP,
                    interpret: bool | None = None) -> jax.Array:
    """Single-query attention over a paged KV cache, one layer.

    ``q``: [slots, heads, head_dim], the current token's query. Its own
    keys and values are in the cache already (this form reads only;
    :func:`paged_attention_write` puts them there in the same call):
    position ``length-1`` attends to itself through the cache, exactly
    like the gather arm.
    ``k_pages``/``v_pages``: the cache arrays whole, [layers,
    num_blocks, block_size, heads, width] with ``layer`` the one to
    read (:class:`servesvc.kv_cache.PagedKVCache`'s, passed as they
    lie: no slice, no copy), or one layer's [num_blocks, block_size,
    heads, width]; ``width >= head_dim``, the row's first ``head_dim``
    elements are the head's. ``block_tables``: [slots, table width]
    int32, dead entries ``NULL_BLOCK``. ``lengths``: [slots] int32 —
    position count INCLUDING the current token; 0 marks an idle slot
    (output zeros). ``scale`` defaults to ``1/sqrt(head_dim)``.

    Returns [slots, heads, head_dim] float32.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    return _paged_call(q, None, k_pages, v_pages, block_tables, lengths,
                       layer=layer, scale=scale,
                       pages_per_step=pages_per_step, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "pages_per_step",
                                             "interpret"))
def paged_attention_write(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                          k_pages: jax.Array, v_pages: jax.Array,
                          block_tables: jax.Array, lengths: jax.Array, *,
                          layer: jax.Array | int = 0,
                          scale: float | None = None,
                          pages_per_step: int = PAGES_PER_STEP,
                          interpret: bool | None = None,
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`paged_attention` of a step that has yet to store its
    token: the same kernel writes the rows, then reads.

    ``k_new``/``v_new``: [slots, heads, width], the token's keys and
    values as the cache stores them (its dtype, its row's width: the
    head's values, zeros beside them). Slot ``s``'s rows go to position
    ``lengths[s] - 1`` (offset ``(lengths[s] - 1) % block_size`` of
    block ``block_tables[s, (lengths[s] - 1) // block_size]``) of
    ``layer`` in both arrays [layers, num_blocks, block_size, heads,
    width], which the call takes and returns as one buffer
    (``input_output_aliases``: donate them, or XLA copies each); a slot
    of length 0 writes nothing, where a scatter would send its row to
    the null block.

    Returns (the attention [slots, heads, head_dim] float32, the two
    cache arrays).
    """
    assert k_new.shape == v_new.shape == (q.shape[0], *k_pages.shape[-2:])
    assert k_new.dtype == k_pages.dtype and v_new.dtype == v_pages.dtype
    if not _interpreted(interpret) and (
            k_pages.shape[-1] % _LANE or _new_rows_tile(k_pages) is None):
        # compiled, a row has to fill whole lanes (what a cache as wide
        # as kv_cache.stored_head_dim answers has) and a token's rows
        # whole tiles of the cache, or a page whole tiles for them to go
        # through one (_paged_kernel): these rows are
        # scattered through XLA and read by the form that pads a layer
        k_pages, v_pages = (
            _scattered(pages, new, block_tables, lengths, layer)
            for pages, new in ((k_pages, k_new), (v_pages, v_new)))
        return (paged_attention(q, k_pages, v_pages, block_tables, lengths,
                                layer=layer, scale=scale,
                                pages_per_step=pages_per_step,
                                interpret=interpret), k_pages, v_pages)
    return _paged_call(q, (k_new, v_new), k_pages, v_pages, block_tables,
                       lengths, layer=layer, scale=scale,
                       pages_per_step=pages_per_step, interpret=interpret)


def _new_rows_tile(pages: jax.Array) -> int | None:
    """How a token's ``kv_heads`` rows get into a cache array ``[layers,
    blocks, block_size, kv_heads, width]`` as a TPU stores it (tiles of
    eight 32-bit sublanes, two bfloat16 rows in each): 0, they fill whole
    tiles and are copied where they lie; ``n``, they fill none and go
    through the tile of ``n`` rows that holds them (_paged_kernel); None,
    neither (a page is no whole tile either): not by the kernel."""
    tile = 8 * max(1, 4 // pages.dtype.itemsize)
    block_size, kv_heads = pages.shape[2:4]
    if kv_heads % tile == 0:
        return 0
    return None if block_size * kv_heads % tile else tile


def _interpreted(interpret: bool | None) -> bool:
    """``interpret`` as asked, or the interpreter off the TPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _scattered(pages, new, block_tables, lengths, layer):
    """``new`` at each live slot's position ``length - 1`` through XLA:
    what the kernel's row copies leave."""
    block_size = pages.shape[2]
    at = lengths - 1
    blocks = jnp.take_along_axis(
        block_tables, (jnp.maximum(at, 0) // block_size)[:, None], axis=1)
    blocks = jnp.where(lengths > 0, blocks[:, 0], pages.shape[1])
    return pages.at[layer, blocks, at % block_size].set(new, mode="drop")


def _paged_call(q, new_rows, k_pages, v_pages, block_tables, lengths, *,
                layer, scale, pages_per_step, interpret):
    """One call of the kernel over the cache arrays whole: the
    attention alone, or with ``new_rows`` (the keys' and the values')
    the attention and the two arrays those rows were written to."""
    num_slots, num_heads, hd = q.shape
    layers, num_blocks, block_size, kv_heads, row = k_pages.shape
    assert num_heads % kv_heads == 0 and row >= hd, (q.shape, k_pages.shape)
    assert v_pages.shape == k_pages.shape
    # the query heads the kernel holds: as given where each reads rows
    # of its own; where a group shares a head's rows, whole tiles of
    # them (heads beyond the model's own are zeros, own no rows and are
    # cut off the output)
    given, group = num_heads, num_heads // kv_heads
    if kv_heads != num_heads:
        num_heads = -(-num_heads // 16) * 16
    assert block_tables.shape[0] == num_slots == lengths.shape[0]
    table_width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    interpret = _interpreted(interpret)
    lengths = lengths.astype(jnp.int32)
    if not interpret and row % _LANE:
        # compiled, a row has to fill whole lanes: this layer's pages
        # padded, a copy a call (a cache as wide as
        # kv_cache.stored_head_dim answers never comes here, and the
        # writing form has turned such rows away)
        assert new_rows is None
        lanes = ((0, 0),) * 4 + ((0, -row % _LANE),)
        k_pages, v_pages = (
            jnp.pad(jax.lax.dynamic_index_in_dim(a, layer, keepdims=True),
                    lanes) for a in (k_pages, v_pages))
        layers, layer, row = 1, 0, row + (-row % _LANE)
    pages = min(pages_per_step, table_width)
    page_rows = block_size * kv_heads
    rows = pages * page_rows

    slot, chunk, num_items = _work_items(lengths, block_size, table_width,
                                         pages)
    # a query zero beyond the head adds nothing to a score; a block's
    # positions and heads are one run of rows as stored (no copy)
    qp = jnp.pad(q.astype(k_pages.dtype),
                 ((0, 0), (0, num_heads - given), (0, row - hd)))
    kp = k_pages.reshape(layers, num_blocks, page_rows, row)
    vp = v_pages.reshape(layers, num_blocks, page_rows, row)
    scalars = (block_tables.astype(jnp.int32), lengths, slot, chunk,
               num_items,
               jnp.asarray(layer, jnp.int32).reshape(1))

    writes = new_rows is not None
    # a token's rows that fill no whole tile of the cache go through a
    # tile in VMEM (interpreted, where a page is no whole tile either,
    # they are copied as they are: the interpreter addresses any row)
    tile_rows = (_new_rows_tile(k_pages) or 0) if writes else 0
    kernel = functools.partial(
        _paged_kernel, scale=scale, num_heads=num_heads, kv_heads=kv_heads,
        block_size=block_size, pages=pages, table_width=table_width,
        writes=writes, group=group, tile_rows=tile_rows)
    a_slots_rows = pl.BlockSpec((num_slots, num_heads, row),
                                lambda *_: (0, 0, 0))
    new_rows_spec = (a_slots_rows if kv_heads == num_heads else
                     pl.BlockSpec((num_slots, kv_heads, row),
                                  lambda *_: (0, 0, 0)))
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = jax.ShapeDtypeStruct((num_slots, num_heads, row),
                                     jnp.float32)
    scratch_shapes = [
        pltpu.VMEM((2, rows, row), k_pages.dtype),
        pltpu.VMEM((2, rows, row), v_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((num_heads, rows), jnp.float32),   # a head's rows
        pltpu.VMEM((1, rows), jnp.int32),             # a row's position
        pltpu.VMEM((num_heads, _LANE), jnp.float32),  # running max
        pltpu.VMEM((num_heads, _LANE), jnp.float32),  # running denom
        pltpu.VMEM((num_heads, row), jnp.float32),    # accumulator
    ]
    if writes:
        scratch_shapes.append(pltpu.SemaphoreType.DMA((2,)))
    if tile_rows:
        scratch_shapes += [pltpu.VMEM((num_slots, tile_rows, row), a.dtype)
                           for a in (k_pages, v_pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=([a_slots_rows] + [new_rows_spec] * (2 if writes else 0)
                  + [where_it_lies] * 2),
        out_specs=((a_slots_rows, where_it_lies, where_it_lies)
                   if writes else a_slots_rows),
        scratch_shapes=scratch_shapes,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=((out_shape, jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                    jax.ShapeDtypeStruct(vp.shape, vp.dtype))
                   if writes else out_shape),
        # the cache arrays (operands 9 and 10, the scalars counted) are
        # outputs 1 and 2: one buffer each
        input_output_aliases=({len(scalars) + 3: 1, len(scalars) + 4: 2}
                              if writes else {}),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )
    if not writes:
        return call(*scalars, qp, kp, vp)[:, :given, :hd]
    out, kp, vp = call(*scalars, qp, *new_rows, kp, vp)
    return (out[:, :given, :hd], kp.reshape(k_pages.shape),
            vp.reshape(v_pages.shape))


#: cached positions an item of the latent kernel holds, whatever a page
#: holds: ``TARGET_ROWS // block_size`` table entries, so that an item is
#: the same rows at 16 positions a page and at 128 (PERF.md has the
#: chip's readings)
TARGET_ROWS = 1024

#: table entries whose DMAs one turn of the latent kernel's page loop
#: holds written out (the loop has ``pages // PAGE_UNROLL`` turns, or
#: the largest divisor of ``pages`` under it). An item of 16-row pages
#: is 64 entries: all written out they were seconds of every step's
#: lowering, one a turn a loop of 64 scalar bodies on the chip (PERF.md
#: has both readings)
PAGE_UNROLL = 8


def _latent_kernel(tables_ref, lengths_ref, slot_ref, chunk_ref, count_ref,
                   layer_ref, qc_hbm, qr_hbm, *refs, scale: float,
                   block_size: int, pages: int, table_width: int,
                   writes: bool, tile_rows: int, unroll: int):
    """Every work item of one layer of a latent cache, in one loop; with
    ``writes``, each live slot's new row put into both arrays first.

    The scalar-prefetch operands are :func:`_paged_kernel`'s. Everything
    else lies in HBM and is copied by hand: the queries ``qc_hbm``
    [slots, heads, latent width] and ``qr_hbm`` [slots, heads, rope
    width] (a slot's pair comes in while the slot before it is
    computed), the cache arrays ``c_hbm`` [layers, blocks, block_size,
    latent width] and ``kr_hbm`` [.., rope width], the output ``o_hbm``
    [slots, heads, latent width] float32 (a slot's goes out while the
    next is computed). An item is ``pages`` table entries, one DMA an
    entry an array; a page serves every head, and the latent page both
    products. An entry past the slot's live pages fetches the last live
    one again (its rows lie past ``length`` and are masked), so a dead
    entry of the table is never looked up and a buffer never holds
    anything but cached rows.

    With ``writes`` the token's rows ``c_new`` / ``kr_new`` [slots, 1,
    width] come in beside the queries and the cache arrays are outputs
    too, aliased to the inputs. One row is less than a tile of the cache
    as the device stores it: ``tile_rows`` > 0, each live slot's tile of
    that many rows comes into ``c_rmw`` / ``kr_rmw`` [slots, tile_rows,
    width], takes the row there and goes back whole (two rounds of
    copies, each all in flight together; no two slots share a page);
    0, interpreted with pages that are no whole tiles, the row is
    copied as it is."""
    if writes:
        c_new, kr_new, _, _, o_hbm, c_hbm, kr_hbm, *scratch = refs
    else:
        c_hbm, kr_hbm, o_hbm, *scratch = refs
    (c_buf, kr_buf, sem, qc_buf, qr_buf, q_sem, m_ref, l_ref, acc_ref,
     o_buf, o_sem, *write_scratch) = scratch
    rows = pages * block_size
    num_slots = lengths_ref.shape[0]
    layer, num_items = layer_ref[0], count_ref[0]

    def live_pages(slot):
        return jnp.minimum(pl.cdiv(lengths_ref[slot], block_size),
                           table_width)

    # -- the token's row into both arrays -------------------------------
    if writes:
        write_sem = write_scratch[0]
        pairs = ((c_new, c_hbm), (kr_new, kr_hbm))

        def where_written(slot):
            at = lengths_ref[slot] - 1
            return tables_ref[slot, at // block_size], at % block_size

        def each_written_row(copies_of, act):
            """``act`` on the DMAs of every slot that has a row to
            write: a slot of length 0 writes nothing (its table is never
            looked up), nor does a position beyond the table."""
            def one(slot, _):
                n = lengths_ref[slot]

                @pl.when((n > 0) & (n <= table_width * block_size))
                def _():
                    for dma in copies_of(slot):
                        act(dma)
            jax.lax.fori_loop(0, num_slots, one, None)

        def tile_copies(slot, back: bool):
            block, off = where_written(slot)
            run = pl.ds(pl.multiple_of(off // tile_rows * tile_rows,
                                       tile_rows), tile_rows)
            both = tuple((hbm.at[layer, block, run], rmw.at[slot])
                         for (_, hbm), rmw in zip(pairs, write_scratch[1:]))
            return tuple(pltpu.make_async_copy(*(pair[::-1] if back
                                                 else pair), write_sem.at[i])
                         for i, pair in enumerate(both))

        def row_copies(slot):
            if tile_rows:
                return tile_copies(slot, back=True)
            block, off = where_written(slot)
            return tuple(pltpu.make_async_copy(
                new.at[slot], hbm.at[layer, block, pl.ds(off, 1)],
                write_sem.at[i]) for i, (new, hbm) in enumerate(pairs))

        def put_new_row(slot, _):
            """The slot's row into its tile, in VMEM: one select."""
            off = (lengths_ref[slot] - 1) % block_size % tile_rows
            for (new, _), rmw in zip(pairs, write_scratch[1:]):
                row_id = jax.lax.broadcasted_iota(jnp.int32, rmw.shape[1:], 0)
                rmw[slot] = jnp.where(row_id == off, new[slot], rmw[slot])

        if tile_rows:
            tiles_in = functools.partial(tile_copies, back=False)
            each_written_row(tiles_in, lambda dma: dma.start())
            each_written_row(tiles_in, lambda dma: dma.wait())
            # (a slot with nothing to write selects into a tile nobody
            # reads)
            jax.lax.fori_loop(0, num_slots, put_new_row, None)
        each_written_row(row_copies, lambda dma: dma.start())

    # -- an idle slot's output: zeros ----------------------------------
    def out_copy(slot):
        return pltpu.make_async_copy(o_buf, o_hbm.at[slot], o_sem.at[0])

    def each_idle_slot(act):
        def one(slot, _):
            @pl.when(lengths_ref[slot] <= 0)
            def _():
                act(out_copy(slot))
        jax.lax.fori_loop(0, num_slots, one, None)

    o_buf[...] = jnp.zeros_like(o_buf)
    each_idle_slot(lambda dma: dma.start())
    each_idle_slot(lambda dma: dma.wait())

    # -- the items ------------------------------------------------------
    def query_copies(item, which):
        slot = slot_ref[item]
        return (pltpu.make_async_copy(qc_hbm.at[slot], qc_buf.at[which],
                                      q_sem.at[which, 0]),
                pltpu.make_async_copy(qr_hbm.at[slot], qr_buf.at[which],
                                      q_sem.at[which, 1]))

    def page_copies(item, half, p):
        slot = slot_ref[item]
        entry = jnp.minimum(chunk_ref[item] * pages + p,
                            live_pages(slot) - 1)
        block = tables_ref[slot, entry]
        run = pl.ds(pl.multiple_of(p * block_size, block_size), block_size)
        return (pltpu.make_async_copy(c_hbm.at[layer, block],
                                      c_buf.at[half, run], sem.at[half, 0]),
                pltpu.make_async_copy(kr_hbm.at[layer, block],
                                      kr_buf.at[half, run], sem.at[half, 1]))

    def each_page(item, half, act):
        """``act`` on the two DMAs of each of the item's pages: a loop
        on the chip of ``unroll`` pages a turn, not all of them in the
        trace (64 pages of 16 rows an item, written out, were seconds of
        every step's lowering)."""
        def some(turn, _):
            for p in range(unroll):
                for dma in page_copies(item, half, turn * unroll + p):
                    act(dma)
        jax.lax.fori_loop(0, pages // unroll, some, None)

    def start(item, half, which):
        """The item's pages on their way, and its slot's queries where
        it is the slot's first: into the pair of buffers the slot being
        computed does not read."""
        @pl.when(chunk_ref[item] == 0)
        def _():
            for dma in query_copies(item, which):
                dma.start()
        each_page(item, half, lambda dma: dma.start())

    if writes:
        # landed before the first page is fetched: position length - 1
        # is read through the cache
        each_written_row(row_copies, lambda dma: dma.wait())

    @pl.when(num_items > 0)
    def _():
        start(0, 0, 0)

    position = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)

    def item_step(item, carry):
        # ``which``: the pair of query buffers this item's slot reads;
        # ``sent``: the slots whose output has been sent on its way
        which, sent = carry
        half = item % 2
        slot, chunk = slot_ref[item], chunk_ref[item]
        which = jnp.where(chunk == 0, 1 - which, which)

        @pl.when(item + 1 < num_items)
        def _():
            start(item + 1, 1 - half, 1 - which)

        length = lengths_ref[slot]
        num_chunks = pl.cdiv(live_pages(slot), pages)

        @pl.when(chunk == 0)
        def _():
            for dma in query_copies(item, which):
                dma.wait()
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        each_page(item, half, lambda dma: dma.wait())
        c = c_buf[half]                                   # [rows, latent]
        exact = (jax.lax.Precision.HIGHEST
                 if c.dtype == jnp.float32 else None)
        across = (((1,), (1,)), ((), ()))
        sc = (jax.lax.dot_general(qc_buf[which], c, across, precision=exact,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(qr_buf[which], kr_buf[half], across,
                                    precision=exact,
                                    preferred_element_type=jnp.float32)
              ) * scale                                   # [h, rows]
        sc = jnp.where(position < length - chunk * rows, sc, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        w = jnp.exp(sc - m_new)                           # [h, rows] f32
        corr = jnp.exp(m_prev - m_new)                    # [h, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(w, axis=1, keepdims=True)
        # the weights rounded to the cache's dtype once, as the gather
        # arm rounds them
        wc = jnp.dot(w.astype(c.dtype), c, precision=exact,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + wc
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        last = chunk == num_chunks - 1

        @pl.when(last)
        def _():
            @pl.when(sent > 0)
            def _():
                out_copy(slot).wait()   # the slot before's has left o_buf
            o_buf[...] = acc_ref[...] / l_ref[:, :1]
            out_copy(slot).start()

        return which, sent + last.astype(jnp.int32)

    _, sent = jax.lax.fori_loop(0, num_items, item_step,
                                (jnp.int32(1), jnp.int32(0)))

    @pl.when(sent > 0)
    def _():
        out_copy(0).wait()


def _latent_rows_tile(c_pages: jax.Array) -> int | None:
    """:func:`_new_rows_tile` of a latent array ``[layers, blocks,
    block_size, width]``: one row a token."""
    return _new_rows_tile(jax.ShapeDtypeStruct(
        (*c_pages.shape[:3], 1, c_pages.shape[3]), c_pages.dtype))


def _latent_call(q_c, q_r, new_rows, c_pages, kr_pages, block_tables,
                 lengths, *, layer, scale, target_rows, interpret):
    """One call of the latent kernel over the two cache arrays whole:
    the attention alone, or with ``new_rows`` (the latent's and the
    rotated key's) the attention and the arrays the rows were written
    to."""
    num_slots, num_heads, latent = q_c.shape
    rope = q_r.shape[-1]
    layers, num_blocks, block_size, c_row = c_pages.shape
    kr_row = kr_pages.shape[-1]
    assert kr_pages.shape[:3] == c_pages.shape[:3], (c_pages.shape,
                                                     kr_pages.shape)
    assert q_r.shape[:2] == (num_slots, num_heads)
    assert c_row >= latent and kr_row >= rope
    assert block_tables.shape[0] == num_slots == lengths.shape[0]
    table_width = block_tables.shape[1]
    interpret = _interpreted(interpret)
    lengths = lengths.astype(jnp.int32)
    pages = max(1, min(target_rows // block_size, table_width))
    rows = pages * block_size
    slot, chunk, num_items = _work_items(lengths, block_size, table_width,
                                         pages)
    # a query zero beyond its own width adds nothing to a score whatever
    # the row's lanes beyond it hold; the heads whole tiles (those
    # beyond the model's own are zeros and are cut off the output)
    heads = -(-num_heads // 16) * 16
    qc, qr = (jnp.pad(q.astype(pages_.dtype),
                      ((0, 0), (0, heads - num_heads),
                       (0, pages_.shape[-1] - q.shape[-1])))
              for q, pages_ in ((q_c, c_pages), (q_r, kr_pages)))
    scalars = (block_tables.astype(jnp.int32), lengths, slot, chunk,
               num_items, jnp.asarray(layer, jnp.int32).reshape(1))

    writes = new_rows is not None
    tile_rows = (_latent_rows_tile(c_pages) or 0) if writes else 0
    unroll = max(u for u in range(1, min(PAGE_UNROLL, pages) + 1)
                 if pages % u == 0)
    kernel = functools.partial(
        _latent_kernel, scale=scale, block_size=block_size, pages=pages,
        table_width=table_width, writes=writes, tile_rows=tile_rows,
        unroll=unroll)
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    a_row_a_slot = [pl.BlockSpec((num_slots, 1, row), lambda *_: (0, 0, 0))
                    for row in (c_row, kr_row)]
    out_shape = jax.ShapeDtypeStruct((num_slots, heads, c_row), jnp.float32)
    scratch_shapes = [
        pltpu.VMEM((2, rows, c_row), c_pages.dtype),
        pltpu.VMEM((2, rows, kr_row), kr_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((2, heads, c_row), c_pages.dtype),  # a slot's queries
        pltpu.VMEM((2, heads, kr_row), kr_pages.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((heads, _LANE), jnp.float32),       # running max
        pltpu.VMEM((heads, _LANE), jnp.float32),       # running denom
        pltpu.VMEM((heads, c_row), jnp.float32),       # accumulator
        pltpu.VMEM((heads, c_row), jnp.float32),       # a slot's output
        pltpu.SemaphoreType.DMA((1,)),
    ]
    if writes:
        scratch_shapes.append(pltpu.SemaphoreType.DMA((2,)))
    if tile_rows:
        scratch_shapes += [pltpu.VMEM((num_slots, tile_rows, a.shape[-1]),
                                      a.dtype) for a in (c_pages, kr_pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(1,),
        in_specs=([where_it_lies] * 2 + (a_row_a_slot if writes else [])
                  + [where_it_lies] * 2),
        out_specs=((where_it_lies,) * 3 if writes else where_it_lies),
        scratch_shapes=scratch_shapes,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=((out_shape,
                    jax.ShapeDtypeStruct(c_pages.shape, c_pages.dtype),
                    jax.ShapeDtypeStruct(kr_pages.shape, kr_pages.dtype))
                   if writes else out_shape),
        # the cache arrays (operands 10 and 11, the scalars counted) are
        # outputs 1 and 2: one buffer each
        input_output_aliases=({len(scalars) + 4: 1, len(scalars) + 5: 2}
                              if writes else {}),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_decode",
    )
    if not writes:
        return call(*scalars, qc, qr, c_pages, kr_pages)[:, :num_heads,
                                                         :latent]
    out, c_pages, kr_pages = call(
        *scalars, qc, qr, *(new[:, None] for new in new_rows), c_pages,
        kr_pages)
    return out[:, :num_heads, :latent], c_pages, kr_pages


def latent_rows_as_they_lie(*shapes: tuple[int, ...], writes: bool = True,
                            itemsize: int = 2) -> bool:
    """Whether Mosaic takes latent cache arrays of ``shapes`` ([layers,
    blocks, block_size, width]) where they lie: every array's rows whole
    lanes and, for the form that ``writes``, a page whole tiles (eight
    32-bit sublanes: 16 rows of ``itemsize`` 2, the narrowest a replica
    stores, which the wider's 8 divide), since a token's one row goes
    through the tile that holds it. The one question the decode step's
    arm (``models/transformer.py::decode_attention_arm``) and the
    compiled entry points ask."""
    tile = 8 * max(1, 4 // itemsize)
    return all(shape[-1] % _LANE == 0 and not (writes and shape[2] % tile)
               for shape in shapes)


def _held_to_the_lanes(c_pages, kr_pages, interpret, writes):
    """A compiled call of arrays Mosaic does not take raises: the gather
    arm is the caller's to choose (``decode_attention_arm`` asks the
    same question first), not a path inside this one."""
    if not _interpreted(interpret) and not latent_rows_as_they_lie(
            c_pages.shape, kr_pages.shape, writes=writes,
            itemsize=c_pages.dtype.itemsize):
        raise ValueError(
            f"the latent paged kernel compiles for rows of whole lanes"
            f"{' and pages of whole tiles' if writes else ''}, not for "
            f"cache arrays {c_pages.shape} and {kr_pages.shape} of "
            f"{c_pages.dtype}: decode.attention_kernel = dense (or auto) "
            f"reads these through the gather")


@functools.partial(jax.jit, static_argnames=("scale", "target_rows",
                                             "interpret"))
def paged_latent_attention(q_c: jax.Array, q_r: jax.Array,
                           c_pages: jax.Array, kr_pages: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           layer: jax.Array | int = 0, scale: float,
                           target_rows: int = TARGET_ROWS,
                           interpret: bool | None = None) -> jax.Array:
    """Single-query attention over a paged LATENT cache, one layer, in
    the absorbed form (``models/transformer.py::
    _latent_decode_attention``): a cached token is one row for all
    heads in each of two arrays, and the value is the latent itself.

    ``q_c``: [slots, heads, latent], the query's unrotated part through
    ``W_uk``; ``q_r``: [slots, heads, rope], its rotated part.
    ``c_pages``: [layers, num_blocks, block_size, latent or wider], the
    normed latents; ``kr_pages``: [.., rope or wider], the rotated keys;
    passed whole, as they lie, with ``layer`` the one to read. The
    token's own row is in both already (this form reads only;
    :func:`paged_latent_attention_write` puts it there in the same
    call). ``block_tables``, ``lengths``: as :func:`paged_attention`'s.
    Scores are ``(q_c · c + q_r · k_r) · scale``: two products on the
    matrix unit over one fetch of the item's pages, every head against
    every row (no mask of heads: a row is every head's).

    Numerics are pinned to the gather arm of
    ``_latent_decode_attention`` (:func:`paged_latent_attention_dense`):
    operands as stored, float32 accumulation, scores and softmax in
    float32, masked positions ``-1e30``, and the weights ROUNDED ONCE to
    the cache's dtype before the product with the latents (the plain
    kernel's three-piece weights are not this arm's); the softmax runs
    online over items, so the weights are rounded before the division by
    their sum and not after it. An idle slot returns zeros.

    Compiled, both rows have to fill whole lanes (what the replica's
    cache has on a TPU; :func:`latent_rows_as_they_lie`); anything else
    runs interpreted, or raises: the gather arm is the caller's.

    Returns ``o_c`` [slots, heads, latent] float32, the weighted sum of
    latents a head (``W_uv`` is the caller's).
    """
    _held_to_the_lanes(c_pages, kr_pages, interpret, writes=False)
    return _latent_call(q_c, q_r, None, c_pages, kr_pages, block_tables,
                        lengths, layer=layer, scale=scale,
                        target_rows=target_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "target_rows",
                                             "interpret"))
def paged_latent_attention_write(
        q_c: jax.Array, q_r: jax.Array, new_c: jax.Array, new_kr: jax.Array,
        c_pages: jax.Array, kr_pages: jax.Array, block_tables: jax.Array,
        lengths: jax.Array, *, layer: jax.Array | int = 0, scale: float,
        target_rows: int = TARGET_ROWS, interpret: bool | None = None,
        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`paged_latent_attention` of a step that has yet to store
    its token: the same kernel writes the row, then reads.

    ``new_c`` [slots, latent width as stored] and ``new_kr`` [slots,
    rope width as stored]: the token's latent and rotated key as the
    cache stores them (its dtype, its row's width: zeros beside the
    values). Slot ``s``'s rows go to position ``lengths[s] - 1`` of
    ``layer`` in both arrays, which the call takes and returns as one
    buffer each (``input_output_aliases``: donate them, or XLA copies
    each); one row is less than a tile of the cache as a TPU stores it,
    so it goes through the tile that holds it (:func:`_latent_kernel`):
    compiled, a page has to be whole tiles besides the rows whole lanes,
    or the call raises. A slot of length 0 writes nothing.

    Returns (``o_c`` [slots, heads, latent] float32, the two arrays).
    """
    assert new_c.shape == (q_c.shape[0], c_pages.shape[-1]), new_c.shape
    assert new_kr.shape == (q_c.shape[0], kr_pages.shape[-1]), new_kr.shape
    assert new_c.dtype == c_pages.dtype and new_kr.dtype == kr_pages.dtype
    _held_to_the_lanes(c_pages, kr_pages, interpret, writes=True)
    return _latent_call(q_c, q_r, (new_c, new_kr), c_pages, kr_pages,
                        block_tables, lengths, layer=layer, scale=scale,
                        target_rows=target_rows, interpret=interpret)


def paged_latent_attention_dense(q_c: jax.Array, q_r: jax.Array,
                                 c_pages: jax.Array, kr_pages: jax.Array,
                                 block_tables: jax.Array,
                                 lengths: jax.Array, *,
                                 scale: float) -> jax.Array:
    """The dense-gather oracle of :func:`paged_latent_attention`: one
    layer's pages [num_blocks, block_size, width] through the full-table
    gather, operation for operation the gather arm of
    ``models/transformer.py::_latent_decode_attention`` (weights rounded
    to the cache's dtype once). Live slots only: an idle slot's row is a
    uniform average here and zeros from the kernel."""
    num_slots, _, latent = q_c.shape
    rope = q_r.shape[-1]
    ctx = block_tables.shape[1] * c_pages.shape[1]
    cs = c_pages[block_tables][..., :latent].reshape(num_slots, ctx, latent)
    krs = kr_pages[block_tables][..., :rope].reshape(num_slots, ctx, rope)
    live = jnp.arange(ctx)[None, :] < lengths[:, None]
    scores = (jnp.einsum("shr,skr->shk", q_c.astype(cs.dtype), cs,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("she,ske->shk", q_r.astype(krs.dtype), krs,
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(live[:, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("shk,skr->shr", w.astype(cs.dtype), cs,
                      preferred_element_type=jnp.float32)


def paged_attention_dense(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          lengths: jax.Array, *,
                          scale: float | None = None) -> jax.Array:
    """The dense-gather oracle: :func:`paged_attention` of one layer's
    pages [num_blocks, block_size, heads, width], implemented with the
    full-table gather the decode step makes off the TPU, for a toy head
    and under ``decode.attention_kernel = dense``. Parity tests pin the
    kernel against this for live slots; idle rows differ by design (see
    module docstring)."""
    num_slots, num_heads, hd = q.shape
    block_size, kv_heads = k_pages.shape[1], k_pages.shape[2]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    ctx = block_tables.shape[1] * block_size
    kd = k_pages[block_tables][..., :hd].reshape(num_slots, ctx, kv_heads,
                                                 hd)
    vd = v_pages[block_tables][..., :hd].reshape(num_slots, ctx, kv_heads,
                                                 hd)
    if kv_heads != num_heads:
        # a group of query heads reads one head's rows
        kd, vd = (jnp.repeat(t, num_heads // kv_heads, axis=2)
                  for t in (kd, vd))
    live = jnp.arange(ctx)[None, :] < lengths[:, None]
    scores = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    scores = jnp.where(live[:, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("shk,skhd->shd", w, vd.astype(jnp.float32))
