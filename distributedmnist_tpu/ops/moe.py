"""Mixture-of-experts FFN with expert parallelism.

The fifth parallelism family (data/tensor/sequence/pipeline/expert —
all absent from the reference, SURVEY §2.2). Switch-Transformer top-1
and GShard top-k routing (cf. arXiv:2101.03961, arXiv:2006.16668) in
the dispatch/combine einsum formulation — static shapes throughout, so
XLA sees dense batched matmuls per expert shard and the MXU stays busy
regardless of routing.

Token groups (the GShard "group" dimension): every sequence row splits
into a fixed number of contiguous chunks, and routing capacity plus the
load-balance auxiliary loss are computed PER CHUNK. Because groups nest
inside rows, the routing math depends only on (config, row contents) —
never on how a batch is split into pipeline microbatches, how many
expert ranks exist, or how the sequence is sharded (given an explicit
``num_groups``). Consequences the tests pin down:

* a pipelined (PP) MoE evaluates/trains identically at ANY microbatch
  count — groups never straddle a microbatch boundary;
* an expert-parallel run equals the dense oracle EXACTLY, including
  with binding capacity (same groups → same drops);
* the aux loss is the MEAN over groups of the per-group Switch loss
  E·Σ_e frac_e·mprob_e — linear in per-group contributions, so
  pipeline ticks / seq shards / expert ranks can average it without
  the round-4 raw-statistics accumulation machinery.

Expert-parallel layout (GShard all-to-all dispatch): each expert rank
owns a contiguous 1/G slice of every row's groups — a free local slice
of the replicated activations. It routes those groups locally and two
``lax.all_to_all``s carry only the dispatched capacity slices
[n_groups, E_local, G·cap, d] to the expert owners and back. The
combined outputs are reassembled replicated via the framework's
scatter+psum idiom (parallel/api.py:_gather_replicated — an
``all_gather`` result stays tracked device-varying and could not feed
the replicated residual stream), fused over the expert and TP axes in
one reduction. all_to_all / psum rendezvous GROUP-locally, which is
what lets this op run inside the 1F1B engine's stage-varying branches
(ops/pipeline.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _route(xg: jax.Array, router_w: jax.Array, e: int, cap: int,
           top_k: int):
    """Route one token group [t, d] → dispatch/combine [t, e, cap]
    (f32) plus per-expert load statistics [e].

    ``top_k == 1``: Switch routing — the token's combine weight is its
    raw top gate. ``top_k >= 2``: GShard — each round dispatches the
    next-best expert, queue positions offset by ALL earlier rounds'
    claims (kept or dropped, matching GShard's ``locations2 += sum
    (mask1)``), and gates renormalize over the chosen set, so a token
    whose first choice overflowed still flows through its second.
    """
    logits = (xg @ router_w.astype(xg.dtype)).astype(jnp.float32)  # [t, e]
    probs = jax.nn.softmax(logits, axis=-1)
    remaining = probs
    counts = jnp.zeros((e,), jnp.float32)   # queue claims so far
    disps, gates = [], []
    for _ in range(top_k):
        gate_k = jnp.max(remaining, axis=-1)              # [t]
        choice = jnp.argmax(remaining, axis=-1)           # [t]
        oh = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [t, e]
        # position within the expert's queue: this round's arrival
        # order plus every earlier round's total claims on that expert
        pos = (jnp.sum((jnp.cumsum(oh, axis=0) - 1.0) * oh, axis=-1)
               + oh @ counts).astype(jnp.int32)           # [t]
        slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # 0 if pos>=cap
        disps.append(oh[:, :, None] * slot[:, None, :])     # [t, e, cap]
        gates.append(gate_k)
        counts = counts + jnp.sum(oh, axis=0)
        remaining = remaining * (1.0 - oh)
    dispatch = disps[0] if top_k == 1 else sum(disps)
    if top_k == 1:
        combine = disps[0] * gates[0][:, None, None]
    else:
        denom = sum(gates) + 1e-9
        combine = sum((g / denom)[:, None, None] * dk
                      for g, dk in zip(gates, disps))
    # load statistics use FIRST-choice fractions (the Switch/GShard
    # aux convention, independent of later rounds' capacity outcomes)
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), e,
                                   dtype=jnp.float32), axis=0)
    mprob = jnp.mean(probs, axis=0)
    return dispatch, combine, frac, mprob


def _expert_ffn(expert_in: jax.Array, w1: jax.Array, w2: jax.Array,
                dtype) -> jax.Array:
    """[e_local, c, d] through each local expert's two-layer FFN —
    scanned so XLA emits one fused kernel pair per expert shard."""
    def one_expert(carry, packed):
        del carry
        inp, w1_e, w2_e = packed
        h = jax.nn.relu(inp @ w1_e.astype(dtype))
        return None, h @ w2_e.astype(dtype)

    _, expert_out = lax.scan(one_expert, None, (expert_in, w1, w2))
    return expert_out


def moe_ffn(x: jax.Array, router_w: jax.Array, w1: jax.Array, w2: jax.Array,
            *, num_experts: int, capacity_factor: float = 1.25,
            router_top_k: int = 1, num_groups: int = 0,
            expert_axis: str | None = None,
            tp_axis: str | None = None,
            stats_axes: tuple[str, ...] = ()) -> tuple[jax.Array, jax.Array]:
    """Top-k routed expert FFN over fixed per-row token groups.

    Args (inside shard_map when ``expert_axis``/``tp_axis`` are set):
      x: [batch, seq, d] activations (replicated over both axes; under
        SP the caller passes its seq-local slice).
      router_w: [d, E] routing weights (replicated).
      w1: [E_local, d, ff_local], w2: [E_local, ff_local, d] — THIS
        rank's expert slice (E_local = E / expert-axis size) and, with
        ``tp_axis``, its Megatron column/row slice of every expert's
        hidden dim (ff_local = ff / tp-axis size). The two shardings
        compose: EP picks which experts live here, TP splits each
        expert's FFN across the model axis, and ONE fused psum over
        both axes reassembles the combined output.
      num_experts: E (global).
      capacity_factor: per-group capacity =
        ceil(cf · top_k · group_size / E); overflow tokens lose that
        round's slot (pass through the residual, or — top-k — flow
        through a later choice).
      router_top_k: experts per token (module docstring).
      num_groups: chunks per GLOBAL sequence row (module docstring);
        the per-call group count divides out any seq sharding named in
        ``stats_axes``. 0 = auto: the minimum this call's sharding
        requires (one group per expert rank, or one group per row
        unsharded) — mesh-dependent, so fixed-mesh comparisons set it
        explicitly.
      stats_axes: extra mesh axes the sequence is sharded over (the seq
        axis under SP): the aux pmean runs over them, and the global
        ``num_groups`` is interpreted per global row across them.

    Returns (out [batch, seq, d], aux): ``aux`` is the mean over token
    groups of the per-group Switch load-balance loss
    E·Σ_e(fraction_e · mean_prob_e), pmean'd over the expert axis and
    ``stats_axes`` — i.e. the mean over ALL of this layer's groups,
    replicated; add ``aux_weight * aux`` to the train loss.
    """
    b, s, d = x.shape
    e = num_experts
    if not 1 <= router_top_k <= e:
        raise ValueError(f"moe_router_top_k={router_top_k} must be in "
                         f"[1, num_experts={e}]")
    # routing math stays f32 (inside _route); the FFN FLOPs run in the
    # compute dtype like the dense branch (bf16 feeds the MXU full-rate)
    dtype = x.dtype

    g_ep = 1
    if expert_axis is not None:
        e_local = w1.shape[0]
        g_ep = e // e_local                       # expert-axis size
    n_seq_shards = 1
    for ax in stats_axes:
        n_seq_shards *= lax.axis_size(ax)

    if num_groups:
        if num_groups % n_seq_shards:
            raise ValueError(
                f"moe_num_groups={num_groups} must divide by the "
                f"sequence sharding ({n_seq_shards} shards) so group "
                "boundaries align with shard boundaries")
        gh = num_groups // n_seq_shards           # groups per local row
    else:
        gh = g_ep                                 # auto: one per EP rank
    if gh % g_ep:
        raise ValueError(
            f"per-shard group count {gh} (moe_num_groups="
            f"{num_groups or 'auto'}) must divide by the expert-parallel "
            f"rank count {g_ep}")
    if s % gh:
        raise ValueError(
            f"local sequence length {s} must divide into {gh} token "
            f"groups (moe_num_groups={num_groups or 'auto'})")
    gs = s // gh                                  # tokens per group
    cap = max(1, math.ceil(capacity_factor * router_top_k * gs / e))

    def route_many(xg):                           # [n_g, gs, d]
        return jax.vmap(lambda g: _route(g, router_w, e, cap,
                                         router_top_k))(xg)

    if expert_axis is None:
        n_g = b * gh
        xg = x.reshape(n_g, gs, d)
        dispatch, combine, frac, mprob = route_many(xg)
        # experts see each group's capacity slots independently
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch.astype(dtype), xg)
        ei = expert_in.transpose(1, 0, 2, 3).reshape(e, n_g * cap, d)
        eo = _expert_ffn(ei, w1, w2, dtype)
        expert_out = eo.reshape(e, n_g, cap, d).transpose(1, 0, 2, 3)
        out = jnp.einsum("gtec,gecd->gtd", combine.astype(dtype), expert_out)
        out = out.reshape(b, s, d)
        if tp_axis is not None:
            out = lax.psum(out, tp_axis)
    else:
        me = lax.axis_index(expert_axis)
        s_r = s // g_ep                   # this rank's contiguous slice
        gh_l = gh // g_ep                 # its groups per row
        x_r = lax.dynamic_slice_in_dim(x, me * s_r, s_r, axis=1)
        n_g = b * gh_l
        xg = x_r.reshape(n_g, gs, d)
        dispatch, combine, frac, mprob = route_many(xg)
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch.astype(dtype), xg)
        # all-to-all #1: [n_g, E, cap, d] → [n_g, E_local, G·cap, d] —
        # each rank receives, for its local experts, every rank's
        # dispatched capacity slices
        expert_in = lax.all_to_all(expert_in, expert_axis, 1, 2, tiled=True)
        ei = (expert_in.transpose(1, 0, 2, 3)
              .reshape(e_local, n_g * g_ep * cap, d))
        eo = _expert_ffn(ei, w1, w2, dtype)
        expert_out = (eo.reshape(e_local, n_g, g_ep * cap, d)
                      .transpose(1, 0, 2, 3))
        # all-to-all #2 (inverse): slots come home, experts back in
        # global order (owners are rank-ordered)
        expert_out = lax.all_to_all(expert_out, expert_axis, 2, 1,
                                    tiled=True)
        out_g = jnp.einsum("gtec,gecd->gtd", combine.astype(dtype),
                           expert_out)
        # reassemble the replicated [b, s, d] residual input:
        # scatter+psum (the _gather_replicated idiom — statically
        # replicated, unlike all_gather), fused with the TP reduction
        scat = lax.dynamic_update_slice_in_dim(
            jnp.zeros((b, s, d), dtype), out_g.reshape(b, s_r, d),
            me * s_r, axis=1)
        reduce_axes = ((expert_axis, tp_axis) if tp_axis is not None
                       else (expert_axis,))
        out = lax.psum(scat, reduce_axes)

    # per-group Switch loss, averaged over every group of the layer:
    # mean over this call's groups, then over expert ranks (disjoint
    # group slices) and seq shards — all equal-sized, so the pmean of
    # means IS the global mean over groups
    group_aux = e * jnp.sum(frac * mprob, axis=-1)        # [n_g]
    aux = jnp.mean(group_aux)
    reduce = ((() if expert_axis is None else (expert_axis,))
              + tuple(stats_axes))
    if reduce:
        aux = lax.pmean(aux, reduce)
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Routing as it is deployed: per-token top-k of many experts with a
# selection bias, none dropped, over the share of experts this chip holds
# ---------------------------------------------------------------------------

#: rows of one tile of the grouped product at most: every held expert's
#: pairs are padded to a whole number of tiles, so one tile meets one
#: expert's weights
TILE_ROWS = 512
#: and at least: a bfloat16 tile's sublanes
MIN_TILE_ROWS = 16


def tile_rows(pairs: int, total: int) -> int:
    """The tile for ``pairs`` (token, expert) pairs over ``total``
    experts: the power of two at or above twice an expert's even share,
    between :data:`MIN_TILE_ROWS` and :data:`TILE_ROWS`. A training
    batch (thousands of pairs an expert) takes the largest; a decode
    step's few pairs an expert would each be padded to a tile sized for
    it, 512 rows of products for two of tokens."""
    share = 2 * -(-pairs // total)
    return min(TILE_ROWS, max(MIN_TILE_ROWS, 1 << (share - 1).bit_length()))


def gated_unit(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    """``(silu(x W_g) * x W_u) W_d``: the gated feed-forward unit, of an
    expert or of a dense layer."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route_tokens(x: jax.Array, router_w: jax.Array, bias: jax.Array,
                 top_k: int, scaling: float, n_group: int = 1,
                 topk_group: int = 1):
    """Sigmoid routing with a selection bias (arXiv:2412.19437 §2.1.2,
    ``noaux_tc``): ``x`` [t, d] → the ``top_k`` expert ids
    [t, k] with the largest ``sigmoid(x W_r) + bias`` and their gates
    [t, k] float32, the chosen scores WITHOUT the bias renormalised to
    sum to ``scaling``. Scores in float32 at full matrix precision: a
    top-k is a discontinuity. The loss's gradient does not reach the
    bias (:func:`balance_term` moves it).

    ``n_group > 1`` limits a token to ``topk_group`` of ``n_group`` equal
    groups of consecutive experts (device-limited routing, same section):
    a group's score is the sum of its two best biased scores, the
    ``topk_group`` best groups stay and the ``top_k`` are taken inside
    them. With one group it is the function above, operation for
    operation."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    biased = scores + lax.stop_gradient(bias.astype(jnp.float32))
    if n_group > 1:
        biased = _group_limited(biased, n_group, topk_group)
    _, ids = lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), gates * scaling


def _group_limited(biased: jax.Array, n_group: int,
                   topk_group: int) -> jax.Array:
    """``biased`` [t, experts] with every expert outside a token's
    ``topk_group`` best groups at ``-inf``; a group's score is the sum of
    its two best."""
    t, total = biased.shape
    grouped = biased.reshape(t, n_group, total // n_group)
    group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = lax.top_k(group_score, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, total)


def balance_term(ids: jax.Array, bias: jax.Array, rate: float) -> jax.Array:
    """The selection bias's update by load (arXiv:2412.19437 §2.1.2, from
    arXiv:2408.15664) as a term of the loss that is always zero and whose
    gradient in the bias is ``rate`` times the sign of each expert's load
    less the mean load: gradient descent then lowers the bias of an
    expert that took more than its share of ``ids`` [t, k] and raises
    the others', by ``rate`` times the learning rate a step."""
    total = bias.shape[0]
    load = jnp.sum(ids[..., None] == jnp.arange(total), axis=(0, 1))
    excess = jnp.sign(load - ids.size / total).astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    return rate * jnp.sum(excess * (bias - lax.stop_gradient(bias)))


def _plan(ids: jax.Array, first: int, count: int, tile: int):
    """Where each (token, expert) pair that lands on a held expert goes
    in a buffer in which every held expert's pairs are contiguous and
    start on a tile boundary. ``ids`` [t, k] → ``row_pair`` [rows]: the
    pair in each row (``t·k`` for an empty row); ``pair_row`` [t·k]: the
    row of each pair (``rows`` for a pair on an expert not held);
    ``tile_expert`` [rows/tile]: whose weights each tile meets;
    ``tiles``: how many tiles hold anything; ``counts`` [count]: pairs
    per held expert. ``rows`` is the worst case, every pair held, so
    nothing is ever dropped; the work done follows ``tiles``."""
    pairs = ids.size
    rows = (-(-pairs // tile) + count) * tile
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)             # pairs by expert
    counts = jnp.sum(key[:, None] == jnp.arange(count + 1)[None, :],
                     axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(counts) - counts              # in sorted order
    padded = -(-counts[:count] // tile) * tile
    ends = jnp.cumsum(padded)                         # in the buffer
    sorted_key = key[order]
    offset = jnp.append(ends - padded, rows)[sorted_key]
    dest = jnp.where(sorted_key < count,
                     offset + jnp.arange(pairs) - starts[sorted_key], rows)
    row_pair = jnp.full((rows,), pairs, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(
        dest.astype(jnp.int32))
    tile_expert = jnp.sum(
        (jnp.arange(rows // tile) * tile)[:, None] >= ends[None, :],
        axis=1, dtype=jnp.int32)
    return (row_pair, pair_row, jnp.minimum(tile_expert, count - 1),
            ends[-1] // tile, counts[:count])


def _zeros(shape, dtype, *like) -> jax.Array:
    """Zeros that vary over the mesh axes ``like`` vary over: what a loop
    carries has to enter it as device-varying as its body leaves it."""
    vma = tuple(sorted(set().union(*(jax.typeof(a).vma for a in like))))
    z = jnp.zeros(shape, dtype)
    return lax.pcast(z, vma, to="varying") if vma else z


def _rows(table: jax.Array, index: jax.Array) -> jax.Array:
    """``table[index]`` where index ``len(table)`` reads zeros."""
    return jnp.take(table, index, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _held_experts(x, gates, weights, plan):
    return _held_experts_fwd(x, gates, weights, plan)[0]


def _tile_of(plan) -> int:
    """The tile a plan was made for: its rows over its tiles."""
    return plan[0].shape[0] // plan[2].shape[0]


def _tile_rows(plan, t, tile, top_k):
    row_pair = lax.dynamic_slice_in_dim(plan[0], t * tile, tile)
    return row_pair, row_pair // top_k


def _held_experts_fwd(x, gates, weights, plan):
    """``x`` [t, d], ``gates`` [t, k] → Σ over a token's pairs on held
    experts of gate · expert(x), as one product grouped by expert: a
    loop over the tiles that hold anything, each gathering its rows of
    ``x``, meeting one expert's weights and writing its rows of the
    buffer; then every pair reads its row back."""
    row_pair, pair_row, tile_expert, tiles, _ = plan
    tile, top_k = _tile_of(plan), gates.shape[1]
    w_gate, w_up, w_down = weights

    def body(t, ys):
        e = tile_expert[t]
        _, token = _tile_rows(plan, t, tile, top_k)
        yt = gated_unit(_rows(x, token), w_gate[e], w_up[e], w_down[e])
        return lax.dynamic_update_slice_in_dim(ys, yt.astype(ys.dtype),
                                               t * tile, axis=0)

    ys = lax.fori_loop(0, tiles, body, _zeros(
        (row_pair.shape[0], x.shape[1]), x.dtype, x, *weights))
    per_pair = _rows(ys, pair_row).reshape(*gates.shape, -1)
    out = jnp.sum(gates[:, :, None] * per_pair.astype(jnp.float32), axis=1)
    return out.astype(x.dtype), (x, gates, weights, plan)


def _held_experts_bwd(saved, dy):
    """Every direction is a gather: a row's cotangent is its token's
    times its gate, a token's is the sum over its pairs' rows. Each tile
    computes its expert's unit again and takes its vector-Jacobian
    product; the weights' cotangents add up in float32."""
    x, gates, weights, plan = saved
    row_pair, pair_row, tile_expert, tiles, _ = plan
    tile, top_k = _tile_of(plan), gates.shape[1]
    flat_gates = gates.reshape(-1)

    def body(t, carry):
        dxs, dgs, dws = carry
        e = tile_expert[t]
        pair, token = _tile_rows(plan, t, tile, top_k)
        w_e = jax.tree.map(lambda w: w[e], weights)
        yt, vjp = jax.vjp(gated_unit, _rows(x, token), *w_e)
        dyt = _rows(dy, token).astype(jnp.float32)
        dgt = jnp.sum(dyt * yt.astype(jnp.float32), axis=-1)
        dxt, *dwt = vjp((dyt * _rows(flat_gates, pair)[:, None])
                        .astype(yt.dtype))
        dws = jax.tree.map(
            lambda acc, d: acc.at[e].add(d.astype(jnp.float32)),
            dws, tuple(dwt))
        return (lax.dynamic_update_slice_in_dim(dxs, dxt, t * tile, axis=0),
                lax.dynamic_update_slice_in_dim(dgs, dgt, t * tile, axis=0),
                dws)

    rows = row_pair.shape[0]
    like = (x, dy, gates, *weights)
    dxs, dgs, dws = lax.fori_loop(0, tiles, body, (
        _zeros((rows, x.shape[1]), x.dtype, *like),
        _zeros((rows,), jnp.float32, *like),
        jax.tree.map(lambda w: _zeros(w.shape, jnp.float32, *like),
                     weights)))
    dx = jnp.sum(_rows(dxs, pair_row).reshape(*gates.shape, -1)
                 .astype(jnp.float32), axis=1).astype(x.dtype)
    dgates = _rows(dgs, pair_row).reshape(gates.shape)
    return (dx, dgates, jax.tree.map(lambda d, w: d.astype(w.dtype),
                                     dws, weights), None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_ffn(x: jax.Array, router_w: jax.Array, bias: jax.Array,
               experts: dict, shared: dict | None, *, total: int,
               held: tuple[int, int], top_k: int, scaling: float,
               bias_rate: float = 0.0, n_group: int = 1,
               topk_group: int = 1):
    """The routed feed-forward of one chip that holds ``held = (first,
    count)`` of a layer's ``total`` experts: ``x`` [b, s, d] is routed
    over all ``total`` (:func:`route_tokens`, under its group limit where
    ``n_group > 1``), the pairs that land on
    held experts are sorted by expert (:func:`_plan`) and go through one
    grouped product with **no pair dropped and no capacity**, and the
    result is that partial sum plus the shared expert, which every chip
    computes alike. The chips that hold the other experts are not stood
    in for: summing the partial sums of every share gives the whole
    layer once the shared expert is counted once.

    Returns ``(out [b, s, d], ids [b, s, k] int32, counts [count],
    balance)``: ``counts`` the pairs each held expert took, ``balance``
    :func:`balance_term` at ``bias_rate`` (0 holds the bias constant)."""
    if router_w.shape[1] != total or experts["w_gate"].shape[0] != held[1]:
        raise ValueError(
            f"router over {router_w.shape[1]} experts and "
            f"{experts['w_gate'].shape[0]} held do not match total={total}, "
            f"held={held}")
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    # routed on what it is handed, float32 where the block keeps its
    # norm there; the experts' products in their weights' dtype
    ids, gates = route_tokens(flat, router_w, bias, top_k, scaling,
                              n_group, topk_group)
    flat = flat.astype(experts["w_gate"].dtype)
    plan = _plan(ids, held[0], held[1], tile_rows(ids.size, total))
    out = _held_experts(flat, gates,
                        (experts["w_gate"], experts["w_up"],
                         experts["w_down"]), plan)
    if shared is not None:
        out = out + gated_unit(flat, shared["w_gate"], shared["w_up"],
                               shared["w_down"])
    balance = (balance_term(ids, bias, bias_rate) if bias_rate
               else jnp.zeros((), jnp.float32))
    return out.reshape(b, s, d), ids.reshape(b, s, top_k), plan[4], balance
