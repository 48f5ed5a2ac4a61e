"""Continuous-batching autoregressive decode replica.

The generation face of the serving tier: same replica contract as
:class:`~.server.ServingReplica` (supervised process, bounded
admission queue, heartbeats, digest-verified weight follow, typed
rejects, zero-drop teardown) with the workload inside it changed from
one-shot classification to streaming decode — the
resource-shape-agnostic-replica move (arXiv:1902.00465): the
supervisor, chaos schedules and invariants apply unchanged.

**Continuous batching.** The replica holds ``decode.decode_slots``
concurrently-generating sequences. Each loop iteration runs ONE
compiled decode step over all of them — a fixed ``[slots]`` shape
whatever mix of lengths is in flight, because every sequence reads its
K/V through its block table over the shared paged cache
(:mod:`.kv_cache`). A sequence that finishes (EOS / max_tokens /
deadline / client gone) frees its blocks and its slot is refilled from
the admission queue the SAME iteration — no padded rounds, no waiting
for a batch to drain.

**The table's width follows the longest live sequence.** The step's
gather arm reads every position its block table spans, so an iteration
hands the step the narrowest of at most four widths (the quarters of
``max_blocks_per_seq``: ``_table_widths``) that holds its longest live
sequence and the token being written. Four compiled programs of one
jitted callable, all compiled in :meth:`DecodeReplica.start` before a
request is accepted; the full width, and its cost, only when a
sequence is that long. (The paged kernel, the arm a TPU runs, reads a
slot's live pages whatever the table's width:
``transformer.decode_attention_arm``; ``decode_start`` says which arm
each width compiled to.)

**State that is a sequence's.** A model with mixer layers
(``model.decode_state_shape``: ops/ssm.py, ops/kda.py) keeps, beside the paged rows
of its attention layers, one recurrent state and one convolution tail a
layer A SLOT, whatever the sequence's length (:class:`.kv_cache.
SlotState`, an array a layer): allocated with the slot at admission, written by the
prefill, handed to the step donated beside the cache and taken back
advanced (an idle slot's slice is left as it was), zeroed at finish, and
zeroed and rebuilt by the re-prefill of a restart. A pinned version's
step advances its own slots only. :func:`build_stores`,
:func:`jit_step`, :func:`run_prefill` and :func:`store_prompt` are what
the loop admits and steps a sequence through, and what
:class:`SlotSession` (one sequence, for the serving check) drives too.

**Prefill.** Prompts are admitted through the existing bounded queue
(typed ``overloaded`` shed when full), padded to power-of-2 buckets
(each bucket's prefill compiles once) and run through the model's
``decode_prefill`` export — the standard causal forward through the
CONFIGURED attention kernel (the fused pallas flash path when
``model.attention_impl=flash``) that also returns every layer's K/V,
scattered into the sequence's blocks. The first token samples off the
prefill logits: time-to-first-token is one prefill, not a decode-queue
wait.

**Sampling.** The compiled step also returns every slot's greedy token,
and an iteration fetches that one ``[slots]`` int32 array. A request
with ``temperature <= 0`` takes its entry; one with ``temperature > 0``
draws from its row of the logits, which stay on the device for it. The
request decides, there is no setting.

**Between a step's tokens and the next launch** the batcher does only
what the launch needs. When the fetch returns it keeps the books, in
order (the token picked and appended, a finish decided, its slot, blocks
and state given back, the tables' epoch bumped): list operations, and
what the next step and the next admission see. What is I/O (a token's
line, a finish's journal record, dedup entry and answer) is queued in
that order and written when the NEXT step's dispatch has returned, while
the chip runs it; or sooner, before anything that would keep a line
waiting: a prefill, a swap, a park on an idle queue, the drain on stop
(``_flush``). A connection receives the bytes it always did, in the
order it always did, a token's line up to one dispatch call later; that
a client is gone is learnt at the write, so it finishes ``client_gone``
one iteration later. Then, still under the running step, the next step's
``positions``, ``lengths`` and tables are built and uploaded: they are
this step's plus one and no token decides them. The next iteration takes
them if the books are as they were predicted (same version, same table
epoch, every slot's length the expected one), with this step's own
``greedy`` array, as it lies on the device, for its tokens where every
live slot is greedy; else it builds all of them as before
(``_step_inputs``). The heartbeat counts both paths and the flushes by
where they happened.

**Weight swaps mid-generation.** The checkpoint follower stages
digest-verified publishes exactly as the classification replica does;
the flip happens at a decode-loop boundary under a declared policy
(``decode.swap_policy``):

* ``pin`` — every in-flight sequence keeps generating on the params it
  started with until it finishes; new admissions use the new weights.
  At most a handful of param versions are live (bounded by slots), and
  a version is dropped the moment its last pinned sequence finishes.
* ``restart`` — every in-flight sequence is re-prefilled on the new
  weights (its streamed tokens are discarded; the stream carries an
  explicit ``restart`` marker so clients reset), journaled per
  sequence as ``seq_restart``.

Either way the swap record grows ``sequences_pinned`` /
``sequences_restarted``, and the ``decode_swap`` replay invariant
(obsv/invariants.py, invariant 10) checks the books: a sequence that
finishes on a different model step than it started on MUST hold a
journaled ``seq_restart`` license, and every ``seq_restart`` must
follow a journaled ``weight_swap`` to its target step.

Wire protocol (one connection per request, line-delimited JSON):

  request:  {"id": ..., "prompt": [int, ...], "max_tokens": N,
             "temperature": t, "top_k": k, "deadline_ms": ...}
  stream:   {"id": ..., "stream": "token", "token": t, "index": i,
             "model_step": s}        (one line per generated token)
            {"id": ..., "stream": "restart", "model_step": s}
            (key "stream", not "event" — journal records own that key)
  terminal: {"id": ..., "status": "ok", "tokens": [...],
             "finish_reason": "eos" | "max_tokens" | "deadline" |
             "client_gone", "model_step": s, "started_step": s0}
            {"id": ..., "status": "rejected", "reason": ...}
"""

from __future__ import annotations

import collections
import json
import queue
import re
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ConfigError
from ..models.registry import sample_token
from ..models.transformer import decode_attention_arm
from ..obsv import spans
from ..obsv.timing import LoopClock
from ..ops import kda
from .kv_cache import (PagedKVCache, SlotState, cache_shapes,
                       stored_head_dim)
from .server import ServingReplica, _Pending


#: the phases of the batcher thread's loop that `LoopClock` times, each
#: the region of the span(s) named beside it in obsv/spans.py; what they
#: leave of `loop_wall_s` (the swap, the deadline sweep, the heartbeat) is
#: the loop's `other`
LOOP_PHASES = ("idle", "admit", "prefill", "inputs", "dispatch", "fetch",
               "emit")
#: where the queued lines are written: after a step's dispatch returned
#: (while the chip runs it), or forced, before what would keep them
#: waiting; the heartbeat's `line_flushes` counts the flushes that found
#: something to write, by these
FLUSH_POINTS = ("dispatch", "prefill", "swap", "park", "stop")


def while_loops(compiled_text: str) -> int:
    """The ``while`` instructions in a compiled program's text."""
    return len(re.findall(r" while\(", compiled_text))


def mosaic_calls(compiled_text: str, kernel: str) -> int:
    """The Mosaic calls in a compiled program's text whose name matches
    ``kernel`` (a regular expression): none where the kernel runs
    interpreted, or not at all."""
    return len(re.findall(rf"%{kernel}[.\d]* = [^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"",
                          compiled_text))


def table_widths(full: int) -> list[int]:
    """The block-table widths an iteration chooses from: the quarters of
    the full table (fewer where a toy table has under four blocks). Each
    is a compiled program and a load at start-up."""
    return sorted({-(-full * k // 4) for k in (1, 2, 3, 4)})


def build_stores(model, dcfg, dtype, sharding, put
                 ) -> tuple[PagedKVCache, SlotState | None]:
    """The paged cache a model's ``decode_cache_shape`` asks for, its
    rows as wide as the device stores them whole (kv_cache.py), and the
    per-slot state its ``decode_state_shape`` asks for (None without
    one), both through ``put``: placed where the weights are, once (no
    copy): a fresh ``jnp.zeros`` is committed to no device, a jitted
    call is compiled again for an argument placed differently, and every
    later call takes the arrays a jitted call returned."""
    layers, heads, head_dim = model.decode_cache_shape
    # One width for keys and values a head; one each where the model
    # keeps a pair of rows a token (a latent, a rotated key)
    shapes = cache_shapes(layers, dcfg.num_blocks, dcfg.block_size, heads,
                          head_dim)
    if isinstance(head_dim, tuple):
        head_dim = tuple(stored_head_dim(shape, dtype, sharding)
                         for shape in shapes)
    else:
        head_dim = stored_head_dim(shapes[0], dtype, sharding)
    cache = PagedKVCache(layers, dcfg.num_blocks, dcfg.block_size, heads,
                         head_dim, dcfg.max_blocks_per_seq(), dtype=dtype)
    cache.k, cache.v = put((cache.k, cache.v))
    state = None
    if model.decode_state_shape is not None:
        state = SlotState(model.decode_state_shape[0], dcfg.decode_slots,
                          *model.decode_state_shape[1:], dtype=dtype)
        state.place(put)
    return cache, state


def jit_step(model, dcfg, return_routing: bool = False):
    """The model's decode step as the replica runs it: jitted, the cache
    arrays (and a slot state's two, which follow ``lengths``) donated,
    every slot's greedy pick made where the logits are.
    ``return_routing`` (a decode session's second program, never the
    loop's): the step asked for its routed layers' choices, which come
    next after the stores."""
    model_step = model.decode_step
    block_size = dcfg.block_size
    attention_kernel = dcfg.attention_kernel
    stateful = model.decode_state_shape is not None

    # a model with per-token routed layers also says how many
    # (token, expert) pairs each expert held here took of the step's
    # tokens, [routed_layers, held]: fetched with the greedy tokens
    counts = {"return_counts": True} if model.decode_counts else {}
    if return_routing:
        counts["return_routing"] = True

    # a named function, not a functools.partial: a profiler trace
    # calls the program `jit_decode_step`, a partial `jit__unknown`
    def decode_step(params, tokens, positions, k_cache, v_cache,
                    block_tables, lengths, *state):
        logits, k_cache, v_cache, *rest = model_step(
            params, tokens, positions, k_cache, v_cache, block_tables,
            lengths, *state, block_size=block_size,
            attention_kernel=attention_kernel, **counts)
        # the greedy pick of every slot, made where the logits are:
        # 4 bytes a slot to fetch, not a [slots, vocab] float32 array
        with jax.named_scope("head"):
            greedy = sample_token(logits)
        return logits, greedy, k_cache, v_cache, *rest

    # the cache arrays are rebound to the step's outputs at every
    # call site, so they are donated: the token's scatter writes in
    # place; so is a slot state
    return jax.jit(decode_step,
                   donate_argnums=(3, 4, 7, 8) if stateful else (3, 4))


def run_prefill(prefill_jit, stateful: bool, params, prompt: np.ndarray,
                bucket: int):
    """The prompt padded to its bucket through the model's prefill
    export: the logits of its last real position and everything the
    export returned. Attention ignores the padding; a state that is a
    sequence's does not, so such a model's export is told the prompt's
    length, hands over the state of its last real token and computes
    that position's logits alone."""
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :prompt.size] = prompt
    if stateful:
        outs = prefill_jit(params, jnp.asarray(toks),
                           jnp.asarray([prompt.size], jnp.int32))
        return outs[0][0, 0], outs
    outs = prefill_jit(params, jnp.asarray(toks))
    return outs[0][0, prompt.size - 1], outs


def store_prompt(cache: PagedKVCache, state: SlotState | None, slot: int,
                 table: np.ndarray, outs: tuple, plen: int) -> None:
    """What a prefill hands over into the stores: every attending
    layer's rows through the sequence's block table, and the slot's
    state whole (nothing of what the slot held before is read)."""
    cache.write_prompt(table, outs[1][:, 0], outs[2][:, 0], plen)
    if state is not None:
        state.write(slot, outs[3], outs[4])


class _Ahead(NamedTuple):
    """A step's inputs built while the step before it ran, and the books
    they hold for: (version, tables' epoch, [(slot, length)])."""
    books: tuple
    width: int
    positions: jax.Array
    tables: jax.Array
    lengths: jax.Array
    greedy: jax.Array      # the step before's own picks, on the device


class _DecodeSeq(_Pending):
    """One in-flight generation (``inputs`` holds the prompt)."""

    __slots__ = ("max_tokens", "temperature", "top_k", "block_table",
                 "length", "tokens", "params_step", "started_step",
                 "first_token_at", "restarts", "conn_dead", "sample_seed")

    def __init__(self, req_id, prompt, conn, admitted_at, deadline_at):
        super().__init__(req_id, prompt, conn, admitted_at, deadline_at)
        self.max_tokens = 0
        self.temperature = 0.0
        self.top_k = 0
        self.block_table = None
        self.length = 0            # context tokens written to the cache
        self.tokens: list[int] = []
        self.params_step = -1
        self.started_step = -1
        self.first_token_at: float | None = None
        self.restarts = 0
        self.conn_dead = False
        self.sample_seed = 0


class DecodeReplica(ServingReplica):
    """Hot-follow published checkpoints and stream autoregressive
    generations with continuous batching over a paged KV cache."""

    def __init__(self, train_dir, serve_dir=".", scfg=None, dcfg=None,
                 cfg=None, topo=None):
        super().__init__(train_dir, serve_dir=serve_dir, scfg=scfg,
                         cfg=cfg, topo=topo)
        if self.tier != "fp32":
            raise ConfigError(
                f"serve.precision_tier={self.tier!r}: the decode "
                "service serves full precision only (quant sidecars "
                "hold weights for the one-shot predict export, not the "
                "decode graph)")
        if (self.model.decode_prefill is None
                or self.model.decode_step is None):
            raise ConfigError(
                f"model {self.cfg.model.name!r} exports no decode step: "
                "the registry exports one for a causal LM with the plain "
                "block, a latent one (dense, gated or per-token routed "
                "feed-forward) or mixer layers (state-space; delta-rule, "
                "also beside latent attention and routed feed-forwards), "
                "and none for a classifier, for capacity "
                "routing (model.num_experts), for more than one residual "
                "stream, or for a gated or sandwich-normed block that "
                "does not attend through a latent")
        self.dcfg = dcfg or self.cfg.decode
        self.dcfg.validate()
        if (self.dcfg.max_prompt_len + self.dcfg.max_new_tokens
                > self.cfg.model.seq_len):
            raise ConfigError(
                f"decode.max_prompt_len + decode.max_new_tokens = "
                f"{self.dcfg.max_prompt_len + self.dcfg.max_new_tokens} "
                f"exceeds model.seq_len={self.cfg.model.seq_len} (the "
                "learned position table is the hard context ceiling)")
        from ..core.config import effective_model_config
        dtype = jnp.dtype(
            effective_model_config(self.cfg, serving=True).compute_dtype)
        self._stateful = self.model.decode_state_shape is not None
        if self._stateful and self.tp_ranks > 1:
            raise ConfigError(
                f"serve.tp_ranks={self.tp_ranks}: a model with mixer layers "
                "(state-space, delta-rule) is served on one chip; its "
                "per-slot state "
                "(servesvc/kv_cache.py::SlotState) has no rule that "
                "splits it over a model axis")
        self.cache, self.state = build_stores(
            self.model, self.dcfg, dtype, self.topo.replicated,
            self.topo.device_put_replicated)
        self._table_widths = table_widths(self.cache.max_blocks_per_seq)
        self._prefill_jit = jax.jit(self.model.decode_prefill)
        self._decode_jit = jit_step(self.model, self.dcfg)
        # {table width: the executable `_warm_up` compiled from it}
        self._steps: dict[int, jax.stages.Compiled] = {}
        # decode-loop-owned state (single writer: the batcher thread)
        self._slots: list[_DecodeSeq | None] = (
            [None] * self.dcfg.decode_slots)
        self._waiting: collections.deque[_DecodeSeq] = collections.deque()
        self._versions: dict[int, object] = {}  # pinned old params
        self._seq_counter = 0
        self.tokens_streamed = 0
        self.decode_steps = 0      # dispatches of the jitted decode step
        self.decode_table_blocks = 0  # the last dispatch's table width
        # of the last step, where the model routes: its held experts'
        # pair counts [routed_layers, held], left on the device until a
        # heartbeat reads them
        self._expert_pairs: jax.Array | None = None
        # tokens by where they were picked: the step's own greedy pick,
        # or `_sample` (a draw, and every prefill's first token)
        self.tokens_sampled_device = 0
        self.tokens_sampled_host = 0
        self.sequences_finished = 0
        # where the batcher thread's time goes, always on: cumulative
        # seconds by phase, read at the boundaries the spans mark and
        # carried by the heartbeat (`loop_s`, `loop_wall_s`)
        self._clock = LoopClock(LOOP_PHASES)
        # block-table upload cache: slot→block assignments only change
        # on admit/finish/restart, so the [slots, width] tables array a
        # decode iteration feeds the jitted step is IDENTICAL between
        # those events at one width — rebuild + re-upload it once per
        # (params version, table epoch, width) instead of every
        # generated token. The epoch counter is bumped by every mutation
        # of any slot's table or version assignment; bumping clears the
        # cache. The width is in the key because a sequence grows past a
        # rung of `_table_widths` with no such event.
        self._tables_epoch = 0
        self._tables_cache: dict[tuple[int, int, int], jax.Array] = {}
        self.table_uploads = 0
        self.table_upload_reuses = 0
        # decided and not yet written, in the order decided: (sequence,
        # its token's line, None) or (sequence, None, its finish's
        # journal fields); `_flush` writes them
        self._deferred: list[tuple] = []
        self.lines_deferred = 0
        self.flushes = dict.fromkeys(FLUSH_POINTS, 0)
        # the next step's inputs, built while this one runs
        self._ahead: _Ahead | None = None
        # whether a step takes its tokens as the step before returned
        # them (`_warm_up` compares the two placements of a compiled
        # step; a jitted one moves what it is given)
        self._feed_greedy = True
        self.step_inputs_ahead = 0
        self.step_inputs_rebuilt = 0

    # -- admission ------------------------------------------------------

    def _build_item(self, req: dict, conn):
        req_id = req.get("id")
        try:
            prompt = np.asarray(req["prompt"], dtype=np.int32)
            if (prompt.ndim != 1 or prompt.size < 1
                    or prompt.size > self.dcfg.max_prompt_len):
                raise ValueError("prompt length out of range")
            if (int(prompt.min()) < 0
                    or int(prompt.max()) >= self.cfg.model.vocab_size):
                raise ValueError("token id out of vocab")
            max_tokens = int(req.get("max_tokens",
                                     self.dcfg.max_new_tokens))
            if not 1 <= max_tokens <= self.dcfg.max_new_tokens:
                raise ValueError("max_tokens out of range")
            temperature = float(req.get("temperature",
                                        self.dcfg.temperature))
            top_k = int(req.get("top_k", self.dcfg.top_k))
        except (KeyError, ValueError, TypeError):
            self._reject(conn, req_id, "bad_request", admitted=False)
            return None
        now = time.time()
        deadline_ms = req.get("deadline_ms",
                              self.scfg.default_deadline_ms)
        # streaming sends run on the SINGLE decode-loop thread: a
        # client that stopped reading must cost the loop a short
        # bounded stall ONCE (then conn_dead), never the accept-side
        # 5 s timeout per token — one stalled reader must not freeze
        # every other slot's generation
        try:
            conn.settimeout(0.5)
        except OSError:
            pass
        seq = _DecodeSeq(req_id, prompt, conn, now,
                         now + float(deadline_ms) / 1e3)
        seq.max_tokens = max_tokens
        seq.temperature = temperature
        seq.top_k = top_k
        return seq

    # -- weights: version registry + swap policies ----------------------

    def _params_for(self, step: int):
        return (self._params if step == self.model_step
                else self._versions[step])

    def _release_version(self, step: int) -> None:
        if step == self.model_step or step not in self._versions:
            return
        if not any(s is not None and s.params_step == step
                   for s in self._slots):
            del self._versions[step]

    def _maybe_swap(self) -> None:
        """Decode-loop-boundary flip under the declared mid-generation
        policy; journals the swap with its per-sequence bookkeeping."""
        with self._staged_lock:
            staged, self._staged = self._staged, None
        if staged is None:
            return
        self._flush("swap")     # a restart's marker follows its lines
        with spans.span(spans.SERVE_SWAP):
            self._swap(*staged)

    def _swap(self, install: dict, t0: float) -> None:
        if install["step"] <= self.model_step:
            return  # monotone: never swap backwards
        in_flight = [s for s in self._slots if s is not None]
        prev_step = self.model_step
        pinned = restarted = 0
        if in_flight:
            if self.dcfg.swap_policy == "pin":
                pinned = len(in_flight)
                if any(s.params_step == prev_step for s in in_flight):
                    # stash only a version something actually runs on:
                    # back-to-back swaps with everything pinned to an
                    # even older version must not leak the middle one
                    self._versions[prev_step] = self._params
            else:
                restarted = len(in_flight)
        self._install(install, t0,
                      extra={"sequences_pinned": pinned,
                             "sequences_restarted": restarted})
        if restarted:
            for s in in_flight:
                self._restart_seq(s, prev_step)

    def _restart_seq(self, s: _DecodeSeq, from_step: int) -> None:
        """The restart policy's per-sequence move: discard what the old
        params generated, re-prefill on the new — journaled as the
        causal license the decode_swap invariant requires."""
        self._journal({"action": "seq_restart", "id": s.req_id,
                       "from_step": from_step,
                       "to_step": self.model_step,
                       "tokens_discarded": len(s.tokens)})
        self._send_line(s, {"id": s.req_id, "stream": "restart",
                            "model_step": self.model_step})
        s.tokens = []
        s.length = 0
        s.restarts += 1
        s.params_step = self.model_step
        self._bump_tables_epoch()  # version composition changed
        # ttft is a property of the stream the client KEEPS: the
        # pre-restart first token was discarded, so the journaled
        # decode_finish must time the post-restart one (matching what
        # the client-side loadgen measures after its reset)
        s.first_token_at = None
        if self.state is not None:
            # nothing the old weights left in the slot stays readable,
            # whatever the re-prefill then writes
            self.state.reset(self._slots.index(s))
        self._prefill(s, restart=True)

    def _pressure_fields(self) -> dict:
        """Queue occupancy plus KV block-pool pressure: free blocks
        against the usable pool (block 0 is the reserved null block)
        and the deferred-admission line — a pool near empty is the
        decode-side signal the broker scales on. Reads only; the
        allocator's single writer is this same batcher thread."""
        alloc = self.cache.allocator
        return {**super()._pressure_fields(),
                "kv_blocks_free": alloc.available,
                "kv_blocks_total": alloc.num_blocks - 1,
                "kv_blocks_reserved": len(alloc.in_use),
                "decode_waiting": len(self._waiting),
                "slots_live": sum(s is not None for s in self._slots),
                "decode_steps": self.decode_steps,
                "decode_table_blocks": self.decode_table_blocks,
                "tokens_sampled_device": self.tokens_sampled_device,
                "tokens_sampled_host": self.tokens_sampled_host,
                "lines_deferred": self.lines_deferred,
                "line_flushes": dict(self.flushes),
                "step_inputs_ahead": self.step_inputs_ahead,
                "step_inputs_rebuilt": self.step_inputs_rebuilt,
                "loop_s": {k: round(v, 6)
                           for k, v in self._clock.seconds.items()},
                "loop_wall_s": round(self._clock.wall_s(), 6),
                **({} if self.state is None
                   else {"state_resets": self.state.resets}),
                **self._routing_fields()}

    def _routing_fields(self) -> dict:
        """Of the last step of a model that routes: the pairs that
        landed on experts held here, and how many of those experts took
        any. Fetched here, where a heartbeat is written, and not an
        iteration."""
        if self._expert_pairs is None:
            return {}
        pairs = np.asarray(self._expert_pairs)
        return {"expert_pairs_held": int(pairs.sum()),
                "experts_touched": int(np.count_nonzero(pairs))}

    def _write_heartbeat(self, n: int) -> None:
        # under its own span: in a model that routes, the write fetches
        # the last step's pair counts from the device
        with spans.span(spans.SERVE_HEARTBEAT):
            super()._write_heartbeat(n)

    # -- the decode loop ------------------------------------------------

    def _batch_loop(self) -> None:  # overrides the classification batcher
        while not self._stop.is_set():
            self._maybe_swap()
            self._admit_new()
            self._step_active()
            self._maybe_heartbeat()
        # graceful drain: what was decided is written first; then
        # in-flight generations, deferred admissions and everything
        # still queued get a TYPED terminal — a stopping replica sheds,
        # it never silently drops
        self._flush("stop")
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                self._free_stores(i, s)
                self._reject(s.conn, s.req_id, "shutting_down",
                             admitted=True)
        while self._waiting:
            s = self._waiting.popleft()
            self._reject(s.conn, s.req_id, "shutting_down", admitted=True)
        while True:
            try:
                it = self._queue.get_nowait()
            except queue.Empty:
                break
            self._reject(it.conn, it.req_id, "shutting_down",
                         admitted=True)
        self._maybe_heartbeat()

    def _admit_new(self) -> None:
        """Refill free slots from the admission queue. Block pressure
        (the free list cannot hold another worst-case sequence) defers
        the admission — bounded by the request's own deadline — rather
        than evicting a running generation."""
        idle = (not self._waiting
                and all(s is None for s in self._slots))
        phase, name = (("idle", spans.SERVE_IDLE) if idle
                       else ("admit", spans.SERVE_ADMIT))
        if idle:
            self._flush("park")
        with self._clock.phase(phase, spans.span(name)):
            try:
                # idle: park briefly on the queue instead of spinning.
                # _waiting is capped at the slot count — anything beyond
                # stays in the BOUNDED socket queue, so sustained block
                # pressure still sheds typed `overloaded` rejects at
                # admission instead of growing an unbounded staging line
                while len(self._waiting) < self.dcfg.decode_slots:
                    self._waiting.append(
                        self._queue.get(timeout=0.05) if idle
                        else self._queue.get_nowait())
                    idle = False
            except queue.Empty:
                pass
        while True:
            # the prefill is a sibling of the admit span, not its child:
            # the leaves tile the loop (obsv/spans.py)
            with self._clock.phase("admit", spans.span(spans.SERVE_ADMIT)):
                s = self._place_next()
            if s is None:
                return
            self._prefill(s)

    def _place_next(self) -> _DecodeSeq | None:
        """Move the head of the deferred line into a free slot with its
        blocks reserved; None when nothing more can be admitted now (no
        one waits, no free slot, or block pressure: retried next
        iteration)."""
        while self._waiting:
            free = next((i for i, s in enumerate(self._slots)
                         if s is None), None)
            if free is None:
                return None
            s = self._waiting[0]
            if time.time() >= s.deadline_at:
                self._waiting.popleft()
                self._reject(s.conn, s.req_id, "deadline_exceeded",
                             admitted=True)
                continue
            table = self.cache.alloc_sequence(
                int(s.inputs.size) + s.max_tokens)
            if table is None:
                return None
            self._waiting.popleft()
            s.block_table = table
            s.params_step = s.started_step = self.model_step
            s.sample_seed = self._seq_counter
            self._seq_counter += 1
            self._slots[free] = s
            if self.state is not None:
                self.state.alloc(free)
            self._bump_tables_epoch()
            return s
        return None

    def _free_stores(self, slot: int, s: _DecodeSeq) -> None:
        """What a sequence held, given back: its blocks to the pool and
        its slot's state zeroed."""
        self.cache.free_sequence(s.block_table)
        if self.state is not None:
            self.state.free(slot)

    def _prefill(self, s: _DecodeSeq, restart: bool = False) -> None:
        """Run the prompt through the model's prefill export (the
        configured attention kernel), seed the paged cache, and sample
        + stream the first token."""
        self._flush("prefill")  # no line waits behind a prefill
        t0 = time.time()
        queue_ms = round((t0 - s.admitted_at) * 1e3, 3)
        slot = self._slots.index(s)
        plen = int(s.inputs.size)
        bucket = self._bucket(plen, self.dcfg.max_prompt_len)
        with self._clock.phase("prefill", spans.span(
                spans.SERVE_PREFILL, id=s.req_id, prompt_len=plen,
                bucket=bucket, queue_ms=queue_ms)):
            with spans.span(spans.SERVE_PREFILL_FORWARD):
                row, outs = run_prefill(
                    self._prefill_jit, self._stateful,
                    self._params_for(s.params_step), s.inputs, bucket)
            with spans.span(spans.SERVE_PREFILL_CACHE_WRITE):
                t_write = time.time()
                store_prompt(self.cache, self.state, slot, s.block_table,
                             outs, plen)
                write_ms = round((time.time() - t_write) * 1e3, 3)
            s.length = plen
            # waits for the prefill on the device
            with spans.span(spans.SERVE_SAMPLE, id=s.req_id, slot=slot):
                tok = self._sample(s, row)
            s.tokens.append(tok)
            # written here and now: the first token waits for nothing
            self._stream(s, self._token_line(s, tok))
            prefill_ms = round((time.time() - t0) * 1e3, 3)
            rec = {"action": "prefill", "id": s.req_id, "prompt_len": plen,
                   "bucket": bucket,
                   "blocks": int(np.count_nonzero(s.block_table)),
                   "model_step": s.params_step,
                   "prefill_ms": prefill_ms,
                   # the same value under its first name, which said
                   # "time to first token" and never was: readers of
                   # the journal (benchmark/lib/serving.py) still ask
                   # for it
                   "ttft_ms": prefill_ms}
            if self.state is not None:
                # the host's part of both writes (rows and slot state):
                # the dispatches, not the device's time
                rec["state_write_ms"] = write_ms
            if restart:
                rec["restart"] = True
            else:
                # admitted_at → the start of this prefill: the wait in
                # the queue and the deferred line for the running step
                rec["queue_ms"] = queue_ms
            self._journal(rec)
        self._maybe_finish(slot, s)

    def _bump_tables_epoch(self) -> None:
        """Invalidate cached block-table uploads — called by every
        mutation of a slot's table or params-version assignment
        (admit, finish, restart)."""
        self._tables_epoch += 1
        self._tables_cache.clear()

    def _tables_for(self, ver: int, mine, num_slots: int,
                    width: int) -> jax.Array:
        """The device-resident [slots, width] block-tables array for
        one params version's compiled step: the first ``width`` entries
        of each of its sequences' tables (``_table_width`` chose a
        width that holds them all). Rows of slots NOT on this version
        are zero (the null block) at every width — load-bearing, not
        padding: the gather arm's step scatters the new token's K/V
        through row ``positions[i] // block_size`` of EVERY slot, and
        zero routes the not-mine writes into the reserved null block
        instead of a live sequence's block 0 (the paged arm's kernel
        writes nothing for a slot of length 0). Cached per (version, table epoch,
        width): between admit/finish/restart events the array at one
        width is bit-identical every iteration, so steady-state
        decoding reuses one upload instead of paying a host rebuild +
        transfer per token (reuse near 0.7 under churn in the harness
        removed at PR 48; tests/test_decode_widths.py holds the counters);
        a sequence that grows past a rung gets a fresh, wider one.
        """
        key = (ver, self._tables_epoch, width)
        cached = self._tables_cache.get(key)
        if cached is not None:
            self.table_upload_reuses += 1
            return cached
        tables = np.zeros((num_slots, width), np.int32)
        for i, s in mine:
            tables[i] = s.block_table[:width]
        dev = jnp.asarray(tables)
        self._tables_cache[key] = dev
        self.table_uploads += 1
        return dev

    def _table_width(self, mine, ahead: int = 0) -> int | None:
        """The narrowest of ``_table_widths`` that holds every one of
        these sequences AND the position the step is about to write:
        ``s.length + 1``, since this token's K/V goes to position
        ``s.length`` through ``block_tables[i, s.length // block_size]``,
        which has to lie inside the table the step is given. Read from
        the current lengths, so it narrows again when a long sequence
        finishes. ``ahead``: for the step after ``ahead`` more tokens
        (None where no table holds that: the sequence ends first)."""
        need = max(s.length for _, s in mine) + ahead + 1
        return next((w for w in self._table_widths
                     if w * self.cache.block_size >= need), None)

    def _slot_vector(self, mine, value) -> jax.Array:
        """``value(sequence)`` at each of these sequences' slots and 0 at
        every other, as an ``int32[slots]`` array on the device: one
        upload."""
        vec = np.zeros((self.dcfg.decode_slots,), np.int32)
        for i, s in mine:
            vec[i] = value(s)
        return jnp.asarray(vec)

    def _step_inputs(self, ver: int, mine) -> tuple:
        """(width, tokens, positions, tables, lengths) for one version's
        step. What `_inputs_ahead` made while the step before ran, if
        the books are as it expected them (the version, the tables'
        epoch, every slot's length: a finish, an admission, a restart or
        a swap changes one of them) and then the tokens alone are new:
        the step's own greedy array where every sequence took its entry,
        else one upload. Otherwise all of it from the books as they
        are."""
        ahead, self._ahead = self._ahead, None
        if ahead is not None and ahead.books == (
                ver, self._tables_epoch, [(i, s.length) for i, s in mine]):
            self.step_inputs_ahead += 1
            tokens = ahead.greedy
            if not (self._feed_greedy
                    and all(s.temperature <= 0.0 for _, s in mine)):
                tokens = self._slot_vector(mine, lambda s: s.tokens[-1])
            return (ahead.width, tokens, ahead.positions, ahead.tables,
                    ahead.lengths)
        self.step_inputs_rebuilt += 1
        width = self._table_width(mine)
        tokens = self._slot_vector(mine, lambda s: s.tokens[-1])
        positions = self._slot_vector(mine, lambda s: s.length)
        tables = self._tables_for(ver, mine, self.dcfg.decode_slots, width)
        lengths = self._slot_vector(mine, lambda s: s.length + 1)
        return width, tokens, positions, tables, lengths

    def _inputs_ahead(self, ver: int, mine, greedy: jax.Array) -> None:
        """While the step just dispatched runs: what the step after it
        takes that this one's tokens do not decide, for the books as
        they will be if this one ends no sequence (each a token longer,
        nobody admitted). Nothing rests on the guess: `_step_inputs`
        compares the books."""
        width = self._table_width(mine, ahead=1)
        if width is None:
            return
        self._ahead = _Ahead(
            (ver, self._tables_epoch, [(i, s.length + 1) for i, s in mine]),
            width,
            self._slot_vector(mine, lambda s: s.length + 1),
            self._tables_for(ver, mine, self.dcfg.decode_slots, width),
            self._slot_vector(mine, lambda s: s.length + 2),
            greedy)

    def _step_active(self) -> None:
        """One decode iteration: a single compiled step per live param
        version over the fixed slot shape, at the table width its
        longest sequence needs; while it runs, the lines the iteration
        before decided are written and the next step's inputs built;
        then its greedy tokens fetched as one array and the books kept
        per slot — a finished slot is free for the NEXT iteration's
        refill."""
        now = time.time()
        for i, s in enumerate(self._slots):
            if s is not None and now >= s.deadline_at:
                self._finish_seq(i, s, "deadline")
        active = [(i, s) for i, s in enumerate(self._slots)
                  if s is not None]
        if not active:
            return
        # pin policy: at most a handful of live versions — one compiled
        # step per version, idle-for-this-version slots masked via the
        # null block table + zero length
        versions = sorted({s.params_step for _, s in active})
        for ver in versions:
            mine = [(i, s) for i, s in active if s.params_step == ver]
            with self._clock.phase("inputs",
                                   spans.span(spans.SERVE_STEP_INPUTS)):
                width, tokens, positions, tables, lengths = (
                    self._step_inputs(ver, mine))
            with self._clock.phase("dispatch", spans.span(
                    spans.SERVE_STEP_DISPATCH, live=len(active),
                    waiting=len(self._waiting), version=ver, blocks=width)):
                logits, greedy, self.cache.k, self.cache.v, *pairs = (
                    self._step(width)(
                        self._params_for(ver), tokens, positions,
                        self.cache.k, self.cache.v, tables, lengths,
                        *self._state_arrays()))
                if self.state is not None:
                    # taken back advanced; a slot not of this version
                    # (length 0) as it was. What is left is the pair
                    # counts of a model that also routes
                    self.state.state, self.state.tail, *pairs = pairs
                self.decode_steps += 1
                self.decode_table_blocks = width
            # the chip is busy from here to the fetch: what the fetch
            # before decided goes out, and the next step's inputs are
            # made (several versions a step each: the next step is not
            # this one's successor)
            self._flush("dispatch")
            if len(versions) == 1:
                with self._clock.phase("inputs",
                                       spans.span(spans.SERVE_STEP_INPUTS)):
                    self._inputs_ahead(ver, mine, greedy)
            with self._clock.phase("fetch",
                                   spans.span(spans.SERVE_STEP_FETCH)):
                on_host = jax.device_get(greedy)
            if pairs:
                self._expert_pairs = pairs[0]
            draws = sum(s.temperature > 0.0 for _, s in mine)
            # every slot's token and what it decides, on the books alone:
            # one span an iteration, nothing written inside it
            with self._clock.phase("emit", spans.span(
                    spans.SERVE_SAMPLE, device=len(mine) - draws,
                    host=draws)):
                on_host = on_host.tolist()
                picked = [on_host[i] if s.temperature <= 0.0
                          else self._sample(s, logits[i])
                          for i, s in mine]
                self.tokens_sampled_device += len(mine) - draws
                for (i, s), tok in zip(mine, picked):
                    s.length += 1  # the fed token's K/V is now cached
                    s.tokens.append(tok)
                    self._deferred.append((s, self._token_line(s, tok),
                                           None))
                    self._maybe_finish(i, s)
                self.lines_deferred += len(mine)

    def _sample(self, s: _DecodeSeq, logits_row: jax.Array) -> int:
        """One token from one row of logits on the device: a prefill's
        first token, or a decode iteration's seeded draw."""
        self.tokens_sampled_host += 1
        if s.temperature <= 0.0:
            return int(sample_token(logits_row))
        key = jax.random.fold_in(
            jax.random.PRNGKey(s.sample_seed),
            len(s.tokens) + 1000 * s.restarts)
        return int(sample_token(logits_row, key,
                                temperature=s.temperature, top_k=s.top_k))

    # -- streaming + termination ----------------------------------------

    def _send_line(self, s: _DecodeSeq, payload: dict) -> None:
        if s.conn_dead:
            return
        try:
            s.conn.sendall((json.dumps(payload) + "\n").encode())
        except OSError:
            s.conn_dead = True  # finish early at the next check

    def _token_line(self, s: _DecodeSeq, tok: int) -> dict:
        """The line of the token just appended, counted as streamed."""
        if s.first_token_at is None:
            s.first_token_at = time.time()
        self.tokens_streamed += 1
        return {"id": s.req_id, "stream": "token", "token": int(tok),
                "index": len(s.tokens) - 1, "model_step": s.params_step}

    def _stream(self, s: _DecodeSeq, line: dict) -> None:
        with spans.span(spans.SERVE_STREAM, id=s.req_id):
            self._send_line(s, line)

    def _flush(self, point: str) -> None:
        """Write what was decided and not yet written, in the order
        decided: a token's line; a finish's journal record, dedup entry
        and answer (exactly one terminal, after the sequence's last
        token). ``point`` (of `FLUSH_POINTS`) says where the loop is,
        for the count."""
        if not self._deferred:
            return
        queued, self._deferred = self._deferred, []
        self.flushes[point] += 1
        with self._clock.phase("emit"):
            for s, line, finish in queued:
                if finish is None:
                    self._stream(s, line)
                    continue
                with spans.span(spans.SERVE_FINISH, id=s.req_id,
                                reason=finish["reason"]):
                    self._terminal("decode_finish", s.req_id, **finish)
                    payload = {
                        "id": s.req_id, "status": "ok",
                        "tokens": [int(t) for t in s.tokens],
                        "finish_reason": finish["reason"],
                        "model_step": s.params_step,
                        "started_step": s.started_step}
                    # idempotency: a mid-stream reset that ate this
                    # terminal makes the retry a dedup hit carrying the
                    # SAME completed tokens — the generation never runs
                    # twice for one request id
                    self._dedup_put(s.req_id, payload)
                    self._respond(s.conn, payload)

    def _maybe_finish(self, i: int, s: _DecodeSeq) -> None:
        eos = self.dcfg.eos_token
        if eos >= 0 and s.tokens and s.tokens[-1] == eos:
            self._finish_seq(i, s, "eos")
        elif len(s.tokens) >= s.max_tokens:
            self._finish_seq(i, s, "max_tokens")
        elif s.conn_dead:
            self._finish_seq(i, s, "client_gone")
        elif time.time() >= s.deadline_at:
            self._finish_seq(i, s, "deadline")

    def _finish_seq(self, i: int, s: _DecodeSeq, reason: str) -> None:
        """Exactly-one-terminal: decide the finish now (its journal
        record's fields as of now; the blocks freed, the slot released,
        refillable this very iteration, the param version dropped if
        this was its last pinned sequence) and queue what is written
        (`_flush`: the journal record, the dedup entry, the final
        line)."""
        fields = {"reason": reason, "tokens_streamed": len(s.tokens),
                  "model_step": s.params_step,
                  "started_step": s.started_step,
                  "latency_ms": round(
                      (time.time() - s.admitted_at) * 1e3, 3)}
        if s.first_token_at is not None:
            fields["ttft_ms"] = round(
                (s.first_token_at - s.admitted_at) * 1e3, 3)
        if s.restarts:
            fields["restarts"] = s.restarts
        self._deferred.append((s, None, fields))
        self.lines_deferred += 1
        self._slots[i] = None
        self._free_stores(i, s)
        self._bump_tables_epoch()
        self._release_version(s.params_step)
        self.sequences_finished += 1

    # -- metadata / lifecycle -------------------------------------------

    def _meta(self) -> dict:
        return {"status": "ok", "meta": True, "decode": True,
                "model": self.cfg.model.name,
                "vocab_size": self.cfg.model.vocab_size,
                "model_step": self.model_step,
                "model_digest": self.model_digest,
                "precision_tier": self.tier,
                "active_tier": self.model_tier,
                "decode_slots": self.dcfg.decode_slots,
                "block_size": self.dcfg.block_size,
                "num_blocks": self.dcfg.num_blocks,
                "max_prompt_len": self.dcfg.max_prompt_len,
                "max_new_tokens": self.dcfg.max_new_tokens,
                "eos_token": self.dcfg.eos_token,
                "swap_policy": self.dcfg.swap_policy}

    def _step(self, width: int):
        """The step for a table ``width`` blocks wide: the executable
        `_warm_up` compiled, else the jitted function (a replica driven
        without ``start()`` compiles a width at its first use)."""
        return self._steps.get(width, self._decode_jit)

    def _warm_up(self) -> None:
        """Every table width's decode step compiled (or loaded from the
        compile cache) before a request is accepted: a rung first
        reached by a live conversation would otherwise stall every slot
        for one compile. Compiled ahead of time, so that ``start()`` can
        say of each what it needs beside its arguments and whether it
        copies the cache. Each width then runs once with every slot
        idle (tables and lengths zero: the writes land in the null
        block, which is what it is for), on cache arrays placed as every
        later call finds them (``__init__``)."""
        num_slots = self.dcfg.decode_slots
        idle = jnp.asarray(np.zeros((num_slots,), np.int32))
        for width in self._table_widths:
            tables = jnp.asarray(np.zeros((num_slots, width), np.int32))
            self._steps[width] = self._decode_jit.lower(
                self._params, idle, idle, self.cache.k, self.cache.v,
                tables, idle, *self._state_arrays()).compile()
            _, greedy, self.cache.k, self.cache.v, *rest = (
                self._steps[width](
                    self._params, idle, idle, self.cache.k, self.cache.v,
                    tables, idle, *self._state_arrays()))
            if self.state is not None:
                self.state.state, self.state.tail = rest[:2]
        jax.block_until_ready(greedy)
        # a compiled step takes an argument only as it was compiled for:
        # its own greedy tokens where they come back placed as its
        # tokens go in (one chip: always)
        self._feed_greedy = all(
            step.input_shardings[0][1].is_equivalent_to(
                step.output_shardings[1], 1)
            for step in self._steps.values())

    def _state_arrays(self) -> tuple:
        """What the step takes beside the cache: a slot state's two
        arrays, or nothing."""
        return () if self.state is None else self.state.arrays

    def _state_said(self, texts: list[str]) -> dict:
        """Of a model whose state is a sequence's, for ``decode_start``
        (``texts``: each width's compiled step):
        the shapes of a layer's two arrays ([slots, N, E] float32 and
        [K - 1, slots, W]: the layout) and how many layers have such a
        pair, what one sequence's state takes, what all of them take on
        the device, the kind of mixer that keeps it, how many layers
        attend (the paged cache's) and the key-value heads their rows
        hold (1: one row a token for all heads); how a step advances the
        state (``state_arm``: ``ops/kda.py::state_arm``, which answers
        ``"xla"`` off a TPU and for a state-space layer's rows) and, a value a
        table width, the Mosaic calls of the kernel that holds it in
        VMEM in the compiled step (``state_kernel_calls``: one a
        delta-rule layer on a TPU)."""
        if self.state is None:
            return {}
        kept = self.state.state[0]
        return {"state_arm": kda.state_arm(kept.shape, kept.dtype),
                "state_kernel_calls": [mosaic_calls(text, "kda_state_step")
                                       for text in texts],
                "state_arrays": [list(a[0].shape)
                                 for a in self.state.arrays],
                "state_layers": len(self.state.state),
                "state_slot_bytes": self.state.slot_bytes(),
                "state_device_bytes": self.state.device_bytes(),
                "mixer_kind": ("kda" if self.cfg.model.kda_head_dim
                               else "ssm"),
                "attention_layers": self.model.decode_cache_shape[0],
                "kv_heads": self.model.decode_cache_shape[1]}

    def _cache_said(self, texts: list[str]) -> dict:
        """How the cache lies on the device and what each width's step
        (``texts``: its compiled text) does with it, for
        ``decode_start``: ``whole_cache_copies`` counts
        the ``copy`` instructions of the cache's shape in a compiled
        step (0 where it takes the arrays as they lie, 4 where it
        transposes both on the way in and back on the way out),
        ``attention_arm`` / ``paged_calls`` how it reads them, and
        ``step_while_loops`` the ``while`` instructions left in it (a
        scatter of the new token's rows compiles to two a layer; the
        paged kernel writes them itself: none)."""
        at = self.cache.k.format.layout
        dims = re.escape(f"[{','.join(map(str, self.cache.k.shape))}]")
        steps = [self._steps[w] for w in self._table_widths]
        arrays = (self.cache.k, self.cache.v)
        device_bytes = sum(a.on_device_size_in_bytes() for a in arrays)
        return {
            "cache_layout": (f"major_to_minor={tuple(at.major_to_minor)} "
                             f"tiling={tuple(at.tiling or ())}"),
            "cache_device_bytes": device_bytes,
            # what a cached token takes on the device, every layer's rows
            "cache_row_bytes": device_bytes // (self.dcfg.num_blocks
                                                * self.dcfg.block_size),
            "cache_arrays": [list(a.shape) for a in arrays],
            "step_temp_bytes": [s.memory_analysis().temp_size_in_bytes
                                for s in steps],
            "whole_cache_copies": [
                len(re.findall(rf"= \w+{dims}\{{[^}}]*\}} copy\(", text))
                for text in texts],
            # how each width's step reads the cache: the arm the block
            # chose, and the Mosaic calls of the paged kernel in the
            # compiled step (one a layer on a TPU; none where the kernel
            # runs interpreted, or not at all)
            "attention_arm": [decode_attention_arm(
                self.dcfg.attention_kernel, self.cache.k.shape,
                self.cache.v.shape)] * len(steps),
            "paged_calls": [mosaic_calls(text, "paged_(?:latent_)?decode")
                            for text in texts],
            "step_while_loops": [while_loops(text) for text in texts]}

    def start(self) -> None:
        super().start()
        texts = [self._steps[w].as_text() for w in self._table_widths]
        self._journal({"action": "decode_start",
                       "slots": self.dcfg.decode_slots,
                       "block_size": self.dcfg.block_size,
                       "num_blocks": self.dcfg.num_blocks,
                       "max_prompt_len": self.dcfg.max_prompt_len,
                       "max_new_tokens": self.dcfg.max_new_tokens,
                       "table_widths": self._table_widths,
                       "swap_policy": self.dcfg.swap_policy,
                       "model_step": self.model_step,
                       **self._cache_said(texts), **self._state_said(texts)})


class SlotSession:
    """One sequence in slot 0 of stores built as :class:`DecodeReplica`
    builds its own (:func:`build_stores`), admitted and stepped through
    what its loop admits and steps a sequence through
    (:func:`run_prefill`, :func:`store_prompt`, :func:`jit_step` at the
    narrowest table width that holds the position): the decode session of
    a model whose state is a sequence's (``SessionModel.decode_session``;
    benchmark/lib/cell.py has the contract the serving check drives it
    by). Every other slot idle, as in a replica with one request.

    A state that is a sequence's is advanced by a step, not rewritten:
    the session keeps what slot 0 held before its last step, and a step
    asked again at that position starts from it, so that it leaves what
    the first left."""

    def __init__(self, model, params, dcfg, cache_dtype):
        self.model, self.params, self.dcfg = model, params, dcfg
        at = jax.tree.leaves(params)[0].sharding
        self.cache, self.state = build_stores(
            model, dcfg, jnp.dtype(cache_dtype), at,
            lambda tree: jax.device_put(tree, at))
        self._widths = table_widths(self.cache.max_blocks_per_seq)
        self._prefill = jax.jit(model.decode_prefill)
        self._step = jit_step(model, dcfg)
        self._steps: dict = {}           # {table width: the executable}
        self._table = None
        self._stepped = None     # (position, slot 0's state before it)
        self._slot0 = jax.jit(lambda state, tail: (
            jnp.stack([s[:1] for s in state]),
            jnp.stack([t[:, :1] for t in tail])))
        # the same exports asked for their routing (a model that routes):
        # another program each, after the unasked one
        self._prefill_asked = jax.jit(lambda p, t, n: model.decode_prefill(
            p, t, n, return_routing=True))
        self._step_asked = jit_step(model, dcfg, return_routing=True)
        self.said = {
            "session": "slot_state",
            "attention_arm": decode_attention_arm(dcfg.attention_kernel,
                                                  self.cache.k.shape,
                                                  self.cache.v.shape),
            "cache_arrays": [list(self.cache.k.shape),
                             list(self.cache.v.shape)],
            "state_arrays": [list(a[0].shape) for a in self.state.arrays],
            "state_layers": len(self.state.state)}

    def _routes(self, return_routing: bool) -> bool:
        if return_routing and not self.model.decode_counts:
            raise NotImplementedError("nothing is routed in this model")
        return return_routing

    def prefill(self, prompt, return_routing: bool = False):
        return_routing = self._routes(return_routing)
        prompt = np.asarray(prompt, np.int32)
        n = int(prompt.size)
        row, outs = run_prefill(
            self._prefill_asked if return_routing else self._prefill, True,
            self.params, prompt,
            ServingReplica._bucket(n, self.dcfg.max_prompt_len))
        if self._table is None:
            # every block the sequence can need, as an admission does
            self._table = self.cache.alloc_sequence(
                n + self.dcfg.max_new_tokens)
            self.state.alloc(0)
        store_prompt(self.cache, self.state, 0, self._table, outs, n)
        self._stepped = None
        # [routed_layers, n, k]: the prompt's own positions of the bucket
        return (row, outs[5][:, 0, :n]) if return_routing else row

    def step(self, token: int, position: int, return_routing: bool = False):
        return_routing = self._routes(return_routing)
        if self._stepped is not None and self._stepped[0] == position:
            self.state.write(0, *self._stepped[1])
        else:
            self._stepped = (position, self._slot0(*self.state.arrays))
        slots = self.dcfg.decode_slots
        width = next(w for w in self._widths
                     if w * self.cache.block_size >= position + 1)
        vec = lambda v: jnp.zeros(  # noqa: E731
            (slots,), jnp.int32).at[0].set(v)
        tables = np.zeros((slots, width), np.int32)
        tables[0] = self._table[:width]
        inputs = (vec(token), vec(position), self.cache.k, self.cache.v,
                  jnp.asarray(tables), vec(position + 1),
                  *self.state.arrays)
        if return_routing:
            (logits, _, self.cache.k, self.cache.v, self.state.state,
             self.state.tail, picked, *_) = self._step_asked(self.params,
                                                             *inputs)
            return logits[0], picked[:, 0]
        if width not in self._steps:
            self._steps[width] = self._step.lower(self.params,
                                                  *inputs).compile()
            self.said["table_blocks"] = width
            m = self._steps[width].memory_analysis()
            if m is not None:
                self.said["step_compiled_bytes"] = int(
                    m.argument_size_in_bytes + m.output_size_in_bytes
                    + m.temp_size_in_bytes - m.alias_size_in_bytes)
        (logits, _, self.cache.k, self.cache.v, self.state.state,
         self.state.tail, *_) = self._steps[width](self.params, *inputs)
        return logits[0]
