"""The program's own names in a ``jax.profiler`` trace: host spans,
device scopes, and the one way a trace is started.

One mechanism, the profiler's own, and no store beside it. (The decode
loop's ``obsv/timing.LoopClock`` reads the host's clock at the same
phase boundaries and keeps one cumulative number a phase, which the
heartbeat carries as ``loop_s``: a counter as ``decode_steps`` is, so
that a replica under no profiler still says where an iteration goes;
it stores no span.) A host span is a ``jax.profiler.TraceAnnotation``:
it lands on the thread's line of plane ``/host:CPU`` in the same
``.xplane.pb`` as the device planes, in the same file but NOT on the
same clock: the device plane lies early against the host plane by an
amount that is constant within a trace, differs between profiler
sessions, and has to be measured by the reader. In the two recorded v5e
traces beside the benchmark's tests (jax 0.9.0, libtpu 0.0.34, months
apart) every execution of ``jit_decode_step`` begins 0.437-0.701 ms
BEFORE the ``dml.serve.step.dispatch`` span that launched it opens, so
the device plane is at least 0.601 and 0.701 ms early there, and at most
2.12 and 2.77 (the step's end against the end of the fetch that waited
for it); of PR 40's seven traced chip runs the first profiler session
on each of three machines read at least 0.776-0.836 and the four later
ones at least 0.000 (at most 1.09-1.16).
``benchmark/lib/host_gaps.py`` joins spans to executions by order,
takes every listed number as a difference within one clock, and
reports that bracket (``trace_clock_offset_ms``). With it a reader can
say what the host was doing while the chip ran nothing. With no
profiler session a span costs about 1 µs
(0.5 µs without keyword facts; measured, jax 0.9.0). A device scope is
a ``jax.named_scope``: it becomes part of every HLO operation's
``op_name`` metadata and changes nothing else of the compiled program.
Kernels (``pallas_call(name=...)``) and jitted programs (a named
function in place of a ``functools.partial``) carry their names the
same way.

Every host span starts with :data:`PREFIX`, so a reader can tell the
program's spans from the runtime's (``PjitFunction(...)``,
``np.asarray(jax.Array)``). The leaves tile the loop they sit in: there
is no outer per-iteration span, because a reader that attributes an
idle gap to the span overlapping it most (``benchmark/lib/
trace_reduce._host_label``) would give every gap that straddles two
phases to the outer one. Iterations are counted from the dispatch span.
Spans of one request share its ``id``; a count rides on the span at
whose boundary it is true.

Readers: ``benchmark/lib/program_trace.py`` (whose ``__main__`` prints
the span table and the scope table of any trace directory),
``benchmark/lib/host_gaps.py`` (whose ``__main__`` prints each gap
between two decode steps split by what the host was doing) and the
per-layer metrics named beside each span below (each a reader under
``benchmark/layer_metrics/``; ``BENCHMARK.json`` lists PR 23's, and PR
40's wait as files that ``host_gaps.py`` prints); PERF.md §3 has the
same list from the metrics' side.
"""

from __future__ import annotations

import jax

PREFIX = "dml."

# -- the decode replica's batcher thread (servesvc/decode.py) --------------
#: the park on the admission queue while no slot is live and nothing
#: waits (`_admit_new`); read by: serve_idle_no_request_share,
#: serve_idle_unattributed_share (an idle replica's gaps are the
#: queue's, not the loop's)
SERVE_IDLE = "dml.serve.idle"
#: queue drain and slot assignment in `_admit_new`, less the prefill it
#: calls; read by: decode_gap_rest_ms (it is not one of the gap's named
#: parts), the span table, serve_idle_unattributed_share
SERVE_ADMIT = "dml.serve.admit"
#: `_maybe_swap`, only when a publish was staged; read by: the span table
#: (a swap is the one stall of the loop that is not a request's)
SERVE_SWAP = "dml.serve.swap"
#: all of `_prefill` for one request (id, prompt_len, bucket, queue_ms);
#: parent of the next two; read by: the span table (prefill_ms_p50 reads
#: the journal's copy of its duration)
SERVE_PREFILL = "dml.serve.prefill"
#: the padded prompt's upload and the prefill program's dispatch
SERVE_PREFILL_FORWARD = "dml.serve.prefill.forward"
#: the scatter of the prompt's K/V into the paged cache
#: (`jit_write_prompt_kv`; prefill_cache_write_share_of_busy reads the
#: program, this span says where the host waits for it)
SERVE_PREFILL_CACHE_WRITE = "dml.serve.prefill.cache_write"
#: twice an iteration since PR 44. Before the dispatch (`_step_inputs`):
#: taking the inputs made ahead and, where a slot drew its token, the
#: tokens' one upload; or, after a finish, an admission, a restart or a
#: swap, all of them built from the books (three uploads and
#: `_tables_for`); this one lies between two steps and is what
#: decode_gap_inputs_ms reads. After the dispatch and the flush
#: (`_inputs_ahead`): the next step's positions, lengths and tables,
#: uploaded while the chip runs this one; under a step, in no gap
SERVE_STEP_INPUTS = "dml.serve.step.inputs"
#: the call of the jitted decode step (live, waiting, version, blocks:
#: the width of the block table it is handed): one per iteration and
#: params version, so it counts iterations; read by:
#: decode_slots_live_p50 (`live`), decode_table_blocks_p50 (`blocks`),
#: decode_sample_ms_per_iter and decode_stream_ms_per_iter (the count);
#: the k-th one launched the k-th execution of `jit_decode_step`
#: (host_gaps.join): decode_gap_beneath_ms, trace_clock_offset_ms
SERVE_STEP_DISPATCH = "dml.serve.step.dispatch"
#: the blocking fetch of the step's [slots] greedy tokens: the wait for
#: the step on the device; read by: decode_gap_beneath_ms (a gap's host
#: part runs from its end to the next dispatch's start),
#: trace_clock_offset_ms, decode_loop_host_share (a trace's fallback)
SERVE_STEP_FETCH = "dml.serve.step.fetch"
#: once an iteration (device, host), after the fetch: the pick-up of
#: every slot's token, `_sample`'s draws for `temperature > 0` included,
#: and the books kept on it (appended, its line queued, a finish decided,
#: its slot, blocks and state given back); nothing is written inside it.
#: And once a prefill (id, slot): `_sample` on its last row, which waits
#: for the prefill on the device; read by: decode_sample_ms_per_iter,
#: serve_idle_sample_share, decode_gap_emit_ms
SERVE_SAMPLE = "dml.serve.sample"
#: `_stream` (id): one JSON line and one `sendall`. A prefill's first
#: token inside the prefill; every other line in `_flush`, after the NEXT
#: step's dispatch returned (or before a prefill, a swap, a park), so
#: between a dispatch and its fetch and in no gap between two plain
#: steps; read by: decode_stream_ms_per_iter, decode_gap_emit_ms
SERVE_STREAM = "dml.serve.stream"
#: a finish's writes in `_flush` (id, reason): journal record, dedup
#: entry, terminal line (the finish was decided, and its blocks freed,
#: under the sample span of the fetch that brought its last token);
#: read by: decode_gap_emit_ms
SERVE_FINISH = "dml.serve.finish"
#: the heartbeat's write, only when one is written (a request ended
#: since the last): in a model that routes it fetches the last step's
#: pair counts from the device; read by: decode_gap_rest_ms
SERVE_HEARTBEAT = "dml.serve.heartbeat"

# -- the trainer's loop thread (train/loop.py) ------------------------------
#: `next(feed)` from the prefetcher, or the inline `device_put_batch`
TRAIN_FEED = "dml.train.feed"
#: the call of the train step (step); one per step
TRAIN_DISPATCH = "dml.train.dispatch"
#: the device probe's drain poll, only when the probe is on
TRAIN_PROBE = "dml.train.probe"
#: the log-window flush, which fetches the window's losses
TRAIN_FLUSH = "dml.train.flush"
#: `_save`: snapshot, write or hand-off to the async writer
TRAIN_SAVE = "dml.train.save"
# -- the prefetcher's producer thread (data/device_prefetch.py) -------------
#: `next()` of the host iterator: one global batch assembled
PREFETCH_ASSEMBLE = "dml.prefetch.assemble"
#: the staged batch's host-to-device copy
PREFETCH_PUT = "dml.prefetch.put"
# The train spans have no per-layer reader yet (PERF.md §7: the train
# cells' exact metric sets are pinned by a test of the benchmark); they
# are read with `python3 benchmark/lib/program_trace.py <dir>`, which is
# how PERF.md §5's train tables are made.

#: device scopes (``jax.named_scope``), by where they are opened:
#: models/transformer.py (cast: the stored weights to the compute dtype;
#: embed, attention, ffn, head; in the decode step cache_write and
#: cache_gather inside attention; loss), servesvc/kv_cache.py
#: (cache_write), parallel/api.py and ops/masked_psum.py (aggregate,
#: update, timing). Opened where the block is made, inside attention and
#: ffn: residual_mix (a stream residual's maps, Sinkhorn iteration, read
#: and write) and moe (router, sort, grouped product, combine, shared
#: expert); mtp is the next-next-token module, whose layer's scopes nest
#: in it. Read by: latent_attention_ms_per_step, moe_ms_per_step,
#: residual_mix_ms_per_step, mtp_ms_per_step. In a latent block's decode
#: step, inside attention beside cache_write and cache_gather:
#: latent_absorb (the query through W_uk, the weighted latents through
#: W_uv), and moe inside ffn as in training. Read by:
#: decode_absorb_ms_per_step, decode_moe_ms_per_step (and attention
#: whole by decode_attention_ms_per_step). A state-space layer
#: (ops/ssm.py) opens ssm around its mixer whole, in the place of
#: attention (read by: decode_ssm_ms_per_step), and inside it ssm_conv (the
#: causal convolution), ssm_scan (the recurrence over a prompt, in
#: jit_decode_prefill and a train step; read by: prefill_scan_ms_p50) and
#: state_update (one token a slot, in jit_decode_step; read by:
#: decode_state_update_ms_per_step, decode_state_update_roofline).
#: state_write is servesvc/kv_cache.py's program that puts a prefill's
#: end state into a slot (jit_write_slot_state; no reader: 9 MB a
#: prefill). The benchmark reads these through benchmark/lib/
#: ssm_scopes.py. A delta-rule layer (ops/kda.py) opens, in the same
#: place, kda around its mixer whole (read by: decode_kda_ms_per_step)
#: and inside it kda_conv (the three convolutions), kda_gate (the
#: log-decay and beta), kda_chunk (the chunked recurrence over a prompt,
#: in jit_decode_prefill; read by: prefill_kda_chunk_ms_p50) and
#: kda_state (one token a slot, the matrix state read and written, in
#: jit_decode_step: on a TPU one Mosaic call a layer, kda_state_step, the
#: kernel of ops/kda.py::state_step that holds a head's matrix in VMEM
#: across the update, and the small fusion that makes e^g beside it; off
#: the TPU, or for a state Mosaic does not take, ops/kda.py::step's
#: fusions, two reads and a write; read by: decode_kda_state_ms_per_step,
#: decode_kda_state_roofline), through benchmark/lib/kda_scopes.py
SCOPES = ("cast", "embed", "attention", "cache_write", "cache_gather",
          "ffn", "head", "loss", "aggregate", "update", "timing",
          "residual_mix", "moe", "mtp", "latent_absorb",
          "ssm", "ssm_conv", "ssm_scan", "state_update", "state_write",
          "kda", "kda_conv", "kda_gate", "kda_chunk", "kda_state")

#: a host span: ``with span(SERVE_STREAM, id=...):``
span = jax.profiler.TraceAnnotation


def start_profile(log_dir) -> None:
    """Start a profiler session as the benchmark's traced runs do
    (``benchmark/lib/runtime.py``): the Python tracer off. It hooks
    every call and return of the loop it is meant to measure, so with
    it on (the profiler's default) a trace measures the tracer."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)


def stop_profile() -> None:
    jax.profiler.stop_trace()
