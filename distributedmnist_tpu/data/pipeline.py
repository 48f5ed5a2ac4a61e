"""Batching pipeline: deterministic per-epoch reshuffle, host sharding,
eval padding, and (optional) native C++ prefetch.

≙ ``DataSet.next_batch`` — which reshuffles per epoch with a *time*
seed (src/mnist_data.py:55,80-84,102-130). Here the shuffle stream is
seeded (replayable) and epoch-indexed; under ``shard_mode="sharded"``
each host iterates only its slice, under ``"independent"`` each host
iterates its own full-data shuffle (the reference's faithful mode).
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from ..core.config import DataConfig
from .datasets import ArrayDataset


class BatchIterator:
    """Infinite epoch-reshuffling batch stream over an ArrayDataset.

    Yields numpy dicts {"image": [b, ...], "label": [b]} where ``b`` is
    the *host-local* batch (global batch / process_count).
    """

    def __init__(self, data: ArrayDataset, batch_size: int, seed: int,
                 host_id: int = 0, num_hosts: int = 1,
                 shard_mode: str = "sharded", drop_remainder: bool = True):
        if batch_size % num_hosts != 0:
            raise ValueError(f"global batch {batch_size} not divisible by "
                             f"{num_hosts} hosts")
        self.local_batch = batch_size // num_hosts
        # the cursor's WORLD: hosts consume in lockstep (one local batch
        # per host per global batch), so a cursor can be re-expressed
        # under a different host count — see restore()
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.global_batch = batch_size
        if shard_mode == "sharded":
            self.data = data.shard(host_id, num_hosts) if num_hosts > 1 else data
            self.seed = seed  # same shuffle stream, disjoint data
        elif shard_mode == "independent":
            self.data = data  # full copy per host, host-distinct stream
            self.seed = seed * 1_000_003 + host_id
        else:
            raise ValueError(f"unknown shard_mode {shard_mode!r}")
        if self.data.num_examples < self.local_batch:
            raise ValueError(
                f"host-local dataset ({self.data.num_examples}) smaller than "
                f"host-local batch ({self.local_batch})")
        self.drop_remainder = drop_remainder
        self._epoch = 0
        self._pos = 0
        self._order = self._epoch_order(0)

    def _epoch_order(self, epoch: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, epoch))
        return rng.permutation(self.data.num_examples)

    @property
    def epoch(self) -> int:
        return self._epoch

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        n = self.data.num_examples
        if self._pos + self.local_batch > n:
            # drop the ragged tail and reshuffle (≙ src/mnist_data.py:113-125)
            self._epoch += 1
            self._order = self._epoch_order(self._epoch)
            self._pos = 0
        idx = self._order[self._pos:self._pos + self.local_batch]
        self._pos += self.local_batch
        return {"image": self.data.images[idx], "label": self.data.labels[idx]}

    @property
    def batches_per_epoch(self) -> int:
        """Full local batches one epoch of THIS host's shard yields
        (the ragged tail is dropped, matching ``__next__``)."""
        return self.data.num_examples // self.local_batch

    @property
    def batches_consumed(self) -> int:
        """Lockstep global-batch count this cursor has advanced through
        — the world-size-independent coordinate every host of every
        world agrees on (each global batch consumes exactly one local
        batch on every host)."""
        return (self._epoch * self.batches_per_epoch
                + self._pos // self.local_batch)

    def state(self) -> dict:
        """Checkpointable position (the reference cannot resume its
        data stream; we can). Tagged with the shuffle implementation —
        an (epoch, pos) cursor only identifies a stream position within
        ONE permutation sequence — and with the WORLD it was taken
        under plus the world-independent ``batches`` coordinate, so a
        resume onto a different host count can re-derive its own
        (epoch, pos) instead of misreading a foreign shard's cursor."""
        return {"impl": "numpy", "epoch": self._epoch, "pos": self._pos,
                "batches": self.batches_consumed,
                "world": {"num_hosts": self.num_hosts,
                          "host_id": self.host_id,
                          "batch_size": self.global_batch}}

    def seek_batches(self, batches: int) -> None:
        """Position the stream exactly ``batches`` global batches in —
        the old-world→new-world cursor reassignment: ``batches`` is
        host-count-independent, so every host of the NEW world seeks to
        the same lockstep coordinate and the union of consumed sample
        slots continues gap- and overlap-free across the world change
        (see :func:`consumed_sample_ranges`)."""
        if batches < 0:
            raise ValueError(f"batches must be >= 0, got {batches}")
        bpe = self.batches_per_epoch
        self._epoch = batches // bpe
        self._order = self._epoch_order(self._epoch)
        self._pos = (batches % bpe) * self.local_batch

    def restore(self, state: dict) -> None:
        impl = state.get("impl", "numpy")
        if impl != "numpy":
            raise ValueError(
                f"data-iterator state was produced by the {impl!r} pipeline; "
                "restoring it into the numpy shuffle stream would replay a "
                "different permutation")
        world = state.get("world")
        if world is not None and (
                world.get("num_hosts") != self.num_hosts
                or world.get("host_id") != self.host_id
                or world.get("batch_size") != self.global_batch):
            # cross-world resume (elastic reconfigure, or a grown
            # worker seeded with a survivor's checkpoint): the saved
            # (epoch, pos) indexes a DIFFERENT shard's permutation —
            # reassign via the lockstep batch coordinate so no sample
            # range is dropped or double-visited
            batches = state.get("batches")
            if batches is None:
                raise ValueError(
                    f"data-iterator state from world {world} has no "
                    f"'batches' coordinate; cannot reassign it to world "
                    f"(num_hosts={self.num_hosts}, host_id={self.host_id}, "
                    f"batch_size={self.global_batch})")
            self.seek_batches(int(batches))
            return
        self._epoch = int(state["epoch"])
        self._order = self._epoch_order(self._epoch)
        self._pos = int(state["pos"])


class GradAccumFeed:
    """Feed adapter for gradient accumulation (train.grad_accum_steps):
    each ``next()`` pulls ``accum`` consecutive batches from the inner
    stream and concatenates them along dim 0 — the train step scans
    that as microbatches and applies the optimizer once.

    The inner ``BatchIterator``'s cursor math is untouched: it simply
    advances ``accum`` batches per training step, so ``state()`` /
    ``restore()`` (passed straight through) checkpoint the exact
    sample-stream position in the same lockstep ``batches`` coordinate
    the elastic-resume contract uses — a resume under a different
    ``grad_accum_steps`` (or world size) re-derives its own grouping
    from the same coordinate with no samples dropped or re-visited."""

    def __init__(self, inner, accum: int):
        if accum < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {accum}")
        self.inner = inner
        self.accum = accum
        self.has_state = callable(getattr(inner, "state", None))
        if not self.has_state:
            # shadow the pass-through methods so feed consumers that
            # probe callable(feed.state) (Trainer._save, the device
            # prefetcher) see the inner stream's true statelessness
            self.state = None      # type: ignore[assignment]
            self.restore = None    # type: ignore[assignment]

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        batches = [next(self.inner) for _ in range(self.accum)]
        if self.accum == 1:
            return batches[0]
        return {k: np.concatenate([b[k] for b in batches])
                for k in batches[0]}

    def state(self) -> dict:
        return self.inner.state()

    def restore(self, state: dict) -> None:
        self.inner.restore(state)

    def close(self) -> None:
        close = getattr(self.inner, "close", None)
        if callable(close):
            close()


def consumed_sample_ranges(state: dict) -> list[tuple[int, int]]:
    """The half-open global CONSUMPTION-SLOT index ranges a cursor
    state covers: global batch ``b`` assigns slots
    ``[b·B + h·lb, b·B + (h+1)·lb)`` to host ``h`` (``B`` = global
    batch, ``lb = B / num_hosts``). Under lockstep consumption the
    union over a world's hosts is exactly ``[0, batches·B)`` and the
    per-host ranges are disjoint — which is the old-world→new-world
    reassignment contract: after :meth:`BatchIterator.restore` onto a
    different host count, the new world's union equals the old world's
    (no slot dropped, none double-visited). The property test in
    tests/test_elastic.py pins this."""
    world = state.get("world")
    if world is None or state.get("batches") is None:
        raise ValueError("cursor state carries no world/batches "
                         "coordinates (legacy pre-elastic state)")
    B = int(world["batch_size"])
    h = int(world["host_id"])
    lb = B // int(world["num_hosts"])
    batches = int(state["batches"])
    return [(b * B + h * lb, b * B + (h + 1) * lb) for b in range(batches)]


def eval_batches(data: ArrayDataset, batch_size: int, pad_multiple: int = 1,
                 host_id: int = 0, num_hosts: int = 1) -> Iterator[dict]:
    """Fixed-order eval batches with 0/1 weights; batches are
    zero-padded to full size so shapes stay static under jit (the
    reference instead builds a graph at batch = full test-set size,
    src/nn_eval.py:121-122 — static shapes are the TPU-native answer).

    Multi-host: ``data`` is the full split on every host; each host
    yields only its strided stripe (so psum'd weights count every
    example exactly once), and the number of batches is computed from
    the *global* size so all hosts stay in lockstep.
    """
    global_n = data.num_examples
    if batch_size <= 0:
        batch_size = global_n
    if batch_size % num_hosts != 0:
        batch_size += num_hosts - batch_size % num_hosts
    local_bs = batch_size // num_hosts
    if local_bs % pad_multiple != 0:
        local_bs += pad_multiple - local_bs % pad_multiple
    stripe = data.shard(host_id, num_hosts) if num_hosts > 1 else data
    max_stripe = -(-global_n // num_hosts)  # ceil: the largest stripe
    num_batches = max(1, -(-max_stripe // local_bs))
    for b in range(num_batches):
        start = b * local_bs
        stop = min(start + local_bs, stripe.num_examples)
        take = max(stop - start, 0)
        x = stripe.images[start:start + take]
        y = stripe.labels[start:start + take]
        w = np.ones(take, np.float32)
        pad = local_bs - take
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + data.images.shape[1:],
                                            data.images.dtype)])
            y = np.concatenate([y, np.zeros((pad,) + data.labels.shape[1:],
                                            data.labels.dtype)])
            w = np.concatenate([w, np.zeros(pad, np.float32)])
        yield {"image": x, "label": y, "weight": w}


def host_can_spare_producer_thread() -> bool:
    """One shared gate for every producer-thread optimization (the
    native C++ prefetcher below, the device-side ``DevicePrefetcher``):
    a producer thread needs a SPARE core. On a 1-core host it only
    fights the consumer for the one core — measured as a net slowdown
    (see the native gate's numbers below). Turn the knobs off
    explicitly (``data.use_native_pipeline`` /
    ``data.device_prefetch``) to override in the other direction."""
    import os

    return (os.cpu_count() or 1) >= 2


def device_prefetch_pays() -> bool:
    """Gate for the DEVICE-side prefetch stage specifically (train
    loop and eval share this one policy): a spare host core, OR a real
    accelerator backend — there the consumer's device drains park the
    host GIL-free, which is exactly when a producer thread gets its
    cycles even on one core. Single-core CPU-backend hosts feed
    inline (same measurement as the gate above)."""
    import jax

    return (host_can_spare_producer_thread()
            or jax.default_backend() != "cpu")


def make_train_iterator(data: ArrayDataset, cfg: DataConfig, seed: int,
                        host_id: int = 0, num_hosts: int = 1) -> BatchIterator:
    it = BatchIterator(data, cfg.batch_size, seed=seed, host_id=host_id,
                       num_hosts=num_hosts, shard_mode=cfg.shard_mode)
    if cfg.use_native_pipeline:
        from ..core.log import get_logger
        if not host_can_spare_producer_thread():
            # a prefetch thread can only fight the consumer for the one
            # core — measured as a net slowdown by the native-loader
            # case of the CPU harness removed at PR 48 (BENCH_r04/r05.json
            # in git history) under BOTH consumer shapes: cpu-busy
            # (~0.6x) AND the train loop's real device-blocked shape AT
            # THE PRODUCTION DEPTH of prefetch_batches=2 (median 0.90x
            # over repeated quiet-box runs). BENCH_r04's earlier 1.07x for
            # this case was measured at depth=10 — re-measured at depth 10
            # it is break-even noise (0.96-1.03x across runs), and at
            # the depth this gate actually governs the native path
            # loses: the per-batch queue handoff on one core costs
            # more than the ~2 ms prep it hides. Prefetching pays off
            # when a SPARE core runs the producer.
            get_logger("data").info(
                "single-core host: skipping the prefetch thread, "
                "using inline batching")
            return it
        try:
            from .native_loader import NativePrefetcher
        except ImportError as e:
            get_logger("data").warning(
                "native pipeline unavailable (%s); using pure-python batching", e)
        else:
            return NativePrefetcher(it, depth=cfg.prefetch_batches)  # type: ignore[return-value]
    return it
