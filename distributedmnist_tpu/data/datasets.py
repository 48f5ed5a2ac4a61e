"""Dataset loading — MNIST/Fashion-MNIST idx files, CIFAR-10, and a
deterministic synthetic fallback.

Capability parity with src/mnist_data.py, redesigned:

* idx.gz parsing and [-0.5, 0.5] normalization match the reference
  (src/mnist_data.py:132-155; normalization at :142).
* The reference accepts ``worker_id``/``n_workers`` but ignores them —
  every worker shuffles the full 60k with a time seed
  (src/mnist_data.py:55,80-84,156-163,212-213). Here sharding is real:
  ``shard_mode="sharded"`` gives each host a deterministic slice;
  ``shard_mode="independent"`` reproduces the reference's
  full-copy-per-worker behavior (with a *seeded* shuffle, not a time
  seed).
* The reference aliases validation := the 10k test set
  (src/mnist_data.py:200-201) — a documented quirk we do not copy:
  validation is carved from the train split.
* The latent fake-data fixture (src/mnist_data.py:46,60-62,164-172) is
  promoted to a first-class deterministic *learnable* synthetic dataset
  — also the default in egress-free environments where the idx files
  cannot be downloaded.
"""

from __future__ import annotations

import dataclasses
import gzip
import os
import pickle
import struct
from pathlib import Path

import numpy as np

from ..core.config import DataConfig

PIXEL_DEPTH = 255  # ≙ src/mnist.py:31


@dataclasses.dataclass(frozen=True)
class ArrayDataset:
    """An in-memory split. For image tasks: images [N,H,W,C] float32 in
    [-0.5, 0.5], labels [N] int32. For LM tasks: images [N,S] int32
    token sequences, labels [N,S] (the same tokens — the loss shifts
    internally for next-token prediction)."""

    images: np.ndarray
    labels: np.ndarray

    def __post_init__(self):
        assert self.images.ndim in (2, 4), self.images.shape
        assert self.labels.ndim in (1, 2), self.labels.shape
        assert len(self.images) == len(self.labels)

    @property
    def num_examples(self) -> int:
        return len(self.labels)

    def take(self, idx: np.ndarray) -> "ArrayDataset":
        return ArrayDataset(self.images[idx], self.labels[idx])

    def shard(self, shard_id: int, num_shards: int) -> "ArrayDataset":
        """Deterministic contiguous-strided shard (fixes the reference's
        no-op sharding, src/mnist_data.py:156-163)."""
        sel = np.arange(shard_id, self.num_examples, num_shards)
        return self.take(sel)


@dataclasses.dataclass(frozen=True)
class Datasets:
    """≙ the reference's ``Datasets(train, validation, test)`` result
    (src/mnist_data.py:212-213)."""

    train: ArrayDataset
    validation: ArrayDataset
    test: ArrayDataset


# --------------------------------------------------------------------------
# idx format (MNIST / Fashion-MNIST)
# --------------------------------------------------------------------------

def _open_maybe_gz(path: Path):
    if path.suffix == ".gz":
        return gzip.open(path, "rb")
    return open(path, "rb")


def _read_idx_ubyte(path: Path, expect_ndim: int) -> np.ndarray:
    """Raw idx(.gz) ubyte payload.

    The numpy path is the DEFAULT decode. Repeated decode runs of the
    native-loader case of the harness removed at PR 48 (BENCH_r04/r05.json
    in git history) on the 60k-image idx3.gz put the two readers within
    run-to-run noise of each other (native 130-157 MB/s vs numpy
    136-151 — both zlib-inflate-bound); numpy avoids the extra ctypes
    boundary copy (native_loader.read_idx's .copy()) and any dependence
    on the C++ build, so it wins the default. The native reader stays
    available for the C-ABI round-trip tests and any caller that wants
    decode off the Python heap."""
    with _open_maybe_gz(path) as f:
        magic = struct.unpack(">HBB", f.read(4))
        if magic[0] != 0 or magic[1] != 0x08:
            raise ValueError(f"{path}: bad idx magic {magic}")
        dims = struct.unpack(f">{magic[2]}I", f.read(4 * magic[2]))
        buf = f.read(int(np.prod(dims)))
    arr = np.frombuffer(buf, dtype=np.uint8).reshape(dims)
    if arr.ndim != expect_ndim:
        raise ValueError(f"{path}: expected {expect_ndim}-d idx, got {arr.ndim}-d")
    return arr


def read_idx_images(path: Path) -> np.ndarray:
    """Parse an idx3-ubyte image file → float32 [N,H,W,1] in [-0.5,0.5]
    (≙ extract_data, src/mnist_data.py:132-146)."""
    data = _read_idx_ubyte(path, 3).astype(np.float32)
    data = (data - PIXEL_DEPTH / 2.0) / PIXEL_DEPTH  # :142 parity
    return data[..., np.newaxis]


def read_idx_labels(path: Path) -> np.ndarray:
    """Parse an idx1-ubyte label file (≙ extract_labels,
    src/mnist_data.py:147-155)."""
    return _read_idx_ubyte(path, 1).astype(np.int32)


def write_idx_ubyte(path: Path, arr: np.ndarray) -> Path:
    """Write a uint8 array as an idx(.gz) file — the exact inverse of
    ``_read_idx_ubyte``. Used by tests (round-trip fixtures) and as a
    dataset snapshot tool; gzip when the suffix is ``.gz``."""
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    header = struct.pack(">HBB", 0, 0x08, arr.ndim)
    header += struct.pack(f">{arr.ndim}I", *arr.shape)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "wb") as f:
        f.write(header)
        f.write(arr.tobytes())
    return path


_IDX_FILES = {
    "train_images": ["train-images-idx3-ubyte", "train-images.idx3-ubyte"],
    "train_labels": ["train-labels-idx1-ubyte", "train-labels.idx1-ubyte"],
    "test_images": ["t10k-images-idx3-ubyte", "t10k-images.idx3-ubyte"],
    "test_labels": ["t10k-labels-idx1-ubyte", "t10k-labels.idx1-ubyte"],
}

# Public mirrors for the canonical idx archives. Fashion-MNIST ships
# the same four file names.
_IDX_MIRRORS = {
    "mnist": [
        "https://storage.googleapis.com/cvdf-datasets/mnist/",
        "https://ossci-datasets.s3.amazonaws.com/mnist/",
    ],
    "fashion_mnist": [
        "http://fashion-mnist.s3-website.eu-central-1.amazonaws.com/",
    ],
}

# Pinned sha256 digests of the canonical MNIST .gz archives (as
# published across OSS dataset tooling) — passed by default so the
# default download path rejects a well-formed substitute served by a
# hostile mirror, not just a corrupt one. A mismatch is handled like
# any fetch failure: the file is discarded and the next mirror (or the
# synthetic fallback) takes over, so a stale pin can never hard-break
# ingest. Fashion-MNIST publishes md5s, not sha256s, in its README —
# no offline-verifiable sha256 exists here, so it stays unpinned
# (structural idx validation still applies).
_PINNED_SHA256 = {
    "mnist": {
        "train-images-idx3-ubyte.gz":
            "440fcabf73cc546fa21475e81ea370265605f56be210a4024d2ca8f203523609",
        "train-labels-idx1-ubyte.gz":
            "3552534a0a558bbed6aed32b30c495cca23d567ec52cac8be1a0730e8010255c",
        "t10k-images-idx3-ubyte.gz":
            "8d422c7b0a1c1c79245a5bcf07fe86e33eeafee792b84584aec276f5a2dbc4e6",
        "t10k-labels-idx1-ubyte.gz":
            "f7ae60f92e00ec6debd23a6088c31dbd2371eca3ffa0defaefb259924204aec6",
    },
}


def maybe_download(data_dir: str | Path, dataset: str = "mnist",
                   timeout: float = 30.0,
                   expected_sha256: dict[str, str] | None = None) -> bool:
    """Fetch any missing idx.gz files into ``data_dir`` with caching
    (≙ maybe_download, src/mnist_data.py:176-187 — which pulled from
    the Yann LeCun host; mirrors here because that host now throttles).

    Returns True when all four files are present afterwards. Network
    failure is not an error — the caller falls back to synthetic data —
    but a file that downloads with a corrupt idx payload is deleted and
    reported so a truncated fetch can't poison the cache. Pass
    ``expected_sha256`` ({file name → hex digest}) to pin archives
    cryptographically — the structural idx validation alone cannot
    reject a well-formed substitute served by a hostile network. When
    omitted, the per-dataset ``_PINNED_SHA256`` pins apply by default;
    pass ``{}`` explicitly to disable pinning.

    Concurrency-safe for shared data dirs (e.g. every process of a
    multi-host launch downloading at once): each writer stages to a
    pid-unique temp file and installs with an atomic rename.
    """
    from ..core.log import get_logger
    logger = get_logger("data")
    root = Path(data_dir)
    mirrors = _IDX_MIRRORS.get(dataset)
    if mirrors is None:
        return False
    if expected_sha256 is None:
        expected_sha256 = _PINNED_SHA256.get(dataset, {})
    root.mkdir(parents=True, exist_ok=True)
    ok = True
    for key, names in _IDX_FILES.items():
        if _find_idx(root, names) is not None:
            continue  # cached
        fname = names[0] + ".gz"
        fetched = False
        for base in mirrors:
            url = base + fname
            # gz suffix kept so the validator opens the staged file
            # through gzip; pid-unique stem avoids cross-process races
            tmp = root / f".{os.getpid()}.part.{fname}"
            final = root / fname
            try:
                import urllib.request
                with urllib.request.urlopen(url, timeout=timeout) as r, \
                        open(tmp, "wb") as f:
                    f.write(r.read())
                if expected_sha256 and fname in expected_sha256:
                    import hashlib
                    got = hashlib.sha256(tmp.read_bytes()).hexdigest()
                    if got != expected_sha256[fname]:
                        raise ValueError(
                            f"sha256 mismatch for {fname}: {got}")
                # full structural parse → truncated/corrupt payloads out
                _read_idx_ubyte(tmp, 3 if "images" in key else 1)
                tmp.rename(final)  # atomic install
            except Exception as e:  # no egress / mirror down / corrupt
                tmp.unlink(missing_ok=True)
                logger.warning("could not fetch %s: %s", url, e)
                continue
            logger.info("downloaded %s from %s", fname, base)
            fetched = True
            break
        # another process may have installed it while we failed
        ok &= fetched or _find_idx(root, names) is not None
    return ok


def _find_idx(root: Path, names: list[str]) -> Path | None:
    for name in names:
        for cand in (root / name, root / (name + ".gz")):
            if cand.exists():
                return cand
    return None


def load_idx_dataset(data_dir: str | Path, validation_size: int = 5000) -> Datasets:
    """Load MNIST-format idx files from ``data_dir`` (works for MNIST
    and Fashion-MNIST, which share the format)."""
    root = Path(data_dir)
    paths = {k: _find_idx(root, v) for k, v in _IDX_FILES.items()}
    missing = [k for k, v in paths.items() if v is None]
    if missing:
        raise FileNotFoundError(
            f"idx files missing under {root}: {missing} "
            f"(no network egress — place files there or use dataset='synthetic')")
    train_x = read_idx_images(paths["train_images"])
    train_y = read_idx_labels(paths["train_labels"])
    test_x = read_idx_images(paths["test_images"])
    test_y = read_idx_labels(paths["test_labels"])
    v = min(validation_size, len(train_y) // 10)
    return Datasets(
        train=ArrayDataset(train_x[v:], train_y[v:]),
        validation=ArrayDataset(train_x[:v], train_y[:v]),
        test=ArrayDataset(test_x, test_y),
    )


# --------------------------------------------------------------------------
# CIFAR-10 (python pickle batches) — the v4-32 stress config's payload
# (BASELINE.json configs[4])
# --------------------------------------------------------------------------

def load_cifar10(data_dir: str | Path, validation_size: int = 5000) -> Datasets:
    root = Path(data_dir)
    batch_dir = root / "cifar-10-batches-py"
    if not batch_dir.exists():
        batch_dir = root
    train_files = sorted(batch_dir.glob("data_batch_*"))
    test_file = batch_dir / "test_batch"
    if not train_files or not test_file.exists():
        raise FileNotFoundError(
            f"CIFAR-10 pickle batches not found under {root} "
            f"(use dataset='synthetic' when no data is on disk)")

    def load_batch(path: Path):
        with open(path, "rb") as f:
            d = pickle.load(f, encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1).astype(np.float32)
        x = (x - PIXEL_DEPTH / 2.0) / PIXEL_DEPTH
        y = np.asarray(d[b"labels"], dtype=np.int32)
        return x, y

    xs, ys = zip(*(load_batch(p) for p in train_files))
    train_x, train_y = np.concatenate(xs), np.concatenate(ys)
    test_x, test_y = load_batch(test_file)
    v = min(validation_size, len(train_y) // 10)
    return Datasets(
        train=ArrayDataset(train_x[v:], train_y[v:]),
        validation=ArrayDataset(train_x[:v], train_y[:v]),
        test=ArrayDataset(test_x, test_y),
    )


# --------------------------------------------------------------------------
# Deterministic learnable synthetic data
# --------------------------------------------------------------------------

def make_synthetic(num_train: int, num_test: int, image_size: int = 28,
                   num_channels: int = 1, num_classes: int = 10,
                   seed: int = 12345, noise: float = 0.08) -> Datasets:
    """Class-conditional smooth templates + Gaussian noise: separable
    (a CNN reaches ≈100% — making it a usable convergence oracle, ≙ the
    evaluator's role in SURVEY §4) yet non-trivial, and fully
    deterministic given ``seed``."""
    rng = np.random.default_rng(seed)
    low = max(4, image_size // 4)
    templates = rng.standard_normal((num_classes, low, low, num_channels)).astype(np.float32)
    # bilinear-upsample templates to full resolution → smooth class shapes
    up = np.empty((num_classes, image_size, image_size, num_channels), np.float32)
    xs = np.linspace(0, low - 1, image_size)
    x0 = np.clip(np.floor(xs).astype(int), 0, low - 2)
    fx = (xs - x0).astype(np.float32)
    for c in range(num_classes):
        t = templates[c]
        rows = (t[x0] * (1 - fx)[:, None, None] + t[x0 + 1] * fx[:, None, None])
        up[c] = (rows[:, x0] * (1 - fx)[None, :, None]
                 + rows[:, x0 + 1] * fx[None, :, None])
    up = up / (np.abs(up).max() + 1e-6) * 0.45  # keep within [-0.5, 0.5]

    def sample(n: int) -> ArrayDataset:
        labels = rng.integers(0, num_classes, size=n).astype(np.int32)
        images = up[labels] + rng.standard_normal(
            (n, image_size, image_size, num_channels)).astype(np.float32) * noise
        images = np.clip(images, -0.5, 0.5)
        return ArrayDataset(images, labels)

    return Datasets(train=sample(num_train),
                    validation=sample(max(num_test // 2, 256)),
                    test=sample(num_test))


def make_synthetic_lm(num_train: int, num_test: int, seq_len: int = 128,
                      vocab_size: int = 256, seed: int = 12345,
                      peak: float = 3.0) -> Datasets:
    """Deterministic learnable token sequences for the long-context
    (transformer) family: a fixed random first-order Markov chain with
    peaked transitions. A causal LM that learns the transition table
    drives next-token loss well below the unigram entropy — the
    convergence oracle for the sequence path."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((vocab_size, vocab_size)) * peak
    probs = np.exp(logits - logits.max(axis=1, keepdims=True))
    probs /= probs.sum(axis=1, keepdims=True)
    cdf = np.cumsum(probs, axis=1)

    def sample(n: int) -> ArrayDataset:
        seqs = np.empty((n, seq_len), np.int32)
        seqs[:, 0] = rng.integers(0, vocab_size, n)
        for t in range(1, seq_len):
            u = rng.random(n)[:, None]
            seqs[:, t] = (cdf[seqs[:, t - 1]] < u).sum(axis=1)
        return ArrayDataset(seqs, seqs.copy())

    return Datasets(train=sample(num_train),
                    validation=sample(max(num_test // 2, 64)),
                    test=sample(num_test))


# --------------------------------------------------------------------------
# registry entry point
# --------------------------------------------------------------------------

def load_datasets(cfg: DataConfig, image_size: int = 28, num_channels: int = 1,
                  num_classes: int = 10, seq_len: int = 128,
                  vocab_size: int = 256) -> Datasets:
    """≙ load_mnist (src/mnist_data.py:212-213), generalized. Falls
    back to synthetic data when real files are absent (logged, never
    silent)."""
    from ..core.log import get_logger
    logger = get_logger("data")
    name = cfg.dataset
    try:
        if name in ("mnist", "fashion_mnist"):
            # hand-placed flat files still load; downloads always land
            # in a per-dataset subdir (mnist and fashion_mnist share
            # file names — a flat cache would silently cross-serve)
            sub = Path(cfg.data_dir) / name
            root = sub if sub.exists() else Path(cfg.data_dir)
            if (cfg.download
                    and any(_find_idx(root, v) is None
                            for v in _IDX_FILES.values())):
                maybe_download(sub, name)
                root = sub
            return load_idx_dataset(root)
        if name == "cifar10":
            return load_cifar10(cfg.data_dir)
        if name == "synthetic":
            return make_synthetic(cfg.synthetic_train_size, cfg.synthetic_test_size,
                                  image_size, num_channels, num_classes)
        if name == "synthetic_lm":
            return make_synthetic_lm(cfg.synthetic_train_size,
                                     cfg.synthetic_test_size,
                                     seq_len, vocab_size)
        raise ValueError(f"unknown dataset {name!r}")
    except FileNotFoundError as e:
        logger.warning("%s — falling back to synthetic data", e)
        return make_synthetic(cfg.synthetic_train_size, cfg.synthetic_test_size,
                              image_size, num_channels, num_classes)
