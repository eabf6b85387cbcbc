"""Dataset builders for the five reference workloads (SURVEY.md §1, [B:6–12]).

Each builder returns train/eval ``ArrayDataset``s.  Real on-disk formats are
read when a data directory is provided (MNIST idx files, CIFAR-10 python
pickles — the formats the reference's torchvision loaders consume); otherwise
deterministic synthetic data with the same shapes/dtypes is generated, so
every config runs end-to-end in the zero-egress sandbox and in CI.

Data may live under ``gs://`` paths (read via tpuframe.data.gcs), matching
the reference's GCS-bucket input pipeline [B:5].
"""

from __future__ import annotations

import gzip
import io
import pickle
import struct
import zlib
from dataclasses import dataclass

import numpy as np

from tpuframe.data import gcs


@dataclass
class ArrayDataset:
    """In-memory columnar dataset: dict of equal-length arrays.

    ``host_presharded`` (instance attribute, default False): set by builders
    whose on-disk layout is already one shard per host, so ShardedLoader
    skips its own host split."""

    columns: dict[str, np.ndarray]
    host_presharded: bool = False

    def __post_init__(self):
        lens = {k: len(v) for k, v in self.columns.items()}
        if len(set(lens.values())) > 1:
            raise ValueError(f"ragged columns: {lens}")

    def __len__(self) -> int:
        return len(next(iter(self.columns.values())))

    def __getitem__(self, idx) -> dict[str, np.ndarray]:
        if (isinstance(idx, np.ndarray) and idx.ndim == 1
                and idx.dtype != np.bool_):
            # (bool masks stay on the numpy fancy-indexing path below — the
            # native gather casts indices to int64 and would silently read
            # rows 0/1 instead of selecting masked rows.)
            # Batch assembly: multi-threaded native gather (tpuframe.native)
            # — the loader's per-step host work, off the GIL.
            from tpuframe import native

            return {k: native.gather_rows(v, idx)
                    for k, v in self.columns.items()}
        return {k: v[idx] for k, v in self.columns.items()}

    def shard(self, num_shards: int, index: int) -> "ArrayDataset":
        """Contiguous per-host shard (the reference's DistributedSampler
        ``num_replicas/rank`` split, SURVEY.md §3a)."""
        if not (0 <= index < num_shards):
            raise ValueError(f"shard index {index} out of range {num_shards}")
        n = len(self) // num_shards  # drop remainder: equal shards, SPMD-safe
        lo = index * n
        return ArrayDataset({k: v[lo:lo + n] for k, v in self.columns.items()})


# ---------------------------------------------------------------------------
# MNIST — config 1 [B:7]
# ---------------------------------------------------------------------------

def _read_idx(data: bytes) -> np.ndarray:
    magic, = struct.unpack(">I", data[:4])
    ndim = magic & 0xFF
    dims = struct.unpack(f">{ndim}I", data[4:4 + 4 * ndim])
    return np.frombuffer(data, np.uint8, offset=4 + 4 * ndim).reshape(dims)


def _maybe_gunzip(raw: bytes) -> bytes:
    return gzip.decompress(raw) if raw[:2] == b"\x1f\x8b" else raw


def mnist(data_dir: str | None = None, *, synthetic_size: int = 2048):
    """[B, 28, 28, 1] float32 in [0,1), int32 labels."""
    if data_dir is not None:
        def load(img_name, lbl_name):
            imgs = _read_idx(_maybe_gunzip(gcs.read_bytes(gcs.join(data_dir, img_name))))
            lbls = _read_idx(_maybe_gunzip(gcs.read_bytes(gcs.join(data_dir, lbl_name))))
            x = (imgs.astype(np.float32) / 255.0)[..., None]
            return ArrayDataset({"image": x, "label": lbls.astype(np.int32)})

        train = load("train-images-idx3-ubyte.gz", "train-labels-idx1-ubyte.gz")
        test = load("t10k-images-idx3-ubyte.gz", "t10k-labels-idx1-ubyte.gz")
        return train, test
    return (_synthetic_images(synthetic_size, (28, 28, 1), 10, seed=0),
            _synthetic_images(max(synthetic_size // 8, 64), (28, 28, 1), 10,
                              seed=1, template_seed=0))


# ---------------------------------------------------------------------------
# CIFAR-10 — config 2 [B:8]
# ---------------------------------------------------------------------------

CIFAR_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def cifar10(data_dir: str | None = None, *, synthetic_size: int = 2048,
            keep_u8: bool = False):
    """[B, 32, 32, 3] float32 normalized (or uint8 raw with ``keep_u8`` —
    see :func:`imagenet`; the pickles are uint8 natively), int32 labels.
    Reads the python pickle batches of the standard
    ``cifar-10-batches-py`` layout."""
    if data_dir is not None:
        def load(names):
            xs, ys = [], []
            for name in names:
                d = pickle.loads(gcs.read_bytes(gcs.join(data_dir, name)),
                                 encoding="bytes")
                xs.append(np.asarray(d[b"data"], np.uint8))
                ys.append(np.asarray(d[b"labels"], np.int64))
            x = np.concatenate(xs).reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
            if not keep_u8:
                x = (x.astype(np.float32) / 255.0 - CIFAR_MEAN) / CIFAR_STD
            return ArrayDataset({"image": np.ascontiguousarray(x),
                                 "label": np.concatenate(ys).astype(np.int32)})

        train = load([f"data_batch_{i}" for i in range(1, 6)])
        test = load(["test_batch"])
        return train, test
    train, test = (
        _synthetic_images(synthetic_size, (32, 32, 3), 10, seed=2),
        _synthetic_images(max(synthetic_size // 8, 64), (32, 32, 3), 10,
                          seed=3, template_seed=2))
    if keep_u8:
        for ds in (train, test):
            ds.columns["image"] = np.round(
                ds.columns["image"] * 255.0).astype(np.uint8)
    return train, test


# ---------------------------------------------------------------------------
# ImageNet — configs 3 & 5 [B:9][B:11]
# ---------------------------------------------------------------------------

def imagenet(data_dir: str | None = None, *, image_size: int = 224,
             synthetic_size: int = 512, keep_u8: bool = False,
             num_classes: int = 1000):
    """[B, S, S, 3] float32 (or uint8), int32 labels in [0, num_classes)
    (synthetic; real shards carry the full 1000-class labels).

    Real ImageNet arrives as per-host ``.npy`` shards (images_XXXXX.npy /
    labels_XXXXX.npy) prepared by ``tpuframe.data.prepare_imagenet`` —
    decoding JPEGs on the training hosts would bottleneck the input pipeline
    (SURVEY.md §7 hard part 2), so decode/resize happens offline.

    ``keep_u8``: keep images uint8 end-to-end on the host — 4x less host
    RAM than the f32 default (real ImageNet: ~150 GB vs ~600 GB per host
    group) and 1 byte/px over the host→device link (vs 2 for the bf16
    infeed cast); the harness normalizes ON DEVICE (train._maybe_normalize
    — XLA-fused on TPU, the native FFI kernel on CPU hosts).  Synthetic
    mode quantizes its f32 images to the same u8 representation.
    """
    if data_dir is not None:
        import jax

        names = sorted(n for n in gcs.listdir(data_dir)
                       if n.startswith("images_"))
        # Each host loads only its slice of the file list — the shard files
        # ARE the host shards; loading everything everywhere would cost
        # O(hosts x dataset) reads and OOM a TPU-VM host on real ImageNet.
        n_proc, proc = jax.process_count(), jax.process_index()
        if n_proc > 1:
            if len(names) % n_proc:
                raise ValueError(
                    f"{len(names)} imagenet shard files not divisible by "
                    f"{n_proc} hosts — re-shard with prepare_imagenet")
            names = names[proc::n_proc]
        xs = [np.load(io.BytesIO(gcs.read_bytes(gcs.join(data_dir, n))))
              for n in names]
        ys = [np.load(io.BytesIO(gcs.read_bytes(gcs.join(data_dir, n.replace("images_", "labels_")))))
              for n in names]
        x = np.concatenate(xs)
        y = np.concatenate(ys).astype(np.int32)
        if x.dtype == np.uint8 and not keep_u8:
            # prepare_imagenet stores uint8 (4x less IO); normalize here.
            x = ((x.astype(np.float32) / 255.0) - IMAGENET_MEAN) / IMAGENET_STD
        split = int(0.99 * len(x))
        train = ArrayDataset({"image": x[:split], "label": y[:split]})
        test = ArrayDataset({"image": x[split:], "label": y[split:]})
        # Tell ShardedLoader the per-host split already happened.
        train.host_presharded = n_proc > 1
        test.host_presharded = n_proc > 1
        return train, test
    # ``num_classes`` (synthetic only): scaled-down smoke configs shrink
    # the model head — the label range must shrink with it (the harness
    # rejects out-of-range labels at build time).
    train, test = (
        _synthetic_images(synthetic_size, (image_size, image_size, 3),
                          num_classes, seed=4),
        _synthetic_images(max(synthetic_size // 8, 64),
                          (image_size, image_size, 3), num_classes,
                          seed=5, template_seed=4))
    if keep_u8:
        for ds in (train, test):
            ds.columns["image"] = np.round(
                ds.columns["image"] * 255.0).astype(np.uint8)
    return train, test


# ---------------------------------------------------------------------------
# GLUE (SST-2) — config 4 [B:10]
# ---------------------------------------------------------------------------

def glue_sst2(data_dir: str | None = None, *, seq_len: int = 128,
              vocab_size: int = 30522, synthetic_size: int = 1024,
              tokenizer=None, vocab_file: str | None = None):
    """Tokenized sentence-classification batches: input_ids / attention_mask /
    token_type_ids int32 [B, S], label int32.

    With ``data_dir``: reads GLUE's SST-2 tsv files.  Tokenization, in
    preference order: a caller-supplied tokenizer (HF-compatible callable);
    the built-in WordPiece tokenizer (tpuframe.data.wordpiece) when
    ``vocab_file`` is given or ``<data_dir>/vocab.txt`` exists — the real
    SST-2 accuracy path, no HF needed; else a hash-based fallback (vocab-free,
    fine for allreduce-stress benchmarking only).
    """
    if data_dir is not None:
        tokenizer = _resolve_tokenizer(tokenizer, data_dir, vocab_file)

        def load(name):
            text = gcs.read_bytes(gcs.join(data_dir, name)).decode()
            lines = text.replace("\r\n", "\n").strip().split("\n")[1:]  # drop header; CRLF-safe
            sents, labels = [], []
            for line in lines:
                sent, _, lbl = line.rpartition("\t")
                sents.append(sent)
                labels.append(int(lbl))
            return _tokenize(sents, np.asarray(labels, np.int32), seq_len,
                             vocab_size, tokenizer)

        return load("train.tsv"), load("dev.tsv")
    return (_synthetic_tokens(synthetic_size, seq_len, vocab_size, seed=6),
            _synthetic_tokens(max(synthetic_size // 8, 64), seq_len, vocab_size, seed=7))


MNLI_LABELS = {"entailment": 0, "neutral": 1, "contradiction": 2}


def glue_mnli(data_dir: str | None = None, *, seq_len: int = 128,
              vocab_size: int = 30522, synthetic_size: int = 1024,
              tokenizer=None, vocab_file: str | None = None):
    """MNLI sentence-PAIR classification (3-way: entailment / neutral /
    contradiction) — the second GLUE task, exercising the ``[CLS] a [SEP]
    b [SEP]`` pair-encoding path (``token_type_ids`` 0/1 segments) that
    single-sentence SST-2 never touches.

    With ``data_dir``: reads MNLI's ``train.tsv`` / ``dev_matched.tsv``.
    MNLI tsv columns vary by split, so fields are located by HEADER NAME
    (``sentence1``, ``sentence2``, ``gold_label``); rows with a missing or
    ``-`` gold label (annotator disagreement) are dropped, matching the
    standard evaluation protocol.  Tokenizer resolution is identical to
    :func:`glue_sst2`.
    """
    if data_dir is not None:
        tokenizer = _resolve_tokenizer(tokenizer, data_dir, vocab_file)

        def parse_label(raw):  # '-' / unknown = no gold consensus: drop
            return MNLI_LABELS.get(raw.strip())

        def load(name):
            pairs, labels = _parse_pair_tsv(
                gcs.read_bytes(gcs.join(data_dir, name)).decode(),
                label_col="gold_label", parse_label=parse_label)
            return _tokenize(pairs, np.asarray(labels, np.int32), seq_len,
                             vocab_size, tokenizer)

        return load("train.tsv"), load("dev_matched.tsv")
    return (_synthetic_token_pairs(synthetic_size, seq_len, vocab_size,
                                   seed=8),
            _synthetic_token_pairs(max(synthetic_size // 8, 64), seq_len,
                                   vocab_size, seed=9))


def _parse_pair_tsv(text: str, *, label_col: str, parse_label):
    """Header-located GLUE pair-task tsv: returns ((a, b) pairs, labels).
    ``parse_label`` maps the raw label field to a value or None (drop row
    — '-' MNLI labels, unscored STS-B test rows).  CRLF-normalized
    (NOT splitlines(), which would also split on \\x0c / U+2028-class
    breaks that can legally appear inside a text field)."""
    lines = text.replace("\r\n", "\n").strip().split("\n")
    col = {c: i for i, c in enumerate(lines[0].split("\t"))}
    ia, ib, il = col["sentence1"], col["sentence2"], col[label_col]
    pairs, labels = [], []
    for line in lines[1:]:
        f = line.split("\t")
        if len(f) <= max(ia, ib, il):
            continue
        lbl = parse_label(f[il])
        if lbl is None:
            continue
        pairs.append((f[ia], f[ib]))
        labels.append(lbl)
    return pairs, labels


def glue_stsb(data_dir: str | None = None, *, seq_len: int = 128,
              vocab_size: int = 30522, synthetic_size: int = 1024,
              tokenizer=None, vocab_file: str | None = None):
    """STS-B sentence-pair REGRESSION (similarity score 0-5, float32
    label) — the GLUE task family's third shape: the harness trains it
    with MSE instead of cross-entropy (HF convention: num_classes=1 ⇒
    regression).  Float labels also exercise the loader's cast_keys
    contract: inputs may be host-cast to bf16, targets must stay f32.

    With ``data_dir``: reads ``train.tsv`` / ``dev.tsv`` with
    header-located ``sentence1``/``sentence2``/``score`` columns.
    """
    if data_dir is not None:
        tokenizer = _resolve_tokenizer(tokenizer, data_dir, vocab_file)

        def parse_label(raw):  # unscored (test-set shape) rows: drop
            try:
                return float(raw)
            except ValueError:
                return None

        def load(name):
            pairs, scores = _parse_pair_tsv(
                gcs.read_bytes(gcs.join(data_dir, name)).decode(),
                label_col="score", parse_label=parse_label)
            return _tokenize(pairs, np.asarray(scores, np.float32), seq_len,
                             vocab_size, tokenizer)

        return load("train.tsv"), load("dev.tsv")
    return (_synthetic_score_pairs(synthetic_size, seq_len, vocab_size,
                                   seed=10),
            _synthetic_score_pairs(max(synthetic_size // 8, 64), seq_len,
                                   vocab_size, seed=11))


def glue_cola(data_dir: str | None = None, *, seq_len: int = 128,
              vocab_size: int = 30522, synthetic_size: int = 1024,
              tokenizer=None, vocab_file: str | None = None):
    """CoLA (Corpus of Linguistic Acceptability) — single-sentence binary
    classification whose standard metric is MATTHEWS CORRELATION (the
    class balance is skewed ~70/30, so accuracy overstates; the harness
    derives MCC from aggregated confusion moments at eval, train.py).

    File format differs from every other GLUE task: ``train.tsv`` /
    ``dev.tsv`` have NO header and four columns
    ``source<TAB>label<TAB>star<TAB>sentence``.
    """
    if data_dir is not None:
        tokenizer = _resolve_tokenizer(tokenizer, data_dir, vocab_file)

        def load(name):
            text = gcs.read_bytes(gcs.join(data_dir, name)).decode()
            sents, labels = [], []
            for line in text.replace("\r\n", "\n").strip().split("\n"):
                cols = line.split("\t")
                if len(cols) < 4:
                    continue
                labels.append(int(cols[1]))
                sents.append(cols[3])
            return _tokenize(sents, np.asarray(labels, np.int32), seq_len,
                             vocab_size, tokenizer)

        return load("train.tsv"), load("dev.tsv")
    return (_synthetic_tokens(synthetic_size, seq_len, vocab_size, seed=12),
            _synthetic_tokens(max(synthetic_size // 8, 64), seq_len,
                              vocab_size, seed=13))


def _synthetic_score_pairs(n, seq_len, vocab_size, *, seed):
    """Pair-encoded batches with a LEARNABLE float score: the signal token
    (position 1) encodes one of 11 levels mapping to scores 0.0-5.0."""
    if vocab_size < 211:  # ids 200..210 must be real embedding rows
        raise ValueError(f"synthetic STS-B needs vocab_size >= 211 for the "
                         f"score signal tokens; got {vocab_size}")
    rng = np.random.default_rng(seed)
    level = rng.integers(0, 11, size=n)
    ds = _synthetic_token_pairs(n, seq_len, vocab_size, seed=seed)
    ds.columns["input_ids"][:, 1] = 200 + level
    ds.columns["label"] = (level / 2.0).astype(np.float32)
    return ds


def _resolve_tokenizer(tokenizer, data_dir, vocab_file):
    """glue_* shared tokenizer resolution: caller-supplied > WordPiece with
    a real vocab > None (hash fallback in _tokenize)."""
    if tokenizer is not None:
        return tokenizer
    vpath = vocab_file or gcs.join(data_dir, "vocab.txt")
    if gcs.exists(vpath):
        from tpuframe.data.wordpiece import WordPieceTokenizer

        return WordPieceTokenizer(vpath)
    if vocab_file is not None:
        # An explicit vocab path that doesn't exist is a config error —
        # silently hash-tokenizing would just show up as mysteriously bad
        # accuracy.
        raise FileNotFoundError(f"vocab_file not found: {vocab_file}")
    return None


def _tokenize(sents, labels, seq_len, vocab_size, tokenizer):
    """``sents``: strings, or (a, b) pair tuples for two-sentence tasks."""
    if tokenizer is not None:
        enc = tokenizer(sents, padding="max_length", truncation=True,
                        max_length=seq_len, return_tensors="np")
        return ArrayDataset({
            "input_ids": enc["input_ids"].astype(np.int32),
            "attention_mask": enc["attention_mask"].astype(np.int32),
            "token_type_ids": enc.get("token_type_ids",
                                      np.zeros_like(enc["input_ids"])).astype(np.int32),
            "label": labels,
        })
    # Hash-based whitespace tokenizer: deterministic (crc32, not Python's
    # salted hash — ids must agree across host processes and restarts),
    # vocab-free. Fine for pipeline/perf work; real GLUE scores need the
    # WordPiece tokenizer.
    ids = np.zeros((len(sents), seq_len), np.int32)
    mask = np.zeros((len(sents), seq_len), np.int32)
    types = np.zeros((len(sents), seq_len), np.int32)
    hashed = lambda w: 2 + (zlib.crc32(w.encode()) % (vocab_size - 4))  # noqa: E731
    for i, s in enumerate(sents):
        if isinstance(s, tuple):
            a, b = ([hashed(w) for w in part.split()] for part in s)
            while len(a) + len(b) > seq_len - 3:  # HF longest_first order
                (a if len(a) > len(b) else b).pop()
            toks = [101] + a + [102] + b + [102]
            types[i, len(a) + 2:len(toks)] = 1
        else:
            toks = [101] + [hashed(w) for w in s.split()][: seq_len - 2] + [102]
        ids[i, :len(toks)] = toks
        mask[i, :len(toks)] = 1
    return ArrayDataset({"input_ids": ids, "attention_mask": mask,
                         "token_type_ids": types, "label": labels})


def _synthetic_token_pairs(n, seq_len, vocab_size, *, seed):
    """Synthetic pair-encoded batches with 3 learnable classes: the signal
    token (position 1) carries the label, and segment B starts at a
    variable boundary so token_type_ids actually vary."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 3, size=n).astype(np.int32)
    ids = rng.integers(4, vocab_size, size=(n, seq_len)).astype(np.int32)
    ids[:, 0] = 101
    ids[:, 1] = 200 + labels
    lengths = rng.integers(seq_len // 2, seq_len + 1, size=n)
    bounds = rng.integers(2, np.maximum(lengths - 1, 3))
    pos = np.arange(seq_len)[None, :]
    mask = (pos < lengths[:, None]).astype(np.int32)
    types = ((pos >= bounds[:, None]) & (pos < lengths[:, None])).astype(
        np.int32)
    return ArrayDataset({"input_ids": ids, "attention_mask": mask,
                         "token_type_ids": types, "label": labels})


# ---------------------------------------------------------------------------
# Causal LM — long-context workload (beyond the reference's capability bar)
# ---------------------------------------------------------------------------

def lm_text(data_dir: str | None = None, *, seq_len: int = 2048,
            vocab_size: int = 32000, synthetic_size: int = 256,
            padded_docs: bool = False, pad_id: int = 0,
            uniform_ids: bool = False):
    """Next-token-prediction chunks: input_ids [N, S], labels [N, S] int32
    (labels pre-shifted on the host so the loss is positionwise — no
    cross-shard shift is needed when the sequence dim is sharded over the
    mesh's seq axis).

    With ``data_dir``: reads ``tokens.npy`` (a single int32 token stream,
    e.g. pre-tokenized wikitext) and chunks it; synthetic mode generates an
    order-2 structured stream so convergence tests are meaningful.

    ``padded_docs``: variable-length documents right-padded to ``seq_len``
    with ``pad_id``; padded label positions carry ``-100`` — torch's
    ``ignore_index`` convention, which the harness LM losses honor (zero
    loss AND zero gradient there, means over valid tokens only).  The
    fine-tuning data shape, vs the packed-stream pretraining shape.

    ``uniform_ids`` (synthetic only): every id drawn uniformly and
    independently — nothing to learn, but every sequence holds the whole
    vocabulary in equal measure, where the affine recurrence walks one
    short cycle of it (a few hundred distinct ids in 8192 tokens); what a
    router's load, and so an expert layer's work, should be measured on.
    """
    if padded_docs:
        if data_dir is not None:
            raise ValueError("padded_docs is a synthetic-data mode; "
                             "pre-tokenized streams are packed, not padded")
        return (_synthetic_lm_docs(synthetic_size, seq_len, vocab_size,
                                   pad_id=pad_id, seed=8),
                _synthetic_lm_docs(max(synthetic_size // 8, 8), seq_len,
                                   vocab_size, pad_id=pad_id, seed=9))
    if data_dir is not None:
        stream = np.load(io.BytesIO(gcs.read_bytes(gcs.join(data_dir, "tokens.npy"))))
        stream = stream.astype(np.int32) % vocab_size
        n = (len(stream) - 1) // seq_len
        split = max(int(0.98 * n), 1)
        def chunk(lo, hi):
            ids = np.stack([stream[i*seq_len:(i+1)*seq_len] for i in range(lo, hi)])
            lbl = np.stack([stream[i*seq_len+1:(i+1)*seq_len+1] for i in range(lo, hi)])
            return ArrayDataset({"input_ids": ids, "labels": lbl})
        return chunk(0, split), chunk(split, n)
    return (_synthetic_lm(synthetic_size, seq_len, vocab_size, seed=8,
                          uniform=uniform_ids),
            _synthetic_lm(max(synthetic_size // 8, 8), seq_len, vocab_size,
                          seed=9, uniform=uniform_ids))


def _synthetic_lm_docs(n, seq_len, vocab_size, *, pad_id, seed):
    """Variable-length affine-recurrence documents, right-padded: lengths
    uniform in [seq_len//4, seq_len]; labels are the shifted next tokens
    inside the document and -100 (ignored) at/after the last real token."""
    rng = np.random.default_rng(seed)
    full = _synthetic_lm(n, seq_len, vocab_size, seed=seed)
    ids = np.array(full[:n]["input_ids"], copy=True)
    labels = np.array(full[:n]["labels"], copy=True)
    lengths = rng.integers(max(seq_len // 4, 2), seq_len + 1, size=n)
    for i, ln in enumerate(lengths):
        ids[i, ln:] = pad_id
        # position t predicts token t+1: the last valid prediction is at
        # index ln-2 (predicting the doc's final token); everything from
        # ln-1 on is padding context -> ignored.
        labels[i, ln - 1:] = -100
    return ArrayDataset({"input_ids": ids, "labels": labels})


def _synthetic_lm(n, seq_len, vocab_size, *, seed, uniform=False):
    """Deterministic affine-recurrence token stream: x_{t+1} =
    (a*x_t + b) mod V with occasional noise — next-token loss can fall well
    below log(V), so "loss decreases" tests measure learning, not chance.
    ``uniform``: independent uniform ids instead."""
    rng = np.random.default_rng(seed)
    if uniform:
        ids = rng.integers(0, vocab_size, size=(n, seq_len + 1))
        return ArrayDataset({"input_ids": ids[:, :-1].astype(np.int32),
                             "labels": ids[:, 1:].astype(np.int32)})
    starts = rng.integers(0, vocab_size, size=n)
    a, b = 31, 17
    ids = np.empty((n, seq_len + 1), np.int64)
    ids[:, 0] = starts
    for t in range(seq_len):
        ids[:, t + 1] = (a * ids[:, t] + b) % vocab_size
    noise = rng.random((n, seq_len + 1)) < 0.05
    ids[noise] = rng.integers(0, vocab_size, size=int(noise.sum()))
    return ArrayDataset({"input_ids": ids[:, :-1].astype(np.int32),
                         "labels": ids[:, 1:].astype(np.int32)})


# ---------------------------------------------------------------------------
# Synthetic generators (deterministic; shapes/dtypes match the real data)
# ---------------------------------------------------------------------------

def _synthetic_images(n, shape, num_classes, *, seed, template_seed=None):
    # A fixed random spatial template per class (high per-pixel SNR) makes the
    # synthetic task quickly learnable, so convergence tests (loss decreasing,
    # accuracy rising) are meaningful, not vacuous.  Pixel statistics mimic
    # real normalized data (mean~0.5, std~0.3 like [0,1) images) — the LR
    # recipes assume that scale.  ``template_seed`` is shared between the
    # train and eval splits of one dataset (same classes, different examples)
    # so eval accuracy actually measures generalization.
    tmpl_rng = np.random.default_rng(seed if template_seed is None else template_seed)
    templates = tmpl_rng.normal(0.0, 1.0, size=(num_classes, *shape)).astype(np.float32)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    noise = rng.normal(0.0, 1.0, size=(n, *shape)).astype(np.float32)
    x = np.clip(0.5 + 0.25 * templates[labels] + 0.1 * noise, 0.0, 1.0)
    return ArrayDataset({"image": x.astype(np.float32), "label": labels})


def _synthetic_tokens(n, seq_len, vocab_size, *, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, size=n).astype(np.int32)
    ids = rng.integers(4, vocab_size, size=(n, seq_len)).astype(np.int32)
    # Learnable signal: first token id correlates with the label.
    ids[:, 0] = 101
    ids[:, 1] = 200 + labels
    lengths = rng.integers(seq_len // 2, seq_len + 1, size=n)
    mask = (np.arange(seq_len)[None, :] < lengths[:, None]).astype(np.int32)
    return ArrayDataset({"input_ids": ids, "attention_mask": mask,
                         "token_type_ids": np.zeros_like(ids), "label": labels})
