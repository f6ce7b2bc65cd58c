"""SequenceVectors: the generic embedding trainer (word2vec engine).

TPU-native equivalent of reference ``models/sequencevectors/SequenceVectors.java``
(fit :192-310, AsyncSequencer :1021, VectorCalculationsThreads :1126) plus the
learning algorithms ``models/embeddings/learning/impl/elements/{SkipGram,CBOW}``
and ``InMemoryLookupTable``.

Idiom shift (SURVEY.md §3.6): the reference's hot loop builds batched native
``AggregateSkipGram`` ops dispatched thread-per-worker over JNI
(``SkipGram.java:176-283``). Here windows are collected into index arrays on
the host and ONE jitted update step performs the whole batch on device:
gather → sigmoid dot products → scatter-add updates, with buffer donation.
Both objective variants are provided: hierarchical softmax (Huffman
codes/points) and negative sampling (unigram^0.75 table).
"""
from __future__ import annotations

import logging
import math
from typing import Iterable, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

from .vocab import VocabCache, VocabWord, Huffman, build_vocab
from ..monitor.jitwatch import monitored_jit

log = logging.getLogger(__name__)


class InMemoryLookupTable:
    """Reference ``models/embeddings/inmemory/InMemoryLookupTable``: syn0
    (word vectors), syn1 (HS inner-node weights), syn1neg (NS weights)."""

    def __init__(self, vocab: VocabCache, vector_length: int, seed: int = 123,
                 use_hs: bool = True, use_neg: bool = False):
        self.vocab = vocab
        self.vector_length = vector_length
        n = vocab.num_words()
        rng = np.random.default_rng(seed)
        self.syn0 = ((rng.random((n, vector_length)) - 0.5)
                     / vector_length).astype(np.float32)
        self.syn1 = (np.zeros((max(n - 1, 1), vector_length), np.float32)
                     if use_hs else None)
        self.syn1neg = (np.zeros((n, vector_length), np.float32)
                        if use_neg else None)

    def reset_weights(self, seed: int = 123):
        n = self.vocab.num_words()
        rng = np.random.default_rng(seed)
        self.syn0 = ((rng.random((n, self.vector_length)) - 0.5)
                     / self.vector_length).astype(np.float32)
        if self.syn1 is not None:
            self.syn1 = np.zeros_like(self.syn1)
        if self.syn1neg is not None:
            self.syn1neg = np.zeros_like(self.syn1neg)

    resetWeights = reset_weights

    def vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])


# ------------------------------------------------------------- jitted kernels
#
# Transfer discipline (per-device_put latency dominated training before):
# pairs arrive as ONE packed [2, B] int32 array of fixed batch shape
# (the tail batch is padded; ``n_valid`` masks the padding on-device), the
# vocab-wide Huffman tables live in HBM and are gathered on-device, and the
# negative-sampling labels are synthesized on-device — so a batch costs one
# 64 KB transfer instead of seven, and one compiled shape serves every batch.

@monitored_jit(name="nlp/hs_step", donate_argnums=(0, 1))
def _hs_step(syn0, syn1, packed, hs_points, hs_codes, hs_mask):
    """Hierarchical-softmax skip-gram/CBOW update, batched.

    packed: [2, B+1] int32 — columns 0..B-1 are (input row ids;
    Huffman-target word ids); the LAST column carries the batch scalars
    (n_valid; lr float bit-cast to int32) so the whole batch arrives in ONE
    host→device transfer (a transfer has a fixed latency whatever its
    size). hs_points/codes/mask: [V, L] device-resident vocab
    tables. Classic w2v update rule: g = (1 - code - σ(h·v)).
    """
    n_valid = packed[0, -1]
    lr = jax.lax.bitcast_convert_type(packed[1, -1], jnp.float32)
    centers, targets = packed[0, :-1], packed[1, :-1]
    points = hs_points[targets]                        # [B, L]
    codes = hs_codes[targets]
    wmask = (jnp.arange(centers.shape[0]) < n_valid).astype(syn0.dtype)
    mask = hs_mask[targets] * wmask[:, None]
    h = syn0[centers]                                  # [B, d]
    v = syn1[points]                                   # [B, L, d]
    f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, v))  # [B, L]
    g = (1.0 - codes - f) * mask * lr                  # [B, L]
    dh = jnp.einsum("bl,bld->bd", g, v)                # [B, d]
    dv = g[..., None] * h[:, None, :]                  # [B, L, d]
    syn0 = syn0.at[centers].add(dh * wmask[:, None])
    syn1 = syn1.at[points.reshape(-1)].add(
        dv.reshape(-1, dv.shape[-1]) * mask.reshape(-1, 1))
    return syn0, syn1


@monitored_jit(name="nlp/ns_step", donate_argnums=(0, 1))
def _ns_step(syn0, syn1neg, packed):
    """Negative-sampling update, single-transfer like :func:`_hs_step`.

    packed: [B+1, K+2] int32 — rows 0..B-1 are (center; positive target; K
    negatives); the LAST row carries (n_valid; lr bit-cast; 0...). Labels
    are synthesized on-device (column 0 = 1); rows ≥ n_valid are padding."""
    n_valid = packed[-1, 0]
    lr = jax.lax.bitcast_convert_type(packed[-1, 1], jnp.float32)
    centers = packed[:-1, 0]                            # [B]
    targets = packed[:-1, 1:]                           # [B, K+1]
    h = syn0[centers]                                   # [B, d]
    v = syn1neg[targets]                                # [B, K+1, d]
    f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, v))
    labels = jnp.zeros_like(f).at[:, 0].set(1.0)
    wmask = (jnp.arange(centers.shape[0]) < n_valid).astype(syn0.dtype)
    g = (labels - f) * lr * wmask[:, None]              # [B, K+1]
    dh = jnp.einsum("bk,bkd->bd", g, v)
    dv = g[..., None] * h[:, None, :]
    syn0 = syn0.at[centers].add(dh)
    syn1neg = syn1neg.at[targets.reshape(-1)].add(dv.reshape(-1, dv.shape[-1]))
    return syn0, syn1neg


class SequenceVectors:
    """Configurable embedding trainer over sequences of tokens."""

    def __init__(self, vector_length: int = 100, window: int = 5,
                 min_word_frequency: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, epochs: int = 1,
                 negative: int = 0,
                 use_hierarchic_softmax: Optional[bool] = None,
                 subsampling: float = 0.0, batch_size: int = 512,
                 seed: int = 123):
        self.vector_length = vector_length
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.negative = negative
        # NS replaces HS unless HS is explicitly requested (word2vec
        # convention; combining both doubles device work for no benefit)
        if use_hierarchic_softmax is None:
            self.use_hs = negative == 0
        else:
            self.use_hs = use_hierarchic_softmax or negative == 0
        self.subsampling = subsampling
        self.batch_size = batch_size
        self.seed = seed
        self.vocab: Optional[VocabCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        self._neg_table: Optional[np.ndarray] = None
        self._code_len = 0

    # ----------------------------------------------------------------- vocab
    def build_vocab(self, sequences: Iterable[Sequence[str]]):
        self.vocab = build_vocab(sequences,
                                 min_word_frequency=self.min_word_frequency,
                                 build_huffman=True)
        self.lookup_table = InMemoryLookupTable(
            self.vocab, self.vector_length, self.seed,
            use_hs=self.use_hs, use_neg=self.negative > 0)
        self._code_len = max((len(w.codes)
                              for w in self.vocab.vocab_words()), default=1)
        if self.use_hs:
            # vocab-wide Huffman tables: batch HS encoding becomes three
            # array gathers instead of a Python loop over targets
            V, L = self.vocab.num_words(), self._code_len
            self._hs_points = np.zeros((V, L), np.int32)
            self._hs_codes = np.zeros((V, L), np.float32)
            self._hs_mask = np.zeros((V, L), np.float32)
            for i, w in enumerate(self.vocab.vocab_words()):
                k = len(w.codes)
                self._hs_points[i, :k] = w.points
                self._hs_codes[i, :k] = w.codes
                self._hs_mask[i, :k] = 1.0
        if self.negative > 0:
            self._neg_table = self._build_unigram_table()
        self._hs_points_dev = None  # rebuilt tables invalidate device copies
        return self

    buildVocab = build_vocab

    def _build_unigram_table(self, size: int = 1 << 20) -> np.ndarray:
        """word2vec unigram^0.75 sampling table."""
        freqs = np.array([w.frequency for w in self.vocab.vocab_words()])
        p = freqs ** 0.75
        p /= p.sum()
        return np.random.default_rng(self.seed).choice(
            len(freqs), size=size, p=p).astype(np.int32)

    # ------------------------------------------------------------------- fit
    def fit(self, sequences_provider):
        """``sequences_provider``: callable returning an iterable of token
        sequences (re-iterable across epochs), or a list of sequences."""
        provider = (sequences_provider if callable(sequences_provider)
                    else (lambda: sequences_provider))
        if self.vocab is None:
            self.build_vocab(provider())
        total_words = max(self.vocab.total_word_count, 1.0)
        rng = np.random.default_rng(self.seed)
        words_seen = 0
        est_total = total_words * self.epochs
        for epoch in range(self.epochs):
            pend_c: List[np.ndarray] = []
            pend_t: List[np.ndarray] = []
            pending = 0
            for seq in provider():
                idxs = self._subsampled_indices(seq, rng)
                words_seen += len(idxs)
                c, t = self._sequence_pairs_arrays(idxs, rng)
                if c.size:
                    pend_c.append(c)
                    pend_t.append(t)
                    pending += c.size
                if pending >= self.batch_size:
                    # concatenate ONCE, then walk batch-size slices — the
                    # remainder is a view, so the copy cost stays linear in
                    # the number of pairs
                    cat_c = np.concatenate(pend_c)
                    cat_t = np.concatenate(pend_t)
                    off = 0
                    while pending - off >= self.batch_size:
                        lr = self._lr(words_seen, est_total)
                        self._apply_pairs(cat_c[off:off + self.batch_size],
                                          cat_t[off:off + self.batch_size],
                                          lr, rng)
                        off += self.batch_size
                    pend_c = [cat_c[off:]]
                    pend_t = [cat_t[off:]]
                    pending -= off
            if pending:
                lr = self._lr(words_seen, est_total)
                self._apply_pairs(np.concatenate(pend_c),
                                  np.concatenate(pend_t), lr, rng)
        return self

    def _sequence_pairs(self, idxs, rng):
        """Yield (center, context) training pairs for one sequence: dynamic
        windows, skip-gram convention. Overridden by doc2vec to add
        document-level pairs; the vectorized array path below is used when
        this method is NOT overridden."""
        for pos, center in enumerate(idxs):
            b = rng.integers(1, self.window + 1)  # dynamic window
            lo = max(0, pos - b)
            hi = min(len(idxs), pos + b + 1)
            for j in range(lo, hi):
                if j != pos:
                    yield center, idxs[j]

    def _sequence_pairs_arrays(self, idxs, rng):
        """(centers, contexts) int32 arrays for one sequence. Vectorized —
        the per-pair Python loop was the host-side bottleneck of training
        (the reference hits the same issue and batches into native
        ``AggregateSkipGram`` calls, ``SkipGram.java:176-283``). Subclasses
        that override ``_sequence_pairs`` (doc2vec) automatically fall back
        to the generator; ``_orient_pairs`` gives CBOW its row/target swap."""
        n = len(idxs)
        if n < 2:
            empty = np.empty(0, np.int32)
            return empty, empty
        if type(self)._sequence_pairs is not SequenceVectors._sequence_pairs:
            pairs = list(self._sequence_pairs(idxs, rng))
            if not pairs:
                empty = np.empty(0, np.int32)
                return empty, empty
            arr = np.asarray(pairs, np.int32)
            return self._orient_pairs(arr[:, 0], arr[:, 1])
        c, t = self._window_pairs_arrays(idxs, rng)
        return self._orient_pairs(c, t)

    def _window_pairs_arrays(self, idxs, rng):
        """Raw vectorized dynamic-window pairs (centers, contexts) — NO
        orientation, no override dispatch; subclasses with custom pair
        semantics (doc2vec) reuse this for their word-word portion."""
        n = len(idxs)
        if n < 2:
            empty = np.empty(0, np.int32)
            return empty, empty
        arr = np.asarray(idxs, np.int32)
        pos = np.arange(n)
        b = rng.integers(1, self.window + 1, size=n)
        lo = np.maximum(0, pos - b)
        hi = np.minimum(n, pos + b + 1)
        counts = hi - lo - 1                      # window size minus center
        total = int(counts.sum())
        if total == 0:
            empty = np.empty(0, np.int32)
            return empty, empty
        centers_pos = np.repeat(pos, counts)
        # within-window offsets 0..count-1 per center
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        offs = np.arange(total) - np.repeat(starts, counts)
        ctx_pos = np.repeat(lo, counts) + offs
        ctx_pos += (ctx_pos >= centers_pos)       # skip the center slot
        return arr[centers_pos], arr[ctx_pos]

    def _orient_pairs(self, centers, contexts):
        """Skip-gram orientation: the CENTER row is updated against the
        context's objective. CBOW overrides to swap."""
        return centers, contexts

    def _lr(self, words_seen, est_total):
        frac = min(words_seen / est_total, 1.0)
        return max(self.learning_rate * (1 - frac), self.min_learning_rate)

    def _subsampled_indices(self, seq, rng) -> List[int]:
        out = []
        for tok in seq:
            i = self.vocab.index_of(tok)
            if i < 0:
                continue
            if self.subsampling > 0:
                f = self.vocab.word_at(i).frequency / self.vocab.total_word_count
                keep = (math.sqrt(f / self.subsampling) + 1) * self.subsampling / f
                if rng.random() > keep:
                    continue
            out.append(i)
        return out

    def _ensure_device_tables(self):
        """Huffman/vocab tables → HBM once; per-batch gathers run on-device."""
        if getattr(self, "_hs_points_dev", None) is None and self.use_hs:
            self._hs_points_dev = jnp.asarray(self._hs_points)
            self._hs_codes_dev = jnp.asarray(self._hs_codes)
            self._hs_mask_dev = jnp.asarray(self._hs_mask)

    def _apply_pairs(self, rows, targets, lr, rng):
        """Update syn0[rows] against targets' objective. Fixed-shape batches
        (tail padded, masked on-device) + packed single-transfer pairs: one
        compiled kernel and one small H2D per batch."""
        lt = self.lookup_table
        rows = np.ascontiguousarray(rows, np.int32)
        targets = np.ascontiguousarray(targets, np.int32)
        n = len(rows)
        B = max(self.batch_size, n)
        if n < B:
            rows = np.concatenate([rows, np.zeros(B - n, np.int32)])
            targets = np.concatenate([targets, np.zeros(B - n, np.int32)])
        if self.use_hs:
            self._ensure_device_tables()
            meta = np.array([n, np.float32(lr).view(np.int32)], np.int32)
            packed = jnp.asarray(np.concatenate(
                [np.stack([rows, targets]), meta[:, None]], axis=1))
            lt.syn0, lt.syn1 = _hs_step(
                jnp.asarray(lt.syn0), jnp.asarray(lt.syn1), packed,
                self._hs_points_dev, self._hs_codes_dev, self._hs_mask_dev)
        if self.negative > 0:
            K = self.negative
            negs = self._neg_table[rng.integers(0, len(self._neg_table),
                                                size=(B, K))]
            body = np.concatenate([rows[:, None], targets[:, None], negs],
                                  axis=1)                       # [B, K+2]
            meta = np.zeros((1, K + 2), np.int32)
            meta[0, 0] = n
            meta[0, 1] = np.float32(lr).view(np.int32)
            lt.syn0, lt.syn1neg = _ns_step(
                jnp.asarray(lt.syn0), jnp.asarray(lt.syn1neg),
                jnp.asarray(np.concatenate([body, meta])))

    # ------------------------------------------------------------- inference
    def word_vector(self, word: str) -> Optional[np.ndarray]:
        return self.lookup_table.vector(word)

    getWordVector = word_vector

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.word_vector(a), self.word_vector(b)
        if va is None or vb is None:
            return float("nan")
        na = np.linalg.norm(va)
        nb = np.linalg.norm(vb)
        if na == 0 or nb == 0:
            return 0.0
        return float(va @ vb / (na * nb))

    def words_nearest(self, word: str, n: int = 10) -> List[str]:
        v = self.word_vector(word)
        if v is None:
            return []
        syn0 = np.asarray(self.lookup_table.syn0)
        norms = np.linalg.norm(syn0, axis=1) * max(np.linalg.norm(v), 1e-9)
        sims = syn0 @ v / np.maximum(norms, 1e-9)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at(int(i)).word
            if w != word:
                out.append(w)
            if len(out) >= n:
                break
        return out

    wordsNearest = words_nearest

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    hasWord = has_word
