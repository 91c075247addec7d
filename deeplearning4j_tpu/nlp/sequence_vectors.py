"""SequenceVectors: the generic embedding-training engine (reference
`models/sequencevectors/SequenceVectors.java:50`, `fit():161`; learning
algorithms SPI `models/embeddings/learning/` — `SkipGram.java`, `CBOW.java`).

TPU-first pipeline: the host walks sequences, applies subsampling and the
shrinking window, and packs (center, targets, labels, mask) int32 batches;
every full batch is one donated-buffer jitted scatter step
(`nlp/kernels.py`). Learning rate decays linearly with words processed, as
in the reference (`SequenceVectors.java:260` alpha handling).
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp import kernels
from deeplearning4j_tpu.nlp.lookup_table import InMemoryLookupTable
from deeplearning4j_tpu.nlp.vocab import (
    AbstractCache,
    VocabConstructor,
    build_huffman_tree,
)


class SequenceVectors:
    """Train element embeddings over sequences of tokens.

    elements_learning_algorithm: 'skipgram' | 'cbow'
    (reference `ElementsLearningAlgorithm` SPI).
    """

    def __init__(self,
                 layer_size: int = 100,
                 window: int = 5,
                 min_word_frequency: float = 1.0,
                 negative: int = 5,
                 use_hierarchic_softmax: bool = False,
                 learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4,
                 epochs: int = 1,
                 iterations: int = 1,
                 batch_size: int = 1024,
                 sampling: float = 0.0,
                 seed: int = 42,
                 elements_learning_algorithm: str = "skipgram",
                 scan_flushes: int = 32,
                 mesh=None,
                 data_axis: str = "data"):
        if negative <= 0 and not use_hierarchic_softmax:
            raise ValueError("need negative sampling (negative>0) and/or "
                             "hierarchical softmax")
        if negative > 0 and use_hierarchic_softmax and \
                elements_learning_algorithm == "cbow":
            raise NotImplementedError(
                "mixed HS+negative-sampling is only supported for skipgram")
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self.sampling = sampling
        self.seed = seed
        self.algorithm = elements_learning_algorithm
        # NS fast path: how many flush-batches ride one scanned dispatch
        self.scan_flushes = max(1, int(scan_flushes))
        self.vocab: Optional[AbstractCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        self._rng = np.random.default_rng(seed)
        self._keep_cache: Optional[np.ndarray] = None
        self._unigram: Optional[np.ndarray] = None
        self._unigram_cdf: Optional[np.ndarray] = None
        self._ns_cdf_dev = None  # device copy of the cdf (NS-on-device)
        self._ns_key = None      # carried PRNG state for device sampling
        self._loss_sum = 0.0
        self._loss_batches = 0
        self._loss_dev = None
        self._loss_dev_count = 0
        # multi-chip data parallelism (the dl4j-spark-nlp role,
        # `spark/models/embeddings/word2vec/Word2VecPerformer.java`): pair
        # batches shard over the mesh's data axis, embedding tables stay
        # replicated, and XLA psums the scatter contributions over ICI —
        # where the reference map-reduces word2vec over Spark executors.
        self.mesh = mesh
        self.data_axis = data_axis
        if mesh is not None:
            n = mesh.shape[data_axis]
            if batch_size % n != 0:
                raise ValueError(
                    f"batch_size {batch_size} must divide by the "
                    f"'{data_axis}' mesh axis size {n}")
        self._sharded_kernels = None
        self._sharded_ns_kernel = None

    # -- vocab/init ---------------------------------------------------------
    def build_vocab(self, sequences: Iterable[Sequence[str]]) -> None:
        self.vocab = VocabConstructor(self.min_word_frequency).build_vocab(sequences)
        self._keep_cache = None
        if self.use_hs:
            build_huffman_tree(self.vocab)
        self.lookup_table = InMemoryLookupTable(
            self.vocab, self.layer_size, seed=self.seed, use_hs=self.use_hs,
            negative=self.negative)
        if self.negative > 0:
            self._unigram = self.vocab.unigram_table()
            self._unigram_cdf = None
            self._ns_cdf_dev = None

    # -- training -----------------------------------------------------------
    def fit(self, sequences: Iterable[Sequence[str]]) -> None:
        seqs = [list(s) for s in sequences]
        if self.vocab is None:
            self.build_vocab(seqs)
        total_words = max(
            1.0, self.vocab.total_word_occurrences * self.epochs * self.iterations)
        self._reset_loss()
        batch = _PairBatcher(self)
        if self.algorithm == "skipgram" and self.negative > 0 \
                and not self.use_hs:
            # NS skip-gram (the common configuration — BASELINE config 4):
            # fully vectorized host pipeline, see _fit_vectorized
            self._fit_vectorized(seqs, total_words, batch)
            batch.flush()
            return
        words_seen = 0.0
        for _ in range(self.epochs * self.iterations):
            for seq in seqs:
                ids = self._to_ids(seq)
                if len(ids) < 2:
                    continue
                alpha = max(self.min_learning_rate,
                            self.learning_rate * (1.0 - words_seen / total_words))
                self._train_sequence(ids, alpha, batch)
                words_seen += len(ids)
        batch.flush()

    # chunk size (tokens) for the vectorized pipeline: big enough that the
    # per-chunk numpy fixed costs amortize, small enough that the (L, 2W)
    # windowing grid stays ~20 MB and alpha decay keeps per-chunk
    # granularity (the reference decays per sentence batch,
    # `SequenceVectors.java:260`)
    _CHUNK_TOKENS = 262_144

    def _encode_corpus(self, seqs):
        """token→id for the whole corpus in ONE pass (OOV dropped): flat
        int32 id array + per-sentence kept lengths. The per-token dict
        lookup — the irreducible host cost — happens exactly once per fit,
        not once per epoch, and everything downstream is numpy array math.
        This finishes the `AggregateSkipGram` replacement host-side
        (reference `SkipGram.java:216` made windowing a native op because
        interpreted per-pair loops cannot keep an accelerator fed)."""
        lookup = {vw.word: vw.index for vw in self.vocab.vocab_words()}
        flat: List[int] = []
        lens = np.empty(len(seqs), np.int64)
        for si, seq in enumerate(seqs):
            ids = [i for i in map(lookup.get, seq) if i is not None]
            flat.extend(ids)
            lens[si] = len(ids)
        return np.asarray(flat, np.int32), lens

    def _keep_probs(self) -> np.ndarray:
        """Per-vocab-index subsampling keep probability
        P(keep) = sqrt(t/f) + t/f (word2vec's formula), computed once per
        vocab and cached (both the vectorized and the per-sentence paths
        index this array, so the two cannot drift)."""
        if self._keep_cache is None:
            # vocab_words() is index-ordered, so position == vocab index
            counts = np.array([vw.count for vw in self.vocab.vocab_words()],
                              np.float64)
            f = counts / self.vocab.total_word_occurrences
            self._keep_cache = np.minimum(
                1.0, np.sqrt(self.sampling / f) + self.sampling / f)
        return self._keep_cache

    def _fit_vectorized(self, seqs, total_words: float,
                        batch: "_PairBatcher") -> None:
        """Corpus-level vectorized NS skip-gram training: encode once, then
        per epoch run chunked whole-corpus windowing (subsampling and the
        shrinking window drawn as arrays, sentence boundaries enforced by a
        mask) and ship the (center, context) id arrays straight to the
        scanned device kernel. Replaces the per-sentence Python loop that
        made r3's word2vec number measure host CPU contention instead of
        the chip."""
        flat, lens = self._encode_corpus(seqs)
        if flat.size == 0:
            return
        starts = np.zeros(lens.size + 1, np.int64)
        np.cumsum(lens, out=starts[1:])
        keep = self._keep_probs() if self.sampling > 0 else None
        # chunk edges in sentence space, each chunk ~_CHUNK_TOKENS ids
        edges = [0]
        tok = 0
        for si in range(lens.size):
            tok += int(lens[si])
            if tok >= self._CHUNK_TOKENS:
                edges.append(si + 1)
                tok = 0
        if edges[-1] != lens.size:
            edges.append(lens.size)
        words_seen = 0.0
        for _ in range(self.epochs * self.iterations):
            for ci in range(len(edges) - 1):
                i, j = edges[ci], edges[ci + 1]
                ids = flat[starts[i]:starts[j]]
                lens_c = lens[i:j]
                if ids.size == 0:
                    continue
                if keep is not None:
                    m = self._rng.random(ids.size) < keep[ids]
                    sent_idx = np.repeat(np.arange(j - i), lens_c)
                    ids = ids[m]
                    lens_c = np.bincount(sent_idx[m], minlength=j - i)
                centers, contexts, counts = _window_pairs(
                    ids, lens_c, self.window, self._rng)
                if centers.size:
                    # per-PAIR linear alpha decay, indexed by the word
                    # position each pair's center occupies — finer than the
                    # reference's per-sentence decay
                    # (`SequenceVectors.java:260`), and in particular still
                    # decaying inside a single-chunk corpus
                    pos = np.repeat(np.arange(ids.size), counts)
                    alphas = np.maximum(
                        self.min_learning_rate,
                        self.learning_rate
                        * (1.0 - (words_seen + pos) / total_words)
                    ).astype(np.float32)
                    batch.add_pairs(centers, contexts, alphas)
                words_seen += float(ids.size)

    def _to_ids(self, seq: Sequence[str]) -> List[int]:
        keep = self._keep_probs() if self.sampling > 0 else None
        ids = []
        for tok in seq:
            i = self.vocab.index_of(tok)
            if i < 0:
                continue
            if keep is not None and self._rng.random() > keep[i]:
                continue
            ids.append(i)
        return ids

    def _train_sequence(self, ids: List[int], alpha: float, batch: "_PairBatcher"):
        window = self.window
        if self.algorithm == "skipgram" and self.negative > 0 \
                and not self.use_hs:
            # vectorized fast path (the common NS configuration): build the
            # whole sentence's (center, context) pair list with array ops —
            # the per-pair Python loop was the training bottleneck, not the
            # XLA scatter step. (SequenceVectors.fit no longer comes here —
            # it runs the chunked corpus-level _fit_vectorized — but
            # ParagraphVectors DBOW word training still does, per document.)
            arr = np.asarray(ids, np.int32)
            centers, contexts, _ = _window_pairs(
                arr, np.array([len(ids)], np.int64), window, self._rng)
            batch.add_pairs(centers, contexts, alpha)
            return
        for pos, center in enumerate(ids):
            b = int(self._rng.integers(1, window + 1))  # shrinking window
            lo, hi = max(0, pos - b), min(len(ids), pos + b + 1)
            context = [ids[j] for j in range(lo, hi) if j != pos]
            if not context:
                continue
            if self.algorithm == "skipgram":
                for c in context:
                    batch.add_pair(center, c, alpha)
            elif self.algorithm == "cbow":
                batch.add_cbow(context, center, alpha)
            else:
                raise ValueError(self.algorithm)

    # hooks used by _PairBatcher ------------------------------------------
    def _kernels(self):
        """(skipgram_step, cbow_step) — module-level jits single-chip, or
        mesh-sharded jits when a mesh was given (batch on the data axis,
        tables replicated; XLA inserts the ICI all-reduce of the scatter
        contributions)."""
        if self.mesh is None:
            return kernels.skipgram_step, kernels.cbow_step
        if self._sharded_kernels is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            bsh = NamedSharding(self.mesh, P(self.data_axis))
            sg = jax.jit(kernels.skipgram_step.__wrapped__,
                         in_shardings=(repl, repl, bsh, bsh, bsh, bsh, repl),
                         out_shardings=(repl, repl, None),
                         donate_argnums=(0, 1))
            cb = jax.jit(kernels.cbow_step.__wrapped__,
                         in_shardings=(repl, repl, bsh, bsh, bsh, bsh, bsh,
                                       repl),
                         out_shardings=(repl, repl, None),
                         donate_argnums=(0, 1))
            self._sharded_kernels = (sg, cb)
        return self._sharded_kernels

    def _ns_kernel(self):
        """Device-side negative-sampling scanned skip-gram step (see
        `kernels.skipgram_ns_scan`). Sharded variant draws are identical to
        the single-chip ones because threefry is partitionable — mesh vs
        single-chip parity holds bit-for-bit (enforced by
        `kernels.require_partitionable_rng`)."""
        kernels.require_partitionable_rng()
        if self.mesh is None:
            return kernels.skipgram_ns_scan
        if self._sharded_ns_kernel is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            bsh = NamedSharding(self.mesh, P(None, self.data_axis))
            self._sharded_ns_kernel = jax.jit(
                kernels.skipgram_ns_scan.__wrapped__,
                in_shardings=(repl, repl, bsh, bsh, repl, repl, repl, repl,
                              repl),
                out_shardings=(repl, repl, None, None),
                donate_argnums=(0, 1, 6), static_argnums=(9,))
        return self._sharded_ns_kernel

    def _ns_device_state(self):
        """(device cdf, carried PRNG key) for on-device negative sampling.
        The cdf ships as uint32 fixed point (f64 cumsum × 2^32): f32 would
        round adjacent tail entries of a large vocabulary equal, making
        those words unsampleable (see `kernels._ns_batch`)."""
        if self._unigram_cdf is None:
            self._unigram_cdf = np.cumsum(self._unigram)
        if self._ns_cdf_dev is None:
            fixed = np.minimum(np.round(self._unigram_cdf * 2.0 ** 32),
                               2.0 ** 32 - 1).astype(np.uint32)
            self._ns_cdf_dev = jnp.asarray(fixed)
        if self._ns_key is None:
            self._ns_key = jax.random.PRNGKey(self.seed)
        return self._ns_cdf_dev, self._ns_key

    def _sample_negatives(self, n) -> np.ndarray:
        """Draw from the 0.75-power unigram distribution. Inverse-CDF via
        searchsorted: O(log V) per draw and fully vectorizable — the
        per-pair `rng.choice(p=...)` it replaces rebuilt an O(V) sampler
        per call and dominated the whole training loop. `n` may be a shape
        tuple."""
        if self._unigram_cdf is None:
            self._unigram_cdf = np.cumsum(self._unigram)
        idx = np.searchsorted(self._unigram_cdf, self._rng.random(n))
        # cumsum rounding can leave cdf[-1] slightly below 1.0, in which
        # case a draw above it would index past the vocabulary
        return np.minimum(idx, len(self._unigram) - 1).astype(np.int32)

    def _reset_loss(self) -> None:
        """Zero ALL loss-accumulation state (host f64 sum, batch count, and
        the carried device accumulator) — every fit entry point must call
        this, or a prior fit's undrained device sum leaks into the next."""
        self._loss_sum, self._loss_batches = 0.0, 0
        self._loss_dev, self._loss_dev_count = None, 0

    def _record_loss(self, loss) -> None:
        """Accumulate WITHOUT a per-flush host sync: reading `float(loss)`
        per flush stalls the dispatch queue once per flush and dominated
        training wall-clock. The per-flush losses chain into ONE device
        scalar (an async eager add — never a list of buffers: fetching N
        separate device scalars costs N syncs), which is folded into
        the host f64 sum every `_LOSS_FOLD` flushes with a single one-
        scalar sync — an f32 running sum alone would stop absorbing small
        increments on very long runs."""
        self._loss_dev = loss if self._loss_dev is None else self._loss_dev + loss
        self._loss_batches += 1
        self._loss_dev_count += 1
        if self._loss_dev_count >= self._LOSS_FOLD:
            self._drain_loss()

    def _record_loss_acc(self, acc, n_batches: int = 1) -> None:
        """Store a kernel-carried running sum (the accumulation already
        happened inside the jitted step — no eager dispatch here)."""
        self._loss_dev = acc
        self._loss_batches += n_batches
        self._loss_dev_count += n_batches
        if self._loss_dev_count >= self._LOSS_FOLD:
            self._drain_loss()

    _LOSS_FOLD = 256

    def _drain_loss(self) -> None:
        if self._loss_dev is not None:
            self._loss_sum += float(self._loss_dev)
            self._loss_dev = None
            self._loss_dev_count = 0

    @property
    def mean_loss(self) -> float:
        self._drain_loss()
        return self._loss_sum / max(self._loss_batches, 1)

    # -- query passthrough --------------------------------------------------
    def words_nearest(self, word, top_n: int = 10):
        return self.lookup_table.words_nearest(word, top_n)

    def similarity(self, w1: str, w2: str) -> float:
        return self.lookup_table.similarity(w1, w2)

    def get_word_vector(self, word: str):
        return self.lookup_table.vector(word)


def _window_pairs(ids: np.ndarray, lens: np.ndarray, window: int,
                  rng) -> tuple:
    """Skip-gram windowing over a chunk of concatenated sentences, fully
    vectorized: per-position shrinking windows b ~ U[1, window] drawn as one
    array, an (L, 2*window) index grid, and a validity mask that enforces
    both the window radius and same-sentence bounds. Returns aligned
    (centers, contexts) int32 arrays plus the per-position pair count —
    the host half of the reference's `AggregateSkipGram` native op
    (`SkipGram.java:216`)."""
    L = ids.size
    if L == 0:
        return (np.empty(0, np.int32),) * 2 + (np.empty(0, np.int64),)
    b = rng.integers(1, window + 1, L)  # shrinking windows
    offs = np.concatenate([np.arange(-window, 0), np.arange(1, window + 1)])
    grid = np.arange(L)[:, None] + offs[None, :]
    ends = np.cumsum(lens)
    sent_of = np.repeat(np.arange(lens.size), lens)
    lo = (ends - lens)[sent_of][:, None]
    hi = ends[sent_of][:, None]
    valid = ((np.abs(offs)[None, :] <= b[:, None])
             & (grid >= lo) & (grid < hi))
    counts = valid.sum(1)
    centers = np.repeat(ids, counts)
    contexts = ids[grid[valid]]  # row-major: aligned with repeat
    return centers, contexts, counts


class _PairBatcher:
    """Accumulates training examples into fixed-shape arrays and flushes
    them through the jitted kernels (fixed batch shape ⇒ one XLA
    compilation; the tail batch is mask-padded)."""

    def __init__(self, sv: SequenceVectors):
        self.sv = sv
        B = sv.batch_size
        # target row count: negatives+1 (NS) and/or max code length (HS)
        self._max_codes = 0
        if sv.use_hs:
            self._max_codes = max((len(vw.codes)
                                   for vw in sv.vocab.vocab_words()), default=0)
        self.K = (sv.negative + 1 if sv.negative > 0 else 0) + self._max_codes
        self.W = 2 * sv.window
        self.center = np.zeros(B, np.int32)
        self.targets = np.zeros((B, self.K), np.int32)
        self.labels = np.zeros((B, self.K), np.float32)
        self.mask = np.zeros((B, self.K), np.float32)
        self.context = np.zeros((B, self.W), np.int32)
        self.cmask = np.zeros((B, self.W), np.float32)
        # pair-mode staging: scan_k flush-batches accumulate and go to the
        # device as ONE scanned dispatch (per-dispatch host latency is
        # the throughput ceiling, so amortize it over scan_k batches)
        self.scan_k = max(1, int(getattr(sv, "scan_flushes", 32)))
        self.pair_center = np.zeros(B * self.scan_k, np.int32)
        self.pair_context = np.zeros(B * self.scan_k, np.int32)
        self.row_alpha = np.full(self.scan_k, 0.025, np.float32)
        self.alpha = 0.025
        self.n = 0
        # "pairs" = NS-only skip-gram fast path (negatives drawn on device,
        # flush ships two (scan_k, B) id arrays); "generic" = host-built
        # (B, K) target/label/mask rows (HS, CBOW, ParagraphVectors
        # add_pair). A batcher serves ONE mode for its lifetime.
        self._mode: Optional[str] = None

    def _fill_targets(self, row: int, predicted: int):
        """Targets for predicting word id `predicted`: NS = [pos, negs];
        HS = its Huffman path (labels = 1 - code)."""
        sv = self.sv
        k = 0
        if sv.negative > 0:
            self.targets[row, 0] = predicted
            self.labels[row, 0] = 1.0
            self.mask[row, 0] = 1.0
            negs = sv._sample_negatives(sv.negative)
            for ng in negs:
                k += 1
                self.targets[row, k] = ng
                self.labels[row, k] = 0.0
                # word2vec skips a negative that equals the positive
                self.mask[row, k] = 0.0 if ng == predicted else 1.0
            k += 1
        if sv.use_hs:
            vw = sv.vocab.element_at_index(predicted)
            for code, point in zip(vw.codes, vw.points):
                self.targets[row, k] = point
                self.labels[row, k] = 1.0 - code
                self.mask[row, k] = 1.0
                k += 1

    def add_pairs(self, centers: np.ndarray, contexts: np.ndarray,
                  alpha):
        """Bulk skip-gram add (NS-only fast path): stages just the
        (center, context) id pairs — negatives, labels, and masks are built
        on device by `skipgram_ns_scan`. `alpha` is a scalar or a per-pair
        array (the kernel applies one learning rate per flush-row of B
        pairs; an array alpha sets each row's rate from its first pair)."""
        if self._mode == "generic":
            raise RuntimeError("batcher already in generic mode")
        self._mode = "pairs"
        B = len(self.center)
        cap = len(self.pair_center)
        i, n_total = 0, len(centers)
        while i < n_total:
            take = min(cap - self.n, n_total - i)
            rows = slice(self.n, self.n + take)
            self.pair_center[rows] = centers[i:i + take]
            self.pair_context[rows] = contexts[i:i + take]
            r0, r1 = self.n // B, (self.n + take - 1) // B + 1
            if np.ndim(alpha) == 0:
                self.row_alpha[r0:r1] = alpha
            else:
                firsts = np.maximum(np.arange(r0, r1) * B, self.n) \
                    - self.n + i
                self.row_alpha[r0:r1] = alpha[firsts]
            self.n += take
            i += take
            if self.n == cap:
                self.flush()

    def add_pair(self, center: int, context: int, alpha: float):
        """Skip-gram: center predicts context. In the NS-only configuration
        this stages the raw pair for device-side sampling (same mode as
        add_pairs, so DBOW doc-pairs and word training share one batcher);
        with hierarchical softmax the targets are built host-side."""
        sv = self.sv
        if sv.negative > 0 and not sv.use_hs:
            if self._mode == "generic":
                raise RuntimeError("batcher already in generic mode")
            self._mode = "pairs"
            row = self.n
            self.pair_center[row] = center
            self.pair_context[row] = context
            self.row_alpha[row // len(self.center)] = alpha
            self.n += 1
            if self.n == len(self.pair_center):
                self.flush()
            return
        if self._mode == "pairs":
            raise RuntimeError("batcher already in pairs mode")
        self._mode = "generic"
        row = self.n
        self.center[row] = center
        self.targets[row] = 0
        self.labels[row] = 0
        self.mask[row] = 0
        self._fill_targets(row, context)
        self.alpha = alpha
        self.n += 1
        if self.n == len(self.center):
            self.flush()

    def add_cbow(self, context: List[int], center: int, alpha: float):
        if self._mode == "pairs":
            raise RuntimeError("batcher already in pairs mode")
        self._mode = "generic"
        row = self.n
        self.context[row] = 0
        self.cmask[row] = 0
        w = min(len(context), self.W)
        self.context[row, :w] = context[:w]
        self.cmask[row, :w] = 1.0
        self.targets[row] = 0
        self.labels[row] = 0
        self.mask[row] = 0
        self._fill_targets(row, center)
        self.alpha = alpha
        self.n += 1
        if self.n == len(self.center):
            self.flush()

    def flush(self):
        if self.n == 0:
            return
        sv = self.sv
        lt = sv.lookup_table
        # COPY the staging buffers before dispatch: device_put of a numpy
        # array can be ZERO-COPY (it aliases host memory, notably on the CPU
        # backend), and the async step may still be reading while the next
        # batch overwrites these rows. Without copies, training corrupts
        # nondeterministically once nothing forces a per-flush sync.
        ja = lambda a: jnp.asarray(np.array(a))  # np.array always copies
        lr = jnp.float32(self.alpha)
        if self._mode == "pairs":
            cdf, key = sv._ns_device_state()
            step = sv._ns_kernel()
            acc = (sv._loss_dev if sv._loss_dev is not None
                   else jnp.float32(0.0))
            B = len(self.center)
            Ks = self.scan_k
            # always dispatch the full (scan_k, B) shape — tail rows get
            # nvalid=0 (fully masked) so there is exactly ONE compilation
            nvalids = np.clip(self.n - np.arange(Ks) * B, 0, B).astype(np.int32)
            n_rows = -(-self.n // B)  # batches actually represented
            lt.syn0, lt.syn1neg, new_acc, sv._ns_key = step(
                lt.syn0, lt.syn1neg,
                ja(self.pair_center.reshape(Ks, B)),
                ja(self.pair_context.reshape(Ks, B)),
                cdf, key, acc, ja(self.row_alpha), ja(nvalids), sv.negative)
            sv._record_loss_acc(new_acc, n_batches=n_rows)
            self.n = 0
            return
        self.mask[self.n:] = 0.0
        self.cmask[self.n:] = 0.0
        syn1 = lt.syn1neg if sv.negative > 0 else lt.syn1
        skipgram_step, cbow_step = sv._kernels()
        if sv.use_hs and sv.negative > 0:
            # mixed mode: split columns — NS rows live in syn1neg, HS rows
            # in syn1; run two steps on the column slices
            ns_cols = sv.negative + 1
            center = ja(self.center)
            lt.syn0, lt.syn1neg, loss1 = skipgram_step(
                lt.syn0, lt.syn1neg, center,
                ja(self.targets[:, :ns_cols]),
                ja(self.labels[:, :ns_cols]),
                ja(self.mask[:, :ns_cols]), lr)
            lt.syn0, lt.syn1, loss2 = skipgram_step(
                lt.syn0, lt.syn1, center,
                ja(self.targets[:, ns_cols:]),
                ja(self.labels[:, ns_cols:]),
                ja(self.mask[:, ns_cols:]), lr)
            sv._record_loss(loss1 + loss2)
        elif sv.algorithm == "cbow":
            lt.syn0, new_syn1, loss = cbow_step(
                lt.syn0, syn1, ja(self.context),
                ja(self.cmask), ja(self.targets),
                ja(self.labels), ja(self.mask), lr)
            self._store_syn1(new_syn1)
            sv._record_loss(loss)
        else:
            lt.syn0, new_syn1, loss = skipgram_step(
                lt.syn0, syn1, ja(self.center),
                ja(self.targets), ja(self.labels),
                ja(self.mask), lr)
            self._store_syn1(new_syn1)
            sv._record_loss(loss)
        self.n = 0

    def _store_syn1(self, new_syn1):
        lt = self.sv.lookup_table
        if self.sv.negative > 0:
            lt.syn1neg = new_syn1
        else:
            lt.syn1 = new_syn1
