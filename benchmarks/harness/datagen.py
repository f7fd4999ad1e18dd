"""The one traffic generator: a seeded stream of training batches.

Documents have log-normal lengths with the short ones left out (a
corpus filter: drawn again, not clipped); each starts with the
end-of-text id and continues with tokens drawn Zipf(exponent) over a
fixed permutation of the rest of the vocabulary (which ids are the
frequent ones is a constant of the traffic mix, never the run's seed:
see ``BatchStream``).  Documents are
concatenated into one stream and the stream is cut into sequences of
``seq_len``, as a GPT data loader does: a document that crosses the cut
continues at the head of the next sequence.  A traffic file gives the
parameters and names the corpus they come from; the program under test
sees only the batches: ``input_ids`` [gas, batch, seq] and, where the
file says so, ``segment_ids`` numbering the documents of each sequence.

Batches are produced on one host thread ``PREFETCH_BATCHES`` ahead of the
training loop, a fresh batch every optimizer step.
"""
import math
import queue
import threading
import time

import numpy as np

PREFETCH_BATCHES = 2


class Documents:
    """The stream of documents cut into rows of ``seq_len`` tokens."""

    def __init__(self, rng, spec):
        self.rng, self.spec = rng, spec
        self.left = 0           # of the document the last row cut

    def _length(self):
        while True:
            n = self.rng.lognormal(math.log(self.spec["median"]),
                                   self.spec["sigma"])
            if n >= self.spec["min"]:
                return int(n)

    def row(self, seq_len):
        """[(tokens, starts a document)] of one row; they sum to seq_len."""
        out, room = [], seq_len
        while room > 0:
            starts = self.left == 0
            if starts:
                self.left = self._length()
            n = min(self.left, room)
            out.append((n, starts))
            self.left -= n
            room -= n
        return out


def effective_context(traffic):
    """S_eff: the mean number of keys a query attends over, times two —
    sum(len^2) / sum(len) over attention spans.  An unpacked stream has
    one span per sequence, so S_eff = seq_len.  A packed one has a span
    per piece of a document inside a sequence; its expectation is taken
    over a fixed sample of the traffic file's own length distribution
    (never the run's seed), so it is a constant of the traffic mix."""
    seq_len = traffic["seq_len"]
    if not traffic["segment_ids"]:
        return float(seq_len)
    documents = Documents(np.random.default_rng(0), traffic["documents"])
    lens = np.array([n for _ in range(4096)
                     for n, _ in documents.row(seq_len)], np.float64)
    return float((lens ** 2).sum() / lens.sum())


class BatchStream:
    """Seeded batches on a prefetch thread.  ``next()`` returns a batch
    and records how long the caller waited for it."""

    def __init__(self, traffic, vocab_size, global_micro_batch, seed):
        self.traffic = traffic
        self.vocab = vocab_size
        self.shape = (traffic["gradient_accumulation_steps"],
                      global_micro_batch, traffic["seq_len"])
        self.rng = np.random.default_rng(seed)
        self.documents = Documents(self.rng, traffic["documents"])
        tok = traffic["tokens"]
        # rank r (1-based) has weight r^-exponent; ranks map to ids by a
        # permutation of every id but the end-of-text one that is the same
        # in every run, as effective_context's sample is: rank 1 is a tenth
        # of all tokens, so which embedding rows the frequent ranks read
        # decides which experts a sigmoid router that does not balance
        # itself sends them to, and with a permutation from the run's seed
        # the routed cells' rate followed --seed by 2.5% (PERF.md PR 41)
        self.eot = vocab_size - 1
        weights = np.arange(1, vocab_size, dtype=np.float64) \
            ** -float(tok["zipf_exponent"])
        self.cdf = np.cumsum(weights / weights.sum())
        self.ids = np.random.default_rng(0).permutation(
            vocab_size - 1).astype(np.int32)
        self.waits_s = []
        self._queue = queue.Queue(maxsize=PREFETCH_BATCHES)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="bench-datagen")
        self._thread.start()

    def make_batch(self):
        gas, batch, seq = self.shape
        n = gas * batch
        ranks = np.searchsorted(self.cdf, self.rng.random((n, seq)))
        tokens = self.ids[np.minimum(ranks, self.vocab - 2)]
        segments = np.zeros((n, seq), np.int32)
        for row in range(n):
            start = 0
            for i, (length, starts) in enumerate(self.documents.row(seq)):
                if starts:
                    tokens[row, start] = self.eot
                segments[row, start:start + length] = i
                start += length
        out = {"input_ids": tokens.reshape(self.shape)}
        if self.traffic["segment_ids"]:
            out["segment_ids"] = segments.reshape(self.shape)
        return out

    def _produce(self):
        while not self._stop.is_set():
            batch = self.make_batch()
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.05)
                    break
                except queue.Full:
                    pass

    def next(self):
        t0 = time.perf_counter()
        batch = self._queue.get()
        self.waits_s.append(time.perf_counter() - t0)
        return batch

    def close(self):
        self._stop.set()
        self._thread.join()
