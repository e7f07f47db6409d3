"""Synthetic training traffic and the loader thread that feeds the window.

The generator is a copy of the repository's C4-like stream (Zipf(1.3)
unigrams, repeated 8-grams), kept here so that the yardstick cannot move
with the program.  Parameters come from a traffic file under
``bench/traffic/``, whose ``kind`` picks the labels:

* ``mlm``: BERT masking, 15% of positions with 80/10/10;
* ``clm``: next-token prediction over a stream of documents (see
  :func:`clm_batch`).

Batch ``i`` of a run is a function of ``(seed, i)`` alone, so the reference
can draw the same rows again.
"""
from __future__ import annotations

import queue
import threading

import numpy as np

EOS_ID = 2
MASK_ID = 4
IGNORE = -1
SPECIAL_IDS = 8                 # ids below this are reserved for specials


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, index]))


def synthetic_tokens(rng: np.random.Generator, batch: int, seq: int,
                     vocab: int, *, zipf_a: float, ngram: int) -> np.ndarray:
    """Zipf unigrams, about half of each row overwritten by repeats of the
    n-gram just before the write position."""
    toks = rng.zipf(zipf_a, size=(batch, seq)) % (vocab - SPECIAL_IDS)
    toks = toks + SPECIAL_IDS
    ngram = min(ngram, max(seq // 4, 1))
    n_rep = seq // (2 * ngram)
    if n_rep and seq - ngram > ngram:
        for b in range(batch):
            for s in rng.integers(ngram, seq - ngram, size=n_rep):
                toks[b, s:s + ngram] = toks[b, s - ngram:s]
    return toks.astype(np.int32)


def mlm_mask(rng: np.random.Generator, tokens: np.ndarray, vocab: int,
             prob: float):
    """BERT masking: ``prob`` of positions are targets; of those 80% become
    [MASK], 10% a random id, 10% stay.  Labels are -1 off target."""
    mask = rng.random(tokens.shape) < prob
    labels = np.where(mask, tokens, IGNORE).astype(np.int32)
    r = rng.random(tokens.shape)
    corrupted = tokens.copy()
    corrupted[mask & (r < 0.8)] = MASK_ID
    rand_sel = mask & (r >= 0.8) & (r < 0.9)
    corrupted[rand_sel] = rng.integers(SPECIAL_IDS, vocab,
                                       size=int(rand_sel.sum()))
    return corrupted.astype(np.int32), labels


def mlm_batch(rng: np.random.Generator, traffic: dict, batch: int,
              vocab: int) -> dict:
    toks = synthetic_tokens(rng, batch, traffic["seq"], vocab,
                            zipf_a=traffic["zipf_a"], ngram=traffic["ngram"])
    tokens, labels = mlm_mask(rng, toks, vocab, traffic["mask_prob"])
    return {"tokens": tokens, "labels": labels}


def clm_batch(rng: np.random.Generator, traffic: dict, batch: int,
              vocab: int) -> dict:
    """Next-token rows cut from one stream of documents.  Each document has
    a lognormal length (``doc_len_median``, ``doc_len_sigma``) of Zipf
    tokens with repeated n-grams, then ``EOS_ID``; the last one is cut where
    the batch ends.  The stream is cut into ``batch`` rows of ``seq + 1``:
    ``tokens`` is a row less its last id, ``labels`` the row less its first,
    every position a target.  Attention is not masked at document
    boundaries (the program has no segment mask), so a row's later
    documents see its earlier ones."""
    seq = traffic["seq"]
    need = batch * (seq + 1)
    mu = np.log(traffic["doc_len_median"])
    docs, have = [], 0
    while have < need:
        n = max(int(round(np.exp(mu + traffic["doc_len_sigma"]
                                 * rng.standard_normal()))), 1)
        doc = synthetic_tokens(rng, 1, n, vocab, zipf_a=traffic["zipf_a"],
                               ngram=traffic["ngram"])[0]
        docs.append(np.append(doc, np.int32(EOS_ID))[:need - have])
        have += len(docs[-1])
    rows = np.concatenate(docs).reshape(batch, seq + 1)
    return {"tokens": rows[:, :-1].copy(), "labels": rows[:, 1:].copy()}


KINDS = {"mlm": mlm_batch, "clm": clm_batch}


def make_batch(traffic: dict, batch: int, vocab: int, seed: int,
               index: int) -> dict:
    """Batch ``index`` of the stream: ``tokens`` and ``labels``, (batch, seq)."""
    return KINDS[traffic["kind"]](_rng(seed, index), traffic, batch, vocab)


class Loader:
    """Background thread that keeps ``prefetch`` batches ready, in order."""

    def __init__(self, traffic: dict, batch: int, vocab: int, seed: int,
                 prefetch: int = 2):
        self._args = (traffic, batch, vocab, seed)
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self) -> None:
        index = 0
        while not self._stop.is_set():
            b = make_batch(*self._args, index)
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    continue
            index += 1

    def get(self) -> dict:
        return self._q.get()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise RuntimeError("loader thread did not stop")
