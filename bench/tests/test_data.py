"""The traffic generator: masked-LM batches as they were pinned, and
next-token batches cut from one stream of documents."""
import hashlib
import itertools

import numpy as np
import pytest

from bench import data as D
from bench import harness as H

VOCAB = 32128
# sha256 of tokens then labels, batches 0-2, pinned before the next-token
# kind was added: the masked-LM path draws the same numbers in the same order
PINNED = {
    ("mlm16x512", 0): [
        "c22fdf0d425d074bc23b5580a8d3d13944631f5b255ea879d36f721705522ed2",
        "696aa527a51b1c50994eb1adedca39264dd468cb29a4c06401192c84ee0f2f47",
        "fff3b40d4d686152c26da1f15a45b7db80c77ea2f4af989b584640093690d1a3"],
    ("mlm16x512", 2**31 + 77): [
        "4027906f459bd1e7e2344396e36e26546f05f7eb696ffde60d07b38a774f15c1",
        "66223954c586c1a367662c12176ed5bfd3d7c663d5789c0bcc1b8b444682d81d",
        "64c53592d4017357c7bb74e0a6547c6cecf9cb5390627118d297ca8c8b2109aa"],
    ("mlm64x512", 0): [
        "0714ad6abcfd521063c84ed3086668121640aacdb4c41ba30ef7096c1fb208ef",
        "6a81b4324e727aefdc1eacf54a5fe79961c2fe0d91c34ed6d71b711199d09c5b",
        "1733d0ca91503a801b07dcaa17dd4f570ae5fb2a1e0d50872bda3f191b675c6d"],
    ("mlm64x512", 2**31 + 77): [
        "9fb14de4f8d0ce082359ee8646c9cb57ba8ac94f9200a35fb3c06050e15bae73",
        "04dc14f56757415e931a19b8fa43d1c6ef79fa0775b03753def9e527162a34c7",
        "ea595d8aac8d02db6225a4c1b00f5260f9a3523c0d4eb09271b07ead4327cb48"],
}
CLM = {"kind": "clm", "batch": 8, "seq": 256, "zipf_a": 1.3, "ngram": 8,
       "doc_len_median": 96, "doc_len_sigma": 0.8}
SEED = 2**31 + 91


def _sha(b):
    return hashlib.sha256(b["tokens"].tobytes() + b["labels"].tobytes()).hexdigest()


@pytest.mark.parametrize("mix,seed", sorted(PINNED))
def test_mlm_batches_are_as_pinned(mix, seed):
    traffic = H.load_json("traffic", mix)
    got = [_sha(D.make_batch(traffic, traffic["batch"], VOCAB, seed, i))
           for i in range(3)]
    assert got == PINNED[mix, seed]


def test_clm_labels_are_the_next_tokens():
    b = D.make_batch(CLM, CLM["batch"], VOCAB, SEED, 0)
    assert b["tokens"].shape == b["labels"].shape == (CLM["batch"], CLM["seq"])
    assert b["tokens"].dtype == b["labels"].dtype == np.int32
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"] != D.IGNORE).all()


def test_clm_rows_continue_one_stream(monkeypatch):
    """With each document made of consecutive ids, the rows joined in order
    and read without their EOS ids count up with no gap: each row starts
    where the one before it stopped, and every document ends in EOS."""
    counter = itertools.count()

    def numbered(rng, batch, seq, vocab, **kw):
        return np.array([[D.SPECIAL_IDS + next(counter) for _ in range(seq)]],
                        np.int32)
    monkeypatch.setattr(D, "synthetic_tokens", numbered)
    b = D.make_batch(CLM, CLM["batch"], 10**9, SEED, 0)
    stream = np.concatenate([np.r_[row, lab[-1]]
                             for row, lab in zip(b["tokens"], b["labels"])])
    words = stream[stream != D.EOS_ID]
    np.testing.assert_array_equal(np.diff(words), 1)
    # an EOS after each document but the last, which the batch cuts
    ends = np.flatnonzero(stream == D.EOS_ID)
    assert len(ends) >= CLM["batch"]
    assert (stream[ends[:-1] + 1] != D.EOS_ID).all()


def test_clm_documents_and_ids():
    """Documents split at EOS have lognormal lengths about the median; every
    other id is an ordinary token of the vocabulary."""
    vocab = 12800
    lengths = []
    for i in range(8):
        b = D.make_batch(CLM, CLM["batch"], vocab, SEED, i)
        stream = np.concatenate([np.r_[row, lab[-1]]
                                 for row, lab in zip(b["tokens"], b["labels"])])
        ends = np.flatnonzero(stream == D.EOS_ID)
        lengths += list(np.diff(ends) - 1)
        words = stream[stream != D.EOS_ID]
        assert words.min() >= D.SPECIAL_IDS and words.max() < vocab
    assert len(lengths) > 100
    assert 0.8 * CLM["doc_len_median"] < np.median(lengths) < 1.25 * CLM["doc_len_median"]


def test_clm_batch_is_a_function_of_seed_and_index():
    a = D.make_batch(CLM, CLM["batch"], VOCAB, SEED, 3)
    again = D.make_batch(CLM, CLM["batch"], VOCAB, SEED, 3)
    assert _sha(a) == _sha(again)
    assert _sha(a) != _sha(D.make_batch(CLM, CLM["batch"], VOCAB, SEED, 4))
    assert _sha(a) != _sha(D.make_batch(CLM, CLM["batch"], VOCAB, SEED + 1, 3))
    loader = D.Loader(CLM, CLM["batch"], VOCAB, SEED)
    try:
        got = [_sha(loader.get()) for _ in range(4)]
    finally:
        loader.close()
    assert got[3] == _sha(a)
