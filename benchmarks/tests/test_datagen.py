"""The traffic generator against what its traffic files say of the corpus."""
import numpy as np
import pytest

from harness import datagen
from harness.manifest import Manifest

TRAFFIC = sorted({w["traffic"] for w in Manifest().data["workloads"]})


@pytest.mark.parametrize("name", TRAFFIC)
def test_document_lengths_have_the_sources_floor_and_mean(name):
    spec = Manifest().traffic(name)["documents"]
    documents = datagen.Documents(np.random.default_rng(3), spec)
    lens = np.array([documents._length() for _ in range(200_000)])
    # the source's floor (a filter: no length under it) and its mean
    # (tokens / documents), which the file's median was solved for
    assert lens.min() >= spec["min"]
    assert abs(lens.mean() / spec["mean"] - 1) < 0.02


def test_the_stream_is_cut_into_rows_and_documents_go_on():
    traffic = {**Manifest().traffic(TRAFFIC[0]), "seq_len": 256,
               "gradient_accumulation_steps": 2, "segment_ids": True}
    stream = datagen.BatchStream(traffic, vocab_size=1000,
                                 global_micro_batch=8, seed=5)
    try:
        batch = stream.next()
    finally:
        stream.close()
    ids = batch["input_ids"].reshape(-1, 256)
    seg = batch["segment_ids"].reshape(-1, 256)
    assert ids.shape == seg.shape == (16, 256)
    starts = ids == 999                              # the end-of-text id
    # a new segment begins exactly where a document does; the head of a
    # row is either a new document or the rest of the last row's
    assert (starts[:, 1:] == (np.diff(seg, axis=1) == 1)).all()
    assert (np.diff(seg, axis=1) >= 0).all() and (seg[:, 0] == 0).all()
    floor = traffic["documents"]["min"]
    assert 0 < starts.mean() < 1 / floor + 1e-3     # none under the floor


def test_effective_context_is_a_constant_of_the_file():
    packed = [Manifest().traffic(n) for n in TRAFFIC]
    packed = [t for t in packed if t["segment_ids"]]
    assert packed, "no packed traffic mix"
    for traffic in packed:
        s_eff = datagen.effective_context(traffic)
        assert s_eff == datagen.effective_context(traffic)
        assert 128 < s_eff < traffic["seq_len"]


def test_which_ids_are_frequent_is_a_constant_of_the_mix():
    """``--seed`` draws the documents' lengths and the tokens' ranks; the
    permutation of ranks to ids is the same in every run (PERF.md PR 41:
    which embedding rows the frequent tokens read moved the routed cells'
    rate by 2.5% from seed to seed)."""
    traffic = {**Manifest().traffic(TRAFFIC[0]), "seq_len": 256,
               "gradient_accumulation_steps": 1, "segment_ids": True}
    batches, ids = [], []
    for seed in (5, 6):
        stream = datagen.BatchStream(traffic, vocab_size=1000,
                                     global_micro_batch=64, seed=seed)
        try:
            batches.append(stream.next()["input_ids"].ravel())
        finally:
            stream.close()
        ids.append(stream.ids)
    np.testing.assert_array_equal(ids[0], ids[1])
    assert sorted(ids[0]) == list(range(999))       # every id but eot's
    assert not np.array_equal(batches[0], batches[1])
    top = [np.argsort(np.bincount(b, minlength=1000)[:999])[-5:]
           for b in batches]
    np.testing.assert_array_equal(top[0], top[1])
    np.testing.assert_array_equal(top[0][::-1], ids[0][:5])
