"""Data pipeline: packing correctness (vs brute force), list-ranking
metadata, determinism; hypothesis on packing invariants."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.launch.mesh import make_mesh
from repro.data import packing, pipeline


def _docs(seed, n_docs=12, max_len=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 1000, rng.integers(1, max_len)).astype(np.int32)
            for _ in range(n_docs)]


def test_pack_roundtrip_tokens():
    docs = _docs(0)
    packed = packing.pack_documents(docs, row_len=64)
    # every document's tokens appear contiguously across its segments
    term, after = packing.segment_metadata(packed)
    doc_id, pos, rem = packing.token_metadata(packed, term, after)
    for d, doc in enumerate(docs):
        mask = doc_id == d
        got = packed.rows[mask]
        order = np.argsort(pos[mask])
        np.testing.assert_array_equal(got[order], doc)
        # positions are 0..len-1 and remaining counts down
        np.testing.assert_array_equal(np.sort(pos[mask]),
                                      np.arange(len(doc)))
        np.testing.assert_array_equal(
            np.sort(rem[mask])[::-1], np.sort(len(doc) - 1 - pos[mask])[::-1])


def test_segment_metadata_is_list_ranking():
    """The segment instance is a valid list-ranking input and the
    ranks equal tokens-after-segment."""
    docs = _docs(3)
    packed = packing.pack_documents(docs, row_len=32)
    term, after = packing.segment_metadata(packed)
    # terminal of every chain is the doc's last segment: 0 tokens after
    last = {}
    for s, d in enumerate(packed.segment_doc):
        last[d] = s
    for s, d in enumerate(packed.segment_doc):
        assert term[s] == last[d]
    # tokens after = sum of later segment lengths
    for d in last:
        segs = [s for s in range(len(packed.segment_doc))
                if packed.segment_doc[s] == d]
        for i, s in enumerate(segs):
            expect = sum(packed.segment_len[t] for t in segs[i + 1:])
            assert after[s] == expect


def test_distributed_matches_oracle():
    import jax
    mesh = make_mesh((1,), ("pe",))
    docs = _docs(7, n_docs=30)
    packed = packing.pack_documents(docs, row_len=48)
    t1, a1 = packing.segment_metadata(packed)
    t2, a2 = packing.segment_metadata(packed, mesh=mesh)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(a1, a2)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), row_len=st.sampled_from([16, 32, 80]),
       n_docs=st.integers(1, 25))
def test_property_packing_conserves_tokens(seed, row_len, n_docs):
    docs = _docs(seed, n_docs=n_docs, max_len=3 * row_len)
    packed = packing.pack_documents(docs, row_len)
    total = sum(len(d) for d in docs)
    term, after = packing.segment_metadata(packed)
    doc_id, pos, rem = packing.token_metadata(packed, term, after)
    assert (doc_id >= 0).sum() == total
    assert packed.segment_len.sum() == total
    # each segment chain's rank decreases along the chain
    assert (after >= 0).all()


def test_pipeline_determinism_and_shapes():
    cfg = pipeline.DataConfig(vocab_size=1000, seq_len=64, global_batch=4)
    b1 = pipeline.global_batch(cfg, step=5)
    b2 = pipeline.global_batch(cfg, step=5)
    b3 = pipeline.global_batch(cfg, step=6)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (4, 64)
    assert b1["labels"].shape == (4, 64)
    assert (b1["labels"][b1["labels"] >= 0] < 1000).all()
