"""Paper application workloads: bitmap index, BitWeaving scans, bitvector
sets, BitFunnel filtering, masked init - engine results vs plain numpy."""

import numpy as np
import pytest

from repro.core import BitVector, BulkBitwiseEngine

RNG = np.random.default_rng(0)


@pytest.fixture(params=["jnp", "ambit_sim"])
def engine(request):
    return BulkBitwiseEngine(request.param)


def test_bitmap_index_query(engine):
    from repro.apps.bitmap_index import BitmapIndex
    n = 3000 if engine.backend == "ambit_sim" else 100_000
    idx = BitmapIndex(n, engine)
    weeks = {}
    for w in range(3):
        members = RNG.choice(n, n // 3, replace=False)
        weeks[f"w{w}"] = set(members.tolist())
        idx.add(f"w{w}", members)
    male = RNG.choice(n, n // 2, replace=False)
    idx.add("male", male)
    uniq, per_week, stats = idx.weekly_active_query(list(weeks), "male")
    expect_uniq = len(set.intersection(*weeks.values()))
    assert uniq == expect_uniq
    male_set = set(male.tolist())
    for i, w in enumerate(weeks):
        assert per_week[i] == len(weeks[w] & male_set)
    if engine.backend == "ambit_sim":
        assert stats.ns > 0 and stats.energy_nj > 0


def test_bitmap_weekly_query_batches_one_drain():
    """The resident weekly_active_query submits the AND-of-weeks root and
    every per-week AND as ONE multi-root batch: the scheduler ledger
    shows a single drain of weeks+1 queries instead of one eval per week,
    and the answers still match the host path exactly."""
    from repro.apps.bitmap_index import BitmapIndex
    from repro.core import DRAMGeometry
    from repro.pim import AmbitRuntime

    rng = np.random.default_rng(31)
    n_users = 1200
    weeks = [f"w{i}" for i in range(4)]
    host = BitmapIndex(n_users, BulkBitwiseEngine("jnp"))
    rt = AmbitRuntime(DRAMGeometry(rows_per_subarray=32), banks=4,
                      subarrays=2, words=2, scratch_rows=2, seed=13)
    res = BitmapIndex(n_users, runtime=rt)
    for w in weeks + ["male"]:
        members = rng.choice(n_users, n_users // 3, replace=False)
        host.add(w, members)
        res.add(w, members)
    want_u, want_pw, _ = host.weekly_active_query(weeks, "male")
    got_u, got_pw, stats = res.weekly_active_query(weeks, "male")
    assert (got_u, got_pw) == (want_u, want_pw)
    assert rt.scheduler.drains == 1              # one drain, not w evals
    assert rt.last_drain.n_queries == len(weeks) + 1
    assert rt.last_drain.stats.ns <= rt.last_drain.serial_ns + 1e-9
    assert stats.ns > 0 and stats.energy_nj > 0


def test_bitweaving_column_scan():
    from repro.apps.bitweaving_db import BitWeavingColumn
    vals = RNG.integers(0, 2**10, 5000).astype(np.uint32)
    col = BitWeavingColumn.from_values(vals, 10)
    for (c1, c2) in ((0, 1023), (100, 100), (256, 700)):
        assert col.count_between(c1, c2) == col.oracle_count(vals, c1, c2)


def test_bitsets_match_numpy(engine):
    from repro.apps.bitsets import BitSetOps, SortedSetOps
    domain = 2048 if engine.backend == "ambit_sim" else 65536
    bs = BitSetOps(domain, engine)
    arrs = [np.sort(RNG.choice(domain, 200, replace=False))
            for _ in range(4)]
    sets = [bs.make(a) for a in arrs]
    got_u = np.nonzero(np.asarray(bs.union(sets).bits()))[0]
    got_i = np.nonzero(np.asarray(bs.intersection(sets).bits()))[0]
    got_d = np.nonzero(np.asarray(
        bs.difference(sets[0], sets[1:]).bits()))[0]
    assert np.array_equal(got_u, SortedSetOps.union(arrs))
    assert np.array_equal(got_i, SortedSetOps.intersection(arrs))
    assert np.array_equal(got_d, SortedSetOps.difference(arrs[0], arrs[1:]))


def test_bitfunnel_no_false_negatives():
    from repro.apps.bitfunnel import BitFunnelIndex
    docs = {0: ["apple", "banana"], 1: ["banana", "cherry"],
            2: ["apple", "cherry", "date"], 3: ["elderberry"]}
    idx = BitFunnelIndex(n_docs=4, filter_bits=256)
    for d, terms in docs.items():
        idx.add_document(d, terms)
    for query, must in ((["apple"], {0, 2}), (["banana"], {0, 1}),
                        (["apple", "cherry"], {2})):
        got = set(idx.query(query).tolist())
        assert must <= got  # Bloom: supersets allowed, no false negatives


def test_masked_init(engine):
    from repro.apps.masked_init import masked_clear, masked_set
    n = 1000
    x = BitVector.from_bits(RNG.integers(0, 2, n).astype(bool))
    m = BitVector.from_bits(RNG.integers(0, 2, n).astype(bool))
    xs = np.asarray(masked_set(engine, x, m).bits())
    xc = np.asarray(masked_clear(engine, x, m).bits())
    xb = np.asarray(x.bits())
    mb = np.asarray(m.bits())
    assert np.array_equal(xs, xb | mb)
    assert np.array_equal(xc, xb & ~mb)


def test_data_pipeline_bitweaving_filter():
    from repro.apps.doc_filter import filter_documents, synth_corpus_meta
    meta = synth_corpus_meta(2048, seed=1)
    mask = filter_documents(meta, 64, 200, 1000)
    expect = ((meta.quality >= 64) & (meta.quality <= 200) &
              (meta.length >= 1000))
    assert np.array_equal(mask, expect)
