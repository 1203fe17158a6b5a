"""Tuple Space semantics (paper §3): put / blocking read / destructive get,
pattern matching, FIFO fairness, ledger integrity, thread safety.

Backend conformance suite — every test taking the ``ts`` fixture runs
identically over all `repro.core.space` backends (local, sharded with
several shard counts, instrumented): same matching semantics, same
blocking behaviour, same FIFO take-fairness, same journal/ledger trace.
"""

import threading
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ANY, Ledger, TSTimeout, TupleSpace, match
from repro.core.space import (InstrumentedBackend, LocalBackend,
                              ShardedBackend, make_backend)

BACKEND_SPECS = ["local", "sharded", "sharded:3", "instrumented:sharded:4",
                 "checked+local", "checked+sharded"]


@pytest.fixture(params=BACKEND_SPECS)
def ts(request):
    return TupleSpace(backend=request.param)


# --------------------------------------------------------------- basic API
def test_put_read_get(ts):
    ts.put(("act", 0, 1), [1, 2, 3])
    k, v = ts.read(("act", ANY, ANY))
    assert k == ("act", 0, 1) and v == [1, 2, 3]
    # read is non-destructive
    assert ts.count(("act", ANY, ANY)) == 1
    k, v = ts.get(("act", 0, ANY))
    assert v == [1, 2, 3]
    # get is destructive — "other handlers will no longer see it" (§4)
    assert ts.count(("act", ANY, ANY)) == 0


def test_try_read_try_get(ts):
    assert ts.try_read(("missing", ANY)) is None
    assert ts.try_get(("missing", ANY)) is None
    ts.put(("k", 1), "v")
    assert ts.try_read(("k", 1)) == (("k", 1), "v")
    assert ts.try_get(("k", ANY)) == (("k", 1), "v")
    assert ts.try_get(("k", ANY)) is None


def test_put_rejects_bad_keys(ts):
    with pytest.raises(TypeError):
        ts.put("notatuple", 1)
    with pytest.raises(TypeError):
        ts.put((), 1)


def test_get_blocks_until_put(ts):
    got = []

    def consumer():
        got.append(ts.get(("task", ANY), timeout=5.0))

    th = threading.Thread(target=consumer)
    th.start()
    time.sleep(0.05)
    assert not got               # consumer is blocked
    ts.put(("task", "t1"), "work")
    th.join(timeout=5.0)
    assert got and got[0][0] == ("task", "t1")


def test_blocking_wakeup_across_shards(ts):
    """A subject-widened (ANY-subject) blocking get must be woken by a put
    landing on *any* shard; a predicate-subject read likewise."""
    got, read_hits = [], []

    def taker():                     # arity-2 pattern
        got.append(ts.get((ANY, ANY), timeout=5.0))

    def reader():                    # arity-3 predicate-subject pattern
        read_hits.append(ts.read((lambda s: s == "zz", ANY, ANY),
                                 timeout=5.0))

    threads = [threading.Thread(target=taker),
               threading.Thread(target=reader)]
    for th in threads:
        th.start()
    time.sleep(0.05)
    assert not got and not read_hits
    ts.put(("zz", 7), "take-me")         # wakes the arity-2 taker
    ts.put(("zz", 7, 8), "read-me")      # wakes the arity-3 reader
    for th in threads:
        th.join(timeout=5.0)
    assert got == [(("zz", 7), "take-me")]
    assert read_hits == [(("zz", 7, 8), "read-me")]


def test_fixed_subject_wakeup_ignores_other_subjects(ts):
    """A blocked get on subject "a" stays blocked through puts on other
    subjects (other shards), then wakes when its subject arrives."""
    got = []

    def consumer():
        got.append(ts.get(("a", ANY), timeout=5.0))

    th = threading.Thread(target=consumer)
    th.start()
    time.sleep(0.02)
    for i in range(8):               # spread across shards, none match
        ts.put((f"other{i}", i), i)
    time.sleep(0.05)
    assert not got
    ts.put(("a", 42), "hit")
    th.join(timeout=5.0)
    assert got == [(("a", 42), "hit")]


def test_get_timeout_is_failure_signal(ts):
    with pytest.raises(TSTimeout):
        ts.get(("task", ANY), timeout=0.05)
    with pytest.raises(TSTimeout):
        ts.get((ANY, ANY), timeout=0.05)    # widened pattern times out too


def test_predicate_pattern(ts):
    for i in range(5):
        ts.put(("x", i), i)
    k, _ = ts.read(("x", lambda i: i >= 3))
    assert k[1] >= 3


# ------------------------------------------------------------ FIFO fairness
def test_fifo_among_matches(ts):
    for i in range(4):
        ts.put(("task", f"t{i}"), i)
    order = [ts.get(("task", ANY))[1] for _ in range(4)]
    assert order == [0, 1, 2, 3]


def test_fifo_across_subjects(ts):
    """Global put order is take order even when the pattern widens across
    subjects — i.e. across shards for the sharded backend."""
    for i in range(12):
        ts.put((f"s{i % 5}", i), i)
    order = [ts.get((ANY, ANY))[1] for _ in range(12)]
    assert order == list(range(12))


def test_put_many_preserves_global_fifo(ts):
    """Regression: sharded put_many once stamped sequence numbers per
    shard group, so a cross-subject batch drained subject-clustered
    instead of in batch order."""
    ts.put_many([((f"m{i % 4}", i), i) for i in range(12)])
    order = [ts.get((ANY, ANY))[1] for _ in range(12)]
    assert order == list(range(12))


def test_reput_of_live_key_moves_to_back_of_fifo(ts):
    """Regression: overwriting a live key left it at its old dict
    position while its seq stamp advanced — dict order and seq order
    disagreed. The latest put defines the key's FIFO position."""
    ts.put(("s", 1), "old")
    ts.put(("s", 2), "b")
    ts.put(("s", 1), "new")          # re-put live key: refresh position
    assert ts.get(("s", ANY)) == (("s", 2), "b")
    assert ts.get(("s", ANY)) == (("s", 1), "new")


def test_take_fairness_concurrent_takers(ts):
    """N concurrent blocking takers on one pattern receive N distinct
    tuples (no tuple delivered twice, none lost)."""
    N = 16
    taken, lock = [], threading.Lock()

    def taker():
        hit = ts.get(("job", ANY), timeout=5.0)
        with lock:
            taken.append(hit[1])

    threads = [threading.Thread(target=taker) for _ in range(N)]
    for th in threads:
        th.start()
    ts.put_many(iter([(("job", i), i) for i in range(N)]))
    for th in threads:
        th.join(timeout=5.0)
    assert sorted(taken) == list(range(N))


# ------------------------------------------- blocking primitives (PR 2)
def test_read_blocking_timeout(ts):
    """read shares get's timeout semantics but never removes."""
    with pytest.raises(TSTimeout):
        ts.read(("missing", ANY), timeout=0.05)
    with pytest.raises(TSTimeout):
        ts.read((ANY, ANY), timeout=0.05)
    ts.put(("k", 1), "v")
    assert ts.read(("k", ANY), timeout=0.05) == (("k", 1), "v")
    assert ts.count(("k", ANY)) == 1


def test_take_batch_fifo_and_partial(ts):
    """A batch is FIFO in global put order, capped at max_n, and a second
    call drains the remainder (fewer than max_n is fine)."""
    for i in range(5):
        ts.put((f"s{i % 2}", i), i)
    batch = ts.take_batch((ANY, ANY), 3)
    assert [v for _, v in batch] == [0, 1, 2]
    batch = ts.take_batch((ANY, ANY), 10)
    assert [v for _, v in batch] == [3, 4]
    assert ts.count((ANY, ANY)) == 0


def test_take_batch_fixed_subject_fifo(ts):
    ts.put_many([(("task", f"t{i}"), i) for i in range(8)])
    batch = ts.take_batch(("task", ANY), 5)
    assert [v for _, v in batch] == [0, 1, 2, 3, 4]


def test_take_batch_timeout_and_bad_max_n(ts):
    with pytest.raises(TSTimeout):
        ts.take_batch(("missing", ANY), 4, timeout=0.05)
    with pytest.raises(TSTimeout):
        ts.take_batch((ANY, ANY), 4, timeout=0.05)   # widened times out too
    with pytest.raises(ValueError):
        ts.take_batch(("x", ANY), 0)


def test_take_batch_blocks_until_put_cross_shard(ts):
    """A blocked widened-pattern batch taker is woken by puts landing on
    any shard and drains what arrived."""
    got = []

    def taker():
        got.append(ts.take_batch((ANY, ANY), 8, timeout=5.0))

    th = threading.Thread(target=taker)
    th.start()
    time.sleep(0.05)
    assert not got
    ts.put_many([((f"subj{i}", i), i) for i in range(4)])  # several shards
    th.join(timeout=5.0)
    # The taker may wake after any prefix of the puts landed; whatever it
    # drained must be that prefix in global put order.
    assert got and [v for _, v in got[0]] == list(range(len(got[0])))


def test_take_batch_is_destructive_and_journaled(ts):
    ts.put(("j", 1), "a")
    ts.put(("j", 2), "b")
    taken = ts.take_batch(("j", ANY), 2)
    assert len(taken) == 2 and ts.count(("j", ANY)) == 0
    ops = [(e.op, e.key) for e in ts.ledger.entries]
    assert ops == [("put", ("j", 1)), ("put", ("j", 2)),
                   ("get", ("j", 1)), ("get", ("j", 2))]


def test_take_batch_concurrent_takers_no_duplicates(ts):
    """Concurrent batch takers on one pattern partition the tuples —
    nothing delivered twice, nothing lost."""
    N, taken, lock = 64, [], threading.Lock()

    def taker():
        while True:
            try:
                batch = ts.take_batch(("job", ANY), 8, timeout=0.3)
            except TSTimeout:
                return
            with lock:
                taken.extend(v for _, v in batch)

    threads = [threading.Thread(target=taker) for _ in range(4)]
    for th in threads:
        th.start()
    ts.put_many(iter([(("job", i), i) for i in range(N)]))
    for th in threads:
        th.join(timeout=5.0)
    assert sorted(taken) == list(range(N))


def test_wait_count_immediate_and_nonpositive(ts):
    for i in range(3):
        ts.put(("done", i), i)
    assert ts.wait_count(("done", ANY), 3) == 3
    assert ts.wait_count(("done", ANY), 0) == 3
    assert ts.wait_count(("done", ANY), -1) == 3
    # non-destructive
    assert ts.count(("done", ANY)) == 3


def test_wait_count_wakes_on_arrivals(ts):
    """A parked wait_count returns as soon as the n-th match arrives —
    fixed-subject pattern, arrivals interleaved with unrelated puts."""
    res = []

    def waiter():
        res.append(ts.wait_count(("done", ANY), 3, timeout=5.0))

    th = threading.Thread(target=waiter)
    th.start()
    time.sleep(0.05)
    ts.put(("done", 0), 0)
    ts.put(("other", 0), 0)            # unrelated subject: no early return
    ts.put(("done", 1), 1)
    time.sleep(0.05)
    assert not res
    ts.put(("done", 2), 2)
    th.join(timeout=5.0)
    assert res == [3]


def test_wait_count_cross_shard_widened(ts):
    """A widened (ANY-subject) wait_count counts across all shards and is
    woken by puts landing on any of them."""
    res = []

    def waiter():
        res.append(ts.wait_count((ANY, ANY, ANY), 3, timeout=5.0))

    th = threading.Thread(target=waiter)
    th.start()
    time.sleep(0.05)
    for i in range(3):                  # distinct subjects -> shards
        ts.put((f"w{i}", i, i), i)
    th.join(timeout=5.0)
    assert res == [3]


def test_wait_count_timeout_semantics(ts):
    ts.put(("done", 0), 0)
    with pytest.raises(TSTimeout):
        ts.wait_count(("done", ANY), 2, timeout=0.05)
    with pytest.raises(TSTimeout):
        ts.wait_count((ANY, ANY), 2, timeout=0.05)   # widened
    # the short-fall wait did not disturb the store
    assert ts.count(("done", ANY)) == 1


# ----------------------------------------------- delete / count / keys
def test_delete_and_snapshot(ts):
    for i in range(6):
        ts.put(("a", i), i)
        ts.put(("b", i), i)
    assert ts.delete(("a", lambda i: i % 2 == 0)) == 3
    snap = ts.snapshot()
    assert len(snap) == 9
    assert ts.count(("a", ANY)) == 3


def test_callable_subject_widens_delete_count_keys(ts):
    """Regression: the seed only widened ANY subjects in delete/count/keys,
    so a predicate subject silently matched nothing there (while _find
    widened correctly) — all four ops must agree."""
    ts.put(("alpha", 1), 1)
    ts.put(("beta", 2), 2)
    ts.put(("gamma", 3), 3)
    starts_ab = lambda s: s.startswith(("alpha", "beta"))
    assert ts.count((starts_ab, ANY)) == 2
    assert sorted(ts.keys((starts_ab, ANY))) == [("alpha", 1), ("beta", 2)]
    assert ts.try_read((starts_ab, ANY)) is not None
    assert ts.delete((starts_ab, ANY)) == 2
    assert ts.count((ANY, ANY)) == 1
    assert ts.keys((ANY, ANY)) == [("gamma", 3)]


def test_keys_count_arity_narrowing(ts):
    """Patterns only ever match keys of their own arity."""
    ts.put(("s", 1), "a2")
    ts.put(("s", 1, 2), "a3")
    assert ts.count(("s", ANY)) == 1
    assert ts.keys(("s", ANY, ANY)) == [("s", 1, 2)]
    assert ts.delete(("s", ANY)) == 1
    assert ts.count(("s", ANY, ANY)) == 1


# ------------------------------------------------------------- put_many
def test_put_many_validates_like_put(ts):
    """Regression: seed put_many skipped put's key validation, so one bad
    key corrupted the store. The batch must be rejected atomically."""
    with pytest.raises(TypeError):
        ts.put_many([(("ok", 1), "v"), ("notatuple", "v")])
    # atomic: nothing from the failed batch landed
    assert ts.count((ANY, ANY)) == 0
    ts.put_many(iter([(("ok", i), i) for i in range(3)]))
    assert ts.count(("ok", ANY)) == 3


def test_mutations_are_journaled(ts):
    ts.put(("k", 1), "v")
    ts.put_many([(("k", 2), "v2")])
    ts.get(("k", 1))
    ts.delete(("k", ANY))
    ops = [(e.op, e.key) for e in ts.ledger.entries]
    assert ops == [("put", ("k", 1)), ("put", ("k", 2)),
                   ("get", ("k", 1)), ("del", ("k", 2))]
    assert ts.ledger.verify()


def test_stats_counters(ts):
    for i in range(5):
        ts.put(("s", i), i)
    ts.read(("s", ANY))
    ts.get(("s", ANY))
    st_ = ts.stats()
    assert st_["puts"] == 5 and st_["takes"] == 1
    assert st_["reads"] >= 1 and st_["live"] == 4


# ------------------------------------------------------------ concurrency
def test_concurrent_producers_consumers(ts):
    N = 200
    results = []
    lock = threading.Lock()

    def producer(base):
        for i in range(N // 2):
            ts.put(("w", base + i), base + i)

    def consumer():
        while True:
            try:
                _, v = ts.get(("w", ANY), timeout=0.3)
            except TSTimeout:
                return
            with lock:
                results.append(v)

    thrs = [threading.Thread(target=producer, args=(0,)),
            threading.Thread(target=producer, args=(1000,))] + \
           [threading.Thread(target=consumer) for _ in range(4)]
    for t in thrs:
        t.start()
    for t in thrs:
        t.join()
    assert sorted(results) == sorted(list(range(N // 2))
                                     + list(range(1000, 1000 + N // 2)))


def test_concurrent_multi_subject_churn(ts):
    """Producers on distinct subjects + widened-pattern consumers: every
    tuple is delivered exactly once across shards."""
    per, n_prod = 50, 4
    results, lock = [], threading.Lock()

    def producer(p):
        for i in range(per):
            ts.put((f"subj{p}", i), (p, i))

    def consumer():
        while True:
            try:
                _, v = ts.get((ANY, ANY), timeout=0.3)
            except TSTimeout:
                return
            with lock:
                results.append(v)

    thrs = [threading.Thread(target=producer, args=(p,))
            for p in range(n_prod)]
    thrs += [threading.Thread(target=consumer) for _ in range(4)]
    for t in thrs:
        t.start()
    for t in thrs:
        t.join()
    assert sorted(results) == [(p, i) for p in range(n_prod)
                               for i in range(per)]


# --------------------------------------------------- backend selection API
def test_make_backend_specs():
    assert isinstance(make_backend("local"), LocalBackend)
    assert isinstance(make_backend("sharded"), ShardedBackend)
    assert make_backend("sharded:5").n_shards == 5
    instr = make_backend("instrumented:sharded:2")
    assert isinstance(instr, InstrumentedBackend)
    assert isinstance(instr.inner, ShardedBackend) and instr.inner.n_shards == 2
    with pytest.raises(ValueError):
        make_backend("redis")
    with pytest.raises(ValueError):
        make_backend("sharded:0")


def test_env_var_selects_backend(monkeypatch):
    monkeypatch.setenv("REPRO_TS_BACKEND", "sharded:7")
    ts = TupleSpace()
    assert isinstance(ts.backend, ShardedBackend)
    assert ts.backend.n_shards == 7
    monkeypatch.delenv("REPRO_TS_BACKEND")
    assert isinstance(TupleSpace().backend, LocalBackend)


def test_explicit_backend_instance_gets_ledger_hook():
    backend = ShardedBackend(n_shards=2)
    ts = TupleSpace(backend=backend)
    ts.put(("k", 1), "v")
    assert ts.backend is backend
    assert [e.op for e in ts.ledger.entries] == ["put"]


def test_prewired_journal_is_chained_not_dropped():
    """Regression: a backend arriving with its own journal hook must keep
    that hook AND feed the facade's ledger — a silently dead ledger would
    still verify() as intact."""
    seen = []
    backend = LocalBackend(journal=lambda op, key: seen.append((op, key)))
    ts = TupleSpace(backend=backend)
    ts.put(("k", 1), "v")
    ts.get(("k", 1))
    assert seen == [("put", ("k", 1)), ("get", ("k", 1))]
    assert [e.op for e in ts.ledger.entries] == ["put", "get"]
    assert ts.ledger.verify()


def test_rewrapping_backend_does_not_stack_journal_hooks():
    """Regression: each facade wrapping a backend chained a new closure
    over the previous one — unbounded hook depth and every historical
    ledger kept recording. Re-wrapping must hand recording to the newest
    facade while preserving only the original pre-facade hook."""
    seen = []
    backend = LocalBackend(journal=lambda op, key: seen.append(op))
    spaces = [TupleSpace(backend=backend) for _ in range(5)]
    spaces[-1].put(("k", 1), "v")
    assert seen == ["put"]                      # user hook fired once
    assert len(spaces[-1].ledger.entries) == 1  # newest facade records
    for old in spaces[:-1]:
        assert len(old.ledger.entries) == 0     # superseded ledgers quiet


def test_instrumented_metrics():
    ts = TupleSpace(backend="instrumented:local")
    for i in range(10):
        ts.put(("k", i), i)
    ts.get(("k", ANY))
    with pytest.raises(TSTimeout):
        ts.get(("missing", ANY), timeout=0.02)
    m = ts.backend.metrics()
    assert m["put"]["calls"] == 10 and m["put"]["mean_us"] > 0
    assert m["get"]["calls"] == 2
    s = ts.stats()
    assert s["instr_timeouts"] == 1 and s["instr_ops"] >= 12


# ------------------------------------------------------------------ ledger
def test_ledger_chain_and_tamper():
    led = Ledger()
    for i in range(20):
        led.append("put", ("k", i))
    assert led.verify()
    # tamper
    import dataclasses
    led.entries[10] = dataclasses.replace(led.entries[10], key=("evil", 0))
    assert not led.verify()


# ------------------------------------------------------------- properties
@given(st.lists(st.tuples(st.sampled_from(["a", "b"]), st.integers(0, 5)),
                min_size=1, max_size=30))
@settings(max_examples=50, deadline=None)
def test_count_matches_matching_keys(keys):
    for spec in BACKEND_SPECS:
        ts = TupleSpace(backend=spec)
        for i, k in enumerate(keys):
            ts.put(k + (i,), i)     # make keys unique by arity-3 suffix
        for subj in ("a", "b"):
            want = sum(1 for k in keys if k[0] == subj)
            assert ts.count((subj, ANY, ANY)) == want


@given(st.lists(st.integers(0, 3), min_size=1, max_size=4),
       st.lists(st.integers(0, 3), min_size=1, max_size=4))
@settings(max_examples=100, deadline=None)
def test_match_properties(key, pat_positions):
    key = tuple(key)
    assert match(key, key)                       # exact match
    assert match((ANY,) * len(key), key)         # full wildcard
    assert not match(key + (0,), key)            # arity must agree


@given(st.lists(st.tuples(st.sampled_from(["p", "q", "r"]),
                          st.integers(0, 50)),
                min_size=1, max_size=25))
@settings(max_examples=30, deadline=None)
def test_backends_agree_on_take_order(keys):
    """Differential conformance: local and sharded drain identically."""
    unique = list(dict.fromkeys(keys))
    spaces = [TupleSpace(backend=s) for s in ("local", "sharded:3")]
    for ts in spaces:
        for k in unique:
            ts.put(k, k[1])
    drains = [[ts.get((ANY, ANY))[0] for _ in unique] for ts in spaces]
    assert drains[0] == drains[1] == unique
