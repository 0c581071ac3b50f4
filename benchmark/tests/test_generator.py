"""The generator: deterministic by seed, with the stated shape."""
import numpy as np

from benchmark import generator, harness, reference


def cfg(cell, docs):
    c = harness.load_cell(cell, unlisted=True)
    c["config"] = dict(c["config"], docs=docs)
    return c


def test_schedule_is_deterministic_and_fixed_in_size():
    c = cfg("map-8k.uniform-steady", 8192)
    a, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 2**33 + 7)
    b, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 2**33 + 7)
    other, _ = generator.schedule(c["config"], c["traffic"], 500, 2, 10, 99)
    assert a == b
    assert a != other
    assert len(a) == len(other) == 8000  # warm-up, window, cool-down
    # the multiset of sizes does not depend on the seed
    assert sorted(p[4] for p in a) == sorted(p[4] for p in other)
    dues = [p[0] for p in a]
    assert dues == sorted(dues) and -2 <= dues[0] and dues[-1] < 14


def test_zipf_rank_mass():
    c = cfg("map-8k.uniform-steady", 8192)
    traffic = dict(c["traffic"], doc_popularity={"kind": "zipf", "s": 0.99,
                                                 "scramble": True})
    plan, scramble = generator.schedule(c["config"], traffic, 2000, 0, 20, 3)
    inverse = np.argsort(scramble)
    ranks = np.array([inverse[p[1]] for p in plan])
    w = 1.0 / np.arange(1, 8193) ** 0.99
    want = w[0] / w.sum()  # about 10%
    assert abs((ranks == 0).mean() - want) < 0.01
    assert abs((ranks < 10).mean() - w[:10].sum() / w.sum()) < 0.02


def test_typing_bursts_keep_the_delete_ratio():
    c = cfg("text-512.typing-steady", 512)
    plan, _ = generator.schedule(c["config"], c["traffic"], 2000, 0, 10, 5)
    keys = sum(p[4] for p in plan)
    deleted = sum(p[4] for p in plan if p[3] == "del")
    assert abs(deleted / keys - 0.3) < 0.02
    lengths = [p[4] for p in plan]
    assert abs(np.mean(lengths) - 4) < 0.3 and max(lengths) <= 32


def _traffic(cell, docs, rate, seed):
    c = cfg(cell, docs)
    c["cell"] = dict(c["cell"], batching={"policy": "docs", "docs": docs // 2,
                                             "max_wait_s": 0.25})
    c["config"]["preload"] = dict(c["config"]["preload"], templates=4)
    return harness.Traffic(c, rate, 1, 4, seed, workers=1)


def test_changes_are_deterministic_by_seed():
    a = _traffic("map-8k.uniform-steady", 16, 30, 2**33 + 11)
    b = _traffic("map-8k.uniform-steady", 16, 30, 2**33 + 11)
    assert a.buf == b.buf and a.ref == b.ref


def test_deps_name_the_actor_and_the_lagged_change():
    from automerge_tpu.columnar import decode_change

    t = _traffic("map-8k.uniform-steady", 8, 40, 17)
    lag = 0.25
    by_hash, seen = {}, {}
    for d in range(8):
        for buf in t.extra[d][0]:
            by_hash[generator.change_hash(buf)] = (float("-inf"), None)
    for i in range(len(t.due)):
        ch = decode_change(t.buf[i])
        for dep in ch["deps"]:
            due, doc = by_hash[dep]
            assert doc in (None, int(t.doc[i]))
            assert due <= t.due[i]
        prev = seen.get((int(t.doc[i]), ch["actor"]))
        if prev is not None:  # own previous change, or a dep that holds it
            assert prev in ch["deps"] or any(
                by_hash[h][0] <= t.due[i] - lag for h in ch["deps"])
        by_hash[ch["hash"]] = (t.due[i], int(t.doc[i]))
        seen[(int(t.doc[i]), ch["actor"])] = ch["hash"]


def test_concurrent_actors_conflict_and_the_reference_applies_them():
    t = _traffic("map-8k.uniform-steady", 4, 60, 21)
    multi = sum(1 for r in t.ref if r for op in r
                if op[0] == "set" and len(op[6]) > 1)
    assert multi > 0  # some sets name two concurrent predecessors
    for d in t.touched:
        ref = reference.RefDoc()
        for ops in t.preload_refs(d):
            ref.apply(ops)
        for i in np.flatnonzero(t.doc == d):
            ref.apply(t.ref[i])
        assert ref.state()["_root"]


def test_text_reference_matches_the_typists_model():
    t = _traffic("text-512.typing-steady", 4, 30, 23)
    for d in t.touched:
        ref = reference.RefDoc()
        for ops in t.preload_refs(d):
            ref.apply(ops)
        for i in np.flatnonzero(t.doc == d):
            ref.apply(t.ref[i])
        (text,) = ref.texts
        assert len(ref.state()[text]) > 200  # 256 preloaded, 30% deletes
