#!/usr/bin/env python3
"""Proves the served merge path runs on the chip, through the entry points
users call, and fails rather than degrades.

    python3 chip_smoke.py            # one chip: ingest, queries, sync, kernels
    python3 chip_smoke.py --chips 4  # only the MeshFarm phase, on four chips

One chip: a ``TpuDocFarm`` of 8192 documents (a sync server holding 8k
active documents of 512 ops each: 8 rounds x 64 ops, about 140 MB of slab)
takes binary changes from 64 actor streams through ``apply_changes`` — root
map key sets over 64 keys plus counter increments. ``get_patch`` on a sample
covering every stream must match the ``opset.OpSet`` reference byte for
byte in canonical JSON, one ``SyncFarm`` exchange must bring a peer that
lacks the last round to the same heads and patch, and the Pallas kernels
must match ``sync_batch.py`` and the host LEB128 scan bit for bit.

Four chips: an inline ``MeshFarm`` (one process driving every chip) holds
4 x 8192 documents, one shard per chip. Each shard's slab must live on its
own chip, patches must match a one-farm reference, a mid-stream
``migrate_doc`` must keep its document's patches, and the actor reconcile
must converge.

Any fallback call, degraded or quarantined document, lost change, slab off
the chip, or exception exits non-zero without printing a result. The last
line of a passing run is one JSON object naming the device. The script
touches JAX in its own process only; it stops when no TPU is found, or when
run outside the repository.
"""
import argparse
import json
import os
import random
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
DOCS, ROUNDS, OPS = 8192, 8, 64  # bench.py's BENCH_DOCS/ROUNDS/OPS
STREAMS, KEYS, COUNTERS = 64, 64, 4
SYNC_DOCS = 8
# the Bloom check: 32 channels x 2048-change histories x 1024 candidates
FILTERS, ENTRIES, QUERIES = 32, 2048, 1024
LEB_VALUES = 16384  # the LEB128 check: about 120 KB of varints


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def canon(patch) -> str:
    return json.dumps(patch, sort_keys=True)


# ---------------------------------------------------------------------- #
# workload


def make_streams(streams: int, rounds: int, ops: int, seed: int = 0):
    """`streams` actors' binary change chains. Round 0 creates COUNTERS
    counters; every later round increments each of them; the other ops set
    root keys k0..k63, each naming the key's previous op as its pred."""
    from automerge_tpu.columnar import decode_change_columns, encode_change

    out = []
    for a in range(streams):
        rng = random.Random(seed * 1000 + a)
        actor = f"{a:02x}" * 4
        last, deps, buffers = {}, [], []
        start_op = 1
        for r in range(rounds):
            ctr, change_ops = start_op, []
            for c in range(COUNTERS):
                key = f"c{c}"
                if r == 0:
                    change_ops.append({
                        "action": "set", "obj": "_root", "key": key,
                        "datatype": "counter", "value": rng.randrange(100),
                        "pred": []})
                    last[key] = f"{ctr}@{actor}"
                else:
                    change_ops.append({
                        "action": "inc", "obj": "_root", "key": key,
                        "value": rng.randrange(1, 10), "pred": [last[key]]})
                ctr += 1
            for _ in range(ops - COUNTERS):
                key = f"k{rng.randrange(KEYS)}"
                change_ops.append({
                    "action": "set", "obj": "_root", "key": key,
                    "datatype": "uint", "value": rng.randrange(10**6),
                    "pred": [last[key]] if key in last else []})
                last[key] = f"{ctr}@{actor}"
                ctr += 1
            buf = encode_change({"actor": actor, "seq": r + 1,
                                 "startOp": start_op, "time": 0,
                                 "deps": deps, "ops": change_ops})
            deps = [decode_change_columns(buf)["hash"]]
            buffers.append(buf)
            start_op = ctr
        out.append(buffers)
    return out


def sample_docs(num_docs: int, streams: int):
    """One doc per stream (doc d carries stream d % streams), spread over
    the farm."""
    per = num_docs // streams
    return sorted(s + streams * ((s * 37) % per) for s in range(streams))


def reference_patches(streams):
    """Per stream: the OpSet reference's patch after each round, and its
    whole-document patch at the end."""
    from automerge_tpu.opset import OpSet

    rounds, whole = [], []
    for chain in streams:
        ref = OpSet()
        rounds.append([canon(ref.apply_changes([buf])) for buf in chain])
        whole.append(canon(ref.get_patch()))
    return rounds, whole


# ---------------------------------------------------------------------- #
# checks


def metric(name: str):
    from automerge_tpu.obs.metrics import get_metrics

    return get_metrics().as_dict().get(name, {}).get("value", 0)


def check_no_fallback(farms) -> None:
    calls, docs = metric("farm.fallback.calls"), metric("farm.fallback.docs")
    degraded = sum(len(f.degraded) for f in farms)
    quarantined = sum(len(f.quarantine) for f in farms)
    say(f"fallback calls={calls} docs={docs} degraded={degraded} "
        f"quarantined={quarantined}")
    check(calls == 0 and docs == 0, "the farm fell back to the host walk")
    check(degraded == 0 and quarantined == 0,
          "documents are degraded or quarantined")


def slab_devices(farm) -> set:
    return {dev for col in farm.engine.slab for dev in col.devices()}


def compile_line() -> str:
    """Compiles and their wall seconds, from the amprof observatory."""
    from automerge_tpu.obs.prof import get_observatory

    progs = get_observatory().programs().values()
    return (f"compiles={sum(p.compiles for p in progs)} "
            f"compile_s={sum(p.compile_s for p in progs)}")


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    return (f"memory bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def ingest(apply, num_docs, streams, rounds, sample, on_round=None):
    """Feeds round r of stream d % len(streams) to every doc d through
    `apply`; returns {doc: [canonical patch per round]} for `sample`."""
    got = {d: [] for d in sample}
    for r in range(rounds):
        t0 = time.perf_counter()
        result = apply([[streams[d % len(streams)][r]]
                        for d in range(num_docs)])
        say(f"round {r}: docs={num_docs} ops={num_docs * OPS} "
            f"wall_s={time.perf_counter() - t0}")
        for d in sample:
            got[d].append(canon(result[d]))
        if on_round is not None:
            on_round(r)
    return got


def check_patches(get_patch, got, streams, want_rounds, want_whole) -> None:
    for d, per_round in got.items():
        s = d % len(streams)
        check(per_round == want_rounds[s][:len(per_round)],
              f"doc {d}: an apply_changes patch differs from OpSet")
        check(canon(get_patch(d)) == want_whole[s],
              f"doc {d}: get_patch differs from OpSet")


# ---------------------------------------------------------------------- #
# phases


def run_farm(device, num_docs=DOCS, rounds=ROUNDS, streams=STREAMS):
    """Ingest, queries and one sync exchange on one chip's TpuDocFarm."""
    from automerge_tpu.tpu.farm import TpuDocFarm

    chains = make_streams(streams, rounds, OPS)
    sample = sample_docs(num_docs, streams)
    want_rounds, want_whole = reference_patches(chains)

    farm = TpuDocFarm(num_docs, capacity=rounds * OPS)
    t0 = time.perf_counter()
    got = ingest(farm.apply_changes, num_docs, chains, rounds, sample)
    say(f"ingest: docs={num_docs} ops={num_docs * rounds * OPS} "
        f"wall_s={time.perf_counter() - t0} {compile_line()}")
    say(memory_line(device))
    committed = sum(len(c) for c in farm.changes)
    say(f"committed changes={committed} sent={num_docs * rounds}")
    check(committed == num_docs * rounds, "committed changes != sent")
    placed = slab_devices(farm)
    say(f"slab devices={sorted(str(d) for d in placed)}")
    check(placed == {device}, "the slab is not on the chip")
    check_no_fallback([farm])

    t0 = time.perf_counter()
    check_patches(farm.get_patch, got, chains, want_rounds, want_whole)
    say(f"queries: {len(sample)} docs covering {streams} streams match "
        f"OpSet wall_s={time.perf_counter() - t0}")

    t0 = time.perf_counter()
    synced = sync_exchange(farm, sample[:SYNC_DOCS], chains, rounds)
    say(f"sync: {len(synced)} docs converged with a peer lacking round "
        f"{rounds - 1} wall_s={time.perf_counter() - t0}")
    check_no_fallback([farm])


def sync_exchange(farm, docs, chains, rounds, max_rounds=10):
    """Runs the reference sync loop between `farm`'s `docs` and a peer
    farm that holds every round but the last; returns the docs once both
    sides agree on heads and patch."""
    from automerge_tpu.tpu.farm import TpuDocFarm
    from automerge_tpu.tpu.sync_farm import SyncFarm

    peer = TpuDocFarm(len(docs), capacity=rounds * OPS)
    peer.apply_changes([chains[d % len(chains)][:rounds - 1] for d in docs])
    ours, theirs = SyncFarm(farm), SyncFarm(peer)
    a_states = [SyncFarm.init_state() for _ in docs]
    b_states = [SyncFarm.init_state() for _ in docs]
    for _ in range(max_rounds):
        moved = False
        out = ours.generate_messages(list(zip(docs, a_states)))
        inbound = []
        for j, (state, msg) in enumerate(out):
            a_states[j] = state
            if msg is not None:
                inbound.append((j, b_states[j], msg))
        for (j, _s, _m), (state, _patch) in zip(
                inbound, theirs.receive_messages(inbound)):
            b_states[j] = state
        out = theirs.generate_messages(list(enumerate(b_states)))
        back = []
        for j, (state, msg) in enumerate(out):
            b_states[j] = state
            if msg is not None:
                back.append((docs[j], a_states[j], msg, j))
        for (_d, _s, _m, j), (state, _patch) in zip(
                back, ours.receive_messages([b[:3] for b in back])):
            a_states[j] = state
        moved = bool(inbound or back)
        if not moved:
            break
    check(not moved, "sync did not quiesce")
    for j, d in enumerate(docs):
        check(peer.get_heads(j) == farm.get_heads(d),
              f"sync: doc {d} heads differ")
        check(canon(peer.get_patch(j)) == canon(farm.get_patch(d)),
              f"sync: doc {d} patch differs")
    check_no_fallback([peer])
    return docs


def run_kernels(interpret=False):
    """The Pallas kernels against their references, bit for bit."""
    import jax.numpy as jnp
    import numpy as np

    from automerge_tpu.codecs import Encoder
    from automerge_tpu.tpu import decode, sync_batch
    from automerge_tpu.tpu.pallas_kernels import bloom_build, bloom_query

    rng = np.random.default_rng(7)
    xyz = jnp.asarray(rng.integers(0, 2**32, (FILTERS, ENTRIES, 3),
                                   dtype=np.uint32))
    counts = jnp.asarray(rng.integers(1, ENTRIES + 1, FILTERS), jnp.int32)
    num_words = (ENTRIES * 10 + 31) // 32
    want_w, want_m = sync_batch.build_filters(xyz, counts, num_words)
    got_w, got_m = bloom_build(xyz, counts, num_words, interpret=interpret)
    check(np.array_equal(np.asarray(got_w), np.asarray(want_w))
          and np.array_equal(np.asarray(got_m), np.asarray(want_m)),
          "pallas bloom_build differs from sync_batch.build_filters")
    members = np.asarray(xyz)[:, :QUERIES // 2]
    others = rng.integers(0, 2**32, (FILTERS, QUERIES - QUERIES // 2, 3),
                          dtype=np.uint32)
    query = jnp.asarray(np.concatenate([members, others], axis=1))
    want_q = sync_batch.query_filters(want_w, want_m, counts, query)
    got_q = bloom_query(want_w, want_m, counts, query, interpret=interpret)
    check(np.array_equal(np.asarray(got_q), np.asarray(want_q)),
          "pallas bloom_query differs from sync_batch.query_filters")

    enc = Encoder()
    for v in rng.integers(0, 2**50, LEB_VALUES):
        enc.append_uint53(int(v))
    data = np.frombuffer(enc.buffer, np.uint8)
    host = decode.leb128_scan(data)
    dev = decode.leb128_scan_device(data, interpret=interpret)
    check(all(np.array_equal(h, np.asarray(d)) for h, d in zip(host, dev)),
          "leb128_scan_device differs from the host scan")
    say(f"kernels: bloom_build, bloom_query ({FILTERS} filters x "
        f"{ENTRIES} entries x {QUERIES} queries) match sync_batch; "
        f"leb128_scan_device ({data.size} bytes) matches the host scan")


def run_mesh(devices, docs_per_chip=DOCS, rounds=ROUNDS, streams=STREAMS):
    """An inline MeshFarm with one shard per chip against a one-farm
    reference of the sample docs."""
    from automerge_tpu.parallel import MeshFarm
    from automerge_tpu.tpu.farm import TpuDocFarm

    n = len(devices)
    num_docs = n * docs_per_chip
    chains = make_streams(streams, rounds, OPS)
    sample = sample_docs(num_docs, streams)
    mover = sample[0]
    mesh = MeshFarm(num_docs, num_shards=n, capacity=rounds * OPS,
                    devices=devices, mesh_backend="inline",
                    reconcile_interval=None)
    ref = TpuDocFarm(len(sample), capacity=rounds * OPS)
    ref_got = {d: [] for d in sample}

    def ref_round(r):
        result = ref.apply_changes([[chains[d % streams][r]] for d in sample])
        for j, d in enumerate(sample):
            ref_got[d].append(canon(result[j]))
        if r == rounds // 2 - 1:
            dest = (mesh.shard_of(mover) + 1) % n
            mesh.migrate_doc(mover, dest)
            say(f"migrated doc {mover} to shard {dest}")

    t0 = time.perf_counter()
    got = ingest(mesh.apply_changes, num_docs, chains, rounds, sample,
                 on_round=ref_round)
    say(f"mesh ingest: shards={n} docs={num_docs} "
        f"ops={num_docs * rounds * OPS} wall_s={time.perf_counter() - t0} "
        f"{compile_line()}")
    for dev in devices:
        say(f"{dev}: {memory_line(dev)}")
    placed = [slab_devices(f) for f in mesh.shards]
    say(f"shard slab devices={[sorted(str(d) for d in p) for p in placed]}")
    check(all(p == {dev} for p, dev in zip(placed, devices)),
          "a shard's slab is not on its own chip")
    committed = sum(len(c) for f in mesh.shards for c in f.changes)
    check(committed == num_docs * rounds, "committed changes != sent")
    check_no_fallback(mesh.shards + [ref])

    t0 = time.perf_counter()
    for d in sample:
        check(got[d] == ref_got[d], f"doc {d}: mesh patch differs from "
                                    "the one-farm reference")
        check(canon(mesh.get_patch(d)) ==
              canon(ref.get_patch(sample.index(d))),
              f"doc {d}: mesh get_patch differs from the one-farm reference")
    say(f"mesh queries: {len(sample)} docs match the one-farm reference "
        f"(doc {mover} migrated mid-stream) wall_s={time.perf_counter() - t0}")
    first = mesh.reconcile_actors()
    check(mesh.reconcile_actors() == 0, "actor reconcile did not converge")
    mesh.audit()
    say(f"reconcile: {first} entries synced, second pass 0")
    check_no_fallback(mesh.shards)


# ---------------------------------------------------------------------- #


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    dev = devices[0]
    say(f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        say("FAIL: no TPU found; chip_smoke.py runs on the chip only")
        return 1
    if len(devices) < args.chips:
        say(f"FAIL: --chips {args.chips} needs {args.chips} chips")
        return 1
    try:
        sys.path.insert(0, REPO)
        from automerge_tpu import native
        from automerge_tpu.obs.metrics import get_metrics
        from automerge_tpu.obs.prof import get_observatory
        from automerge_tpu.tpu.compile_cache import (
            compile_cache_stats,
            enable_compile_cache,
        )

        say(f"compile cache dir={enable_compile_cache()}")
        say(f"native codecs active={native.available()}")
        get_metrics().enable()
        get_observatory().enable()
        t0 = time.perf_counter()
        if args.chips == 4:
            run_mesh(devices[:4])
        else:
            run_farm(dev)
            run_kernels()
        cache = compile_cache_stats()
        say(f"compile cache hits={cache['hits']} misses={cache['misses']} "
            f"dir={cache['dir']}")
        say(f"total wall_s={time.perf_counter() - t0}")
    except Exception:  # noqa: BLE001 - every failure exits non-zero
        traceback.print_exc()
        say("FAIL")
        return 1
    count = 4 if args.chips == 4 else len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
