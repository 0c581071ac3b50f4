"""Worker supervision, crash recovery and controller-policy tests for
``mesh_backend="process"`` (parallel/workers.py + meshfarm.py).

The crash tests use ``inject_worker_fault`` — the chaos hook that makes
one worker SIGKILL itself, indistinguishable from an external kill -9 —
and pin the full recovery contract: the mesh keeps serving, survivors'
patches stay byte-identical to the inline oracle, the in-flight docs
land in quarantine under ``WorkerCrashError`` (kind "worker_crash"),
and after ``release_quarantine`` + re-delivery the recovered docs
converge to the oracle too (the respawned worker was re-hydrated from
the controller's delivery log).

The PR 19 additions pin the zero-copy shm data plane end-to-end:
transport patch parity (shm byte-identical to the pickle oracle and the
inline farm, including a mid-delivery migration), SIGKILL while slots
are held (generation-counter reclaim, remap metering, convergence), the
payload/control pipe-accounting split, and zero leaked ``/dev/shm``
segments after clean shutdown AND after crash-respawn cycles.
"""
import json
import multiprocessing
import os
import time

import pytest

from automerge_tpu.errors import WorkerCrashError, error_kind
from automerge_tpu.opset import OpSet
from automerge_tpu.parallel.meshfarm import MeshFarm
from test_farm import Workload

NUM_DOCS = 8
NUM_SHARDS = 2
ROUNDS = 6
CRASH_ROUND = 2


def _rounds(seed=3, rounds=ROUNDS):
    gen = OpSet()
    w = Workload(seed)
    return [r for r in (w.next_round(gen) for _ in range(rounds)) if r]


def _final_patches(mesh):
    return [
        json.dumps(mesh.get_patch(d), sort_keys=True)
        for d in range(NUM_DOCS)
    ]


def _drive_inline(deliveries):
    mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                    mesh_backend="inline")
    try:
        for buffers in deliveries:
            mesh.apply_changes(
                [list(buffers) for _ in range(NUM_DOCS)], isolation="doc"
            )
        return _final_patches(mesh)
    finally:
        mesh.close()


def test_worker_crash_mid_delivery_recovers_to_oracle():
    deliveries = _rounds()
    oracle = _drive_inline(deliveries)
    mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                    mesh_backend="process")
    try:
        for r, buffers in enumerate(deliveries):
            per_doc = [list(buffers) for _ in range(NUM_DOCS)]
            if r == CRASH_ROUND:
                mesh.inject_worker_fault(1, when="next_apply")
            res = mesh.apply_changes(per_doc, isolation="doc")
            if r != CRASH_ROUND:
                assert not res.quarantined
                continue
            # the delivery the worker died under: every shard-1 doc was
            # in flight and is quarantined under the crash taxonomy...
            q = res.quarantined
            assert sorted(q) == sorted(
                d for d in range(NUM_DOCS) if mesh.shard_of(d) == 1
            )
            for outcome in q.values():
                assert isinstance(outcome.error, WorkerCrashError)
                assert error_kind(outcome.error) == "worker_crash"
            assert set(q) == set(mesh.quarantine)
            # ...while shard 0's docs applied as if nothing happened
            for d in range(NUM_DOCS):
                if d not in q:
                    assert res.outcomes[d].status == "applied"
            # release + re-deliver the lost round: the respawned worker
            # was re-hydrated from the delivery log, so this converges
            assert sorted(mesh.release_quarantine()) == sorted(q)
            redo = [per_doc[d] if d in q else [] for d in range(NUM_DOCS)]
            redo_res = mesh.apply_changes(redo, isolation="doc")
            assert all(o.status == "applied" for o in redo_res.outcomes)
        assert _final_patches(mesh) == oracle
        mesh.audit()
    finally:
        mesh.close()
    assert multiprocessing.active_children() == []


def test_heartbeat_detects_and_respawns_dead_worker():
    mesh = MeshFarm(4, num_shards=NUM_SHARDS, capacity=16,
                    mesh_backend="process")
    try:
        assert mesh.heartbeat() == {0: "ok", 1: "ok"}
        mesh.inject_worker_fault(0, when="now")
        deadline = time.monotonic() + 10.0
        while mesh._handles[0].alive and time.monotonic() < deadline:
            time.sleep(0.05)
        assert mesh.heartbeat() == {0: "respawned", 1: "ok"}
        assert mesh.heartbeat() == {0: "ok", 1: "ok"}
    finally:
        mesh.close()
    assert multiprocessing.active_children() == []


def test_migration_and_rebalance_over_the_pipe_match_inline():
    def drive(backend):
        mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                        mesh_backend=backend)
        try:
            for r, buffers in enumerate(_rounds(seed=5)):
                mesh.apply_changes(
                    [list(buffers) for _ in range(NUM_DOCS)],
                    isolation="doc",
                )
                if r == 2:
                    d = next(x for x in range(NUM_DOCS)
                             if mesh.shard_of(x) == 0)
                    mesh.migrate_doc(d, 1)
                    mesh.audit()
            mid = _final_patches(mesh)
            mesh.rebalance(max_moves=1, min_gain_pages=0)
            mesh.audit()
            return mid, _final_patches(mesh)
        finally:
            mesh.close()

    assert drive("inline") == drive("process")
    assert multiprocessing.active_children() == []


def test_dispatch_shards_reraises_first_shard_error_after_draining():
    """The satellite regression: a mid-dispatch shard exception must
    neither deadlock the pool nor abandon other shards' results, and the
    FIRST failing shard (lowest id) surfaces with its id attached."""
    import os
    os.environ["AM_MESH_CONCURRENCY"] = "4"
    try:
        mesh = MeshFarm(9, num_shards=3, capacity=16, mesh_backend="inline")
    finally:
        del os.environ["AM_MESH_CONCURRENCY"]
    try:
        assert mesh._executor is not None
        done = []

        def fn(s):
            done.append(s)
            if s in (1, 2):
                raise RuntimeError(f"boom shard {s}")
            return s * 10

        with pytest.raises(RuntimeError) as ei:
            mesh._dispatch_shards([0, 1, 2], fn)
        assert ei.value.shard == 1
        assert ei.value.args[0].startswith("[shard 1]")
        assert sorted(done) == [0, 1, 2]  # every future drained

        # serial path (no pool): same drain-and-attribute contract
        mesh._executor.shutdown(wait=True)
        mesh._executor = None
        done.clear()
        with pytest.raises(RuntimeError) as ei:
            mesh._dispatch_shards([0, 1, 2], fn)
        assert ei.value.shard == 1
        assert ei.value.args[0].startswith("[shard 1]")
        assert sorted(done) == [0, 1, 2]
    finally:
        mesh.close()


def test_quarantine_reads_are_rpc_free_on_process_backend():
    """The serve batcher checks ``farm.quarantine`` on EVERY submit
    (serve/batcher.py admission), so the process controller must answer
    from its local mirror without a worker round trip."""
    mesh = MeshFarm(4, num_shards=NUM_SHARDS, capacity=16,
                    mesh_backend="process")
    try:
        calls = []
        for h in mesh._handles:
            orig = h.call
            h.call = (lambda orig: lambda *a, **k: (
                calls.append(a[0]), orig(*a, **k))[1])(orig)
        for _ in range(50):
            assert mesh.quarantine == {}
        assert calls == []
    finally:
        mesh.close()
    assert multiprocessing.active_children() == []


def test_worker_crash_flight_dump_contains_blackbox_forensics(tmp_path):
    """The ISSUE 13 acceptance shape: SIGKILL a worker mid-delivery with
    the flight plane on — the controller's ``mesh.worker.crash`` auto-dump
    must contain the dead worker's shard-tagged pre-crash events (live
    shipped over the pipe, topped up from its black-box file) alongside
    the crash entry with its forensic fields."""
    from automerge_tpu.obs.flight import enabled_flight, load_jsonl

    deliveries = _rounds(rounds=2)
    with enabled_flight(dump_dir=str(tmp_path)) as rec:
        rec.clear()
        mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                        mesh_backend="process")
        try:
            # round 0 runs clean: the workers compile, record shard-tagged
            # flight events and ship them live with the result frame
            mesh.apply_changes(
                [list(deliveries[0]) for _ in range(NUM_DOCS)],
                isolation="doc",
            )
            assert any(e.get("shard") == 1 for e in rec.snapshot()), \
                "round 0 shipped no shard-1 worker events"
            # the worker flushes its black box AFTER sending the result
            # frame; a heartbeat round trip sequences behind that flush
            # (the worker is single-threaded)
            assert mesh.heartbeat() == {0: "ok", 1: "ok"}
            bb_path = mesh._handles[1].spec["blackbox_path"]
            assert os.path.exists(bb_path), "worker wrote no black box"
            mesh.inject_worker_fault(1, when="next_apply")
            res = mesh.apply_changes(
                [list(deliveries[1]) for _ in range(NUM_DOCS)],
                isolation="doc",
            )
            assert res.quarantined
        finally:
            mesh.close()
    assert multiprocessing.active_children() == []
    assert rec.dump_paths, "the crash did not auto-dump the timeline"
    events = load_jsonl(open(rec.dump_paths[-1], encoding="utf-8").read())
    crashes = [e for e in events if e["event"] == "mesh.worker.crash"]
    assert crashes, [e["event"] for e in events]
    fields = crashes[-1]["fields"]
    assert fields["shard"] == 1
    assert isinstance(fields["pid"], int) and fields["pid"] > 0
    assert fields["phase"] == "apply"
    assert "heartbeat_age_s" in fields
    assert fields["blackbox"] == bb_path      # S2: recovered file path
    assert fields["blackbox_events"] >= 0
    # the dead worker's own events sit in the same dump, shard-tagged and
    # ordered before the crash entry
    worker_events = [e for e in events
                     if e.get("shard") == 1
                     and e["event"] != "mesh.worker.crash"]
    assert worker_events, "no shard-1 pre-crash events in the crash dump"
    crash_idx = events.index(crashes[-1])
    assert events.index(worker_events[0]) < crash_idx
    # the inline backend, fed the same rounds, produces an untagged
    # single-process dump: byte-identical to the pre-mesh shape
    with enabled_flight() as rec2:
        rec2.clear()
        _drive_inline(deliveries)
        assert all("shard" not in e for e in rec2.snapshot())
    assert multiprocessing.active_children() == []


def test_worker_exemplar_resolves_to_controller_span():
    """The ISSUE 13 trace-propagation acceptance: a latency exemplar
    recorded inside a process-mode worker (``farm.dispatch.latency_ms``)
    resolves to the controller-side dispatch span id in ONE lookup — the
    span id travels in the fan-out payload, the worker stamps it, and the
    shipped metric delta carries it back."""
    from automerge_tpu.obs.metrics import enabled_metrics
    from automerge_tpu.obs.scope import dispatch_context, get_amscope

    deliveries = _rounds(rounds=1)
    with enabled_metrics() as reg:
        reg.reset()
        mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                        mesh_backend="process")
        try:
            span = get_amscope().begin_dispatch([], 0.0)
            with dispatch_context(span):
                mesh.apply_changes(
                    [list(deliveries[0]) for _ in range(NUM_DOCS)],
                    isolation="doc",
                )
            hist = reg.find("farm.dispatch.latency_ms")
            assert hist is not None and hist.count > 0, \
                "no worker-side dispatch observations merged back"
            # one lookup: the p99 bucket's exemplar IS the controller span
            assert hist.exemplar_for(0.99) == span.dispatch_id
        finally:
            mesh.close()
    assert multiprocessing.active_children() == []


def _shm_segments():
    import glob
    return glob.glob("/dev/shm/am-*")


def test_shm_patch_parity_with_pickle_oracle_and_inline():
    """PR 19 acceptance: shm-transport patches are byte-for-byte the
    pickle oracle's (and the inline farm's), including a mid-delivery
    migration — the rings change how bytes move, never what they say."""
    deliveries = _rounds(seed=7)
    inline = _drive_inline(deliveries)

    def drive(transport):
        mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                        mesh_backend="process", mesh_transport=transport)
        try:
            assert mesh.transport == transport
            for r, buffers in enumerate(_rounds(seed=7)):
                mesh.apply_changes(
                    [list(buffers) for _ in range(NUM_DOCS)],
                    isolation="doc",
                )
                if r == 1:
                    d = next(x for x in range(NUM_DOCS)
                             if mesh.shard_of(x) == 0)
                    mesh.migrate_doc(d, 1)
                    mesh.audit()
            return _final_patches(mesh)
        finally:
            mesh.close()

    shm_patches = drive("shm")
    assert shm_patches == drive("pickle")
    assert shm_patches == inline
    assert _shm_segments() == []
    assert multiprocessing.active_children() == []
    assert deliveries  # the workload generator produced real rounds


def test_worker_sigkill_while_holding_slot_reclaims_and_remaps():
    """The PR 19 satellite: SIGKILL a worker mid-apply under the shm
    transport — the dead worker's held ring slots reclaim via the
    generation counter (no deadlock on later acquires), the in-flight
    docs quarantine, the respawned worker remaps the SAME segments
    (``mesh.shm.remaps`` + a ``mesh.shm.remap`` flight event with plain
    int fields — the PR 14 np.int64 pin), and re-delivery converges to
    the inline oracle."""
    from automerge_tpu.obs.flight import enabled_flight
    from automerge_tpu.obs.metrics import enabled_metrics

    deliveries = _rounds()
    oracle = _drive_inline(deliveries)
    with enabled_metrics() as reg, enabled_flight() as rec:
        reg.reset()
        rec.clear()
        mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                        mesh_backend="process", mesh_transport="shm")
        try:
            assert mesh.transport == "shm"
            assert len(_shm_segments()) == 2 * NUM_SHARDS
            assert reg.as_dict()["mesh.shm.segments"]["value"] \
                == 2 * NUM_SHARDS
            for r, buffers in enumerate(deliveries):
                per_doc = [list(buffers) for _ in range(NUM_DOCS)]
                if r == CRASH_ROUND:
                    mesh.inject_worker_fault(1, when="next_apply")
                res = mesh.apply_changes(per_doc, isolation="doc")
                if r != CRASH_ROUND:
                    assert not res.quarantined
                    continue
                q = res.quarantined
                assert sorted(q) == sorted(
                    d for d in range(NUM_DOCS) if mesh.shard_of(d) == 1
                )
                for outcome in q.values():
                    assert isinstance(outcome.error, WorkerCrashError)
                    assert error_kind(outcome.error) == "worker_crash"
                # the crash-reclaim freed the dead worker's send-ring
                # slots — nothing held, nothing deadlocked
                send_ring, _result_ring = mesh._rings[1]
                assert send_ring.slots_in_use() == 0
                assert sorted(mesh.release_quarantine()) == sorted(q)
                redo = [per_doc[d] if d in q else []
                        for d in range(NUM_DOCS)]
                redo_res = mesh.apply_changes(redo, isolation="doc")
                assert all(o.status == "applied"
                           for o in redo_res.outcomes)
            assert _final_patches(mesh) == oracle
            snap = reg.as_dict()
            assert snap["mesh.shm.remaps"]["value"] >= 1
            remaps = [e for e in rec.snapshot()
                      if e["event"] == "mesh.shm.remap"]
            assert remaps, "respawn recorded no mesh.shm.remap event"
            fields = remaps[-1]["fields"]
            assert fields["shard"] == 1
            for key in ("shard", "epoch", "freed_slots"):
                assert type(fields[key]) is int, (key, fields[key])
            json.dumps(fields)  # JSONL-safe: no np.int64 leaks
        finally:
            mesh.close()
        # clean shutdown unlinked every segment, gauge agrees
        assert reg.as_dict()["mesh.shm.segments"]["value"] == 0
    assert _shm_segments() == []
    assert multiprocessing.active_children() == []


def test_pipe_payload_control_split_by_transport():
    """The PR 19 satellite: ``mesh.pipe.<s>.serialize_ms`` aggregate
    gets a payload/control breakdown. Under the pickle oracle the apply
    batches and result frames classify as payload; under shm the payload
    legs sit at exactly zero — every remaining pipe frame is control."""
    from automerge_tpu.obs.metrics import enabled_metrics

    deliveries = _rounds(rounds=2)

    def split(transport):
        with enabled_metrics() as reg:
            reg.reset()
            mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                            mesh_backend="process",
                            mesh_transport=transport)
            try:
                for buffers in deliveries:
                    mesh.apply_changes(
                        [list(buffers) for _ in range(NUM_DOCS)],
                        isolation="doc",
                    )
                snap = reg.as_dict()
            finally:
                mesh.close()

        def total(suffix, field):
            return sum(
                snap.get(f"mesh.pipe.{s}.{suffix}", {}).get(field, 0)
                for s in range(NUM_SHARDS)
            )

        return {
            "payload_frames": total("payload_ms", "count"),
            "payload_bytes": total("payload_bytes", "value"),
            "control_frames": total("control_ms", "count"),
            "control_bytes": total("control_bytes", "value"),
        }

    p = split("pickle")
    assert p["payload_frames"] > 0 and p["payload_bytes"] > 0
    assert p["control_frames"] > 0 and p["control_bytes"] > 0
    s = split("shm")
    assert s["payload_frames"] == 0 and s["payload_bytes"] == 0
    assert s["control_frames"] > 0 and s["control_bytes"] > 0
    assert _shm_segments() == []
    assert multiprocessing.active_children() == []


def test_mesh_transport_resolution():
    """``mesh_transport=None`` reads AM_MESH_TRANSPORT; non-process
    backends always resolve to pickle (there are no rings to map); an
    unknown value is an API-usage error."""
    old = os.environ.get("AM_MESH_TRANSPORT")
    os.environ["AM_MESH_TRANSPORT"] = "pickle"
    try:
        mesh = MeshFarm(4, num_shards=NUM_SHARDS, capacity=16,
                        mesh_backend="process")
        try:
            assert mesh.transport == "pickle"
            assert _shm_segments() == []  # pickle mode maps no rings
        finally:
            mesh.close()
    finally:
        if old is None:
            os.environ.pop("AM_MESH_TRANSPORT", None)
        else:
            os.environ["AM_MESH_TRANSPORT"] = old
    inline = MeshFarm(4, num_shards=NUM_SHARDS, capacity=16,
                      mesh_backend="inline", mesh_transport="shm")
    try:
        assert inline.transport == "pickle"
    finally:
        inline.close()
    with pytest.raises(ValueError):
        MeshFarm(4, num_shards=NUM_SHARDS, capacity=16,
                 mesh_backend="inline", mesh_transport="bogus")
    assert multiprocessing.active_children() == []


def test_rebalance_policy_hook_is_called_on_interval():
    calls = []
    mesh = MeshFarm(NUM_DOCS, num_shards=NUM_SHARDS, capacity=64,
                    mesh_backend="inline",
                    rebalance_policy=calls.append, rebalance_interval=2)
    try:
        gen = OpSet()
        w = Workload(9)
        applied = 0
        while applied < 4:
            buffers = w.next_round(gen)
            if not buffers:
                continue
            mesh.apply_changes(
                [list(buffers) for _ in range(NUM_DOCS)], isolation="doc"
            )
            applied += 1
        assert calls == [mesh, mesh]
    finally:
        mesh.close()


def test_process_backend_refuses_an_accelerator(monkeypatch):
    """A chip belongs to one process: until workers are pinned to chips,
    the process backend runs on the CPU only and says so up front instead
    of letting N workers race for the chip."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="CPU only"):
        MeshFarm(8, num_shards=2, mesh_backend="process")
