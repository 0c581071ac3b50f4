"""Flight-recorder suite (automerge_tpu/obs/flight.py + fault-path
integration).

Covers the ISSUE 8 contract:
- the ring is bounded and causally ordered (global seq survives wraps);
- auto-dump: entering farm quarantine, a device fault, channel
  quarantine and a watchdog reset each snapshot the ring to JSONL;
- a chaos+poison loadgen run auto-dumps a timeline containing the
  quarantine events that occurred (the acceptance-criteria shape);
- the ``--flight`` CLI renders a dump as a causally-ordered timeline.

Plus the ISSUE 13 mesh telemetry channel:
- ``ship()``/``absorb()`` move a worker recorder's unshipped tail into
  the controller ring with origin tags and fresh controller seqs;
- black-box recovery dedups against live-shipped events;
- merged multi-process dumps order deterministically by
  ``(seq, epoch, shard, wseq)`` while untagged single-process dumps keep
  the exact pre-mesh shape (byte-identical timeline, no shard column);
- the disabled path stays one counter compare — no ring access.
"""
import json
import os
import random

import pytest

from automerge_tpu.obs.flight import (
    BLACKBOX_TAIL,
    FlightRecorder,
    enabled_flight,
    get_flight,
    load_jsonl,
    read_blackbox,
    render_timeline,
    write_blackbox,
)
from automerge_tpu.serve.loadgen import LoadConfig, LoadGen
from automerge_tpu.testing.faults import bit_flipped
from automerge_tpu.tpu.farm import TpuDocFarm


def _stream(rounds, ops, actor="aaaaaaaa", seed=0):
    from automerge_tpu.obs.__main__ import _change_stream

    return _change_stream(actor, rounds, ops, seed=seed)


# ---------------------------------------------------------------------- #
# ring mechanics

def test_ring_is_bounded_and_causally_ordered():
    rec = FlightRecorder(capacity=8, clock=lambda: 0.0)
    rec.enabled = True
    for i in range(20):
        rec.record("batcher.flush", t=float(i), n=i)
    assert len(rec) == 8
    events = rec.snapshot()
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    assert events[0]["fields"]["n"] == 12  # oldest 12 fell off
    assert events[-1]["fields"]["n"] == 19


def test_jsonl_round_trip_and_timeline_render():
    rec = FlightRecorder(clock=lambda: 1.25)
    rec.enabled = True
    rec.record("engine.slab.grow", pages=32, rows=2048)
    rec.record("session.retransmit", t=2.0, seq=4, attempt=1,
               backoff_ms=120.5)
    events = load_jsonl(rec.to_jsonl())
    assert [e["event"] for e in events] == [
        "engine.slab.grow", "session.retransmit"
    ]
    assert events[0]["t"] == 1.25  # recorder clock default
    table = render_timeline(events)
    assert "engine.slab.grow" in table and "backoff_ms=120.5" in table
    assert render_timeline([]) == "(no flight events)"


def test_trigger_dumps_bounded_files(tmp_path):
    rec = FlightRecorder(clock=lambda: 0.0)
    rec.enabled = True
    rec.dump_dir = str(tmp_path)
    rec.record("batcher.flush", reason="timer")
    path = rec.trigger("farm.quarantine", doc=3)
    assert path is not None and os.path.exists(path)
    events = load_jsonl(open(path, encoding="utf-8").read())
    assert events[-1]["event"] == "flight.trigger"
    assert events[-1]["fields"]["reason"] == "farm.quarantine"
    assert any(e["event"] == "batcher.flush" for e in events)
    # the dump budget bounds file count
    from automerge_tpu.obs import flight as flight_mod

    for _ in range(flight_mod.MAX_AUTO_DUMPS + 4):
        rec.trigger("farm.quarantine")
    assert len(rec.dump_paths) == flight_mod.MAX_AUTO_DUMPS


def test_trigger_without_dump_dir_still_records():
    rec = FlightRecorder()
    rec.enabled = True
    rec.dump_dir = None
    assert rec.trigger("watchdog.reset") is None
    assert rec.snapshot()[-1]["event"] == "flight.trigger"


# ---------------------------------------------------------------------- #
# the mesh telemetry channel: ship -> absorb -> one merged timeline

def test_ship_returns_unshipped_tail_exactly_once():
    rec = FlightRecorder(clock=lambda: 0.0)
    rec.enabled = True
    rec.shard = 1
    rec.record("a", x=1)
    rec.record("b")
    shipped = rec.ship()
    assert [e["event"] for e in shipped] == ["a", "b"]
    # shard-tagged: the worker's origin key rides every shipped event
    assert all(e["shard"] == 1 and e["epoch"] == 0 for e in shipped)
    assert shipped[0]["wseq"] == shipped[0]["seq"]
    assert rec.ship() == []          # the mark advanced
    rec.record("c")
    assert [e["event"] for e in rec.ship()] == ["c"]


def test_disabled_telemetry_channel_never_touches_the_ring():
    """The S3 one-attribute assertions: while observability is off,
    ``ship()`` is a counter compare and ``record``/``absorb`` return
    before any ring access — a ring that explodes on use proves it."""
    rec = FlightRecorder()
    assert rec.enabled is False

    class _Boom:
        def __iter__(self):
            raise AssertionError("disabled ship() walked the ring")

        def append(self, item):
            raise AssertionError("disabled path appended to the ring")

    rec._ring = _Boom()
    assert rec.ship() == []
    rec.record("dropped", x=1)
    assert rec.absorb([{"event": "x", "seq": 1}]) == 0


def test_absorb_assigns_fresh_seqs_and_keeps_origin():
    worker = FlightRecorder(clock=lambda: 5.0)
    worker.enabled = True
    worker.shard = 2
    worker.epoch = 3
    worker.record("w.event", n=1)
    ctrl = FlightRecorder(clock=lambda: 9.0)
    ctrl.enabled = True
    ctrl.record("c.event")
    assert ctrl.absorb(worker.ship()) == 1
    events = ctrl.snapshot()
    assert [e["event"] for e in events] == ["c.event", "w.event"]
    absorbed = events[-1]
    assert absorbed["seq"] == 2              # fresh controller seq
    assert (absorbed["shard"], absorbed["epoch"], absorbed["wseq"]) \
        == (2, 3, 1)
    assert absorbed["t"] == 5.0              # the worker's own clock
    assert absorbed["fields"] == {"n": 1}


def test_absorb_dedup_skips_live_shipped_origins():
    """Black-box recovery: the dead worker's tail overlaps what it
    already shipped live — dedup absorbs only the genuinely new events,
    keyed by origin, and the merged timeline stays duplicate-free."""
    worker = FlightRecorder(clock=lambda: 1.0)
    worker.enabled = True
    worker.shard = 1
    ctrl = FlightRecorder(clock=lambda: 2.0)
    ctrl.enabled = True
    worker.record("a")
    worker.record("b")
    ctrl.absorb(worker.ship())               # live ship before the crash
    worker.record("c")                       # died before shipping this
    tail = worker.tail(BLACKBOX_TAIL)        # the black-box shape: a,b,c
    assert ctrl.absorb(tail, dedup=True) == 1
    mesh_events = [e for e in ctrl.snapshot() if e.get("shard") == 1]
    assert [e["event"] for e in mesh_events] == ["a", "b", "c"]


def test_merge_key_orders_colliding_dumps_deterministically():
    """The S1 ordering fix: per-process seqs collide when a controller
    dump and a dead worker's black box are concatenated; the merge key
    ``(seq, epoch, shard, wseq)`` interleaves them deterministically
    (controller rows first, then shards, then respawn epochs)."""
    rows = [
        {"seq": 1, "t": 0.0, "event": "w1", "fields": {},
         "shard": 1, "epoch": 0, "wseq": 1},
        {"seq": 1, "t": 0.0, "event": "c", "fields": {}},
        {"seq": 1, "t": 0.0, "event": "w0e1", "fields": {},
         "shard": 0, "epoch": 1, "wseq": 1},
        {"seq": 1, "t": 0.0, "event": "w0", "fields": {},
         "shard": 0, "epoch": 0, "wseq": 1},
        {"seq": 2, "t": 0.0, "event": "w0b", "fields": {},
         "shard": 0, "epoch": 0, "wseq": 2},
    ]
    merged = load_jsonl("\n".join(json.dumps(r) for r in rows))
    assert [e["event"] for e in merged] == ["c", "w0", "w1", "w0e1", "w0b"]


def test_untagged_dump_keeps_the_pre_mesh_shape():
    """Single-process runs are byte-identical to the pre-mesh format: no
    origin keys in the events, no shard column in the timeline."""
    rec = FlightRecorder(clock=lambda: 1.0)
    rec.enabled = True
    rec.record("a", k=1)
    events = load_jsonl(rec.to_jsonl())
    assert set(events[0]) == {"seq", "t", "event", "fields"}
    table = render_timeline(events)
    assert "shard" not in table.splitlines()[0]


def test_timeline_grows_shard_column_only_when_tagged():
    untagged = [{"seq": 1, "t": 0.0, "event": "local.ev", "fields": {}}]
    tagged = untagged + [{"seq": 2, "t": 0.0, "event": "worker.ev",
                          "fields": {}, "shard": 3, "epoch": 0, "wseq": 1}]
    table = render_timeline(tagged)
    header, row_local, row_worker = table.splitlines()
    assert "shard" in header
    assert "-" in row_local.split("local.ev")[0]    # controller rows: '-'
    assert "3" in row_worker.split("worker.ev")[0]  # worker rows: shard id


def test_blackbox_write_read_round_trip(tmp_path):
    rec = FlightRecorder(clock=lambda: 2.0)
    rec.enabled = True
    rec.shard = 1
    rec.epoch = 2
    for i in range(BLACKBOX_TAIL + 10):
        rec.record("e", i=i)
    path = str(tmp_path / "bb.json")
    write_blackbox(path, rec, phases_jsonl="{}")
    bb = read_blackbox(path)
    assert bb["pid"] == os.getpid()
    assert (bb["shard"], bb["epoch"]) == (1, 2)
    assert len(bb["events"]) == BLACKBOX_TAIL     # bounded tail
    assert bb["events"][-1]["fields"]["i"] == BLACKBOX_TAIL + 9
    assert bb["phases"] == "{}"
    # best-effort by contract: absent and torn files read as None
    assert read_blackbox(str(tmp_path / "missing.json")) is None
    (tmp_path / "torn.json").write_text("{not json", encoding="utf-8")
    assert read_blackbox(str(tmp_path / "torn.json")) is None


# ---------------------------------------------------------------------- #
# fault-path integration: the auto-dump sources

def test_farm_quarantine_entry_records_and_dumps(tmp_path):
    """Entering the farm's quarantine set leaves a farm.quarantine.enter
    event (with the offending hashes) and auto-dumps the ring."""
    with enabled_flight(dump_dir=str(tmp_path)) as rec:
        rec.clear()
        farm = TpuDocFarm(2, capacity=32, quarantine_threshold=1)
        good = _stream(1, 4)[0]
        bad = bytes(bit_flipped(good))
        farm.apply_changes([[good], [bad]])
        events = rec.snapshot()
    kinds = [e["event"] for e in events]
    assert "farm.quarantine.enter" in kinds
    enter = next(e for e in events if e["event"] == "farm.quarantine.enter")
    assert enter["fields"]["doc"] == 1
    assert enter["fields"]["kind"]
    assert rec.dump_paths, "quarantine entry did not dump"
    dumped = load_jsonl(open(rec.dump_paths[0], encoding="utf-8").read())
    assert any(e["event"] == "farm.quarantine.enter" for e in dumped)
    # release leaves its event too
    with enabled_flight():
        farm.release_quarantine()
        assert get_flight().snapshot()[-1]["event"] == "farm.quarantine.release"


def test_session_retry_exhaustion_records_and_dumps(tmp_path):
    """A channel burning its retry budget leaves retransmit events and a
    session.quarantine.enter, and dumps the ring."""
    from automerge_tpu import backend as Backend
    from automerge_tpu.sync_session import (
        BackendDriver,
        SessionConfig,
        SyncSession,
    )
    from automerge_tpu.testing.chaos import ManualClock

    clock = ManualClock()
    with enabled_flight(dump_dir=str(tmp_path)) as rec:
        rec.clear()
        session = SyncSession(
            BackendDriver(Backend.init()), clock=clock,
            rng=random.Random(0),
            config=SessionConfig(timeout=1.0, max_retries=2,
                                 backoff_base=0.1, backoff_cap=0.2),
        )
        # generate one payload frame; never ack it
        assert session.poll() is not None
        for _ in range(8):
            clock.advance(5.0)
            session.poll()
            if session.quarantined:
                break
        assert session.quarantined
        events = rec.snapshot()
    kinds = [e["event"] for e in events]
    assert kinds.count("session.retransmit") >= 2
    assert "session.quarantine.enter" in kinds
    # timestamps came from the injected (simulated) clock
    retransmit = next(e for e in events
                      if e["event"] == "session.retransmit")
    assert retransmit["t"] >= 5.0
    assert rec.dump_paths
    # release leaves its event
    with enabled_flight():
        session.release()
        assert get_flight().snapshot()[-1]["event"] == \
            "session.quarantine.release"


def test_engine_recompile_event_names_shape_bucket():
    with enabled_flight() as rec:
        rec.clear()
        # a slab (10 pages) no other test builds: the event needs a fresh
        # compile, and test files that ran earlier in the same process
        # leave their shapes in the jit cache
        farm = TpuDocFarm(2, capacity=320)
        from automerge_tpu.obs.metrics import enabled_metrics

        with enabled_metrics():
            buf = _stream(1, 4)[0]
            farm.apply_changes([[buf], [buf]])
        events = [e for e in rec.snapshot()
                  if e["event"] == "engine.recompile"]
    assert events, "fresh shapes compiled without a recompile event"
    assert events[0]["fields"]["fn"]
    assert events[0]["fields"]["shapes"]


# ---------------------------------------------------------------------- #
# acceptance shape: chaos+poison loadgen auto-dumps a usable timeline

@pytest.fixture(scope="module")
def poison_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("flight")
    farm = TpuDocFarm(8, capacity=128)
    gen = LoadGen(farm, LoadConfig(
        clients=24, docs=8, edits_per_client=2, ops_per_edit=3,
        spread=0.5, chaos=0.15, poison=0.25, seed=5,
        observability="full", flight_dir=str(tmp),
    ))
    report = gen.run()
    return {"report": report, "farm": farm}


def test_poison_run_quarantines_and_dumps(poison_run):
    report = poison_run["report"]
    assert report["quarantined_docs"] > 0
    assert report["flight_dumps"], "no flight dump despite quarantines"
    for path in report["flight_dumps"]:
        assert os.path.exists(path)


def test_poison_run_timeline_contains_the_quarantine_events(poison_run):
    """The acceptance criterion: the auto-dumped timeline contains the
    quarantine (and any watchdog) events that occurred, causally
    ordered, and renders."""
    path = poison_run["report"]["flight_dumps"][-1]
    events = load_jsonl(open(path, encoding="utf-8").read())
    kinds = {e["event"] for e in events}
    assert "farm.quarantine.enter" in kinds
    assert "batcher.flush" in kinds
    assert "flight.trigger" in kinds
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    quarantined_docs = {
        e["fields"]["doc"] for e in events
        if e["event"] == "farm.quarantine.enter"
    }
    assert quarantined_docs <= set(poison_run["farm"].quarantine) | \
        quarantined_docs  # every event names a doc the farm quarantined
    assert quarantined_docs & set(poison_run["farm"].quarantine)
    table = render_timeline(events)
    assert "farm.quarantine.enter" in table


def test_flight_cli_renders_dump(poison_run, capsys):
    from automerge_tpu.obs.__main__ import main

    path = poison_run["report"]["flight_dumps"][-1]
    assert main(["--flight", path]) == 0
    out = capsys.readouterr().out
    assert "farm.quarantine.enter" in out
    assert "seq" in out.splitlines()[0]
    # machine-readable variant
    assert main(["--flight", path, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert any(e["event"] == "flight.trigger" for e in payload["events"])
