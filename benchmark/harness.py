"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``workloads/<cell>.json`` holds the
cell's fixed rate, ``configs/<config>.json`` and ``traffic/<mix>.json``
the deployment and the mix, and ``metrics/<metric>.py`` the reader of each
per-layer metric. A later PR adds a cell, a mix or a metric as new files.

The window drives ``TpuDocFarm.apply_changes(per_doc_buffers,
isolation="doc")`` open loop: changes fall due on the schedule whatever the
farm does, and the harness batches them by the cell's policy (`drive`). A
change's latency runs from its due time to the return of the call that
carries its patch.
"""
from __future__ import annotations

import bisect
import gc
import importlib.util
import json
import logging
import os
import re
import sys
import time

import numpy as np

from . import generator, reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("lose_ack", "stale", "half_batch", "alter")
LATE_S = 60.0  # how long past the window's close a change may still come


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = REPO, unlisted: bool = False) -> dict:
    """The cell's manifest entry, cell file, configuration, traffic mix,
    and the end-to-end and per-layer metrics it reports. With `unlisted`,
    a cell left out of BENCHMARK.json runs from the ``entry`` its cell
    file keeps (control.py can run it; run.py never does)."""
    manifest = _load_json(root, "BENCHMARK.json")
    bench = os.path.join(root, manifest["paths"][0])
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells and unlisted:
        cells[name] = dict(_load_json(bench, "workloads", f"{name}.json")
                           ["entry"], name=name)
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = cells[name]

    def reports(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "entry": entry,
        "bench": bench,
        "cell": _load_json(bench, "workloads", f"{name}.json"),
        "config": _load_json(bench, "configs", f"{entry['config']}.json"),
        "traffic": _load_json(bench, "traffic", f"{entry['traffic']}.json"),
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


def load_reader(bench: str, metric: str):
    """The `read(ctx)` function of metrics/<metric>.py."""
    path = os.path.join(bench, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def find_chip(chips: int, peaks: dict):
    """The device list, or NoChip: the benchmark runs on a TPU only."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU found (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    return devices


# ---------------------------------------------------------------------- #
# traffic


class Traffic:
    """Every change of the run, in due order, plus the set-up deliveries."""

    def __init__(self, cfg, rate, warmup_s, seconds, seed, workers=8):
        config, traffic = cfg["config"], cfg["traffic"]
        self.docs = config["docs"]
        kind = config["doc"]["kind"]
        plan, _scramble = generator.schedule(
            config, traffic, rate, warmup_s, seconds, seed,
            cfg["cell"]["batching"].get("docs", 0))
        # a preload of each document's own is made with its traffic, in
        # the worker; shared templates here, once
        own = config["preload"].get("kind") == "trace"
        if own:
            self.templates = [None] * self.docs
            self.tpl_of = list(range(self.docs))
        else:
            self.templates = (generator.map_templates(config)
                              if kind == "map"
                              else generator.text_templates(config))
            ntpl = len(self.templates)
            self.tpl_of = [d % ntpl for d in range(self.docs)]
        entries: dict[int, list] = {}
        for i, (due, doc, a, k, length) in enumerate(plan):
            entries.setdefault(doc, []).append((i, due, a, k, length))
        self.touched = sorted(entries)
        n = len(plan)
        self.due = np.array([p[0] for p in plan])
        self.doc = np.array([p[1] for p in plan], np.int64)
        self.buf = [None] * n
        self.nops = np.zeros(n, np.int64)
        self.actor = [None] * n
        self.seq = np.zeros(n, np.int64)
        self.ref = [None] * n
        self.rows = {}
        self.extra = {d: ([], []) for d in range(self.docs)}  # bufs, refs
        # the set-up deliveries that compile every change width (plan
        # indices per delivery)
        self.shape_batches = []
        for i, p in enumerate(plan):
            if p[0] < -warmup_s - 1.0:
                if not self.shape_batches or plan[i - 1][0] != p[0]:
                    self.shape_batches.append([])
                self.shape_batches[-1].append(i)

        # each pool actor joins once, in the first doc it edits: the farm
        # interns actor ids globally, so the actor table is complete (and
        # every program's actor-rank shape fixed) before the run
        joins: dict[int, list] = {}
        joined: set = set()
        for d in self.touched:
            for a, j in enumerate(generator.doc_actors(config, seed, d)):
                if j not in joined:
                    joined.add(j)
                    joins.setdefault(d, []).append(a)

        def task(d):
            return (config, traffic, seed, d,
                    None if own else self.templates[self.tpl_of[d]][2],
                    entries.get(d, []), joins.get(d, []))

        tasks = [task(d) for d in (range(self.docs) if own else self.touched)]
        if workers > 1 and (own or len(tasks) > 64):
            import multiprocessing

            with multiprocessing.get_context("spawn").Pool(
                    min(workers, len(tasks))) as pool:
                for done in pool.imap_unordered(
                        generator.generate_doc, tasks,
                        chunksize=max(1, len(tasks) // (4 * workers))):
                    self._take(done)
        else:
            for t in tasks:
                self._take(generator.generate_doc(t))

    def _take(self, done):
        d, joins, changes, rows, preload = done
        if preload is not None:
            self.templates[d] = preload + (None,)
        bufs, refs = self.extra[d]
        self.extra[d] = (bufs + [b for b, _ in joins],
                         refs + [r for _, r in joins])
        for i, buf, ref, nops, actor, seq in changes:
            self.buf[i], self.ref[i], self.nops[i] = buf, ref, nops
            self.actor[i], self.seq[i] = actor, seq
        self.rows[d] = rows

    def preload(self):
        """Per doc: template history, then the joins (set-up)."""
        out = []
        for d in range(self.docs):
            bufs = list(self.templates[self.tpl_of[d]][0])
            bufs.extend(self.extra[d][0])
            out.append(bufs)
        return out

    def preload_refs(self, d):
        refs = list(self.templates[self.tpl_of[d]][1])
        refs.extend(self.extra[d][1])
        return refs

    def preload_ops(self) -> int:
        total = 0
        for d in range(self.docs):
            for r in self.templates[self.tpl_of[d]][1]:
                total += len(r)
            for r in self.extra[d][1]:
                total += len(r)
        return total


def farm_capacity(traffic: Traffic, config) -> int:
    """The farm's per-document sizing hint, from the configuration. The
    program sizes its slab from it and never lets its dense working width
    fall below its pow2, so a hint that no document outgrows keeps every
    program's shapes the same from seed to seed (a hint computed from the
    run's own traffic changed them)."""
    capacity = config["capacity"]
    longest = config["preload"].get("rows", 0) + max(traffic.rows.values(),
                                                     default=0)
    if longest > capacity:
        say(f"warning: a document grows to ~{longest} rows, past the "
            f"sizing hint {capacity}: its width changes in the run")
    return capacity


# ---------------------------------------------------------------------- #
# the delivery loop


class Recorder:
    """Return times, acknowledgement checks and the patches served."""

    def __init__(self, traffic: Traffic):
        n = len(traffic.due)
        self.t = traffic
        self.returned = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.delivery_of = np.full(n, -1, np.int64)
        self.deliveries = []  # (change indices, t_call, t_return)
        self.late = []  # per delivery: flushed by the max wait, not full
        self.patches = {}  # doc -> [(delivery index, diffs)]

    def record(self, batch, result, t_call, t_ret, late=False):
        t = self.t
        k = len(self.deliveries)
        self.deliveries.append((batch, t_call, t_ret))
        self.late.append(late)
        self.returned[batch] = t_ret
        self.delivery_of[batch] = k
        seen = set()
        for i in batch:
            d = int(t.doc[i])
            outcome = result.outcomes[d]
            patch = result[d]
            ok = (outcome.status == "applied" and not outcome.fallback
                  and patch["clock"].get(t.actor[i], 0) >= t.seq[i]
                  and patch["pendingChanges"] == 0)
            if not ok:
                self.failed[i] = True
            if d not in seen:
                seen.add(d)
                self.patches.setdefault(d, []).append((k, patch["diffs"]))


def _faulty(apply, fault, traffic):
    """apply_changes with the served path broken underneath (tests and the
    control only)."""
    rng = np.random.default_rng(7)

    def call(per_doc, isolation="doc"):
        active = [d for d, b in enumerate(per_doc) if b]
        if fault == "lose_ack" and active:
            per_doc = list(per_doc)
            d = active[int(rng.integers(len(active)))]
            per_doc[d] = per_doc[d][:-1]  # acknowledged, never applied
        if fault == "half_batch":
            keep = set(active[:len(active) // 2])
            per_doc = [b if d in keep else [] for d, b in enumerate(per_doc)]
        if fault == "stale":
            return apply([[] for _ in per_doc], isolation=isolation)
        result = apply(per_doc, isolation=isolation)
        if fault == "alter" and active:
            d = active[int(rng.integers(len(active)))]
            _alter(result[d]["diffs"])
        return result

    return call


def _alter(diff):
    """Changes one served value in place."""
    for values in diff.get("props", {}).values():
        for v in values.values():
            if "objectId" in v:
                return _alter(v)
            v["value"] = (v["value"] + 1 if isinstance(v["value"], int)
                          else v["value"] + "!")
            return True
    for e in diff.get("edits", ()):
        if e["action"] == "insert":
            e["value"]["value"] += "!"
            return True
        if e["action"] == "multi-insert":
            e["values"][0] += "!"
            return True
    return False


def drive(apply, traffic: Traffic, rec: Recorder, origin, batching: dict,
          done, start=0, on_delivery=None, annotate=None):
    """Delivers the run's changes open loop until `done()`. `origin` is the
    perf_counter reading at due time 0. The batching policy is the cell
    file's ``batching``, ``{"policy": "docs", "docs": D, "max_wait_s": T}``,
    the flush rule of the program's own ``DynamicBatcher`` (N dirty docs or
    T since the window opened), one call at a time: a delivery goes as soon
    as D documents have a change waiting, or when the oldest waiting change
    has waited T, or once every change has fallen due. It carries every
    waiting change of the D documents whose oldest waiting change fell due
    first, in due order, so one document may take several causally ordered
    changes. Returns the number of deliveries that T flushed."""
    import heapq
    from collections import deque

    annotate = annotate or _NoAnnotation
    due, docs = traffic.due, traffic.docs
    n = len(due)
    if batching["policy"] != "docs":
        raise ValueError(f"unknown batching policy {batching['policy']!r}")
    width, max_wait = batching["docs"], batching["max_wait_s"]
    nxt = start
    queues: dict = {}  # doc -> deque of waiting indices
    heap: list = []  # (oldest due, doc) of docs with a change waiting
    deadline_flushes = 0
    while not done():
        now = time.perf_counter() - origin
        while nxt < n and due[nxt] <= now:
            d = int(traffic.doc[nxt])
            q = queues.setdefault(d, deque())
            if not q:
                heapq.heappush(heap, (due[nxt], d))
            q.append(nxt)
            nxt += 1
        full = len(heap) >= width
        late = bool(heap) and now - heap[0][0] >= max_wait
        if not heap or not (full or late or nxt == n):
            if not heap and nxt == n:
                break
            wake = due[nxt] if nxt < n else now
            if heap:
                wake = min(wake, heap[0][0] + max_wait)
            with annotate("bench.wait"):
                time.sleep(min(max(wake - now, 0.0), 0.05))
            continue
        deadline_flushes += not full
        batch = []
        for _ in range(min(width, len(heap))):
            _due, d = heapq.heappop(heap)
            batch.extend(queues.pop(d))
        batch.sort()
        per_doc = [[] for _ in range(docs)]
        for k in batch:
            per_doc[traffic.doc[k]].append(traffic.buf[k])
        if on_delivery is not None:
            on_delivery(batch)
        with annotate("bench.deliver"):
            t_call = time.perf_counter()
            result = apply(per_doc, isolation="doc")
            t_ret = time.perf_counter()
        rec.record(np.asarray(batch), result, t_call - origin, t_ret - origin,
                   late=not full)
    return deadline_flushes


class _NoAnnotation:
    def __init__(self, _name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------------- #
# the check


def check(traffic: Traffic, rec: Recorder, preload_patches, final_patches,
          window) -> dict:
    """Holds every patch served to every touched document, the preload's
    included, against the reference fed the same changes
    (``reference.patch_agrees``), and the sampled documents' whole-document
    patches against the reference's final state. Returns the numbers
    compared."""
    lo, hi = window
    by_doc: dict[int, list] = {}
    for i in range(len(traffic.due)):
        by_doc.setdefault(int(traffic.doc[i]), []).append(i)
    delivery_of = rec.delivery_of
    users: dict[int, int] = {}  # template -> touched documents it serves
    for d in traffic.touched:
        users[traffic.tpl_of[d]] = users.get(traffic.tpl_of[d], 0) + 1
    tpl_ref = {}

    def preloaded(t):
        base = reference.RefDoc()
        for ops in traffic.templates[t][1]:
            base.apply(ops)
        return base

    patches, bad_patches, bad_final = 0, 0, 0
    for d in traffic.touched:
        t = traffic.tpl_of[d]
        if users[t] == 1:  # the template serves this document alone
            ref = preloaded(t)
        else:
            if t not in tpl_ref:
                tpl_ref[t] = preloaded(t)
            ref = tpl_ref[t].copy()
        touched: set = set()
        for ops in traffic.extra[d][1]:
            ref.apply(ops)
            touched |= reference.root_keys(ops)
        got = reference.PatchDoc()
        patches += 1
        bad_patches += not reference.patch_agrees(
            preload_patches[d], got, ref, touched)
        pending = by_doc.get(d, [])
        pos = 0
        for k, diffs in rec.patches.get(d, ()):
            touched = set()
            while pos < len(pending) and delivery_of[pending[pos]] == k:
                ref.apply(traffic.ref[pending[pos]])
                touched |= reference.root_keys(traffic.ref[pending[pos]])
                pos += 1
            patches += 1
            bad_patches += not reference.patch_agrees(diffs, got, ref,
                                                      touched)
        if d in final_patches:
            whole = reference.PatchDoc()
            whole.apply(final_patches[d])
            bad_final += whole.state() != ref.state()
    failed = int(rec.failed[lo:hi].sum()
                 + np.isnan(rec.returned[lo:hi]).sum())
    return {"patches_checked": patches, "patch_mismatches": bad_patches,
            "final_checked": len(final_patches),
            "final_mismatches": bad_final, "failed_changes": failed}


# ---------------------------------------------------------------------- #
# the run


def pct(values, q):
    return float(np.percentile(np.asarray(values), q))


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = REPO, require_chip: bool = True,
             fault: str | None = None, overrides: dict | None = None,
             trace_dir: str | None = None, diag: dict | None = None,
             unlisted: bool = False) -> dict:
    """Runs one cell and returns the result object (the last line).
    `diag`, when given, is filled with the run's latencies and deliveries
    (the knee sweep reads them)."""
    cfg = load_cell(name, root, unlisted)
    for key, value in (overrides or {}).items():
        section, field = key.split(".", 1)
        cfg[section] = dict(cfg[section], **{field: value})
    bench = cfg["bench"]
    peaks = _load_json(bench, "peaks.json")
    import jax

    if require_chip:
        devices = find_chip(cfg["entry"]["chips"], peaks)
    else:
        devices = jax.devices()
    dev = devices[0]
    sys.path.insert(0, root)
    from automerge_tpu import native
    from automerge_tpu.obs.prof import get_observatory
    from automerge_tpu.profiling import PhaseProfile, use_profile
    from automerge_tpu.tpu.compile_cache import (
        compile_cache_stats,
        enable_compile_cache,
    )
    from automerge_tpu.tpu.farm import TpuDocFarm

    say(f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    say(f"compile cache dir={enable_compile_cache()} "
        f"native={native.available()}")
    jax_compiles = _listen_for_compiles(jax)

    config, traffic_cfg, cell = cfg["config"], cfg["traffic"], cfg["cell"]
    rate, warmup_s = cell["rate_per_s"], traffic_cfg["warmup_s"]
    t0 = time.perf_counter()
    traffic = Traffic(cfg, rate, warmup_s, seconds, seed)
    n_warm = int((traffic.due < 0).sum())
    n = int((traffic.due < seconds).sum())  # warm-up and window
    say(f"generated {n} changes ({n_warm} warm-up) for "
        f"{len(traffic.touched)} docs in {time.perf_counter() - t0:.3f} s")

    capacity = farm_capacity(traffic, config)
    farm = TpuDocFarm(config["docs"], capacity=capacity)
    apply = farm.apply_changes
    if fault is not None:
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        apply = _faulty(apply, fault, traffic)
    t0 = time.perf_counter()
    pre = farm.apply_changes(traffic.preload(), isolation="doc")
    bad_pre = [d for d, o in enumerate(pre.outcomes)
               if o.status != "applied" or o.fallback]
    preload_patches = {d: pre[d]["diffs"] for d in traffic.touched}
    del pre
    say(f"preload: docs={config['docs']} ops={traffic.preload_ops()} "
        f"capacity={capacity} wall_s={time.perf_counter() - t0:.3f} "
        f"failed_docs={len(bad_pre)}")

    rec = Recorder(traffic)
    t0 = time.perf_counter()
    for batch in traffic.shape_batches:
        per_doc = [[] for _ in range(traffic.docs)]
        for k in batch:
            per_doc[traffic.doc[k]].append(traffic.buf[k])
        t_call = time.perf_counter()
        result = apply(per_doc, isolation="doc")
        rec.record(np.asarray(batch), result, t_call, time.perf_counter())
    shaped = sum(len(b) for b in traffic.shape_batches)
    say(f"shape warm-up: {len(traffic.shape_batches)} deliveries "
        f"wall_s={time.perf_counter() - t0:.3f}")
    obs = get_observatory()
    annotate = _NoAnnotation
    if trace:
        annotate = jax.profiler.TraceAnnotation

    def programs():
        return {name: max(p.cache_size(), 0)
                for name, p in obs.programs().items()}

    def compiles():
        return sum(programs().values())

    compile_log = _CompileLog()

    prof = PhaseProfile()
    marks = {}
    lo = n_warm

    def on_delivery(batch):
        if batch[-1] >= lo and "window" not in marks:
            marks["window"] = (compiles(), jax_compiles["backend"])
            marks["programs"] = programs()
            compile_log.start(jax)
            if trace:
                marks["trace_ctx"] = use_profile(prof)
                marks["trace_ctx"].__enter__()
                marks["annotation"] = annotate("bench.window")
                marks["annotation"].__enter__()

    trace_path = None
    if trace:
        trace_path = trace_dir or os.path.join(bench, ".trace")
        os.makedirs(trace_path, exist_ok=True)
    origin = time.perf_counter() + warmup_s
    setup_s = origin - t_start
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_path, profiler_options=options)
    window_left = [n - lo]

    def done():
        # every window change served, or a minute past the close: what
        # never came counts as failed
        return (window_left[0] == 0
                or time.perf_counter() - origin > seconds + LATE_S)

    def record(batch, result, t_call, t_ret, late=False):
        rec_record(batch, result, t_call, t_ret, late)
        window_left[0] -= int(((batch >= lo) & (batch < n)).sum())

    rec_record, rec.record = rec.record, record
    drive(apply, traffic, rec, origin, cell["batching"], done,
          start=shaped, on_delivery=on_delivery, annotate=annotate)
    t_end = time.perf_counter() - origin
    if "window" not in marks:
        on_delivery(np.array([lo]))
    compile_log.stop(jax)
    if trace:
        marks["annotation"].__exit__(None, None, None)
        marks["trace_ctx"].__exit__(None, None, None)
        jax.block_until_ready(farm.engine.slab)
        jax.profiler.stop_trace()
    window_compiles = compiles() - marks["window"][0]
    window_backend = jax_compiles["backend"] - marks["window"][1]
    grown = {name: k - marks["programs"].get(name, 0)
             for name, k in programs().items()
             if k > marks["programs"].get(name, 0)}
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    in_window = [k for k, d in enumerate(rec.deliveries) if d[0][-1] >= lo]
    say(f"window: changes={n - lo} deliveries={len(in_window)} "
        f"max_wait_flushes={sum(rec.late[k] for k in in_window)} "
        f"closed_at_s={t_end:.3f} "
        f"compiles={window_compiles} backend_compiles={window_backend} "
        f"cache={compile_cache_stats()}")
    say(f"window compiles by program: {json.dumps(grown)}")
    for line in compile_log.names:
        say(f"window compile: {line}")

    # the whole-document patches of a sample drawn from the seed
    srng = np.random.default_rng(seed ^ 0x5A)
    hot = sorted(traffic.touched, key=lambda d: -len(rec.patches.get(d, ())))
    sample = set(hot[:16])
    rest = [d for d in traffic.touched if d not in sample]
    if rest:
        sample.update(int(d) for d in srng.choice(
            rest, min(48, len(rest)), replace=False))
    final_patches = {d: farm.get_patch(d)["diffs"] for d in sorted(sample)}
    degraded = len(farm.degraded) + len(farm.quarantine)
    del farm, apply
    gc.collect()

    # the numbers users feel
    window = slice(lo, n)
    due, ret = traffic.due[window], rec.returned[window]
    lat_ms = (ret - due) * 1000.0
    done = np.isfinite(lat_ms)
    # the window's served work over the time it took: from the window's
    # start to the return of its last served change, so a farm that falls
    # behind reads lower
    served = done & ~rec.failed[window]
    took = float(ret[served].max()) if served.any() else None
    ops_total = traffic.preload_ops() + int(traffic.nops.sum())
    metrics = {
        "ops_per_s": (float(traffic.nops[window][served].sum() / took)
                      if took else None),
        "change_p50_ms": pct(lat_ms[done], 50) if done.any() else None,
        "change_p95_ms": pct(lat_ms[done], 95) if done.any() else None,
        "device_bytes_per_op": peak / ops_total,
        "setup_s": setup_s,
    }
    wins = [d for d in rec.deliveries if d[0][-1] >= lo]
    lateness = [t_call - traffic.due[b[0]] for b, t_call, _r in wins]
    say(f"generator lateness: window deliveries={len(wins)} start p50="
        f"{pct(lateness, 50) if wins else 0:.4f} s after their oldest "
        f"change fell due; changes/delivery p50="
        f"{pct([len(b) for b, _c, _r in wins], 50) if wins else 0}; "
        f"delivery wall p50="
        f"{pct([r - c for _b, c, r in wins], 50) if wins else 0:.4f} s")

    if diag is not None:
        half = len(lat_ms) // 2
        diag.update(
            rate=rate, lat_ms=lat_ms, due=due,
            p50_first_half=pct(lat_ms[:half][done[:half]], 50) if half else None,
            p50_second_half=pct(lat_ms[half:][done[half:]], 50) if half else None,
            offered_ops_per_s=float(traffic.nops[window].sum() / seconds),
            deliveries=[(len(b), c, r) for b, c, r in rec.deliveries
                        if b[-1] >= lo],
            docs_touched=len(traffic.touched),
            compiles=window_compiles, backend_compiles=window_backend,
            peak=peak, setup_s=setup_s)
    t0 = time.perf_counter()
    numbers = check(traffic, rec, preload_patches, final_patches, (lo, n))
    numbers["failed_changes"] += len(bad_pre) + degraded
    say(f"reference check: {numbers['patches_checked']} patches of "
        f"{len(traffic.touched)} docs, {numbers['final_checked']} "
        f"whole-document patches, in {time.perf_counter() - t0:.3f} s")
    checks = {key: {"value": numbers[key], "limit": 0} for key in
              ("failed_changes", "patch_mismatches", "final_mismatches")}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {
        "correct": bool(correct),
        "attempted": int(n - lo),
        "failed": int(rec.failed[window].sum() + (~done).sum()),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    units = {m["name"]: m["unit"] for m in cfg["end_to_end"]}
    if trace:
        from . import tracereduce

        window_ops = int(traffic.nops[window].sum())
        dev_red = tracereduce.reduce_dir(trace_path, len(devices))
        ctx = {
            "spans": spans_self_s(prof),
            "counters": prof.counters,
            "kop": window_ops / 1000.0,
            "device": dev_red,
            "compiles_in_window": window_compiles,
            "compiled_in_window": grown,
        }
        say_trace(dev_red, prof.counters, obs.modules())
        units = {m["name"]: m["unit"] for m in cfg["per_layer"]}
        for m in cfg["per_layer"]:
            value = load_reader(bench, m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if dev_red is not None:
            result["device"]["busy_s"] = dev_red["busy_s"]
            result["device"]["window_s"] = dev_red["window_s"]
            result["breakdown"] = dev_red["breakdown"]
    else:
        for name_, unit in units.items():
            if metrics.get(name_) is not None:
                result["metrics"][name_] = {"value": metrics[name_],
                                            "unit": unit}
    say("end-to-end: " + json.dumps(metrics))
    for key, c in checks.items():
        say(f"check {key}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


class _CompileLog(logging.Handler):
    """The jit programs XLA compiles (or loads from the persistent cache)
    while it listens: JAX's own compile log, by function name and shapes."""

    def __init__(self):
        super().__init__()
        self.names: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            name = msg[len("Compiling "):].split(" ", 1)[0]
            shapes = re.findall(r"\w+\[\d+(?:,\d+)+\]", msg)
            self.names.append(f"{name} {' '.join(shapes)}")

    def start(self, jax):
        logging.getLogger("jax._src.interpreters.pxla").addHandler(self)
        jax.config.update("jax_log_compiles", True)

    def stop(self, jax):
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax._src.interpreters.pxla").removeHandler(self)


_BACKEND_COMPILES = {"backend": 0}


def _listen_for_compiles(jax) -> dict:
    """Counts XLA backend compiles (persistent-cache misses) in this
    process; registers its listener once."""
    if "listening" not in _BACKEND_COMPILES:
        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _BACKEND_COMPILES["backend"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _BACKEND_COMPILES["listening"] = True
    return _BACKEND_COMPILES


def spans_self_s(prof) -> dict:
    """{span name: self seconds}: each span's time less its children's."""
    out: dict[str, float] = {}
    stack = list(prof.root.children.values())
    while stack:
        node = stack.pop()
        child = sum(c.total_s for c in node.children.values())
        out[node.name] = out.get(node.name, 0.0) + node.total_s - child
        stack.extend(node.children.values())
    return out


def say_trace(dev: dict | None, counters: dict, modules: dict) -> None:
    """Prints what the traced run's reduction holds beyond the metrics:
    the device-idle split by ``am.*`` span, the labelled gaps, the marks,
    the residual of the calls, the program's counters, and device seconds
    by amprof program (`modules`: XLA module name -> amprof name)."""
    say("program counters in the window (s, calls): " + ", ".join(
        f"{k} {c['seconds']:.4f} x{c['calls']}"
        for k, c in sorted(counters.items())))
    if dev is None:
        return
    by_name: dict = {}
    for module, seconds in dev["programs"].items():
        name = modules.get(f"jit_{module}", f"jit_{module}")
        by_name[name] = by_name.get(name, 0.0) + seconds
    say("device seconds by amprof program: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(by_name.items(),
                                           key=lambda kv: -kv[1])))
    out = dev["timeline"]
    if out is None:
        return
    ms = {k: round(v * 1000.0, 3) for k, v in out["idle_by_span"].items()}
    say(f"device idle by span (ms): idle={out['idle_s'] * 1000.0:.3f} "
        f"in_apply={out['idle_in_apply_s'] * 1000.0:.3f} {ms}")
    say("idle gaps by span (s): " + ", ".join(
        f"{label} {s:.4f}" for label, s in out["idle_gaps"]))
    say("am marks in the window (s, calls): " + ", ".join(
        f"{k} {m['seconds']:.4f} x{m['calls']}"
        for k, m in sorted(out["marks"].items())))
    apply_s = out["apply_s"] or 1.0
    say(f"apply_changes outside its phases: {out['residual_s']:.4f} s "
        f"of {out['apply_s']:.4f} s "
        f"({100.0 * out['residual_s'] / apply_s:.3f}%); under no mark: "
        f"{out['unnamed_s']:.4f} s "
        f"({100.0 * out['unnamed_s'] / apply_s:.3f}%)")
