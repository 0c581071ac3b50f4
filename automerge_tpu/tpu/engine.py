"""Batched merge kernels: the TPU equivalent of the OpSet engine's hot loop.

The reference merge (mergeDocChangeOps, /root/reference/backend/new.js:1052)
is a sequential two-pointer walk per document. Here the same result is
computed as a data-parallel array program over a whole batch of documents:

  1. concatenate existing doc ops with incoming change ops
  2. lexsort rows into the canonical op order: (key, opId counter, opId actor)
     -- the same total order the columnar engine maintains
  3. resolve succ/overwrite relationships: an op is overwritten when another
     (non-increment) op names it in `pred` (matched with a sorted binary
     search, no scatter loops)
  4. visibility = zero successors; the winning value per key is the visible
     op with the greatest Lamport opId (segmented max over the sorted keys);
     counter increments accumulate onto their target set op instead of
     hiding it (new.js:937-965)

Everything is static-shape and jit/vmap/shard_map friendly: padded rows carry
key = PAD_KEY and sort to the end. Map objects and counters are supported in
this v1 engine (benchmark configs 1 and 3); list/text RGA ordering stays on
the sequential engine for now (see SURVEY.md §7 step 5).

Lamport opIds are packed into a single int64 as (counter << 20 | actor_num),
which preserves (counter, actor) ordering for up to 2^20 actors and 2^43 ops.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.flight import get_flight
from ..obs.metrics import get_metrics
from ..obs.prof import get_observatory
from ..obs.spans import get_trace
from ..testing.faults import fire as _fault_point
from .jitprof import profiled_jit

PAD_KEY = jnp.iinfo(jnp.int32).max
ACTOR_BITS = 20
ACTOR_MASK = (1 << ACTOR_BITS) - 1
_NEG_INF = jnp.int64(-(2**62))

ACTION_SET = 0
ACTION_INC = 1
ACTION_DEL = 2

# engine metrics (process-wide registry, disabled unless a workload opts
# in — obs/metrics.py). Dispatch accounting lives in the HOST wrappers
# below, never inside traced code (amlint AM303).
_METRICS = get_metrics()
_M_DISPATCHES = _METRICS.counter(
    "engine.device.dispatches",
    "batched device programs dispatched (merge + visibility)",
)
_M_JIT_HITS = _METRICS.counter(
    "engine.jit.cache_hits",
    "dispatches served by an already-compiled program",
)
_M_JIT_RECOMPILES = _METRICS.counter(
    "engine.jit.recompiles",
    "dispatches that triggered an XLA compile (shape-bucket misses)",
)
_M_STATE_GROWS = _METRICS.counter(
    "engine.state.grows",
    "capacity doublings of the dense device state",
)

# flight-recorder hook (obs/flight.py): recompiles and slab growth are the
# two engine events worth a postmortem timeline entry — a steady-state
# recompile storm or a surprise slab doubling explains a latency cliff.
_FLIGHT = get_flight()

# amprof observatory (obs/prof.py): every jit program below registers a
# named ProfiledProgram via tpu/jitprof.py, so recompiles carry program
# identity and dispatches get per-program latency attribution.
_OBSERVATORY = get_observatory()


def _dispatch(prog, *args, **kwargs):
    """Runs a named profiled program (tpu/jitprof.py), classifying the
    call as a jit cache hit or a recompile by the growth of the program's
    compile cache across the call. This is the single device-dispatch
    funnel for the engine, so the recompile-storm and dispatch-count
    metrics cover every merge and visibility program. Per-program
    attribution (compile/dispatch tallies, shape buckets, the
    ``engine.recompile`` flight event with program identity) lives in
    ``ProfiledProgram.call_profiled``; with both metrics and the
    observatory disabled this degrades to a plain call."""
    if not _METRICS.enabled and not _OBSERVATORY.enabled:
        return prog.fn(*args, **kwargs)
    out, grew, _dt = prog.call_profiled(args, kwargs)
    if _METRICS.enabled:
        _M_DISPATCHES.inc()
        if grew > 0:
            _M_JIT_RECOMPILES.inc(grew)
        elif grew == 0:
            _M_JIT_HITS.inc()
    return out


def pack_opid(counter, actor):
    """Packs (counter, actorNum) into one int64 preserving Lamport order."""
    counter = jnp.asarray(counter)
    actor = jnp.asarray(actor)
    return (counter.astype(jnp.int64) << ACTOR_BITS) | actor.astype(jnp.int64)


def unpack_opid(opid):
    return opid >> ACTOR_BITS, opid & ACTOR_MASK


def remap_opid_actors(opid, actor_rank):
    """Rebuilds packed opIds with the actor index replaced by its
    lexicographic rank, so int64 comparison == (counter, actorId-string)
    comparison (the reference's tie-break, new.js:146, apply_patch.js:33)."""
    actor_rank = jnp.asarray(actor_rank)
    counter = opid >> ACTOR_BITS
    actor = (opid & ACTOR_MASK).astype(jnp.int32)
    rank = actor_rank[jnp.minimum(actor, actor_rank.shape[0] - 1)]
    return (counter << ACTOR_BITS) | rank.astype(jnp.int64)


class BatchedDocState(NamedTuple):
    """Dense op storage for a batch of map documents.

    All row arrays have shape [docs, capacity], sorted by (key, opId);
    padded slots have key == PAD_KEY and sort last. `overwritten` marks ops
    with at least one non-increment successor (the dense analogue of
    succNum > 0); `pred` is the packed opId each op overwrites/increments
    (-1 if none), from which full succ lists are recovered host-side when
    transcoding back to the columnar format.
    """

    key: jax.Array          # int32 interned key id
    op: jax.Array           # int64 packed opId
    action: jax.Array       # int32 (ACTION_SET / ACTION_INC / ACTION_DEL)
    value: jax.Array        # int64 value payload (interned ref or small int)
    pred: jax.Array         # int64 packed opId, -1 if none
    overwritten: jax.Array  # bool
    num_ops: jax.Array      # int32 [docs] live op count


class ChangeOpsBatch(NamedTuple):
    """One batch of incoming change ops per document, shape [docs, m]."""

    key: jax.Array
    op: jax.Array
    action: jax.Array
    value: jax.Array
    pred: jax.Array


def make_empty_state(num_docs: int, capacity: int) -> BatchedDocState:
    return BatchedDocState(
        key=jnp.full((num_docs, capacity), PAD_KEY, jnp.int32),
        op=jnp.zeros((num_docs, capacity), jnp.int64),
        action=jnp.zeros((num_docs, capacity), jnp.int32),
        value=jnp.zeros((num_docs, capacity), jnp.int64),
        pred=jnp.full((num_docs, capacity), -1, jnp.int64),
        overwritten=jnp.zeros((num_docs, capacity), jnp.bool_),
        num_ops=jnp.zeros((num_docs,), jnp.int32),
    )


# Merge keys pack (key, opId) into one int64: key in the top 20 bits, the
# packed opId (counter << 20 | actor) in the low 44. Requires counter < 2^24.
_MKEY_OP_BITS = 44
_I64_MAX = jnp.iinfo(jnp.int64).max


def _merge_key(key, op):
    return jnp.where(
        key == PAD_KEY,
        _I64_MAX,
        (key.astype(jnp.int64) << _MKEY_OP_BITS) | op,
    )


def _merge_one_doc(s_key, s_op, s_action, s_value, s_pred, s_over, num_ops,
                   c_key, c_op, c_action, c_value, c_pred):
    """Merges one document's change ops into its sorted op table (vmapped
    over the batch).

    The doc state is invariant-sorted by (key, opId), so instead of
    re-sorting the whole table (the naive O(N log N) per merge), only the
    small change batch is sorted and merged in by insertion position:
    searchsorted gives each change op's slot, and every row moves to its
    final position with one scatter -- O(N) memory traffic + O(M log N)
    compute, the TPU analogue of the reference's two-pointer merge
    (mergeDocChangeOps, new.js:1052).
    """
    n = s_key.shape[0]
    m = c_key.shape[0]
    s_mkey = _merge_key(s_key, s_op)

    # sort the change ops into canonical order
    c_mkey = _merge_key(c_key, c_op)
    c_order = jnp.argsort(c_mkey)
    c_mkey = c_mkey[c_order]
    c_key = c_key[c_order]
    c_op = c_op[c_order]
    c_action = c_action[c_order]
    c_value = c_value[c_order]
    c_pred = c_pred[c_order]

    # insertion positions: new row j lands at pos[j] + j. The output is then
    # built by pure gathers (TPU scatters serialize; gathers vectorise):
    # output slot t holds new row k-1 if new_pos[k-1] == t, else old row
    # t - k, where k = |{j : new_pos[j] <= t}|.
    pos = jnp.searchsorted(s_mkey, c_mkey)
    new_pos = pos + jnp.arange(m)
    t = jnp.arange(n)
    k = jnp.searchsorted(new_pos, t, side="right")
    is_new = (k > 0) & (new_pos[jnp.maximum(k - 1, 0)] == t)
    new_idx = jnp.maximum(k - 1, 0)
    old_idx = jnp.minimum(t - k, n - 1)

    def place(s_arr, c_arr):
        return jnp.where(is_new, c_arr[new_idx], s_arr[old_idx])

    out_key = place(s_key, c_key)
    out_op = place(s_op, c_op)
    out_action = place(s_action, c_action)
    out_value = place(s_value, c_value)
    out_pred = place(s_pred, c_pred)
    out_over = place(s_over, jnp.zeros((m,), jnp.bool_))

    # succ resolution: a non-increment change op overwrites its pred
    # (increments are successors that keep the counter visible,
    # new.js:937-965). pred ops share the change op's key, so the target row
    # is identified exactly by its merge key; membership is a sorted lookup.
    hides = (c_action != ACTION_INC) & (c_pred >= 0)
    hide_mkey = jnp.sort(jnp.where(
        hides,
        (c_key.astype(jnp.int64) << _MKEY_OP_BITS) | jnp.where(c_pred >= 0, c_pred, 0),
        _I64_MAX,
    ))
    out_mkey = _merge_key(out_key, out_op)
    p = jnp.minimum(jnp.searchsorted(hide_mkey, out_mkey), m - 1)
    out_over = out_over | ((hide_mkey[p] == out_mkey) & (out_mkey != _I64_MAX))

    new_num = num_ops + jnp.sum(c_key != PAD_KEY).astype(jnp.int32)
    return out_key, out_op, out_action, out_value, out_pred, out_over, new_num


@profiled_jit("engine.apply_ops", donate_argnums=(0,))
def batched_apply_ops(state: BatchedDocState, changes: ChangeOpsBatch) -> BatchedDocState:
    """applyChanges over a whole document batch: one fused XLA program,
    vmapped over the doc axis."""
    key, op, action, value, pred, over, num = jax.vmap(_merge_one_doc)(
        state.key, state.op, state.action, state.value, state.pred,
        state.overwritten, state.num_ops,
        changes.key, changes.op, changes.action, changes.value, changes.pred,
    )
    return BatchedDocState(key, op, action, value, pred, over, num)


def _visible_state_one_doc(key, op, action, value, pred, over, cmp):
    """Computes per-row visibility for one document.

    Returns (key, op, visible, winner, value_total):
    - `visible[i]`: row i is a visible set op (no non-increment successor) —
      the rows that populate a conflict map (new.js:112-130);
    - `winner[i]`: row i is the winning visible set op of its key (the
      visible set op with the greatest Lamport opId, apply_patch.js:33-42);
    - `value_total[i]` at a visible row: the row's value plus the sum of
      live increments targeting *that row* (per-target succ accumulation,
      new.js:937-965), so conflicting counters each carry their own total.

    `cmp` is the comparison opId per row: the packed opId itself, or its
    actor bits remapped to lexicographic actor ranks (rga.remap_opid_actors)
    so counter ties break on the actor *string* like the reference
    (new.js:146, apply_patch.js:33).

    Per-key reductions exploit the sorted key column: a run ends where the
    key differs from its right neighbour; each row's run-end index is one
    suffix min over the end positions, and the segmented max rides a single
    global cummax by packing the (ascending) key into the high bits — no
    scatters in the winner path (TPU scatters serialise) and no deep scan
    graphs.
    """
    n = key.shape[0]
    is_real = key != PAD_KEY
    is_set = is_real & (action == ACTION_SET)
    is_inc = is_real & (action == ACTION_INC)
    visible_set = is_set & ~over

    iota = jnp.arange(n, dtype=jnp.int32)
    is_end = jnp.concatenate([key[:-1] != key[1:], jnp.ones((1,), jnp.bool_)])
    run_end = jax.lax.cummin(
        jnp.where(is_end, iota, jnp.iinfo(jnp.int32).max), reverse=True
    )

    # winner: the visible set row with the greatest cmp in its key run.
    packed = jnp.where(
        visible_set, (key.astype(jnp.int64) << _MKEY_OP_BITS) | cmp, jnp.int64(-1)
    )
    run_max = jax.lax.cummax(packed)[run_end]
    winner = visible_set & (packed == run_max)

    # live increments: an inc is live iff its target set op is not
    # overwritten. The target shares the inc's key, so locate it by merge
    # key within the sorted rows.
    mkey = _merge_key(key, op)
    target_mkey = jnp.where(
        is_inc & (pred >= 0),
        (key.astype(jnp.int64) << _MKEY_OP_BITS) | jnp.where(pred >= 0, pred, 0),
        _I64_MAX,
    )
    tpos = jnp.minimum(jnp.searchsorted(mkey, target_mkey), n - 1)
    target_live = (mkey[tpos] == target_mkey) & ~over[tpos]
    inc_live = is_inc & target_live

    # per-target accumulation: each live inc adds its value onto the row it
    # names in pred (a segment-sum scatter-add over target positions).
    inc_vals = jnp.where(inc_live, value, 0)
    row_inc = jax.ops.segment_sum(inc_vals, tpos, num_segments=n)
    value_total = jnp.where(visible_set, value + row_inc, 0)
    return key, op, visible_set, winner, value_total


@profiled_jit("engine.visible_cmp")
def _batched_visible_state_cmp(state: BatchedDocState, cmp):
    return jax.vmap(_visible_state_one_doc)(
        state.key, state.op, state.action, state.value, state.pred,
        state.overwritten, cmp,
    )


def batched_visible_state(state: BatchedDocState, actor_rank=None):
    """Materialises the visible state of every document: the device-side
    equivalent of documentPatch (new.js:1604). Returns per-row
    (key, op, visible, winner, value_total) arrays of shape
    [docs, capacity].

    `actor_rank` (int32[A], actor intern index -> lexicographic rank) makes
    counter-tied conflicts resolve on the actor id string exactly like the
    reference; without it, ties break on actor intern order (sufficient for
    single-engine convergence, not for cross-engine parity).
    """
    if actor_rank is None:
        cmp = state.op
    else:
        cmp = remap_opid_actors(state.op, actor_rank)
    return _dispatch(_batched_visible_state_cmp, state, cmp)


@profiled_jit("engine.gather_rows")
def _gather_rows(visible, totals, idx):
    """Row gather for the incremental readback path: `idx` is a flat array
    of ``doc * capacity + row`` indices (padded to a power-of-two length so
    jit shapes are bucketed; the host trims the padding)."""
    return visible.reshape(-1)[idx], totals.reshape(-1)[idx]


# page-storage metrics: the slab's figure of merit (farm.pages.occupancy
# replaces pad-waste as the HBM measure — see paging.py)
_M_PAGES_ALLOC = _METRICS.gauge(
    "farm.pages.allocated", "slab pages currently owned by documents"
)
_M_PAGES_FREE = _METRICS.gauge(
    "farm.pages.free", "slab pages on the allocator free list"
)
_M_PAGES_OCC = _METRICS.gauge(
    "farm.pages.occupancy", "live op rows / allocated page cells"
)

# imported mid-module: paging.py needs the kernel functions above, the
# driver below needs paging's slab programs — the split keeps kernels and
# storage layout in separate files without a third module
from .paging import (  # noqa: E402
    PageAllocator,
    grow_slab,
    make_empty_slab,
    paged_adopt_rows,
    paged_apply_ops,
    paged_dense_view,
    paged_probe_ops,
    paged_visible_plain,
    paged_visible_ranked,
    patch_column_rows,
)


class BatchedMapEngine:
    """Host-side driver for the batched map/counter engine over ragged
    paged op storage (paging.py).

    Documents' op rows live in fixed-size pages of one shared device slab
    (per-doc page table + length on the host). A merge gathers only the
    ACTIVE documents' rows into a pow2-bucketed dense working view, runs
    the unchanged merge kernel, and scatters the result back through the
    new page map — one XLA program, shapes bucketed by (active docs,
    largest active doc), so a farm of wildly different doc sizes neither
    pays largest-doc HBM per doc nor recompiles the whole farm when one
    document grows. ``version`` counts committed merges; visibility
    pytrees are memoised per (version, doc subset, actor rank) so repeated
    reads between merges cost one dispatch each.
    """

    def __init__(self, num_docs: int, capacity: int = 1024,
                 page_size: int | None = None):
        import os

        self.num_docs = num_docs
        self.capacity = capacity  # legacy sizing hint; storage is paged
        # the dense WORKING width (gather/merge/visibility views) never
        # shrinks below the caller's sizing hint and ratchets up with the
        # largest doc: stable pow2 shapes keep the program cache warm (the
        # hint does NOT reserve HBM — the slab allocates by page)
        self._width_floor = self._pow2(min(capacity, 1 << 13))
        page_size = page_size or int(os.environ.get("AM_PAGE_SIZE", "64"))
        # the slab starts at the caller's sizing hint (num_docs x capacity
        # rows) and grows in pow2 jumps: every distinct slab size is a
        # compiled-program shape, so a hint-sized farm never recompiles in
        # the steady state, while farms of mostly-small docs simply leave
        # pages on the free list (allocation is per page, the hint only
        # sizes the arena)
        hint_pages = (num_docs * min(capacity, 1 << 13)) // page_size
        self.pages = PageAllocator(
            page_size, initial_pages=max(4, min(hint_pages, 1 << 17))
        )
        self.slab = make_empty_slab(self.pages.num_pages * page_size)
        self.page_table: list[list] = [[] for _ in range(num_docs)]
        self.lengths = np.zeros(num_docs, np.int64)
        self.version = 0
        self._vis_memo: dict = {}

    @staticmethod
    def _pow2(n) -> int:
        return 1 << max(0, int(n) - 1).bit_length()

    def _width(self, needed: int) -> int:
        """Dense working width for `needed` rows: pow2-bucketed (never
        below one page) with the never-shrinking floor, so steady-state
        dispatches reuse one compiled shape instead of recompiling at
        every doubling."""
        width = max(self._pow2(needed), self._width_floor,
                    self.pages.page_size)
        self._width_floor = width
        return width

    def _page_map(self, tables, width, a_pad, fill):
        """[a_pad, width / P] PAGE indices: slot j of doc k names the slab
        page holding its rows [j*P, (j+1)*P), else `fill` (0 = the PAD
        page for gathers, num_pages = dropped for scatters). Device moves
        are whole contiguous pages; the page-tail invariant (paging.py)
        makes per-row masking unnecessary."""
        npg = width // self.pages.page_size
        mat = np.full((a_pad, npg), fill, np.int32)
        for k, pt in enumerate(tables):
            n = min(len(pt), npg)
            if n:
                mat[k, :n] = pt[:n]
        return mat

    def apply_batch(self, changes: ChangeOpsBatch, docs=None, counts=None):
        """Merges `changes` into the slab. `docs` names the documents the
        batch rows belong to (None = all docs, the legacy full-farm shape);
        rows past ``len(docs)`` are pow2 padding. `counts` gives each doc's
        real (non-pad) row count — passed by the farm, derived from the
        batch otherwise."""
        _fault_point("engine.apply_batch", changes=changes)
        docs = (
            list(range(self.num_docs)) if docs is None
            else [int(d) for d in docs]
        )
        if not docs:
            return
        a_pad, m = changes.key.shape
        assert a_pad >= len(docs)
        if counts is None:
            counts = np.asarray(changes.key != PAD_KEY).sum(axis=1)[: len(docs)]
        counts = np.asarray(counts, np.int64)
        old_lens = self.lengths[docs]
        new_lens = old_lens + counts
        width = self._width(int(old_lens.max()) + m)
        P = self.pages.page_size

        old_tables = [self.page_table[d] for d in docs]
        gidx = self._page_map(old_tables, width, a_pad, fill=0)

        extra = [
            self.pages.pages_for(int(n)) - len(t)
            for n, t in zip(new_lens, old_tables)
        ]
        if self.pages.ensure(sum(e for e in extra if e > 0)):
            self.slab = grow_slab(self.slab, self.pages.num_pages * P)
            _M_STATE_GROWS.inc()
            if _FLIGHT.enabled:
                _FLIGHT.record("engine.slab.grow",
                               pages=self.pages.num_pages,
                               rows=self.pages.num_pages * P)
        fresh: list = []
        new_tables = []
        for t, e in zip(old_tables, extra):
            if e > 0:
                pages = self.pages.alloc(e)
                fresh.extend(pages)
                new_tables.append(list(t) + pages)
            else:
                new_tables.append(list(t))
        dest = self._page_map(new_tables, width, a_pad,
                              fill=self.pages.num_pages)
        try:
            self.slab = _dispatch(
                paged_apply_ops, self.slab, jnp.asarray(gidx), changes,
                jnp.asarray(dest), page_size=P,
            )
        except Exception:
            # nothing committed: hand the delta pages back so a failed
            # dispatch (degraded mode) leaks no slab capacity
            self.pages.free(fresh)
            raise
        for d, t, n in zip(docs, new_tables, new_lens):
            self.page_table[d] = t
            self.lengths[d] = int(n)
        self.version += 1
        self._vis_memo.clear()
        self._update_page_metrics()

    def probe_apply(self, changes: ChangeOpsBatch, docs, counts=None):
        """Runs the merge for `docs` on a throwaway basis (no scatter, no
        donation, no state advance): the bisection probe for device-fault
        isolation."""
        docs = [int(d) for d in docs]
        a_pad, m = changes.key.shape
        lens = self.lengths[docs] if docs else np.zeros(0, np.int64)
        width = self._width((int(lens.max()) if docs else 0) + m)
        tables = [self.page_table[d] for d in docs]
        gidx = self._page_map(tables, width, a_pad, fill=0)
        out = paged_probe_ops(
            self.slab, jnp.asarray(gidx), changes,
            page_size=self.pages.page_size,
        )
        jax.block_until_ready(out)

    def visible_state(self, actor_rank=None, docs=None):
        """Device-resident visibility pytree for `docs` (None = every
        document): per-row (key, op, visible, winner, value_total) arrays
        of shape [len(docs), W], W = pow2 bucket of the largest requested
        doc. Memoised per (state version, doc subset, actor-rank table)."""
        _fault_point("engine.visible_state")
        docs_t = (
            tuple(range(self.num_docs)) if docs is None
            else tuple(int(d) for d in docs)
        )
        rank_key = (
            None if actor_rank is None else np.asarray(actor_rank).tobytes()
        )
        key = (docs_t, rank_key)
        hit = self._vis_memo.get(key)
        if hit is not None:
            return hit
        lens = (
            self.lengths[list(docs_t)] if docs_t else np.zeros(0, np.int64)
        )
        width = self._width(int(lens.max()) if len(lens) else 1)
        a_pad = self._pow2(len(docs_t))
        tables = [self.page_table[d] for d in docs_t]
        gidx = self._page_map(tables, width, a_pad, fill=0)
        if actor_rank is None:
            out = _dispatch(
                paged_visible_plain, self.slab, jnp.asarray(gidx),
                page_size=self.pages.page_size,
            )
        else:
            out = _dispatch(
                paged_visible_ranked, self.slab, jnp.asarray(gidx),
                jnp.asarray(actor_rank), page_size=self.pages.page_size,
            )
        out = jax.tree_util.tree_map(lambda a: a[: len(docs_t)], out)
        if len(self._vis_memo) > 16:
            self._vis_memo.clear()
        self._vis_memo[key] = out
        return out

    def read_visibility_rows(self, plan, actor_rank=None):
        """Scoped device→host visibility readback: `plan` is a list of
        ``(doc, row_idx array)`` pairs; returns (visible, value_total)
        numpy arrays concatenated in plan order. Visibility is computed
        for ONLY the planned docs' rows, then one padded device gather and
        ONE jax.device_get move exactly the requested rows — O(rows
        requested), not O(whole farm state). The host's wait for that
        result (the device's queue and run included) is the ambient
        trace's ``device_wait`` interval."""
        plan = [
            (int(d), np.asarray(idx, np.int64))
            for d, idx in plan if len(idx)
        ]
        if not plan:
            return np.zeros(0, bool), np.zeros(0, np.int64)
        docs_t = tuple(sorted({d for d, _ in plan}))
        _k, _o, visible, _w, totals = self.visible_state(
            actor_rank, docs=docs_t
        )
        w = visible.shape[1]
        pos = {d: i for i, d in enumerate(docs_t)}
        flat = np.concatenate([pos[d] * w + idx for d, idx in plan])
        n = int(flat.shape[0])
        padded = 1 << max(0, n - 1).bit_length()
        idx = np.zeros(padded, np.int64)
        idx[:n] = flat
        v, t = _dispatch(_gather_rows, visible, totals, jnp.asarray(idx))
        with get_trace().interval("device_wait"):
            v, t = jax.device_get((v, t))
        return v[:n], t[:n]

    def read_patch_columns(self, plan, actor_rank):
        """Scoped readback + device patch-column emission: `plan` is a
        list of ``(doc, row_idx array, cut array)`` triples, where `cut`
        holds each requested row's walk cutoff as a rank-packed int64
        (``-1`` = the row's slot is outside the delivery's cutoff set,
        int64 max = walk to the end of the key run). Returns
        (visible, value_total, emit) numpy arrays concatenated in plan
        order. Visibility comes from the memoised stable-shape program
        (visible_state), then paging.patch_column_rows gathers exactly
        the requested rows and decides patch emission on device — the
        shape-varying half compiles in milliseconds, so growing readback
        sizes never re-pay the visibility kernel's compile."""
        plan = [
            (int(d), np.asarray(idx, np.int64), np.asarray(cut, np.int64))
            for d, idx, cut in plan if len(idx)
        ]
        if not plan:
            return (
                np.zeros(0, bool), np.zeros(0, np.int64), np.zeros(0, bool)
            )
        docs_t = tuple(sorted({d for d, _, _ in plan}))
        _k, op, visible, _w, totals = self.visible_state(
            actor_rank, docs=docs_t
        )
        w = visible.shape[1]
        pos = {d: i for i, d in enumerate(docs_t)}
        flat = np.concatenate([pos[d] * w + idx for d, idx, _ in plan])
        cuts = np.concatenate([cut for _, _, cut in plan])
        n = int(flat.shape[0])
        padded = 1 << max(0, n - 1).bit_length()
        idx = np.zeros(padded, np.int64)
        idx[:n] = flat
        cut = np.full(padded, -1, np.int64)  # pad rows never emit
        cut[:n] = cuts
        v, t, e = _dispatch(
            patch_column_rows, visible, totals, op,
            jnp.asarray(actor_rank), jnp.asarray(idx), jnp.asarray(cut),
        )
        with get_trace().interval("device_wait"):
            v, t, e = jax.device_get((v, t, e))
        return v[:n], t[:n], e[:n]

    def dense_view(self, docs=None):
        """Host copies of the six op columns as dense [D, W] arrays (the
        whole-state debug/parity readback — production paths stay paged)."""
        docs_t = (
            tuple(range(self.num_docs)) if docs is None
            else tuple(int(d) for d in docs)
        )
        lens = self.lengths[list(docs_t)] if docs_t else np.zeros(0, np.int64)
        width = self._width(int(lens.max()) if len(lens) else 1)
        gidx = self._page_map(
            [self.page_table[d] for d in docs_t], width,
            self._pow2(len(docs_t)), fill=0,
        )
        out = paged_dense_view(
            self.slab, jnp.asarray(gidx), page_size=self.pages.page_size
        )
        return jax.device_get(
            jax.tree_util.tree_map(lambda a: a[: len(docs_t)], out)
        )

    def restore_doc(self, d: int, pages, length: int) -> None:
        """Rolls doc `d`'s page allocation back to a snapshot, returning
        pages acquired since to the free list. No device rows are
        rewritten: rollback always precedes the commit that would have
        used them (or that commit's dispatch failed and already freed its
        delta pages)."""
        keep = set(pages)
        self.pages.free([p for p in self.page_table[d] if p not in keep])
        self.page_table[d] = list(pages)
        self.lengths[d] = int(length)
        self._update_page_metrics()

    def adopt_rows(self, d: int, key, op, action, value, pred, over) -> None:
        """Installs a migrated document's op rows as doc `d`'s pages (the
        destination half of cross-farm page-granular migration). Doc `d`
        must be empty; rows arrive as host arrays already translated into
        THIS engine's id space and sorted by merge key. Pages are
        allocated fresh and written by one whole-page scatter program —
        host padding keeps the page-tail invariant."""
        assert not self.page_table[d], "adopt_rows into an occupied doc"
        n = int(np.asarray(key).shape[0])
        self.lengths[d] = n
        self.version += 1
        self._vis_memo.clear()
        if n == 0:
            self._update_page_metrics()
            return
        P = self.pages.page_size
        npg = self.pages.pages_for(n)
        if self.pages.ensure(npg):
            self.slab = grow_slab(self.slab, self.pages.num_pages * P)
            _M_STATE_GROWS.inc()
        pages = self.pages.alloc(npg)
        npg_pad = self._pow2(npg)
        dest = np.full(npg_pad, self.pages.num_pages, np.int32)
        dest[:npg] = pages
        w = npg_pad * P

        def pad(col, fill, dtype):
            out = np.full(w, fill, dtype)
            out[:n] = col
            return out

        self.slab = _dispatch(
            paged_adopt_rows, self.slab, jnp.asarray(dest),
            jnp.asarray(pad(key, PAD_KEY, np.int32)),
            jnp.asarray(pad(op, 0, np.int64)),
            jnp.asarray(pad(action, 0, np.int32)),
            jnp.asarray(pad(value, 0, np.int64)),
            jnp.asarray(pad(pred, -1, np.int64)),
            jnp.asarray(pad(over, False, np.bool_)),
            page_size=P,
        )
        self.page_table[d] = pages
        self._update_page_metrics()

    def evict_doc(self, d: int) -> None:
        """Releases doc `d`'s pages to the free list and zeroes its length
        (the source half of migration). No device rows are wiped: freed
        pages are fully overwritten at their next allocation — every
        scatter (paged_apply_ops / paged_adopt_rows) writes whole pages,
        the same reasoning that lets restore_doc return pages untouched."""
        self.pages.free(self.page_table[d])
        self.page_table[d] = []
        self.lengths[d] = 0
        self.version += 1
        self._vis_memo.clear()
        self._update_page_metrics()

    def _update_page_metrics(self) -> None:
        if not _METRICS.enabled:
            return
        allocated = self.pages.allocated
        _M_PAGES_ALLOC.set(allocated)
        _M_PAGES_FREE.set(self.pages.free_count)
        if allocated:
            _M_PAGES_OCC.set(
                float(self.lengths.sum()) / (allocated * self.pages.page_size)
            )


def _grow_state(state: BatchedDocState, capacity: int) -> BatchedDocState:
    num_docs, old_cap = state.key.shape
    pad = capacity - old_cap

    def grow(arr, fill):
        return jnp.concatenate(
            [arr, jnp.full((num_docs, pad), fill, arr.dtype)], axis=1
        )

    return BatchedDocState(
        key=grow(state.key, PAD_KEY),
        op=grow(state.op, 0),
        action=grow(state.action, 0),
        value=grow(state.value, 0),
        pred=grow(state.pred, -1),
        overwritten=grow(state.overwritten, False),
        num_ops=state.num_ops,
    )


def changes_from_numpy(keys, ops, actions, values, preds) -> ChangeOpsBatch:
    return ChangeOpsBatch(
        key=jnp.asarray(keys, jnp.int32),
        op=jnp.asarray(ops, jnp.int64),
        action=jnp.asarray(actions, jnp.int32),
        value=jnp.asarray(values, jnp.int64),
        pred=jnp.asarray(preds, jnp.int64),
    )
