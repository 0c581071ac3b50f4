# Repo-level developer targets. The analyzer and tests force
# JAX_PLATFORMS=cpu so they run on any host (no TPU required); amlint
# itself is stdlib-only and never initialises jax.

PY ?= python

.PHONY: lint test native obs-report faults bench-smoke gate-bench chaos serve decode mesh mesh-workers mesh-shm store sync2

lint:
	JAX_PLATFORMS=cpu $(PY) -m automerge_tpu.analysis automerge_tpu

# incremental lint: files changed vs REF (default HEAD) plus their
# transitive importers; falls back to the full scan when a rule-scoped
# module (workers/meshfarm/serve) imports a changed one
REF ?= HEAD
lint-changed:
	JAX_PLATFORMS=cpu $(PY) -m automerge_tpu.analysis --changed $(REF) automerge_tpu

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# the fault-corpus suite: per-doc isolation, quarantine lifecycle, device
# bisect/fallback, sync survival (tests/test_faults.py). A degradation
# curve with N% poison docs: `python bench.py --faults N`.
faults:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py -q

# the chaos soak suite (incl. slow sweeps): supervised sync convergence
# under seeded loss/dup/reorder/corruption, peer restarts, partitions
# (tests/test_chaos_sync.py + the session unit suite). Goodput vs loss:
# `python bench.py --chaos P`.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos_sync.py tests/test_sync_session.py -q

# host perf gate: fails when the visibility+patch_assembly share of
# end-to-end time regresses above BENCH_SMOKE_MAX_TAIL_SHARE (README
# "Performance"); also runs as a tier-1 test (tests/test_bench_smoke.py)
bench-smoke:
	JAX_PLATFORMS=cpu $(PY) bench.py --quick

# gate-phase microbench: the same delivery stream through the columnar
# causal gate and the scalar oracle chain (gate_mode="oracle"); gates on
# canonical patch parity and the columnar gate phases beating the scalar
# chain (README "Performance")
gate-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --gate

# columnar decode microbench (cold/warm MB/s, scalar vs vectorized vs
# native) + mixed-size page-packing report; gates on the vectorized path
# beating the scalar oracle and >= 80% slab occupancy (README
# "Performance")
decode:
	JAX_PLATFORMS=cpu $(PY) bench.py --decode

# serving front-door demo (README "Serving"): 192 simulated clients over
# the chaos transport in simulated time through the session multiplexer +
# dynamic batcher; gates on convergence, batch occupancy, zero
# unexplained sheds, a populated amscope phase breakdown with a p99
# exemplar trace, and bounded observability overhead vs the metrics-only
# baseline. The full-scale harness (10^4+ clients):
# `python bench.py --serve`; also a tier-1 test (tests/test_serve_smoke.py)
serve:
	JAX_PLATFORMS=cpu $(PY) bench.py --serve --quick

# multi-chip mesh smoke (README "Multi-chip"): the doc-sharded MeshFarm
# on 8 forced virtual CPU host devices — fan-out, mid-run page-granular
# migration, actor-table reconcile convergence, ownership audit; gates
# are machine-independent. The full MULTICHIP record run (8192 docs;
# needs the chips, fails without them): `python bench.py --mesh`; also a
# tier-1 test (tests/test_mesh_smoke.py)
mesh:
	$(PY) bench.py --mesh --quick

# process-worker mesh smoke (README "Process workers"): the same quick
# gates with every shard in its own spawned worker process, pinned to
# the pickle-pipe ORACLE transport — pickled column fan-out, migration
# over the pipe, clean worker shutdown. The full MULTICHIP_r08 record
# run: `python bench.py --mesh --backend process --transport pickle`;
# byte parity + crash recovery are tier-1
# (tests/test_mesh_workers_smoke.py, tests/test_mesh_workers.py)
mesh-workers:
	$(PY) bench.py --mesh --quick --backend process --transport pickle

# shared-memory mesh smoke (README "Process workers"): the same quick
# gates over the zero-copy column rings — bulk bytes ride the shm
# segments and the pipe collapses to control frames, gated at
# BENCH_MESH_SHM_PIPE_BYTES_PER_ROUND (default 4096 bytes/round/shard).
# The full MULTICHIP_r09 record run (shm + pickle-oracle delta):
# `python bench.py --mesh --backend process --transport shm`
mesh-shm:
	$(PY) bench.py --mesh --quick --backend process --transport shm

# persistence-tier smoke (README "Persistence"): WAL-attached merge
# round-trip, then both cold-start paths rebuilt from the on-disk log —
# gates byte parity with the writer, a clean recovery report, and full
# change accounting. The full STORE_r01 record run (batched hydration
# >= 5x the per-doc load loop): `python bench.py --store`; the same
# quick gates are tier-1 as tests/test_store_smoke.py
store:
	$(PY) bench.py --store --quick

# sync v2 smoke (README "Resilient sync"): Bloom (v1) vs range
# reconciliation (v2) — deterministic round-trip bound, the poisoned
# sentHashes stall that only v1's watchdog can break, byte-for-byte
# v1<->v2 interop, and the one-dispatch-per-sweep farm fingerprint pin.
# The full SYNC_r01 record run (1e5-change divergence):
# `python bench.py --sync2`
sync2:
	JAX_PLATFORMS=cpu $(PY) bench.py --sync2 --quick

native:
	$(MAKE) -C native

# span tree + metrics table for a small canned farm merge + sync
# round-trip (automerge_tpu/obs; see README "Observability"). The CLI
# contract — including the --flight timeline and --watch telemetry
# renderers — is pinned in tier-1 by tests/test_obs_cli.py, so this
# target cannot rot silently.
obs-report:
	JAX_PLATFORMS=cpu $(PY) -m automerge_tpu.obs --docs 4 --rounds 2 --ops 8
