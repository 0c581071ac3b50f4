"""Tier-1 perf smoke gate for the incremental-readback / vectorized-
assembly work (ISSUE 4): the ``visibility + patch_assembly`` share of
end-to-end apply_changes time must stay under the pinned threshold.

BENCH_r05 measured that tail at >65% of wall time (9.79s + 8.31s of
26.7s) because every call re-read and re-walked the whole farm state on
the host. The host row mirror + scoped readback + column-mask assembly
keep it a minority share; this test (and `make bench-smoke`, which runs
the same check at a larger config via ``bench.py --quick``) fails any
change that reintroduces O(whole farm) host work per call.
"""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench

# generous vs the post-fix steady state (~0.4 at the delta config) but
# below the regression signature (tail_share -> 1 as host work returns to
# O(whole farm) per call)
MAX_TAIL_SHARE = 0.55

# gate_verdicts + transcode_columns + gate+transcode + patch_assembly:
# the phases the columnar causal gate + device-emitted patch columns
# retired from per-change host Python (BENCH_r07 measures ~0.03 at the
# delta config; a revert to the scalar chain pushes this past 0.5)
MAX_GATE_SHARE = 0.45

_RESULT = None


def _smoke():
    global _RESULT
    if _RESULT is None:
        _RESULT = bench.bench_smoke(
            num_docs=48, seed_rounds=4, seed_ops=32, delta_rounds=4,
            delta_ops=4,
        )
    return _RESULT


def test_visibility_assembly_share_stays_bounded():
    result = _smoke()
    assert result["ops_per_sec"] > 0
    assert result["tail_share"] <= MAX_TAIL_SHARE, (
        f"visibility+patch_assembly is {result['tail_share']:.0%} of the "
        f"delta-round time (limit {MAX_TAIL_SHARE:.0%}): the incremental "
        f"readback / vectorized assembly path has regressed; phases: "
        f"{result['phases']}"
    )


def test_gate_assembly_share_stays_bounded():
    """The columnar-gate regression signature: per-change Python creeping
    back into gate/transcode or patch assembly drags their combined share
    of the delta-round time back toward the scalar chain's profile."""
    result = _smoke()
    assert result["gate_share"] <= MAX_GATE_SHARE, (
        f"gate+transcode+patch_assembly is {result['gate_share']:.0%} of "
        f"the delta-round time (limit {MAX_GATE_SHARE:.0%}): the columnar "
        f"gate / device patch-column path has regressed; phases: "
        f"{result['phases']}"
    )


def test_gate_is_columnar_with_device_patch_columns():
    """Machine-independent row-count properties: deliveries ride the
    columnar verdict path (no oracle re-routes on a clean workload) and
    patch emission happens on device."""
    result = _smoke()
    assert result["vector_changes"] > 0, result
    assert result["gate_oracle_docs"] == 0, result
    assert result["transcode_oracle_docs"] == 0, result
    assert result["device_patch_columns"] > 0, result


def test_readback_is_incremental():
    """Steady-state delta rounds must serve most rows from the host
    visibility cache: a revert to full-state readback collapses
    rows_skipped to ~0 and fails here whatever the machine speed."""
    result = _smoke()
    assert result["readback_rows"] > 0
    assert result["readback_rows_skipped"] > result["readback_rows"], result


def test_decode_cache_absorbs_the_fanout():
    """The same change fanned across the batch must be parsed ~once, not
    once per doc: decode-cache hits dominate misses."""
    result = _smoke()
    assert result["decode_cache_hits"] > result["decode_cache_misses"], result


def test_main_fails_without_an_accelerator():
    """No CPU fallback: with only the CPU visible, the device benchmark
    reports an error and exits non-zero instead of printing a rate."""
    proc = subprocess.run(
        [sys.executable, bench.__file__],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "no accelerator" in out["error"] and "value" not in out
