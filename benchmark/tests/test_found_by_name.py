"""A later PR adds a cell, a traffic mix and a per-layer metric as new
files plus manifest entries; the harness finds them by name."""
import json
import os
import shutil
import time

from benchmark import harness


def test_new_cell_traffic_and_metric_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.REPO, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    manifest = json.loads(
        open(os.path.join(harness.REPO, "BENCHMARK.json")).read())
    bench = root / "benchmark"
    (bench / "traffic" / "throwaway-mix.json").write_text(json.dumps({
        "warmup_s": 0.5, "doc_popularity": {"kind": "zipf", "s": 0.99,
                                            "scramble": True},
        "sync_lag_ms": 100,
        "change": {"kind": "typing_burst", "mean_keystrokes": 2,
                   "max_keystrokes": 6, "delete_share": 0.5,
                   "continue_p": 0.5}}))
    (bench / "workloads" / "text-512.throwaway-mix.json").write_text(
        json.dumps({"name": "text-512.throwaway-mix", "rate_per_s": 10,
                    "batching": {"policy": "docs", "docs": 4,
                                 "max_wait_s": 0.2}}))
    (bench / "metrics" / "throwaway_metric.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    manifest["workloads"].append({
        "name": "text-512.throwaway-mix", "config": "text-512",
        "traffic": "throwaway-mix", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "throwaway_metric", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "decode",
        "moves": "change_p95_ms", "workloads": ["text-512.throwaway-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cfg = harness.load_cell("text-512.throwaway-mix", str(root))
    assert cfg["traffic"]["change"]["max_keystrokes"] == 6
    assert [m["name"] for m in cfg["per_layer"]] == ["throwaway_metric"]
    assert harness.load_reader(str(bench), "throwaway_metric")({}) == 1.5
    result = harness.run_cell(
        "text-512.throwaway-mix", 41, 1.5, True, time.perf_counter(),
        root=str(root), require_chip=False,
        overrides={"config.docs": 8,
                   "config.preload": {"templates": 4, "chars": 32,
                                      "rows": 33}},
        trace_dir=str(tmp_path / "trace"))
    assert result["correct"], result["checks"]
    assert result["metrics"]["throwaway_metric"]["value"] == 1.5
