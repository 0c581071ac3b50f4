"""The knee sweep: one cell stepped through offered rates.

    python3 benchmark/sweep.py --workload <cell> --seconds <s> --rates 100,200,...

Each rate runs as one run of the cell does, in a child process of its own
(this process never touches JAX), at that fixed rate. Prints, per rate,
the offered and completed ops/s, change latency p50/p95, the p50 of the
window's first and second halves and each window delivery's (changes,
start, wall). A rate is sustained when the second half's p50 stays within
1.25x the first half's and under 2 s (no growing backlog) and the window's
work is served at 95% or more of the offered rate; the knee is the
highest sustained rate. The cell file records the knee and its rate, 0.8 x
the knee. Runs on the chip only.
"""
import argparse
import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_rate(workload, seed, seconds, rate, out):
    sys.path.insert(0, ROOT)
    from benchmark import harness

    diag = {}
    try:
        result = harness.run_cell(workload, seed, seconds, False,
                                  time.perf_counter(), root=ROOT,
                                  overrides={"cell.rate_per_s": rate},
                                  diag=diag)
    except harness.NoChip as exc:
        out.put({"error": str(exc)})
        return
    m = {k: v["value"] for k, v in result["metrics"].items()}
    out.put({
        "rate": rate, "correct": result["correct"],
        "offered_ops_per_s": diag["offered_ops_per_s"],
        "ops_per_s": m.get("ops_per_s"),
        "p50_ms": m.get("change_p50_ms"), "p95_ms": m.get("change_p95_ms"),
        "p50_first_half_ms": diag["p50_first_half"],
        "p50_second_half_ms": diag["p50_second_half"],
        "compiles": diag["compiles"], "setup_s": diag["setup_s"],
        "peak_bytes": diag["peak"],
        "deliveries": [[n, round(c, 3), round(r - c, 3)]
                       for n, c, r in diag["deliveries"]],
    })


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seed", type=int, default=2**31 + 77)
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ctx = multiprocessing.get_context("spawn")
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        out = ctx.Queue()
        child = ctx.Process(target=one_rate, args=(
            args.workload, args.seed, args.seconds, rate, out))
        child.start()
        row = out.get()
        child.join()
        if "error" in row:
            print(f"FAIL: {row['error']}", file=sys.stderr)
            return 1
        row["sustained"] = bool(
            row["p50_second_half_ms"] is not None
            and row["ops_per_s"] is not None
            and row["ops_per_s"] >= 0.95 * row["offered_ops_per_s"]
            and row["p50_second_half_ms"] <= 2000.0
            and row["p50_second_half_ms"]
            <= 1.25 * max(row["p50_first_half_ms"], 1.0))
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    knee = max((r["rate"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "rate_per_s": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
