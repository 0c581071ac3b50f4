"""The control and the planted faults, run on the chip at a cell's size.

    python3 benchmark/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --fault lose_ack

Runs the cell as run.py does, with the timed path broken underneath
(harness.FAULTS), once per seed, and prints each run's compared numbers.
The control (`lose_ack`: one acknowledged change per delivery never
reaches the farm) breaks the configuration's stated guarantee and has to
come out not correct; benchmark/tests/test_faults.py keeps the same
faults as tests at a size a CPU test run can hold. With ``--unlisted``
it runs a cell that is kept as files but left out of BENCHMARK.json
(``--fault none`` for a sound run). The benchmark's own runs never run
this. Runs on the chip only.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--fault", default="lose_ack",
                        help="a harness.FAULTS name, or none for a sound run")
    parser.add_argument("--unlisted", action="store_true",
                        help="run a cell left out of BENCHMARK.json from "
                        "its cell file")
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from benchmark import harness

    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            result = harness.run_cell(
                args.workload, seed, args.seconds, False, time.perf_counter(),
                root=ROOT, fault=None if args.fault == "none" else args.fault,
                unlisted=args.unlisted)
        except harness.NoChip as exc:
            print(f"FAIL: {exc}", file=sys.stderr)
            return 1
        print("CONTROL " + json.dumps({
            "workload": args.workload, "fault": args.fault, "seed": seed,
            "correct": result["correct"], "checks": result["checks"],
            "metrics": result["metrics"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
