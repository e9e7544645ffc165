#!/usr/bin/env python3
"""A run of a cell that also reads the control, for setting limits.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Does everything ``bench/run.py`` does and prints its result line for the
program.  Then, once the window has closed, it puts the control's answers
into the recorded answers in place of the program's, runs the same check
on them and prints that result line last: its ``correct`` has to read
false.  The control belongs to the mix's answer check, which says what
it is in its ``CONTROL`` (``bench/check.py``; for BFS levels, the plain
reference stopped one level short).  The benchmark's own runs do not
run it.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    args = bench_run.parse(argv)
    from bench.harness import run_cell
    run = run_cell(bench_run.ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START, control=True)
    print(json.dumps(bench_run.report(run)), flush=True)
    bench_run.log(f"control: {run.answer_check.CONTROL}")
    print(json.dumps(bench_run.report(run, run.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
