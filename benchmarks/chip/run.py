"""``python3 benchmarks/chip/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``: one cell of the chip benchmark, in this
process, on the chips of this machine. The last line of standard output
is the result object; see harness.py and README.md."""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import sys  # noqa: E402

if __name__ == "__main__":
    import harness

    sys.exit(harness.run(sys.argv[1:], _T0))
