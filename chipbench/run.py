"""Chip benchmark of DFedAvgM training rounds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``). See ``chipbench/harness.py``.
"""
import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started, by the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    now = time.clock_gettime(time.CLOCK_BOOTTIME)
    return now - start_ticks / os.sysconf("SC_CLK_TCK")


HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(sys.argv[1:], process_age=_process_age))
