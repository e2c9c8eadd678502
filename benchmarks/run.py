"""One run of one cell of BENCHMARK.json, in a new process:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

loads, warms up, measures for --seconds, checks the outputs against the
plain reference and prints the contract's result line last. It fails,
and prints no result, where JAX finds no TPU or fewer chips than the
cell asks for. `--rehearse` walks the same code on the CPU at the tiny
widths in the configuration's `rehearse` group (virtual devices stand
for four chips): it proves control flow and prints under the device
name `cpu`, never a chip metric.
"""
import os
import sys
import time

_WALL_AT_IMPORT = time.time()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == '__main__':
    from harness import runner
    sys.exit(runner.main(sys.argv[1:], _WALL_AT_IMPORT))
