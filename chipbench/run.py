"""The one command: ``python3 chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

It needs the chips the cell names: off-chip it says what JAX found and
exits 3 with no result line; there is no flag that lets it pass on a CPU.
The last line of a run's standard output is the contract's JSON object;
sample counts, the set-up breakdown, peak HBM and every number compared
beside its limit go on earlier lines.  ``--control 1`` (never given by the
driver) also evaluates the lower-precision control on the same sample and
prints its number: that is how the limits in the configuration files were
set (see README.md)."""

import time

_T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness

    try:
        return harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t0=_T0,
                                control=bool(args.control))
    except harness.NoChipError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
