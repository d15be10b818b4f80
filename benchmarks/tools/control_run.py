"""A run of a cell that proves the comparison can fail, at the cell's own size
on the chip.  The benchmark's own runs never do this.

    python3 benchmarks/tools/control_run.py [--control fp8,int8] [--fault altered_token] \
        --workload ... --seed ... --seconds ... --trace 0      (run.py's arguments)

``--control`` names modes of the reference (``fp8``, ``int8``: the nearest
precisions below the configuration's; for a training cell also ``half``, the
reference on half of the rows).  Each is put in the program's place and judged
as a run is, by the harness's own ``judge`` against the cell's limits (the
``in_place`` lines), and has to come out not correct.  ``--fault`` plants one
of ``tools/faults.py`` in the program; the run's own ``correct`` then has to
come out false.  The exit code is 0 when everything came out as it has to.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    args, rest = ap.parse_known_args()
    from benchmarks import run
    from benchmarks.tools.faults import FAULTS
    if args.fault:
        with FAULTS[args.fault]():
            code, ok, obs = run.run_cell(rest, control=args.control or None)
    else:
        code, ok, obs = run.run_cell(rest, control=args.control or None)
    if code:
        return code
    in_place = obs["check"].get("in_place", {})
    as_expected = (ok == (not args.fault)) and not any(in_place.values())
    print("control_run " + json.dumps({
        "fault": args.fault or None, "correct": ok, "in_place": in_place,
        "as_expected": as_expected}), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
