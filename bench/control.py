"""Readings that set the limits of ``correct``: the program's, the
control's and the probe fault's, on several seeds in one process.

    python3 -m bench.control --workload <name> --seconds S --seeds 1,2,3

For each seed it makes one whole run of the cell (set-up, window, sample)
and then reads the same sample three times against the float32 reference:
as the program served it; with the control in the program's place, the
family's reference one precision below the configuration (for
``bench.families.llama`` int8 weights and activations in every linear
layer of the model), the probe's state in bfloat16 below its float32; and
with the probe fault, the reference with the probe's fast weights left
unchanged.  One JSON line per seed, each
reading with its verdict under the cell's limits (``bench/limits``): the
program's has to be correct, the control's and the fault's not.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import check as C
from bench import run as RUN


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    limits = RUN.load_cell(args.workload)["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        res = RUN.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        line = {"seed": seed, "limits": limits, "metrics": res["metrics"]}
        for name, key in (("program", "readings"), ("control", "control"),
                          ("probe_frozen", "probe_frozen")):
            line[name] = dict(res[key],
                              correct=C.verdict(res[key], limits))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
