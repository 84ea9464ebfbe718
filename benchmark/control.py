"""The control of "How ``correct`` is decided": the plain reference put in
the program's place, computed in the precision below the configuration's
(fp8 operands for a bf16 configuration), and held to the cell's limits. It
has to come out as NOT correct. Run on the chip at the cell's own size:

    python3 -m benchmark.control --workload <cell> --seeds 11,12,13

The benchmark's own runs never run this; ``benchmark/tests`` runs it at
the rehearsal sizes.
"""
import argparse
import json
import sys

from benchmark import run


def control(cell, seed, precision="fp8"):
    """[(name, value, limit)] of the control against the reference."""
    runner = cell.module("runners")
    return runner.control(cell, seed, precision)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from mxtpu import compile_service
    compile_service.use_checkout_xla_cache()
    cell = run.Cell(args.workload, rehearse=args.rehearse)
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = control(cell, seed)
        failed = [n for n, v, lim in rows if not v <= lim]
        print(json.dumps({"seed": seed, "control_fails": failed,
                          "numbers": {n: v for n, v, _ in rows},
                          "limits": {n: lim for n, _, lim in rows}}))
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
