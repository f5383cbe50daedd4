"""A run of a cell with its controls compared beside the program.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The controls are the mix's ``controls``: each the plain reference with
one guarantee the configuration states broken, put in the program's
place (``bench/harness.py``, ``check``).  The run is an ordinary run of
the cell; its last line is the result line with each control's compared
numbers under ``controls``.  A sound benchmark reads the program as
correct and every control as not.  The benchmark's own runs never run
them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import run_cell
    from bench.run import chips_or_refuse, process_setup
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    chips_or_refuse(cell.chips)
    process_setup()
    out = run_cell(ROOT, cell, args.seed, args.seconds, False, T_START,
                   controls=tuple(cell.mix["controls"]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
