"""Run one cell of BENCHMARK.json on the chip(s) this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's index from ``--seed``, serves its traffic through
``CoocServer`` for ``--seconds`` after a warm-up, checks the answers
against the plain reference (``bench/reference.py``) and prints, as the
last line of standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device`` and ``checks``.  The compared numbers and their
limits are also the last lines of standard error.

It refuses to run, with a non-zero exit and no result line, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def chips_or_refuse(chips: int) -> str:
    """The platform to run on; raises where JAX finds no TPU or fewer
    than ``chips`` of them."""
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's default backend is "
                         f"{platform!r}; nothing was run")
    n = len(jax.devices())
    if n < chips:
        raise SystemExit(f"bench: the cell needs {chips} chip(s), JAX sees "
                         f"{n}; nothing was run")
    return platform


def process_setup() -> None:
    """JAX's persistent compile cache at the checkout's fixed path (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, so
    that only the first run of a cell in a checkout compiles."""
    import jax
    from repro.launch.flags import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from bench.harness import run_cell
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    chips_or_refuse(cell.chips)
    process_setup()
    out = run_cell(ROOT, cell, args.seed, args.seconds, bool(args.trace),
                   T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
