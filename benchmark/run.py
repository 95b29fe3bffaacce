#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child that needs the chip.  It resolves the cell to its
files (``harness/manifest.py``), refuses any backend that is not a TPU of
the cell's width with a non-zero exit and no result line, and hands the
rest of the run to ``harness/runner.py``.  The last line of stdout is the
result object.  JAX is touched only after the arguments are parsed."""

import time

_T0 = time.time()       # process start, as close as Python lets us

import argparse         # noqa: E402
import os               # noqa: E402
import sys              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import device, manifest, runner

    try:
        cell = manifest.load_cell(ROOT, args.workload)
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    # The program's one compile-cache seam, before anything compiles:
    # JAX_COMPILATION_CACHE_DIR where it is set, else
    # <checkout>/.jax_cache — a fixed path inside the checkout.
    from dslabs_tpu.tpu import compile_cache

    compile_cache.setup()
    try:
        dev = device.require(cell.chips)
    except device.NoChip as e:
        print(f"benchmark: {e} — nothing was run", file=sys.stderr)
        return 2
    runner.run(cell, args.seed, args.seconds, bool(args.trace), dev, _T0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
