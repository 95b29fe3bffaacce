"""The control: the guarantee the configurations state — exact counts,
every unique state expanded — broken from outside the program, for one
run, by a fingerprint that reads only part of a state.

It stands where a lower precision stands for a model: the step that
would tempt a later PR (hashing fewer lanes is less work in the
hottest loop).  With only the first ``KEEP`` of a row's lanes mixed into
the 128-bit key, states that differ only in the later lanes (pending
timers, the tail of the network) get one key, are taken for visited and
are never expanded: the unique counts fall short of the object
checker's — 707 for 713 at Paxos depth 4, 49 for 255 in lab 1's
exhausted space (my CPU runs, PR 24; the twins are value-blind, so the
numbers are the same for every seed).  A key of FEWER BITS was tried
first and is no control: every state lands in a few buckets of the
visited table and the strict engine raises ``CapacityOverflow``.
Nothing in the program is switched: the module-level mixer
``engine._fingerprint32`` is wrapped while the block runs, and engines
built inside it trace the wrapper.

``python3 benchmark/tests/control.py <cell> <seed> [<seed> ...]`` runs
the cell's real size on the chip under the control, once a seed, in one
process, and prints which checks failed (PERF.md has the readings)."""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEEP = 0.5    # the share of a row's lanes the control's key reads


@contextlib.contextmanager
def narrowed_fingerprint(keep: float = KEEP):
    from dslabs_tpu.tpu import engine

    real = engine._fingerprint32

    def narrow(flat, seed, sum_fn=None):
        return real(flat[:, :int(flat.shape[1] * keep)], seed, sum_fn)

    engine._fingerprint32 = narrow
    try:
        yield
    finally:
        engine._fingerprint32 = real


def main(argv) -> int:
    import time

    t0 = time.time()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import device, manifest, runner
    from dslabs_tpu.tpu import compile_cache

    cell = manifest.load_cell(ROOT, argv[0])
    compile_cache.setup()
    dev = device.require(cell.chips)
    seconds = float(os.environ.get("CONTROL_SECONDS", "12"))
    verdicts = []
    with narrowed_fingerprint():
        for seed in map(int, argv[1:]):
            try:
                res = runner.run(cell, seed, seconds, False, dev, t0)
                verdicts.append((seed, res["correct"]))
            except Exception as e:  # noqa: BLE001 — a control that
                # crashes has failed the comparison too; say how
                print(f"control seed {seed}: {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
                verdicts.append((seed, f"raised {type(e).__name__}"))
            t0 = time.time()
    print(f"control verdicts (correct must be False in each): {verdicts}",
          flush=True)
    return 0 if all(v is not True for _s, v in verdicts) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
