"""What a walk step must move, whatever the program does: the byte
function behind ``round_roofline.swarm``.

A walker that advances reads its state row and writes its successor's,
each once and at the width the fleet holds a row (``row_bytes``: the
fleet's rows are unpacked int32 lanes); writes one word of its history;
and puts one 16-byte fingerprint to the visited table, reading the one
bucket of eight 16-byte slots it lands in.  The event tables, the pick,
the restart's seed gather and the predicates re-read what the step
already holds: nothing more is NECESSARY.  No operation count: the step
is integer compares and selects, so bytes bound it."""

from __future__ import annotations

HISTORY_BYTES = 4
KEY_BYTES = 16
BUCKET_BYTES = 8 * 16


def step_bytes(row_bytes: int) -> int:
    """Bytes one advanced walker step has to move through HBM."""
    return 2 * row_bytes + HISTORY_BYTES + KEY_BYTES + BUCKET_BYTES


def necessary_bytes(walker_steps: int, row_bytes: int) -> int:
    return walker_steps * step_bytes(row_bytes)


def roofline_pct(walker_steps: int, row_bytes: int, device_secs: float,
                 hbm_bytes_per_s: float, chips: int) -> float:
    """Share of the bandwidth roofline: the least time ``chips`` chips
    could take for the steps' bytes over the measured device time (per
    chip, the chips working side by side), in percent."""
    return (100.0 * necessary_bytes(walker_steps, row_bytes)
            / (hbm_bytes_per_s * chips) / device_secs)
