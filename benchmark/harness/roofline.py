"""What the search must move, whatever the program does: the byte
function behind ``superstep_roofline_pct``.

A strict BFS level reads every frontier row once (``expanded`` rows of
``bytes_per_state``), and for every successor it generates
(``explored``) writes the successor's row once and touches the visited
table twice with a 16-byte fingerprint (probe read + insert write, 32
bytes).  No operation count: the step is integer mixing and compares,
so bytes bound it."""

from __future__ import annotations

FINGERPRINT_TRAFFIC_BYTES = 32


def necessary_bytes(explored: int, expanded: int,
                    bytes_per_state: int) -> int:
    """Bytes a level has to move through HBM."""
    return (explored * (bytes_per_state + FINGERPRINT_TRAFFIC_BYTES)
            + expanded * bytes_per_state)


def least_seconds(explored: int, expanded: int, bytes_per_state: int,
                  hbm_bytes_per_s: float, chips: int) -> float:
    """The least time ``chips`` chips could take for those bytes."""
    return (necessary_bytes(explored, expanded, bytes_per_state)
            / (hbm_bytes_per_s * chips))


def roofline_pct(explored: int, expanded: int, bytes_per_state: int,
                 device_secs: float, hbm_bytes_per_s: float,
                 chips: int) -> float:
    """Share of the bandwidth roofline: least time over measured device
    time (per chip, the chips working side by side), in percent."""
    return 100.0 * least_seconds(explored, expanded, bytes_per_state,
                                 hbm_bytes_per_s, chips) / device_secs
