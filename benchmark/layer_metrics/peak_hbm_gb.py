"""Peak device memory on the fullest chip, GB (10^9 bytes)."""


def compute(run: dict):
    peak = run.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
