"""The cell ``shardkv-n3-deep`` at the size the CPU can compile: two
servers a group (an n = 3 engine is minutes of compile here).  Shared by
``tests/test_lab4_multi_deep.py``, ``tests/test_lab4_multi_entry.py``
and ``benchmark/tests/test_rehearsal_lab4_multi_deep.py``."""

__all__ = ["SMALL_COUNTS", "SMALL_N", "at_small_size"]

# The object checker's cumulative unique counts below the joined state
# of setupStates(2, 2, 1, 10) (tests/test_lab4_multi.py's oracle table):
# the size the CPU tests and rehearsals run the cell's driver at.
SMALL_N, SMALL_COUNTS = 2, {1: 8, 2: 42, 3: 180, 4: 681, 5: 2365}


def at_small_size(config: dict) -> dict:
    """``config`` at ``SMALL_N`` servers a group wherever it states the
    group size (the deployment, the Join phase's frozen timers, the
    factory's kwargs and the twin's name) with the oracle's counts at
    that size for its pinned ones; lanes, packed bytes and ``sizing``
    are the full size's and are left out."""
    n = SMALL_N
    deployment = dict(config["deployment"])
    deployment["object_state"] = dict(deployment["object_state"],
                                      servers_per_group=n)
    groups = deployment["object_state"]["groups"]
    protocol = {k: v for k, v in config["protocol"].items()
                if k not in ("lanes", "packed_bytes_per_state", "nodes",
                             "node_width")}
    protocol["kwargs"] = dict(protocol["kwargs"], n=n)
    protocol["name"] = (f"shardstore-multi-g{groups}x{n}"
                        f"-w{protocol['kwargs']['w']}")
    small = {k: v for k, v in config.items() if k != "sizing"}
    small.update(
        deployment=deployment, protocol=protocol,
        join=dict(config["join"], timers_off=[
            f"server{g}-{i}" for g in range(1, groups + 1)
            for i in range(1, n + 1)]),
        reference_counts={str(d): c for d, c in SMALL_COUNTS.items()},
        reference_live_depth=min(config["reference_live_depth"],
                                 max(SMALL_COUNTS)),
        must_pass_depth=min(config["must_pass_depth"], max(SMALL_COUNTS)))
    return small
