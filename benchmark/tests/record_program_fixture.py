"""Record the small CPU traces ``test_program_spans.py`` reduces
(``fixtures/program-*.xplane.pb.gz`` and ``fixtures/program-spans.json``):
one call of the lab entry point, and the Paxos configuration searched to
depth 5 with level 4 traced — each through the harness's own runner at
the rehearsals' tiny caps, so that the program writes its ``dslabs:``
annotations and registers its executables exactly as in a traced run on
the chip.  Run by hand, on the CPU, from the checkout's root:

    python3 benchmark/tests/record_program_fixture.py

The numbers in these fixtures are CPU numbers: they pin the reduction's
arithmetic and the exact counters, never a device metric.
"""

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import conftest  # noqa: E402,F401  (the CPU backend, before JAX loads)
from helpers import ROOT, run_cell, tiny_cell  # noqa: E402

KEEP_STATS = ("hlo_module", "run_id", "device_ordinal")
# key: (cell, parameters over the cell's, seconds, keep the operations)
RECORDINGS = {
    "lab": ("lab1-entry", dict(traced_calls=1), 1.0, False),
    "paxos": ("paxos3-deep", dict(max_depth=5,
                                  trace_min_frontier_rows=100), 60.0,
              True),
}


def trim(raw: bytes, ops: bool):
    """Keep of a CPU trace what the readers read: on the host's plane,
    the ``dslabs:`` and ``bench:`` annotations with their stats, and the
    XLA threads' operations (if ``ops``) with the stats that place them
    in a module run.  Returns the bytes and the operations' names.
    Needs TensorFlow's ``xplane_pb2`` (only recording does)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace.FromString(raw)
    kept = xplane_pb2.XSpace()
    op_names = set()
    for plane in space.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        out = kept.planes.add(id=plane.id, name=plane.name)
        keep_ids = {mid for mid, m in plane.stat_metadata.items()
                    if m.name in KEEP_STATS}
        used_events, used_stats = set(), set()
        for line in plane.lines:
            new = None
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                ours = name.startswith(("dslabs:", "bench:"))
                stats = [st for st in ev.stats
                         if ours or st.metadata_id in keep_ids]
                is_op = (ops and not ours and not name.startswith("end:")
                         and any(plane.stat_metadata[st.metadata_id].name
                                 == "hlo_module" for st in stats))
                if not (ours or is_op):
                    continue
                if is_op:
                    op_names.add(name)
                if new is None:
                    new = out.lines.add(id=line.id, name=line.name,
                                        timestamp_ns=line.timestamp_ns)
                copy = new.events.add(metadata_id=ev.metadata_id,
                                      offset_ps=ev.offset_ps,
                                      duration_ps=ev.duration_ps)
                for st in stats:
                    copy.stats.add().CopyFrom(st)
                    used_stats.add(st.metadata_id)
                    if st.WhichOneof("value") == "ref_value":
                        used_stats.add(st.ref_value)
                used_events.add(ev.metadata_id)
        for mid in used_events:
            out.event_metadata[mid].id = mid
            out.event_metadata[mid].name = plane.event_metadata[mid].name
        for mid in used_stats:
            out.stat_metadata[mid].id = mid
            out.stat_metadata[mid].name = plane.stat_metadata[mid].name
    return kept.SerializeToString(), op_names


def main() -> int:
    from benchmark.harness import program_spans
    from dslabs_tpu.tpu import telemetry

    side = {}
    for key, (cell_name, params, seconds, ops) in RECORDINGS.items():
        cell = tiny_cell(cell_name, **params)
        result, _lines = run_cell(cell, seconds=seconds, trace=True)
        assert result["correct"], result
        path = program_spans.xplane_path({"cell": cell_name})
        out = os.path.join(HERE, "fixtures", f"program-{key}.xplane.pb.gz")
        with open(path, "rb") as src, gzip.open(out, "wb", 9) as dst:
            raw, op_names = trim(src.read(), ops)
            dst.write(raw)
        side[key] = {
            "cell": cell_name, "params": params,
            "metrics": {k: v["value"]
                        for k, v in result["metrics"].items()}}
        print(f"{out}: {os.path.getsize(out)} bytes; metrics "
              f"{side[key]['metrics']}")
    # the superstep's instruction -> scope map, as the Paxos run's
    # executable gave it (a fixture has no executable to ask)
    side["paxos"]["traced_depth"] = 4
    side["paxos"]["scopes"] = {
        name: list(scope_named) for name, scope_named
        in telemetry.program_scopes("superstep").items()
        if name in op_names}
    # one instruction a line: the file is read in review
    scopes = side["paxos"].pop("scopes")
    text = json.dumps(side, indent=0, sort_keys=True)
    rows = ",\n".join(f"{json.dumps(k)}: {json.dumps(v)}"
                      for k, v in sorted(scopes.items()))
    head, _, tail = text.rpartition("}\n}")
    with open(os.path.join(HERE, "fixtures", "program-spans.json"),
              "w") as fh:
        fh.write(f'{head},\n"scopes": {{\n{rows}\n}}\n}}\n}}\n')
    return 0


if __name__ == "__main__":
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.exit(main())
