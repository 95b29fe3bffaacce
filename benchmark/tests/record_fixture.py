"""Record the small chip trace the reduction's test reads
(``fixtures/*.xplane.pb.gz``): the cell's own supervisor, searched to a
shallow depth once to warm it and once more under the benchmark's tracer
and level recorder.  Run on the chip, by hand:

    python3 benchmark/tests/record_fixture.py <cell> <depth> <out.gz>
"""

import gzip
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def trim(raw: bytes) -> bytes:
    """Keep of a recorded trace what the reduction reads — the chips'
    ``XLA Ops`` and ``XLA Modules`` lines and the host's ``bench:``
    annotations, without the events' stats — so that the fixture stays
    small.  Needs TensorFlow's ``xplane_pb2`` (installed here; only
    re-recording a fixture needs it, the tests do not)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from benchmark.harness import trace
    from benchmark.harness.spans import PREFIX

    space = xplane_pb2.XSpace.FromString(raw)
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        on_device = bool(trace.DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:CPU"):
            continue
        out = kept.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if on_device and line.name not in (trace.OPS_LINE,
                                               trace.MODULES_LINE):
                continue
            events = [ev for ev in line.events if on_device or
                      plane.event_metadata[ev.metadata_id].name
                      .startswith(PREFIX)]
            if not events:
                continue
            new = out.lines.add(id=line.id, name=line.name,
                                timestamp_ns=line.timestamp_ns)
            for ev in events:
                new.events.add(metadata_id=ev.metadata_id,
                               offset_ps=ev.offset_ps,
                               duration_ps=ev.duration_ps)
                used.add(ev.metadata_id)
        for mid in used:
            meta = plane.event_metadata[mid]
            out.event_metadata[mid].id = meta.id
            out.event_metadata[mid].name = meta.name
    return kept.SerializeToString()


def main(argv) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import device, manifest, spans
    from dslabs_tpu.tpu import compile_cache

    cell = manifest.load_cell(ROOT, argv[0])
    depth, out_path = int(argv[1]), argv[2]
    compile_cache.setup()
    device.require(cell.chips)
    sup = cell.driver.build_supervisor(cell, depth)
    sup.run()
    tracer = spans.Tracer(os.path.join(ROOT, ".bench_trace", "fixture"),
                          60.0)
    recorder = spans.level_recorder(None, 0)
    sup.telemetry = recorder
    tracer.start()
    out = sup.run()
    recorder.finish()
    tracer.close()
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(tracer.xplane(), "rb") as src, \
            gzip.open(out_path, "wb", 9) as dst:
        raw = src.read()
        try:
            raw = trim(raw)
        except ImportError as e:     # no TensorFlow on that machine:
            print(f"not trimmed ({e}); trim it where xplane_pb2 is")
        dst.write(raw)
    print(f"{out_path}: {os.path.getsize(out_path)} bytes; depth "
          f"{out.depth}, unique {out.unique_states}, explored "
          f"{out.states_explored}, slice {tracer.window_s:.3f}s, "
          f"dispatches {recorder.dispatches_by_level}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
