"""Write ``tpu_layout.xplane.pb.gz``: a profiler file laid out as JAX writes
one from a one-chip TPU run of the ``gp.n5000.k16`` window (a device plane
with ``XLA Modules`` and ``XLA Ops`` lines, a host plane with the
benchmark's spans beside other host events), with times chosen by hand so
that the reducer's readings can be checked exactly.

    python3 bench/tests/data/make_tpu_layout_trace.py bench/tests/data/tpu_layout.xplane.pb.gz
"""
import gzip
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

MS = 1_000_000      # nanoseconds
T0 = 1_000_000_000  # start of every line


def _plane(space, pid, name, event_names):
    plane = space.planes.add()
    plane.id, plane.name = pid, name
    ids = {}
    for i, event in enumerate(event_names, 1):
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = event
        ids[event] = i
    return plane, ids


def _line(plane, ids, lid, name, events):
    line = plane.lines.add()
    line.id, line.name, line.timestamp_ns = lid, name, T0
    for event, start, end in events:
        ev = line.events.add()
        ev.metadata_id = ids[event]
        ev.offset_ps = int((start - T0) * 1000)
        ev.duration_ps = int((end - start) * 1000)


def main(path):
    space = xplane_pb2.XSpace()
    dev, ids = _plane(space, 1, "/device:TPU:0", [
        "fusion.1", "tpu_custom_call", "jit_bench_update(12)",
        "jit_bench_downdate(13)"])
    ops, modules, t = [], [], T0 + 1 * MS
    for _ in range(3):   # three steps: an update, then a guarded downdate
        ops += [("fusion.1", t, t + 0.5 * MS),
                ("tpu_custom_call", t + 0.5 * MS, t + 6 * MS)]
        modules.append(("jit_bench_update(12)", t, t + 6 * MS))
        t += 6.5 * MS
        ops.append(("tpu_custom_call", t, t + 7 * MS))
        modules.append(("jit_bench_downdate(13)", t, t + 7 * MS))
        t += 8 * MS
    _line(dev, ids, 1, "XLA Modules", modules)
    _line(dev, ids, 2, "XLA Ops", ops)

    host, hids = _plane(space, 2, "/host:CPU", [
        "bench.window", "bench.update", "bench.downdate", "bench.verdict",
        "PjitFunction(bench_update)"])
    spans, u = [("bench.window", T0, t + 1 * MS)], T0 + 0.2 * MS
    for _ in range(3):
        spans += [("bench.update", u, u + 0.3 * MS),
                  ("PjitFunction(bench_update)", u + 0.05 * MS,
                   u + 0.25 * MS),
                  ("bench.downdate", u + 0.3 * MS, u + 0.6 * MS),
                  ("bench.verdict", u + 0.6 * MS, u + 14.4 * MS)]
        u += 14.5 * MS
    _line(host, hids, 1, "python", spans)
    with open(path, "wb") as f:
        f.write(gzip.compress(space.SerializeToString(), mtime=0))


if __name__ == "__main__":
    main(sys.argv[1])
