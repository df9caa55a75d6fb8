"""Split a traced window's device time, idle included, among the phases the
program names (``repro.obs.phases``).

    python3 -m bench.phasereduce <trace.xplane.pb[.gz]>

prints one JSON object: per step (an execution of ``jit_bench_downdate``),
the device milliseconds of each phase, of ops in no phase (``unphased``)
and of the idle between program executions (``host_wait``), which
together make up the window.

The join. Every device op carries its HLO instruction name (the
``hlo_op`` stat, or the ``%name =`` prefix of a TPU op's event name) and
its program (the ``hlo_module``/``program_id`` stats, or the last ``XLA
Modules`` execution that started before it). The profiler file keeps
each program's compiled HLO in its ``/host:metadata`` plane (an ``Hlo
Proto`` stat per program, on the TPU as on the CPU); there
``metadata.op_name`` of an instruction holds the named-scope path, such as
``jit(bench_downdate)/repro.guard/...``. The innermost ``repro.`` phase on
that path is the op's phase. A fusion whose own ``op_name`` names no
phase takes the phase most of its fused instructions name. Where XLA
fuses the work of two phases into one op, the whole op goes to the one
phase its ``op_name`` (the fusion's root) or that count gives; such ops
are listed in ``mixed_ops`` with their seconds and every phase their
instructions name. Ops are keyed by ``(program, instruction)``:
``fusion.1`` of one program is not ``fusion.1`` of another.

The split, per device, over the traced window (``bench.window``):

* time some op covers goes to that op's phase (to the op that started
  first where ops overlap), or to ``unphased``;
* idle inside a program execution goes to the phase of the op that ends
  it, or, with no later op in that execution, of the op before it;
* idle outside every program execution is ``host_wait``.

So the phases, ``unphased`` and ``host_wait`` sum to the window exactly.
The wire format of the profiler's protobufs is read directly, so nothing
beyond jax is needed.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import dataclasses
import gzip
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import tracereduce

PHASE_PREFIX = "repro."
UNPHASED = "unphased"
STEP_PROGRAM = "jit_bench_downdate"
_HLO_PROTO_STAT = "Hlo Proto"
_OP_NAME = re.compile(r"^%?([^\s=]+) = ")
_PROGRAM_ID = re.compile(r"^(.*)\((\d+)\)$")


# -- protobuf wire format ----------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield num, value


def _first(buf, num: int, default=None):
    for f, v in _fields(buf):
        if f == num:
            return v
    return default


def _text(buf, num: int) -> str:
    v = _first(buf, num)
    return bytes(v).decode() if v is not None else ""


def _ints(buf, num: int) -> List[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for f, v in _fields(buf):
        if f != num:
            continue
        if isinstance(v, int):
            out.append(v)
        else:
            j = 0
            while j < len(v):
                x, j = _varint(v, j)
                out.append(x)
    return out


# -- the programs' HLO -------------------------------------------------------


def phase_of(op_name: str) -> Optional[str]:
    """The innermost ``repro.`` scope on a named-scope path, or None."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PHASE_PREFIX):
            return part
    return None


def instruction_phases(hlo_module) -> Tuple[Dict[str, Optional[str]],
                                             Dict[str, Tuple[str, ...]]]:
    """``({instruction: phase}, {instruction: phases})`` of one serialized
    ``HloModuleProto`` (computations: field 3; instructions: 2; name: 1,
    metadata: 7 with ``op_name``: 2, called computation ids: 38;
    computation id: 5). The second map holds each instruction whose own
    and fused instructions name more than one phase, with those phases."""
    comps: Dict[int, List[Tuple[str, Optional[str], List[int]]]] = {}
    for f, comp in _fields(hlo_module):
        if f != 3:
            continue
        rows = []
        for g, ins in _fields(comp):
            if g == 2:
                meta = _first(ins, 7)
                op_name = _text(meta, 2) if meta is not None else ""
                rows.append((_text(ins, 1), phase_of(op_name),
                             _ints(ins, 38)))
        comps[_first(comp, 5, 0)] = rows

    def called(ids, seen) -> collections.Counter:
        count: collections.Counter = collections.Counter()
        for cid in ids:
            if cid in seen or cid not in comps:
                continue
            seen.add(cid)
            for _, phase, sub in comps[cid]:
                if phase:
                    count[phase] += 1
                count.update(called(sub, seen))
        return count

    out: Dict[str, Optional[str]] = {}
    mixed: Dict[str, Tuple[str, ...]] = {}
    for rows in comps.values():
        for name, phase, sub in rows:
            inner = called(sub, set()) if sub else collections.Counter()
            # An instruction without a phase of its own (a fusion) takes
            # the one most instructions of the computations it calls name.
            if phase is None and inner:
                phase = inner.most_common(1)[0][0]
            out[name] = phase
            named = set(inner) | ({phase} if phase else set())
            if len(named) > 1:
                mixed[name] = tuple(sorted(named))
    return out, mixed


def program_phases(xspace) -> Tuple[Dict[str, Dict[str, Optional[str]]],
                                    Dict[str, Dict[str, Tuple[str, ...]]]]:
    """``({program: {instruction: phase}}, {program: {instruction:
    phases}})`` from the ``/host:metadata`` plane of a serialized
    ``XSpace`` (the second as ``instruction_phases`` gives it). Programs
    are named as the trace names them, ``jit_f(7)``; each also answers to
    ``jit_f`` where that name is unique."""
    out: Dict[str, Dict[str, Optional[str]]] = {}
    mixed: Dict[str, Dict[str, Tuple[str, ...]]] = {}
    for f, plane in _fields(xspace):
        if f != 1 or _text(plane, 2) != "/host:metadata":
            continue
        stat_names = {}
        for g, entry in _fields(plane):
            if g == 5:  # stat_metadata map entry: key 1, XStatMetadata 2
                stat_names[_first(entry, 1)] = _text(_first(entry, 2), 2)
        for g, entry in _fields(plane):
            if g != 4:  # event_metadata map entry: key 1, XEventMetadata 2
                continue
            meta = _first(entry, 2)
            for h, stat in _fields(meta):
                if h == 5 and stat_names.get(
                        _first(stat, 1)) == _HLO_PROTO_STAT:
                    proto = _first(stat, 6)  # bytes_value: an HloProto
                    program = _text(meta, 2)
                    out[program], mixed[program] = instruction_phases(
                        _first(proto, 1))
    bare = collections.Counter(_PROGRAM_ID.sub(r"\1", p) for p in out)
    for p in list(out):
        name = _PROGRAM_ID.sub(r"\1", p)
        if bare[name] == 1:
            out.setdefault(name, out[p])
            mixed.setdefault(name, mixed[p])
    return out, mixed


# -- the trace ---------------------------------------------------------------


@dataclasses.dataclass
class PhaseTrace:
    """Intervals in nanoseconds on the profiler's clock, per device."""

    ops: Dict[str, List[Tuple[str, str, float, float]]]    # program, instr
    executions: Dict[str, List[Tuple[str, float, float]]]  # program
    window: Optional[Tuple[float, float]]
    phases: Dict[str, Dict[str, Optional[str]]]            # program_phases
    mixed: Dict[str, Dict[str, Tuple[str, ...]]]           # program_phases


def _stats(event) -> Dict[str, str]:
    return {k: str(v) for k, v in event.stats}


def load_phase_trace(path) -> PhaseTrace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``). Device ops come from
    the ``XLA Ops`` line of each ``/device:`` plane; without one (the CPU
    backend), from host events that carry an ``hlo_op`` stat, whose
    executions are grouped by ``run_id``."""
    import jax

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    ops: Dict[str, list] = collections.defaultdict(list)
    executions: Dict[str, list] = collections.defaultdict(list)
    spans = []
    hosts = [p for p in pd.planes if p.name.startswith("/host:")]
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            _device_ops(plane, ops[plane.name], executions[plane.name])
    for plane in hosts:
        for line in plane.lines:
            spans.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(tracereduce.SPAN_PREFIX))
    if not any(ops.values()):
        for plane in hosts:
            _host_ops(plane, ops, executions)
    ops = {p: v for p, v in ops.items() if v}
    window = tracereduce.window_of(tracereduce.Trace(
        ops={p: [(i, s, e) for _, i, s, e in v] for p, v in ops.items()},
        modules={}, spans=spans))
    phases, mixed = program_phases(data)
    return PhaseTrace(ops=ops, executions=dict(executions),
                      window=window, phases=phases, mixed=mixed)


def _device_ops(plane, ops: list, executions: list) -> None:
    """A TPU plane: executions from ``XLA Modules``, ops from ``XLA Ops``,
    each op in the program its stats name or the last execution started
    before it."""
    mods = []
    for line in plane.lines:
        if line.name == tracereduce.MODULES_LINE:
            mods.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
    mods.sort(key=lambda m: m[1])
    starts = [s for _, s, _ in mods]
    executions.extend(mods)
    for line in plane.lines:
        if line.name != tracereduce.OPS_LINE:
            continue
        for e in line.events:
            st = _stats(e)
            instr = st.get("hlo_op")
            if instr is None:
                m = _OP_NAME.match(e.name)
                instr = m.group(1) if m else e.name
            program = _program(st)
            if program is None:
                # The last execution started before the op: programs run
                # one at a time, and an op may end after its execution's
                # event does.
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0:
                    program = mods[i][0]
            ops.append((program or "", instr, e.start_ns, e.end_ns))


def _host_ops(plane, ops, executions) -> None:
    """The CPU backend: ops are host events with ``hlo_op`` and
    ``hlo_module`` stats; an execution spans the ops of one ``run_id``."""
    runs: Dict[tuple, list] = collections.defaultdict(list)
    for line in plane.lines:
        for e in line.events:
            st = _stats(e)
            if "hlo_op" in st and "hlo_module" in st:
                runs[(_program(st), st.get("run_id"))].append(
                    (st["hlo_op"], e.start_ns, e.end_ns))
    for (program, _), evs in runs.items():
        ops[plane.name].extend((program, i, s, e) for i, s, e in evs)
        executions[plane.name].append(
            (program, min(s for _, s, _ in evs), max(e for _, _, e in evs)))


def _program(stats: Dict[str, str]) -> Optional[str]:
    name = stats.get("hlo_module")
    if name is None:
        return None
    pid = stats.get("program_id")
    return f"{name}({pid})" if pid is not None else name


# -- the split ---------------------------------------------------------------


def _lookup(tables, program: str, instr: str):
    table = tables.get(program)
    if table is None:
        table = tables.get(_PROGRAM_ID.sub(r"\1", program), {})
    return table.get(instr)


def split_device(ops, executions, lo: float, hi: float, phases, mixed
                 ) -> Tuple[Dict[str, float], float, Dict[str, float],
                            Dict[str, float]]:
    """``(seconds by phase or 'unphased', host_wait seconds, unphased
    seconds by 'program/instruction', busy seconds of the ops that
    ``mixed`` lists, by 'program/instruction')`` of one device over
    ``[lo, hi]``."""
    tagged = []
    for program, instr, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            phase = _lookup(phases, program, instr)
            tagged.append((s, e, phase or UNPHASED, f"{program}/{instr}",
                           _lookup(mixed, program, instr) is not None))
    tagged.sort(key=lambda t: t[0])
    starts = [t[0] for t in tagged]
    execs = sorted((max(s, lo), min(e, hi)) for _, s, e in executions
                   if min(e, hi) > max(s, lo))
    seconds: Dict[str, float] = collections.Counter()
    unphased_ops: Dict[str, float] = collections.Counter()
    mixed_ops: Dict[str, float] = collections.Counter()
    exec_starts = [x for x, _ in execs]
    host_wait = 0.0

    def charge_idle(a: float, b: float) -> None:
        nonlocal host_wait
        # Split the gap at the edges of the executions it touches.
        cur = a
        j = max(bisect.bisect_right(exec_starts, a) - 1, 0)
        while cur < b:
            while j < len(execs) and execs[j][1] <= cur:
                j += 1
            if j >= len(execs) or execs[j][0] >= b:
                host_wait += b - cur
                return
            xs, xe = execs[j]
            if xs > cur:
                host_wait += xs - cur
                cur = xs
            part_end = min(b, xe)
            k = bisect.bisect_left(starts, part_end)
            if k < len(tagged) and tagged[k][0] < xe:
                phase = tagged[k][2]
            else:
                k = bisect.bisect_left(starts, cur) - 1
                phase = (tagged[k][2] if k >= 0 and tagged[k][0] >= xs
                         else UNPHASED)
            seconds[phase] += part_end - cur
            cur = part_end

    cursor = lo
    for s, e, phase, key, is_mixed in tagged:
        if s > cursor:
            charge_idle(cursor, s)
            cursor = s
        if e > cursor:
            seconds[phase] += e - cursor
            if phase == UNPHASED:
                unphased_ops[key] += (e - cursor) * 1e-9
            if is_mixed:
                mixed_ops[key] += (e - cursor) * 1e-9
            cursor = e
    if hi > cursor:
        charge_idle(cursor, hi)
    return ({k: v * 1e-9 for k, v in seconds.items()}, host_wait * 1e-9,
            dict(unphased_ops), dict(mixed_ops))


def reduce_phases(trace: PhaseTrace, top: int = 10) -> Optional[dict]:
    """Device seconds by phase in the traced window, averaged over devices.

    ``phase_seconds`` holds each phase some op of the window ran in, and
    ``unphased``; ``host_wait_s`` the idle between executions;
    ``phase_steps`` the executions of ``STEP_PROGRAM`` that lie in the
    window; ``unphased_ops`` the ``top`` ops in no phase, and
    ``mixed_ops`` the ``top`` ops whose instructions name more than one
    phase, each with its busy seconds, the phase it was charged to and
    the phases it holds. None without device ops, a window, or the
    programs' HLO."""
    if (not any(trace.ops.values()) or trace.window is None
            or not trace.phases):
        return None
    lo, hi = trace.window
    if hi <= lo:
        return None
    total: Dict[str, float] = collections.Counter()
    unphased: Dict[str, float] = collections.Counter()
    mixed: Dict[str, float] = collections.Counter()
    wait = 0.0
    steps = 0
    devices = sorted(trace.ops)
    for dev in devices:
        execs = trace.executions.get(dev, [])
        secs, w, un, mix = split_device(trace.ops[dev], execs, lo, hi,
                                        trace.phases, trace.mixed)
        total.update(secs)
        unphased.update(un)
        mixed.update(mix)
        wait += w
        steps = max(steps, sum(
            1 for p, s, e in execs if s >= lo and e <= hi
            and _PROGRAM_ID.sub(r"\1", p) == STEP_PROGRAM))
    nd = len(devices)

    def held(key: str) -> list:
        program, instr = key.rsplit("/", 1)
        return [_lookup(trace.phases, program, instr),
                list(_lookup(trace.mixed, program, instr))]

    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": nd,
        "phase_seconds": {k: v / nd for k, v in total.items()},
        "host_wait_s": wait / nd,
        "phase_steps": steps,
        "unphased_ops": [[k, v / nd] for k, v in sorted(
            unphased.items(), key=lambda kv: -kv[1])[:top]],
        "mixed_ops": [[k, v / nd, *held(k)] for k, v in sorted(
            mixed.items(), key=lambda kv: -kv[1])[:top]],
    }


# -- per-step readings -------------------------------------------------------


def phase_ms(red: Optional[dict], phases) -> Optional[float]:
    """Device milliseconds per step of the named phases together. None
    without a split, without steps, or where none of them was found."""
    if not red or not red.get("phase_steps"):
        return None
    found = [red["phase_seconds"][p] for p in phases
             if p in red["phase_seconds"]]
    if not found:
        return None
    return 1e3 * sum(found) / red["phase_steps"]


def host_wait_ms(red: Optional[dict]) -> Optional[float]:
    """Idle milliseconds per step between program executions."""
    if not red or not red.get("phase_steps"):
        return None
    return 1e3 * red["host_wait_s"] / red["phase_steps"]


def per_step(red: Optional[dict]) -> Optional[dict]:
    """The split per step, in ms: ``kernel``, ``layout`` (pad and unpad),
    ``guard``, ``unphased`` and ``host_wait``, with their sum beside the
    window over the steps."""
    from repro.obs import phases

    if not red or not red.get("phase_steps"):
        return None
    out = {
        "kernel_ms": phase_ms(red, [phases.KERNEL]),
        "layout_ms": phase_ms(red, [phases.PAD, phases.UNPAD]),
        "guard_ms": phase_ms(red, [phases.GUARD]),
        "unphased_ms": phase_ms(red, [UNPHASED]) or 0.0,
        "host_wait_ms": host_wait_ms(red),
    }
    out["sum_ms"] = sum(v or 0.0 for v in out.values())
    out["window_per_step_ms"] = 1e3 * red["window_s"] / red["phase_steps"]
    return out


def main(argv=None) -> int:
    src = str(Path(__file__).resolve().parents[1] / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args(argv)
    red = reduce_phases(load_phase_trace(args.trace))
    print(json.dumps({"split": red, "per_step": per_step(red)}))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main())
