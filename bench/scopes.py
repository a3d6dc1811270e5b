"""Device time under one ``jax.named_scope`` of the program, read from the
traced run's profile.

``trace.reduce_xspace`` keeps each device op's name and times; the scope
an op was traced under is its op-name metadata
(``jit(round_fn)/vmap(local_train)/while/body/...``), which this module
reads from the compiled programs that the profiler stores in its
``/host:metadata`` plane (their ``HloProto``), by program and instruction
name. ``ProfileData`` does not show the ``tf_op`` stat that a TPU op's
event metadata carries, and a CPU op has none, so the few protobuf fields
needed are read here from the wire format. An op is matched to its program
by the enclosing event of its device's ``XLA Modules`` line (TPU) or by its
``hlo_module`` and ``program_id`` stats (CPU), and devices are found as
``trace.reduce_xspace`` finds them.

The readers find the profile where ``bench/run.py`` writes it,
``bench/.trace``; a program without the scope reads nothing there.
"""
from __future__ import annotations

import bisect
import functools
import os
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace as trace_mod

TRACE_DIR = Path(__file__).resolve().parent / ".trace"

ScopedOp = Tuple[float, float, str]   # (start_ns, end_ns, scope path)


def scoped_device_ms(ctx, scope: str) -> Optional[float]:
    """Device time per traced round of the ops under ``scope``, averaged
    over the devices; None where no op of the profile is under it."""
    rounds = ctx["trace"].rounds()
    if not rounds:
        return None
    try:
        path = trace_mod.find_xspace(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    busy = busy_ns(ctx["trace"], scoped_ops(path), scope)
    return busy / len(rounds) * 1e-6 if busy else None


def busy_ns(tr: "trace_mod.Trace", ops: Dict[str, List[ScopedOp]],
            scope: str) -> float:
    """Busy time inside ``tr``'s window of the ops whose scope path holds
    ``scope`` as a whole part, whatever transformation wraps it
    (``vmap(local_train)``, ``transpose(jvp(local_train))``), averaged over
    the devices."""
    if not ops:
        return 0.0
    part = re.compile(rf"(?<![\w.]){re.escape(scope)}(?![\w.])")
    return sum(trace_mod.total(trace_mod.clip(
        trace_mod.union([(a, b) for a, b, path in dev if part.search(path)]),
        tr.lo, tr.hi)) for dev in ops.values()) / len(ops)


def scoped_ops(path: str) -> Dict[str, List[ScopedOp]]:
    """Per device, its ops as ``(start, end, scope path)``."""
    st = os.stat(path)
    return _scoped_ops(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=1)
def _scoped_ops(path: str, _mtime_ns: int, _size: int
                ) -> Dict[str, List[ScopedOp]]:
    # one parse serves every reader of a run (the key changes with the file)
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    scopes = program_scopes(raw)
    devices: Dict[str, List[ScopedOp]] = {}
    cpu_ops: List[ScopedOp] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            modules = sorted((e.start_ns, e.name)
                             for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in modules]
            ops = []
            for e in lines["XLA Ops"]:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                program = scopes.get(modules[i][1], {}) if i >= 0 else {}
                ops.append((e.start_ns, e.start_ns + e.duration_ns,
                            program.get(trace_mod.short_name(e.name), "")))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" not in stats:
                        continue
                    program = scopes.get(f"{stats.get('hlo_module')}"
                                         f"({stats.get('program_id')})", {})
                    cpu_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    program.get(str(stats["hlo_op"]), "")))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return devices


# --- the protobuf fields the scopes need, read from the wire format ---
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map: key 1,
# value 2), .stat_metadata = 5 (map); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .bytes_value = 6.
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
# .metadata = 7; OpMetadata.op_name = 2.

def program_scopes(raw: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: scope path}}`` from the ``HloProto`` of
    each program in the ``/host:metadata`` plane. ``program`` is named as
    the ``XLA Modules`` events name it, ``jit_f(<program id>)``; the scope
    path is the op name less its last part, the op itself."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1 or not any(g == 2 and _text(v) == "/host:metadata"
                             for g, v in _fields(plane)):
            continue
        stat_kinds = (dict(_fields(m)) for m in _map_values(plane, 5))
        hlo_stat = {k.get(1) for k in stat_kinds
                    if _text(k.get(2, b"")) == "Hlo Proto"}
        for meta in _map_values(plane, 4):
            fields = list(_fields(meta))
            program = "".join(_text(v) for g, v in fields if g == 2)
            for g, v in fields:
                stat = dict(_fields(v)) if g == 5 else {}
                if stat.get(1) in hlo_stat and 6 in stat:
                    out[program] = _instruction_scopes(stat[6])
    return out


def _instruction_scopes(proto) -> Dict[str, str]:
    out = {}
    for module in _values(proto, 1):
        for comp in _values(module, 3):
            for inst in _values(comp, 2):
                name = op_name = ""
                for k, v in _fields(inst):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = _text(dict(_fields(v)).get(2, b""))
                out[name] = op_name.rpartition("/")[0]
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int, or a memoryview of a
    length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _values(buf, field: int) -> Iterator:
    return (v for f, v in _fields(buf) if f == field)


def _map_values(plane, field: int) -> Iterator:
    for entry in _values(plane, field):
        yield from _values(entry, 2)


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")
