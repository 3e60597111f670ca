"""Device time by the program's named scopes in a traced window.

The model code wraps parts of a step in `jax.named_scope`s (for Zamba2:
`zamba2.mamba`, the Mamba-2 mixer; `zamba2.ssd`, its recurrence, inside
it; `zamba2.shared`, a shared-block application with its linear). XLA
keeps the scope path in each instruction's metadata
(`op_name`, "jit(_call)/.../zamba2.ssd/while/body/mul"); the device's
operations in the trace carry only the instruction's name. The profiler
keeps each program's HLO (a serialized `HloProto`, stat "Hlo Proto") in
the `/host:metadata` plane, under the program's name as the "XLA
Modules" line names it ("jit_decode_tokens(123)"). So an operation of
the "XLA Ops" line is placed in the program running around it, and its
instruction's `op_name` there says which scopes it is under (a scope
counts as a path segment).

`of(run)` reads the window's one `.xplane.pb` (where `bench/run.py`
writes it) once per run and keeps, per scope, the union of the device
time of its operations inside the `bench.traced` mark, the busy union of
all operations, and the busy time under none of the scopes. A program
without the scopes (an older commit, another model) yields zero seconds
for each, and the readers return None.

    python3 bench/scopes.py

prints, for the last traced run, seconds and shares of busy time by
scope and under none.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import re
import sys
from typing import Dict, Iterator, List, NamedTuple, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import devtrace

SCOPES = ("zamba2.mamba", "zamba2.ssd", "zamba2.shared")
METADATA = "/host:metadata"
HLO_PROTO = "Hlo Proto"


# -------------------------------------------------------- protobuf wire
def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, span=None) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of the protobuf message in
    buf[span]: an int, or the (start, end) span of a length-delimited
    value."""
    i, end = span or (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _sub(buf, span, number: int) -> List[Tuple[int, int]]:
    return [v for f, v in _fields(buf, span) if f == number]


def hlo_op_names(buf, span) -> Dict[str, str]:
    """{instruction name: metadata op_name} of every computation of one
    serialized HloProto (hlo_module 1 > computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2)."""
    out = {}
    for module in _sub(buf, span, 1):
        for comp in _sub(buf, module, 3):
            for ins in _sub(buf, comp, 2):
                name = op_name = ""
                for f, v in _fields(buf, ins):
                    if f == 1:
                        name = _text(buf, v)
                    elif f == 7:
                        op_name = "".join(_text(buf, s)
                                          for s in _sub(buf, v, 2))
                out[name] = op_name
    return out


def programs(buf, wanted=None) -> Dict[str, Dict[str, str]]:
    """Program name ("jit_f(5)") -> {instruction: op_name}, for the
    programs in `wanted` (all where None), from the `/host:metadata`
    plane of a serialized XSpace (planes 1 > name 2,
    event_metadata 4 > {name 2, stats 5}, stat_metadata 5 > {id 1,
    name 2}; an XStat's metadata_id 1 and bytes_value 6)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        parts = {n: [] for n in (2, 4, 5)}
        for g, v in _fields(buf, plane):
            if g in parts:
                parts[g].append(v)
        if not parts[2] or _text(buf, parts[2][0]) != METADATA:
            continue
        stat_names = {}
        for entry in parts[5]:
            for meta in _sub(buf, entry, 2):
                fs = dict(_fields(buf, meta))
                stat_names[fs.get(1, 0)] = _text(buf, fs[2]) if 2 in fs \
                    else ""
        for entry in parts[4]:
            for meta in _sub(buf, entry, 2):
                name, protos = "", []
                for g, v in _fields(buf, meta):
                    if g == 2:
                        name = _text(buf, v)
                        if wanted is not None and name not in wanted:
                            break
                    elif g == 5:
                        st = dict(_fields(buf, v))
                        if stat_names.get(st.get(1)) == HLO_PROTO and 6 in st:
                            protos.append(st[6])
                for p in protos:
                    out.setdefault(name, {}).update(hlo_op_names(buf, p))
    return out


# -------------------------------------------------------------- reduce
class Scoped(NamedTuple):
    seconds: Dict[str, float]    # scope -> union of its ops' device time
    busy_s: float                # union of all ops' device time
    no_scope_s: float            # busy time under none of the scopes
    devices: int


def scopes_of(op_name: str, scopes: Sequence[str] = SCOPES
              ) -> Tuple[str, ...]:
    """The scopes that appear as a path segment of `op_name`."""
    parts = set(op_name.split("/"))
    return tuple(s for s in scopes if s in parts)


def instruction(event_name: str) -> str:
    """`%fusion.418 = f32[16,112]{...} fusion(...)` -> `fusion.418`."""
    return re.split(r"\s*=\s|\s", event_name.strip(), 1)[0].lstrip("%")


def reduce(planes: Sequence[devtrace.Plane], names: Dict[str, Dict[str, str]],
           scopes: Sequence[str] = SCOPES) -> Scoped:
    """Scoped device time in the marked window. `planes` as
    `devtrace.load` gives them; `names` as `programs` gives them. Each
    operation is placed in the program whose "XLA Modules" event holds
    its start. A `while` has an event of its own over its body's
    operations, so a loop under a scope counts whole."""
    lo, hi = devtrace.mark_window(planes)
    seconds = {s: 0.0 for s in scopes}
    busy = none = 0.0
    devs = devtrace.device_planes(planes)
    for p in devs:
        lines = {l.name: l for l in p.lines}
        mods = sorted(lines[devtrace.MODULES].events, key=lambda e: e[1]) \
            if devtrace.MODULES in lines else []
        starts = [s for _, s, _ in mods]
        cache: Dict[Tuple[str, str], Tuple[str, ...]] = {}
        per: Dict[str, List[Tuple[int, int]]] = {s: [] for s in scopes}
        every, scoped = [], []
        ops = lines[devtrace.OPS].events if devtrace.OPS in lines else []
        for name, start, dur in ops:
            iv = (start, start + dur)
            every.append(iv)
            k = bisect.bisect_right(starts, start) - 1
            module = mods[k][0] if k >= 0 and start < mods[k][1] + mods[k][2] \
                else ""
            key = (module, name)
            if key not in cache:
                cache[key] = scopes_of(
                    names.get(module, {}).get(instruction(name), ""), scopes)
            for s in cache[key]:
                per[s].append(iv)
            if cache[key]:
                scoped.append(iv)
        span = lambda ivs: sum(b - a for a, b in
                               devtrace.merge(devtrace.clip(ivs, lo, hi)))
        # a loop's own event spans its body's operations, so the time
        # under no scope is what no scoped operation covers
        busy += span(every) / 1e9
        none += (span(every) - span(scoped)) / 1e9
        for s in scopes:
            seconds[s] += span(per[s]) / 1e9
    return Scoped(seconds, busy, none, len(devs))


def read(path) -> Scoped:
    """The scoped device time of the trace file at `path`."""
    planes = devtrace.load(str(path))
    wanted = {name for p in devtrace.device_planes(planes) for l in p.lines
              if l.name == devtrace.MODULES for name, _, _ in l.events}
    return reduce(planes, programs(
        memoryview(pathlib.Path(path).read_bytes()), wanted))


def of(run):
    """The scoped device time of `run`'s traced window (None without
    one), read from the trace file once per run."""
    if run.traced is None:
        return None
    if "scopes" not in run.traced:
        from bench import spans

        run.traced["scopes"] = read(spans.trace_file())
    return run.traced["scopes"]


def share(run, scope: str):
    """Device time under `scope` / device busy time, in %; None where the
    window holds no operation under it."""
    sc = of(run)
    if sc is None or not sc.seconds.get(scope) or not sc.busy_s:
        return None
    return 100.0 * sc.seconds[scope] / sc.busy_s


def main() -> int:
    from bench import spans

    sc = read(spans.trace_file())
    pct = lambda v: 100 * v / sc.busy_s if sc.busy_s else None
    print(json.dumps({
        "busy_s": sc.busy_s, "seconds": sc.seconds,
        "share_pct": {s: pct(v) for s, v in sc.seconds.items()},
        "no_scope_s": sc.no_scope_s, "no_scope_pct": pct(sc.no_scope_s)},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
