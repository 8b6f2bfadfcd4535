"""The serving core's own spans and device scopes in a traced run.

The harness's loader (:mod:`bench.trace`) keeps its own three spans and
each device's ops.  The serving core writes more into the same profiler
trace: host spans named ``serve.*`` inside ``ingest()`` (the stages of a
verdict step, some with integer stats: ``cycle``, ``h2d_bytes``,
``d2h_bytes``), and device ops under named scopes (``ring_scatter``).  This
module reads them into a plain form (the recorded fixture of the tests
keeps its ``stages`` and ``device_scopes`` beside :mod:`bench.trace`'s):

    {"window": [lo_ns, hi_ns],
     "stages": [[name, start_ns, end_ns, {stat: int, ...}], ...],
     "device_scopes": {"<plane name>": [[scope, start_ns, end_ns], ...]}}

``window`` is the harness's window (:func:`bench.trace.window`) of the same
file, which ties the stages to the run that reads them.  A device op's
scope comes from the stat of its event metadata that carries JAX's name
stack (``NAME_STACK_STAT``); a fusion carries the name stack of its root
instruction only, so a scope's time is that of the fusions whose root lies
in it.  A program without these spans leaves ``stages`` empty, and every
reader then returns None.

    python3 bench/stages.py TRACE_DIR

prints, for the trace under ``TRACE_DIR`` (a traced run's
``.bench_cache/trace/<cell>``), each stage's time per verdict step and the
busiest device's idle time by the innermost stage that covers it.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # The script's own directory would shadow top-level modules (``trace``).
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".")
                   != os.path.join(ROOT, "bench")]
    sys.path.insert(0, ROOT)

from bench import trace as T  # noqa: E402

STAGE_PREFIX = "serve."
# Named scopes of the device step that ``device_scopes`` keeps.
SCOPES = ("ring_scatter",)
# The device-op stat that carries JAX's name stack.
NAME_STACK_STAT = "tf_op"
# The traced runs' profiles, as the harness writes them.
TRACE_GLOB = os.path.join(".bench_cache", "trace", "*", "plugins", "profile",
                          "*", "*.xplane.pb")
# Stages of the verdict step that the coverage sums (normalize runs on
# every cycle, finalize holds the last four).
STEP_STAGES = ("serve.operands", "serve.dispatch", "serve.block",
               "serve.unpack", "serve.head", "serve.rows")

_loaded: Dict[str, Tuple[float, dict]] = {}


# ---------------------------------------------------------------------------
# The name stack of a device op is a stat of its event *metadata*, which
# ``jax.profiler.ProfileData`` does not expose: it is read from the
# ``XSpace`` protocol buffer itself, by the few fields it needs.
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the string).


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int, end: int):
    """(field number, value) of one message in ``buf[start:end]``: an int
    for a varint, ``(start, end)`` of a length-delimited field, None for a
    fixed-width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, tuple]:
    key, value = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def name_stacks(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane, the JAX name stack (``NAME_STACK_STAT``) of each
    op, keyed by the op's name as ``ProfileData`` gives it."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events.append(_map_entry(buf, v)[1])
            elif g == 5:
                key, meta = _map_entry(buf, v)
                stat_names[key] = next((_text(buf, s) for h, s in
                                        _fields(buf, *meta) if h == 2), "")
        if not name.startswith("/device:"):
            continue
        wanted = {k for k, n in stat_names.items() if n == NAME_STACK_STAT}
        stacks = out.setdefault(name, {})
        for meta in events:
            op, stack = "", ""
            for h, v in _fields(buf, *meta):
                if h == 2:
                    op = _text(buf, v)
                elif h == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in wanted:
                        if 5 in stat:
                            stack = _text(buf, stat[5])
                        elif 7 in stat:
                            stack = stat_names.get(stat[7], "")
            if stack:
                stacks[op] = stack
    return out


def op_scopes(stack: str) -> List[str]:
    """The names of ``SCOPES`` in one JAX name stack
    (``jit(_step)/ring_scatter/scatter:``)."""
    parts = stack.rstrip(":").split("/")
    return [s for s in SCOPES if s in parts]


def load(path: str) -> dict:
    """The plain form of one ``.xplane.pb`` (see the module docstring)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    stacks = name_stacks(path)
    spans, stages = [], []
    scopes: Dict[str, list] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            named = {op: op_scopes(s)
                     for op, s in stacks.get(plane.name, {}).items()}
            for line in plane.lines:
                if line.name != T.DEVICE_OPS_LINE:
                    continue
                found = scopes.setdefault(plane.name, [])
                for e in line.events:
                    for scope in named.get(e.name, ()):
                        found.append([scope, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in T.SPANS:
                        spans.append([e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)])
                    elif e.name.startswith(STAGE_PREFIX):
                        stats = {k: int(v) for k, v in e.stats
                                 if isinstance(v, int)}
                        stages.append([e.name, int(e.start_ns),
                                       int(e.start_ns + e.duration_ns),
                                       stats])
    for found in scopes.values():
        found.sort(key=lambda o: o[1])
    stages.sort(key=lambda s: (s[1], -s[2]))
    spans.sort(key=lambda s: s[1])
    window = list(T.window({"spans": spans})) if spans else None
    return {"window": window, "stages": stages, "device_scopes": scopes}


def _cached(path: str) -> dict:
    mtime = os.path.getmtime(path)
    hit = _loaded.get(path)
    if hit is None or hit[0] != mtime:
        hit = _loaded[path] = (mtime, load(path))
    return hit[1]


def of(ctx) -> Optional[dict]:
    """The stages and device scopes of the traced run ``ctx`` reads: those
    its plain trace holds, else those of the profile under the checkout
    whose harness window is the run's own; None where there are none."""
    if ctx.trace is None:
        return None
    if "stages" in ctx.trace:
        got = ctx.trace
    else:
        got = None
        paths = glob.glob(os.path.join(ROOT, TRACE_GLOB))
        for path in sorted(paths, key=os.path.getmtime, reverse=True):
            found = _cached(path)
            if found["window"] == [ctx.lo, ctx.hi]:
                got = found
                break
    if got is None or not got["stages"]:
        return None
    return got


# ---------------------------------------------------------------------------
# Reductions


def innermost(stages) -> List[list]:
    """The stages' union cut into disjoint ``[name, start, end]`` pieces,
    each named by the innermost stage that covers it.  Stages nest, as the
    spans of one thread do."""
    out: List[list] = []
    stack: List[Tuple[int, str]] = []    # (end, name), outermost first
    t = 0

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append([name, t, end])
                t = end

    for name, s, e, *_ in sorted(stages, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            out.append([stack[-1][1], t, s])
        t = s
        stack.append((e, name))
    close_until(float("inf"))
    return out


def clip(labelled, lo: int, hi: int) -> List[list]:
    """``[name, start, end, ...]`` intervals cut to ``[lo, hi)``."""
    return [[n, max(s, lo), min(e, hi)] for n, s, e, *_ in labelled
            if e > lo and s < hi]


def total_ns(stages, lo: int, hi: int) -> Dict[str, int]:
    """Summed duration of each stage name inside ``[lo, hi)``."""
    out: Dict[str, int] = {}
    for name, s, e in clip(stages, lo, hi):
        out[name] = out.get(name, 0) + e - s
    return out


def self_ns(stages, lo: int, hi: int) -> Dict[str, int]:
    """Self time of each stage name inside ``[lo, hi)``, summed: its spans'
    durations minus the part of them their child stages cover."""
    return total_ns(innermost(stages), lo, hi)


def count(stages, lo: int, hi: int) -> Dict[str, int]:
    """Number of spans of each stage name that start inside ``[lo, hi)``."""
    out: Dict[str, int] = {}
    for name, s, *_ in stages:
        if lo <= s < hi:
            out[name] = out.get(name, 0) + 1
    return out


def stat_sum(stages, name: str, stat: str, lo: int, hi: int
             ) -> Optional[int]:
    """Sum of one integer stat over the spans of ``name`` that start inside
    ``[lo, hi)`` and carry it; None where none does."""
    vals = [st[stat] for n, s, _, st in stages
            if n == name and lo <= s < hi and stat in st]
    return sum(vals) if vals else None


def overlap_by_label(intervals, labelled, out: Dict[str, int]
                     ) -> Dict[str, int]:
    """Adds to ``out[name]`` the part of ``intervals`` (sorted, disjoint)
    that each ``[name, start, end, ...]`` of ``labelled`` (sorted by start,
    disjoint) covers."""
    j = 0
    for name, s, e, *_ in labelled:
        while j < len(intervals) and intervals[j][1] <= s:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < e:
            o = min(e, intervals[k][1]) - max(s, intervals[k][0])
            if o > 0:
                out[name] = out.get(name, 0) + o
            k += 1
    return out


def minus(intervals, cut) -> List[Tuple[int, int]]:
    """The parts of ``intervals`` (sorted, disjoint) outside every
    ``[name, start, end, ...]`` of ``cut``."""
    out = []
    cut = T.union((s, e) for _, s, e, *_ in cut)
    j = 0
    for s, e in intervals:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(cut) and cut[k][0] < e:
            if cut[k][0] > t:
                out.append((t, cut[k][0]))
            t = max(t, cut[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def idle_by_stage(ops, stages, spans, lo: int, hi: int) -> Dict[str, int]:
    """Idle ns of one device inside ``[lo, hi)``, split by the innermost
    stage that covers it; idle time outside every stage goes to the harness
    span it fell in (``none`` outside those too), as
    :func:`bench.trace.idle_by_span` splits it."""
    idle = T.gaps(ops, lo, hi)
    pieces = innermost(stages)
    out = overlap_by_label(idle, pieces, {})
    overlap_by_label(minus(idle, pieces), spans, out)
    rest = sum(e - s for s, e in idle) - sum(out.values())
    if rest > 0:
        out["none"] = rest
    return out


def scope_ns(scoped, scope: str, lo: int, hi: int) -> int:
    """Nanoseconds inside ``[lo, hi)`` in which an op of ``scope`` ran on
    one device."""
    return sum(e - s for s, e in T.clip(T.union(
        (o[1], o[2]) for o in scoped if o[0] == scope), lo, hi))


# ---------------------------------------------------------------------------
# What the readers share


def verdict_steps(ctx) -> int:
    return sum(1 for s in ctx.trace["spans"] if s[0] == "ingest.verdict")


def stage_ms(ctx, name: str, own: bool = False) -> Optional[float]:
    """Time of stage ``name`` per verdict step in the traced window, in ms
    (its self time where ``own``); None where the run has no such stage."""
    got = of(ctx)
    steps = verdict_steps(ctx) if got is not None else 0
    if not steps:
        return None
    by = (self_ns if own else total_ns)(got["stages"], ctx.lo, ctx.hi)
    if name not in by:
        return None
    return by[name] / steps / 1e6


def kb_per_step(ctx, name: str, stat: str) -> Optional[float]:
    """An integer byte stat of stage ``name``, summed over the traced window,
    per verdict step, in KB of 1024 bytes."""
    got = of(ctx)
    steps = verdict_steps(ctx) if got is not None else 0
    if not steps:
        return None
    total = stat_sum(got["stages"], name, stat, ctx.lo, ctx.hi)
    return None if total is None else total / steps / 1024


def report(trace_dir: str) -> dict:
    """The stage split of one traced run, per verdict step, and the busiest
    device's idle time by stage."""
    path = T.find_xplane(trace_dir)
    plain = T.load(path)
    got = load(path)
    lo, hi = T.window(plain)
    steps = sum(1 for s in plain["spans"] if s[0] == "ingest.verdict")
    device, busy = T.busiest(plain, lo, hi)
    stages = got["stages"]
    verdict_ns = sum(e - s for n, s, e in plain["spans"]
                     if n == "ingest.verdict")
    total, own = total_ns(stages, lo, hi), self_ns(stages, lo, hi)
    step_ms = {n: total.get(n, 0) / steps / 1e6 for n in sorted(total)}
    covered = own.get("serve.operands", 0) + sum(
        total.get(n, 0) for n in STEP_STAGES[1:])
    idle = idle_by_stage(plain["devices"].get(device, []), stages,
                         plain["spans"], lo, hi)
    return {
        "verdict_steps": steps, "device": device,
        "ingest_verdict_ms": verdict_ns / steps / 1e6,
        "device_busy_ms_per_step": busy / steps / 1e6,
        "stage_ms_per_step": step_ms,
        "self_ms_per_step": {n: v / steps / 1e6
                             for n, v in sorted(own.items())},
        "stage_counts": count(stages, lo, hi),
        "coverage": covered / verdict_ns if verdict_ns else None,
        "scope_ms_per_step": {
            s: scope_ns(got["device_scopes"].get(device, []), s, lo, hi)
            / steps / 1e6 for s in SCOPES},
        "idle_by_stage_s": sorted(([k, v / 1e9] for k, v in idle.items()),
                                  key=lambda kv: -kv[1]),
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
