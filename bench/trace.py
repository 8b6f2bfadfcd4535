"""From a profiler trace to the numbers the per-layer metrics read.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote into a small
plain form, which the tests keep as a recorded fixture:

    {"devices": {"<plane name>": [[name, start_ns, end_ns, category], ...]},
     "spans": [[name, start_ns, end_ns], ...]}

``devices`` holds the operations that ran on each device: the plane's
``XLA Ops`` line, the ops of the device's compute stream, each named by its
HLO instruction and opcode.  (The ``Async XLA Ops`` line, copies in flight
beside them, is left out.)  ``spans`` holds the harness's own host
annotations.  Both are on the profiler's one clock.  The reductions below
work on that form only.

A kernel is matched by category, not by name: the profiler names a device
op by its HLO text, and a Mosaic (Pallas TPU) kernel is the custom call
whose target is ``tpu_custom_call``, whatever the ``pallas_call`` is
called.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

# Host annotations the harness writes around its own calls.
SPANS = ("ingest.verdict", "ingest.nonverdict", "harness")
DEVICE_OPS_LINE = "XLA Ops"
KERNEL_CATEGORY = "tpu_custom_call"
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def short_op(text: str) -> Tuple[str, str]:
    """(short name, category) of a device op from its HLO text: the
    instruction's name and opcode, and the category, which is the opcode
    or, for a Mosaic kernel, ``tpu_custom_call``."""
    head, _, rest = text.partition(" = ")
    name = head.lstrip("%")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    if 'custom_call_target="tpu_custom_call"' in rest:
        return f"{name} {opcode} tpu_custom_call", KERNEL_CATEGORY
    return f"{name} {opcode}".strip(), opcode


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """The plain form of one ``.xplane.pb`` (see the module docstring)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for e in line.events:
                    name, cat = short_op(e.name)
                    ops.append([name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns), cat])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append([e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)])
    for ops in devices.values():
        ops.sort(key=lambda o: o[1])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Nanoseconds inside ``[lo, hi)`` in which some op ran."""
    return sum(e - s for s, e in clip(union((o[1], o[2]) for o in ops),
                                      lo, hi))


def window(trace: dict) -> Tuple[int, int]:
    """The traced window: from the first harness span to the end of the
    last."""
    spans = trace["spans"]
    if not spans:
        raise ValueError("the trace holds no harness span")
    return spans[0][1], max(s[2] for s in spans)


def busiest(trace: dict, lo: int, hi: int) -> Tuple[str, int]:
    """(device plane, busy ns) of the busiest device in the window."""
    best = ("", 0)
    for name, ops in trace["devices"].items():
        b = busy_ns(ops, lo, hi)
        if b > best[1] or not best[0]:
            best = (name, b)
    return best


def gaps(ops, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Idle intervals of one device inside ``[lo, hi)``."""
    out, t = [], lo
    for s, e in clip(union((o[1], o[2]) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(gap: Tuple[int, int], spans) -> str:
    """The harness span that covers most of ``gap`` ("none" if no span
    does)."""
    best, most = "none", 0
    for name, s, e in spans:
        if s >= gap[1]:
            break
        o = min(e, gap[1]) - max(s, gap[0])
        if o > most:
            best, most = name, o
    return best


def idle_by_span(ops, spans, lo: int, hi: int) -> Dict[str, int]:
    """Idle ns of one device, split by the harness span it fell in."""
    out: Dict[str, int] = {}
    idle = gaps(ops, lo, hi)
    ends = [g[1] for g in idle]
    for name, s, e in spans:
        i = bisect.bisect_right(ends, s)
        while i < len(idle) and idle[i][0] < e:
            o = min(e, idle[i][1]) - max(s, idle[i][0])
            if o > 0:
                out[name] = out.get(name, 0) + o
            i += 1
    covered = sum(out.values())
    total = sum(e - s for s, e in idle)
    if total > covered:
        out["none"] = total - covered
    return out


def clip_ops(ops, lo: int, hi: int) -> list:
    """One device's ops cut to ``[lo, hi)``."""
    return [[n, max(s, lo), min(e, hi), c] for n, s, e, c in ops
            if e > lo and s < hi]


def kernel_ops(ops) -> list:
    """The Mosaic kernel calls among one device's ops."""
    return [o for o in ops if o[3] == KERNEL_CATEGORY]


def top_ops(trace: dict, lo: int, hi: int, n: int = 10) -> List[list]:
    """The ``n`` op names that took most device time, summed over the
    devices and the window, in seconds."""
    total: Dict[str, int] = {}
    for ops in trace["devices"].values():
        for name, s, e, _ in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[name] = total.get(name, 0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def top_gaps(trace: dict, device: str, lo: int, hi: int,
             n: int = 10) -> List[list]:
    """Idle time of ``device`` by the harness span it fell in, then the
    longest single gaps, at most ``n`` entries in all, in seconds."""
    ops = trace["devices"].get(device, [])
    spans = trace["spans"]
    by_span = sorted(idle_by_span(ops, spans, lo, hi).items(),
                     key=lambda kv: -kv[1])
    out = [[f"all idle in {k}", v / 1e9] for k, v in by_span]
    longest = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])
    for g in longest[:max(0, n - len(out))]:
        out.append([f"gap in {label(g, spans)}", (g[1] - g[0]) / 1e9])
    return out[:n]
