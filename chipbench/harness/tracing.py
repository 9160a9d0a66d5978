"""From a JAX profiler trace to the device numbers of one traced call.

:func:`events_from_profile` flattens an ``.xplane.pb`` into plain event
records ``(plane, line, name, start_ns, dur_ns)``; :func:`reduce` works
on those records alone, so it is checked on a small recorded sample
(``tests/data/``) without a chip.

Conventions of a TPU trace read here: each chip is a plane named
``/device:TPU:<n>``; its line ``XLA Ops`` holds the operations that
ran, its line ``XLA Modules`` one event per executable run.  Host
threads are planes named ``/host:...``; the benchmark's own spans are
events there named ``chipbench.<span>``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
SPAN_PREFIX = "chipbench."
CALL_SPAN = "chipbench.call"
#: idle gaps shorter than this, between back-to-back operations, are
#: left out of the list of the longest gaps
MIN_GAP_NS = 1000

Event = Tuple[str, str, str, int, int]  # plane, line, name, start_ns, dur_ns


def events_from_profile(log_dir: str) -> List[Event]:
    """The device operations and executables, and the benchmark's host
    spans, of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        if not plane.name.startswith((DEVICE_PREFIX, HOST_PREFIX)):
            continue
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _host_span_at(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The innermost benchmark span (other than the call) covering t."""
    best: Optional[Tuple[int, str]] = None
    for name, a, b in spans:
        if name == CALL_SPAN or not (a <= t < b):
            continue
        if best is None or b - a < best[0]:
            best = (b - a, name[len(SPAN_PREFIX):])
    return best[1] if best else "host"


def reduce(events: Sequence[Event], top: int = 10) -> Optional[Dict[str, object]]:
    """Device numbers of the traced call, or None where the trace holds
    no call span or no device operation inside it.

    Returns ``window_s`` (the call's span), ``busy_s`` (union of device
    operations inside it, averaged over chips), ``modules``
    (``{name: [runs, seconds]}`` of executables inside it), and
    ``device_ops`` / ``idle_gaps``: the ``top`` operations by total
    time (an HLO instruction's name, its text cut at `` = ``; a loop's
    own op spans the ops of its body), and the ``top`` longest idle
    gaps named by the host span they fall in.
    """
    calls = [(s, s + d) for p, _l, n, s, d in events if n == CALL_SPAN and p.startswith(HOST_PREFIX)]
    if not calls:
        return None
    w0, w1 = min(a for a, _b in calls), max(b for _a, b in calls)
    host = [(n, s, s + d) for p, _l, n, s, d in events
            if p.startswith(HOST_PREFIX) and n.startswith(SPAN_PREFIX)]
    ops_by_chip: Dict[str, List[Tuple[int, int]]] = {}
    op_time: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for plane, line, name, s, d in events:
        if not plane.startswith(DEVICE_PREFIX):
            continue
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if line == OPS_LINE:
            ops_by_chip.setdefault(plane, []).append((a, b))
            op = name.split(" = ", 1)[0]
            op_time[op] = op_time.get(op, 0.0) + (b - a) * 1e-9
        elif line == MODULES_LINE:
            rec = modules.setdefault(name, [0, 0.0])
            rec[0] += 1
            rec[1] += (b - a) * 1e-9
    if not ops_by_chip:
        return None
    busy_ns = []
    gaps: List[Tuple[float, str]] = []
    for plane, ivs in sorted(ops_by_chip.items()):
        merged = _union(ivs)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_NS:
                gaps.append(((b - a) * 1e-9, _host_span_at(host, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "chips": len(busy_ns),
        "modules": modules,
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for s, n in gaps[:top]],
    }
