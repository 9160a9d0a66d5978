"""The program's own spans, counters and named scopes, for the metrics
that read them.

The system's registry (``repro.obs.metrics``) times the SoA path's
layers as phases and counts its attempts, rounds and lanes.  While the
registry is enabled, every phase is also a profiler annotation named
``repro.<phase>``, and the round loop's device operations carry the
``jax.named_scope`` of their phase in their name stack whether it is on
or not.  This module reads both for the per-layer metrics:

* in a ``--trace 1`` run of ``run.py`` it enables the registry for the
  whole run (``--trace 0`` runs measure the program with it off);
* it reads the traced call's profile for the ``repro.*`` spans, gives
  each operation of the round loop its name stack (a TPU trace names an
  operation by its HLO instruction only; the compiled loop's metadata
  maps the instruction to its op_name), and reduces them
  (:func:`reduce`): the device seconds of the loop's leaf operations by
  body scope, the idle gaps named by the innermost span of either
  prefix, and the longest operations named with their scope path;
* it takes a snapshot of the registry when the window opens, so that
  :meth:`Reader.window` gives the window's phase totals and counters.

It joins the harness at one point, ``tracing.events_from_profile``,
which every traced call goes through and whose result it leaves as it
was (:func:`install`).  Where the program has no such spans or scopes,
every reading here is empty and the metrics that use it are absent.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import tracing
from .bench import log

BENCH_DIR = Path(__file__).resolve().parents[1]
RUN_PY = BENCH_DIR / "run.py"
#: the round loop's executable (``soa_kernels.round_loop`` jits ``run``)
LOOP_MODULE = "jit_run"
#: the round loop body's top-level named scopes (``soa_kernels._build_loop``)
SCOPES = ("window", "step", "policy", "apply")
UNSCOPED = "unscoped"
SPAN_PREFIXES = (tracing.SPAN_PREFIX, "repro.")
#: name-stack components that JAX adds for calls and control flow
_STRUCTURE = re.compile(r"while|body|cond|closed_call|branch_\d+_fun")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%[\w.\-]+) = ([^\n]*)$", re.M)

#: plane, line, name, start_ns, dur_ns, name stack ("" where none)
Event = Tuple[str, str, str, int, int, str]


def hlo_op_names(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of an optimised HLO module's text (the
    op_name of its metadata, "" where it has none)."""
    out = {}
    for m in _INSTRUCTION.finditer(text):
        op = _OP_NAME.search(m.group(2))
        out[m.group(1)] = op.group(1) if op else ""
    return out


def compiled_loop_op_names(seen: Set[str]) -> Dict[str, str]:
    """The op_names of the round loop that ran, where the trace names
    its operations by instruction only (as a TPU trace does).

    The program keeps its compiled round loops
    (``soa_kernels._LOOP_CACHE``); each is lowered again for the shapes
    it was built for and compiled (found in JAX's compilation cache),
    newest first, and the first whose instructions include every
    operation the trace saw is the one that ran.  Empty where there is
    no such loop."""
    import jax
    import jax.numpy as jnp

    try:
        from repro.core.sim import soa_kernels
    except ImportError:
        return {}
    for key, loop in reversed(list(getattr(soa_kernels, "_LOOP_CACHE", {}).items())):
        R, N, A1 = key[-1]
        args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in ((R, N), (R, N), (R, A1))]
        names = hlo_op_names(loop.lower(*args).compile().as_text())
        if seen <= names.keys():
            return names
    return {}


def events_from_profile(log_dir: str) -> List[Event]:
    """Device operations and executables, and the host spans of either
    prefix, of the newest ``.xplane.pb`` under ``log_dir``; each
    operation of the round loop with its name stack."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])  # its planes live as long as it
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith(tracing.DEVICE_PREFIX)
        if not device and not plane.name.startswith(tracing.HOST_PREFIX):
            continue
        for line in plane.lines:
            if device and line.name not in (tracing.OPS_LINE, tracing.MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIXES):
                    continue
                out.append((plane.name, line.name, ev.name.split(" = ", 1)[0],
                            int(ev.start_ns), int(ev.duration_ns), ""))
    return with_name_stacks(out, compiled_loop_op_names)


def with_name_stacks(events: Sequence[Event], op_names) -> List[Event]:
    """``events`` with the name stack of each operation that ran inside
    a round-loop executable, from ``op_names(seen)``: the op_names of
    the loop that ran, given the instructions the trace saw."""
    loops = [(s, s + d) for p, line, name, s, d, _x in events
             if p.startswith(tracing.DEVICE_PREFIX) and line == tracing.MODULES_LINE
             and LOOP_MODULE in name]

    def in_loop(p, line, s):
        return (p.startswith(tracing.DEVICE_PREFIX) and line == tracing.OPS_LINE
                and any(a <= s < b for a, b in loops))

    seen = {name for p, line, name, s, _d, _x in events if in_loop(p, line, s)}
    names = op_names(seen) if seen else {}
    return [(p, line, name, s, d, names.get(name, "") if in_loop(p, line, s) else x)
            for p, line, name, s, d, x in events]


def scope_path(stack: str) -> List[str]:
    """The named scopes of a name stack from the first body scope on,
    without JAX's own components (control flow, calls, the names of
    inner functions) and the primitive's name at its end; empty where
    the stack holds no body scope."""
    parts = stack.split("/")[:-1]
    for i, part in enumerate(parts):
        if part in SCOPES:
            return [p for p in parts[i:]
                    if p.isidentifier() and not _STRUCTURE.fullmatch(p)]
    return []


def _leaves(ops: Sequence[Tuple[int, int, str, str]]) -> List[Tuple[int, int, str, str]]:
    """The operations of one line that contain no other operation of it
    (a loop's or a branch's own event spans the events of its body)."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    out = []
    for i, (a, b, name, stack) in enumerate(ops):
        j = i + 1
        while j < len(ops) and ops[j][0] < b and ops[j][1] > b:
            j += 1  # starts inside, ends after: overlaps, holds nothing
        if j == len(ops) or ops[j][0] >= b:
            out.append((a, b, name, stack))
    return out


def _innermost(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The innermost host span of either prefix (other than the call)
    covering t, without its prefix."""
    best: Optional[Tuple[int, str]] = None
    for name, a, b in spans:
        if name == tracing.CALL_SPAN or not (a <= t < b):
            continue
        if best is None or b - a < best[0]:
            best = (b - a, name.split(".", 1)[1])
    return best[1] if best else "host"


def reduce(events: Sequence[Event], top: int = 10) -> Optional[Dict[str, object]]:
    """The program's side of one traced call, or None where the trace
    holds no call span or no device operation inside it.

    ``scopes`` holds the device seconds of the round loop's leaf
    operations (those inside a ``LOOP_MODULE`` executable run) by the
    top-level body scope of their name stack, with ``UNSCOPED`` for the
    rest; ``leaf_s`` is their sum and ``loop_s`` the executable runs'
    own time.  ``idle_gaps`` are the ``top`` longest idle gaps, named by
    the innermost span of either prefix, and ``device_ops`` the ``top``
    operations by time, each named with its scope path.
    """
    calls = [(s, s + d) for p, _l, n, s, d, _x in events
             if n == tracing.CALL_SPAN and p.startswith(tracing.HOST_PREFIX)]
    if not calls:
        return None
    w0, w1 = min(a for a, _b in calls), max(b for _a, b in calls)
    host = [(n, s, s + d) for p, _l, n, s, d, _x in events
            if p.startswith(tracing.HOST_PREFIX) and n.startswith(SPAN_PREFIXES)]
    ops_by_line: Dict[Tuple[str, str], List[Tuple[int, int, str, str]]] = {}
    loops: List[Tuple[int, int]] = []
    for plane, line, name, s, d, stack in events:
        if not plane.startswith(tracing.DEVICE_PREFIX):
            continue
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if line == tracing.OPS_LINE:
            ops_by_line.setdefault((plane, line), []).append(
                (a, b, name.split(" = ", 1)[0], stack))
        elif line == tracing.MODULES_LINE and LOOP_MODULE in name:
            loops.append((a, b))
    if not ops_by_line:
        return None
    scopes = {name: 0.0 for name in SCOPES + (UNSCOPED,)}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for _line, ops in sorted(ops_by_line.items()):
        for a, b, name, stack in ops:
            path = scope_path(stack)
            label = "/".join(path + [name])
            op_time[label] = op_time.get(label, 0.0) + (b - a) * 1e-9
        for a, b, name, stack in _leaves(ops):
            if any(la <= a and b <= lb for la, lb in loops):
                path = scope_path(stack)
                scopes[path[0] if path else UNSCOPED] += (b - a) * 1e-9
        merged = tracing._union((a, b) for a, b, _n, _s in ops)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= tracing.MIN_GAP_NS:
                gaps.append(((b - a) * 1e-9, _innermost(host, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "loop_s": sum(b - a for a, b in loops) * 1e-9,
        "leaf_s": sum(scopes.values()),
        "scopes": scopes,
        "device_ops": [[n, s] for n, s in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s] for s, n in gaps[:top]],
    }


def traced_run() -> bool:
    """Whether this process is a ``--trace 1`` run of ``run.py``."""
    main = sys.modules.get("__main__")
    path = getattr(main, "__file__", None)
    if path is None or Path(path).resolve() != RUN_PY:
        return False
    return bool(main.parse().trace)


def _snapshot() -> Dict[str, dict]:
    from repro.obs import metrics

    return metrics.snapshot()


class Reader:
    """What one run reads from the program: the reduction of its traced
    call, and the registry's snapshot when its window opened."""

    def __init__(self) -> None:
        self.trace: Optional[Dict[str, object]] = None
        self.at_window: Optional[Dict[str, dict]] = None
        self._logged = False

    def read_trace(self, log_dir: str) -> None:
        """Reduce the traced call's profile; the window opens next."""
        self.trace = reduce(events_from_profile(log_dir))
        self.at_window = _snapshot()
        self._logged = False
        log(f"[program] set-up and traced call: {self.at_window['counters']}")
        if self.trace is not None:
            log(f"[program] traced call: scopes {self.trace['scopes']}, "
                f"idle gaps {self.trace['idle_gaps']}, "
                f"device ops {self.trace['device_ops']}")

    def window(self) -> Optional[Dict[str, dict]]:
        """``{"counters": {name: value}, "phases": {name: (n, seconds)}}``
        recorded since the window opened, or None where it never did."""
        if self.at_window is None:
            return None
        now, then = _snapshot(), self.at_window
        counters = {k: v - then["counters"].get(k, 0)
                    for k, v in now["counters"].items()
                    if v != then["counters"].get(k, 0)}
        phases = {}
        for k, p in now["phases"].items():
            q = then["phases"].get(k, {"n": 0, "total_s": 0.0})
            if p["n"] > q["n"]:
                phases[k] = (p["n"] - q["n"], p["total_s"] - q["total_s"])
        if not self._logged:
            self._logged = True
            log(f"[program] window: counters {counters}, phases {phases}")
        return {"counters": counters, "phases": phases}

    def scope_us_per_round(self, ctx: dict, scope: str) -> Optional[float]:
        """Device microseconds per round in one body scope of the round
        loop, in the traced call that ``ctx`` reports."""
        red, rounds = self.trace, ctx["traced_rounds"]
        if ctx["trace"] is None or red is None or not rounds:
            return None
        if abs(red["window_s"] - ctx["trace"]["window_s"]) > 1e-9:
            return None  # not the call the harness reduced
        secs = red["scopes"].get(scope, 0.0)
        return secs * 1e6 / rounds if secs > 0 else None

    def ms_per_drive_s(self, ctx: dict, phases: Sequence[str]) -> Optional[float]:
        """Host milliseconds per simulated drive-second in ``phases``,
        summed over the window."""
        if ctx["window"]["drive_s"] <= 0:
            return None
        win = self.window()
        if win is None or not all(p in win["phases"] for p in phases):
            return None
        secs = sum(win["phases"][p][1] for p in phases)
        return secs * 1e3 / ctx["window"]["drive_s"]


#: the one reader of this process: the harness has one run per process
READER = Reader()


def install() -> None:
    """Join the harness (once per process): read each traced call's
    profile for the program's side, and enable the program's registry in
    a ``--trace 1`` run of ``run.py``."""
    if hasattr(tracing.events_from_profile, "harness"):
        return
    harness_events = tracing.events_from_profile

    def events_from_profile(log_dir):
        READER.read_trace(log_dir)
        return harness_events(log_dir)

    events_from_profile.harness = harness_events
    tracing.events_from_profile = events_from_profile
    if traced_run():
        from repro.obs import metrics

        metrics.enable()
