"""Reduction of a profiler trace of the window to device busy time, idle
gaps by what the host was doing, top device ops, and step times.

The trace is jax's ``.xplane.pb``, read with ``jax.profiler.ProfileData``:

- device planes ``/device:TPU:<n>``: their ``XLA Ops`` line holds every
  operation the chip ran, their ``XLA Modules`` line every program run;
- host planes hold the benchmark's annotations (``harness.py``): one
  ``window`` around the measured window, ``call`` around each call of the
  plane, ``prompt`` around the harness's work between calls, ``prefill``,
  ``decode.<rung>`` and ``catchup`` around each program it dispatches.

Busy time is the union of the op intervals inside the window, averaged
over the chips.  A gap in it is charged to the innermost annotation open
at its middle on the host; a gap inside a ``call`` after its last decode
dispatch is ``handoff`` (logits to the host, stacking).  A program run
on the device is matched to its dispatch by order, among runs of the same
kind; since no run starts before its dispatch, the latest dispatch-to-run
lead that would be negative gives the offset between the host's clock and
the device's, and device times are moved by it.  Device ops are named
``<program>:<op>``, where the program is ``prefill.<rung>`` or
``decode.<rung>`` for the plane's own, and timed exclusive of the ops
they contain.
"""

from __future__ import annotations

import bisect
import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DISPATCH = ("prefill", "decode.accurate", "decode.fast", "catchup")
ANNOTATIONS = ("window", "call", "prompt") + DISPATCH
TOP = 10


def start(trace_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the host's own Python is not traced
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop_and_read(trace_dir: Path, modules: Dict[str, str], steps, *,
                  n_devices: int, keep: bool = False) -> Optional["Reading"]:
    """Stop the profiler, reduce its trace, and remove it unless
    ``keep``."""
    import jax

    jax.profiler.stop_trace()
    try:
        return read(newest_xplane(trace_dir), modules, steps,
                    n_devices=n_devices)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@dataclass
class Span:
    name: str
    start: int            # ns
    end: int              # ns


def _union(spans: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(spans, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


@dataclass
class Reading:
    window_s: float
    busy_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    timed: List[Tuple[object, float]] = field(default_factory=list)

    def step_times(self, kind: str, rung: str) -> List[float]:
        """Device seconds of each ``kind`` program run for ``rung``
        (served and catch-up steps alike); empty where the runs could not
        be matched to the dispatches."""
        return [t for s, t in self.timed if s.kind == kind and s.rung == rung]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.device_ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps]}


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def _self_times(ops):
    """Exclusive time of each op event: its span less its children's (a
    ``while`` op's span holds its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def read(path: Path, modules: Dict[str, str], steps, *,
         n_devices: int) -> Optional[Reading]:
    """Reduce the trace at ``path``; ``None`` where it holds no device
    plane (a CPU run).  ``modules`` maps a program's module name to the
    kind of step it runs (``prefill``, ``decode``); ``steps`` are the
    dispatches the harness recorded, in order."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    host: List[Span] = []
    devices = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.append((int(m.group(1)), plane))
            continue
        for line in plane.lines:
            host += [Span(n, s, e) for n, s, e in _events(line)
                     if n in ANNOTATIONS]
    if not devices:
        return None
    host.sort(key=lambda h: h.start)
    windows = [h for h in host if h.name == "window"]
    if not windows:
        raise ValueError(f"{path.name}: no window annotation in the trace")
    lo, hi = windows[0].start, windows[0].end
    devices = [p for _, p in sorted(devices, key=lambda x: x[0])][:n_devices]
    lines = [{ln.name: ln for ln in p.lines} for p in devices]

    # program runs on the first chip, matched in order to the dispatches
    runs = sorted(_events(lines[0][MODULES_LINE])
                  if MODULES_LINE in lines[0] else [], key=lambda r: r[1])
    sent = [h for h in host if h.name in DISPATCH]
    label = {}                                   # run start -> program label
    timed = []
    shift = 0
    for kind in ("prefill", "decode"):
        mine = [r for r in runs if modules.get(re.sub(r"\(\d+\)$", "", r[0]))
                == kind]
        want = [(s, h) for s, h in zip(steps, sent) if s.kind == kind]
        if len(mine) != len(want) or len(sent) != len(steps):
            continue
        for (_, s, e), (step, h) in zip(mine, want):
            label[s] = f"{kind}.{step.rung}"
            timed.append((step, (e - s) * 1e-9))
            shift = max(shift, h.start - s)      # no run starts before its
                                                 # dispatch: the clocks' offset
    starts = [r[1] for r in runs]

    busy_ns, own_ns = [], {}
    first_busy = None
    for i, ln in enumerate(lines):
        ops = _events(ln[OPS_LINE]) if OPS_LINE in ln else []
        ops = [(n, s + shift, e + shift) for n, s, e in ops]
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        if i:
            continue
        first_busy = busy
        for (n, s, e), t in zip(ops, _self_times(ops)):
            if e <= lo or s >= hi:
                continue
            k = bisect.bisect_right(starts, s - shift) - 1
            run = runs[k] if k >= 0 and s - shift < runs[k][2] else None
            prog = (label.get(run[1]) or re.sub(r"\(\d+\)$", "", run[0])
                    if run else "outside a program")
            name = f"{prog}:{n.split(' = ', 1)[0].lstrip('%')}"
            own_ns[name] = own_ns.get(name, 0) + t

    device_ops = sorted(((n, t * 1e-9) for n, t in own_ns.items()),
                        key=lambda x: -x[1])
    return Reading(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy_ns) * 1e-9 / len(devices),
                   device_ops=device_ops[:TOP],
                   idle_gaps=_gaps_by_host(first_busy, host, lo, hi)[:TOP],
                   timed=timed)


def _gaps_by_host(busy, host: List[Span], lo: int, hi: int):
    """Idle time of one chip, summed by the host annotation it fell in
    (a sweep over the gaps in time order, with the annotations open)."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted((h for h in host if h.name != "window"),
                   key=lambda h: h.start)
    last_decode: Dict[int, int] = {}        # call start -> last dispatch end
    call = None
    for h in spans:
        if h.name == "call":
            call = h
            last_decode[h.start] = h.start
        elif call and h.name.startswith(("decode.", "catchup")) \
                and call.start <= h.start < call.end:
            last_decode[call.start] = max(last_decode[call.start], h.end)
    by: Dict[str, int] = {}
    active: List[Span] = []
    k = 0
    for s, e in gaps:
        mid = (s + e) // 2
        while k < len(spans) and spans[k].start <= mid:
            active.append(spans[k])
            k += 1
        active = [h for h in active if h.end > mid]
        name = "untraced host"
        if active:
            inner = min(active, key=lambda h: h.end - h.start)
            name = inner.name
            if name == "call" and mid >= last_decode[inner.start]:
                name = "handoff"
        by[name] = by.get(name, 0) + (e - s)
    return sorted(((n, t * 1e-9) for n, t in by.items()), key=lambda x: -x[1])
