"""Reduce a JAX profiler trace (``.xplane.pb``) to device events.

``jax.profiler.ProfileData`` reads the file. A device plane is one named
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per executed HLO
instruction, named by the instruction's text (``%fusion.133 = f32[...]
fusion(...)``). Control flow nests: a ``while`` event spans its body's
events, so containers are dropped and the rest do not overlap. The trace
does not say which JAX operation an instruction came from; the compiled
program's text does (``metadata={op_name=...}``), and :func:`op_names`
reads it, so each event carries its ``op`` (e.g. ``.../while/body/gather``).
The reducer keeps those events, the busy union per device, and the
benchmark's own host spans (``chipbench.*``), all clipped to the
``chipbench.window`` span. Which events belong to which layer is for each
metric reader to say. On the CPU backend (tests, rehearsals) the ops are
the ``hlo_op`` events of ``/host:CPU``, read as device 0.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import typing

SPAN_PREFIX = "chipbench."
WINDOW = SPAN_PREFIX + "window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# lines of a TPU plane that hold one event per executed HLO op
_OP_LINES = ("XLA Ops",)
# control-flow ops whose events span their whole body
_CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


# the largest share of a device's op time that may have no JAX op behind
# it; above it the layers' rules no longer see the program (``op_names``
# read another program than the one traced, or the compiler has moved
# work into ops of its own), and the traced run fails
MAX_UNATTRIBUTED = 0.02

_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def op_names(hlo_text: str) -> dict[str, str]:
    """``instruction -> op_name`` from a compiled program's HLO text."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


class Event(typing.NamedTuple):
    device: int
    name: str  # the HLO instruction, e.g. "fusion.12", "all-reduce.3"
    start_ns: float
    dur_ns: float
    op: str = ""  # the JAX op it came from (op_name), "" where unknown


@dataclasses.dataclass
class Trace:
    events: list[Event]
    busy_ns: dict[int, float]
    window_ns: float
    window_start_ns: float
    host_spans: list[tuple[str, float, float]]  # (name, start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def busy_s_mean(self) -> float:
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def time_ns(self, match) -> dict[int, float]:
        """Device time of the events ``match(event)`` accepts, per device
        (every device of the trace appears, with 0 where none match)."""
        out = {d: 0.0 for d in self.busy_ns}
        for ev in self.events:
            if match(ev):
                out[ev.device] += ev.dur_ns
        return out

    def unattributed_share(self) -> float:
        """The largest share, over the devices, of a device's op time in
        ops with no JAX op behind them."""
        ops = self.time_ns(lambda ev: True)
        none = self.time_ns(lambda ev: not ev.op)
        return max(none[d] / ops[d] if ops[d] else 0.0 for d in ops)

    def op_totals(self) -> collections.Counter:
        """Device time per instruction, named with the op it came from."""
        tot = collections.Counter()
        for ev in self.events:
            op = ev.op.removeprefix("jit(solve)/")
            tot[f"{ev.name} {op}".strip()] += ev.dur_ns
        return tot

    def idle_gaps(self, device: int) -> list[tuple[float, float]]:
        """(start_ns, end_ns) of the window's stretches with no op running
        on ``device``."""
        lo, hi = self.window_start_ns, self.window_start_ns + self.window_ns
        ivs = _union((ev.start_ns, ev.start_ns + ev.dur_ns)
                     for ev in self.events if ev.device == device)
        gaps, t = [], lo
        for a, b in ivs:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_at(self, t0: float, t1: float) -> str:
        """The innermost benchmark span that covers most of [t0, t1]."""
        best, best_cover = "host (no span)", 0.0
        for name, a, b in self.host_spans:
            if name == WINDOW:
                continue
            cover = min(b, t1) - max(a, t0)
            if cover > best_cover or (cover == best_cover > 0
                                      and name > best):
                best, best_cover = name, cover
        return best

    def breakdown(self, top: int = 10) -> dict:
        ops = [[name, ns / 1e9] for name, ns in self.op_totals().most_common(top)]
        busiest = max(self.busy_ns, key=self.busy_ns.get)
        gaps = sorted(self.idle_gaps(busiest), key=lambda g: g[0] - g[1])[:top]
        idle = [[self.host_at(a, b), (b - a) / 1e9] for a, b in gaps]
        return {"device_ops": ops, "idle_gaps": idle}


class UnattributedError(ValueError):
    """Too much of the traced device time has no JAX op behind it."""


def check_attributed(tr: Trace) -> None:
    share = tr.unattributed_share()
    if share > MAX_UNATTRIBUTED:
        raise UnattributedError(
            f"{100 * share:.2f}% of a device's op time has no JAX op behind "
            f"it (limit {100 * MAX_UNATTRIBUTED:g}%): the layer rules of "
            "chipbench/metrics no longer see the program")


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _instruction(ev, host_cpu: bool) -> str | None:
    """The HLO instruction an op event ran, or None for other events."""
    if host_cpu:
        return next((str(v) for k, v in ev.stats if k == "hlo_op"), None)
    name = ev.name
    if not name.startswith("%"):
        return None
    return name[1:name.find(" = ")] if " = " in name else name[1:]


def _device_of(plane_name: str, host_cpu: bool) -> int | None:
    m = _DEVICE.match(plane_name)
    if m:
        return int(m.group(1))
    if host_cpu and plane_name == "/host:CPU":
        return 0
    return None


def reduce(path: str, ops: dict[str, str] | None = None,
           host_cpu: bool = False) -> Trace:
    """Reduce one ``.xplane.pb``; ``ops`` maps instructions to op names
    (:func:`op_names`). ``host_cpu`` reads the CPU backend's ops as device
    0's, for rehearsals and tests without a chip."""
    import jax

    ops = ops or {}
    pd = jax.profiler.ProfileData.from_file(path)
    raw, spans = [], []
    for plane in pd.planes:
        dev = _device_of(plane.name, host_cpu)
        for line in plane.lines:
            ops_line = dev is not None and (host_cpu or line.name in _OP_LINES)
            if plane.name != "/host:CPU" and not ops_line:
                continue
            for ev in line.events:
                if plane.name == "/host:CPU" and ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
                if not ops_line:
                    continue
                inst = _instruction(ev, host_cpu)
                if inst is None or _CONTAINERS.match(inst):
                    continue
                raw.append(Event(dev, inst, ev.start_ns, ev.duration_ns,
                                 ops.get(inst, "")))
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW} span, found {len(windows)}")
    _, lo, hi = windows[0]
    events = []
    for ev in raw:
        a, b = max(ev.start_ns, lo), min(ev.start_ns + ev.dur_ns, hi)
        if b <= a:
            continue
        clipped = a != ev.start_ns or b - a != ev.dur_ns
        events.append(ev._replace(start_ns=a, dur_ns=b - a) if clipped else ev)
    busy = {}
    for d in sorted({ev.device for ev in raw}):
        busy[d] = sum(b - a for a, b in _union(
            (ev.start_ns, ev.start_ns + ev.dur_ns)
            for ev in events if ev.device == d))
    return Trace(events=events, busy_ns=busy, window_ns=hi - lo,
                 window_start_ns=lo, host_spans=spans)


def reduce_dir(log_dir: str, n_devices: int, ops: dict[str, str] | None = None,
               host_cpu: bool = False) -> Trace:
    """Reduce the one trace a ``jax.profiler`` session wrote under
    ``log_dir``; checks that it saw ``n_devices`` devices."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{log_dir}: expected one .xplane.pb, found {len(paths)}")
    tr = reduce(paths[0], ops, host_cpu=host_cpu)
    if len(tr.busy_ns) != n_devices:
        raise ValueError(f"trace has ops on {len(tr.busy_ns)} devices, "
                         f"the cell uses {n_devices}")
    return tr
