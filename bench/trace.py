"""Reduction of a profiler trace to the numbers the per-layer readers use.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are ``/device:TPU:<k>``; their ``XLA Ops`` line
holds one event per executed HLO op, named after the instruction
(``fusion.12``, ``knn_merge_cand.1``).  The harness's own host spans are
``bench.*`` annotations on the host plane, on the same clock.

Busy time is the union of op intervals (control-flow ops enclose the ops
they run, so durations are never simply summed); a kernel's time is the
union of its own events; time outside every kernel is busy minus the
union of all Mosaic kernels (``tpu_custom_call`` events), leaving out
the ``bench.init`` span, in which the window builds its initial state
and waits for it.  On a TPU an
event's name is the whole HLO instruction text,
``%knn_merge_cand.3 = (...) custom-call(...), custom_call_target=...``.
"""
from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
# one HLO instruction's text: name, result shape, opcode
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*?) ([a-z][\w-]*)\(")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?$")


def op_name(event_name: str) -> str:
    """The HLO instruction name of an op event (``knn_merge_cand.3``)."""
    m = re.match(r"%(\S+) = ", event_name)
    return m.group(1) if m else event_name


def base_name(event_name: str) -> str:
    """Instruction name without its ``.N`` uniquifier."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def opcode(event_name: str) -> str:
    """The HLO opcode of an op event named by its instruction's text
    (``all-gather-start``), else its base name."""
    m = INSTRUCTION.match(event_name)
    return m.group(3) if m else base_name(event_name)


def union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted [start, end) intervals, clipped to [lo, hi)."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def subtract(merged, holes) -> list:
    """``merged`` intervals minus the merged ``holes``."""
    out = []
    for s, e in merged:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append([s, hs])
            s = max(s, he)
        if e > s:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def overlap(merged, lo, hi) -> float:
    return length(union(merged, lo, hi))


def roofline_share(seconds: float, ops: float, nbytes: float,
                   peaks: dict) -> dict:
    """Least time for ``ops`` and ``nbytes`` at the chip's peaks, the
    larger of the two bounds, over the measured ``seconds``, in percent;
    ``bound`` says which of the two it is."""
    t_ops = ops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"value": 100.0 * max(t_ops, t_bytes) / seconds,
            "bound": "ops" if t_ops > t_bytes else "bytes"}


class Trace:
    """Device op events per device and host spans, in nanoseconds."""

    def __init__(self, ops: dict, spans: list):
        self.ops = ops          # {device: [(name, start, end)]}
        self.spans = spans      # [(name, start, end)]

    @classmethod
    def from_file(cls, path, devices: int):
        import jax
        pd = jax.profiler.ProfileData.from_file(str(path))
        ops, spans = {}, []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) < devices:
                evs = ops.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend((e.name, e.start_ns, e.end_ns)
                                   for e in line.events)
            elif plane.name.startswith("/host"):
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns, e.end_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
        return cls(ops, spans)

    def window(self):
        """(start, end) of the traced window span."""
        w = [s for s in self.spans if s[0] == SPAN_PREFIX + "window"]
        if len(w) != 1:
            raise ValueError(f"expected one window span, found {len(w)}")
        return w[0][1], w[0][2]

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == SPAN_PREFIX + name]

    def busy(self, device) -> list:
        lo, hi = self.window()
        return union(((s, e) for _, s, e in self.ops.get(device, [])),
                     lo, hi)

    def kernel(self, device, prefix: str) -> tuple:
        """(count, merged intervals) of events whose base name starts
        with ``prefix``, inside the window."""
        lo, hi = self.window()
        evs = [(s, e) for n, s, e in self.ops.get(device, [])
               if base_name(n).startswith(prefix) and e > lo and s < hi]
        return len(evs), union(evs, lo, hi)

    def collectives(self, device) -> tuple:
        """(count, merged intervals) of the collective ops in the window,
        found by opcode.  An asynchronous one spans its ``-start`` event,
        its ``-done`` event and the time between, while the transfer is
        in flight: a ``-done`` closes the oldest open ``-start`` of its
        kind, and either half without the other counts alone."""
        lo, hi = self.window()
        spans, open_ = [], collections.defaultdict(collections.deque)
        for n, s, e in sorted(self.ops.get(device, []),
                              key=lambda ev: ev[1]):
            m = COLLECTIVE.match(opcode(n))
            if not m:
                continue
            kind, half = m.groups()
            if half == "-start":
                open_[kind].append((s, e))
                continue
            if half == "-done" and open_[kind]:
                s = open_[kind].popleft()[0]
            spans.append((s, e))
        spans += [iv for starts in open_.values() for iv in starts]
        spans = [(s, e) for s, e in spans if e > lo and s < hi]
        return len(spans), union(spans, lo, hi)

    def summary(self) -> dict:
        """Window length, busy seconds (mean over devices), idle share,
        seconds outside the Mosaic kernels, and the breakdown lists."""
        lo, hi = self.window()
        win = (hi - lo) * 1e-9
        devs = sorted(self.ops) or [0]
        busy = [self.busy(d) for d in devs]
        busy_s = sum(length(b) for b in busy) / len(devs) * 1e-9
        kern = union(((s, e) for n, s, e in self.ops.get(devs[0], [])
                      if KERNEL_TARGET in n), lo, hi)
        init = union(self.spans_named("init"))
        outside = length(subtract(busy[0], init)) \
            - length(subtract(kern, init))
        return {"window_s": win, "busy_s": busy_s,
                "idle_share": 1.0 - busy_s / win if win > 0 else None,
                "outside_kernels_s": outside * 1e-9,
                "device_ops": self.top_ops(devs[0]),
                "idle_gaps": self.idle_gaps(busy[0])}

    def top_ops(self, device) -> list:
        """Ops that took the most device time, by base name.  Ops enclosed
        by another op (a loop or conditional body) are counted under the
        innermost name only."""
        lo, hi = self.window()
        evs = sorted((s, -e, n) for n, s, e in self.ops.get(device, [])
                     if e > lo and s < hi)
        totals, stack = {}, []
        for s, neg_e, n in evs:
            e = -neg_e
            while stack and stack[-1][1] <= s:
                stack.pop()
            if stack and e <= stack[-1][1]:
                # enclosed: take its time away from the enclosing op
                outer = stack[-1][0]
                totals[outer] = totals.get(outer, 0.0) - (min(e, hi)
                                                          - max(s, lo))
            name = op_name(n)
            totals[name] = totals.get(name, 0.0) + (min(e, hi) - max(s, lo))
            stack.append((name, e))
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, busy) -> list:
        """The longest idle gaps in the window, each named by the
        innermost harness span that holds its start."""
        lo, hi = self.window()
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        inner = sorted(((e - s, n, s, e) for n, s, e in self.spans
                        if n != SPAN_PREFIX + "window"))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
            name = next((n[len(SPAN_PREFIX):] for _, n, a, b in inner
                         if a <= s < b), "window")
            out.append([name, (e - s) * 1e-9])
        return out
