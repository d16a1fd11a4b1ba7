"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A trace holds one plane per device (``/device:TPU:<n>``), whose
``XLA Ops`` line carries one event per operation the device ran, and
host planes whose lines carry the host's annotations: the program's
``repro.obs`` spans (with ``jax_annotations=True``) and the harness's
own ``bench.window``, which marks the traced window.

Everything here reads the trace alone: the busy share is the union of
the op intervals inside the window, kernel time is the summed duration
of the ops whose names match, and an idle gap is named by what the host
was doing at its middle: the innermost program span, and the innermost
host event.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# an op event's name is its HLO text: "%fusion.12 = f32[...] fusion(...)"
HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")
PALLAS = 'custom_call_target="tpu_custom_call"'
# the program's spans: dotted lower-case names (``serving.rerank``)
PROGRAM_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")


def op_stem(text):
    """A short name for an op event: its HLO instruction name without
    the number (``fusion.12`` -> ``fusion``), marked ``pallas:`` where
    the op is a Pallas kernel."""
    m = HLO_NAME.match(text)
    name = re.sub(r"[.]\d+$", "", m.group(1)) if m else text[:80]
    return "pallas:" + name if PALLAS in text else name


def find_trace(log_dir):
    """The one ``.xplane.pb`` file the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}"
        )
    return paths[0]


class Trace:
    """The events of one trace, as plain tuples ``(name, start_ns,
    end_ns)``: ``ops[device]`` on each device's op line, ``host`` on
    every host line (each line's events kept apart in ``host_lines``),
    and ``window`` the ``(start_ns, end_ns)`` of ``bench.window``."""

    def __init__(self, ops, host_lines, window, async_ops=None):
        self.ops, self.host_lines, self.window = ops, host_lines, window
        self.async_ops = async_ops or {}

    @classmethod
    def load(cls, path):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        ops, async_ops, host_lines, window = {}, {}, [], None
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns)) for e in line.events]
                if m:
                    if line.name == OPS_LINE:
                        ops[int(m.group(2))] = evs
                    elif line.name == ASYNC_LINE:
                        async_ops[int(m.group(2))] = evs
                    continue
                spans = [e for e in evs if e[2] > e[1]]
                for e in spans:
                    if e[0] == WINDOW_SPAN:
                        window = (e[1], e[2])
                if spans:
                    host_lines.append(spans)
        if window is None:
            raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
        return cls(ops, host_lines, window, async_ops)

    @property
    def window_ns(self):
        return self.window[1] - self.window[0]

    def _clipped(self, device, line=None):
        lo, hi = self.window
        for name, s, e in (line if line is not None
                           else self.ops.get(device, ())):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield name, s, e

    def busy_ns(self, device):
        """The union of the device's op intervals inside the window."""
        busy, end = 0, None
        for _, s, e in sorted(self._clipped(device), key=lambda x: x[1]):
            if end is None or s >= end:
                busy += e - s
                end = e
            elif e > end:
                busy += e - end
                end = e
        return busy

    def op_ns(self, device, pattern, with_async=False):
        """Summed duration, inside the window, of the device's ops whose
        HLO text matches ``pattern`` (a regular expression,
        ``re.search``); ``with_async`` adds the async ops' line."""
        rx = re.compile(pattern)
        lines = [self.ops.get(device, ())]
        if with_async:
            lines.append(self.async_ops.get(device, ()))
        return sum(e - s for line in lines
                   for name, s, e in self._clipped(device, line)
                   if rx.search(name))

    def op_count(self, device, pattern):
        rx = re.compile(pattern)
        return sum(1 for name, _, _ in self._clipped(device)
                   if rx.search(name))

    def _leaves(self, device):
        """The window's op events that hold no other op event (a while
        loop's event spans the ops of its body)."""
        evs = sorted(self._clipped(device), key=lambda x: (x[1], -x[2]))
        for i, (name, s, e) in enumerate(evs):
            if i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e:
                continue
            yield name, s, e

    def top_ops(self, device, n=10):
        """``[[name, seconds], ...]``: the ``n`` op names (``op_stem``)
        that took most device time inside the window, over the ops that
        hold no other op."""
        tot = {}
        for name, s, e in self._leaves(device):
            stem = op_stem(name)
            tot[stem] = tot.get(stem, 0) + (e - s)
        top = sorted(tot.items(), key=lambda x: -x[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, device, n=10):
        """``[[host activity, seconds], ...]``: the ``n`` longest
        intervals inside the window in which the device ran nothing, each
        named by ``host_span_at`` its middle."""
        lo, hi = self.window
        gaps, end = [], lo
        for _, s, e in sorted(self._clipped(device), key=lambda x: x[1]):
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if hi > end:
            gaps.append((end, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_span_at((a + b) // 2), (b - a) / 1e9]
                for a, b in gaps[:n]]

    def spans(self, name):
        """Durations in ns of the host spans ``name`` inside the window."""
        lo, hi = self.window
        return [e - s for line in self.host_lines for n, s, e in line
                if n == name and lo <= s and e <= hi]

    def host_span_at(self, t):
        """What the host was doing at time ``t``: the innermost program
        span (``repro.obs`` names, such as ``router.pump.admit``) and the
        innermost host event of any kind, as ``"span > event"``; ``none``
        outside every program span."""
        span = event = None
        for line in self.host_lines:
            for name, s, e in line:
                if not s <= t < e or name == WINDOW_SPAN:
                    continue
                if event is None or e - s < event[1]:
                    event = (name, e - s)
                if PROGRAM_SPAN.match(name) and (
                        span is None or e - s < span[1]):
                    span = (name, e - s)
        head = span[0] if span else "none"
        if event is None or event[0] == head:
            return head
        return f"{head} > {event[0]}"


def main(argv=None):
    """``python -m bench.xplane TRACE.xplane.pb``: list each device's op
    names by total time, and the host span names, over the whole trace
    (a look at a trace before writing a reader against it)."""
    import sys
    from collections import Counter

    from jax.profiler import ProfileData

    path = (argv or sys.argv[1:])[0]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            tot, cnt = Counter(), Counter()
            for e in line.events:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            if not tot:
                continue
            print(f"{plane.name} | {line.name}: {sum(cnt.values())} events")
            for name, ns in tot.most_common(40):
                print(f"    {ns / 1e6:12.3f} ms {cnt[name]:7d}x  {name[:120]}")


if __name__ == "__main__":
    main()
