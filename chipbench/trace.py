"""Profiler trace of a steady part of the window, reduced to numbers.

``Tracer`` records a ``jax.profiler`` trace around a stretch of the window
marked by the host span ``chipbench.window``.  ``reduce_events`` turns the
trace's events into:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices;
* ``window_s``: the length of the window;
* ``kernel_ns``: the device time of the LCS kernel's events;
* ``device_ops``: the device operations that took the most time;
* ``idle_gaps``: the idle time between device operations, by the host span
  that covered each gap most (the harness's calls and the program's
  phases), longest first.

The reduction works on plain ``(name, start_ns, duration_ns)`` tuples so
that it can be checked on a hand-made trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import sys
import tempfile

WINDOW = "chipbench.window"
# the LCS kernel's device events: the Pallas call, a ``tpu_custom_call``
# named after its jitted wrapper ``lcs_lanes`` (src/repro/kernels/lcs/)
KERNEL = re.compile(r"^lcs_lanes[.\d]*$")
# host spans that explain idle time: the harness's calls into the system,
# its own waits, and the program's timed phases
HOST_SPAN = re.compile(r"^(job|query|update|harness\..+|phase\..+)$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# an op event's name is its HLO instruction, "%fusion.24 = s32[...] ..."
INSTRUCTION = re.compile(r"^%?([^\s=]+)")
MODULE = re.compile(r"^([^(]+)")


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int):
    """``(start, end)`` of every stretch of ``[lo, hi]`` that no interval
    covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s and s < hi]


def _span_of(gap, spans) -> str:
    """The host span that overlaps ``gap`` most; the shorter on a tie."""
    g0, g1 = gap
    best, key = "(no span)", None
    for name, s, e in spans:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        k = (ov, -(e - s))
        if key is None or k > key:
            best, key = name, k
    return best


def self_times(ops, lo: int, hi: int):
    """``(name, ns)`` of each op's own time inside ``[lo, hi]``: its
    duration less that of the ops nested in it (a loop's body ops run
    inside the loop's own event)."""
    out, stack = [], []
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            stack.pop()
        inside = max(0, min(e, hi) - max(s, lo))
        if stack and e <= stack[-1][2]:
            out[stack[-1][0]][1] -= inside
        stack.append((len(out), s, e))
        out.append([name, inside])
    return out


def reduce_events(devices, spans, window, kernel=KERNEL, top: int = 10):
    """Numbers of one traced window.

    devices: per device, a list of ``(name, start_ns, duration_ns)`` ops;
             a name ``module/op`` is matched against ``kernel`` by its op.
    spans:   host spans ``(name, start_ns, duration_ns)``.
    window:  ``(start_ns, end_ns)``.
    """
    lo, hi = window
    busy, kernel_ns, by_op, by_span = 0, 0, {}, {}
    host = [(n, s, s + d) for n, s, d in spans]
    for ops in devices:
        iv = [(s, s + d) for _, s, d in ops]
        busy += union_ns(iv, lo, hi)
        for name, own in self_times(ops, lo, hi):
            by_op[name] = by_op.get(name, 0) + own
            if kernel.search(name.rsplit("/", 1)[-1]):
                kernel_ns += own
        for g in gaps_ns(iv, lo, hi):
            name = _span_of(g, host)
            by_span[name] = by_span.get(name, 0) + (g[1] - g[0])
    n = max(len(devices), 1)
    rank = lambda d: sorted(([k, v / n / 1e9] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / n / 1e9, "window_s": (hi - lo) / 1e9,
            "kernel_ns": kernel_ns / n, "device_ops": rank(by_op),
            "idle_gaps": rank(by_span)}


def read_xplane(path: str):
    """``(devices, spans, window)`` from a profiler ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, window = [], [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           MODULE.match(e.name).group(1))
                          for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
                op = INSTRUCTION.match(e.name).group(1)
                ops.append((f"{mod}/{op}", e.start_ns, e.duration_ns))
            devices.append(ops)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif HOST_SPAN.match(e.name):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    return devices, spans, window


class Tracer:
    """A profiler trace of one stretch of the window, reduced when it ends.

    The trace goes to a temporary directory (under ``TMPDIR``), which is
    removed once it has been read."""

    def begin(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self.span = jax.profiler.TraceAnnotation(WINDOW)
        self.span.__enter__()

    def end(self) -> dict:
        import jax

        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            return reduce_events(*read_xplane(files[0]))
        except (IndexError, ValueError) as e:
            # no trace, or one without the window: say what it held
            print(f"trace not reduced: {e!r}; planes: {describe(files)}",
                  file=sys.stderr)
            return None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def describe(files) -> list:
    """Plane and line names of a trace file, for a trace that did not
    reduce."""
    from jax.profiler import ProfileData

    if not files:
        return []
    return [(p.name, [ln.name for ln in p.lines])
            for p in ProfileData.from_file(files[0]).planes]
