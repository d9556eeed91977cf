"""The program's own spans in a traced run: the ``automerge_tpu.obs``
ring over the traced window, for the per-layer readers, and the link from
the ring's clock to the profiler trace's, with the idle-gap naming that
uses it.

Readers take the records from the ring itself: tracing is switched off
after the window, and the ring keeps its records. The window is the span
of the service's ``svc/tick`` records, the only ticks the ring saw. A
ring that wrapped, or a program whose tick has no child spans (one that
predates them), gives no window, so its readers report nothing.

The clock link: ``obs.anchor_profiler()`` writes an ``obs.clock``
annotation carrying the ring clock's reading into the profiler trace;
two of them, one at each end of the trace, map ring instants onto the
trace's timeline (``anchors``, ``to_trace``). ``name_gaps`` then names
each idle gap of the device by the innermost span that holds the largest
part of it, among the harness phases and the program's spans.
"""

from __future__ import annotations

ANCHOR = "obs.clock"


class Window:
    """The ring's records that overlap [lo, hi) (ns, ring clock), the
    first instant of the first tick to the last instant of the last."""

    def __init__(self, records, lo: int, hi: int, ticks: int):
        self.records, self.lo, self.hi, self.ticks = records, lo, hi, ticks

    @property
    def seconds(self) -> float:
        return (self.hi - self.lo) / 1e9

    def spans(self, cat: str, name: str) -> list:
        return [r for r in self.records
                if r[2] == cat and r[3] == name and r[1] >= 0]

    def durations(self, cat: str, name: str) -> list:
        return [r[1] for r in self.spans(cat, name)]

    def busy_ns(self, cat: str, name: str) -> int:
        """Union per thread of the window-clipped spans of one name
        (nested or overlapping spans of a thread count once)."""
        from loadbench.trace_reduce import clip, union
        by_tid: dict = {}
        for ts, dur, _cat, _name, tid, _args in self.spans(cat, name):
            by_tid.setdefault(tid, []).append((ts, ts + dur))
        return sum(sum(e - s for s, e in union(clip(iv, self.lo, self.hi)))
                   for iv in by_tid.values())


def window():
    """The traced window's records, or None (see the module's doc)."""
    from automerge_tpu import obs
    rec = obs.recorder()
    if rec is None or rec.n_emitted != rec.n_retained:
        return None
    records = rec.snapshot()
    ticks = [r for r in records if r[2] == "svc" and r[3] == "tick"
             and r[1] >= 0]
    if not ticks or not any(r[2] == "svc" and r[3] == "admit"
                            for r in records):
        return None
    lo = min(r[0] for r in ticks)
    hi = max(r[0] + r[1] for r in ticks)
    inside = [r for r in records if r[0] < hi and r[0] + max(r[1], 0) >= lo]
    return Window(inside, lo, hi, len(ticks))


# -- the profiler trace's clock ------------------------------------------


def anchors(path: str) -> list:
    """[(trace ns, ring ns)] of the ``obs.clock`` annotations of one
    ``.xplane.pb``, in trace order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    out.extend((int(ev.start_ns), int(value))
                               for key, value in ev.stats
                               if key == "perf_ns")
    return sorted(out)


def to_trace(marks: list):
    """Ring ns -> trace ns, linear through the first and last anchor (an
    offset alone when there is one)."""
    if not marks:
        raise ValueError("the trace holds no obs.clock anchor")
    (t0, p0), (t1, p1) = marks[0], marks[-1]
    slope = (t1 - t0) / (p1 - p0) if p1 != p0 else 1.0
    return lambda perf_ns: t0 + (perf_ns - p0) * slope


def span_name(record) -> str:
    """``cat/name``, with the kernel for a compile."""
    _ts, _dur, cat, name, _tid, args = record
    if cat == "device" and name == "compile" and args:
        return f"device/compile:{args.get('kernel')}"
    return f"{cat}/{name}"


#: spans that time a message's wait, not what the host does: never a
#: gap's name
WAITS = {("svc", "inbox_wait")}


def on_trace(records, clock) -> list:
    """Ring spans but the waits, as (start, end, name) on the trace's
    timeline."""
    return [(clock(r[0]), clock(r[0] + r[1]), span_name(r))
            for r in records if r[1] >= 0 and (r[2], r[3]) not in WAITS]


def name_gaps(idle, spans, default: str = "other") -> list:
    """(name, ns) per idle gap (start, end), named by the innermost span
    that holds the largest part of it: from the span holding most of the
    gap, descend to the span inside it that holds most (the longest of
    equals, so a child is reached through its parent) while that span
    holds at least as much of the gap as the outer span's own time
    outside every span inside it. ``spans`` are (start, end, name),
    harness phases and program spans alike, on one clock."""
    from loadbench.trace_reduce import clip, union
    out = []
    for lo, hi in idle:
        def held(s):
            return min(s[1], hi) - max(s[0], lo)

        def largest(cands):
            return max(cands, key=lambda s: (held(s), s[1] - s[0]))

        cands = [s for s in spans if s[0] < hi and s[1] > lo]
        outer = largest(cands) if cands else None
        while outer is not None:
            cands = [s for s in cands if s is not outer
                     and outer[0] <= s[0] and s[1] <= outer[1]]
            if not cands:
                break
            own = held(outer) - sum(e - b for b, e in union(
                clip([(s[0], s[1]) for s in cands], lo, hi)))
            inner = largest(cands)
            if held(inner) < own:
                break
            outer = inner
        out.append((default if outer is None else outer[2], hi - lo))
    return out
