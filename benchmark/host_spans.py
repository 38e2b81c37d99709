"""The device's idle time in a capture, put down to what the host was doing.

``python -m benchmark.host_spans <capture dir or file> <platform>`` prints
one JSON object.  Like ``benchmark/trace_reduce.py`` (whose loader and
device lines it uses) it runs in a process of its own under
``JAX_PLATFORMS=cpu``, after the server has gone.

The program annotates the stages of a served round as ``fantoch/<stage>``
host events (``jax.profiler.TraceAnnotation``; the keyword it passes may
leave ``#round=..#`` in an event's name, which is cut off).  They land on
the host plane, one line per thread: the event loop's line holds ``round``
and what the loop does inside it, a pool thread's line holds ``step`` with
``assemble`` / ``enqueue`` / ``fetch`` / ``execute`` inside.

* The gaps are those of ``trace_reduce``: per device, what the union of its
  operation intervals leaves of the captured window (first event start to
  last event end over every plane), the stretch before the first operation
  and after the last included.
* Each instant of a gap goes to the innermost stage open at that instant,
  over all threads: the one opened last.  A gap under several stages in
  turn is split between them.  Where only ``round`` is open, the loop has
  handed the step to the pool or waits to get it back, and runs whatever
  else is ready meanwhile: before the round's ``step`` that is
  ``handoff``, after it ``resume`` (the program records both in its ring,
  and cannot annotate a span that starts on one thread and ends on
  another).  An instant under no stage is ``unnamed``.
* ``off_step_share``: of the idle time, the part during which no ``step``
  was open, i.e. the device waited on the event loop (sessions, delivery,
  hand-offs) and not on the round's own assembly and drain.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict

from benchmark.trace_reduce import _DEVICE_LINES, _union, find_capture, load_planes

PREFIX = "fantoch/"
UNNAMED = "unnamed"
# a capture that starts inside a step holds the step's later children and
# not the step itself: any of them open means the step is
ON_STEP = {"step", "assemble", "enqueue", "fetch", "execute"}
TOP = 10


def stage_spans(planes) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, stage) of every annotated stage, any thread."""
    spans = []
    for _, lines in planes:
        for _, events in lines:
            for name, start, duration in events:
                if name.startswith(PREFIX):
                    spans.append((start, start + duration, name[len(PREFIX):].split("#", 1)[0]))
    return spans


def device_gaps(planes, platform: str) -> list[tuple[float, float]]:
    """The idle intervals of every device in the capture."""
    prefix, op_lines, _ = _DEVICE_LINES[platform]
    ends = [(start, start + duration) for _, lines in planes for _, events in lines
            for _, start, duration in events]
    if not ends:
        return []
    first, last = min(s for s, _ in ends), max(e for _, e in ends)
    gaps = []
    for plane, lines in planes:
        if not plane.startswith(prefix):
            continue
        ops = [(start, start + duration) for name, events in lines
               if name.startswith(op_lines) for _, start, duration in events]
        if not ops:
            continue
        _, between = _union(ops)
        gaps += [(first, min(s for s, _ in ops)), *between, (max(e for _, e in ops), last)]
    return [(start, stop) for start, stop in gaps if stop > start]


def attribute(gaps, spans) -> dict:
    """Idle nanoseconds per stage, the longest gaps under the stage that
    takes most of each, and the two shares."""
    # where only `round` is open: before or after that round's step
    steps = sorted((start, stop) for start, stop, name in spans if name == "step")
    step_of = {}
    for start, stop, name in spans:
        if name == "round":
            step_of[(start, stop)] = next(
                (step for step in steps if start <= step[0] <= stop), None)

    def label(active, at):
        if not active:
            return UNNAMED
        # opened last; of two opened together, the one that closes first
        start, stop, name = max(active, key=lambda span: (span[0], -span[1]))
        if name != "round":
            return name
        step = step_of.get((start, stop))
        if step is None:
            return name
        return "handoff" if at < step[0] else "resume" if at >= step[1] else name

    # at one instant: stages close, stages open, gaps close, gaps open
    points = sorted(
        [(span[1], 0, span) for span in spans] + [(span[0], 1, span) for span in spans]
        + [(stop, 2, None) for _, stop in gaps] + [(start, 3, None) for start, _ in gaps],
        key=lambda point: point[:2],
    )
    by_stage: dict[str, float] = defaultdict(float)
    per_gap: list[dict[str, float]] = []
    active: set = set()
    in_gaps, off_step, previous = 0, 0.0, None
    for at, kind, span in points:
        if in_gaps and previous is not None and at > previous:
            stage = label(active, previous)
            by_stage[stage] += (at - previous) * in_gaps
            per_gap[-1][stage] = per_gap[-1].get(stage, 0.0) + at - previous
            if not any(name in ON_STEP for _, _, name in active):
                off_step += (at - previous) * in_gaps
        previous = at
        if kind == 1:
            active.add(span)
        elif kind == 0:
            active.discard(span)
        elif kind == 3:
            in_gaps += 1
            per_gap.append({})
        else:
            in_gaps -= 1
    idle = sum(by_stage.values())
    if idle <= 0:
        return {}
    # several devices idle at once share one entry of `per_gap`: fine for a
    # list of the longest, which is for reading and not for arithmetic
    longest = sorted(((max(parts, key=parts.get), sum(parts.values()))
                      for parts in per_gap if parts), key=lambda kv: -kv[1])[:TOP]
    return {
        "idle_s": idle / 1e9,
        "by_stage": {stage: ns / 1e9 for stage, ns in
                     sorted(by_stage.items(), key=lambda kv: -kv[1])},
        "longest": [[stage, ns / 1e9] for stage, ns in longest],
        "unnamed_share": by_stage.get(UNNAMED, 0.0) / idle,
        "off_step_share": off_step / idle,
    }


def reduce_capture(path: str, platform: str) -> dict:
    """{} where there is no capture, no device operation in it, or no
    annotated stage (a program from before the stage recorder)."""
    capture = find_capture(path)
    if capture is None:
        return {}
    planes = load_planes(capture)
    spans = stage_spans(planes)
    if not spans:
        return {}
    return attribute(device_gaps(planes, platform), spans)


if __name__ == "__main__":
    print(json.dumps(reduce_capture(sys.argv[1], sys.argv[2])))
