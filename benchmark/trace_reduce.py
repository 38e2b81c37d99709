"""From a profiler capture (``.xplane.pb``) to the device's numbers.

``python -m benchmark.trace_reduce <capture dir or file> <platform> <device_kind>``
prints one JSON object.  It runs in a process of its own, under
``JAX_PLATFORMS=cpu``, after the server has gone: reading a trace needs
``jax.profiler.ProfileData`` and no device.

* ``window_s``: the captured window, first event start to last event end
  over every plane (host threads included: they are traced for the whole
  capture).
* ``busy_s``: per device, the union of the intervals in which an operation
  ran on it; averaged over the devices found.  ``idle_share`` = 1 - busy /
  window.
* ``rounds``: executions of the device program that took most device time
  (the protocol round), per device; ``busy_per_round_s`` = busy / rounds.
* ``device_ops``: operations by total device time, under the names the trace
  gives them.  ``idle_gaps``: the longest gaps between device operations,
  attributed to "host" only (host spans in the trace are a later issue).

Which lines of which planes hold device operations is a property of the
platform's tracer, kept in ``_DEVICE_LINES``.  An unknown ``device_kind`` is
an error (``benchmark/peaks.json`` is the table).
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict

# platform -> (plane name prefix, op line names, program line names).  On a
# TPU each chip is a plane of its own; its "XLA Ops" line holds one event per
# operation and "XLA Modules" one per program run.  The CPU backend has no
# device plane: its XLA thunks run on host threads whose lines start with
# "tf_XLA" (used by the CPU tests of the harness only).
_DEVICE_LINES = {
    "tpu": ("/device:TPU:", ("XLA Ops",), ("XLA Modules",)),
    "cpu": ("/host:CPU", ("tf_XLA",), ("tf_XLAPjRtCpuClient",)),
}
TOP = 10


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def find_capture(path: str) -> str | None:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load_planes(path: str) -> list[tuple[str, list[tuple[str, list[tuple[str, float, float]]]]]]:
    """[(plane, [(line, [(event, start_ns, duration_ns)])])] with jax alone."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name, [
            (line.name, [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


def _union(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total covered length and the gaps between covered stretches."""
    covered, gaps = 0.0, []
    end = None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            if end is not None:
                gaps.append((end, start))
            covered += stop - start
            end = stop
        elif stop > end:
            covered += stop - end
            end = stop
    return covered, gaps


def reduce_planes(planes, platform: str) -> dict:
    prefix, op_lines, program_lines = _DEVICE_LINES[platform]
    first, last = None, None
    for _, lines in planes:
        for _, events in lines:
            for _, start, duration in events:
                first = start if first is None else min(first, start)
                last = start + duration if last is None else max(last, start + duration)
    if first is None:
        return {}
    window_ns = last - first
    busy, rounds, devices = [], [], 0
    op_time: dict[str, float] = defaultdict(float)
    all_gaps: list[float] = []
    for plane, lines in planes:
        if not plane.startswith(prefix):
            continue
        ops = [e for name, events in lines if name.startswith(op_lines) for e in events]
        if not ops:
            continue
        devices += 1
        covered, gaps = _union([(start, start + duration) for _, start, duration in ops])
        busy.append(covered)
        all_gaps.append(min(s for _, s, _ in ops) - first)
        all_gaps += [stop - start for start, stop in gaps]
        all_gaps.append(last - max(s + d for _, s, d in ops))
        for name, _, duration in ops:
            # a TPU op is named by its whole HLO line: "%fusion.7 = s32[...] fusion(...)"
            op_time[name.split(" = ")[0].lstrip("%")[:80]] += duration
        programs: dict[str, list[float]] = defaultdict(list)
        for name, events in lines:
            if name.startswith(program_lines):
                for event, _, duration in events:
                    programs[event].append(duration)
        if programs:
            rounds.append(len(max(programs.values(), key=sum)))
    if not devices:
        return {"window_s": window_ns / 1e9, "devices": 0}
    busy_s = sum(busy) / devices / 1e9
    out = {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "devices": devices,
        "device_ops": [[name, seconds / devices / 1e9] for name, seconds in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [["host", gap / 1e9] for gap in sorted(all_gaps, reverse=True)[:TOP]
                      if gap > 0],
    }
    if rounds:
        out["rounds"] = sum(rounds) / devices
        out["busy_per_round_s"] = busy_s / out["rounds"]
    return out


def reduce_trace(path: str, platform: str, device_kind: str | None = None) -> dict:
    if platform == "tpu":
        peaks_for(device_kind or "")
    capture = find_capture(path)
    if capture is None:
        return {}
    out = reduce_planes(load_planes(capture), platform)
    if out.get("busy_s", 0.0) > out.get("window_s", 0.0) * 1.0001:
        raise ValueError(f"device busy {out['busy_s']} s exceeds the window {out['window_s']} s")
    return out


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1], sys.argv[2], sys.argv[3] if len(sys.argv) > 3 else None)))
