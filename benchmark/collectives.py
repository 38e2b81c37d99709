"""The device's time in collective operations, from a profiler capture.

``python -m benchmark.collectives <capture dir or file> <platform>`` prints
one JSON object.  Like ``benchmark/host_spans.py`` it uses the loader and
the device lines of ``benchmark/trace_reduce.py`` and runs in a process of
its own under ``JAX_PLATFORMS=cpu``, after the server has gone.

* ``busy_s``: per device, the union of the intervals in which an operation
  ran on it, summed over the devices found (``trace_reduce`` averages; a
  share of sums is the same share).
* ``collective_s``: per device, the union of the intervals of its collective
  operations, summed likewise.  A collective is an operation whose name, as
  the trace gives it (``%all-gather.31 = ...``), starts with one of
  ``COLLECTIVES``: what crosses the interconnect, and the wait for the
  slowest device that an operation of this kind includes.
* ``share`` = collective / busy, 0 where the program holds no collective (one
  chip); nothing where the capture holds no device operation.
* ``by_op``: the collectives by device time, summed over devices.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict

from benchmark.trace_reduce import _DEVICE_LINES, _union, find_capture, load_planes

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute", "reduce-scatter", "all-to-all")
TOP = 10


def op_name(event: str) -> str:
    """``%all-gather.31 = s32[...] all-gather(...)`` -> ``all-gather.31``."""
    return event.split(" = ")[0].lstrip("%")[:80]


def reduce_planes(planes, platform: str) -> dict:
    prefix, op_lines, _ = _DEVICE_LINES[platform]
    busy = collective = 0.0
    devices = 0
    by_op: dict[str, float] = defaultdict(float)
    for plane, lines in planes:
        if not plane.startswith(prefix):
            continue
        ops = [e for name, events in lines if name.startswith(op_lines) for e in events]
        if not ops:
            continue
        devices += 1
        busy += _union([(start, start + duration) for _, start, duration in ops])[0]
        named = [(op_name(name), start, duration) for name, start, duration in ops]
        crossing = [op for op in named if op[0].startswith(COLLECTIVES)]
        collective += _union([(start, start + duration) for _, start, duration in crossing])[0]
        for name, _, duration in crossing:
            by_op[name] += duration
    if not devices or busy <= 0:
        return {}
    return {
        "devices": devices, "busy_s": busy / 1e9, "collective_s": collective / 1e9,
        "share": collective / busy,
        "by_op": [[name, ns / 1e9] for name, ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def reduce_capture(path: str, platform: str) -> dict:
    capture = find_capture(path)
    return {} if capture is None else reduce_planes(load_planes(capture), platform)


if __name__ == "__main__":
    print(json.dumps(reduce_capture(sys.argv[1], sys.argv[2])))
