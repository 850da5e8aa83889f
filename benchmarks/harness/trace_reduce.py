"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time by
operation, the longest idle gaps and what the host was doing in them.

Read with ``jax.profiler.ProfileData`` alone. A device plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation
that ran (nested where one operation, such as a loop, holds others); host
threads are the lines of the ``/host:CPU`` plane.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
OWN_SPANS = "bench."
SUFFIX = re.compile(r"(\.(remat|clone)\d*|\.\d+)+$")


def op_name(event_name: str) -> str:
    """An operation's name as the program gave it: the trace holds the whole
    HLO line (``%paged_decode.31 = bf16[...] custom-call(...)``); numbered
    and rematerialised siblings (``.31``, ``.remat2``) go under one name."""
    name = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return SUFFIX.sub("", name) or name


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line):
    out = []
    for event in line.events:
        start = float(event.start_ns)
        out.append((start, start + float(event.duration_ns),
                    op_name(event.name)))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union_seconds(events) -> tuple[float, list]:
    """(busy seconds, gaps as (start_ns, end_ns)) of sorted intervals."""
    busy, gaps = 0.0, []
    cur_start = cur_end = None
    for start, end, _name in events:
        if cur_end is None:
            cur_start, cur_end = start, end
        elif start <= cur_end:
            cur_end = max(cur_end, end)
        else:
            busy += cur_end - cur_start
            gaps.append((cur_end, start))
            cur_start, cur_end = start, end
    if cur_end is not None:
        busy += cur_end - cur_start
    return busy * 1e-9, gaps


def self_seconds(events) -> tuple[dict, dict]:
    """Time by operation name with what its children cover taken out, and
    the count of events of each name."""
    totals, counts = {}, {}
    stack = []          # [end, name, duration, covered by children]

    def close(item):
        _end, name, duration, covered = item
        totals[name] = totals.get(name, 0.0) + max(0.0, duration - covered)
        counts[name] = counts.get(name, 0) + 1

    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(end, stack[-1][0]) - start
        stack.append([end, name, end - start, 0.0])
    while stack:
        close(stack.pop())
    return ({k: v * 1e-9 for k, v in totals.items()}, counts)


def _host_events(profile):
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(OWN_SPANS):
                    continue
                start = float(event.start_ns)
                out.append((start, start + float(event.duration_ns),
                            event.name, line.name))
    return out


def attribute_gap(gap, host_events) -> str:
    """What the host was doing in a gap: the span that covers most of it,
    the shortest such span where several cover it whole."""
    lo, hi = gap
    best, best_key = "nothing recorded on the host", None
    for start, end, name, _thread in host_events:
        overlap = min(hi, end) - max(lo, start)
        if overlap <= 0:
            continue
        key = (overlap, -(end - start))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce(profile, chips: int = 1, top: int = 10) -> dict:
    """The summary every reader works from. Busy and window are averaged
    over the chips used; the operation totals are summed over them."""
    busy, window, ops, counts, all_gaps = [], [], {}, {}, []
    names = []
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        if not match or int(match.group(1)) >= chips:
            continue
        names.append(plane.name)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            events = _events(line)
            if not events:
                continue
            seconds, gaps = union_seconds(events)
            busy.append(seconds)
            window.append((max(e[1] for e in events) - events[0][0]) * 1e-9)
            totals, seen = self_seconds(events)
            for name, value in totals.items():
                ops[name] = ops.get(name, 0.0) + value
            for name, value in seen.items():
                counts[name] = counts.get(name, 0) + value
            all_gaps.extend(gaps)
    if not busy:
        return {"device_planes": names, "busy_s": 0.0, "window_s": 0.0,
                "op_seconds": {}, "op_counts": {}, "device_ops": [],
                "idle_gaps": []}
    all_gaps.sort(key=lambda g: g[0] - g[1])
    host = _host_events(profile) if all_gaps else []
    longest = all_gaps[:2 * top]
    by_host = {}
    for gap in longest:
        name = attribute_gap(gap, host)
        by_host[name] = by_host.get(name, 0.0) + (gap[1] - gap[0]) * 1e-9
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_planes": names,
            "busy_s": sum(busy) / len(busy),
            "window_s": sum(window) / len(window),
            "op_seconds": ops, "op_counts": counts,
            "device_ops": [[name, value] for name, value in device_ops],
            "idle_gaps": [[name, value] for name, value in idle]}


def matching(summary: dict, pattern: str) -> tuple[float, int]:
    """Seconds and count of the device operations whose name matches."""
    rx = re.compile(pattern)
    seconds = sum(v for k, v in summary["op_seconds"].items()
                  if rx.search(k))
    count = sum(v for k, v in summary["op_counts"].items() if rx.search(k))
    return seconds, count
