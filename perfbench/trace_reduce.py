"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

:func:`load_xplane` turns the file into plain lists with nothing but JAX
(``jax.profiler.ProfileData``); :func:`reduce` works on those lists alone,
so it is tested on a small recorded trace kept as JSON beside the tests.

A trace is ``{plane name: {line name: [[event name, start_ns, dur_ns], ...]}}``.
Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds one
event per execution of a compiled program, their ``XLA Ops`` line one per
operation. Host threads are the lines of ``/host:CPU``.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CONTAINERS = ("%while", "%conditional", "%call")
OP_NAME_CHARS = 120
SHORT_GAP_NS = 20_000
SHORT_GAP = "(gaps under 20 us, between operations)"


def find_xplane(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path, keep_host=None):
    """The trace as plain lists. ``keep_host``: a predicate on host event
    names (host lines hold very many events; only spans are needed)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OPS_LINE):
                continue
            events = []
            for ev in line.events:
                if not device and keep_host is not None and not keep_host(ev.name):
                    continue
                events.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.setdefault(line.name, []).extend(events)
        out[plane.name] = lines
    return out


def merge(intervals):
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def total(merged):
    return sum(e - s for s, e in merged)


def covered(merged, windows):
    """Length of ``merged`` that lies inside any of the disjoint sorted
    ``windows``."""
    out, j = 0, 0
    for lo, hi in windows:
        while j < len(merged) and merged[j][1] <= lo:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < hi:
            out += min(merged[k][1], hi) - max(merged[k][0], lo)
            k += 1
    return out


def span_window(trace, prefix):
    """(start, end) from the first to the last host span whose name starts
    with ``prefix``; None where there is none."""
    spans = [
        (s, s + dur)
        for events in trace.get(HOST_PLANE, {}).values()
        for name, s, dur in events if name.startswith(prefix)
    ]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def module_base(name):
    """``jit_train_step(123456)`` -> ``train_step``."""
    name = name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce(trace, step_modules, collective_ops=(), window=None):
    """The summary every trace metric reads.

    ``step_modules``: {module base name: optimizer steps per execution}.
    ``window``: (start_ns, end_ns) on the trace's clock, default the span
    from the first to the last device event. Times in the result are
    seconds; per-device values come in ``devices``, and the top level holds
    the busiest device's (its idle share, its collectives) plus the mean
    busy time."""
    devices = {}
    for plane, lines in trace.items():
        if not DEVICE_PLANE.match(plane):
            continue
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULE_LINE, [])
        if not ops and not mods:
            continue
        devices[plane] = {"ops": ops, "mods": mods}
    if not devices:
        return None
    if window is None:
        starts = [ev[1] for d in devices.values() for ev in d["ops"] + d["mods"]]
        ends = [ev[1] + ev[2] for d in devices.values() for ev in d["ops"] + d["mods"]]
        window = (min(starts), max(ends))
    lo, hi = window
    per_device = {}
    for plane, d in devices.items():
        busy = clip(merge([[s, s + dur] for _, s, dur in d["ops"]]), lo, hi)
        steps_run, step_windows, starts = 0, [], []
        for name, s, dur in sorted(d["mods"], key=lambda ev: ev[1]):
            per = step_modules.get(module_base(name))
            if per is None or s < lo or s + dur > hi:
                continue
            steps_run += per
            step_windows.append([s, s + dur])
            starts.append((s, per))
        intervals = [
            (b[0] - a[0]) / a[1] for a, b in zip(starts[:-1], starts[1:])
        ]
        coll = [
            [s, s + dur] for name, s, dur in d["ops"]
            if any(name.startswith(c) for c in collective_ops)
            and s >= lo and s + dur <= hi
        ]
        other = clip(merge([
            [s, s + dur] for name, s, dur in d["ops"]
            if not any(name.startswith(c) for c in collective_ops)
        ]), lo, hi)
        coll_merged = merge(coll)
        per_device[plane] = {
            "busy_s": total(busy) * 1e-9,
            "steps": steps_run,
            "step_busy_s": covered(busy, merge(step_windows)) * 1e-9,
            "intervals_s": [v * 1e-9 for v in intervals],
            "collective_s": sum(e - s for s, e in coll) * 1e-9,
            "collective_exposed_s": (
                total(coll_merged) - covered(other, coll_merged)
            ) * 1e-9,
            "busy": busy,
        }
    busiest = max(per_device, key=lambda p: per_device[p]["busy_s"])
    top = per_device[busiest]
    by_name = {}
    for name, s, dur in devices[busiest]["ops"]:
        # a loop or a branch is listed with its body's operations: skip it
        if s >= lo and s + dur <= hi and not name.startswith(CONTAINERS):
            short = name[:OP_NAME_CHARS]
            by_name[short] = by_name.get(short, 0) + dur
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    summary = {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "busiest": busiest,
        "busiest_busy_s": top["busy_s"],
        "steps": top["steps"],
        "step_busy_s": top["step_busy_s"],
        "intervals_s": top["intervals_s"],
        "collective_s": top["collective_s"],
        "collective_exposed_s": top["collective_exposed_s"],
        "device_ops": [[n, d * 1e-9] for n, d in device_ops],
        "idle_gaps": idle_gaps(top["busy"], lo, hi, trace.get(HOST_PLANE, {})),
        "devices": {
            p: {k: v for k, v in d.items() if k not in ("busy", "intervals_s")}
            for p, d in per_device.items()
        },
    }
    return summary


def idle_gaps(busy, lo, hi, host_lines, keep=10):
    """Idle seconds of the device by what the host was doing: every gap
    between device operations goes to the innermost host span that covers
    its midpoint (``(no host span)`` where none does); the ``keep`` names
    with most idle time."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [
        (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    spans = sorted(
        (s, s + dur, name)
        for events in host_lines.values() for name, s, dur in events
    )
    by_name = {}
    for g0, g1 in gaps:
        if g1 - g0 < SHORT_GAP_NS:
            by_name[SHORT_GAP] = by_name.get(SHORT_GAP, 0) + (g1 - g0)
            continue
        mid = (g0 + g1) // 2
        best = None
        for s, e, name in spans:
            if s > mid:
                break
            if e >= mid and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        name = best[2] if best else "(no host span)"
        by_name[name] = by_name.get(name, 0) + (g1 - g0)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:keep]
    return [[n, d * 1e-9] for n, d in ranked]
