"""From the profiler's trace to numbers: the reduction kept with the benchmark.

Grown from ``benchmarks/xprof.py`` (device lanes of the profiler's trace;
module and op totals). Reads the ``.xplane.pb`` that ``jax.profiler``
writes with nothing but JAX (``jax.profiler.ProfileData``), turns it into
plain lists (``simplify`` — the form the recorded test trace is kept in)
and reduces those (``reduce``):

- ``window_s``: first start to last end of any device operation;
- ``busy_s``: union of the intervals in which an operation ran on a
  device, averaged over the chips used; ``busy_s_chip0`` for chip 0;
- ``module_events``: ``(name, seconds)`` of chip 0's "XLA Modules" lane —
  one per execution of a jitted program; ``module_s`` their sum;
- ``op_seconds``: chip 0's "XLA Ops" lane summed by operation name with
  the numeric suffix dropped (``fusion.12`` -> ``fusion``);
- ``device_ops``: the ten that took most time;
- ``idle_gaps``: chip 0's idle gaps longer than ``MIN_GAP_S``, named by
  what the host was doing at the gap's midpoint (the deepest host event
  that covers it) and summed by that name, the ten largest.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

MODULES_LANE = "XLA Modules"
OPS_LANE = "XLA Ops"
MIN_GAP_S = 20e-6
_SUFFIX = re.compile(r"(\.\d+)+$")
_LABEL = re.compile(r"[^A-Za-z0-9_.]+")


def find_xplane(logdir: str) -> str:
    paths = glob.glob(
        os.path.join(logdir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return sorted(paths)[-1]


def simplify(profile) -> dict:
    """``ProfileData`` -> ``{"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns], ...]}]}]}``."""
    planes = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData

    return simplify(ProfileData.from_file(find_xplane(logdir)))


def describe(trace: dict) -> list[dict]:
    """Planes and lanes with their event counts: what to look at by hand
    before trusting a reduction on a new device or JAX version."""
    return [
        {"plane": p["name"], "lanes": {
            ln["name"]: len(ln["events"]) for ln in p["lines"]
        }}
        for p in trace["planes"]
    ]


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: the device
    lanes name an event by the operation's whole HLO text."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _device_index(plane_name: str) -> int | None:
    hit = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(hit.group(1)) if hit else None


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


class _HostIndex:
    """Deepest host event that covers an instant, over every host lane."""

    def __init__(self, planes: list[dict]) -> None:
        self.lanes = []
        for plane in planes:
            if not plane["name"].startswith("/host:"):
                continue
            for line in plane["lines"]:
                evs = sorted(
                    (s, s + d, n) for n, s, d in line["events"] if d > 0
                )
                if evs:
                    self.lanes.append(([e[0] for e in evs], evs))

    def at(self, t: float) -> str | None:
        best = None
        for starts, evs in self.lanes:
            i = bisect.bisect_right(starts, t)
            # Walk back through the events that began before t; stop once
            # far past anything that could still cover it.
            for j in range(i - 1, max(i - 400, -1), -1):
                s, e, n = evs[j]
                if e >= t and (best is None or e - s < best[0]):
                    best = (e - s, n)
        return best[1] if best else None


def reduce(trace: dict, chips: int = 1) -> dict:
    devices = {}
    for plane in trace["planes"]:
        idx = _device_index(plane["name"])
        if idx is not None and idx < chips:
            devices[idx] = plane
    if 0 not in devices:
        raise ValueError(
            "the trace holds no /device:TPU:0 plane: planes are "
            f"{[p['name'] for p in trace['planes']]}"
        )
    busy = {}
    lo, hi = float("inf"), float("-inf")
    for idx, plane in devices.items():
        ops = [(s, s + d) for _, s, d in _line(plane, OPS_LANE)]
        if not ops:
            continue
        busy[idx] = _union(ops)
        lo = min(lo, busy[idx][0][0])
        hi = max(hi, max(b for _, b in busy[idx]))
    if 0 not in busy:
        raise ValueError("no operation ran on chip 0 in the traced part")
    busy_s = {i: sum(b - a for a, b in iv) * 1e-9 for i, iv in busy.items()}

    chip0 = devices[0]
    modules = sorted(
        (s, s + d, n) for n, s, d in _line(chip0, MODULES_LANE)
    )
    op_seconds: dict[str, float] = defaultdict(float)
    for n, _, d in _line(chip0, OPS_LANE):
        op_seconds[op_name(n)] += d * 1e-9

    host = _HostIndex(trace["planes"])
    mod_starts = [m[0] for m in modules]
    gaps: dict[str, float] = defaultdict(float)
    iv = busy[0]
    for (_, a), (b, _) in zip(iv, iv[1:]):
        if (b - a) * 1e-9 < MIN_GAP_S:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(mod_starts, mid) - 1
        if i >= 0 and modules[i][1] >= mid:
            label = "inside_" + op_name(modules[i][2])
        else:
            what = host.at(mid)
            label = "between_programs__host_" + (
                "in_" + what if what else "outside_the_runtime"
            )
        gaps[_LABEL.sub("_", label)[:80]] += (b - a) * 1e-9

    def top(d: dict[str, float]) -> list[list]:
        return [
            [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
        ]

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy_s_chip0": busy_s[0],
        "chips_traced": len(busy_s),
        "module_events": [[n, (e - s) * 1e-9] for s, e, n in modules],
        "module_s": sum(e - s for s, e, _ in modules) * 1e-9,
        "op_seconds": dict(op_seconds),
        "device_ops": top(op_seconds),
        "idle_gaps": top(gaps),
    }
