"""XLA profile capture + parsing: device time from a captured trace.

Host wall-clock numbers carry whatever the host was doing; device time
from a captured XLA profile does not. (Written for an older harness and
not yet run on a locally attached chip — the benchmark PR owns that.)
``measure(fn)`` wraps a callable in jax.profiler trace capture and
returns:

- ``module_ms``: total device time in the "XLA Modules" lane (the
  compiled-program executions — deterministic run to run to <1%);
- ``ops``: per-fusion/op device totals from the "XLA Ops" lane, sorted
  descending — the attribution that says WHICH fusion to attack.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
from collections import defaultdict
from typing import Any, Callable


def _load_trace(logdir: str) -> dict:
    paths = glob.glob(
        os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {logdir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        return json.load(f)


def parse_trace(logdir: str) -> dict[str, Any]:
    trace = _load_trace(logdir)
    events = trace.get("traceEvents", [])
    # pid/tid -> names: find device-side lanes.
    names: dict[tuple, str] = {}
    pid_names: dict[int, str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[(ev["pid"], ev["tid"])] = ev["args"]["name"]
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev["args"]["name"]
    device_pids = {
        pid for pid, n in pid_names.items()
        if "TPU" in n or "/device:" in n
    }

    module_us = 0.0
    op_us: dict[str, float] = defaultdict(float)
    op_lane_us = 0.0
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        lane = names.get((ev["pid"], ev["tid"]), "")
        dur = float(ev.get("dur", 0.0))
        if lane == "XLA Modules":
            module_us += dur
        elif lane == "XLA Ops":
            op_us[ev.get("name", "?")] += dur
            op_lane_us += dur
    ops = sorted(op_us.items(), key=lambda kv: -kv[1])
    return {
        "module_ms": module_us / 1000.0,
        "ops_ms": [(n, round(us / 1000.0, 3)) for n, us in ops],
        "ops_total_ms": op_lane_us / 1000.0,
    }


def measure(fn: Callable[[], Any], logdir: str | None = None) -> dict:
    """Run ``fn`` under a jax profiler trace; return parse_trace output.
    The caller must wait for its results inside ``fn``
    (``jax.block_until_ready``), or the trace closes on queued work."""
    import jax

    own = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="xprof_")
    jax.profiler.start_trace(logdir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    out = parse_trace(logdir)
    out["logdir"] = logdir
    if own:
        pass  # keep for inspection; /tmp cleanup is the host's problem
    return out
