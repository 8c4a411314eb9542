"""What one run observed, in the form the readers take it.

The harness fills an ``Observations`` and every metric's reader
(``chipbench/readers/<name>.py: read(obs, **params)``) takes its number
from it; a reader that finds nothing to read returns ``None`` and the
metric is left out of the result line.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Observations:
    window: tuple[float, float]          # time.monotonic() of the window
    chips: int
    setup_s: float
    records: list[dict]                  # loadgen's per-request records
    #: time.time() - time.monotonic(), to place unix-stamped records
    unix_minus_mono: float = 0.0
    flight: list[dict] = field(default_factory=list)      # window's steps
    readiness: list[dict] = field(default_factory=list)   # polled snapshots
    readiness_edges: tuple[dict, dict] | None = None      # at window ends
    traces: list[dict] = field(default_factory=list)      # tracer, finished
    compile_events: list[tuple[float, str]] = field(default_factory=list)
    dispatches: list[tuple[float, list]] = field(default_factory=list)
    trace: dict | None = None            # xprof.reduce() of the traced part
    model: dict = field(default_factory=dict)    # served ModelConfig fields
    engine: dict = field(default_factory=dict)   # served EngineConfig fields
    device_kind: str = ""

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def tokens_in_window(self) -> int:
        return sum(
            1 for r in self.records for t in r.get("token_times") or ()
            if self.in_window(t)
        )

    def sample(self, name: str) -> list[float]:
        """``ttft_ms``: send to first streamed token, of the requests SENT
        in the window (a first token that comes after the window closed
        counts: a tail is the tail of all requests). ``itl_ms``: the gaps
        between a request's streamed tokens, pooled, each counted where
        its later token falls."""
        out: list[float] = []
        for r in self.records:
            times = r.get("token_times") or []
            if name == "ttft_ms":
                if self.in_window(r["sent"]) and times:
                    out.append(1000.0 * (times[0] - r["sent"]))
            elif name == "itl_ms":
                out.extend(
                    1000.0 * (b - a)
                    for a, b in zip(times, times[1:])
                    if self.in_window(b)
                )
            else:
                raise KeyError(f"no client sample {name!r}")
        return out
