"""Engine step flight recorder: a black box for postmortems.

The unified step (docs/architecture/unified_step.md) made per-step batch
composition the central performance variable, and until now nothing
recorded it: a latency spike or an engine fault left no evidence of what
the steps around it looked like. The flight recorder is a bounded
in-memory ring of per-dispatch records — step kind ("unified", or
"spec" for a draft-verify dispatch, which additionally carries its
drafted/accepted token split), token counts, batch fill ratio, dispatch
duration, the compile-stall and shed/deadline counters at that instant —
cheap enough to run always-on (one dict append per dispatch, no I/O).

Two ways out of the ring:

- live: ``/debug/steps?n=N`` (llm/http_service.py) returns the last N
  records while the engine serves;
- postmortem: the engine loop's top-level catch calls ``dump_fault()``,
  flushing the whole ring plus the fault reason to a JSON file under
  ``EngineConfig.flight_record_dir`` (or ``$DYNTPU_FLIGHT_DIR``) before
  the engine dies — the steps leading INTO the fault survive it.

Thread model: the engine thread writes, HTTP handlers read — every
access takes the (uncontended) lock, and records are plain dicts copied
out at snapshot time.

``StepPhases`` beside it names what the host does between two programs
(``PHASES``: the engine thread's pass) and what a start is made of
(``START_PHASES``): each phase's seconds on the host's clock, carried by
the step records as ``host_<phase>_ms``, and the same names on the
profiler's clock, as host events ``engine/<phase>`` / ``start/<phase>``
beside the device's trace (docs/architecture/observability.md).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import time
from collections import deque
from typing import Any, Mapping

from dynamo_tpu.utils.atomic_io import atomic_write_text
from dynamo_tpu.utils.concurrency import make_lock

logger = logging.getLogger(__name__)

DEFAULT_CAPACITY = 512

#: The engine thread's pass, by phase (``TpuEngine``, ``ModelRunner``): what
#: each is around is in docs/architecture/observability.md. ``idle``,
#: ``retire_wait`` and ``handoff_wait`` are waits (for work, for the device,
#: for the frontend's loop); the rest is the host's own work.
PHASES = (
    "idle", "drain", "retire_wait", "retire", "handoff_wait", "admit",
    "compose", "pack", "put", "dispatch", "side_channels",
)
#: A step record's fields for them, and for the time outside every phase.
_HOST_FIELDS = tuple(
    (name, f"host_{name}_ms") for name in (*PHASES, "other")
)
#: What a start does before it serves (``cli._start_engine``,
#: ``TpuEngine``, ``ModelRunner.__init__``).
START_PHASES = ("runtime", "weights", "build", "warmup")

_trace_annotation = None
_NO_SPAN = contextlib.nullcontext()


def _annotation(label: str):
    """``jax.profiler.TraceAnnotation(label)``: a host event on the
    profiler's clock while a profiler session runs, some 0.4 us without
    one. None in a process that has not imported jax (the first phase of a
    start, a JAX-free frontend) or cannot: nothing is imported for its
    sake, and the clock runs all the same."""
    global _trace_annotation
    if _trace_annotation is None:
        if sys.modules.get("jax") is None:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            return None
        _trace_annotation = TraceAnnotation
    return _trace_annotation(label)


class _Phase:
    __slots__ = ("_phases", "_name", "_note")

    def __init__(self, phases: "StepPhases", name: str) -> None:
        self._phases = phases
        self._name = name

    def __enter__(self) -> None:
        p = self._phases
        note = self._note = _annotation(p._labels[self._name])
        if note is not None:
            note.__enter__()
        p._book()
        p._open.append(self._name)

    def __exit__(self, *exc) -> None:
        p = self._phases
        p._book()
        p._open.pop()
        if self._note is not None:
            self._note.__exit__(*exc)


class StepPhases:
    """Where one thread's wall time goes, by named phase. ``with
    phases.phase(name):`` books the seconds inside it to ``name``, LESS
    what phases opened inside it took (self time: every instant belongs to
    the innermost open phase, and to ``other`` where none is open), so the
    phases and ``other`` sum to the wall time and nothing counts twice.
    Always on: two clock reads and one profiler annotation a phase.

    The annotation's name is the constant ``<prefix>/<name>``: the
    benchmark sums the device's idle gaps by the host event that covers
    them (chipbench/xprof.py), so no step number or request id goes into
    it. One thread at a time uses an instance (the engine's thread for a
    pass; a start's steps follow each other), and anyone may read
    ``seconds()``."""

    def __init__(
        self, names: tuple[str, ...] = PHASES, prefix: str = "engine"
    ) -> None:
        self.names = names
        self.prefix = prefix
        self._labels = {name: f"{prefix}/{name}" for name in names}
        self._acc = dict.fromkeys((*names, "other"), 0.0)
        self._open: list[str] = []  # innermost last
        self._mark = time.perf_counter()

    def _book(self) -> None:
        now = time.perf_counter()
        self._acc[self._open[-1] if self._open else "other"] += (
            now - self._mark
        )
        self._mark = now

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def span(self, name: str):
        """The annotation alone, around a stretch whose time stays with
        whatever phase it runs in: ``engine/pass`` around a pass, so that a
        gap inside a pass and outside every phase still reads as the
        engine's."""
        return _annotation(f"{self.prefix}/{name}") or _NO_SPAN

    def take(self) -> dict[str, float]:
        """Seconds by phase, and ``other``, since the take before (or the
        construction): they sum to the wall time between the two. A phase
        that is open is booked up to now and runs on."""
        self._book()
        taken = self._acc
        self._acc = dict.fromkeys(taken, 0.0)
        return taken

    def seconds(self) -> dict[str, float]:
        """Seconds by phase so far; nothing is reset."""
        return {name: self._acc[name] for name in self.names}


class FlightRecorder:
    def __init__(
        self, capacity: int = DEFAULT_CAPACITY, dump_dir: str | None = None
    ) -> None:
        self._lock = make_lock("flight.ring")
        self._ring: deque[dict[str, Any]] = deque(maxlen=max(8, capacity))
        self._seq = 0  # every ring record (steps AND events)
        self._steps = 0  # dispatches only — what total_steps reports
        self.dump_dir = dump_dir or os.environ.get("DYNTPU_FLIGHT_DIR")
        self.dumped_path: str | None = None  # last fault dump (tests/ops)

    def note_step(
        self,
        kind: str,
        *,
        decode_tokens: int = 0,
        prefill_tokens: int = 0,
        batch_fill_ratio: float = 0.0,
        dispatch_ms: float = 0.0,
        lanes: int = 0,
        inflight_depth: int = 0,
        waiting: int = 0,
        running: int = 0,
        compile_stall_ms_total: float = 0.0,
        mid_traffic_compiles_total: int = 0,
        shed_total: int = 0,
        deadline_total: int = 0,
        quantum: int = 0,
        itl_ema_ms: float = 0.0,
        headroom_ms: float = 0.0,
        drafted: int = 0,
        accepted: int = 0,
        operand_transfers: int = 0,
        handoff_items: int = 0,
        diffusion_lanes: int = 0,
        denoise_rows: int = 0,
        commit_rows: int = 0,
        ride_rows: int = 0,
        committed_tokens: int = 0,
        moe_experts_hit: int = 0,
        moe_rows_held: int = 0,
        kda_decode_lanes: int = 0,
        kda_prefill_rows: int = 0,
        kda_fresh_spans: int = 0,
        kda_chunk_tiles: int = 0,
        retention_decode_lanes: int = 0,
        retention_prefill_rows: int = 0,
        retention_fresh_spans: int = 0,
        ssd_decode_lanes: int = 0,
        ssd_chunk_rows: int = 0,
        ssd_chunk_tiles: int = 0,
        ssd_fresh_spans: int = 0,
        conv_rows: int = 0,
        kv_full_blocks: int = 0,
        kv_window_blocks: int = 0,
        kv_window_released: int = 0,
        kv_bytes_live: int = 0,
        context_tokens_live: int = 0,
        attn_short_folds: int = 0,
        attn_long_folds: int = 0,
        attn_expanded_spans: int = 0,
        attn_expanded_rows: int = 0,
        host: Mapping[str, float] | None = None,
    ) -> None:
        """One dispatch's record. Counter fields are the process totals
        AT the step, so a reader diffs adjacent records to see exactly
        which step paid a compile stall or shed load. The co-location
        fields (quantum / itl_ema_ms / headroom_ms — engine/coloc.py)
        let a trace_merge timeline attribute an ITL spike to the quantum
        decision that caused it. ``kind="spec"`` records (unified
        draft-verify dispatches) carry the drafted/accepted token
        split — the per-step acceptance evidence next to the cumulative
        spec counters on the metric surfaces. ``operand_transfers`` is
        the host arrays the runner handed to the device for the
        dispatch: one packed buffer (engine/runner.py operand_layout),
        plus a replayed host feed or the multimodal rows. ``handoff_items``
        is the frames (a token or a finish each) the engine's thread
        handed to the frontend's loop since the record before this one:
        a plain dispatch records at its issue, so it reads the retire
        just behind it. The five
        diffusion fields are a block-diffusion model's: the block SPANS
        of the dispatch (a lane's pass, B rows or the 2B of a ride), the
        rows fed in denoising passes, EVERY row fed unmasked to write a
        finished block's final keys and values (``commit_rows``: a lone
        commit pass's, and the first B rows of a ride, which
        ``ride_rows`` counts again on their own), and the tokens the
        dispatch committed (known at its retire, where such a dispatch
        records);
        ``moe_experts_hit`` is the experts that had a row, summed over its
        grouped expert layers: the weights its grouped kernels had to
        read, which routing decides; ``moe_rows_held`` the routed (row,
        expert) pairs that landed on an expert held here, summed likewise
        (an expert share: models/moe.py). The three ``kda_`` fields and
        the three ``retention_`` fields are what a model's state table
        saw, by the kind of its recurrent layers (delta-rule linear
        attention; power retention): the lanes whose state advanced by
        one row, the prefill rows that went through the chunk path, and
        the spans that started from zeros; ``kda_chunk_tiles`` the tiles
        of the delta rule's chunk kernel those rows filled (a span of more
        rows is whole tiles of ``ops/pallas/kda.py`` ``TILE`` rows: rows /
        (tiles x ``TILE``) is how full they run). The four ``ssd_`` fields
        are a state-space (Mamba-2) model's: the lanes of one row
        (``ssd_recurrent``), the rows of the longer spans and the tiles of
        ``ops/pallas/ssd.py`` ``TILE`` rows they filled (``ssd_chunk``),
        the spans that started from zeros. ``conv_rows`` is a model's whose
        recurrent layers are gated short convolutions (LFM2): the rows fed
        times those layers (no kernel of its own to count tiles of). The
        five ``kv_`` /
        ``context_`` fields are a model's that keeps its cache by layer
        group (docs/architecture/cache_groups.md): blocks in use in the
        full-attention and in the windowed pools as the step is noted,
        blocks released behind a window since the record before, the live
        bytes of both pools, and the context tokens of the running
        sequences those bytes stand for. ``attn_short_folds`` /
        ``attn_long_folds`` are the folds of its K/V ring the ragged
        kernel's SHORT tile (decode rows, diffusion blocks) and LONG tile
        (prefill quanta, verify spans) walk in the dispatch, summed over
        the layers that call it: how the kernel's work divides between
        its two fold bodies (the host's count from the spans,
        ops/pallas/ragged_attention.py ``fold_counts``; 0 where the XLA
        twin serves). ``attn_expanded_spans`` / ``attn_expanded_rows`` are
        the spans, and their rows, that a latent layer held once sent
        through the expanded body instead (ops/pallas/latent_expanded.py
        ``expanded_spans``, the program's own rule on the host): they are
        no folds of the ragged kernel's, and rows / (decode + prefill
        tokens) is how much of the dispatch the expanded form answered.
        ``host`` is what ``StepPhases.take()`` hands out: the engine
        thread's seconds by phase since the record BEFORE this one,
        written as ``host_<phase>_ms`` for every phase of ``PHASES``,
        ``host_other_ms`` (the pass's own glue, outside every phase) and
        ``host_period_ms``, the thread's wall time since that record, which
        the twelve sum to. A record noted at its dispatch's issue (plain)
        and one noted at its retire (expert layers, speculation, block
        diffusion) both read "since the record before": a period is one
        turn of the engine's loop either way, cut at another point of it,
        and it is not the time of the dispatch the record describes."""
        rec = {
            "t_unix": round(time.time(), 6),
            "kind": kind,
            "decode_tokens": decode_tokens,
            "prefill_tokens": prefill_tokens,
            "batch_fill_ratio": round(batch_fill_ratio, 4),
            "dispatch_ms": round(dispatch_ms, 3),
            "lanes": lanes,
            "drafted": drafted,
            "accepted": accepted,
            "operand_transfers": operand_transfers,
            "handoff_items": handoff_items,
            "diffusion_lanes": diffusion_lanes,
            "denoise_rows": denoise_rows,
            "commit_rows": commit_rows,
            "ride_rows": ride_rows,
            "committed_tokens": committed_tokens,
            "moe_experts_hit": moe_experts_hit,
            "moe_rows_held": moe_rows_held,
            "kda_decode_lanes": kda_decode_lanes,
            "kda_prefill_rows": kda_prefill_rows,
            "kda_fresh_spans": kda_fresh_spans,
            "kda_chunk_tiles": kda_chunk_tiles,
            "retention_decode_lanes": retention_decode_lanes,
            "retention_prefill_rows": retention_prefill_rows,
            "retention_fresh_spans": retention_fresh_spans,
            "ssd_decode_lanes": ssd_decode_lanes,
            "ssd_chunk_rows": ssd_chunk_rows,
            "ssd_chunk_tiles": ssd_chunk_tiles,
            "ssd_fresh_spans": ssd_fresh_spans,
            "conv_rows": conv_rows,
            "kv_full_blocks": kv_full_blocks,
            "kv_window_blocks": kv_window_blocks,
            "kv_window_released": kv_window_released,
            "kv_bytes_live": kv_bytes_live,
            "context_tokens_live": context_tokens_live,
            "attn_short_folds": attn_short_folds,
            "attn_long_folds": attn_long_folds,
            "attn_expanded_spans": attn_expanded_spans,
            "attn_expanded_rows": attn_expanded_rows,
            "inflight_depth": inflight_depth,
            "waiting": waiting,
            "running": running,
            "compile_stall_ms_total": round(compile_stall_ms_total, 1),
            "mid_traffic_compiles_total": mid_traffic_compiles_total,
            "shed_total": shed_total,
            "deadline_total": deadline_total,
            "quantum": quantum,
            "itl_ema_ms": round(itl_ema_ms, 3),
            "headroom_ms": round(headroom_ms, 3),
        }
        host = host or {}
        for name, field in _HOST_FIELDS:
            rec[field] = round(1e3 * host.get(name, 0.0), 4)
        rec["host_period_ms"] = round(1e3 * sum(host.values()), 4)
        with self._lock:
            self._seq += 1
            self._steps += 1
            rec["seq"] = self._seq
            self._ring.append(rec)

    def note_event(self, kind: str, **fields: Any) -> None:
        """Out-of-band event in the same timeline (engine fault, drain,
        degradation) — rides the ring between step records."""
        rec = {"t_unix": round(time.time(), 6), "kind": kind, **fields}
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._ring.append(rec)

    def snapshot(self, n: int | None = None) -> list[dict[str, Any]]:
        """The last ``n`` records (all with ``n=None``), oldest first."""
        with self._lock:
            records = list(self._ring)
        if n is not None:
            # n<=0 asks for nothing — falling through would return the
            # WHOLE ring (/debug/steps?n=0 dumping 512 records).
            records = records[-n:] if n > 0 else []
        return records

    @property
    def total_steps(self) -> int:
        """Dispatches recorded — events (fault/drain notes) ride the
        ring and bump ``seq`` but are not steps."""
        with self._lock:
            return self._steps

    def dump(self, path: str, reason: str = "") -> str:
        """Flush the ring to ``path`` as one JSON document."""
        doc = {
            "reason": reason,
            "dumped_unix": time.time(),
            "pid": os.getpid(),
            "records": self.snapshot(),
        }
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # Atomic: a dump raced by the crash it documents must never
        # leave torn JSON for the post-mortem tooling to choke on.
        atomic_write_text(path, json.dumps(doc))
        return path

    def dump_fault(self, reason: str) -> str | None:
        """Fault-path dump: never raises (the engine is already dying —
        the black box must not mask the original fault). Returns the
        written path, or None when no dump dir is configured or the
        write itself failed."""
        d = self.dump_dir
        if not d:
            return None
        path = os.path.join(
            d, f"flight_{os.getpid()}_{int(time.time())}.json"
        )
        try:
            self.note_event("fault", reason=reason[:500])
            self.dumped_path = self.dump(path, reason=reason[:500])
            logger.error("engine fault: flight record dumped to %s", path)
            return self.dumped_path
        except Exception:  # dynalint: allow[DT003] fault-path dump is best-effort; the original fault must surface
            logger.exception("flight-record dump failed")
            return None
