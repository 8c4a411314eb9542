"""Compile-lifecycle subsystem: make first-compile cost a managed event.

A serving shape XLA has not seen yet stalls the engine thread for the
length of its compile (seconds for a whole-model step). This module owns
the two legs of the fix:

1. **Where compiled programs live** — `resolve_cache_base` places XLA's
   persistent compilation cache and `activate_cache` points jax at it
   (yielding to ``$JAX_COMPILATION_CACHE_DIR``), so a relaunched worker
   reads its programs from disk. XLA keys the entries by the HLO's hash;
   this program writes nothing of its own into the directory.
2. **What is warmed and what a first execution cost** —
   `default_shape_grid` is the whole compiled shape set (the budget
   ladder, then one top rung a configured variant); `WarmupPlanMixin`
   runs it before the engine turns ready, on the real ModelRunner and
   the mocker's SimRunner alike. `CompileStats` times the first
   execution of every shape, counts mid-traffic compiles (first
   executions outside warmup) and, during warmup, XLA's own cache hits
   and misses (`jax.monitoring`), exported through the engine metrics
   snapshot.
"""

from __future__ import annotations

import logging
import os
import sys
import time
from contextlib import contextmanager
from typing import Any, Callable

from dynamo_tpu.utils.concurrency import make_lock

logger = logging.getLogger(__name__)

ENV_CACHE_DIR = "DYNAMO_TPU_COMPILE_CACHE_DIR"
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Default base when nothing places the cache from outside: a fixed path
#: inside the checkout (gitignored), never /tmp, $HOME, a pid or a time.
DEFAULT_CACHE_BASE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _bucket(n: int, minimum: int = 16) -> int:
    """Next power-of-two bucket ≥ n (the runner's static-shape rule)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def token_budget(n: int, cap: int, minimum: int = 16) -> int:
    """Snap a unified batch's token count UP onto the warmed budget
    ladder {minimum, 2*minimum, ..., bucket(cap)} — the ENTIRE compiled
    shape set of the unified path (EngineConfig.unified_token_budget).
    Padding unused rows is microseconds; an off-ladder extent would be a
    mid-traffic XLA compile."""
    return min(_bucket(max(n, 1), minimum=minimum), _bucket(cap, minimum=minimum))


def budget_ladder(cap: int, minimum: int = 16) -> list[int]:
    """Every budget the unified path can dispatch — what warmup compiles
    INSTEAD of the phase×bucket×lane grid (a handful of programs)."""
    out = []
    b = minimum
    top = _bucket(cap, minimum=minimum)
    while b <= top:
        out.append(b)
        b *= 2
    return out


def shape_key(kind: str, t: int) -> str:
    """Stable string key for one compiled program shape: the program's
    kind and its token budget (``unified:t64``)."""
    return f"{kind}:t{t}"


def _disabled(value: str | None) -> bool:
    return value is not None and value.lower() in ("none", "0", "off", "")


def env_cache_base() -> str | None:
    """$DYNAMO_TPU_COMPILE_CACHE_DIR, with "none"/"0"/"off" (or empty)
    meaning explicitly disabled — a deploy (or the test harness) can turn
    the cache off through the environment alone."""
    env = os.environ.get(ENV_CACHE_DIR)
    return None if env is None or _disabled(env) else env


def resolve_cache_base(arg: str | None = "auto") -> str | None:
    """The base directory this repo picks for compiled programs — the
    CLI, bench.py and chip_smoke.py all ask here. ``"none"``/``"0"``/
    ``"off"`` disables, as ``arg`` or (with ``arg`` auto) as
    ``$DYNAMO_TPU_COMPILE_CACHE_DIR``; otherwise an explicit ``arg``
    path, else ``$DYNAMO_TPU_COMPILE_CACHE_DIR``, else the fixed
    ``<checkout>/.jax_cache``. An enabled cache is still moved to
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set: the one place that
    does it is ``activate_cache``."""
    if _disabled(arg):
        return None
    if arg and arg.lower() != "auto":
        return arg
    if ENV_CACHE_DIR in os.environ:
        return env_cache_base()  # set-but-disabling sentinels win
    return DEFAULT_CACHE_BASE


def activate_cache(base_dir: str) -> str:
    """Point jax's persistent compilation cache at ``base_dir`` and return
    the directory XLA's entries land in. Must run before the first compile
    of the process (the runner calls it at build time, ahead of any jit).

    ``$JAX_COMPILATION_CACHE_DIR`` wins over any base handed in: JAX reads
    that variable itself, so the entries land there whatever we do, and no
    cache-dir config call is made. XLA's keys hash the HLO, so one
    directory safely serves every engine config of a process (the cache-dir
    config is process-global, last writer wins). A hit is never promised:
    XLA evicts on its own under ``jax_compilation_cache_max_size``, and a
    Pallas kernel's source locations are inside what it hashes, so a moved
    checkout misses — ``CompileStats`` counts what a warmup found."""
    import jax

    cache_dir = os.environ.get(JAX_CACHE_ENV) or base_dir
    os.makedirs(cache_dir, exist_ok=True)
    if JAX_CACHE_ENV not in os.environ:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Default floors (1 s compile time) would skip exactly the small
    # programs whose RE-compile is still a mid-traffic stall — cache
    # everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


# ---------------------------------------------------------------------------
# compile-stall observability
# ---------------------------------------------------------------------------


#: jax's own duration events of a program's way to the device (jax/_src/
#: dispatch.py), by the phase a start's summary names. "backend" is XLA's
#: compile or, where the persistent cache holds the program, its read.
JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "tracing",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
    "/jax/core/compile/backend_compile_duration": "backend",
}

#: XLA's own account of the persistent cache, one event a compile request
#: that consulted it (jax/_src/compiler.py, compilation_cache.py).
JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


@contextmanager
def jax_phase_seconds(into: dict, cache_events: dict | None = None):
    """Add to ``into[phase]`` the seconds jax spent in each of ``JAX_PHASES``
    while the block ran (``jax.monitoring`` time spans, any thread's), and
    to ``cache_events["hits"]`` / ``["misses"]`` the ``JAX_CACHE_EVENTS`` it
    recorded. A jitted function traced inside another's trace reports a
    span of its own inside the outer one, so a phase's seconds are the
    UNION of its spans, not their sum. A process that never imported jax
    (the mocker's runner) compiles nothing, and nothing is listened for."""
    if "jax" not in sys.modules:
        yield
        return
    import jax.monitoring

    spans: dict[str, list] = {phase: [] for phase in JAX_PHASES.values()}
    events: list[str] = []

    def on_span(event, start, end, **_kw):
        phase = JAX_PHASES.get(event)
        if phase is not None:
            spans[phase].append((start, end))

    def on_event(event, **_kw):
        name = JAX_CACHE_EVENTS.get(event)
        if name is not None:
            events.append(name)

    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        jax.monitoring.unregister_event_listener(on_event)
        for phase, seen in spans.items():
            total, upto = 0.0, float("-inf")
            for start, end in sorted(seen):
                total += max(0.0, end - max(start, upto))
                upto = max(upto, end)
            into[phase] = into.get(phase, 0.0) + total
        if cache_events is not None:
            for name in events:
                cache_events[name] = cache_events.get(name, 0) + 1


class CompileStats:
    """Times the first execution of every program shape.

    jit compilation is synchronous at first call (execution dispatches
    async, tracing + XLA compile block the caller), so the first-call
    duration of a shape IS the serving-visible stall. A first execution
    during warmup counts as a warmed program; outside warmup it is a
    **mid-traffic compile** — the event this whole subsystem exists to
    drive to zero."""

    def __init__(self) -> None:
        # The counters below are written from every thread that executes
        # a jitted program — the engine dispatch thread in a single-
        # process engine, executor workers under the stepcast follower —
        # and snapshot() is scraped from the asyncio loop. Unlocked this
        # dropped increments and served torn scrapes (dynarace DT007).
        self._lock = make_lock("compile.stats")
        self.seen: set[str] = set()
        self.warming = False
        self.warmed_programs = 0
        self.mid_traffic_compiles = 0
        self.mid_traffic_keys: list[str] = []
        self.compile_stall_ms_total = 0.0
        self.last_compile_stall_ms = 0.0
        #: Where the warm-ups' seconds went, and how many of their compile
        #: requests XLA read from the persistent cache or compiled anew, by
        #: jax's own account (``jax_phase_seconds``; written by
        #: ``run_warm_ops``). Both 0 where no cache is active.
        self.warm_phase_s = {phase: 0.0 for phase in JAX_PHASES.values()}
        self.warm_cache_events = {n: 0 for n in JAX_CACHE_EVENTS.values()}

    @contextmanager
    def observe(self, kind: str, *, t: int):
        key = shape_key(kind, t)
        with self._lock:
            first = key not in self.seen
        if not first:
            yield
            return
        t0 = time.monotonic()
        # The lock is NEVER held across the yield: the body is the jitted
        # dispatch itself (seconds of XLA compile on a first execution).
        yield
        dt_ms = (time.monotonic() - t0) * 1000.0
        with self._lock:
            if key in self.seen:
                return  # lost the first-execution race to another thread
            self.seen.add(key)
            if self.warming:
                self.warmed_programs += 1
                return
            self.mid_traffic_compiles += 1
            self.mid_traffic_keys.append(key)
            self.compile_stall_ms_total += dt_ms
            self.last_compile_stall_ms = dt_ms
        logger.warning(
            "mid-traffic compile: shape %s stalled %.0f ms (warmup "
            "did not cover it)", key, dt_ms,
        )

    def layer_body(self) -> tuple[int, int]:
        """(traces, calls) of the served model's layer body in this process
        (models/llama.py ``LAYER_BODY``): 1 / 16 a program of a 16-layer
        model whose layers are one program. (0, 0) where no model function
        was imported (the mocker's runner)."""
        llama = sys.modules.get("dynamo_tpu.models.llama")
        if llama is None:
            return 0, 0
        return llama.LAYER_BODY["traces"], llama.LAYER_BODY["calls"]

    def snapshot(self) -> dict:
        traces, calls = self.layer_body()
        with self._lock:
            return {
                "layer_body_traces_total": traces,
                "layer_body_calls_total": calls,
                **{
                    f"warmup_{phase}_seconds_total": round(secs, 3)
                    for phase, secs in self.warm_phase_s.items()
                },
                **{
                    f"warmup_cache_{name}_total": n
                    for name, n in self.warm_cache_events.items()
                },
                "mid_traffic_compiles_total": self.mid_traffic_compiles,
                "compile_stall_ms_total": round(
                    self.compile_stall_ms_total, 1
                ),
                "warmed_programs": self.warmed_programs,
                # Canonical Prometheus name for warmed-program count — the
                # unified-path co-location A/Bs gate on this staying at
                # the budget-ladder size instead of the old lane×bucket
                # grid.
                "warmup_programs_total": self.warmed_programs,
            }


# ---------------------------------------------------------------------------
# warmup planning
# ---------------------------------------------------------------------------


def default_shape_grid(cfg) -> list[tuple[str, int]]:
    """The config-derived serving shape set as (kind, token budget) — the
    unified budget ladder (one ragged program per budget rung) then ONE
    top-rung program per configured variant: "unified_full" (sampling
    extras — penalties/logprobs) and "unified_mm" (multimodal soft
    prompts). Extras/mm batches snap to the top rung at runtime, so each
    variant costs one warm program instead of a second ladder, and the
    whole grid stays ≤ 8 programs at the default budget."""
    top = _bucket(cfg.unified_token_budget)
    specs = [("unified", b) for b in budget_ladder(cfg.unified_token_budget)]
    if cfg.sampling_extras and not cfg.speculative_k:
        # Extras requests are rejected on speculative engines
        # (engine._validate_request), so the unified_full program would
        # be unreachable dead warmup weight there.
        specs.append(("unified_full", top))
    if cfg.multimodal:
        specs.append(("unified_mm", top))
    return specs


class WarmupPlanMixin:
    """Shared warmup planning/execution for ModelRunner and SimRunner.

    Hosts need: ``cfg``, ``compile_stats``, and ``_warm_op(kind, t) ->
    callable | None`` building the actual trash-block warm call for one
    shape."""

    def warm_ops(self) -> list[tuple[str, Callable[[], Any]]]:
        """(shape key, warm call) for the whole grid, in its order: all of
        it runs before the engine turns ready."""
        out = []
        for kind, t in default_shape_grid(self.cfg):
            op = self._warm_op(kind, t)
            if op is not None:
                out.append((shape_key(kind, t), op))
        return out

    def run_warm_ops(self, ops) -> int:
        """Execute warm ops under the warming flag (first executions count
        as warmed programs, not mid-traffic compiles)."""
        cs = self.compile_stats
        cs.warming = True
        try:
            with jax_phase_seconds(cs.warm_phase_s, cs.warm_cache_events):
                for _key, fn in ops:
                    fn()
        finally:
            cs.warming = False
        return len(ops)
