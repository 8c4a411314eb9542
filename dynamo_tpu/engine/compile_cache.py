"""Compile-lifecycle subsystem: make first-compile cost a managed event.

A serving shape XLA has not seen yet stalls the engine thread for the
length of its compile (seconds for a whole-model step), and a shape grid
with several axes multiplies the un-warmed set. This module owns the four
legs of the fix:

1. **Persistent compilation cache** — `PersistentCompileCache` keeps
   XLA's entries in one base directory (placed by `resolve_cache_base`)
   so warmed programs survive process restarts; a relaunched worker
   replays its compiles from disk. The fingerprint (model config +
   mesh + quant + flags) namespaces the cache so a config change can
   never replay stale programs, and a ledger (`warmed_shapes.json`)
   records which shape keys have a disk entry.
2. **Shape manifest** — `ShapeManifest` records every (kind, T-bucket,
   lane-bucket, steps) shape serving actually executes; warmup loads it
   and warms exactly that set first (decode ladder → dominant prefill →
   tail) instead of the multiplicative default grid.
3. **Warmup planning** — `default_shape_grid` + `split_plan` turn config
   + manifest into an ordered (hot, tail) program plan shared by the real
   ModelRunner and the mocker's SimRunner (`WarmupPlanMixin`).
4. **Compile-stall observability** — `CompileStats` times the first
   execution of every shape and counts mid-traffic compiles (first
   executions outside warmup), exported through the engine metrics
   snapshot and asserted zero by bench.py.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import sys
import time
from contextlib import contextmanager
from typing import Any, Callable

from dynamo_tpu.utils.atomic_io import atomic_write_text
from dynamo_tpu.utils.concurrency import make_lock

logger = logging.getLogger(__name__)

MANIFEST_VERSION = 1
ENV_CACHE_DIR = "DYNAMO_TPU_COMPILE_CACHE_DIR"
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Default base when nothing places the cache from outside: a fixed path
#: inside the checkout (gitignored), never /tmp, $HOME, a pid or a time.
DEFAULT_CACHE_BASE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

#: ShapeSpec tuple layout: (kind, t, lanes, steps, draft_k). Unused axes
#: are 0 — e.g. a unified budget rung is ("unified", 64, 0, 0, 0). The
#: lanes/steps/draft_k axes survive only for manifest wire compatibility
#: (the phase-alternating grid that used them is gone).
ShapeSpec = tuple


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


def shape_key(
    kind: str, t: int = 0, lanes: int = 0, steps: int = 0, draft_k: int = 0
) -> str:
    """Stable string key for one compiled program shape."""
    parts = [kind]
    if t:
        parts.append(f"t{t}")
    if lanes:
        parts.append(f"n{lanes}")
    if steps:
        parts.append(f"s{steps}")
    if draft_k:
        parts.append(f"k{draft_k}")
    return ":".join(parts)


# ---------------------------------------------------------------------------
# fingerprint
# ---------------------------------------------------------------------------


def engine_fingerprint(cfg) -> dict:
    """Everything that changes the compiled program set: model config,
    shapes, mesh, quantization, attention-path flags, jax version. Guards
    both the persistent-cache directory and manifest staleness — a config
    change lands in a fresh namespace instead of replaying stale state."""
    model = cfg.model
    model_fields = {
        k: v for k, v in sorted(vars(model).items())
        if isinstance(v, (int, float, str, bool, type(None)))
    }
    fp = {
        "model": model_fields,
        "dtype": cfg.dtype,
        "quant": cfg.quant,
        # Both quant family members change the compiled program set:
        # kv_quant adds the scale operand to the unified programs and
        # weight_quant changes the param-tree structure every program
        # closes over ({"q","s"} dicts where plain matrices were).
        "kv_quant": getattr(cfg, "kv_quant", None),
        "weight_quant": getattr(cfg, "weight_quant", None),
        "block_size": cfg.block_size,
        "num_blocks": cfg.num_blocks,
        "max_num_seqs": cfg.max_num_seqs,
        "max_model_len": cfg.max_model_len,
        "mesh_shape": dict(sorted((cfg.mesh_shape or {}).items())),
        "kv_sp": cfg.kv_sp,
        "speculative_k": cfg.speculative_k,
        "sampling_extras": cfg.sampling_extras,
        "multimodal": cfg.multimodal,
        "unified_token_budget": getattr(cfg, "unified_token_budget", 0),
        "pallas": os.environ.get("DYNAMO_TPU_PALLAS", ""),
    }
    import jax

    fp["jax"] = jax.__version__
    return fp


def fingerprint_key(fp: dict) -> str:
    blob = json.dumps(fp, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _disabled(value: str | None) -> bool:
    return value is not None and value.lower() in ("none", "0", "off", "")


def env_cache_base() -> str | None:
    """$DYNAMO_TPU_COMPILE_CACHE_DIR, with "none"/"0"/"off" (or empty)
    meaning explicitly disabled — a deploy (or the test harness) can turn
    the cache off through the environment alone."""
    env = os.environ.get(ENV_CACHE_DIR)
    return None if env is None or _disabled(env) else env


def resolve_cache_base(arg: str | None = "auto") -> str | None:
    """The base directory this repo picks for compiled programs and its
    own ledger and manifest — the CLI, bench.py and chip_smoke.py all ask
    here. ``"none"``/``"0"``/``"off"`` disables, as ``arg`` or (with
    ``arg`` auto) as ``$DYNAMO_TPU_COMPILE_CACHE_DIR``; otherwise an
    explicit ``arg`` path, else ``$DYNAMO_TPU_COMPILE_CACHE_DIR``, else
    the fixed ``<checkout>/.jax_cache``. An enabled cache is still moved
    to ``$JAX_COMPILATION_CACHE_DIR`` when that is set: the one place
    that does it is ``PersistentCompileCache``."""
    if _disabled(arg):
        return None
    if arg and arg.lower() != "auto":
        return arg
    if ENV_CACHE_DIR in os.environ:
        return env_cache_base()  # set-but-disabling sentinels win
    return DEFAULT_CACHE_BASE


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


class PersistentCompileCache:
    """Persistent XLA cache directory + fingerprint-namespaced ledger.

    `activate()` points jax's persistent cache at the shared BASE
    directory (unless ``$JAX_COMPILATION_CACHE_DIR`` already does) with
    the entry-size/compile-time floors dropped to zero, so
    every warmup program (even the fast ones) gets a disk entry. XLA's
    own cache keys hash the HLO, so one base dir safely serves every
    engine config — crucial for multi-engine processes (bench disagg,
    router scenarios), where the process-global cache-dir config is
    last-writer-wins and per-fingerprint XLA dirs would strand entries.
    What IS namespaced under ``<base>/<fingerprint>`` is OUR metadata:
    the ledger (`warmed_shapes.json`) tracking which shape keys this
    engine config has compiled in ANY process — a warmup that finds its
    key in the ledger EXPECTS a disk replay, not a fresh compile (the
    ledger's belief: XLA evicts on its own under
    ``jax_compilation_cache_max_size``, and a Pallas kernel's source
    locations are inside what XLA hashes, so a moved checkout misses;
    XLA's own hit count is in ``jax.monitoring``, which chip_smoke.py
    prints) — plus `meta.json` and the engine's shape manifest."""

    LEDGER = "warmed_shapes.json"
    META = "meta.json"

    def __init__(self, base_dir: str, fingerprint: dict) -> None:
        self.fingerprint = fingerprint
        self.key = fingerprint_key(fingerprint)
        # $JAX_COMPILATION_CACHE_DIR wins over any base handed in: JAX
        # reads that variable itself, so XLA's entries land there
        # whatever we do, and the ledger and manifest must sit beside
        # them or their "on disk" claim would be about another directory.
        self.base_dir = os.environ.get(JAX_CACHE_ENV) or base_dir
        self.dir = os.path.join(self.base_dir, self.key)
        self._lock = make_lock("compile.cache")
        self._ledger: set[str] = set()
        self._dirty = False
        self._load_ledger()

    def _load_ledger(self) -> None:
        try:
            with open(os.path.join(self.dir, self.LEDGER)) as f:
                data = json.load(f)
            if data.get("fingerprint") == self.key:
                self._ledger = set(data.get("shapes", []))
        except FileNotFoundError:
            pass
        except Exception:  # dynalint: allow[DT003] corrupt ledger degrades to a cold start
            logger.warning("unreadable compile-cache ledger in %s", self.dir)

    def activate(self) -> None:
        """Wire jax's persistent compilation cache at this directory. Must
        run before the first compile of the process (the runner calls it
        at build time, ahead of any jit)."""
        os.makedirs(self.dir, exist_ok=True)
        meta = os.path.join(self.dir, self.META)
        if not os.path.exists(meta):
            # Atomic (utils/atomic_io): a crash mid-write must not leave
            # a torn meta.json a later activate would read as a foreign
            # fingerprint and discard the whole warmed cache over.
            atomic_write_text(
                meta, json.dumps(self.fingerprint, indent=1, default=str)
            )
        import jax

        if JAX_CACHE_ENV not in os.environ:
            # The SHARED base (see class docstring), not the fingerprint
            # subdir — XLA keys by HLO hash, so co-resident configs mix
            # safely and the ledger's "on disk" claim stays truthful even
            # when another engine activated last. With the variable set
            # JAX already points there and __init__ made base_dir the
            # same directory: no config call.
            jax.config.update("jax_compilation_cache_dir", self.base_dir)
        # Default floors (1 s compile time) would skip exactly the small
        # programs whose RE-compile is still a mid-traffic stall — cache
        # everything.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def has(self, key: str) -> bool:
        with self._lock:
            return key in self._ledger

    def note(self, key: str) -> None:
        with self._lock:
            if key in self._ledger:
                return
            self._ledger.add(key)
            self._dirty = True

    def flush(self) -> None:
        with self._lock:
            if not self._dirty:
                return
            shapes = sorted(self._ledger)
            self._dirty = False
        os.makedirs(self.dir, exist_ok=True)
        path = os.path.join(self.dir, self.LEDGER)
        # tmp+replace+FSYNC (utils/atomic_io): the bare-rename version
        # was atomic but not power-loss durable — a ledger rolled back to
        # empty silently forgets which shapes have disk entries.
        atomic_write_text(
            path, json.dumps({"fingerprint": self.key, "shapes": shapes})
        )

    @property
    def num_ledger_entries(self) -> int:
        with self._lock:
            return len(self._ledger)


# ---------------------------------------------------------------------------
# shape manifest
# ---------------------------------------------------------------------------


class ShapeManifest:
    """Record of the shapes serving actually executed, with counts.

    Warmup loads the previous run's manifest and warms exactly that set
    first — the measured workload's shapes, in usage order — instead of
    the whole default grid. Entries are keyed by `shape_key`."""

    def __init__(self) -> None:
        self._lock = make_lock("compile.manifest")
        self.shapes: dict[str, dict] = {}

    def record(
        self, kind: str, t: int = 0, lanes: int = 0, steps: int = 0,
        draft_k: int = 0,
    ) -> None:
        key = shape_key(kind, t, lanes, steps, draft_k)
        with self._lock:
            entry = self.shapes.get(key)
            if entry is None:
                self.shapes[key] = {
                    "kind": kind, "t": t, "lanes": lanes, "steps": steps,
                    "draft_k": draft_k, "count": 1,
                }
            else:
                entry["count"] += 1

    def specs(self) -> list[ShapeSpec]:
        with self._lock:
            return [
                (e["kind"], e["t"], e["lanes"], e["steps"], e["draft_k"])
                for e in self.shapes.values()
            ]

    def count_of(self, key: str) -> int:
        with self._lock:
            e = self.shapes.get(key)
            return e["count"] if e else 0

    def save(self, path: str, fingerprint: str) -> None:
        with self._lock:
            entries = list(self.shapes.values())
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        # tmp+replace+fsync (utils/atomic_io): a torn manifest degrades
        # the NEXT warmup to the default grid — load() treats corrupt as
        # missing — but a rolled-back rename would do so silently.
        atomic_write_text(
            path,
            json.dumps(
                {
                    "version": MANIFEST_VERSION,
                    "fingerprint": fingerprint,
                    "shapes": entries,
                },
                indent=1,
            ),
        )

    @staticmethod
    def load(path: str, fingerprint: str) -> "ShapeManifest | None":
        """None on missing / corrupt / version or fingerprint mismatch —
        a stale manifest must degrade to the default grid, never warm the
        wrong shapes."""
        try:
            with open(path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return None
        except Exception:  # dynalint: allow[DT003] stale/corrupt manifest degrades to the default grid
            logger.warning("unreadable shape manifest %s; ignoring", path)
            return None
        if (
            data.get("version") != MANIFEST_VERSION
            or data.get("fingerprint") != fingerprint
        ):
            logger.info(
                "shape manifest %s is for another engine fingerprint; "
                "ignoring", path,
            )
            return None
        m = ShapeManifest()
        for e in data.get("shapes", []):
            try:
                m.shapes[shape_key(
                    e["kind"], e.get("t", 0), e.get("lanes", 0),
                    e.get("steps", 0), e.get("draft_k", 0),
                )] = {
                    "kind": e["kind"], "t": int(e.get("t", 0)),
                    "lanes": int(e.get("lanes", 0)),
                    "steps": int(e.get("steps", 0)),
                    "draft_k": int(e.get("draft_k", 0)),
                    "count": int(e.get("count", 1)),
                }
            except (KeyError, TypeError, ValueError):
                logger.warning("bad manifest entry %r; skipped", e)
        return m


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


@contextmanager
def jax_phase_seconds(into: dict):
    """Add to ``into[phase]`` the seconds jax spent in each of ``JAX_PHASES``
    while the block ran (``jax.monitoring`` time spans, any thread's). A
    jitted function traced inside another's trace reports a span of its
    own inside the outer one, so a phase's seconds are the UNION of its
    spans, not their sum. A process that never imported jax (the mocker's
    runner) compiles nothing, and nothing is listened for."""
    if "jax" not in sys.modules:
        yield
        return
    import jax.monitoring

    spans: dict[str, list] = {phase: [] for phase in JAX_PHASES.values()}

    def on_span(event, start, end, **_kw):
        phase = JAX_PHASES.get(event)
        if phase is not None:
            spans[phase].append((start, end))

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
        for phase, seen in spans.items():
            total, upto = 0.0, float("-inf")
            for start, end in sorted(seen):
                total += max(0.0, end - max(start, upto))
                upto = max(upto, end)
            into[phase] = into.get(phase, 0.0) + total


class CompileStats:
    """Times the first execution of every program shape.

    jit compilation is synchronous at first call (execution dispatches
    async, tracing + XLA compile block the caller), so the first-call
    duration of a shape IS the serving-visible stall. A first execution
    during warmup counts as a warmed program (a ledger hit additionally
    as an expected disk replay); outside warmup it is a **mid-traffic compile** —
    the event this whole subsystem exists to drive to zero."""

    def __init__(self, cache: PersistentCompileCache | None = None) -> None:
        self.cache = cache
        self.manifest = ShapeManifest()
        # The counters below are written from every thread that executes
        # a jitted program — the engine dispatch thread in a single-
        # process engine, executor workers under the stepcast follower —
        # and snapshot() is scraped from the asyncio loop. Unlocked this
        # dropped increments and served torn scrapes (dynarace DT007).
        self._lock = make_lock("compile.stats")
        self.seen: set[str] = set()
        self.warming = False
        self.warmed_programs = 0
        self.replayed_programs = 0
        self.mid_traffic_compiles = 0
        self.mid_traffic_keys: list[str] = []
        self.compile_stall_ms_total = 0.0
        self.last_compile_stall_ms = 0.0
        #: Where the warm-ups' seconds went, by jax's own account
        #: (``jax_phase_seconds``; written by ``run_warm_ops``).
        self.warm_phase_s = {phase: 0.0 for phase in JAX_PHASES.values()}

    @contextmanager
    def observe(
        self, kind: str, *, t: int = 0, lanes: int = 0, steps: int = 0,
        draft_k: int = 0,
    ):
        key = shape_key(kind, t, lanes, steps, draft_k)
        with self._lock:
            first = key not in self.seen
        t0 = time.monotonic() if first else 0.0
        # The lock is NEVER held across the yield: the body is the jitted
        # dispatch itself (seconds of XLA compile on a first execution).
        yield
        if not self.warming:
            # Only REAL serving executions feed the manifest; recording
            # warmup would accrete the whole default grid and the pruning
            # could never prune.
            self.manifest.record(kind, t, lanes, steps, draft_k)
        if not first:
            return
        dt_ms = (time.monotonic() - t0) * 1000.0
        with self._lock:
            if key in self.seen:
                return  # lost the first-execution race to another thread
            self.seen.add(key)
            if self.warming:
                self.warmed_programs += 1
                if self.cache is not None and self.cache.has(key):
                    self.replayed_programs += 1
                mid_traffic = False
            else:
                self.mid_traffic_compiles += 1
                self.mid_traffic_keys.append(key)
                self.compile_stall_ms_total += dt_ms
                self.last_compile_stall_ms = dt_ms
                mid_traffic = True
        if mid_traffic:
            logger.warning(
                "mid-traffic compile: shape %s stalled %.0f ms (warmup "
                "did not cover it)", key, dt_ms,
            )
        if self.cache is not None:
            self.cache.note(key)

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
                "replayed_programs": self.replayed_programs,
            }


# ---------------------------------------------------------------------------
# warmup planning
# ---------------------------------------------------------------------------

# Shapes that must stay hot regardless of manifest coverage: every
# running sequence pays one of these on its next step — the whole
# unified program family qualifies (decode lanes ride every variant).
_DECODE_KINDS = ("unified", "unified_full", "unified_mm")


def default_shape_grid(cfg) -> list[ShapeSpec]:
    """The config-derived serving shape set — the unified budget ladder
    (one ragged program per budget rung; ROADMAP item #2, completed)
    plus ONE top-rung program per configured variant: "unified_full"
    (sampling extras — penalties/logprobs) and "unified_mm" (multimodal
    soft prompts). Extras/mm batches snap to the top rung at runtime, so
    each variant costs one warm program instead of a second ladder, and
    the whole grid stays ≤ 8 programs at the default budget."""
    top = _bucket(cfg.unified_token_budget)
    specs: list[ShapeSpec] = [
        ("unified", b, 0, 0, 0)
        for b in budget_ladder(cfg.unified_token_budget)
    ]
    if cfg.sampling_extras and not cfg.speculative_k:
        # Extras requests are rejected on speculative engines
        # (engine._validate_request), so the unified_full program would
        # be unreachable dead warmup weight there.
        specs.append(("unified_full", top, 0, 0, 0))
    if cfg.multimodal:
        specs.append(("unified_mm", top, 0, 0, 0))
    return specs


def split_plan(
    specs: list[ShapeSpec], manifest: ShapeManifest | None
) -> tuple[list[ShapeSpec], list[ShapeSpec]]:
    """(hot, tail) split. Without a manifest everything is hot (the
    pruned grid is the contract for zero mid-traffic compiles). With one,
    hot = the shapes serving demonstrably runs — decode ladder first,
    then prefill shapes by descending observed count — and the rest of
    the grid becomes the background tail, warmed between engine steps."""
    if manifest is None or not manifest.shapes:
        return list(specs), []
    remaining = {shape_key(*s): s for s in specs}
    hot: list[ShapeSpec] = []

    def take(key: str, spec: ShapeSpec | None = None) -> None:
        s = remaining.pop(key, spec)
        if s is not None and s not in hot:
            hot.append(s)

    recorded = sorted(
        manifest.shapes.items(),
        key=lambda kv: (
            # decode ladder first (small steps → large), then by count
            0 if kv[1]["kind"] in _DECODE_KINDS else 1,
            kv[1]["steps"],
            -kv[1]["count"],
        ),
    )
    for key, e in recorded:
        take(key, (e["kind"], e["t"], e["lanes"], e["steps"], e["draft_k"]))
    # Decode shapes stay hot even when the manifest missed them (a fresh
    # traffic mix reaches any budget rung).
    for key, s in sorted(remaining.items()):
        if s[0] in _DECODE_KINDS:
            take(key)
    tail = [remaining[k] for k in sorted(remaining)]
    return hot, tail


class WarmupPlanMixin:
    """Shared warmup planning/execution for ModelRunner and SimRunner.

    Hosts need: ``cfg``, ``compile_stats``, and ``_warm_op(spec) ->
    callable | None`` building the actual trash-block warm call for one
    shape."""

    def warmup_plan(
        self, manifest: ShapeManifest | None = None
    ) -> tuple[
        list[tuple[str, Callable[[], Any]]],
        list[tuple[str, Callable[[], Any]]],
    ]:
        specs = default_shape_grid(self.cfg)
        hot_specs, tail_specs = split_plan(specs, manifest)

        def ops(ss: list[ShapeSpec]) -> list[tuple[str, Callable[[], Any]]]:
            out = []
            for s in ss:
                op = self._warm_op(s)
                if op is not None:
                    out.append((shape_key(*s), op))
            return out

        return ops(hot_specs), ops(tail_specs)

    def run_warm_ops(self, ops) -> int:
        """Execute warm ops under the warming flag (first executions count
        as warmed programs, not mid-traffic compiles)."""
        cs = self.compile_stats
        cs.warming = True
        try:
            with jax_phase_seconds(cs.warm_phase_s):
                for _key, fn in ops:
                    fn()
        finally:
            cs.warming = False
            if cs.cache is not None:
                cs.cache.flush()
        return len(ops)

    def save_manifest(self, path: str) -> None:
        self.compile_stats.manifest.save(
            path, fingerprint_key(engine_fingerprint(self.cfg))
        )
