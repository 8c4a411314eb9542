"""dynarace thread-context model: which execution context runs each function.

The serving stack deliberately spans several execution contexts in one
process — the dedicated engine dispatch thread (`TpuEngine._engine_loop`),
the asyncio event loop (HTTP handlers, routers, pumps), `asyncio.to_thread`
executor workers (block transfers, blocking waits), and ad-hoc daemon
threads (operator watch pumps). Rust's compiler enforces Send/Sync across
that split in the source framework; here the equivalent guarantee is this
model plus the DT007–DT010 rules built on it.

A function's context set is derived, in priority order, from:

1. An explicit annotation on (or immediately above) its ``def`` line::

       def record(self, event):  # dynarace: context[engine, loop]

2. The seed registry below — the known entry-point seams, so the analyzer
   is useful on the existing tree without annotating everything.
3. ``async def`` ⇒ ``loop`` (coroutines execute on the event loop).
4. Intra-file spawn inference: a function passed as ``target=`` to
   ``threading.Thread(...)`` gets ``thread:<name>``; a function passed to
   ``asyncio.to_thread(...)`` / ``loop.run_in_executor(...)`` gets
   ``worker``.

Contexts then PROPAGATE through the intra-file call graph: a sync helper
called from the engine loop runs on the engine thread; one called from
both an async handler and the engine loop runs in both contexts (exactly
the functions DT007 cares about). Propagation never enters an ``async
def`` — calling a coroutine function from a thread produces a coroutine
object, not execution in that thread.

Functions that end up with no known context are ignored by the rules —
the model is deliberately precise-over-complete, so every finding is
worth reading.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from tools.dynalint.core import FileContext

#: Canonical context labels (annotations may also introduce new ones —
#: e.g. per-thread labels like ``thread:pump`` from spawn inference).
LOOP = "loop"          # the asyncio event loop
ENGINE = "engine"      # the dedicated TPU engine dispatch thread
WORKER = "worker"      # asyncio.to_thread / run_in_executor pool threads
CONTROL = "control"    # control-plane pump / operator reconcile

_ANNOTATION_RE = re.compile(
    r"#\s*dynarace:\s*context\[([A-Za-z0-9_:\-,\s]+)\]"
)

#: Seed registry: (repo-relative path) -> {function qualname -> contexts}.
#: These are the known entry-point seams; everything else is reached by
#: annotation, async-def inference, spawn inference, or call-graph
#: propagation from these.
SEED_CONTEXTS: dict[str, dict[str, tuple[str, ...]]] = {
    "dynamo_tpu/engine/engine.py": {
        # The dispatch loop IS the engine thread (started in start()).
        "TpuEngine._engine_loop": (ENGINE,),
        # First runner build + device allocation run on a to_thread worker.
        "TpuEngine._build_runner": (WORKER,),
        # Read by /health + /metrics handlers on the asyncio loop.
        "TpuEngine.readiness": (LOOP,),
    },
    "dynamo_tpu/engine/compile_cache.py": {
        # observe() wraps every jitted dispatch: the engine thread in a
        # single-process engine, executor threads under the stepcast
        # follower (parallel/stepcast.py runs runner ops via to_thread).
        "CompileStats.observe": (ENGINE, WORKER),
        # Scraped by readiness()/metrics callbacks on the asyncio loop.
        "CompileStats.snapshot": (LOOP, ENGINE),
    },
    "dynamo_tpu/engine/flight_recorder.py": {
        "FlightRecorder.note_step": (ENGINE,),
        "FlightRecorder.note_event": (ENGINE,),
        # /debug/steps handler reads the ring from the loop.
        "FlightRecorder.snapshot": (LOOP,),
    },
    "dynamo_tpu/utils/recorder.py": {
        # The tracer streams capture records from both the engine
        # dispatch thread and the asyncio thread (PR 9's litigated seam).
        "Recorder.record": (ENGINE, LOOP),
    },
    "dynamo_tpu/utils/tracing.py": {
        # Span open/close happens on the engine hot path AND in HTTP
        # handlers; render()/snapshot() on scrapes from the loop.
        "Tracer.mark": (ENGINE, LOOP),
        "Tracer.span_begin": (ENGINE, LOOP),
        "Tracer.span_end": (ENGINE, LOOP),
        "Tracer.add_span": (ENGINE, LOOP),
        "Tracer.mark_if_active": (ENGINE, LOOP),
        "Tracer.finish": (ENGINE, LOOP),
        "Tracer.export": (ENGINE, LOOP),
        "Tracer.render": (LOOP,),
        "Tracer.snapshot": (LOOP,),
    },
    "dynamo_tpu/block_manager/offload.py": {
        # Blocking byte moves run on to_thread workers so the loop never
        # blocks on PCIe/disk; the shared pool lock serializes them with
        # the engine thread's match/offer.
        "OffloadManager._store": (WORKER,),
        "OffloadManager._onboard_blocking": (WORKER,),
    },
    "dynamo_tpu/block_manager/manager.py": {
        # match/offer are driven from the engine thread; stats() is the
        # deliberately lock-free telemetry probe on the asyncio loop.
        "KvBlockManager.match_host": (ENGINE,),
        "KvBlockManager.offer": (ENGINE,),
        "KvBlockManager.stats": (LOOP,),
        # The scrubber's verify slice runs via asyncio.to_thread (the
        # _scrub_loop pacer stays on the loop); tests also call it
        # directly — the manager lock is the shared-state contract.
        "KvBlockManager.scrub_tick": (WORKER, LOOP),
    },
    "dynamo_tpu/block_manager/integrity.py": {
        # The process-wide corruption ledger is written from EVERY
        # verification seam: the engine thread's match_host, to_thread
        # workers (G3 promotion, scrub ticks, sidecar recovery), and
        # the asyncio loop's wire receivers (G4 pulls, disagg frames).
        # snapshot() feeds the loop-side stats probe and the engine
        # thread's metrics flush; its own lock is the contract.
        "IntegrityStats.note_failure": (ENGINE, WORKER, LOOP),
        "IntegrityStats.note_scrub": (WORKER, LOOP),
        "IntegrityStats.snapshot": (LOOP, ENGINE),
    },
    "dynamo_tpu/block_manager/storage.py": {
        # Crash-consistent sidecar writes happen under the offload
        # worker's _store (and scrub quarantines, also on workers); the
        # pool lock serializes them with the engine thread.
        "DiskStorage.record_block": (WORKER,),
        "DiskStorage.drop_block": (WORKER, LOOP),
    },
    "dynamo_tpu/llm/http_service.py": {
        # aiohttp handlers are coroutines — async-def inference covers
        # them; listed here only to anchor the seam in one place.
    },
    "dynamo_tpu/llm/kv_router/audit.py": {
        # Routers record decisions on the loop; /metrics scrapes (loop)
        # and worker-side HealthServer probes read gauges.
        "RouteObservatory.record": (LOOP,),
        "RouteObservatory.gauges": (LOOP,),
        "RouteObservatory.snapshot": (LOOP,),
    },
    "dynamo_tpu/llm/kv_router/publisher.py": {
        # Engine-side fire-and-forget publishes cross from the engine
        # thread onto the loop (the call_soon_threadsafe seam).
        "KvEventPublisher.publish": (ENGINE,),
        "KvEventPublisher.publish_hit_actual": (ENGINE,),
    },
    "dynamo_tpu/runtime/failover.py": {
        # The failover loop runs on the asyncio loop (ingress-side);
        # the FAILOVER counters are ALSO read by the engine thread's
        # metrics flush (engine.py _flush_side_channels) and by scrape
        # handlers — the registry's lock is the shared-state contract.
        "FailoverStats.note_attempt": (LOOP,),
        "FailoverStats.note_success": (LOOP,),
        "FailoverStats.note_marked_dead": (LOOP,),
        "FailoverStats.snapshot": (LOOP, ENGINE),
        "FailoverStats.render_labeled": (LOOP,),
    },
    "benchmarks/chaos_bench.py": {
        # Pure asyncio driver: async-def inference covers the harness;
        # listed to anchor the chaos seam in the registry.
    },
    "benchmarks/ingress_bench.py": {
        # Pure asyncio driver (the 100k replicated-ingress replay):
        # async-def inference covers it; anchored like chaos_bench.
    },
    "dynamo_tpu/llm/admission.py": {
        # The gate runs inside HTTP handlers (and bench drivers) on the
        # asyncio loop; snapshot() is scraped from the same loop. The
        # per-class OVERLOAD counters it feeds are ALSO read by the
        # engine thread's metrics flush — that registry carries its own
        # lock (utils/deadline.py).
        "AdmissionController.admit": (LOOP,),
        "AdmissionController.snapshot": (LOOP,),
    },
    "dynamo_tpu/llm/kv_router/replicas.py": {
        # Replica fleet management (spawn/kill/rejoin/staleness) is
        # loop-only; the module-level dynarace annotation covers the
        # rest — anchored here for the registry.
        "RouterReplicaSet.staleness": (LOOP,),
    },
    "dynamo_tpu/block_manager/peer.py": {
        # The G4 tier lives on the asyncio loop (discovery watch, pull
        # transfers, re-announce pump); its counters/EMAs are written
        # loop-side only and read lock-free by manager.stats() — the
        # same GIL-atomic contract as every other KVBM gauge. PrefixHeat
        # is the exception: noted from the ENGINE thread's kv_actual
        # hook and read by the planner hook on the loop (its own lock).
        "PeerBlockClient.stats": (LOOP,),
        "PrefixHeat.note": (ENGINE, LOOP),
        "PrefixHeat.hottest": (LOOP,),
    },
    "benchmarks/g4_bench.py": {
        # Pure asyncio driver (the G4 pull/pre-place/peer-death legs):
        # async-def inference covers it; anchored like chaos_bench.
    },
    "dynamo_tpu/ops/quant.py": {
        # Weight-quant math (docs/architecture/weight_quant.md):
        # policy quantize-on-load runs on the runner build's to_thread
        # worker (TpuEngine._build_runner); qdot/qeinsum execute inside
        # jitted programs driven from the engine thread. Pure functions
        # over immutable trees — anchored for the registry.
        "quantize_params_policy": (WORKER,),
        "init_params_policy": (WORKER,),
        "quant_tree_stats": (WORKER,),
    },
    "dynamo_tpu/mocker/engine.py": {
        # The simulated runner is driven by MockerEngine's engine
        # thread — the same dispatch-loop seam as the real TpuEngine;
        # its weight-pass pricing and quant gauges live there.
        "_SimRunner._weight_pass_us": (ENGINE,),
    },
    "dynamo_tpu/planner/obs.py": {
        # Planner control loop runs on the loop; scrapes read from HTTP
        # handlers and the standalone exporter (also loop).
        "PlannerObservatory.note_decision": (LOOP,),
        "PlannerObservatory.note_size": (LOOP,),
        "PlannerObservatory.gauges": (LOOP,),
        "PlannerObservatory.snapshot": (LOOP,),
    },
    # operator/kube.py's watch pump is covered by spawn inference
    # (threading.Thread(target=pump) in the same file).
}


@dataclass
class ContextModel:
    """Context assignment for every function in one file."""

    #: qualname ("Class.method", "func", "outer.inner") -> context set.
    contexts: dict[str, frozenset[str]] = field(default_factory=dict)
    #: qualname -> def node (for rules that re-walk bodies).
    functions: dict[str, ast.AST] = field(default_factory=dict)
    #: qualname -> enclosing class name ("" at module level).
    owner_class: dict[str, str] = field(default_factory=dict)

    def of(self, qualname: str) -> frozenset[str]:
        return self.contexts.get(qualname, frozenset())


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def _parse_annotations(source: str) -> dict[int, frozenset[str]]:
    """Line -> contexts for every `# dynarace: context[...]` marker."""
    out: dict[int, frozenset[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _ANNOTATION_RE.search(line)
        if m:
            out[i] = frozenset(
                s.strip() for s in m.group(1).split(",") if s.strip()
            )
    return out


def _spawn_inference(ctx: FileContext) -> dict[str, frozenset[str]]:
    """Contexts for functions handed to Thread(target=...) /
    asyncio.to_thread(...) / run_in_executor(...) within this file.
    Keyed by the TERMINAL name (methods resolve per owning class later —
    a terminal-name match is deliberate: `self._store` passed to
    to_thread marks every `_store` in the file, which is conservative in
    the right direction for a single-module analysis)."""
    out: dict[str, set[str]] = {}

    def _note(funcref: ast.AST, context: str) -> None:
        name = None
        if isinstance(funcref, ast.Attribute):
            name = funcref.attr
        elif isinstance(funcref, ast.Name):
            name = funcref.id
        if name:
            out.setdefault(name, set()).add(context)

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        qn = ctx.qualname(node.func)
        terminal = node.func.attr if isinstance(
            node.func, ast.Attribute) else getattr(node.func, "id", None)
        if qn == "threading.Thread" or terminal == "Thread":
            for kw in node.keywords:
                if kw.arg == "target":
                    tname = None
                    if isinstance(kw.value, ast.Attribute):
                        tname = kw.value.attr
                    elif isinstance(kw.value, ast.Name):
                        tname = kw.value.id
                    if tname:
                        # The Thread name= kwarg, when a literal, labels
                        # the context; else the target's own name does.
                        label = tname
                        for kw2 in node.keywords:
                            if kw2.arg == "name" and isinstance(
                                kw2.value, ast.Constant
                            ) and isinstance(kw2.value.value, str):
                                label = kw2.value.value
                        out.setdefault(tname, set()).add(f"thread:{label}")
        elif qn == "asyncio.to_thread" and node.args:
            _note(node.args[0], WORKER)
        elif terminal == "run_in_executor" and len(node.args) >= 2:
            _note(node.args[1], WORKER)
    return {k: frozenset(v) for k, v in out.items()}


def build_context_model(ctx: FileContext) -> ContextModel:
    """Assign contexts to every function in `ctx` and propagate through
    the intra-file call graph to a fixpoint. Memoized on the context:
    DT007/DT009/DT010 all need the model, and one build per file per
    lint run is enough."""
    cached = getattr(ctx, "_dynarace_model", None)
    if cached is not None:
        return cached
    model = ContextModel()
    annotations = _parse_annotations(ctx.source)
    seeds = SEED_CONTEXTS.get(ctx.path, {})
    spawned = _spawn_inference(ctx)

    # Pass 1: collect functions with qualnames + direct context evidence.
    async_funcs: set[str] = set()

    def collect(node: ast.AST, stack: list[str], class_name: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC_NODES):
                qual = ".".join(stack + [child.name])
                model.functions[qual] = child
                model.owner_class[qual] = class_name
                ctxs: set[str] = set()
                for line in (child.lineno, child.lineno - 1):
                    ctxs |= annotations.get(line, frozenset())
                ctxs |= set(seeds.get(qual, ()))
                if isinstance(child, ast.AsyncFunctionDef):
                    ctxs.add(LOOP)
                    async_funcs.add(qual)
                if not ctxs:
                    # Spawn inference is the weakest evidence: an explicit
                    # seed/annotation already NAMES the thread a target
                    # runs on — adding a second `thread:` label for the
                    # same spawn would fake a two-context function.
                    ctxs |= set(spawned.get(child.name, frozenset()))
                if ctxs:
                    model.contexts[qual] = frozenset(ctxs)
                collect(child, stack + [child.name], class_name)
            elif isinstance(child, ast.ClassDef):
                collect(child, stack + [child.name], child.name)
            else:
                collect(child, stack, class_name)

    collect(ctx.tree, [], "")

    # Pass 2: intra-file call graph (resolvable edges only).
    edges: dict[str, set[str]] = {q: set() for q in model.functions}
    for qual, fnode in model.functions.items():
        class_name = model.owner_class[qual]
        for node in ast.walk(fnode):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            f = node.func
            if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                    and f.value.id in ("self", "cls") and class_name:
                cand = f"{class_name}.{f.attr}"
                if cand in model.functions:
                    callee = cand
            elif isinstance(f, ast.Name):
                # Nested helper of this function first, else module-level.
                nested = f"{qual}.{f.id}"
                if nested in model.functions:
                    callee = nested
                elif f.id in model.functions:
                    callee = f.id
            if callee is not None and callee != qual:
                edges[qual].add(callee)

    # Pass 3: propagate caller contexts into sync callees to a fixpoint.
    changed = True
    rounds = 0
    while changed and rounds < 50:
        changed = False
        rounds += 1
        for caller, callees in edges.items():
            cctx = model.contexts.get(caller)
            if not cctx:
                continue
            for callee in callees:
                if callee in async_funcs:
                    continue  # calling a coroutine fn ≠ executing it here
                cur = model.contexts.get(callee, frozenset())
                merged = cur | cctx
                if merged != cur:
                    model.contexts[callee] = frozenset(merged)
                    changed = True
    ctx._dynarace_model = model
    return model


def has_context_annotations(source: str) -> bool:
    """Cheap pre-check rules use to opt un-seeded files into analysis."""
    return _ANNOTATION_RE.search(source) is not None
