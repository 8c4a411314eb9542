"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass, field

from dynamo_tpu.models.config import CACHE_FORMS, ModelConfig


def cache_form_of(
    *, entries: int, pool: bool, latent: bool, kv_quant, kv_sp: bool
) -> str:
    """THE rule for the form a paged layer's pages take (``CACHE_FORMS``;
    docs/architecture/unified_step.md "Three forms of a layer's pages"),
    from what a configuration itself shows and nothing else (no flag, no
    model's name, no dtype): ``EngineConfig.cache_form`` asks it, and
    ``tools/ragged_kernel_bench.py`` for the shapes it builds.

    - "once": the model holds a latent ONCE (``entries`` 1,
      ``ModelConfig.cache_arrays``): one array a layer, the values are the
      key entry's leading columns.
    - "joined": a (k, v) layer whose K and V are one shape, with neither
      int8 scales (K and V have a scale each, ``kv_scale``) nor the
      striped ``kv_sp`` scan (it slices the SLOT axis): ONE array a layer,
      a block's keys and then its values one contiguous page, so the
      ragged kernel moves a page with one descriptor and the layer writes
      both with one scatter.
    - "apart": every other cache (int8, ``kv_sp``; a model with no pool
      keeps the empty pair; a latent-attention layer that still stores its
      latent twice, ``ModelConfig.layer_cache_arrays``: its V is its K's
      leading columns, and what it goes to is "once"): K and V an array
      each."""
    if entries == 1:
        form = "once"
    elif pool and not latent and not kv_quant and not kv_sp:
        form = "joined"
    else:
        form = "apart"
    assert form in CACHE_FORMS
    return form


@dataclass
class EngineConfig:
    model: ModelConfig
    dtype: str = "bfloat16"
    block_size: int = 16
    num_blocks: int = 512            # device KV blocks (block 0 is trash)
    max_num_seqs: int = 8            # decode batch slots
    max_model_len: int = 512         # context limit per sequence
    prefill_batch: int = 4           # prompts fused into one prefill call
    watermark: float = 0.05          # keep this fraction of blocks free
    enable_prefix_caching: bool = True
    # Serve image requests (llm/multimodal.py): warmup also compiles the
    # soft-prompt prefill variant so the first image isn't a mid-traffic
    # XLA compile.
    multimodal: bool = False
    seed: int = 0
    remote_kv_timeout_s: float = 30.0  # disagg: max wait for inbound KV
    # Decode chunks allowed in flight before forcing results. Depth 2 hides
    # dispatch/fetch latency behind device compute: chunk N+1 feeds on
    # chunk N's device-resident tokens, so issuing never waits on a fetch.
    pipeline_depth: int = 2
    # Parallelism (parallel/mesh.py): data/tensor/sequence axis sizes.
    mesh_shape: dict[str, int] = field(default_factory=dict)
    # Long-context mode: shard the paged KV cache's SLOT axis over the
    # mesh's sp axis, so max_model_len can exceed ONE device's cache
    # arrays (total capacity = sp x per-device slots), COMPOSABLE with
    # tp head-sharding (per-device KV = 1/(sp*tp) of the total). The
    # engine allocator stripes logical block i onto sp shard i % sp and
    # each shard's attention (Pallas or jnp) scans ONLY its own stripe,
    # so attention FLOPs partition over sp too; per-shard partials merge with a logsumexp
    # combine (ops/attention.py AttnDispatch). Requires sp > 1 and
    # num_blocks % sp == 0 (validated at runner build).
    kv_sp: bool = False
    # Multi-host bootstrap (parallel/multihost.py): when num_nodes > 1,
    # every participating process calls jax.distributed.initialize(
    # coordinator, num_nodes, node_rank) before touching devices, and
    # mesh_shape spans the GLOBAL device set (reference analogue:
    # MultiNodeConfig, lib/llm/src/engines.rs:42-60).
    coordinator: str | None = None
    num_nodes: int = 1
    node_rank: int = 0
    # Weight-only quantization (ops/quant.py): None = serve weights in
    # `dtype`; "int8" halves decode's weight-streaming bytes (per-output-
    # channel symmetric scales; KV cache and activations stay in `dtype`).
    quant: str | None = None
    # KV-cache quantization (docs/architecture/kv_quant.md): None = the
    # G1 device cache stays in `dtype` (bf16-hot); "int8" stores KV
    # blocks as int8 with per-(block, kv-head) float32 scales riding the
    # block-table metadata — roughly half the decode HBM read bytes and
    # double the KV capacity per chip. Dequant happens in-kernel on the
    # ragged path (the XLA oracle twin does identical arithmetic); the
    # G2/G3 KVBM tiers are always quantized when a block manager runs
    # with a quantized layout, independent of this G1 knob (the
    # per-tier precision policy).
    kv_quant: str | None = None
    # Per-matmul weight-quantization policy (docs/architecture/
    # weight_quant.md; models/llama.py WeightQuantPolicy): None = serve
    # weights in `dtype`; "int8"/"fp8" quantizes every site; a comma
    # list of site=fmt pairs ("attn=int8,mlp=int8") selects the
    # embedding / attn / mlp / unembed sites independently. Weights
    # quantize ON LOAD (the full-precision copy never materializes
    # resident), scales ride as jit state sharded like the matrices
    # they scale, and dequant is in-register inside the existing
    # budget-ladder programs — zero new XLA programs, composes with
    # kv_quant (weights and KV halve independently). Supersedes the
    # legacy whole-model `quant` flag (mutually exclusive).
    weight_quant: str | None = None
    # EXPERIMENTAL (net −17% on random weights — older harness, not
    # reproduced; no demonstrated win without a real checkpoint;
    # watch spec_tokens_per_step on /metrics before enabling in prod).
    # Prompt-lookup speculative decoding ON THE UNIFIED STEP
    # (docs/architecture/unified_step.md "Speculative decode on the
    # ragged step"): each decode lane's dispatch drafts up to this many
    # tokens by matching the trailing bigram against the sequence's
    # host token history and verifies them as a draft-verify span of
    # the SAME ragged program — per-span verify logits, greedy
    # accept-prefix, and the bonus sample all run in-dispatch (zero
    # extra warm programs). 0 = off. Greedy lanes accept matching
    # prefixes (exact equivalence with sequential greedy); sampled
    # lanes fall back to 1 token/step.
    speculative_k: int = 0
    # Speculative auto-gating: each spec step scores
    # K+1 positions, so below ~1.4 delivered tokens/step speculation is a
    # net LOSS (~27% at K=3 — older harness, not reproduced). The engine tracks
    # delivered tokens/step over a rolling window; if the mean sits below
    # break-even it falls back to plain decode, then re-probes after
    # speculative_probe_steps plain steps in case traffic changed.
    speculative_break_even: float = 1.4
    speculative_window: int = 128      # spec steps per measurement window
    speculative_probe_steps: int = 1024  # plain steps before re-probing
    # Re-probe cost cap: a re-probe
    # after the gate disabled speculation runs only this many spec steps
    # before re-judging, instead of a full speculative_window — so on
    # traffic where speculation keeps losing, the steady-state overhead is
    # probe_window/probe_steps (~1.6% at defaults), not window/probe_steps
    # (~12.5%). A probe that beats break-even re-commits to full windows.
    speculative_probe_window: int = 16
    # Overload bounds on the engine waiting list (0 = unbounded, the
    # historical behavior): depth bound sheds the OLDEST waiting sequence
    # (it has burned the most of its deadline and is likeliest already
    # abandoned) with FinishReason.SHED; age bound sheds waiters older
    # than this many seconds. Shed requests surface as typed client
    # errors, never silent drops (docs/architecture/overload_and_drain.md).
    max_waiting: int = 0
    max_queue_delay_s: float = 0.0
    # Frequency/presence penalties + per-token logprobs run through the
    # unified_full variant (engine/runner.py — ONE program at the top
    # budget rung) dispatched only for batches that need it, so plain
    # traffic never pays the [B, vocab] count-buffer traffic. False
    # skips compiling it and 400-rejects such requests.
    sampling_extras: bool = True

    # The unified step (docs/architecture/unified_step.md): every engine
    # step is ONE ragged token batch mixing decode lanes (draft-verify
    # spans under speculative_k) with chunked-prefill quanta, run through
    # the ragged attention kernel (ops/pallas/ragged_attention.py) — the
    # only compiled extent is the total token budget, so warmup is the
    # budget ladder (≤ 8 programs).
    # Max tokens per unified dispatch. Runtime batches snap UP through
    # compile_cache.token_budget() onto the power-of-two ladder
    # {16, 32, ..., bucket(unified_token_budget)} — the entire warmed
    # shape set of the unified path.
    unified_token_budget: int = 256
    # Prefill tokens one sequence may take per unified step WHILE decode
    # lanes share the batch (the Nexus chunked-prefill quantum: bounds
    # how much one prompt can stretch a step and therefore decode ITL).
    # Doubles as the budget slice RESERVED for prefill when prompts are
    # waiting — decode lanes can never starve prefill below one quantum,
    # and decode-first fill means prefill can never starve decode.
    unified_prefill_quantum: int = 64

    # SLO-aware co-location on the unified step (engine/coloc.py; ROADMAP
    # item #3). itl_slo_ms is the decode inter-token-latency target the
    # ColocController measures each unified dispatch against (0 = no
    # target: no violation accounting, no adaptation). coloc selects the
    # policy: "static" keeps the hand-tuned unified_prefill_quantum (the
    # A/B control); "adaptive" runs the AIMD loop — the quantum grows
    # while measured ITL headroom exists, shrinks multiplicatively under
    # SLO pressure, and floors at coloc_min_quantum so prefill never
    # fully starves (the two-sided bound compose_unified promises).
    # Adaptation is pure batch composition: totals still snap onto the
    # compiled budget ladder, so it costs zero new XLA programs.
    itl_slo_ms: float = 0.0
    coloc: str = "static"
    coloc_min_quantum: int = 16

    # Host-tier (G2) onboarding is only a win when moving the bytes beats
    # recomputing the prefill — true on PCIe-attached hosts, false when the
    # host↔device link is slow. The engine
    # measures both rates live (EMA of onboard bytes/s and prefill tok/s)
    # and skips onboarding while it predicts a loss; the first onboard
    # always runs to seed the estimate.
    kvbm_adaptive_gate: bool = True

    # G4 peer tier (block_manager/peer.py): max wall-clock a request
    # admitted for prefill may stay PARKED waiting for a fleet peer pull
    # to land its missing prefix blocks in G2. Past the deadline it
    # proceeds by local recompute (counted in degraded_requests_total) —
    # the pull itself keeps running and warms the tier for the next
    # request. Deliberately much tighter than remote_kv_timeout_s: a
    # pull is an opportunistic TTFT optimization, not a correctness
    # dependency like disagg's inbound KV.
    kvbm_peer_timeout_s: float = 2.0

    # Compile lifecycle (engine/compile_cache.py). `compile_cache_dir` is
    # the directory of the persistent XLA compilation cache (XLA keys its
    # entries by the program's hash, so one directory serves every
    # config): a relaunched worker reads its warmup's programs from disk.
    # None = $DYNAMO_TPU_COMPILE_CACHE_DIR or disabled.
    compile_cache_dir: str | None = None
    # Readiness gating while the shape set compiles: "hold" parks
    # admission until warmup is done (requires the operator to actually
    # run warmup — the CLI does); "degraded" serves immediately and flags
    # it (engine.served_unwarmed; mid-traffic compiles are counted either
    # way).
    warmup_gate: str = "degraded"

    # Flight recorder (engine/flight_recorder.py): bounded in-memory ring
    # of per-dispatch records (step kind, token counts, batch fill ratio,
    # dispatch ms, counter snapshots) served by /debug/steps and dumped
    # to `flight_record_dir` (or $DYNTPU_FLIGHT_DIR) when the engine
    # loop faults — the black box for postmortems
    # (docs/architecture/observability.md).
    flight_record_capacity: int = 512
    flight_record_dir: str | None = None

    _QUANT_MODES = (None, "int8")
    _WARMUP_GATES = ("hold", "degraded")
    _COLOC_MODES = ("static", "adaptive")

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_model_len + self.block_size - 1) // self.block_size

    @property
    def group_num_blocks(self) -> tuple:
        """Blocks of each cache group's pool (``model.cache_groups``;
        docs/architecture/cache_groups.md). ``num_blocks`` sizes the pool
        of a model with one group, and of the full-attention group. A
        windowed group beside others holds what its window can keep live:
        for every sequence slot the window, the largest span a dispatch
        writes ahead of it (``unified_token_budget``) and a block of
        lookahead at each end, never more than ``num_blocks``: a pool cut
        so still holds one sequence's need and the trash block, since
        ``validate`` asks ``num_blocks`` for a whole table and one more,
        so a span that finds it full can always preempt its way in
        (``Scheduler.fund_span``)."""
        groups = self.model.cache_groups
        if len(groups) <= 1:
            # one pool; none where no layer pages (``num_blocks`` is then
            # not read)
            return (self.num_blocks,) * len(groups)
        bs = self.block_size

        def windowed(w: int) -> int:
            per_seq = -(-w // bs) + -(-self.unified_token_budget // bs) + 2
            return min(
                self.num_blocks,
                self.max_num_seqs * min(per_seq, self.max_blocks_per_seq) + 1,
            )

        return tuple(windowed(w) if w else self.num_blocks for w in groups)

    @property
    def cache_form(self) -> str:
        """The form this configuration's paged layers take (``cache_form_of``
        decides, here and nowhere else in the engine). The runner's
        allocation and its sharding and ``readiness()`` read it here; the
        layer body, the attention call and block IO read the form off the
        arrays they are handed (``ops/attention.py`` ``page_form``)."""
        m = self.model
        return cache_form_of(
            entries=m.cache_arrays, pool=m.has_pool, latent=m.is_mla,
            kv_quant=self.kv_quant, kv_sp=self.kv_sp,
        )

    def validate(self) -> None:
        if (
            self.model.has_pool
            and self.num_blocks < self.max_blocks_per_seq + 1
        ):
            raise ValueError(
                f"num_blocks={self.num_blocks} cannot hold even one "
                f"max-length sequence ({self.max_blocks_per_seq} blocks)"
            )
        if self.quant not in self._QUANT_MODES:
            raise ValueError(
                f"quant={self.quant!r} not in {self._QUANT_MODES}"
            )
        if self.kv_quant not in self._QUANT_MODES:
            raise ValueError(
                f"kv_quant={self.kv_quant!r} not in {self._QUANT_MODES}"
            )
        if self.kv_quant and self.kv_sp:
            raise ValueError(
                "conflicting flags --kv-quant + --kv-sp: kv_quant does "
                "not support the striped (sequence-parallel) KV cache "
                "yet — per-block scales would need the striped-allocator "
                "sharding. Drop one of the two flags."
            )
        if self.weight_quant:
            # Parse-validate the policy spec so a typo fails at config
            # time with the site/format vocabulary, not mid-load.
            from dynamo_tpu.models.llama import WeightQuantPolicy

            WeightQuantPolicy.from_string(self.weight_quant)
            if self.quant:
                raise ValueError(
                    "conflicting flags --quant + --weight-quant: the "
                    "legacy whole-model quant flag and the per-matmul "
                    "weight_quant policy both own the weight tree — "
                    "use --weight-quant alone (--weight-quant int8 is "
                    "the superset of --quant int8)"
                )
        if self.speculative_k < 0 or self.speculative_k > self.block_size:
            raise ValueError(
                f"speculative_k={self.speculative_k} must be in "
                f"[0, block_size={self.block_size}]"
            )
        B = self.model.diffusion_block_length
        if B:
            # A block-diffusion model (docs/architecture/unified_step.md
            # "The block step"): a page holds whole diffusion blocks, so a
            # page is final once its last block is committed.
            if self.block_size % B or self.unified_prefill_quantum % B:
                raise ValueError(
                    f"block_size={self.block_size} and "
                    f"unified_prefill_quantum={self.unified_prefill_quantum}"
                    f" must be multiples of the model's "
                    f"diffusion_block_length={B}"
                )
            if self.speculative_k or self.kv_sp or self.model.sliding_window:
                raise ValueError(
                    "a block-diffusion model serves without speculative "
                    "drafting, the striped kv_sp cache or a sliding window"
                )
        if self.model.cache_arrays == 1 and self.kv_sp:
            # A latent cache held once (docs/architecture/unified_step.md):
            # the kernel, its XLA twin, int8 KV, a tp mesh, block IO (KVBM,
            # disaggregation) and prefix matching take the one array; the
            # striped scan's two-array gather has not been taught it.
            raise ValueError(
                f"{self.model.name} holds its latent cache once (one array "
                "a layer) and serves without the striped kv_sp cache"
            )
        if len(self.model.cache_groups) > 1:
            # Window and full layers side by side, for a model that says
            # ``cache_by_layer_group`` (docs/architecture/cache_groups.md;
            # Gemma-3 and Qwen2 do not, and serve over one table with
            # everything below as before): a sequence's past is a table a
            # group, and
            # the windowed group's has let go of what lies behind the
            # window, so everything that reads ONE table as the whole past
            # is off or refused, each by name.
            if self.enable_prefix_caching:
                import logging

                logging.getLogger(__name__).info(
                    "prefix caching is off for %s: a matched block of the "
                    "full-attention group has no block of the windowed "
                    "group behind it once the window has moved on",
                    self.model.name,
                )
                self.enable_prefix_caching = False
            refused = {
                "speculative drafting (speculative_k): a verify span's "
                "lookahead is not funded per group": self.speculative_k,
                "the striped kv_sp cache": self.kv_sp,
                "int8 KV (kv_quant): the per-block scales are one array "
                "over one pool": self.kv_quant,
                "block diffusion": self.model.diffusion_block_length,
                "recurrent layers": self.model.has_recurrent,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"{self.model.name} keeps its cache by layer group "
                        f"(window and full layers) and serves without {what}"
                    )
        if self.model.has_recurrent:
            # A model with recurrent (linear-attention) layers
            # (docs/architecture/unified_step.md "State that is not
            # pages"): a sequence's past is pages AND a state that only
            # its own slot holds, so everything that assumes the past is
            # pages is off or refused, each by name.
            if self.enable_prefix_caching:
                import logging

                logging.getLogger(__name__).info(
                    "prefix caching is off for %s: a matched block has "
                    "keys and values and no recurrent state behind it",
                    self.model.name,
                )
                self.enable_prefix_caching = False
            refused = {
                "speculative drafting (speculative_k): a rejected draft "
                "has already advanced the recurrent state":
                    self.speculative_k,
                "the striped kv_sp cache": self.kv_sp,
                "int8 KV (kv_quant): the recurrent state has no per-block "
                "scales": self.kv_quant,
                "a device mesh (mesh_shape): the recurrent state is not "
                "sharded": any(n > 1 for n in self.mesh_shape.values()),
                "a sliding window": self.model.sliding_window,
                "block diffusion": self.model.diffusion_block_length,
            }
            for what, on in refused.items():
                if on:
                    raise ValueError(
                        f"{self.model.name} has recurrent layers and "
                        f"serves without {what}"
                    )
        if self.warmup_gate not in self._WARMUP_GATES:
            raise ValueError(
                f"warmup_gate={self.warmup_gate!r} not in "
                f"{self._WARMUP_GATES}"
            )
        if self.speculative_probe_window < 1:
            raise ValueError(
                f"speculative_probe_window={self.speculative_probe_window} "
                f"must be >= 1"
            )
        if self.coloc not in self._COLOC_MODES:
            raise ValueError(
                f"coloc={self.coloc!r} not in {self._COLOC_MODES}"
            )
        if self.itl_slo_ms < 0:
            raise ValueError(
                f"itl_slo_ms={self.itl_slo_ms} must be >= 0 (0 = no SLO)"
            )
        if self.coloc == "adaptive":
            if self.itl_slo_ms <= 0:
                raise ValueError(
                    "coloc='adaptive' requires itl_slo_ms > 0 — the "
                    "feedback loop needs a decode ITL target to hold"
                )
            if not 1 <= self.coloc_min_quantum <= self.unified_token_budget:
                raise ValueError(
                    f"coloc_min_quantum={self.coloc_min_quantum} must "
                    f"be in [1, unified_token_budget]"
                )
        if self.max_waiting < 0 or self.max_queue_delay_s < 0:
            raise ValueError(
                "max_waiting and max_queue_delay_s must be >= 0 "
                "(0 = unbounded)"
            )
        if self.unified_token_budget < 16:
            raise ValueError(
                f"unified_token_budget={self.unified_token_budget} "
                f"must be >= 16 (one minimum bucket)"
            )
        if not 1 <= self.unified_prefill_quantum <= self.unified_token_budget:
            raise ValueError(
                f"unified_prefill_quantum="
                f"{self.unified_prefill_quantum} must be in "
                f"[1, unified_token_budget]"
            )
        # Every budget rung must be REACHABLE so warmup can compile it:
        # runtime totals snap UP onto the ladder, so a rung no span
        # combination can fill exactly would be un-warmable yet still
        # dispatched — a guaranteed mid-traffic compile. Small-context
        # configs CLAMP the budget down to the largest reachable rung
        # (the tighter ladder serves them fully) instead of erroring —
        # the default budget must stay valid on tiny test engines.
        reachable = (
            (self.max_num_seqs + self.prefill_batch)
            * (self.max_model_len - 1)
        )
        if self.unified_token_budget > reachable:
            clamped = 16
            while clamped * 2 <= reachable:
                clamped *= 2
            if clamped < 16 or reachable < 16:
                raise ValueError(
                    f"no reachable unified budget rung: (max_num_seqs + "
                    f"prefill_batch) * (max_model_len - 1) = {reachable} "
                    f"< 16; raise the slot/context limits"
                )
            import logging

            logging.getLogger(__name__).warning(
                "unified_token_budget=%d exceeds the largest fillable "
                "batch (%d); clamped to the %d-token rung — raise "
                "max_num_seqs/prefill_batch/max_model_len to serve the "
                "requested budget",
                self.unified_token_budget, reachable, clamped,
            )
            self.unified_token_budget = clamped
            # The clamp can undercut a quantum that was valid against
            # the pre-clamp budget; snap it into range.
            self.unified_prefill_quantum = min(
                self.unified_prefill_quantum, self.unified_token_budget
            )
        if self.speculative_k + 1 > self.unified_token_budget // 2:
            # compose_unified guarantees decode at least half the
            # (possibly clamped) budget; a draft-verify span must always
            # fit inside that share.
            raise ValueError(
                f"speculative_k={self.speculative_k} needs "
                f"unified_token_budget >= {2 * (self.speculative_k + 1)} "
                f"(a k+1-row verify span must fit in decode's half of "
                f"the budget)"
            )
