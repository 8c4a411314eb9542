"""ModelRunner: device state + jitted step programs.

Owns the params and the paged KV cache on device, and wraps the model's
step functions in `jit` with KV donation (in-place cache updates under XLA
buffer donation — the TPU analogue of the reference's in-place CUDA cache
writes). Sampling runs inside the step (ops/sampling.py) so only the
sampled token ids leave the device.

The serving engine has ONE step family (ROADMAP item #2, completed):
`unified_step` runs ONE ragged dispatch mixing decode lanes,
chunked-prefill quanta, and speculative draft-verify spans in a flat
token batch; the only compiled extent is the token budget
(compile_cache.token_budget ladder), so the whole warmed shape set is a
handful of programs. Three program variants share the trunk:

- **unified** (the budget ladder): plain spans; with
  ``cfg.speculative_k > 0`` the SAME ladder carries draft-verify spans
  — per-span verify logits, greedy accept-prefix, and the bonus sample
  all run in-dispatch, so spec decode adds ZERO extra programs.
  A block-diffusion model (``model.diffusion_block_length`` = B > 0)
  runs the ladder's "block" variant instead: a lane's span is a block
  of B rows, every row sampled with its confidence and the commit rule
  applied in-dispatch, the block's next pass fed from the device
  (docs/architecture/unified_step.md "The block step").
- **unified_full** (one program, top budget rung): sampling extras —
  frequency/presence penalties over the per-slot count buffer plus
  top-logprob outputs — dispatched only for batches that need them.
- **unified_mm** (one program, top budget rung): multimodal soft-prompt
  rows scattered into the flat batch (carries the extras operands too,
  so mm and extras lanes co-batch).

`unified_step` is the runner's only step entry: the engine, the check,
the stepcast leader/follower replay and the multihost bring-up utility
all dispatch through it, and the runner builds no other step program.
"""

from __future__ import annotations

import logging
from collections import Counter
from functools import lru_cache, partial
from itertools import chain
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.compile_cache import (
    CompileStats,
    WarmupPlanMixin,
    activate_cache,
    env_cache_base,
    token_budget,
)
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.flight_recorder import START_PHASES, StepPhases
from dynamo_tpu.models import llama
from dynamo_tpu.models.moe import GROUPED_MIN_EXPERTS, collect_experts_hit
from dynamo_tpu.ops.sampling import (
    MAX_LOGPROBS,
    apply_penalties,
    commit_block,
    commit_floor_rows,
    sample_tokens,
    token_logprobs,
)

logger = logging.getLogger(__name__)


class UnifiedOut(NamedTuple):
    """One unified dispatch's device-resident outputs.

    ``last``: [S] — span s's (final) sampled token; the next dispatch's
    device feed. ``toks``/``counts`` are the spec contract ([S, K+1]
    emitted rows / accepted+1 per span) on a speculative engine's
    budget-ladder program, or the block contract of a block-diffusion
    model's ([S, B] the block's ids after the pass, -1 where a row is
    still masked / rows committed by it; ``last`` is ``toks`` then: the
    device feed of the block's next pass), None otherwise."""

    last: Any
    toks: Any = None
    counts: Any = None
    #: block contract only: experts that had a row, summed over the
    #: grouped expert layers (a scalar; 0 where the model has none)
    experts_hit: Any = None
    #: the plain program of a model whose expert layers take the grouped
    #: path: [2], the experts held here that had a row and the routed (row,
    #: expert) pairs that landed on one, each summed over those layers
    moe_counts: Any = None


def _norm_sampling(sampling) -> tuple[float, int, float, int]:
    """Accept both (temp, top_k, top_p) and (temp, top_k, top_p, seed)
    lane-sampling tuples; seed -1 = unseeded."""
    if len(sampling) == 3:
        t, k, p = sampling
        return t, k, p, -1
    return tuple(sampling)


#: The nine metadata operands of ``llama.unified``, in its order.
META_SEGMENTS = (
    "token_ids", "token_pos", "slot_mapping", "token_seq", "block_tables",
    "q_start", "q_len", "kv_len", "row_start",
)


class OperandLayout(NamedTuple):
    """Where each operand of one unified dispatch lies in the ONE packed
    int32 buffer the host transfers for it (docs/architecture/
    unified_step.md "The operand buffer"). ``segs`` maps a name to
    (first word, end word, shape, dtype): float32 and uint32 rows ride
    as bit-exact views of the int32 words, bools as 0/1. ``template``
    holds every segment's padding value; a dispatch starts from a copy
    of it."""

    size: int
    segs: dict
    template: np.ndarray

    def views(self, buf: np.ndarray) -> dict:
        """Host side: each segment as a writable view of ``buf``."""
        return {
            name: buf[lo:hi]
            .view(np.int32 if dtype is bool else dtype)
            .reshape(shape)
            for name, (lo, hi, shape, dtype) in self.segs.items()
        }

    def unpack(self, packed) -> dict:
        """Program side: each segment as a static slice of ``packed``."""
        out = {}
        for name, (lo, hi, shape, dtype) in self.segs.items():
            x = packed[lo:hi].reshape(shape)
            if dtype is bool:
                x = x != 0
            elif dtype is not np.int32:
                x = jax.lax.bitcast_convert_type(x, dtype)
            out[name] = x
        return out


@lru_cache(maxsize=None)
def operand_layout(
    T: int, S: int, max_blocks_per_seq: int, speculative_k: int, variant: str
) -> OperandLayout:
    """THE source of the packed buffer's offsets, for a dispatch of
    budget ``T`` over ``S`` metadata rows. ``variant``: "plain" (the
    budget ladder), "spec" (the ladder of a speculative engine: adds
    ``drafts``/``draft_len``), "block" (the ladder of a block-diffusion
    model: adds ``tok_masked``, which rows are fed as masks) or "extras"
    (the penalties/logprob and multimodal programs: adds the count-buffer
    rows). ``+rec`` behind any of them (a model with recurrent layers)
    adds ``state_slot``: the slot of the state table each span owns (0,
    the trash slot, for an idle row). ``+grpN`` (a model whose layers fall
    into N > 1 cache groups, docs/architecture/cache_groups.md) adds a
    ``block_tables_g`` and a ``slot_mapping_g`` segment for each group g
    behind the first, which keeps the unnumbered pair: a segment a group,
    not a new operand."""
    variant, *mods = variant.split("+")
    rec = "rec" in mods
    groups = max([int(m[3:]) for m in mods if m.startswith("grp")] or [1])
    rows = [
        ("token_ids", (T,), np.int32, 0),
        ("token_pos", (T,), np.int32, -1),      # -1 = padding row
        ("slot_mapping", (T,), np.int32, 0),    # padding -> trash block 0
        ("token_seq", (T,), np.int32, 0),
        ("block_tables", (S, max_blocks_per_seq), np.int32, 0),
        ("q_start", (S,), np.int32, 0),
        ("q_len", (S,), np.int32, 0),
        ("kv_len", (S,), np.int32, 0),
        ("row_start", (S,), np.int32, 0),
        ("use_prev", (S,), bool, 0),
        ("prev_row", (S,), np.int32, 0),
        ("top_k", (S,), np.int32, 0),
        ("seed", (S,), np.int32, -1),           # -1 = unseeded
        ("temp", (S,), np.float32, 0.0),
        ("top_p", (S,), np.float32, 1.0),
        ("key", (2,), np.uint32, 0),
    ]
    if variant == "spec":
        rows += [
            ("drafts", (S, speculative_k), np.int32, 0),
            ("draft_len", (S,), np.int32, 0),
        ]
    elif variant == "block":
        rows += [("tok_masked", (T,), bool, 0)]
    elif variant == "extras":
        rows += [
            ("span_slot", (S,), np.int32, -1),
            ("counts_add", (S,), bool, 0),
            ("reset", (S,), bool, 0),
            ("freq", (S,), np.float32, 0.0),
            ("pres", (S,), np.float32, 0.0),
        ]
    else:
        assert variant == "plain", variant
    if rec:
        rows += [("state_slot", (S,), np.int32, 0)]
    for g in range(1, groups):
        rows += [
            (f"block_tables_{g}", (S, max_blocks_per_seq), np.int32, 0),
            (f"slot_mapping_{g}", (T,), np.int32, 0),
        ]
    segs, off = {}, 0
    for name, shape, dtype, _fill in rows:
        end = off + int(np.prod(shape))
        segs[name] = (off, end, shape, dtype)
        off = end
    template = np.zeros(off, np.int32)
    lay = OperandLayout(off, segs, template)
    views = lay.views(template)
    for name, _shape, _dtype, fill in rows:
        if fill:
            views[name][...] = fill
    template.flags.writeable = False
    return lay


def operand_layout_of(
    size: int, S: int, max_blocks_per_seq: int, speculative_k: int,
    variant: str,
) -> OperandLayout:
    """The layout of a packed buffer of ``size`` words: the budget is
    the one extent the program cannot read off its configuration."""
    rest = (S, max_blocks_per_seq, speculative_k, variant)
    fixed = operand_layout(0, *rest).size
    per_token = operand_layout(1, *rest).size - fixed
    lay = operand_layout((size - fixed) // per_token, *rest)
    assert lay.size == size, (size, lay.size)
    return lay


def _meta_of(seg: dict, groups: int) -> list:
    """``llama.unified``'s nine metadata operands in its order from a
    layout's segments; with several cache groups ``slot_mapping`` and
    ``block_tables`` are tuples, one entry a group."""
    meta = [seg[name] for name in META_SEGMENTS]
    if groups > 1:
        for i, name in enumerate(META_SEGMENTS):
            if name in ("slot_mapping", "block_tables"):
                meta[i] = (seg[name],) + tuple(
                    seg[f"{name}_{g}"] for g in range(1, groups))
    return meta


class _Operands(NamedTuple):
    """One dispatch's packed operands on the host: the buffer, its
    segment views, the fed tokens (a device array) and how many host
    arrays placing that feed took (0 or 1)."""

    buf: np.ndarray
    seg: dict
    prev_toks: Any
    feed_transfers: int


def _unified_warm_lanes(
    t: int, max_lanes: int, max_model_len: int, trash_table, sampling,
) -> list[tuple]:
    """Spans that fill a unified warm dispatch to EXACTLY budget ``t``:
    the budget is the compiled extent, so the warm call must land on it
    precisely. Tokens split into model-length-bounded spans across the
    metadata rows (all writes land in trash block 0)."""
    lanes = []
    remaining = t
    while remaining > 0 and len(lanes) < max_lanes:
        n = min(remaining, max_model_len - 1)
        lanes.append(([1] * n, trash_table, 0, sampling))
        remaining -= n
    if remaining > 0:
        return []  # budget unreachable at runtime too (S spans can't fill it)
    return lanes


class ModelRunner(WarmupPlanMixin):
    def __init__(
        self,
        cfg: EngineConfig,
        params=None,
        mesh=None,
        rng_seed: int = 0,
        donate_params: bool = False,
        *,
        phases: StepPhases | None = None,
        start_phases: StepPhases | None = None,
    ) -> None:
        """`donate_params=True` lets the quantize step consume the caller's
        bf16 buffers as it writes the int8 copies — halving the transient
        HBM peak during a quantized load. The caller's `params` tree is
        INVALID afterwards; only pass it when handing over ownership (the
        CLI load path does; tests that reuse a params tree must not).
        ``phases`` / ``start_phases`` are the engine's
        (engine/flight_recorder.py): a dispatch books its ``pack``, ``put``
        and ``dispatch`` to the first, and this constructor the ``weights``
        to the second: drawn, sharded or quantized, as long as the HOST is
        at it (a draw is traced, compiled and enqueued; its device time
        runs on behind the rest of the build)."""
        #: the engine thread's pass by phase (the engine's, or one nobody
        #: reads where a runner is driven alone)
        self.phases = phases or StepPhases()
        start = start_phases or StepPhases(START_PHASES, "start")
        self.cfg = cfg
        m = cfg.model
        # Compile lifecycle (engine/compile_cache.py): the persistent
        # cache must be active BEFORE the first jit below so init/quantize
        # programs also replay from disk on relaunch.
        cache_base = cfg.compile_cache_dir or env_cache_base()
        #: Where XLA's entries land (None: no persistent cache).
        self.compile_cache_dir = (
            activate_cache(cache_base) if cache_base else None
        )
        self.compile_stats = CompileStats()
        if cfg.num_nodes > 1:
            # Join the multi-host coordination service BEFORE any device
            # use so jax.devices() below enumerates every host's chips.
            from dynamo_tpu.parallel.multihost import (
                MultiHostConfig,
                initialize,
            )

            initialize(MultiHostConfig(
                cfg.coordinator, cfg.num_nodes, cfg.node_rank
            ))
        if mesh is None and cfg.mesh_shape:
            from dynamo_tpu.parallel.mesh import build_mesh

            mesh = build_mesh(cfg.mesh_shape)
        self.mesh = mesh
        self.dtype = jnp.dtype(cfg.dtype)
        # KV-cache storage dtype (docs/architecture/kv_quant.md): int8
        # blocks + per-(block, head) f32 scales under kv_quant; compute
        # (activations, q, dequantized pages) stays in `dtype`.
        self.kv_quant = cfg.kv_quant
        self.kv_dtype = (
            jnp.dtype(jnp.int8) if cfg.kv_quant == "int8" else self.dtype
        )
        #: Blocks of each cache group's pool (one group: ``num_blocks``).
        self.group_blocks = cfg.group_num_blocks
        n_groups = len(self.group_blocks)
        #: A block table's width in the packed operands: one entry where
        #: the model has no pool (nothing reads it).
        self.table_width = cfg.max_blocks_per_seq if n_groups else 1

        # Per-runner attention path (ops/attention.py AttnDispatch): the
        # Pallas kernels need D % 128 == 0 inside the kernel, so smaller
        # head dims run with lane-PADDED caches (transparent to the math —
        # see ops/pallas/attention.py; the jnp path also accepts padded
        # caches, so one allocation serves both). Under a mesh the kernels
        # run per-shard via shard_map over the tp axis — the KV cache is
        # head-sharded, so each chip's local kv-head count is what the
        # kernel sees and what the support check must use.
        from dynamo_tpu.ops import attention as attn_ops

        tp = 1
        if mesh is not None and "tp" in mesh.shape:
            tp = mesh.shape["tp"]
        # MLA models (m.is_mla) cache ONE shared latent entry per token
        # (models/llama.py _qkv_mla): the cache replicates across tp while
        # q heads shard, so the head-divisibility constraint moves from kv
        # heads to q heads.
        cache_heads = m.num_cache_heads
        self.cache_head_dim = m.kv_cache_head_dim
        heads_ok = (
            m.num_heads % tp == 0 if m.is_mla else m.num_kv_heads % tp == 0
        )
        sp = 1
        if mesh is not None and "sp" in mesh.shape:
            sp = mesh.shape["sp"]
        if cfg.kv_sp:
            if mesh is None or sp <= 1:
                raise ValueError("kv_sp requires a mesh with sp > 1")
            if cfg.num_blocks % sp != 0:
                # Blocks must not straddle sp shards (the striped
                # allocator hands shard r blocks [r*bps, (r+1)*bps)).
                raise ValueError(
                    f"num_blocks={cfg.num_blocks} must divide by sp={sp}"
                )
        # kv_sp composes with tp since r05 (heads over tp AND slots over
        # sp) and runs the Pallas kernels per (tp, sp) shard — each shard
        # streams only its own stripe of the paged cache.
        self.kv_shards = sp if cfg.kv_sp else 1
        use_pallas = False
        if attn_ops.pallas_enabled():
            from dynamo_tpu.ops.pallas.attention import (
                cache_head_dim,
                pallas_supported,
            )

            padded = cache_head_dim(m.kv_cache_head_dim)
            local_heads = cache_heads if m.is_mla else cache_heads // tp
            if heads_ok and pallas_supported(
                cfg.block_size, local_heads, padded, self.kv_dtype
            ):
                self.cache_head_dim = padded
                use_pallas = True
            else:
                # Never silent: on a TPU this is the slow path, and a
                # smoke or benchmark must be able to refuse it (the
                # choice also rides TpuEngine.readiness()).
                logger.warning(
                    "Pallas attention requested but the XLA twin serves: "
                    "shape failed the kernel gate (block_size=%d, local "
                    "cache heads=%s, head_dim=%d padded to %d, kv dtype "
                    "%s, heads %% tp=%d %s)",
                    cfg.block_size, local_heads, m.kv_cache_head_dim,
                    padded, self.kv_dtype, tp,
                    "ok" if heads_ok else "NOT divisible",
                )
        #: "pallas" | "xla" — which attention implementation this runner
        #: compiled in (DYNAMO_TPU_PALLAS is the one override).
        self.attention_path = "pallas" if use_pallas else "xla"
        self.attn = attn_ops.AttnDispatch(
            use_pallas=use_pallas, mesh=mesh, kv_replicated=m.is_mla,
            kv_sp=cfg.kv_sp,
        )
        #: What the host's count of the ragged kernel's work goes by
        #: (`_count_folds`): the long tile's rows and a fold's keys at a
        #: chip's heads, and the layers that call the kernel by their
        #: window; None where the XLA twin or the striped kv_sp scan
        #: serves. `attn_folds` is the last dispatch's (short, long) folds
        #: over those layers, `attn_folds_total` every dispatch's. The LONG
        #: spans of a latent layer held once leave that kernel for the
        #: expanded body (ops/pallas/latent_expanded.py): `attn_expanded`
        #: is the last dispatch's (spans, rows) that did, by the rule the
        #: program applies, `attn_expanded_total` every dispatch's.
        self._fold_plan = None
        #: One page descriptor's bytes on a chip, and the descriptors a fold
        #: of the kernel's ring starts (0 where it does not serve).
        self.kv_page_dma_bytes = self.page_dmas_per_fold = 0
        if use_pallas and not cfg.kv_sp and n_groups:
            from dynamo_tpu.ops.pallas.ragged_attention import (
                fold_counts,
                long_tile,
                ring_shape,
            )

            page = (
                cfg.block_size * local_heads * self.cache_head_dim
                * self.kv_dtype.itemsize
            )
            # PP pages a fold, of K and of V where they are apart, of both
            # at once where joined (`EngineConfig.cache_form`).
            streams = 2 if cfg.cache_form == "apart" else 1
            self.kv_page_dma_bytes = page * m.cache_arrays // streams
            self.page_dmas_per_fold = (
                ring_shape(page, cfg.block_size)[1] * streams
            )
            self._fold_plan = dict(
                count=partial(
                    fold_counts,
                    long_rows=long_tile(m.num_heads // tp, local_heads),
                    fold_keys=(
                        ring_shape(page, cfg.block_size)[1] * cfg.block_size
                    ),
                    diffusion_block=max(m.diffusion_block_length, 1),
                ),
                # a window no context can pass skips nothing
                layers=Counter(
                    w if w < cfg.max_model_len else 0
                    for w in (
                        m.layer_window(li) for li in range(m.num_layers)
                        if m.layer_kind(li) == "attn"
                    )
                ),
            )
        self.attn_folds = (0, 0)
        self.attn_folds_total = [0, 0]
        self.attn_expanded = (0, 0)
        self.attn_expanded_total = [0, 0]
        #: A chip's query heads where the layer body's static gates let a
        #: long span through the expanded body, else 0 (set below, once the
        #: weights are there).
        self._expand_heads = 0

        joined = cfg.cache_form == "joined"

        def kv_shape(li: int) -> tuple:
            blocks = self.group_blocks[m.layer_cache_group(li)]
            entry = (cache_heads, self.cache_head_dim)
            if joined:  # a block's keys and then its values, one page
                return (blocks, 2, cfg.block_size, *entry)
            return (blocks * cfg.block_size, *entry)

        def make_kv():
            # A layer that keeps a recurrent state has no pages: its entry
            # is empty and its state lives in `rec_state`. A layer's pages
            # are its cache group's pool: every layer's alike where the
            # model has one group. Where NO layer pages the cache is the
            # empty cache: a (k, v) pair of no slots a layer, no bytes, so
            # whoever asks a cache for its head size or dtype still can
            # (the benchmark's harness does; ROADMAP.md Design #4 says when
            # this goes and every such layer's entry becomes `()`).
            # A paged layer's arrays are the model's and the
            # configuration's to say (``ModelConfig.layer_cache_arrays``,
            # ``EngineConfig.cache_form``): keys and values apart; ONE
            # array of joined pages; or ONE array where the values are the
            # key entry's leading columns (a latent cache held once).
            def pages(li):
                if m.layer_kind(li) == "attn":
                    shape = kv_shape(li)
                elif not n_groups:
                    shape = (0, cache_heads, self.cache_head_dim)
                else:
                    return ()
                arrays = 1 if joined else m.layer_cache_arrays(li) or 2
                return tuple(
                    jnp.zeros(shape, self.kv_dtype) for _ in range(arrays)
                )

            return [pages(li) for li in range(m.num_layers)]

        def make_kv_scales():
            # Per-(layer, cache array, block, head) scales; zero = empty
            # block (the write law resets a block's scale on its first
            # slot's write, so stale scales never survive allocator reuse).
            if cfg.kv_quant != "int8":
                return None
            return jnp.zeros(
                (m.num_layers, m.cache_arrays, cfg.num_blocks, cache_heads),
                jnp.float32,
            )

        quant = cfg.quant
        # Per-matmul weight-quant policy (docs/architecture/weight_quant.md):
        # quantize-on-load per site group so the resident tree holds int8/fp8
        # data + f32 scale rows from the first moment — the bf16 copy of a
        # policy-covered matrix never materializes resident. The policy is
        # value-level: quantized sites store {"q", "s"} dicts and every
        # matmul dispatches on the VALUE (ops/quant.py qdot), so the forward
        # programs are the SAME XLA programs either way.
        wq_policy = (
            llama.WeightQuantPolicy.from_string(cfg.weight_quant)
            if cfg.weight_quant
            else None
        )
        wq_active = wq_policy is not None and wq_policy.active
        if mesh is None:
            with start.phase("weights"):
                if params is None and wq_active:
                    # Init layer-wise, straight into the policy's formats — the
                    # full bf16 tree of an 8B model would not even fit resident.
                    from dynamo_tpu.ops.quant import init_params_policy

                    params = init_params_policy(
                        jax.random.PRNGKey(rng_seed), m, wq_policy,
                        dtype=self.dtype,
                    )
                elif params is None and quant == "int8":
                    # Init layer-wise, straight into int8 — the full bf16 tree
                    # of an 8B model would not even fit on a 16 GB chip.
                    from dynamo_tpu.ops.quant import init_params_int8

                    params = init_params_int8(
                        jax.random.PRNGKey(rng_seed), m, dtype=self.dtype
                    )
                elif params is None:
                    params = llama.init_params(
                        jax.random.PRNGKey(rng_seed), m, dtype=self.dtype
                    )
                elif wq_active:
                    from dynamo_tpu.ops.quant import quantize_params_policy

                    params = jax.jit(
                        partial(
                            quantize_params_policy,
                            policy=wq_policy,
                            tie_embed=m.tie_word_embeddings,
                        ),
                        donate_argnums=(0,) if donate_params else (),
                    )(params)
                elif quant == "int8":
                    from dynamo_tpu.ops.quant import quantize_params

                    params = jax.jit(
                        partial(quantize_params, tie_embed=m.tie_word_embeddings),
                        donate_argnums=(0,) if donate_params else (),
                    )(params)
            kv_caches = make_kv()
            kv_scales = make_kv_scales()
        else:
            # Create arrays sharded from the start (init/quantize under jit
            # with out_shardings) so nothing ever materializes on one chip —
            # required for models that only fit when TP-sharded.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from dynamo_tpu.parallel.sharding import (
                kv_cache_spec,
                llama_param_specs,
                shard_params,
            )

            specs = llama_param_specs(m)
            if wq_active:
                # Scales ride as jit state beside the matrices they scale,
                # with the SAME mesh specs minus the contracted axis
                # (ops/quant.py quant_spec) — a tp-sharded matrix keeps its
                # scale row tp-sharded, so dequantize never gathers.
                from dynamo_tpu.ops.quant import (
                    quantize_param_specs_policy,
                    quantize_params_policy,
                )

                specs = quantize_param_specs_policy(
                    specs, wq_policy, tie_embed=m.tie_word_embeddings
                )
            elif quant == "int8":
                from dynamo_tpu.ops.quant import (
                    quantize_param_specs,
                    quantize_params,
                )

                specs = quantize_param_specs(
                    specs, tie_embed=m.tie_word_embeddings
                )
            p_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            with start.phase("weights"):
                if params is None:
                    def _init(key):
                        p = llama.init_params(key, m, dtype=self.dtype)
                        if wq_active:
                            p = quantize_params_policy(
                                p, wq_policy, tie_embed=m.tie_word_embeddings
                            )
                        elif quant == "int8":
                            p = quantize_params(p, tie_embed=m.tie_word_embeddings)
                        return p

                    params = jax.jit(_init, out_shardings=p_sh)(
                        jax.random.PRNGKey(rng_seed)
                    )
                elif wq_active:
                    params = jax.jit(
                        partial(
                            quantize_params_policy,
                            policy=wq_policy,
                            tie_embed=m.tie_word_embeddings,
                        ),
                        out_shardings=p_sh,
                        donate_argnums=(0,) if donate_params else (),
                    )(params)
                elif quant == "int8":
                    params = jax.jit(
                        partial(quantize_params, tie_embed=m.tie_word_embeddings),
                        out_shardings=p_sh,
                        donate_argnums=(0,) if donate_params else (),
                    )(params)
                else:
                    params = shard_params(params, mesh, cfg=m)
            kv_caches = jax.jit(
                make_kv,
                out_shardings=NamedSharding(
                    mesh,
                    kv_cache_spec(m.is_mla, sp=cfg.kv_sp, form=cfg.cache_form),
                ),
            )()
            kv_scales = None
            if cfg.kv_quant == "int8":
                # Scales shard their head axis exactly like the cache
                # heads (replicated for MLA); every other axis replicates.
                kv_scales = jax.jit(
                    make_kv_scales,
                    out_shardings=NamedSharding(
                        mesh,
                        P(None, None, None, None if m.is_mla else "tp"),
                    ),
                )()
        self.params = params
        self.kv_caches = kv_caches
        self.kv_scales = kv_scales
        #: The paged cache as allocated (``readiness()``, ``/metrics``): the
        #: bytes one cached token costs over every layer's arrays (a leaf's
        #: bytes over the tokens it holds: its slots, or its blocks'), and
        #: the arrays a paged layer is.
        self.kv_bytes_per_token = sum(
            leaf.nbytes
            // (leaf.shape[0] * (cfg.block_size if joined else 1))
            for leaf in jax.tree.leaves(kv_caches) if leaf.shape[0]
        )
        self.kv_arrays_per_layer = max(
            (len(arrays) for arrays in kv_caches), default=m.cache_arrays
        )
        #: The share of a stored page that is lane padding (0-1): a head
        #: narrower than the kernel's lane row is stored a whole row wide
        #: (ops/pallas/attention.py ``cache_head_dim``), the rest zeros.
        #: 0 wherever the head fills its row, and where no layer pages.
        self.kv_cache_lane_pad = (
            1.0 - m.kv_cache_head_dim / self.cache_head_dim
            if m.has_pool else 0.0
        )
        # The state that is not pages (docs/architecture/unified_step.md):
        # for each recurrent layer the arrays its kind keeps
        # (``ModelConfig.recurrent_state_arrays``: a delta-rule layer's
        # state and convolution tail, a retention layer's S and z) over
        # max_num_seqs + 1 slots, slot 0 the trash slot; donated through
        # every program beside the cache. None where the model has no
        # such layer: no array, no operand.
        rec_on = m.has_recurrent
        self.rec_state = None
        if rec_on:
            self.rec_state = [
                tuple(
                    jnp.zeros(shape, dt)
                    for shape, dt in m.recurrent_state_arrays(
                        li, cfg.max_num_seqs + 1, self.dtype.name
                    )
                )
                for li in m.recurrent_layers
            ]
        #: Bytes of recurrent state resident on the device (0 for a model
        #: that keeps keys and values only); fixed at construction.
        self.recurrent_state_bytes = m.recurrent_state_bytes(
            cfg.max_num_seqs + 1, self.dtype.name
        )
        #: and what ONE sequence's slot holds over all its layers
        self.recurrent_state_bytes_per_slot = m.recurrent_state_bytes(
            1, self.dtype.name
        )
        self._step = 0
        # Weight-quant observability (DT011 surfaces read these via
        # getattr): bytes saved vs a full-precision tree, fraction of
        # weight bytes quantized, and whether a policy is armed. Shape/
        # dtype math only — no device transfer, works under any mesh.
        self.weight_quant_policy = wq_policy
        self.weight_quant_active = 1.0 if wq_active else 0.0
        self.weight_quant_bytes_saved = 0.0
        self.weight_quant_density = 0.0
        if wq_active or quant == "int8":
            from dynamo_tpu.ops.quant import quant_tree_stats

            saved, density = quant_tree_stats(
                params, dtype_bytes=self.dtype.itemsize
            )
            self.weight_quant_bytes_saved = float(saved)
            self.weight_quant_density = float(density)

        # The layer body's static gates for the expanded form, mirrored
        # (models/llama.py `_layer_rows`): every layer's cache held once,
        # the Pallas path, pages and `w_uk` / `w_uv` plain arrays of the
        # model's dtype.
        if self._fold_plan is not None and m.is_mla and m.cache_arrays == 1:
            from dynamo_tpu.ops.quant import is_quantized

            if self.kv_scales is None and all(
                not is_quantized(w) and w.dtype == self.kv_dtype == self.dtype
                for layer in self.params["layers"]
                for w in (layer["w_uk"], layer["w_uv"])
            ):
                self._expand_heads = m.num_heads // tp

        bs = cfg.block_size
        attn = self.attn

        K_spec = cfg.speculative_k

        def _feed_tokens(token_ids, row_start, use_prev, prev_row, prev_toks):
            """Substitute ONLY the feeding lanes' rows: idle lanes share
            row_start 0, so a plain scatter's duplicate-index last-write
            would clobber a real lane's substituted token with the stale
            placeholder. Non-feeding lanes aim out of range and
            mode="drop" discards them."""
            T = token_ids.shape[0]
            rows = jnp.where(use_prev, row_start, T)
            return token_ids.at[rows].set(prev_toks[prev_row], mode="drop")

        def _feed_block(o, prev_ids):
            """The block step's device feed: a feeding lane's B rows are
            the PREVIOUS dispatch's block ids ([S, B], -1 = still masked)
            from its old metadata row — the ids and which rows are masks
            both come from the device, so a block's next pass is issued
            before the host has read what the last one committed."""
            T = o["token_ids"].shape[0]
            B = prev_ids.shape[1]
            rows = (
                jnp.where(o["use_prev"], o["row_start"], T)[:, None]
                + jnp.arange(B)[None, :]
            )
            fed = prev_ids[o["prev_row"]]                        # [S, B]
            o["token_ids"] = o["token_ids"].at[rows].set(
                jnp.where(fed < 0, m.mask_token_id, fed), mode="drop"
            )
            o["tok_masked"] = o["tok_masked"].at[rows].set(
                fed < 0, mode="drop"
            )

        S_rows = self.unified_slots
        MB = self.table_width
        rec_sfx = ("+rec" if rec_on else "") + (
            f"+grp{n_groups}" if n_groups > 1 else "")
        #: Does the plain program hand out the expert layers' counts? Where
        #: they take the grouped path (what the block program keys on too).
        moe_counts_on = m.is_moe and m.experts_here >= GROUPED_MIN_EXPERTS

        def _model(params, kv, kv_sc, o, meta, **kw):
            """``llama.unified`` over a dispatch's operands -> (logits, kv,
            kv_sc). ``kv`` is the cache operand as the programs donate it:
            the paged caches, bundled with the recurrent state where the
            model has recurrent layers (``_program_args``)."""
            pages, rec = kv if rec_on else (kv, None)
            out = llama.unified(
                m, params, pages, *meta, bs, attn=attn, kv_scales=kv_sc,
                rec_state=rec, state_slot=o.get("state_slot"), **kw,
            )
            kv = (out[1], out[-1]) if rec_on else out[1]
            return out[0], kv, out[2] if kv_sc is not None else None

        def _unpack(packed, variant, prev_toks):
            """The packed buffer's segments by the layout's static
            offsets, the fed tokens substituted, and ``llama.unified``'s
            nine metadata operands in its order."""
            o = operand_layout_of(
                packed.shape[0], S_rows, MB, K_spec, variant + rec_sfx
            ).unpack(packed)
            if variant == "block":
                _feed_block(o, prev_toks)
            else:
                o["token_ids"] = _feed_tokens(
                    o["token_ids"], o["row_start"], o["use_prev"],
                    o["prev_row"], prev_toks,
                )
            return o, _meta_of(o, n_groups)

        def unified_fn(params, kv, kv_sc, packed, prev_toks):
            """One ragged mixed prefill+decode dispatch (llama.unified).
            ``packed`` is the dispatch's ONE host operand (operand_layout).
            Decode spans can feed from the PREVIOUS unified dispatch's
            device-resident tokens (`use_prev`/`prev_row` map each span
            to its old metadata row), so steady-state decode never pays a
            host round trip for token values. ``kv_sc`` is the per-block
            KV scale state under kv_quant (None otherwise) — it rides
            the dispatch like the caches do, so steady-state decode pays
            no extra host traffic for quantization either. Where the
            model's expert layers take the grouped path (``moe_counts_on``)
            their counts come back behind the tokens ([2]: the experts held
            here that had a row, the routed rows that landed here, each
            summed over the grouped expert layers), as the block program
            hands out its own."""
            o, meta = _unpack(packed, "plain", prev_toks)
            with collect_experts_hit() as hit:
                logits, kv, kv_sc = _model(params, kv, kv_sc, o, meta)
            toks = sample_tokens(
                logits, o["key"], o["temp"], o["top_k"], o["top_p"],
                seed=o["seed"], sample_pos=o["kv_len"],
            )
            toks = jnp.where(o["q_len"] > 0, toks, 0)
            if not moe_counts_on:
                return toks, kv, kv_sc
            counts = jnp.stack([sum(hit), sum(hit.rows_held)])
            return toks, counts, kv, kv_sc

        def unified_spec_fn(params, kv, kv_sc, packed, prev_toks):
            """The budget-ladder program of a spec-enabled engine
            (cfg.speculative_k > 0): the SAME ragged dispatch, with
            draft-verify spans of ``q_len = draft_len + 1`` rows and the
            greedy accept-prefix law run in-dispatch. Per-span verify
            logits come back ``[S, K+1, V]`` (llama.unified verify_rows);
            acceptance, the bonus sample, and the device-side
            accepted-length output all stay on device — steady-state
            spec decode pays no extra host RTT over plain decode.

            Plain spans (draft_len = 0 — gated-off traffic, sampled
            lanes, prefill quanta) reduce EXACTLY to the non-spec
            program: their single verify row is the span's last row and
            ``sample_pos = kv_len``, so greedy streams are byte-
            identical whether speculation is configured or not. Returns
            (emitted [S, K+1], counts [S], bonus [S], kv, kv_sc) —
            row s carries counts[s] real tokens, bonus is the last
            delivered token (the device feed for the next dispatch)."""
            o, meta = _unpack(packed, "spec", prev_toks)
            drafts, draft_len = o["drafts"], o["draft_len"]
            q_len, kv_len, temp = o["q_len"], o["kv_len"], o["temp"]
            logits, kv, kv_sc = _model(          # [S, K+1, V]
                params, kv, kv_sc, o, meta,
                draft_len=draft_len, verify_rows=K_spec + 1,
            )
            greedy = jnp.argmax(logits, axis=-1)  # [S, K+1]
            matches = (drafts == greedy[:, :K_spec]) & (
                jnp.arange(K_spec)[None, :] < draft_len[:, None]
            )
            lead = jnp.cumprod(matches.astype(jnp.int32), axis=1).sum(axis=1)
            # Greedy accept-prefix law: only greedy lanes with real
            # drafts accept; sampled lanes take 0 drafts and sample from
            # their first verify row — identical to plain decode.
            eligible = (q_len > 0) & (draft_len > 0) & (temp <= 0.0)
            acc = jnp.where(eligible, lead, 0)    # [S]
            at_acc = jnp.take_along_axis(
                logits, acc[:, None, None], axis=1
            )[:, 0]                               # [S, V]
            bonus = sample_tokens(
                at_acc, o["key"], temp, o["top_k"], o["top_p"],
                seed=o["seed"], sample_pos=kv_len - draft_len + acc,
            )
            bonus = jnp.where(q_len > 0, bonus, 0)
            offs = jnp.arange(K_spec + 1)[None, :]
            dpad = jnp.pad(drafts, ((0, 0), (0, 1)))  # [S, K+1]
            emitted = jnp.where(
                offs < acc[:, None],
                dpad,
                jnp.where(offs == acc[:, None], bonus[:, None], 0),
            )
            counts = jnp.where(q_len > 0, acc + 1, 0)
            return emitted, counts, bonus, kv, kv_sc

        B_blk = m.diffusion_block_length

        def block_unified_fn(params, kv, kv_sc, packed, prev_toks):
            """(Named ``*unified_fn`` like the plain program: the device
            trace's module name is how the benchmark finds the step.)
            The budget-ladder program of a block-diffusion model
            (``m.diffusion_block_length`` = B > 0): the SAME ragged
            dispatch under the mask by block, every span's last B rows
            read for logits (``llama.unified`` verify_rows — a block pass
            IS a span of B rows, a prefill quantum's last block rides
            along unread), every row sampled with its confidence and the
            commit rule applied in-dispatch (ops/sampling.py
            commit_block). A block fed without a masked row is its commit
            pass: nothing is sampled into it and its keys and values are
            what the cache keeps. Returns (ids [S, B], counts [S], the
            experts that had a row summed over the grouped expert layers,
            kv, kv_sc)."""
            o, meta = _unpack(packed, "block", prev_toks)
            q_len = o["q_len"]
            with collect_experts_hit() as hit:
                logits, kv, kv_sc = _model(      # [S, B, V]
                    params, kv, kv_sc, o, meta,
                    draft_len=jnp.full_like(q_len, B_blk - 1),
                    verify_rows=B_blk,
                )
            experts_hit = sum(hit, jnp.zeros((), jnp.int32))
            T = o["token_ids"].shape[0]
            offs = jnp.arange(B_blk)[None, :]
            rows = jnp.clip(
                (o["row_start"] + jnp.maximum(q_len - B_blk, 0))[:, None]
                + offs, 0, T - 1,
            )
            live = offs < q_len[:, None]
            with jax.named_scope("block_sampler"):
                ids, counts = commit_block(
                    logits, o["token_ids"][rows],
                    o["tok_masked"][rows] & live, o["key"], o["temp"],
                    o["top_k"], o["top_p"], o["seed"],
                    o["kv_len"] - jnp.minimum(q_len, B_blk),
                    m.confidence_threshold,
                    commit_floor_rows(B_blk, m.denoising_steps),
                )
            return jnp.where(live, ids, 0), counts, experts_hit, kv, kv_sc

        def make_unified_extras_fn(with_mm: bool):
            """Factory for the extras variants (penalties + logprobs over
            the per-slot count buffer; ``with_mm`` adds the soft-prompt
            scatter). One program each at the TOP budget rung — extras/mm
            batches snap there, so these cost ONE warm program apiece
            instead of a second ladder."""

            def fn(params, kv, kv_sc, counts, packed, prev_toks, *mm_ops):
                o, meta = _unpack(packed, "extras", prev_toks)
                token_ids, q_len, row_start = (
                    o["token_ids"], o["q_len"], o["row_start"]
                )
                span_slot, reset = o["span_slot"], o["reset"]
                embeds, embed_mask = (
                    mm_ops if with_mm else (None, None)
                )
                logits, kv, kv_sc = _model(       # [S, V]
                    params, kv, kv_sc, o, meta,
                    embeds=embeds, embed_mask=embed_mask,
                )
                B = counts.shape[0]
                slot_clip = jnp.clip(span_slot, 0, B - 1)
                valid = (span_slot >= 0) & (span_slot < B) & (q_len > 0)
                # Reset first (re-slotted sequences inherit a stale row),
                # then count each decode span's FED token — the same
                # law the phased full program applied on scan entry.
                rs = jnp.zeros((B,), jnp.int32).at[
                    jnp.where(reset & valid, slot_clip, B)
                ].add(1, mode="drop")
                counts = jnp.where((rs > 0)[:, None], 0, counts)
                fed = token_ids[
                    jnp.clip(row_start, 0, token_ids.shape[0] - 1)
                ]
                add = o["counts_add"] & valid
                counts = counts.at[
                    jnp.where(add, slot_clip, B), fed
                ].add(add.astype(counts.dtype), mode="drop")
                pen = apply_penalties(
                    logits, counts[slot_clip], o["freq"], o["pres"]
                )
                toks = sample_tokens(
                    pen, o["key"], o["temp"], o["top_k"], o["top_p"],
                    seed=o["seed"], sample_pos=o["kv_len"],
                )
                clp, tids, tlps = token_logprobs(pen, toks)
                toks = jnp.where(q_len > 0, toks, 0)
                return toks, clp, tids, tlps, counts, kv, kv_sc

            return fn

        unified_full_fn = make_unified_extras_fn(with_mm=False)
        unified_mm_fn = make_unified_extras_fn(with_mm=True)

        if mesh is None:
            tok_sh = kv_sh = sc_sh = None
        else:
            # Pin token outputs to a REPLICATED sharding and the cache to
            # its canonical spec. On a mesh spanning multiple processes
            # (multi-host, parallel/multihost.py) every host must be able
            # to read the sampled tokens locally — an unconstrained output
            # could land shard-distributed and be unaddressable off-host.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from dynamo_tpu.parallel.sharding import kv_cache_spec

            tok_sh = NamedSharding(mesh, P())
            kv_sh = NamedSharding(
                mesh,
                kv_cache_spec(m.is_mla, sp=cfg.kv_sp, form=cfg.cache_form),
            )
            sc_sh = (
                NamedSharding(
                    mesh, P(None, None, None, None if m.is_mla else "tp")
                )
                if cfg.kv_quant == "int8"
                else None
            )

        def _jit(fn, out_sh, **kw):
            if mesh is not None:
                kw["out_shardings"] = out_sh
            return jax.jit(fn, **kw)

        self._tok_sh = tok_sh
        #: The budget ladder's operand layout variant.
        self._ladder_variant = (
            "spec" if K_spec > 0 else "block" if B_blk else "plain"
        ) + rec_sfx
        self._extras_variant = "extras" + rec_sfx
        #: Host arrays handed to the device, by the last dispatch and in
        #: all (the flight recorder and /metrics read them).
        self.operand_transfers = 0
        self.operand_transfers_total = 0
        # How a dispatch's host operand reaches the device: ONE placement
        # at the sharding the program wants. Under a mesh that is the
        # replicated token sharding, so nothing lands on chip 0 to be
        # copied on by the jitted call. A mesh spanning processes takes
        # the process-local form: `device_put` of a host array onto a
        # sharding this process cannot address whole asserts the value
        # equal across hosts first — a collective a step.
        if tok_sh is None:
            self._put = jax.device_put
        elif tok_sh.is_fully_addressable:
            self._put = partial(jax.device_put, device=tok_sh)
        else:
            self._put = partial(jax.make_array_from_process_local_data, tok_sh)
        # Stand-in for the fed tokens when no lane reads them, resident
        # and sharded like the unified programs' own token output (see
        # _unified_operands). Built by a jit so that under multi-host no
        # host array has to be checked equal across processes.
        # (A block-diffusion model feeds a block's ids: [S, B].)
        prev_shape = (self.unified_slots,) + (
            (m.diffusion_block_length,) if m.diffusion_block_length else ()
        )
        self._zero_prev = _jit(
            lambda: jnp.zeros(prev_shape, jnp.int32), tok_sh,
        )()
        if K_spec > 0:
            self._unified = _jit(
                unified_spec_fn,
                (tok_sh, tok_sh, tok_sh, kv_sh, sc_sh),
                donate_argnums=(1, 2),
            )
        elif B_blk:
            self._unified = _jit(
                block_unified_fn, (tok_sh, tok_sh, tok_sh, kv_sh, sc_sh),
                donate_argnums=(1, 2),
            )
        else:
            self._unified = _jit(
                unified_fn,
                (tok_sh,) * (1 + moe_counts_on) + (kv_sh, sc_sh),
                donate_argnums=(1, 2),
            )
        lp4 = (tok_sh, tok_sh, tok_sh, tok_sh)
        self._unified_full = _jit(
            unified_full_fn, lp4 + (tok_sh, kv_sh, sc_sh),
            donate_argnums=(1, 2, 3),
        )
        self._unified_mm = _jit(
            unified_mm_fn, lp4 + (tok_sh, kv_sh, sc_sh),
            donate_argnums=(1, 2, 3),
        )
        # Penalty/logprob count buffer ([B, V] output-token occurrence
        # counts) — engine state for the unified_full/mm variants; created
        # lazily so plain serving never allocates it.
        self._counts = None
        # Logprob arrays (chosen_lp [S], top_ids [S, K], top_lps [S, K])
        # from the most recent unified_full/mm dispatch — device-resident,
        # forced by the engine at chunk retirement only when some lane
        # asked for logprobs.
        self.last_unified_logprobs = None

    # -- warmup -------------------------------------------------------------
    def warmup(self) -> int:
        """Compile the serving shape set off the clock: the unified
        budget ladder, then the single extras/mm top-rung programs when
        configured (`warm_ops`, engine/compile_cache.py). All writes land
        in trash block 0, so the real cache/allocator state is untouched.
        Returns the number of XLA programs touched. First compiles
        dominate TTFT otherwise (seconds per shape)."""
        return self.run_warm_ops(self.warm_ops())

    def run_warm_ops(self, ops) -> int:
        n = super().run_warm_ops(ops)
        # Warm writes (trash block 0) must drain before serving reuses
        # the cache buffers under donation.
        # dynalint: allow[DT005] warmup drain, not serving: warm writes must land before donation; runs before traffic is admitted
        jax.block_until_ready(jax.tree.leaves(self.kv_caches)[0])
        return n

    def _warm_op(self, kind, t):
        """One shape → a trash-block warm call (WarmupPlanMixin).
        The whole warm surface is the unified family: the budget ladder
        (which IS the spec-verify program on a spec-enabled engine — one
        family, zero extra programs) plus one top-rung program each for
        the extras and multimodal variants when configured."""
        cfg = self.cfg
        sampling = (0.0, 0, 1.0)
        trash = self._trash_table()
        warm_lanes = _unified_warm_lanes(
            t, self.unified_slots, cfg.max_model_len, trash, sampling
        )
        if not warm_lanes:
            return None
        if kind == "unified":
            return lambda: self.unified_step(warm_lanes)
        if cfg.model.diffusion_block_length:
            # A block-diffusion model refuses penalties, logprobs and
            # multimodal inputs at the request: those programs never run.
            return None
        if kind == "unified_full":
            if not cfg.sampling_extras:
                return None
            extras = {
                "slots": [0] * len(warm_lanes),
                "counts_add": [False] * len(warm_lanes),
                "reset": [False] * len(warm_lanes),
                "freq": [0.0] * len(warm_lanes),
                "pres": [0.0] * len(warm_lanes),
            }
            return lambda: self.unified_step(warm_lanes, extras=extras)
        if kind == "unified_mm":
            if not cfg.multimodal:
                return None
            zero_seg = np.zeros((1, cfg.model.hidden_size), np.float32)
            mm = [None] * len(warm_lanes)
            mm[0] = [(0, zero_seg)]
            return lambda: self.unified_step(warm_lanes, mm=mm)
        return None

    # -- helpers ------------------------------------------------------------
    def _next_key(self) -> np.ndarray:
        """Per-step PRNG key as HOST data: (engine seed, step counter) used
        directly as threefry key words — deterministic per run, distinct
        per step, and crucially NO device dispatch (a jax.random.fold_in
        here would be one more dispatch per engine step). Seeded lanes never consume this key (ops/sampling.py
        lane_keys derives theirs from the request seed)."""
        self._step += 1
        # dynalint: allow[DT005] constructs a host uint32 pair from python ints - no device value, no sync (the whole point of this key scheme)
        return np.array(
            [self.cfg.seed & 0xFFFFFFFF, self._step & 0xFFFFFFFF], np.uint32
        )

    def ensure_counts(self):
        """Lazy [B, V] output-token count buffer for the penalties path.
        Under a mesh it is born with the sharding the extras programs
        hand it back in (as ``_zero_prev`` is): an uncommitted first
        buffer compiled them a second time on their second dispatch."""
        if self._counts is None:
            self._counts = jnp.zeros(
                (self.cfg.max_num_seqs, self.cfg.model.vocab_size),
                jnp.int32, device=self._tok_sh,
            )
        return self._counts

    def _trash_table(self):
        """A lane's block table with every slot -> trash block 0 (one a
        cache group where the model has several)."""
        trash = [0] * self.table_width
        n = len(self.group_blocks)
        return trash if n == 1 else (trash,) * n

    def slot_of(self, block_ids: list[int], position: int) -> int:
        bs = self.cfg.block_size
        return block_ids[position // bs] * bs + position % bs

    # -- block IO (KVBM G1 edge; engine-thread only) ------------------------
    @property
    def _block_shape(self) -> tuple:
        """One block as block IO moves it: [L, A, bs, H, D], A the arrays
        a layer's cache is (``ModelConfig.cache_arrays``: 2 for (k, v), 1
        for a latent held once)."""
        m = self.cfg.model
        return (
            m.num_layers, m.cache_arrays, self.cfg.block_size,
            m.num_cache_heads, self.cache_head_dim,
        )

    def gather_block(self, block_idx: int):
        from dynamo_tpu.ops.kv_copy import gather_block

        return gather_block(self.kv_caches, block_idx, self.cfg.block_size)

    def gather_block_device(self, block_idx: int):
        """Device-resident block snapshot (the HBM→HBM transfer path)."""
        from dynamo_tpu.ops.kv_copy import gather_block_device

        return gather_block_device(self.kv_caches, block_idx, self.cfg.block_size)

    def scatter_block(self, block_idx: int, data) -> None:
        """Accepts the [L, A, bs, H, D] gather layout as a host array, flat
        host bytes (same-width ints reinterpreted, e.g. uint16 ↔ bfloat16),
        or a DEVICE array from gather_block_device — the latter never
        round-trips through host memory. Under kv_quant, host bytes are
        the PACKED row form (int8 data + scale sidecar — what
        export_block_rows / the KVBM tiers emit): the scale row scatters
        alongside the data."""
        from dynamo_tpu.ops.kv_copy import scatter_block

        shape = self._block_shape
        if isinstance(data, jax.Array):
            arr = data.astype(self.kv_dtype).reshape(shape)
        elif self.kv_quant:
            from dynamo_tpu.block_manager import quant as bq

            q, scales = bq.unpack_block(data, self._quant_layout())
            arr = q
            self.set_block_scales([block_idx], scales[None])
        else:
            arr = self._normalize_block_host(data).reshape(shape)
        self.kv_caches = scatter_block(
            self.kv_caches, block_idx, self.cfg.block_size, arr
        )

    def _normalize_block_host(self, data) -> np.ndarray:
        """Host block bytes → the cache dtype: same-width ints are
        REINTERPRETED (uint16 ↔ bfloat16), width changes convert. The one
        rule both the single and batched scatter paths share."""
        arr = np.asarray(data)  # dynalint: allow[DT005] input is G2 host-tier block bytes, never a device array
        target = np.dtype(self.dtype)
        if arr.dtype != target:
            arr = (
                arr.view(target)
                if arr.dtype.itemsize == target.itemsize
                else arr.astype(target)
            )
        return arr

    def gather_many(self, block_idxs) -> np.ndarray:
        """Read N blocks to host in one device call: [N, L, A, bs, H, D]
        — one device→host round trip instead of N."""
        from dynamo_tpu.ops.kv_copy import gather_blocks

        return gather_blocks(self.kv_caches, block_idxs, self.cfg.block_size)

    def scatter_many_device(self, block_idxs, data) -> None:
        """Write N blocks from a DEVICE-resident [N, ...] snapshot in one
        program (the batched device-channel receive)."""
        from dynamo_tpu.ops.kv_copy import scatter_blocks

        shape = (len(block_idxs), *self._block_shape)
        self.kv_caches = scatter_blocks(
            self.kv_caches, block_idxs, self.cfg.block_size,
            data.astype(self.kv_dtype).reshape(shape),
        )

    def gather_many_device(self, block_idxs):
        """Batched device-resident snapshot (no host sync) — the offload
        path's TTFT-friendly form: dispatch now, materialize on the KVBM
        pump thread."""
        from dynamo_tpu.ops.kv_copy import gather_blocks_device

        return gather_blocks_device(
            self.kv_caches, block_idxs, self.cfg.block_size
        )

    def prepare_blocks_host(self, datas) -> np.ndarray:
        """Normalize/validate N host block payloads into the stacked
        [N, L, A, bs, H, D] scatter layout WITHOUT touching the device.
        Splitting this from the donated dispatch lets callers treat a bad
        row (layout drift on a shared kvbm) as recoverable — once the
        donating program is dispatched, the old cache buffers are gone."""
        shape = self._block_shape
        return np.stack([
            self._normalize_block_host(data).reshape(shape) for data in datas
        ])

    def scatter_many_prepared(self, block_idxs, rows: np.ndarray) -> None:
        """The donated dispatch half of scatter_many: `rows` must come
        from prepare_blocks_host."""
        from dynamo_tpu.ops.kv_copy import scatter_blocks

        self.kv_caches = scatter_blocks(
            self.kv_caches, block_idxs, self.cfg.block_size, rows
        )

    def scatter_many(self, block_idxs, datas) -> None:
        """Write N blocks from host arrays in one device call. `datas` is a
        sequence of per-block arrays in the scatter_block-accepted host
        layouts (gather layout or same-width byte views)."""
        self.scatter_many_prepared(
            block_idxs, self.prepare_blocks_host(datas)
        )

    # -- quantized block IO (kv_quant int8; docs/architecture/kv_quant.md) --
    @property
    def kv_bytes_ratio(self) -> float:
        """Stored-KV bytes per token relative to the compute dtype:
        1.0 unquantized; ~0.5 under int8 (data halves, the f32 scale
        sidecar adds 4B per (layer, K/V, head) per block). Advertised on
        the metric plane so the network-aware router prices transfers in
        this worker's REAL bytes."""
        if not self.kv_quant:
            return 1.0
        lay = self._quant_layout()
        return lay.block_bytes / lay.unquantized_block_bytes

    def _quant_layout(self):
        """This runner's G1 block layout as a quantized KvLayoutConfig —
        the packed-row wire/tier format for its blocks."""
        from dynamo_tpu.block_manager.config import KvLayoutConfig

        return KvLayoutConfig.for_engine(self.cfg, self.cache_head_dim)

    def gather_scales_device(self, block_idxs):
        """Device-resident [N, L, A, kvH] per-block scale rows (pairs
        with gather_many_device; no host sync)."""
        from dynamo_tpu.ops.kv_copy import gather_scales_device

        return gather_scales_device(self.kv_scales, block_idxs)

    def set_block_scales(self, block_idxs, rows) -> None:
        """Write N blocks' scale rows ([N, L, A, kvH], host or device)
        in one donated program."""
        from dynamo_tpu.ops.kv_copy import scatter_scales

        self.kv_scales = scatter_scales(self.kv_scales, block_idxs, rows)

    def export_block_rows(self, block_idxs) -> list[np.ndarray]:
        """N quantized blocks as PACKED host rows (int8 data + f32 scale
        sidecar) — the wire form disagg frames and the KVBM tiers move.
        One batched data gather + one scale gather, then per-row packs."""
        from dynamo_tpu.block_manager import quant as bq
        from dynamo_tpu.ops.kv_copy import gather_scales

        layout = self._quant_layout()
        batch = self.gather_many(block_idxs)          # [N, L, A, bs, H, D] i8
        scales = gather_scales(self.kv_scales, block_idxs)
        return [
            bq.pack_block(batch[i], scales[i], layout)
            for i in range(len(block_idxs))
        ]

    def import_host_rows(self, rows, layout):
        """Quantized host-tier/wire rows → (scatter-ready data, scale
        rows or None) under this runner's device policy: an int8 G1
        passes the packed bytes through (bit-exact); a bf16-hot G1
        dequantizes on host and scatters compute-dtype values. Validates
        BEFORE any donating dispatch (bad rows raise here)."""
        from dynamo_tpu.block_manager import quant as bq

        unpacked = [bq.unpack_block(r, layout) for r in rows]
        if self.kv_quant:
            data = np.stack([q for q, _ in unpacked])
            scales = np.stack([s for _, s in unpacked])
            return data, scales
        deq = [
            bq.dequantize_kv_block_host(q, s) for q, s in unpacked
        ]
        return self.prepare_blocks_host(deq), None

    # -- steps --------------------------------------------------------------
    @property
    def unified_slots(self) -> int:
        """Metadata rows per unified dispatch: every decode slot plus
        every concurrently-prefilling sequence can own a span."""
        return self.cfg.max_num_seqs + self.cfg.prefill_batch

    def unified_step(
        self,
        lanes: list[tuple[list[int], list[int], int, tuple]],
        feed: tuple | None = None,
        draft_lens: list[int] | None = None,
        extras: dict | None = None,
        mm: list | None = None,
        state_slots: list[int] | None = None,
    ) -> "UnifiedOut":
        """ONE ragged dispatch for a mixed prefill+decode batch.

        ``lanes``: [(new_tokens, block_ids, prefix_len, sampling), ...] —
        span s of the flat batch is lane s's tokens; a decode lane is a
        single token, a prefill quantum its chunk, a draft-verify span
        the fed token plus its drafts, a block-diffusion model's block
        pass the block's ids with -1 where a row is fed as a mask.
        Total tokens snap UP to the
        budget ladder (compile_cache.token_budget) — the ONLY compiled
        extent, in place of the phase×bucket×lane grid.

        ``feed``: optional (prev_toks_device [S], prev_row [S],
        use_prev [S]) — decode lanes whose token was sampled by the
        previous unified dispatch read it on DEVICE from its old
        metadata row instead of a host round trip.

        ``draft_lens``: per-lane count of DRAFT tokens in the lane's
        tail (speculative verify spans; requires cfg.speculative_k > 0).
        The accept-prefix law runs in-dispatch and UnifiedOut carries
        (toks [S, K+1], counts [S]) device arrays.

        ``extras``: {"slots", "counts_add", "reset", "freq", "pres"}
        per-lane arrays — dispatches the unified_full variant (penalties
        + logprob outputs over the per-slot count buffer) at the TOP
        budget rung; logprob arrays land in ``last_unified_logprobs``.

        ``mm``: per-lane multimodal segment lists ((chunk-relative
        offset, [n, hidden]) pairs, None for text lanes) — dispatches
        the unified_mm variant (top rung; carries the extras operands
        so mm and extras lanes co-batch).

        ``state_slots``: per-lane slot of the recurrent-state table
        (1..max_num_seqs; a model with recurrent layers only). A lane
        whose ``prefix_len`` is 0 starts from zeros in the program; lanes
        given no slot (warmup) aim at the trash slot 0.

        Returns a UnifiedOut of DEVICE arrays (not forced — the engine
        pipelines the fetch): ``last`` [S] is span s's (last) sampled
        token, and under the spec contract ``toks`` [S, K+1] /
        ``counts`` [S] carry the accepted drafts + bonus."""
        cfg = self.cfg
        S = self.unified_slots
        assert len(lanes) <= S, f"{len(lanes)} lanes > {S} metadata rows"
        total = sum(len(t) for t, _, _, _ in lanes)
        use_mm = mm is not None and any(seg for seg in mm)
        use_full = use_mm or extras is not None
        if use_full:
            # The extras/mm variants are warmed at ONE rung (the top of
            # the ladder) — rare-path batches pad there instead of
            # doubling the warmed program count per variant.
            T = token_budget(cfg.unified_token_budget, cfg.unified_token_budget)
        else:
            T = token_budget(total, cfg.unified_token_budget)
        assert total <= T, (
            f"{total} tokens exceed the unified budget "
            f"{cfg.unified_token_budget}"
        )
        variant = self._extras_variant if use_full else self._ladder_variant
        phase = self.phases.phase
        with phase("pack"):
            base_args, _meta, ops = self._unified_operands(
                lanes, feed, T, variant
            )
        seg = ops.seg
        seg["key"][:] = self._next_key()
        if state_slots is not None:
            seg["state_slot"][: len(lanes)] = state_slots
        base_args = self._program_args(base_args)
        mm_args = ()
        if use_full:
            if extras is not None:
                n_l = len(lanes)
                seg["span_slot"][:n_l] = extras["slots"]
                seg["counts_add"][:n_l] = extras["counts_add"]
                seg["reset"][:n_l] = extras["reset"]
                seg["freq"][:n_l] = extras["freq"]
                seg["pres"][:n_l] = extras["pres"]
            if use_mm:
                D = cfg.model.hidden_size
                embeds = np.zeros((T, D), np.float32)
                mask = np.zeros(T, bool)
                row_start, q_len = seg["row_start"], seg["q_len"]
                for s, segs in enumerate(mm):
                    if not segs:
                        continue
                    r0 = row_start[s]
                    n = q_len[s]
                    for off, mm_seg in segs:
                        # dynalint: allow[DT005] mm embeddings arrive as host arrays from the preprocessor; dtype view, not a device fetch
                        mm_seg = np.asarray(mm_seg, np.float32)
                        w = min(len(mm_seg), max(0, int(n) - off))
                        if w <= 0 or off < 0:
                            continue
                        embeds[r0 + off : r0 + off + w] = mm_seg[:w]
                        mask[r0 + off : r0 + off + w] = True
                with phase("put"):
                    mm_args = (self._put(embeds), self._put(mask))
        elif variant == "spec" and draft_lens is not None:
            drafts, dlen = seg["drafts"], seg["draft_len"]
            for s, dl in enumerate(draft_lens):
                if dl:
                    dlen[s] = dl
                    drafts[s, :dl] = lanes[s][0][-dl:]
        self.operand_transfers = 1 + ops.feed_transfers + len(mm_args)
        self.operand_transfers_total += self.operand_transfers
        with phase("put"):
            packed = self._put(ops.buf)

        if use_full:
            kind = "unified_mm" if use_mm else "unified_full"
            program = self._unified_mm if use_mm else self._unified_full
            with phase("dispatch"), self.compile_stats.observe(kind, t=T):
                (
                    toks, clp, tids, tlps, self._counts, kv, self.kv_scales,
                ) = program(
                    *base_args, self.ensure_counts(), packed,
                    ops.prev_toks, *mm_args,
                )
            self._set_kv(kv)
            self.last_unified_logprobs = (clp, tids, tlps)
            return UnifiedOut(last=toks, toks=None, counts=None)

        with phase("dispatch"), self.compile_stats.observe("unified", t=T):
            out = self._unified(*base_args, packed, ops.prev_toks)
        *heads, kv, self.kv_scales = out
        self._set_kv(kv)
        if variant == "spec":
            toks2d, counts, bonus = heads
            return UnifiedOut(last=bonus, toks=toks2d, counts=counts)
        if variant == "block":
            ids, counts, hit = heads
            # `ids` is the next dispatch's device feed.
            return UnifiedOut(
                last=ids, toks=ids, counts=counts, experts_hit=hit
            )
        toks, *moe_counts = heads
        return UnifiedOut(last=toks, moe_counts=(moe_counts or [None])[0])

    def _program_args(self, base_args: tuple) -> tuple:
        """``_unified_operands``' (params, caches, scales) as the programs
        take them: the recurrent state rides beside the pages in the cache
        operand, donated with them."""
        if self.rec_state is None:
            return base_args
        params, pages, scales = base_args
        return params, (pages, self.rec_state), scales

    def _set_kv(self, kv) -> None:
        """Take a program's cache output back: the pages and, where the
        model has recurrent layers, their state beside them."""
        if self.rec_state is not None:
            self.kv_caches, self.rec_state = kv
        else:
            self.kv_caches = kv


    def _unified_operands(self, lanes, feed, T: int, variant=None):
        """One dispatch of ``lanes`` padded to budget ``T``, on the
        host: ``(params, caches, scales)``, ``llama.unified``'s nine
        metadata arrays in its order (views of the packed buffer — the
        benchmark's check feeds them to the model function), and the
        packed ``_Operands`` every program variant takes. Nothing is
        transferred here but a host feed whose values are read."""
        cfg = self.cfg
        S = self.unified_slots
        bs = cfg.block_size
        lay = operand_layout(
            T, S, self.table_width, cfg.speculative_k,
            variant or self._ladder_variant,
        )
        # A fresh buffer every dispatch: at pipeline depth 2 the
        # previous one may still be in flight to the device.
        buf = lay.template.copy()
        seg = lay.views(buf)
        n_l = len(lanes)
        self.attn_folds = self.attn_expanded = (0, 0)
        if n_l:
            q_len = np.fromiter((len(t) for t, _, _, _ in lanes), np.int32, n_l)
            prefix = np.fromiter((p for _, _, p, _ in lanes), np.int32, n_l)
            row_start = np.cumsum(q_len, dtype=np.int32) - q_len
            total = int(row_start[-1] + q_len[-1])
            seg["row_start"][:n_l] = row_start
            seg["q_start"][:n_l] = prefix
            seg["q_len"][:n_l] = q_len
            kv_len = prefix + q_len
            seg["kv_len"][:n_l] = kv_len
            self._count_folds(prefix, q_len, kv_len, T)
            # A table and the written rows' slots for each cache group; the
            # first group's pair of segments is the unnumbered one.
            n_groups = len(self.group_blocks)
            tables = [seg["block_tables"]] + [
                seg[f"block_tables_{g}"] for g in range(1, n_groups)]
            slots = [seg["slot_mapping"]] + [
                seg[f"slot_mapping_{g}"] for g in range(1, n_groups)]
            temp, top_k, top_p, seed = (
                seg["temp"], seg["top_k"], seg["top_p"], seg["seed"]
            )
            # A lane's second place is its table, or one table a group:
            # the lanes' ids, one list a group.
            # (A model with no pool has no table to fill: whatever a lane
            # carries there is not read.)
            lane_ids = [block_ids for _, block_ids, _, _ in lanes]
            ids_of = [lane_ids] if n_groups == 1 else zip(*lane_ids)
            for table, ids_g in zip(tables[:n_groups], ids_of):
                for s, ids in enumerate(ids_g):
                    table[s, : len(ids)] = ids
            for s, (_toks, _ids, _prefix, sampling) in enumerate(lanes):
                temp[s], top_k[s], top_p[s], seed[s] = _norm_sampling(sampling)
            # Every token's span, position and cache slot by array
            # arithmetic over the flat batch, not a Python loop a token.
            token_seq = np.repeat(np.arange(n_l, dtype=np.int32), q_len)
            token_pos = np.arange(total, dtype=np.int32) - np.repeat(
                row_start - prefix, q_len
            )
            seg["token_ids"][:total] = np.fromiter(
                chain.from_iterable(t for t, _, _, _ in lanes), np.int32, total
            )
            if "tok_masked" in seg:
                # A negative id is a row fed as a mask (block diffusion).
                ids = seg["token_ids"][:total]
                masked = ids < 0
                seg["tok_masked"][:total] = masked
                ids[masked] = cfg.model.mask_token_id
            seg["token_seq"][:total] = token_seq
            seg["token_pos"][:total] = token_pos
            for table, slot in zip(tables[:n_groups], slots):
                slot[:total] = (
                    table[token_seq, token_pos // bs] * bs + token_pos % bs
                )

        prev_toks = None
        if feed is not None:
            prev_toks, prev_row, use_prev = feed
            seg["prev_row"][:] = prev_row
            seg["use_prev"][:] = use_prev
        feed_transfers = 0
        if not isinstance(prev_toks, jax.Array):
            # The fed tokens must carry the SAME sharding whether they
            # are a previous dispatch's device output or a stand-in:
            # input shardings are part of jit's cache key, and under a
            # mesh a plain host array here compiled one program at
            # warmup and a second, UNCOUNTED one per budget rung on the
            # first fed dispatch mid-traffic (four chips, Llama-3.1-8B
            # tp=4: 78 s for ten requests — PERF.md, PR 22).
            if prev_toks is None or not seg["use_prev"].any():
                # No lane reads it (warmup, a first dispatch, a
                # follower's placeholder): the resident zeros, no
                # transfer and no cross-host check per step.
                prev_toks = self._zero_prev
            else:
                # A replayed host feed whose values ARE read.
                with self.phases.phase("put"):
                    prev_toks = self._put(prev_toks)
                feed_transfers = 1
        base_args = (self.params, self.kv_caches, self.kv_scales)
        meta_args = tuple(_meta_of(seg, len(self.group_blocks)))
        return base_args, meta_args, _Operands(buf, seg, prev_toks, feed_transfers)

    def _count_folds(self, q_start, q_len, kv_len, T: int) -> None:
        """Note how the dispatch's kernel work divides between the
        kernel's two tiles: the folds of the ring its SHORT tile (decode
        rows, diffusion blocks) and its LONG tile (prefill quanta, verify
        spans) walk, summed over the layers that call it (ops/pallas/
        ragged_attention.py ``fold_counts``; the engine's thread, every
        dispatch: microseconds). The spans a latent layer held once sends
        through the expanded body at this rung of ``T`` rows are counted
        apart (``attn_expanded``: spans, rows) by the program's own rule
        (ops/pallas/latent_expanded.py ``expanded_spans``), and are no
        folds of the ragged kernel's."""
        plan = self._fold_plan
        if plan is None:
            return
        if self._expand_heads:
            from dynamo_tpu.ops.pallas.latent_expanded import (
                expanded_k,
                expanded_spans,
            )

            k = expanded_k(self.cfg.model, T, self._expand_heads)
            gone = expanded_spans(q_len, kv_len, k) if k else None
            if gone is not None and gone.any():
                self.attn_expanded = (int(gone.sum()), int(q_len[gone].sum()))
                self.attn_expanded_total[0] += self.attn_expanded[0]
                self.attn_expanded_total[1] += self.attn_expanded[1]
                q_start, q_len, kv_len = (
                    a[~gone] for a in (q_start, q_len, kv_len))
                if not len(q_len):
                    return
        short = long = 0
        for window, layers in plan["layers"].items():
            s, l = plan["count"](q_start, q_len, kv_len, window=window)
            short += layers * s
            long += layers * l
        self.attn_folds = (short, long)
        self.attn_folds_total[0] += short
        self.attn_folds_total[1] += long

    def lower_unified_top(self):
        """Lower (not compile, not run) this runner's own plain unified
        program at the top budget rung from the warmup's lanes — what a
        smoke inspects for the attention kernel's custom call."""
        cfg = self.cfg
        T = token_budget(cfg.unified_token_budget, cfg.unified_token_budget)
        lanes = _unified_warm_lanes(
            T, self.unified_slots, cfg.max_model_len,
            self._trash_table(), (0.0, 0, 1.0),
        )
        # The key segment stays zero: _next_key() would advance the run.
        base, _meta, ops = self._unified_operands(lanes, None, T)
        return self._unified.lower(
            *self._program_args(base), self._put(ops.buf), ops.prev_toks
        )

    def unified_executables(self) -> int:
        """Executables the plain unified jit holds. jit's own count also
        sees a recompile that keeps (kind, budget) and changes an input
        sharding or dtype, which CompileStats cannot."""
        return self._unified._cache_size()

