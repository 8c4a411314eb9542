"""Attention over a paged KV cache — jnp reference implementations.

The cache layout is the contract shared with the Pallas kernels
(ops/pallas/): per layer, ``k_cache/v_cache: [num_slots, n_kv_heads,
head_dim]`` where ``num_slots = num_blocks * block_size`` and block ``b``
owns slots ``[b*block_size, (b+1)*block_size)``. A sequence's KV lives in
the blocks listed by its block table, in order; the global position of a
token equals its index in that slot ordering. Block 0 is the trash block:
padded query positions write there and it is never allocated.

A (k, v) layer's pages come in two forms (docs/architecture/
unified_step.md "Three forms of a layer's pages"; ``EngineConfig.
cache_form`` decides, everything that is handed the arrays reads the form
off them in ONE function, ``page_form``). APART, the two arrays above.
JOINED, ONE array
``[num_blocks, 2, block_size, n_kv_heads, head_dim]`` in which block ``b``
is one contiguous page, its keys and then its values: the layer writes both
with one scatter (over the pages as rows), the ragged kernel streams a page with one descriptor, and
the twin gathers both from the one array. A latent cache HELD ONCE is one
``[num_slots, 1, head_dim]`` array whose keys hold the values.

Both prefill and decode process key blocks with an online-softmax scan
(flash-attention style) so peak memory is one key block per step — no
materialized [ctx, ctx] score matrices and no full-cache gather. This is
the XLA-friendly formulation (static shapes, lax.scan); the Pallas kernels
keep the same math but stream pages HBM→VMEM explicitly.

Role of the reference's engine-internal attention (delegated to vLLM/FA in
the reference — here first-class, per SURVEY.md §2 'Native components' #3).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def page_form(k_cache, v_cache=None) -> str:
    """THE place that reads the form of a layer's pages (``CACHE_FORMS``)
    off the arrays a call is handed (the layer body, the attention call,
    both kernels' wrappers and block IO ask here). "apart": K and V, an
    array ``[num_slots, heads, D]`` each. "once": ONE such array whose keys
    hold the values. "joined": ONE array ``[num_blocks, 2, block_size,
    heads, D]``, told by its rank AND its pair axis (``v_cache`` is then
    None or the same array). Any other operand is refused, so that a cache
    of another shape never passes for one of these."""
    if k_cache.ndim == 5 and k_cache.shape[1] == 2 and (
        v_cache is None or v_cache.shape == k_cache.shape
    ):
        return "joined"
    if k_cache.ndim == 3 and (v_cache is None or v_cache.ndim == 3):
        return "apart" if v_cache is not None else "once"
    raise ValueError(
        f"no form of a layer's pages: {k_cache.shape}, "
        f"{None if v_cache is None else v_cache.shape}"
    )


def join_pages(k, v, block_size: int):
    """K and V ``[num_slots, kvH, D]`` as ONE array of joined pages
    ``[num_blocks, 2, block_size, kvH, D]`` (tests and tools build their
    caches with it; the engine allocates the joined array outright)."""
    return jnp.stack(
        [a.reshape(-1, block_size, *a.shape[1:]) for a in (k, v)], axis=1
    )


def pallas_enabled() -> bool:
    """Use the Pallas kernels (ops/pallas/) for paged attention.

    Default: on for real TPU backends (compiled Mosaic kernels); off
    elsewhere (interpret mode is a correctness tool, far too slow to be a
    default on CPU). ``DYNAMO_TPU_PALLAS=1/0`` overrides either way — the
    A/B switch for benches and the CPU-interpret path for tests.
    """
    env = os.environ.get("DYNAMO_TPU_PALLAS")
    if env is not None:
        return env.lower() not in ("0", "false", "off")
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class AttnDispatch:
    """Per-runner attention path selection (threaded through the model fns
    instead of process-global state, so two runners in one process — e.g. a
    sharded server plus a single-chip sidecar — never fight over a global).

    With a mesh, the Pallas kernels run under ``shard_map`` over the ``tp``
    axis: the KV cache is head-sharded (parallel/sharding.py kv_cache_spec),
    queries arrive head-sharded from the column-parallel q projection, and
    attention is embarrassingly parallel over kv-head groups — each chip
    runs the kernel on its local heads with zero cross-chip traffic.
    (pallas_call has no GSPMD partitioning rule; shard_map is the supported
    way to place a kernel per-shard.)
    """

    use_pallas: bool = False
    mesh: object | None = None  # jax.sharding.Mesh when TP-sharded
    tp_axis: str = "tp"
    # MLA models: the cache is ONE shared latent head per token, so it
    # replicates across tp while q heads shard — each shard runs the
    # kernel on its local q heads against the full cache.
    kv_replicated: bool = False
    # Long-context mode: the paged cache's SLOT axis is sharded over the
    # sp mesh axis (total KV = sp x one device's arrays); attention runs
    # per-shard partials merged with a logsumexp combine. Composes with
    # tp (heads shard over tp AND slots over sp) and with the Pallas
    # kernels (per-shard kernel call over a compacted stripe of the block
    # table, logsumexp stats merged across sp). Requires the striped
    # allocator: logical block i of a sequence lives on shard i % sp.
    kv_sp: bool = False

    def _wrap(self, fn, in_specs, out_specs):
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    @property
    def _ax(self):
        """The tp axis name if the mesh has one (head-sharded kernels),
        else None (fully replicated per-device kernels — e.g. a dp-only
        mesh, where pallas_call still needs shard_map placement because
        GSPMD has no partitioning rule for it)."""
        shape = getattr(self.mesh, "shape", {})
        return self.tp_axis if self.tp_axis in shape else None

    @property
    def _sp_n(self) -> int:
        return getattr(self.mesh, "shape", {}).get("sp", 1)

    def _kv_sp_specs(self):
        """(q/out spec, cache spec) for the kv_sp shard_map: q and out are
        head-sharded over tp (replicated if no tp axis / MLA-replicated
        cache keeps its heads whole), cache is slot-sharded over sp and
        head-sharded over tp."""
        from jax.sharding import PartitionSpec as P

        kv_ax = None if self.kv_replicated else self._ax
        return P(None, self._ax, None), P("sp", kv_ax, None)

    @staticmethod
    def _stats_merge(out, m, l, axis: str):
        """Merge per-shard NORMALIZED outputs + logsumexp stats (m, l)
        across `axis`: out_r = acc_r / l_r, so acc_g = Σ out_r·l_r·e^(m_r−m_g)
        and l_g = Σ l_r·e^(m_r−m_g). Empty shards (l=0, m=−inf) weigh 0."""
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g) * l
        l_g = jax.lax.psum(w, axis)
        o = jax.lax.psum(out.astype(jnp.float32) * w[..., None], axis)
        return jnp.where(
            l_g[..., None] > 0, o / jnp.maximum(l_g[..., None], 1e-30), 0.0
        )

    def _stripe_tables(self, block_tables, local_blocks: int):
        """This sp shard's stripe of the block tables, localized: column j
        holds the LOCAL page id of logical page r + j·sp (r = shard index).
        Entries outside the shard (impossible under the striped allocator;
        padding zeros on r>0) clip into range — their key positions land
        ≥ context and mask out."""
        sp = self._sp_n
        r = jax.lax.axis_index("sp")
        max_blocks = block_tables.shape[-1]
        cols = jnp.minimum(
            r + jnp.arange(-(-max_blocks // sp)) * sp, max_blocks - 1
        )
        local = jnp.take(block_tables, cols, axis=-1) - r * local_blocks
        return jnp.clip(local, 0, local_blocks - 1), r

    def _kv_sp_decode(self, qp, k_cache, v_cache, tables, ctx,
                      block_size: int, window: int):
        """Shared striped-scan body for every decode-shaped kv_sp call
        (one query row per table row): each sp shard scans only its own
        stripe of the paged cache and partials merge with the logsumexp
        combine. ``decode`` feeds per-LANE tables; ``ragged`` reduces
        its flat batch to per-TOKEN tables and reuses this verbatim."""
        from jax.sharding import PartitionSpec as P

        sp = self._sp_n
        qh, sp_cache = self._kv_sp_specs()
        if self.use_pallas:
            from dynamo_tpu.ops.pallas import paged_decode_attention_pallas

            def body(qs, ks, vs, bt, c):
                lt, r = self._stripe_tables(bt, ks.shape[0] // block_size)
                o, m, l = paged_decode_attention_pallas(
                    qs, ks, vs, lt, c, block_size, window=window,
                    page_offset=jnp.reshape(r, (1,)), page_stride=sp,
                    with_stats=True,
                )
                return self._stats_merge(o, m, l, "sp").astype(qs.dtype)

        else:
            body = partial(
                paged_decode_attention_sp, block_size=block_size,
                window=window, num_shards=sp,
            )
        return self._wrap(
            body,
            in_specs=(qh, sp_cache, sp_cache, P(), P()),
            out_specs=qh,
        )(qp, k_cache, v_cache, tables, ctx)

    def ragged(
        self, q, k_cache, v_cache, block_tables, token_seq, token_pos,
        q_start, q_len, kv_len, row_start, block_size: int, window: int = 0,
        k_scales=None, v_scales=None, diffusion_block: int = 1,
    ):
        """Unified mixed prefill+decode attention over one flat ragged
        token batch (the single-dispatch step — ops/pallas/
        ragged_attention.py). Token-level metadata (``token_seq`` /
        ``token_pos``) drives the XLA twin; span-level metadata drives
        the kernel. Both views describe the same batch and the runner
        builds them together (engine/runner.py unified_step).

        ``k_scales``/``v_scales`` ([num_blocks, kvH] float32) flip the
        int8-KV path on: the cache holds int8 pages that dequantize by
        per-(block, head) scale inside whichever implementation runs
        (kernel in-register, oracle on the gathered page). Under a mesh
        the scales head axis shards exactly like the cache heads.

        ``diffusion_block = B > 1``: the mask is by block of ``B``
        positions (a query sees its own block whole and every earlier
        one), in the kernel and in the twin alike; ``1`` is causal.

        ``v_cache=None``: a latent cache held once (docs/architecture/
        unified_step.md). The values are the key entry's own leading
        columns, so every implementation takes them from the key slot:
        the kernel streams ONE array and the twin gathers one (the
        striped kv_sp scan is refused: ``EngineConfig.validate``). The
        output is then ``P @ K`` at the key's width, whose columns past
        ``kv_lora_rank`` the caller does not read (models/llama.py
        ``_mla_out``); ``v_scales`` is ``k_scales``. ``v_cache=None`` over
        an array of JOINED pages (``page_form``): the values are the second
        half of each block's page; the kernel streams the one array, a
        descriptor a page, and the twin gathers keys and values from it
        (never int8, never under kv_sp: ``EngineConfig.cache_form``)."""
        D = q.shape[-1]
        qp = _pad_q_for_cache(q, k_cache)
        once = v_cache is None
        joined = page_form(k_cache, v_cache) == "joined"
        if once:
            v_scales = k_scales
        assert diffusion_block == 1 or not self.kv_sp, (
            "the striped kv_sp scan is causal only"
        )
        if self.kv_sp:
            # Slot-sharded cache: the ragged batch is exactly batched
            # decode attention with per-TOKEN block tables (the oracle's
            # own reduction), so the striped-scan machinery the decode
            # path already runs applies verbatim with T in place of B.
            # (kv_quant × kv_sp stays rejected at config validation.)
            tok_tables = jnp.take(
                block_tables,
                jnp.clip(token_seq, 0, block_tables.shape[0] - 1),
                axis=0,
            )  # [T, max_blocks]
            ctx = jnp.maximum(token_pos + 1, 0)
            assert not once, "one array a layer serves without kv_sp"
            out = self._kv_sp_decode(
                qp, k_cache, v_cache, tok_tables, ctx, block_size, window
            )
            return out[..., :D]
        if not self.use_pallas:
            out = ragged_paged_attention(
                qp, k_cache, k_cache if once else v_cache, block_tables,
                token_seq, token_pos, block_size, window,
                k_scales=k_scales, v_scales=v_scales,
                diffusion_block=diffusion_block, kv_len=kv_len,
            )
        else:
            from dynamo_tpu.ops.pallas.ragged_attention import (
                ragged_paged_attention_pallas,
            )

            base = partial(
                ragged_paged_attention_pallas, block_size=block_size,
                window=window, diffusion_block=diffusion_block,
            )
            if once:
                # One array and one scale array: (q, k, tables, qs, ql, kv,
                # rs[, ks]) is the positional layout shard_map maps onto.
                def fn(qx, kx, bt, a, b, c, d, ks=None):  # noqa: E306
                    return base(qx, kx, None, bt, a, b, c, d, k_scales=ks)
            elif k_scales is not None:
                # Keyword-forward the trailing scale operands so the
                # positional layout shard_map maps in_specs onto stays
                # (q, k, v, tables, qs, ql, kv, rs[, ks, vs]).
                def fn(qx, kx, vx, bt, a, b, c, d, ks, vs):  # noqa: E306
                    return base(
                        qx, kx, vx, bt, a, b, c, d, k_scales=ks, v_scales=vs
                    )
            else:
                fn = base
            arrays = (k_cache,) if once else (k_cache, v_cache)
            scales = () if k_scales is None else (k_scales, v_scales)
            scales = scales[: len(arrays)]
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                qh = P(None, self._ax, None)
                kv_ax = None if self.kv_replicated else self._ax
                kvh = (
                    P(None, None, None, kv_ax, None) if joined
                    else P(None, kv_ax, None),
                ) * len(arrays)
                # Scales shard their head axis with the cache heads
                # (replicated for MLA / headless meshes).
                sc = (P(None, kv_ax),) * len(scales)
                fn = self._wrap(
                    fn,
                    in_specs=(qh, *kvh, P(), P(), P(), P(), P(), *sc),
                    out_specs=qh,
                )
            out = fn(
                qp, *arrays, block_tables, q_start, q_len, kv_len, row_start,
                *scales,
            )
        return out[..., :D]

    def latent_expanded(
        self, q, k_cache, w_uk, w_uv, block_tables, q_start, q_len,
        row_start, block_size: int, *, scale: float,
    ):
        """The LONG spans of a latent layer whose cache is held once, in
        the expanded form (ops/pallas/latent_expanded.py; the Pallas path
        only: the twin stays absorbed): ``q`` ``[T, H, nope + rope]``
        un-absorbed, ``q_len`` the long spans' rows and 0 elsewhere;
        returns the up-projected values ``[T, H, v]``, zeros on every other
        row. Under a mesh each shard runs its own query heads against the
        replicated array, ``w_uk`` / ``w_uv`` sharded with them."""
        from dynamo_tpu.ops.pallas.latent_expanded import (
            ragged_paged_attention_pallas_expanded,
        )

        fn = partial(
            ragged_paged_attention_pallas_expanded, block_size=block_size,
            scale=scale,
        )
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            qh, wh = P(None, self._ax, None), P(self._ax, None, None)
            fn = self._wrap(
                fn, in_specs=(qh, P(), wh, wh, P(), P(), P(), P()),
                out_specs=qh,
            )
        return fn(
            q, k_cache, w_uk, w_uv, block_tables, q_start, q_len, row_start)


def _pad_q_for_cache(q, k_cache):
    """Lane-pad q to a padded cache's head dim (ops/pallas/attention.py
    cache-layout contract). Every implementation scales scores by
    1/sqrt(q.shape[-1]), so pre-scale by sqrt(Dc/D) to keep the net scale
    at the TRUE head dim; the zero lanes are otherwise transparent."""
    D, Dc = q.shape[-1], k_cache.shape[-1]
    if Dc == D:
        return q
    q = (q * jnp.asarray((Dc / D) ** 0.5, q.dtype)).astype(q.dtype)
    return jnp.pad(q, ((0, 0),) * (q.ndim - 1) + ((0, Dc - D),))


def _safe_div(acc: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """acc / l, returning 0 where nothing was attended (fully masked)."""
    return jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0)


def _dequant_rows(vals, entry, scales):
    """Per-block dequant for a gathered page: ``vals`` [..., bs, kvH, D]
    float32 (cast from int8), ``entry`` the physical block id(s) ([] or
    [B]), ``scales`` [num_blocks, kvH]. This is the oracle half of the
    exact-contract arithmetic the Pallas ragged kernel performs
    in-register (ops/pallas/ragged_attention.py): int8 * scale, nothing
    else."""
    s = scales[entry]                       # [kvH] or [B, kvH]
    return vals * s[..., None, :, None]


def _prefill_partials(
    q, k_cache, v_cache, block_table, q_start, total_len, block_size: int,
    slot_fn, window: int = 0, page_offset=0, page_stride: int = 1,
    k_scales=None, v_scales=None,
):
    """Online-softmax scan core for one lane's prefill attention; returns
    the UN-normalized partials (m, l, acc) so both the plain path
    (normalize locally) and the sp-sharded path (merge across shards
    first) share one copy of the math. ``slot_fn(cache, slots) ->
    (indices, ownership_mask)`` translates global slot ids; the identity
    hook owns everything.

    ``page_offset``/``page_stride`` restrict the scan to logical pages
    ``offset, offset+stride, offset+2*stride, ...`` — the striped-scan
    mode where sp shard r (holding the blocks the striped allocator
    placed at logical indices ≡ r mod sp) scans ONLY its own pages, so
    attention FLOPs partition sp-ways along with the memory."""
    T, H, D = q.shape
    kvH = k_cache.shape[1]
    G = H // kvH
    scale = 1.0 / (D**0.5)
    qr = (q.astype(jnp.float32) * scale).reshape(T, kvH, G, D)
    q_pos = q_start + jnp.arange(T)  # [T]
    max_blocks = block_table.shape[0]
    if window:
        # Page skip: the earliest key any of this call's queries can see
        # is q_start - window + 1; pages wholly before it are never
        # scanned, so windowed prefill is O(T + window), not O(ctx).
        start = jnp.maximum(q_start - window + 1, 0) // block_size
        span = -(-(T + window) // block_size) + 1
    else:
        start = jnp.int32(0)
        span = max_blocks
    nsteps = min(
        -(-max_blocks // page_stride),
        -(-span // page_stride) + (1 if page_stride > 1 else 0),
    )
    # First strided index at/after `start`: ceil((start - offset)/stride).
    q0 = jnp.maximum((start - page_offset + page_stride - 1) // page_stride, 0)

    def body(carry, j):
        m, l, acc = carry
        blk = page_offset + (q0 + j) * page_stride
        entry = block_table[jnp.minimum(blk, max_blocks - 1)]
        slots = entry * block_size + jnp.arange(block_size)
        idx, ok = slot_fn(k_cache, slots)
        k = k_cache[idx].astype(jnp.float32)  # [bs, kvH, D]
        v = v_cache[idx].astype(jnp.float32)
        if k_scales is not None:
            k = _dequant_rows(k, entry, k_scales)
            v = _dequant_rows(v, entry, v_scales)
        scores = jnp.einsum("tkgd,skd->tkgs", qr, k)  # [T, kvH, G, bs]
        # Positions from the UNCLAMPED page index: a clamped over-the-end
        # gather returns garbage data whose key_pos lands >= total_len and
        # is therefore masked.
        key_pos = blk * block_size + jnp.arange(block_size)
        mask = (
            (key_pos[None, :] <= q_pos[:, None])
            & (key_pos[None, :] < total_len)
            & ok[None, :]
        )
        if window:
            # Sliding-window attention (Mistral-style): each query sees
            # only the last `window` keys.
            mask = mask & (key_pos[None, :] > q_pos[:, None] - window)
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        # Renormalize previous accumulator; masked-out rows stay at zero.
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[:, None, None, :], p, 0.0)
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum("tkgs,skd->tkgd", p, v)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((T, kvH, G), NEG_INF, jnp.float32),
        jnp.zeros((T, kvH, G), jnp.float32),
        jnp.zeros((T, kvH, G, D), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nsteps))
    return m, l, acc


def _decode_partials(
    q, k_cache, v_cache, block_tables, context_lens, block_size: int,
    slot_fn, window: int = 0, page_offset=0, page_stride: int = 1,
    k_scales=None, v_scales=None,
):
    """Batched decode counterpart of _prefill_partials (one query token per
    lane); returns un-normalized (m, l, acc).

    With a sliding window the scan SKIPS pages wholly behind it: each lane
    starts at its first in-window page and the trip count shrinks to
    ceil(window/bs)+1 — windowed decode cost is O(window), not O(ctx).

    ``page_offset``/``page_stride``: striped-scan mode (see
    _prefill_partials) — scan only logical pages ≡ offset (mod stride).

    ``k_cache`` of JOINED pages (``page_form``; ``v_cache`` is then the
    same array): a block's page is gathered whole and its two halves are
    the keys and the values."""
    B, H, D = q.shape
    joined = page_form(k_cache, v_cache) == "joined"
    kvH = k_cache.shape[-2]
    G = H // kvH
    scale = 1.0 / (D**0.5)
    qr = (q.astype(jnp.float32) * scale).reshape(B, kvH, G, D)
    max_blocks = block_tables.shape[1]
    if window:
        span = -(-window // block_size) + 1
        start = jnp.maximum(context_lens - window, 0) // block_size  # [B]
    else:
        span = max_blocks
        start = jnp.zeros_like(context_lens)
    nsteps = min(
        -(-max_blocks // page_stride),
        -(-span // page_stride) + (1 if page_stride > 1 else 0),
    )
    q0 = jnp.maximum((start - page_offset + page_stride - 1) // page_stride, 0)

    def body(carry, j):
        m, l, acc = carry
        blk = page_offset + (q0 + j) * page_stride               # [B]
        entry = jnp.take_along_axis(
            block_tables, jnp.minimum(blk, max_blocks - 1)[:, None], axis=1
        )[:, 0]
        if joined:
            page = k_cache[entry].astype(jnp.float32)  # [B, 2, bs, kvH, D]
            k, v = page[:, 0], page[:, 1]
            ok = jnp.ones((B, block_size), bool)
        else:
            slots = entry[:, None] * block_size + jnp.arange(block_size)
            idx, ok = slot_fn(k_cache, slots)
            k = k_cache[idx].astype(jnp.float32)  # [B, bs, kvH, D]
            v = v_cache[idx].astype(jnp.float32)
        if k_scales is not None:
            k = _dequant_rows(k, entry, k_scales)
            v = _dequant_rows(v, entry, v_scales)
        scores = jnp.einsum("bkgd,bskd->bkgs", qr, k)  # [B, kvH, G, bs]
        # Per-lane positions (lanes start at different pages). A clamped
        # over-the-end blk gives key_pos >= ctx, so it is masked.
        key_pos = blk[:, None] * block_size + jnp.arange(block_size)
        mask = (key_pos < context_lens[:, None]) & ok  # [B, bs]
        if window:
            mask = mask & (key_pos >= context_lens[:, None] - window)
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[:, None, None, :], p, 0.0)
        l_new = l * correction + p.sum(axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum("bkgs,bskd->bkgd", p, v)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, kvH, G), NEG_INF, jnp.float32),
        jnp.zeros((B, kvH, G), jnp.float32),
        jnp.zeros((B, kvH, G, D), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(nsteps))
    return m, l, acc


def _own_all(cache, slots):
    """Identity slot hook: single/replicated cache owns every slot."""
    return slots, jnp.ones(slots.shape, bool)


def paged_prefill_attention(
    q: jnp.ndarray,           # [T, n_heads, head_dim] — new tokens' queries
    k_cache: jnp.ndarray,     # [num_slots, n_kv_heads, head_dim]
    v_cache: jnp.ndarray,
    block_table: jnp.ndarray, # [max_blocks] int32
    q_start: jnp.ndarray,     # scalar: global position of q[0] (prefix length)
    total_len: jnp.ndarray,   # scalar: prefix + new tokens (real, unpadded)
    block_size: int,
    window: int = 0,          # sliding-window size (0 = full causal)
) -> jnp.ndarray:
    """Causal attention of new tokens over (cached prefix + themselves).

    Assumes the new tokens' K/V were already scattered into the cache, so
    every key this needs is reachable through `block_table`. Supports
    prefix-cache hits natively: q_start > 0 attends to blocks computed by an
    earlier request (or a remote prefill worker).
    """
    T, H, D = q.shape
    m, l, acc = _prefill_partials(
        q, k_cache, v_cache, block_table, q_start, total_len, block_size,
        _own_all, window,
    )
    return _safe_div(acc, l).reshape(T, H, D).astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,             # [B, n_heads, head_dim]
    k_cache: jnp.ndarray,       # [num_slots, n_kv_heads, head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] int32 — includes the current token
    block_size: int,
    window: int = 0,            # sliding-window size (0 = full causal)
    k_scales: jnp.ndarray | None = None,  # [num_blocks, kvH] (int8 cache)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """One-token-per-sequence attention over each sequence's paged KV.

    Inactive batch slots (context_len == 0) return zeros. With
    ``k_scales``/``v_scales`` the cache holds int8 blocks and each
    gathered page dequantizes by its per-(block, head) scale — the
    quantized-KV oracle path (docs/architecture/kv_quant.md).
    """
    B, H, D = q.shape
    m, l, acc = _decode_partials(
        q, k_cache, v_cache, block_tables, context_lens, block_size,
        _own_all, window, k_scales=k_scales, v_scales=v_scales,
    )
    return _safe_div(acc, l).reshape(B, H, D).astype(q.dtype)


def ragged_paged_attention(
    q: jnp.ndarray,             # [T, H, D] — flat mixed prefill+decode batch
    k_cache: jnp.ndarray,       # [num_slots, n_kv_heads, head_dim]
    v_cache: jnp.ndarray,       # (joined pages: the one array for both)
    block_tables: jnp.ndarray,  # [S, max_blocks] int32 — per-sequence rows
    token_seq: jnp.ndarray,     # [T] int32 — owning sequence row per token
    token_pos: jnp.ndarray,     # [T] int32 — global position (-1 = padding)
    block_size: int,
    window: int = 0,
    k_scales: jnp.ndarray | None = None,  # [num_blocks, kvH] (int8 cache)
    v_scales: jnp.ndarray | None = None,
    diffusion_block: int = 1,
    kv_len: jnp.ndarray | None = None,    # [S] — clips a block's reach
) -> jnp.ndarray:
    """XLA twin of the ragged unified kernel (ops/pallas/
    ragged_attention.py) — identical semantics, jnp formulation, and the
    tier-1 oracle the kernel is tested against. ``k_scales``/``v_scales``
    enable the int8-KV path: pages dequantize by per-(block, head) scale
    with the SAME arithmetic the kernel performs in-register, so parity
    stays exact-contract.

    Every row is one token of SOME sequence: a decode lane contributes one
    row, a chunked-prefill quantum its chunk's rows. Causality makes each
    token's visible context exactly ``token_pos + 1`` keys of its own
    sequence, so the whole mixed batch reduces to batched decode attention
    with per-token block tables — one lax.scan over pages, no per-phase
    program. Padding rows carry ``token_pos = -1`` (context 0) and return
    zeros. Under a mask by block (``diffusion_block = B > 1``) a token's
    context runs to the end of its own block of ``B`` positions, clipped
    to its sequence's ``kv_len`` as the kernel clips it."""
    rows = jnp.clip(token_seq, 0, block_tables.shape[0] - 1)
    tables = jnp.take(block_tables, rows, axis=0)  # [T, max_blocks]
    ctx = jnp.maximum(token_pos + 1, 0)
    if diffusion_block > 1:
        assert not window, "no window under a block mask"
        ctx = jnp.where(
            token_pos >= 0,
            (token_pos // diffusion_block + 1) * diffusion_block,
            0,
        )
        if kv_len is not None:
            ctx = jnp.minimum(ctx, kv_len[rows])
    return paged_decode_attention(
        q, k_cache, v_cache, tables, ctx, block_size, window,
        k_scales=k_scales, v_scales=v_scales,
    )


def ragged_attention(
    q, k_cache, v_cache, block_tables, token_seq, token_pos, q_start,
    q_len, kv_len, row_start, block_size: int, window: int = 0,
    k_scales=None, v_scales=None, diffusion_block: int = 1,
):
    """Default (single-chip, env-driven) dispatch for the unified step,
    for callers with no per-runner AttnDispatch to thread in."""
    return default_dispatch(block_size, k_cache).ragged(
        q, k_cache, v_cache, block_tables, token_seq, token_pos, q_start,
        q_len, kv_len, row_start, block_size, window,
        k_scales=k_scales, v_scales=v_scales,
        diffusion_block=diffusion_block,
    )


def default_dispatch(block_size: int, k_cache) -> AttnDispatch:
    """The single-chip dispatch the environment asks for over this cache:
    the Pallas kernels where they are enabled and the cache's shape passes
    their gate, else the XLA twin."""
    use_pallas = False
    if pallas_enabled():
        from dynamo_tpu.ops.pallas.attention import pallas_supported

        use_pallas = pallas_supported(
            block_size, k_cache.shape[-2], k_cache.shape[-1], k_cache.dtype
        )
    return AttnDispatch(use_pallas=use_pallas)


def full_causal_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, window: int = 0,
    diffusion_block: int = 1,
) -> jnp.ndarray:
    """Plain causal attention [T, H, D] x [T, kvH, D] — the no-cache
    reference path used to validate the paged implementations. With
    ``diffusion_block = B > 1`` the mask is by block: key j is visible to
    query i iff ``j // B <= i // B``."""
    T, H, D = q.shape
    kvH = k.shape[1]
    G = H // kvH
    scale = 1.0 / (D**0.5)
    qr = (q.astype(jnp.float32) * scale).reshape(T, kvH, G, D)
    scores = jnp.einsum("tkgd,skd->tkgs", qr, k.astype(jnp.float32))
    B = diffusion_block
    mask = jnp.arange(T)[None, :] // B <= jnp.arange(T)[:, None] // B
    if window:
        mask = mask & (
            jnp.arange(T)[None, :] > jnp.arange(T)[:, None] - window
        )
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgs,skd->tkgd", p, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# sp-sharded cache: the paged KV SLOT axis sharded over the `sp` mesh axis,
# so total KV CAPACITY is sp x one device's arrays — the beyond-chip
# long-context mode (SURVEY §5). With ``num_shards`` set,
# each shard runs a STRIDED scan over only the logical pages the striped
# allocator (engine/kv_cache.py BlockAllocator num_shards) placed on it —
# attention FLOPs and memory both partition sp-ways. Partials then merge
# with a pmax/psum logsumexp combine. ``num_shards=1`` keeps the legacy
# full-scan-with-ownership-mask mode (any block layout, sp-fold compute).
# Communication is O(query) per call, never O(cache). Composes with tp:
# heads shard over tp, slots over sp (AttnDispatch routes the specs).
# ---------------------------------------------------------------------------


def _sp_merge(acc, m, l, axis: str):
    """Cross-shard online-softmax merge: [., kvH, G(, D)] partials →
    replicated combined output."""
    m_g = jax.lax.pmax(m, axis)
    w = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * w, axis)
    acc_g = jax.lax.psum(acc * w[..., None], axis)
    return acc_g, l_g


def _local_slot_fn(axis: str):
    """Slot hook for a slot-sharded cache: translate GLOBAL slot ids to
    this shard's local range; non-owned slots are masked."""

    def slot_fn(cache, slots):
        per = cache.shape[0]
        r = jax.lax.axis_index(axis)
        local = slots - r * per
        ok = (local >= 0) & (local < per)
        return jnp.clip(local, 0, per - 1), ok

    return slot_fn


def paged_decode_attention_sp(
    q, k_cache, v_cache, block_tables, context_lens, block_size: int,
    axis: str = "sp", window: int = 0, num_shards: int = 1,
):
    """Per-shard decode body (inside shard_map over `axis`; cache in_spec
    P(axis, head_axis, None), q/out head-sharded over tp, everything else
    replicated). ``num_shards > 1`` enables the striped scan (allocator
    must stripe logical block i onto shard i % num_shards)."""
    B, H, D = q.shape
    off = jax.lax.axis_index(axis) if num_shards > 1 else 0
    m, l, acc = _decode_partials(
        q, k_cache, v_cache, block_tables, context_lens, block_size,
        _local_slot_fn(axis), window, page_offset=off,
        page_stride=num_shards,
    )
    acc_g, l_g = _sp_merge(acc, m, l, axis)
    return _safe_div(acc_g, l_g).reshape(B, H, D).astype(q.dtype)
