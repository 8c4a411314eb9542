"""The ragged paged attention kernel alone, on the chip, at the per-chip
shapes the benchmark's cells dispatch: microseconds a (span, layer) beside
the bytes bound of ``chipbench/costs/ragged_paged_attention.py``.

Not part of the benchmark (``BENCHMARK.json`` reads the kernel from a served
run's device trace); this is where a kernel PR starts. It answers what the
records cannot split:

- ``cells``: each shape as its cell dispatches it (decode lanes of mixed
  context and one prefill quantum), with the folds of the ring its long
  and short tiles take and the seconds ``.lower()`` takes on this host;
- ``parts``: the decode lanes alone (the short tile) and the prefill
  quantum alone (the long tile);
- ``split``: the context varied at fixed spans, then the spans varied at a
  fixed context; a least-squares fit of both gives the cost a span (grid
  step, q in, output out, a cold ring) and a page (DMA issue and wait,
  the fold's arithmetic), beside what their bytes would take and the
  descriptors a page takes (``descriptors_per_page``: 1 where the
  shape's pages are joined or held once, 2 where K and V are apart);
- ``ladder``: the ring depth and pages a fold (``ring_shape`` in
  ``ops/pallas/ragged_attention.py``), overridden point by point;
- ``ops``: one traced chain, device time an op a layer: the kernel as the
  benchmark's trace names it and what XLA runs around it;
- ``check``: the kernel against its XLA twin on the cell's dispatch (a
  kernel can pass interpret mode and be wrong on the chip);
- ``forms`` (a latent held once, ``--shapes dsv2``): ONE span through each
  form of latent attention, the absorbed ragged kernel and the expanded
  body (``ops/pallas/latent_expanded.py``), at ``--form-rows`` behind
  ``--form-prefixes``: the table that sets ``EXPANDED_MARGIN``, and the
  expanded body against the absorbed kernel at every point.

    chiprun -- python -m tools.ragged_kernel_bench --sweep check,cells,split
    chiprun -- python -m tools.ragged_kernel_bench --sweep ladder --shapes tp4
    chiprun -- python -m tools.ragged_kernel_bench --sweep split --shapes lfm2
        (PR 61, 64-wide heads stored 128 wide: a span 0.59 us, a page 0.081
        us where its 64 KiB take 0.080, half of them padding)
    chiprun -- python -m tools.ragged_kernel_bench --sweep cells \
        --shapes cmda-window,cmda-full \
        --kernel-file _parent/dynamo_tpu/ops/pallas/ragged_attention.py

(``--kernel-file``: another checkout's kernel, so that parent and change are
read in one call on one chip.)

A shape's cache is built in the form the engine would give it (the
engine's own rule, ``engine/config.py`` ``cache_form_of``, asked by
``cache_form`` below): a (k, v) layer's pages JOINED, one array and one descriptor a
page; ``once`` for a latent held once (``dsv2``); K and V apart for the
latent layer that still stores its latent twice (``mla``), under ``--form
apart`` (this kernel's two-stream path beside its joined one), and for a
``--kernel-file`` that predates the joined form (PR 58's and older: the
same keys and values in two arrays).

A line of JSON a measurement on stdout, all of them in
``chiprun_out/ragged_kernel_bench.jsonl``. Times are host clock around
``--layers`` chained calls inside one jitted scan, ended by
``block_until_ready``: device time, never a CPU number (without a TPU the
script stops, unless ``--allow-cpu`` rehearses it at a tiny size).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.util
import json
import os
import statistics
import sys
import threading
import time
import zlib

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.costs.latent_once_paged_attention import (
    cost as latent_once_cost,
)
from chipbench.costs.ragged_paged_attention import cost
from chipbench.costs.window_full_paged_attention import one_layer
from chipbench.peaks import peaks_for
from dynamo_tpu.engine.config import cache_form_of
from dynamo_tpu.ops.attention import join_pages
from dynamo_tpu.ops.pallas import latent_expanded
from dynamo_tpu.ops.pallas import ragged_attention as ragged_kernel

#: The kernel under measurement: this checkout's, or ``--kernel-file``'s.
kernel_mod = ragged_kernel

BS = 16
MAX_MODEL_LEN = 4096    # unless a shape names its own

#: Per-chip shapes of the benchmark's cells (``PERF.md`` §4): heads and
#: KV heads a chip, decode lanes, rows a lane, the prefill quantum beside
#: them, the token budget, the window and the block mask; then two shapes
#: of draft-verify spans, which no cell sends. A shape may name its own
#: ``max_model_len``, the lanes' contexts (``ctx``) and where its prefill
#: quantum ends (``prefill_ends``: a measurement each).
_LONGMIX = dict(max_model_len=20480, ctx=(600, 16000),
                prefill_ends=(2048, 6144, 12288), by_layer_window=True)
SHAPES = {
    # mistral-7b-tp4.chat-c128: 32 / 8 heads over four chips
    "tp4": dict(H=8, kvH=2, lanes=128, rows=1, prefill=128, T=256,
                window=4096, diffusion_block=1),
    # mistral-7b-l16.chat-c64, .chat-c64-think and mixtral-8x7b-l4.chat-c64
    "dense": dict(H=32, kvH=8, lanes=64, rows=1, prefill=80, T=256,
                  window=4096, diffusion_block=1),
    # sdar-30b-a3b-l7.chat-c64: a lane is a block of 4 rows, no window
    "sdar": dict(H=32, kvH=4, lanes=64, rows=4, prefill=60, T=512,
                 window=0, diffusion_block=4),
    # The same cell where a commit rides the next block's first pass (PR
    # 55): sixteen of the 64 lanes (a lane in four, its block's last pass
    # in flight) are ONE span of 2 x 4 rows, which the kernel tiles by its
    # length; spans start on a block's boundary, as the engine's do. Named
    # in ``--shapes``; ``--kernel-file`` a copy whose short tile is 8 rows
    # beside it is how the tile for that span was chosen (``PERF.md`` §6).
    "sdar-ride": dict(H=32, kvH=4, lanes=64, rows=4, prefill=100, T=512,
                      window=0, diffusion_block=4, wide=(16, 8),
                      ctx=(100, 1500), prefill_ends=(752,)),
    # No cell's: a speculative engine's dispatch, every lane a draft-verify
    # span of k + 1 = 5 rows (the long tile under ``diffusion_block=1``),
    # at one chip's widths and at a tp=4 chip's. Named in ``--shapes``.
    "verify": dict(H=32, kvH=8, lanes=64, rows=5, prefill=0, T=512,
                   window=4096, diffusion_block=1),
    "verify-tp4": dict(H=8, kvH=2, lanes=128, rows=5, prefill=0, T=1024,
                       window=4096, diffusion_block=1),
    # ling-3.0-flash-ep4-l8.chat-c128's one latent-attention layer: 32
    # query heads over ONE cached head of 512 + 64, lane-padded to 640.
    # Named in ``--shapes``.
    "mla": dict(H=32, kvH=1, lanes=128, rows=1, prefill=64, T=256,
                window=0, diffusion_block=1, D=640),
    # deepseek-v2-ep4-l5.docqa-c48: 128 query heads over ONE cached head
    # of 640 that holds the latent ONCE (``once``: no V operand, the values
    # are read from the key slot), a mixed step's 47 decode lanes at
    # document contexts beside a 977-row quantum. Named in ``--shapes``.
    "dsv2": dict(H=128, kvH=1, lanes=47, rows=1, prefill=977, T=1024,
                 window=0, diffusion_block=1, D=640, once=True,
                 max_model_len=20480, ctx=(2200, 16800),
                 prefill_ends=(2048, 8192, 16384)),
    # No cell's: `dense` at one, two and seven queries a cached head (a
    # Llama-2-style model's 32 heads over 32, the default preset's; 16 over
    # 8; Qwen2's ratio at 56 over 8), where the long tile's folded rows a
    # head are fewest or no multiple of a lane tile. Named in ``--shapes``.
    "mha": dict(H=32, kvH=32, lanes=64, rows=1, prefill=80, T=256,
                window=0, diffusion_block=1),
    "gqa2": dict(H=16, kvH=8, lanes=64, rows=1, prefill=80, T=256,
                 window=4096, diffusion_block=1),
    "gqa7": dict(H=56, kvH=8, lanes=64, rows=1, prefill=80, T=256,
                 window=0, diffusion_block=1),
    # command-a-plus-ep8-l4.longmix-c48: 128 query heads over 8 cached
    # heads, a mixed step's 45 decode lanes beside a 770-row quantum
    # (``PERF.md`` §5), one layer of each kind: three of four under the
    # window, the fourth over the whole context. Named in ``--shapes``.
    "cmda-window": dict(H=128, kvH=8, lanes=45, rows=1, prefill=770, T=1024,
                        window=4096, diffusion_block=1, **_LONGMIX),
    "cmda-full": dict(H=128, kvH=8, lanes=45, rows=1, prefill=770, T=1024,
                      window=0, diffusion_block=1, **_LONGMIX),
    # lfm2-24b-a2b-l10.chat-c128's two attention layers: 32 query heads
    # over 8 cached heads of 64, STORED a lane row (128) wide, so what the
    # kernel sees is ``dense`` without a window at 128 lanes (half of every
    # page it streams is zeros; the bytes bound below counts the stored
    # width, as the kernel must move it). Named in ``--shapes``.
    "lfm2": dict(H=32, kvH=8, lanes=128, rows=1, prefill=64, T=512,
                 window=0, diffusion_block=1),
}
D = 128


def width(shape: dict) -> int:
    """The (padded) head width of a shape: ``D`` unless it names its own."""
    return shape.get("D", D)


def build(shape: dict, contexts: np.ndarray, prefill_ctx: int, rng):
    """One dispatch's operands: ``len(contexts)`` lanes of ``rows`` rows
    whose context after the step is ``contexts[i]``, then one prefill
    span of ``shape['prefill']`` rows ending at ``prefill_ctx``. A shape
    with ``wide = (n, r)`` makes its first ``n`` lanes spans of ``r`` rows,
    and every lane's span start on a multiple of ``rows``."""
    rows, n_pre = shape["rows"], shape["prefill"]
    n_wide, wide_rows = shape.get("wide", (0, rows))
    spans = [(int(c) - rows, rows) for c in contexts]
    if n_wide:
        spans = [
            (p - p % rows, wide_rows if i < n_wide else n)
            for i, (p, n) in enumerate(spans)
        ]
    if n_pre:
        assert prefill_ctx >= n_pre, (prefill_ctx, n_pre)
        spans.append((prefill_ctx - n_pre, n_pre))
    S = len(spans)
    max_blocks = shape.get("max_model_len", MAX_MODEL_LEN) // BS
    need = sum(-(-(p + n) // BS) for p, n in spans)
    num_blocks = need + 8
    ids = rng.permutation(np.arange(1, num_blocks))
    tables = np.zeros((S, max_blocks), np.int32)
    q_start = np.zeros(S, np.int32)
    q_len = np.zeros(S, np.int32)
    row_start = np.zeros(S, np.int32)
    used = cursor = 0
    for s, (p, n) in enumerate(spans):
        nb = -(-(p + n) // BS)
        tables[s, :nb] = ids[used : used + nb]
        used += nb
        q_start[s], q_len[s], row_start[s] = p, n, cursor
        cursor += n
    assert cursor <= shape["T"], (cursor, shape["T"])
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(
        kq, (shape["T"], shape["H"], width(shape)), jnp.bfloat16)
    cshape = (num_blocks * BS, shape["kvH"], width(shape))
    k = jax.random.normal(kk, cshape, jnp.bfloat16)
    # a latent held once has no V: the values are K's leading columns
    v = None if shape.get("once") else jax.random.normal(
        kv, cshape, jnp.bfloat16)
    if cache_form(shape) == "joined":
        # the SAME keys and values as ONE array of joined pages
        k, v = join_pages(k, v, BS), None
    meta = tuple(
        jnp.asarray(a)
        for a in (tables, q_start, q_len, q_start + q_len, row_start)
    )
    return q, k, v, meta, spans


#: ``--form``: "engine" builds each shape's cache as the engine would
#: (``cache_form_of``); "apart" keeps K and
#: V an array each, for this kernel's two-stream path beside its joined one.
FORM = "engine"


def cache_form(shape: dict) -> str:
    """The form ``build`` gives a shape's cache: the engine's own rule
    (``engine/config.py`` ``cache_form_of``) over what the shape shows (a
    latent held once says ``once``; a latent layer that stores its latent
    twice, ``D`` of its own at one cached head, is Ling's), unless
    ``--form apart`` asks for this kernel's two streams or the kernel under
    measurement predates joined pages (a parent's ``--kernel-file``)."""
    form = cache_form_of(
        entries=1 if shape.get("once") else 2, pool=True,
        latent=shape["kvH"] == 1 and "D" in shape, kv_quant=None,
        kv_sp=False,
    )
    reads_joined = hasattr(kernel_mod, "page_form")
    if form == "joined" and (FORM == "apart" or not reads_joined):
        return "apart"
    return form


def bound_us(shape: dict, spans) -> tuple[float, float]:
    """(bytes bound, operations bound) of one layer's call, microseconds;
    a shape of a model whose layers differ by window is reckoned by the
    cost that reckons each layer by ITS window."""
    if shape.get("once"):
        flops, nbytes = latent_once_cost(
            spans,
            model=dict(num_heads=shape["H"], num_layers=1, kv_lora_rank=512,
                       qk_rope_head_dim=64, qk_nope_head_dim=128,
                       v_head_dim=128),
            engine=dict(dtype_bytes=2))
    elif shape.get("by_layer_window"):
        flops, nbytes = one_layer(
            spans, shape["window"], heads=shape["H"], kv_heads=shape["kvH"],
            d=width(shape), dc=width(shape), itemsize=2, kv_itemsize=2)
    else:
        flops, nbytes = cost(
            spans,
            model=dict(num_heads=shape["H"], num_kv_heads=shape["kvH"],
                       head_dim=width(shape), num_layers=1,
                       sliding_window=shape["window"]),
            engine=dict(tp=1, cache_head_dim=width(shape), dtype_bytes=2),
        )
    peaks = chip_peaks()
    return (1e6 * nbytes / peaks["hbm_bytes_per_s"],
            1e6 * flops / peaks["flops_bf16"])


def chip_peaks() -> dict:
    """The device's published peaks; a CPU rehearsal borrows the v5e's
    (its lines are not times of anything)."""
    kind = jax.devices()[0].device_kind if on_tpu() else "TPU v5 lite"
    return peaks_for(kind)


def layer_call(shape: dict):
    """One layer's call of the kernel under measurement, unjitted: a fresh
    jit a measurement, because the ladder overrides module state that the
    kernel reads while it is traced."""
    return functools.partial(
        kernel_mod.ragged_paged_attention_pallas.__wrapped__,
        block_size=BS, window=shape["window"],
        diffusion_block=shape["diffusion_block"],
    )


def lower_seconds(shape: dict, operands) -> float:
    """Seconds to trace and lower one call on this host (a start pays it a
    program; the Mosaic compile behind it is not in it)."""
    q, k, v, meta = operands
    t0 = time.perf_counter()
    jax.jit(layer_call(shape)).lower(q, k, v, *meta)
    return time.perf_counter() - t0


def long_tile_of(shape: dict) -> int:
    """Rows of the measured kernel's long tile (a parent's rule may go by
    the heads alone)."""
    try:
        rows = kernel_mod.long_tile(shape["H"], shape["kvH"])
    except TypeError:
        rows = kernel_mod.long_tile(shape["H"])
    return max(8, rows)


def fold_counts(shape: dict, spans) -> dict:
    """Folds of the ring the call's long and short tiles take, by the
    measured kernel's tile and ring (the host's count a step's flight
    record carries)."""
    _, pp = kernel_mod.ring_shape(BS * shape["kvH"] * width(shape) * 2, BS)
    starts, rows = (np.asarray(x, np.int32) for x in zip(*spans))
    short, long = ragged_kernel.fold_counts(
        starts, rows, starts + rows, long_rows=long_tile_of(shape),
        fold_keys=pp * BS, window=shape["window"],
        diffusion_block=shape["diffusion_block"])
    return dict(long_folds=long, short_folds=short)


def time_call(shape: dict, operands, layers: int, reps: int,
              trace_to: str | None = None) -> float:
    """Median microseconds of ONE call, from ``layers`` chained calls;
    with ``trace_to``, one more chain runs under the profiler."""
    q, k, v, meta = operands
    call = layer_call(shape)

    @jax.jit
    def chain(q, k, v, meta):
        def body(x, _):
            out = call(x, k, v, *meta)
            # the next layer's q depends on this layer's output
            return q + out * jnp.asarray(1e-3, q.dtype), None

        x, _ = jax.lax.scan(body, q, None, length=layers)
        return x

    jax.block_until_ready(chain(q, k, v, meta))
    if trace_to:
        with jax.profiler.trace(trace_to):
            jax.block_until_ready(chain(q, k, v, meta))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, k, v, meta))
        times.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(times) / layers


def measure(name: str, what: str, shape: dict, contexts, prefill_ctx: int,
            args, rng, **extra) -> dict:
    q, k, v, meta, spans = build(shape, contexts, prefill_ctx, rng)
    if what == "cells":
        extra = dict(extra, lower_s=round(
            lower_seconds(shape, (q, k, v, meta)), 3),
            **fold_counts(shape, spans))
    us = time_call(shape, (q, k, v, meta), args.layers, args.reps)
    bytes_us, flops_us = bound_us(shape, spans)
    pages = sum(-(-(p + n) // BS) for p, n in spans)
    line = dict(
        shape=name, sweep=what, spans=len(spans), pages=pages,
        rows=sum(n for _, n in spans), call_us=round(us, 2),
        us_per_span=round(us / len(spans), 3),
        bytes_bound_us=round(bytes_us, 2),
        bytes_bound_us_per_span=round(bytes_us / len(spans), 3),
        flops_bound_us=round(flops_us, 2),
        roofline_pct=round(100 * max(bytes_us, flops_us) / us, 2),
        device=jax.devices()[0].device_kind, cache_form=cache_form(shape),
        **extra,
    )
    if args.kernel_file:
        line["kernel_file"] = args.kernel_file
    emit(line)
    return line


def emit(line: dict) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ragged_kernel_bench.jsonl", "a") as f:
        f.write(text + "\n")


def sweep_check(name, shape, args, rng):
    """Largest difference from the XLA twin, as a share of the largest
    output; bf16 rounds at 2^-8, a wrong row reads near 1."""
    from dynamo_tpu.ops.attention import ragged_paged_attention

    contexts = mixed_contexts(shape, rng, args.ctx_lo, args.ctx_hi)
    q, k, v, meta, spans = build(
        shape, contexts, prefill_ends(shape, args)[-1], rng)
    tables, q_start, q_len, kv_len, row_start = meta
    token_seq = np.zeros(shape["T"], np.int32)
    token_pos = np.full(shape["T"], -1, np.int32)
    cursor = 0
    for s, (p, n) in enumerate(spans):
        token_seq[cursor : cursor + n] = s
        token_pos[cursor : cursor + n] = np.arange(p, p + n)
        cursor += n
    kw = dict(diffusion_block=shape["diffusion_block"])
    want = ragged_paged_attention(
        q, k, k if v is None else v, tables, jnp.asarray(token_seq),
        jnp.asarray(token_pos),
        BS, shape["window"], kv_len=kv_len, **kw)
    got = kernel_mod.ragged_paged_attention_pallas(
        q, k, v, *meta, block_size=BS, window=shape["window"], **kw)
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    err = np.abs(got - want).max(axis=(1, 2)) / np.abs(want).max()
    emit(dict(shape=name, sweep="check", spans=len(spans),
              kernel_file=args.kernel_file,
              finite=bool(np.isfinite(got).all()),
              worst_row=int(err.argmax()), worst_rel=round(float(err.max()), 5),
              padding_zero=not got[cursor:].any(),
              ok=bool(np.isfinite(got).all() and err.max() < 0.02
                      and not got[cursor:].any())))


def sweep_ops(name, shape, args, rng):
    """One traced chain on the cell's dispatch: device time an op a layer
    (the kernel as the benchmark's trace reads it, and what XLA runs
    around it: the pad, the mask of rows nobody owns, the chain's add)."""
    import tempfile

    from chipbench import xprof

    contexts = mixed_contexts(shape, rng, args.ctx_lo, args.ctx_hi)
    q, k, v, meta, spans = build(
        shape, contexts, prefill_ends(shape, args)[-1], rng)
    with tempfile.TemporaryDirectory() as logdir:
        time_call(shape, (q, k, v, meta), args.layers, 1, trace_to=logdir)
        reduced = xprof.reduce(xprof.load(logdir))
    emit(dict(shape=name, sweep="ops", spans=len(spans),
              us_per_layer=top_ops(reduced, args.layers, 12),
              module_us_per_layer=round(
                  1e6 * reduced["module_s"] / args.layers, 2)))


def top_ops(reduced: dict, layers: int, n: int) -> dict:
    """Device microseconds an op a layer, the ``n`` largest."""
    return {
        op: round(1e6 * sec / layers, 2)
        for op, sec in sorted(reduced["op_seconds"].items(),
                              key=lambda kv: -kv[1])[:n]
    }


def traced(run) -> dict:
    """``run()`` under the profiler, reduced as the benchmark reduces."""
    import tempfile

    from chipbench import xprof

    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            run()
        return xprof.reduce(xprof.load(logdir))


#: DeepSeek-V2's latent widths (``forms``): the un-absorbed q is ``NOPE +
#: ROPE`` wide, the cache entry ``RANK + ROPE`` padded to the shape's ``D``.
NOPE, ROPE, RANK, V_DIM = 128, 64, 512, 128


def sweep_forms(name, shape, args, rng):
    """One span of n rows behind a prefix, alone in the dispatch, through
    the absorbed kernel and through the expanded body: microseconds a
    layer's call each (with the XLA glue each wrapper runs), what the rule
    says of the span, and the two forms' largest difference
    after the absorbed output's up-projection, as a share of the largest
    output."""
    assert shape.get("once"), "forms: a latent held once"
    H, T, Dc = shape["H"], shape["T"], width(shape)
    max_len = shape.get("max_model_len", MAX_MODEL_LEN)
    num_blocks = max_len // BS + 8
    key = jax.random.PRNGKey(int(rng.integers(1 << 30)))
    kq, kk, ka, kb = jax.random.split(key, 4)
    bf = jnp.bfloat16
    q = jax.random.normal(kq, (T, H, NOPE + ROPE), bf)
    cache = jax.random.normal(kk, (num_blocks * BS, 1, Dc), bf)
    w_uk = (jax.random.normal(ka, (H, NOPE, RANK)) / RANK**0.5).astype(bf)
    w_uv = (jax.random.normal(kb, (H, V_DIM, RANK)) / RANK**0.5).astype(bf)
    scale = (NOPE + ROPE) ** -0.5
    k_rule = latent_expanded.expanded_k(
        argparse.Namespace(kv_lora_rank=RANK, qk_rope_head_dim=ROPE,
                           qk_nope_head_dim=NOPE, v_head_dim=V_DIM), T, H)

    def absorbed(q, meta):
        q_lat = jnp.einsum("thn,hnc->thc", q[..., :NOPE], w_uk)
        q_abs = jnp.concatenate([q_lat, q[..., NOPE:]], -1) * (
            scale * Dc**0.5)
        q_abs = jnp.pad(q_abs.astype(bf), ((0, 0), (0, 0),
                                           (0, Dc - RANK - ROPE)))
        return q_abs, lambda x: kernel_mod.ragged_paged_attention_pallas(
            x, cache, None, *meta, block_size=BS)

    def expanded(x, meta):
        tables, q_start, q_len, _, row_start = meta
        return latent_expanded.ragged_paged_attention_pallas_expanded(
            x, cache, w_uk, w_uv, tables, q_start, q_len, row_start,
            block_size=BS, scale=scale)

    def chained(form):
        @jax.jit
        def chain(q, meta):
            if form == "absorbed":
                x0, call = absorbed(q, meta)
            else:
                x0, call = q, lambda x: expanded(x, meta)

            def body(x, _):
                out = call(x)
                step = jnp.resize(out, x.shape) if form == "expanded" else out
                return x + step * jnp.asarray(1e-3, x.dtype), None

            return jax.lax.scan(body, x0, None, length=args.layers)[0]
        return chain

    @jax.jit
    def both(q, meta):
        x0, call = absorbed(q, meta)
        want = jnp.einsum("thc,hvc->thv", call(x0)[..., :RANK], w_uv,
                          preferred_element_type=jnp.float32)
        return want, expanded(q, meta).astype(jnp.float32)

    chains = {form: chained(form) for form in ("absorbed", "expanded")}
    for prefix in args.form_prefixes:
        for n in args.form_rows:
            if prefix + n > max_len or n > T:
                continue
            nb = -(-(prefix + n) // BS)
            tables = np.zeros((1, max_len // BS), np.int32)
            tables[0, :nb] = rng.permutation(np.arange(1, num_blocks))[:nb]
            meta = tuple(jnp.asarray(a, jnp.int32) for a in (
                tables, [prefix], [n], [prefix + n], [0]))
            want, got = (np.asarray(a) for a in both(q, meta))
            err = float(np.abs(got - want).max() / np.abs(want).max())
            line = dict(shape=name, sweep="forms", prefix=prefix, rows=n,
                        rule_expands=bool(k_rule and latent_expanded.
                                          expanded_spans(
                            np.int32([n]), np.int32([prefix + n]), k_rule)[0]),
                        worst_rel=round(err, 5),
                        padding_zero=not got[n:].any())
            for form, chain in chains.items():
                jax.block_until_ready(chain(q, meta))
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(chain(q, meta))
                    times.append(time.perf_counter() - t0)
                line[f"{form}_us"] = round(
                    1e6 * statistics.median(times) / args.layers, 2)
            line["absorbed_over_expanded"] = round(
                line["absorbed_us"] / line["expanded_us"], 3)
            emit(line)
    if on_tpu():
        # the last point's chains traced: the kernels as the benchmark's
        # trace names them, and what XLA runs around each (the absorbed
        # call's pad of q and zeroing of rows nobody owns, the expanded
        # call's)
        for form, chain in chains.items():
            reduced = traced(lambda: jax.block_until_ready(chain(q, meta)))
            emit(dict(shape=name, sweep="forms_ops", form=form,
                      prefix=prefix, rows=n,
                      us_per_layer=top_ops(reduced, args.layers, 10)))


def mixed_contexts(shape: dict, rng, lo: int, hi: int) -> np.ndarray:
    lo, hi = shape.get("ctx", (lo, hi))
    return rng.integers(lo, hi + 1, size=shape["lanes"])


def prefill_ends(shape: dict, args) -> tuple[int, ...]:
    return shape.get("prefill_ends", (args.ctx_hi // 2,))


def sweep_cells(name, shape, args, rng):
    contexts = mixed_contexts(shape, rng, args.ctx_lo, args.ctx_hi)
    for end in prefill_ends(shape, args):
        measure(name, "cells", shape, contexts, end, args, rng,
                prefill_end=end)


def fit(lines: list[dict]) -> dict:
    """Least squares of call time on (1, spans, pages)."""
    a = np.array([[1.0, ln["spans"], ln["pages"]] for ln in lines])
    y = np.array([ln["call_us"] for ln in lines])
    coef, *_ = np.linalg.lstsq(a, y, rcond=None)
    resid = float(np.abs(a @ coef - y).max())
    return dict(us_per_call=round(float(coef[0]), 2),
                us_per_span=round(float(coef[1]), 3),
                us_per_page=round(float(coef[2]), 4),
                worst_residual_us=round(resid, 2))


def sweep_split(name, shape, args, rng):
    """Decode lanes only (no prefill quantum), every lane at one context:
    the context varied at the cell's lanes, then the lanes varied at one
    context; fitted together."""
    lanes_only = dict(shape, prefill=0)
    lines = []
    for ctx in args.contexts:
        lines.append(measure(
            name, "ctx", lanes_only,
            np.full(shape["lanes"], ctx), 0, args, rng, ctx=ctx))
    for lanes in args.lanes:
        if lanes * shape["rows"] > shape["T"]:
            continue
        lines.append(measure(
            name, "spans", lanes_only, np.full(lanes, args.fit_ctx), 0,
            args, rng, ctx=args.fit_ctx))
    form = cache_form(shape)
    # a page as the fit counts it: a block's K and V (its K alone where
    # the latent is held once), in one descriptor or in two
    page_bytes = (1 if form == "once" else 2) * BS * shape["kvH"] * width(
        shape) * 2
    emit(dict(shape=name, sweep="fit", **fit(lines),
              cache_form=form, kernel_file=args.kernel_file,
              descriptors_per_page=2 if form == "apart" else 1,
              page_bytes=page_bytes,
              bytes_bound_us_per_page=round(
                  1e6 * page_bytes / chip_peaks()["hbm_bytes_per_s"], 4)))
    # the prefill quantum alone, beside the decode lanes' numbers
    prefill_alone(name, shape, args, rng,
                  shape.get("prefill_ends", (shape["prefill"], 512, 1024)))


def prefill_alone(name, shape, args, rng, ends):
    if shape["prefill"]:
        for ctx in ends:
            measure(name, "prefill_alone", dict(shape, lanes=0),
                    np.zeros(0, np.int64), max(ctx, shape["prefill"]),
                    args, rng, ctx=ctx)


def sweep_parts(name, shape, args, rng):
    """The cell's dispatch in its two parts: the decode lanes without the
    prefill quantum (the short tile), the quantum without the lanes (the
    long tile) at each place it ends."""
    measure(name, "lanes_alone", dict(shape, prefill=0),
            mixed_contexts(shape, rng, args.ctx_lo, args.ctx_hi), 0,
            args, rng)
    prefill_alone(name, shape, args, rng, prefill_ends(shape, args))


def sweep_ladder(name, shape, args, rng):
    contexts = mixed_contexts(shape, rng, args.ctx_lo, args.ctx_hi)
    state = rng.bit_generator.state
    for nbuf, pp in args.ladder:
        rng.bit_generator.state = state  # the same operands a point
        try:
            with pinned_ring(nbuf, pp):
                measure(name, "ladder", shape, contexts,
                        prefill_ends(shape, args)[-1], args, rng,
                        nbuf=nbuf, pp=pp)
        except Exception as e:  # a point the compiler refuses
            emit(dict(shape=name, sweep="ladder", nbuf=nbuf, pp=pp,
                      error=f"{type(e).__name__}: {str(e)[:200]}"))


@contextlib.contextmanager
def pinned_ring(nbuf: int, pp: int):
    """The ring's depth and the pages a fold, pinned for what is traced
    inside (``time_call`` jits anew every measurement)."""
    rule = kernel_mod.ring_shape
    kernel_mod.ring_shape = lambda *a, **k: (nbuf, pp)
    try:
        yield
    finally:
        kernel_mod.ring_shape = rule


def pairs(text: str) -> list[tuple[int, int]]:
    return [tuple(int(x) for x in p.split("x")) for p in text.split(",")]


def ints(text: str) -> list[int]:
    return [int(x) for x in text.split(",")]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="tp4,dense,sdar")
    ap.add_argument("--sweep", default="cells,split",
                    help="cells, parts (lanes alone, quantum alone), split "
                    "(ctx and spans, fitted), ladder, check, ops (a traced "
                    "chain, time an op), forms (a latent held once: one "
                    "span through each form)")
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ctx-lo", type=int, default=200)
    ap.add_argument("--ctx-hi", type=int, default=1500)
    ap.add_argument("--contexts", type=ints,
                    default=[64, 128, 256, 512, 1024, 2048])
    ap.add_argument("--lanes", type=ints, default=[8, 16, 32, 64, 128])
    ap.add_argument("--fit-ctx", type=int, default=512)
    ap.add_argument("--ladder", type=pairs,
                    default=pairs("2x16,3x16,4x16,6x16,4x8,3x32"),
                    help="NBUFxPP points; the default is the ladder "
                    "recorded in the kernel's docstring")
    ap.add_argument("--form-rows", type=ints, default=[128, 256, 384, 512, 977])
    ap.add_argument("--form-prefixes", type=ints,
                    default=[0, 2048, 8192, 16384])
    ap.add_argument("--kernel-file", default=None, metavar="PATH",
                    help="measure the kernel of this file (another "
                    "checkout's ops/pallas/ragged_attention.py)")
    ap.add_argument("--form", default="engine", choices=("engine", "apart"),
                    help="the cache each shape is built as: the engine's "
                    "choice, or K and V apart (this kernel's two streams)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=INT", help="override a kernel constant")
    ap.add_argument("--deadline", type=int, default=1200,
                    help="hard stop, seconds: a DMA wait that never "
                    "returns blocks inside the runtime")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse on the CPU (interpret mode); no times")
    args = ap.parse_args(argv)
    if not on_tpu() and not args.allow_cpu:
        print("no TPU: a CPU run gives no device time (--allow-cpu "
              "rehearses)", file=sys.stderr)
        return 1
    watchdog = threading.Timer(args.deadline, lambda: os._exit(3))
    watchdog.daemon = True
    watchdog.start()
    global FORM
    FORM = args.form
    if args.kernel_file:
        global kernel_mod
        spec = importlib.util.spec_from_file_location(
            "ragged_attention_under_test", args.kernel_file)
        kernel_mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kernel_mod)
    for item in args.set:
        key, value = item.split("=")
        # a constant of the ragged kernel's file, or of the expanded body's
        mod = kernel_mod if hasattr(kernel_mod, key) else latent_expanded
        assert hasattr(mod, key), key
        setattr(mod, key, int(value))
    sweeps = {"cells": sweep_cells, "split": sweep_split,
              "parts": sweep_parts,
              "ladder": sweep_ladder, "check": sweep_check,
              "ops": sweep_ops, "forms": sweep_forms}
    for name in args.shapes.split(","):
        for what in args.sweep.split(","):
            # the same operands a shape whatever else the call measures:
            # parent and change, or two calls, read the same dispatch
            rng = np.random.default_rng([args.seed, zlib.crc32(name.encode())])
            sweeps[what](name, SHAPES[name], args, rng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
