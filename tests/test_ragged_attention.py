"""Ragged unified attention: the Pallas kernel (interpret mode) and the
jnp twin (ops/attention.py ragged_paged_attention) against the per-phase
jnp oracles (paged decode, paged prefill, full causal), over mixed prefill+decode batches, GQA, bf16, sliding windows,
prefix hits, and idle metadata rows. The same kernel compiles under
Mosaic on real TPU; interpret mode runs the identical code path on CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.attention import (
    paged_decode_attention,
    paged_prefill_attention,
    ragged_paged_attention,
)
from dynamo_tpu.ops.pallas.ragged_attention import (
    ragged_paged_attention_pallas,
)

BS = 16  # block size


def _caches(rng, num_blocks, kvH, D, dtype=jnp.float32):
    shape = (num_blocks * BS, kvH, D)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return k, v


def _tables(rng, S, max_blocks, num_blocks):
    """Disjoint block tables (block 0 is the trash block, never used)."""
    ids = rng.permutation(np.arange(1, num_blocks))[: S * max_blocks]
    return jnp.asarray(ids.reshape(S, max_blocks), jnp.int32)


def _flat_batch(rng, spans, T, H, D, dtype=jnp.float32):
    """Build (q, span arrays, token arrays) for spans =
    [(q_start, q_len), ...] packed back to back from row 0."""
    S = len(spans)
    q_start = np.zeros(S, np.int32)
    q_len = np.zeros(S, np.int32)
    row_start = np.zeros(S, np.int32)
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    cursor = 0
    for s, (qs, ql) in enumerate(spans):
        q_start[s], q_len[s], row_start[s] = qs, ql, cursor
        token_seq[cursor : cursor + ql] = s
        token_pos[cursor : cursor + ql] = np.arange(qs, qs + ql)
        cursor += ql
    assert cursor <= T
    q = jnp.asarray(rng.standard_normal((T, H, D)), dtype)
    return (
        q,
        jnp.asarray(q_start),
        jnp.asarray(q_len),
        jnp.asarray(q_start + q_len),
        jnp.asarray(row_start),
        jnp.asarray(token_seq),
        jnp.asarray(token_pos),
    )


def _both(q, k, v, tables, qs, ql, kv, rs, tseq, tpos, window=0, q_tile=8):
    want = ragged_paged_attention(q, k, v, tables, tseq, tpos, BS, window)
    got = ragged_paged_attention_pallas(
        q, k, v, tables, qs, ql, kv, rs, BS, q_tile=q_tile, window=window
    )
    return np.asarray(want), np.asarray(got)


@pytest.mark.parametrize("H,kvH,D", [(8, 8, 128), (8, 2, 128), (4, 1, 128)])
def test_mixed_batch_matches_twin(H, kvH, D):
    """Decode spans + prefill quanta + a prefix-hit chunk + an idle row
    in ONE flat batch: kernel == jnp twin (incl. zeroed padding rows)."""
    rng = np.random.default_rng(0)
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 5, 4, 64)
    # decode@ctx37, decode@ctx1, prefill 20 from 0, chunk 13 @ prefix 16,
    # idle row; padding rows after.
    spans = [(36, 1), (0, 1), (0, 20), (16, 13), (0, 0)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 40, H, D)
    want, got = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not got[35:].any()  # padding rows stay zero


def test_decode_only_matches_decode_oracle():
    """A decode-only unified batch must equal batched decode attention."""
    rng = np.random.default_rng(1)
    H, kvH, D = 8, 2, 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 4, 4, 64)
    ctx = np.asarray([64, 37, 1, 16], np.int32)
    spans = [(c - 1, 1) for c in ctx]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    want, got = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    oracle = paged_decode_attention(
        q[:4], k, v, tables, jnp.asarray(ctx), BS
    )
    np.testing.assert_allclose(
        got[:4], np.asarray(oracle), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("H,kvH", [(8, 8), (8, 2)])
@pytest.mark.parametrize("q_tile", [8, 64, 128])
def test_prefill_only_matches_prefill_oracle(H, kvH, q_tile):
    """Prefill-only unified batches (incl. a prefix hit) against the
    per-lane prefill oracle, across head geometries (MHA, GQA) and long
    tiles (8 asks for less than the kernel's 32 rows and gets 32: a ragged
    tail after no full tile; 64 and 128 are wider than any span here)."""
    rng = np.random.default_rng(2)
    D = 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 2, 4, 64)
    spans = [(0, 24), (16, 13)]  # span 1 extends a 16-token prefix
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 40, H, D)
    want, got = _both(
        q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos, q_tile=q_tile
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    o0 = paged_prefill_attention(
        q[:24], k, v, tables[0], jnp.int32(0), jnp.int32(24), BS
    )
    o1 = paged_prefill_attention(
        q[24:37], k, v, tables[1], jnp.int32(16), jnp.int32(29), BS
    )
    np.testing.assert_allclose(got[:24], np.asarray(o0), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[24:37], np.asarray(o1), rtol=2e-5, atol=2e-5)


def test_prefill_span_matches_full_causal_attention_end_to_end():
    """Scatter K/V into the paged cache, then hold a whole-prompt prefill
    span (beside a decode lane of another sequence) to plain causal
    attention — the no-cache oracle, with no paged code on its side."""
    from dynamo_tpu.ops.attention import full_causal_attention

    rng = np.random.default_rng(3)
    T, H, kvH, D, num_blocks = 40, 4, 2, 128, 16
    kx = jnp.asarray(rng.standard_normal((T, kvH, D)), jnp.float32)
    vx = jnp.asarray(rng.standard_normal((T, kvH, D)), jnp.float32)
    k, v = _caches(rng, num_blocks, kvH, D)  # other sequences' pages
    blocks = [1, 2, 3]  # 3 blocks cover 40 tokens
    slots = jnp.asarray(
        [blocks[t // BS] * BS + t % BS for t in range(T)], jnp.int32
    )
    k, v = k.at[slots].set(kx), v.at[slots].set(vx)
    tables = jnp.asarray([[7, 8, 0, 0], blocks + [0]], jnp.int32)
    spans = [(20, 1), (0, T)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 48, H, D)
    want_twin, got = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    want = np.asarray(full_causal_attention(q[1 : 1 + T], kx, vx))
    np.testing.assert_allclose(got[1 : 1 + T], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        want_twin[1 : 1 + T], want, rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [10, 24])
def test_windowed_prefill_span_matches_prefill_oracle(window):
    """Sliding-window prefill spans (one from position 0, one extending a
    16-token prefix past the window) against the windowed per-lane
    prefill oracle; the window changes the answer."""
    rng = np.random.default_rng(9)
    H, kvH, D = 8, 2, 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 2, 4, 64)
    spans = [(0, 24), (16, 24)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 48, H, D)
    _, got = _both(
        q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos, window=window
    )
    for lane, (start, n, row) in enumerate([(0, 24, 0), (16, 24, 24)]):
        want = paged_prefill_attention(
            q[row : row + n], k, v, tables[lane], jnp.int32(start),
            jnp.int32(start + n), BS, window=window,
        )
        np.testing.assert_allclose(
            got[row : row + n], np.asarray(want), rtol=2e-5, atol=2e-5,
            err_msg=f"lane {lane}",
        )
    _, full = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    assert np.abs(got[24:48] - full[24:48]).max() > 1e-4


def test_bf16_mixed_batch():
    rng = np.random.default_rng(3)
    H, kvH, D = 8, 4, 128
    k, v = _caches(rng, 32, kvH, D, jnp.bfloat16)
    tables = _tables(rng, 3, 3, 32)
    spans = [(19, 1), (0, 12), (8, 5)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(
        rng, spans, 24, H, D, jnp.bfloat16
    )
    want, got = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=2e-2, atol=2e-2
    )


def test_sliding_window_mixed_batch():
    """Windowed attention (Mistral-style) over a mixed batch: kernel ==
    twin, and a long-context decode span sees only the window."""
    rng = np.random.default_rng(4)
    H, kvH, D = 4, 2, 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 3, 4, 64)
    spans = [(63, 1), (0, 20), (30, 9)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 32, H, D)
    for window in (8, 24):
        want, got = _both(
            q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos, window=window
        )
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5, err_msg=f"window={window}"
        )
    # Decode span vs the windowed decode oracle.
    want_d = paged_decode_attention(
        q[:1], k, v, tables[:1], jnp.asarray([64], jnp.int32), BS, window=8
    )
    got_w = ragged_paged_attention_pallas(
        q, k, v, tables, qs, ql, kv_len, rs, BS, window=8
    )
    np.testing.assert_allclose(
        np.asarray(got_w)[:1], np.asarray(want_d), rtol=2e-5, atol=2e-5
    )


def test_twin_is_pure_decode_reformulation():
    """The jnp twin's mixed-batch output equals running each phase
    through its own oracle — the contract that makes it a valid oracle
    for the kernel."""
    rng = np.random.default_rng(5)
    H, kvH, D = 8, 2, 64  # twin has no lane constraint; D=64 fine
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 2, 4, 64)
    spans = [(47, 1), (0, 10)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    out = np.asarray(
        ragged_paged_attention(q, k, v, tables, tseq, tpos, BS)
    )
    dec = paged_decode_attention(
        q[:1], k, v, tables[:1], jnp.asarray([48], jnp.int32), BS
    )
    pre = paged_prefill_attention(
        q[1:11], k, v, tables[1], jnp.int32(0), jnp.int32(10), BS
    )
    np.testing.assert_allclose(out[:1], np.asarray(dec), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[1:11], np.asarray(pre), rtol=2e-5, atol=2e-5)
    assert not out[11:].any()


def test_gqa_grouping_matches_full_heads():
    """GQA (kvH < H) kernel output equals a full-head run on a cache with
    each kv head repeated over its query group."""
    rng = np.random.default_rng(6)
    H, kvH, D = 8, 2, 128
    k, v = _caches(rng, 32, kvH, D)
    tables = _tables(rng, 2, 3, 32)
    spans = [(21, 1), (0, 9)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    got = np.asarray(
        ragged_paged_attention_pallas(
            q, k, v, tables, qs, ql, kv_len, rs, BS
        )
    )
    G = H // kvH
    k_full = jnp.repeat(k, G, axis=1)
    v_full = jnp.repeat(v, G, axis=1)
    want = np.asarray(
        ragged_paged_attention_pallas(
            q, k_full, v_full, tables, qs, ql, kv_len, rs, BS
        )
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,kvH", [(8, 8), (8, 2)])
def test_spec_verify_spans_match_twin(H, kvH):
    """Speculative draft-verify spans (q_len = k+1 rows at
    q_start = ctx-1) mixed with plain decode and prefill quanta in ONE
    flat batch: kernel == jnp twin, GQA included. A verify span's
    attention math is identical to a short prefill over the draft
    positions — this pins the contract the unified spec port rides."""
    rng = np.random.default_rng(7)
    D = 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 4, 4, 64)
    # verify span: ctx 36, fed token + 3 drafts (rows 35..38);
    # verify span at the context floor: ctx 1, fed + 2 drafts;
    # a plain decode span and a prefill quantum ride along.
    spans = [(35, 4), (0, 3), (21, 1), (0, 10)]
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 32, H, D)
    want, got = _both(q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # Each verify ROW equals the prefill oracle over the same span —
    # verification IS a short prefill over the draft positions.
    o0 = paged_prefill_attention(
        q[:4], k, v, tables[0], jnp.int32(35), jnp.int32(39), BS
    )
    np.testing.assert_allclose(got[:4], np.asarray(o0), rtol=2e-5, atol=2e-5)


def test_spec_verify_spans_match_twin_windowed():
    """Draft-verify spans under a sliding window: kernel == twin, and
    the verify rows see only the window."""
    rng = np.random.default_rng(8)
    H, kvH, D = 4, 2, 128
    k, v = _caches(rng, 64, kvH, D)
    tables = _tables(rng, 2, 4, 64)
    spans = [(50, 5), (0, 8)]  # ctx-51 verify span (4 drafts) + prefill
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(rng, spans, 16, H, D)
    for window in (8, 16):
        want, got = _both(
            q, k, v, tables, qs, ql, kv_len, rs, tseq, tpos, window=window
        )
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5, err_msg=f"window={window}"
        )


# ---------------------------------------------------------------------------
# The pipeline's seams: the kernel walks every span in ONE program, keeps
# the page ring full across spans, starts outputs without waiting for them
# and takes a short tile (a decode row, a diffusion block) or a long one by
# the span's length. Each case is a mixed batch against the jnp twin.
# ---------------------------------------------------------------------------

from dynamo_tpu.ops.pallas import ragged_attention as ragged_kernel

_NBUF, _PP = ragged_kernel.ring_shape(BS * 2 * 128 * 2)
_FOLD = _PP * BS                  # keys a fold
_LONG = ragged_kernel.LONG_TILE   # rows of a long span's tile

# name -> (spans, geometry and mode). ``None`` in ``spans`` is an idle
# metadata row; a span is (prefix, rows).
_SEAMS = {
    "idle_first_last_between": dict(
        spans=[None, (36, 1), None, None, (0, 20), (9, 1), None]),
    "single_span_decode": dict(spans=[(41, 1)]),
    "single_span_long": dict(spans=[(5, 2 * _LONG + 3)]),
    "all_idle": dict(spans=[None, None]),
    "row_between_long_spans": dict(
        spans=[(0, _LONG + 5), (30, 1), (16, _LONG), (0, 1), (3, 7)]),
    "row_between_long_spans_wide_tile": dict(
        spans=[(0, 40), (30, 1), (16, 37), (63, 1)], q_tile=128),
    "less_than_one_fold": dict(spans=[(4, 1), (0, 1), (_FOLD - 2, 1)]),
    "ring_depth_boundaries": dict(
        # contexts of NBUF - 1 and NBUF folds, and one key more of each,
        # between short neighbours: the ring wraps inside and across spans
        spans=[(6, 1), ((_NBUF - 1) * _FOLD - 1, 1), ((_NBUF - 1) * _FOLD, 1),
               (2, 1), (_NBUF * _FOLD - 1, 1), (_NBUF * _FOLD, 1), (77, 1)]),
    "long_span_over_deep_context": dict(
        spans=[(11, 1), (_NBUF * _FOLD - 9, _LONG + 2), (19, 1)]),
    "tp4_chip_shape": dict(
        spans=[(199, 1), (640, 1), (0, 50), (128, 1), (300, 40)],
        H=8, kvH=2, window=4096),
    "sdar_chip_shape": dict(
        spans=[(8, 4), (0, 12), (36, 4), (4, 4), (16, 24)],
        H=32, kvH=4, diffusion_block=4),
    "diffusion_short_block": dict(
        # the last block of a sequence may hold fewer rows than a block
        spans=[(8, 2), (12, 4), (4, 1), (0, 8)], H=8, kvH=4,
        diffusion_block=4),
    "window_longer_than_context": dict(
        spans=[(150, 1), (0, 33), (60, 1)], window=4096),
    "window_binds": dict(
        spans=[(300, 1), (100, 40), (17, 1), (200, 3)], window=48),
    "int8_kv": dict(
        spans=[(36, 1), None, (0, 40), (260, 1), (130, 35)], int8=True),
    "int8_kv_window": dict(
        spans=[(280, 1), (100, 36), (5, 1)], int8=True, window=64),
    "bf16": dict(
        spans=[(140, 1), (0, 35), (3, 1), None, (130, 2)],
        dtype=jnp.bfloat16, H=8, kvH=4),
    "bf16_tp4_chip_shape": dict(
        spans=[(270, 1), (9, 1), (100, 33)], dtype=jnp.bfloat16, H=8, kvH=2,
        window=4096),
    "draft_verify_rows": dict(
        spans=[(35, 4), (0, 3), (140, 1), (0, 10), (50, 5)]),
    "rows_nobody_owns": dict(spans=[(20, 1), (0, 9)], gap=5),
}


# The long tile's fold (PR 46: scores held transposed, bf16 K and V read
# from the slot's 32-bit words a head pair at a time) where its mask does
# different work: folds every row sees whole, the diagonal, a window's
# lower edge, a span's partial last tile. Each case is a long span between
# two short ones, so the ring's one order runs through both tiles; at 16, 4,
# 4 and 32 queries a cached head.
_K = _FOLD


def _long_fold_cases(H, kvH):
    tl = ragged_kernel.long_tile(H, kvH)
    bf16 = dict(dtype=jnp.bfloat16)
    return {
        # (a) a context under one fold: the diagonal is all there is
        "only_edge_folds": dict(
            spans=[(33, 1), (40, tl + 3), (7, 1)], **bf16),
        # (b) window edge, an interior run, the diagonal
        "interior_run_under_window": dict(
            spans=[(2 * _K + 5, 1), (5 * _K + 7, 2 * tl), (90, 1)],
            window=2 * _K + 88, **bf16),
        # (c) a window shorter than a fold: its lower edge and the diagonal
        # meet inside one fold, and no fold is interior
        "window_edge_inside_a_fold": dict(
            spans=[(300, 1), (2 * _K + 100, tl + 1), (12, 1)],
            window=100, **bf16),
        # (d) whole tiles over interior folds, then a partial tile whose
        # dead rows see nothing and are never written
        "partial_tile_beside_interior": dict(
            spans=[(19, 1), (3 * _K + 9, 2 * tl + 5), (_K + 1, 1)], **bf16),
        "partial_tile_beside_interior_f32": dict(
            spans=[(19, 1), (2 * _K + 9, tl + 5), (_K + 1, 1)]),
        # (e) a block mask over a span longer than a block
        "block_mask_long_span": dict(
            spans=[(8, 4), (2 * _K + 8, tl + 8), (36, 4)],
            diffusion_block=4, **bf16),
        # (f) int8 pages keep the dequantised f32 fold
        "int8_pages_interior": dict(
            spans=[(60, 1), (3 * _K + 30, tl + 2), (_K, 1)],
            window=2 * _K + 40, int8=True),
    }


# Each axis, not the whole product (interpret mode at 128 heads is slow):
# every case at 16 queries a cached head, two each at the other shapes.
for _H, _kvH, _names in (
    (128, 8, None),
    (32, 8, ("interior_run_under_window", "partial_tile_beside_interior")),
    (8, 2, ("window_edge_inside_a_fold", "block_mask_long_span")),
    (32, 1, ("only_edge_folds", "int8_pages_interior")),
):
    for _name, _case in _long_fold_cases(_H, _kvH).items():
        if _names is None or _name in _names:
            _SEAMS[f"long_{_name}_h{_H}_kv{_kvH}"] = dict(
                _case, H=_H, kvH=_kvH)

# The short tile by cached head (PR 47: where a head's rows x queries fill
# a sublane tile, a head's folded rows against its own keys, read at their
# stride): rows of 1 and of a block of 4, contexts that end inside a fold
# and on its edge, a window whose lower edge lies inside a fold, a short
# last block, the three cache dtypes. The long cases above carry short
# spans at every shape too (below the threshold at (32, 8), (8, 2), (32, 1)
# with one row).
_SEAMS.update({
    "short_by_head_folds_h128_kv8": dict(
        spans=[(3 * _K + 17, 1), (_K - 1, 1), (_K, 1), (5, 1)],
        H=128, kvH=8, dtype=jnp.bfloat16),
    "short_by_head_window_h128_kv8": dict(
        spans=[(3 * _K + 17, 1), (2 * _K + 100, 1), (40, 1)],
        H=128, kvH=8, window=_K + 60, dtype=jnp.bfloat16),
    "short_by_head_blocks_h32_kv4": dict(
        spans=[(2 * _K + 8, 4), (_K - 4, 4), (_K + 4, 2), (8, 4)],
        H=32, kvH=4, diffusion_block=4, dtype=jnp.bfloat16),
    "short_by_head_blocks_f32_h32_kv4": dict(
        spans=[(_K + 8, 4), (12, 3)], H=32, kvH=4, diffusion_block=4),
    "short_by_head_blocks_int8_h32_kv4": dict(
        spans=[(_K + 8, 4), (12, 3)], H=32, kvH=4, diffusion_block=4,
        int8=True),
    # an odd number of cached heads: no head pair a word, the f32 fold
    "short_by_head_odd_heads_h24_kv3": dict(
        spans=[(_K + 9, 1), (30, 1), (3, 20)], H=24, kvH=3,
        dtype=jnp.bfloat16),
    # 4 queries a head fill a sublane tile only with a block's 4 rows
    "short_by_head_blocks_h32_kv8": dict(
        spans=[(_K + 12, 4), (20, 4), (4, 1)], H=32, kvH=8,
        diffusion_block=4, dtype=jnp.bfloat16),
})


@pytest.mark.parametrize("name", sorted(_SEAMS))
def test_pipeline_seams(name):
    case = dict(_SEAMS[name])
    spans = [(0, 0) if sp is None else sp for sp in case.pop("spans")]
    H, kvH = case.get("H", 8), case.get("kvH", 2)
    dtype = case.get("dtype", jnp.float32)
    window = case.get("window", 0)
    B = case.get("diffusion_block", 1)
    gap = case.get("gap", 0)  # budget rows left between the spans
    D = 128
    rng = np.random.default_rng(sorted(_SEAMS).index(name))
    max_blocks = max(-(-(p + n) // BS) for p, n in spans) + 1
    num_blocks = len(spans) * max_blocks + 1
    tables = _tables(rng, len(spans), max_blocks, num_blocks)
    T = sum(n + gap for _, n in spans) + 3
    q, qs, ql, kv_len, rs, tseq, tpos = _flat_batch(
        rng, spans, T - gap * len(spans), H, D, dtype
    )
    if gap:
        # spread the spans: ``gap`` rows nobody owns after each
        rows = np.concatenate([
            np.arange(int(r), int(r) + int(n)) for r, n in zip(rs, ql)
        ])
        shift = np.concatenate([
            np.full(int(n), i * gap) for i, n in enumerate(ql)
        ])
        big = jnp.asarray(rng.standard_normal((T, H, D)), dtype)
        q = big.at[rows + shift].set(q[rows])
        seq2 = np.zeros(T, np.int32)
        pos2 = np.full(T, -1, np.int32)
        seq2[rows + shift] = np.asarray(tseq)[rows]
        pos2[rows + shift] = np.asarray(tpos)[rows]
        tseq, tpos = jnp.asarray(seq2), jnp.asarray(pos2)
        rs = rs + jnp.arange(len(spans), dtype=jnp.int32) * gap
    scales = {}
    if case.get("int8"):
        shape = (num_blocks * BS, kvH, D)
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        scales = {
            name_: jnp.asarray(
                rng.uniform(0.002, 0.02, (num_blocks, kvH)), jnp.float32
            )
            for name_ in ("k_scales", "v_scales")
        }
    else:
        k, v = _caches(rng, num_blocks, kvH, D, dtype)
    want = np.asarray(ragged_paged_attention(
        q, k, v, tables, tseq, tpos, BS, window, diffusion_block=B,
        kv_len=kv_len, **scales,
    )).astype(np.float32)
    got = np.asarray(ragged_paged_attention_pallas(
        q, k, v, tables, qs, ql, kv_len, rs, BS,
        q_tile=case.get("q_tile", 8), window=window, diffusion_block=B,
        **scales,
    )).astype(np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    owned = np.asarray(tpos) >= 0
    assert owned.sum() == sum(n for _, n in spans)
    assert not got[~owned].any(), "a row no span owns must read zero"
    if owned.any():
        assert np.abs(got[owned]).max() > 0


@pytest.mark.parametrize(
    "kv_heads,itemsize,block,want_pp",
    [
        (2, 2, 16, 16),    # tp=4's share: 8 KiB pages, folds of 256 keys
        (8, 2, 16, 16),    # one chip: 32 KiB pages, a slot of 512 KiB
        (4, 2, 16, 16),    # SDAR
        (8, 1, 16, 16),    # int8 KV: half the bytes, the same keys
        (32, 2, 16, 8),    # no GQA: 128 KiB pages cap the slot; 128 keys
        (8, 2, 32, 8),     # a larger block: the same 256 keys
        (64, 4, 16, 8),    # pages past the slot: never under a lane tile
    ],
)
def test_ring_shape_follows_the_page(kv_heads, itemsize, block, want_pp):
    """Pages a fold come from what the kernel can observe (a page's bytes
    and the block size), never from a model's name."""
    nbuf, pp = ragged_kernel.ring_shape(
        block * kv_heads * 128 * itemsize, block
    )
    assert pp == want_pp
    assert (pp * block) % 128 == 0      # a fold fills whole lane tiles
    assert nbuf >= 2                    # the ring spans spans


@pytest.mark.parametrize(
    "heads,kv_heads,want",
    [
        (8, 2, 32),     # a tp=4 chip's share of 32 heads over 8
        (32, 8, 32),    # one chip, 4 queries a cached head
        (32, 4, 32),    # SDAR, 8
        (128, 8, 32),   # Command A+, 16: 512 folded rows a cached head
        (32, 1, 16),    # one latent head under 32 queries: never under 16
        (64, 1, 16),
        (56, 8, 32),    # 7 queries a head: 224 folded rows
        (24, 8, 40),    # 3: a lane tile of folded rows less 8 (whole 8 rows)
        (16, 8, 64),    # 2, and
        (32, 32, 128),  # no grouping: a lane tile of folded rows at least
        (8, 8, 128),
    ],
)
def test_long_tile_follows_the_heads(heads, kv_heads, want):
    """A long fold's scores a cached head are [keys, rows x G], the folded
    rows along the lanes: the tile holds 512 of them where 16 to 32 rows
    allow it and never under a lane tile (the tool's readings:
    ops/pallas/ragged_attention.py), whatever the model."""
    assert ragged_kernel.long_tile(heads, kv_heads) == want


@pytest.mark.parametrize(
    "rows,heads,kv_heads,want",
    [
        (1, 128, 8, True),    # Command A+: 16 queries a cached head
        (1, 32, 4, True),     # 8: a sublane tile
        (4, 32, 4, True),     # SDAR's block: 32 rows a head
        (1, 32, 8, False),    # Mistral, Mixtral: 4 queries a head
        (1, 8, 2, False),     # a tp=4 chip's share
        (4, 32, 8, True),     # 4 a head under a block of 4
        (2, 8, 4, False),
        (1, 32, 1, False),    # one latent head: the slot is the head
        (4, 32, 1, False),
    ],
)
def test_short_fold_goes_by_head_where_a_heads_rows_fill_a_tile(
    rows, heads, kv_heads, want
):
    """The short tile folds one cached head at a time where rows x queries
    a head reach a sublane tile (the tool read 4 a head no faster by head)
    and there is more than one head; by the shapes alone."""
    assert ragged_kernel.short_by_head(rows, heads, kv_heads) is want


def _tile_folds_by_hand(spans, tile, K, window, B):
    """The kernel's ``tile_folds`` a tile at a time, in plain integers:
    ``(short, long)`` folds of the spans' tiles."""
    out = [0, 0]
    for q0, n in spans:
        tq = B if n <= B else tile
        for first in range(q0, q0 + n, tq):
            hi = first + tq if B == 1 else ((first + tq - 1) // B + 1) * B
            nb = -(-min(hi, q0 + n) // BS)
            lo_f = max(first - window + 1, 0) // K if window else 0
            out[n > B] += -(-nb // (K // BS)) - lo_f
    return tuple(out)


def test_host_fold_counts_match_the_kernels_tile_folds():
    """``fold_counts`` (the flight record's ``attn_short_folds`` /
    ``attn_long_folds``) is ``tile_folds`` summed over every tile of every
    live span: random spans, windows, block masks, tiles and fold sizes."""
    rng = np.random.default_rng(47)
    for _ in range(300):
        B = int(rng.choice([1, 1, 4]))
        window = 0 if B > 1 else int(rng.choice([0, 100, 777, 4096]))
        n = int(rng.integers(1, 40))
        K = int(rng.choice([128, 256]))
        tile = int(rng.choice([16, 32, 40, 128]))
        q0 = rng.integers(0, 9000, n).astype(np.int32) // B * B
        short = rng.random(n) < 0.8
        ql = np.where(
            short, rng.integers(1, B + 1, n), rng.integers(B + 1, 900, n)
        ).astype(np.int32)
        ql = np.where(short, ql, -(-ql // B) * B).astype(np.int32)
        got = ragged_kernel.fold_counts(
            q0, ql, q0 + ql, long_rows=tile, fold_keys=K, window=window,
            diffusion_block=B)
        want = _tile_folds_by_hand(
            list(zip(q0.tolist(), ql.tolist())), tile, K, window, B)
        assert got == want, (got, want, B, window, K, tile)
    # a step of decode rows only walks no long fold
    ctx = np.asarray([1, 256, 257, 5000], np.int32)
    assert ragged_kernel.fold_counts(
        ctx - 1, np.ones(4, np.int32), ctx, long_rows=32, fold_keys=256,
        window=4096) == (1 + 1 + 2 + 17, 0)


def test_fold_counts_of_a_dispatch_that_mixes_b_and_2b_row_spans(monkeypatch):
    """A block model's dispatch where some lanes' commits ride (PR 55):
    spans of B rows take the SHORT tile, a span of 2B rows (a finished
    block, then the next block's masks) the LONG tile, one tile whose last
    row sees the span's end; a prefill quantum beside them. The host's
    count is the kernel's ``tile_folds`` span by span, and the runner notes
    it for the dispatch it packs (``attn_folds``: every layer's)."""
    B, K, tile = 4, 256, 32
    spans = [(100, 4), (1032, 8), (508, 4), (252, 8), (0, 100), (2044, 8),
             (1020, 4), (256, 8)]
    q0, ql = (np.asarray(x, np.int32) for x in zip(*spans))
    got = ragged_kernel.fold_counts(
        q0, ql, q0 + ql, long_rows=tile, fold_keys=K, diffusion_block=B)
    assert got == _tile_folds_by_hand(spans, tile, K, 0, B)
    # three B spans: 1 + 2 + 4 folds; four rides and the quantum (4 tiles)
    assert got == (1 + 2 + 4, (5 + 2 + 9 + 2) + 4)
    # a ride's one tile walks the folds the lone commit pass and the next
    # block's first pass would each have walked
    for p, n in spans:
        if n == 2 * B:
            lone = ragged_kernel.fold_counts(
                *(np.asarray([x], np.int32) for x in (p + B, B, p + 2 * B)),
                long_rows=tile, fold_keys=K, diffusion_block=B)
            ride = ragged_kernel.fold_counts(
                *(np.asarray([x], np.int32) for x in (p, n, p + n)),
                long_rows=tile, fold_keys=K, diffusion_block=B)
            assert ride == (0, lone[0])

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models.config import ModelConfig

    monkeypatch.setenv("DYNAMO_TPU_PALLAS", "1")
    model = ModelConfig.tiny_sdar_test()
    runner = ModelRunner(EngineConfig(
        model=model, dtype="float32", block_size=8, num_blocks=64,
        max_num_seqs=4, max_model_len=256, seed=0,
        unified_token_budget=64, unified_prefill_quantum=16), rng_seed=0)
    plan = runner._fold_plan["count"].keywords
    small = [(40, 4), (132, 8), (0, 24), (200, 8), (16, 4)]
    lanes = [([7] * n, [1], p, (0.0, 0, 1.0)) for p, n in small]
    runner._unified_operands(lanes, None, 64)
    short, long = _tile_folds_by_hand(
        small, plan["long_rows"], plan["fold_keys"], 0, B)
    assert long and runner.attn_folds == (
        model.num_layers * short, model.num_layers * long)


def test_the_tools_ride_shape_is_the_cells_dispatch_with_a_lane_in_four_riding():
    """``tools/ragged_kernel_bench.py`` ``sdar-ride`` (what set the tile a
    2B span takes): 64 lanes on block boundaries, sixteen of them spans of
    two blocks, beside a quantum that starts on one; inside the rung."""
    from tools import ragged_kernel_bench as tool

    shape = tool.SHAPES["sdar-ride"]
    B = shape["diffusion_block"]
    rng = np.random.default_rng(0)
    contexts = tool.mixed_contexts(shape, rng, 200, 1500)
    assert contexts.min() >= 100 and contexts.max() <= 1500
    # the operands at a head width that costs the CPU nothing
    *_ops, meta, spans = tool.build(
        dict(shape, H=2, kvH=1, D=8), contexts, shape["prefill_ends"][0], rng)
    assert [n for _, n in spans] == [2 * B] * 16 + [B] * 48 + [100]
    assert all(p % B == 0 for p, _ in spans)
    assert sum(n for _, n in spans) <= shape["T"]
    folds = tool.fold_counts(shape, spans)
    assert folds["long_folds"] > 16 and folds["short_folds"] >= 48
    # the plain cell's shape is built as it was: every lane B rows
    *_ops, _meta, plain = tool.build(
        dict(tool.SHAPES["sdar"], H=2, kvH=1, D=8), contexts, 750, rng)
    assert [n for _, n in plain] == [B] * 64 + [60]


def test_unified_verify_rows_match_reference_forward():
    """llama.unified verify_rows > 1: every verify row's logits equal
    the no-cache reference forward at the same position — the law the
    in-dispatch accept-prefix check scores drafts against."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    prompt = [1, 5, 9, 2, 7, 3]
    P = len(prompt)
    drafts = [11, 12, 4]
    num_slots = 8 * BS
    kv_caches = [
        (
            jnp.zeros((num_slots, cfg.num_kv_heads, cfg.head_dim)),
            jnp.zeros((num_slots, cfg.num_kv_heads, cfg.head_dim)),
        )
        for _ in range(cfg.num_layers)
    ]

    def build(toks, prefix, S=2):
        T = 16
        token_ids = np.zeros(T, np.int32)
        token_ids[: len(toks)] = toks
        token_pos = np.full(T, -1, np.int32)
        token_pos[: len(toks)] = np.arange(prefix, prefix + len(toks))
        slot_mapping = np.zeros(T, np.int32)
        slot_mapping[: len(toks)] = np.arange(
            BS + prefix, BS + prefix + len(toks)
        )  # block 1
        token_seq = np.zeros(T, np.int32)
        tables = np.zeros((S, 4), np.int32)
        tables[0, 0] = 1
        n = len(toks)
        return (
            jnp.asarray(token_ids), jnp.asarray(token_pos),
            jnp.asarray(slot_mapping), jnp.asarray(token_seq),
            jnp.asarray(tables),
            jnp.asarray([prefix, 0], jnp.int32),
            jnp.asarray([n, 0], jnp.int32),
            jnp.asarray([prefix + n, 0], jnp.int32),
            jnp.asarray([0, 0], jnp.int32),
        )

    # Prefill the prompt (all but the last token is "fed history"; the
    # verify span feeds the last prompt token + the drafts).
    _, kv_caches = llama.unified(
        cfg, params, kv_caches, *build(prompt[:-1], 0), BS
    )
    verify = [prompt[-1]] + drafts
    K = len(drafts)
    logits, _ = llama.unified(
        cfg, params, kv_caches, *build(verify, P - 1), BS,
        draft_len=jnp.asarray([K, 0], jnp.int32), verify_rows=K + 1,
    )
    assert logits.shape[:2] == (2, K + 1)
    full = prompt + drafts
    ref = llama.reference_forward(cfg, params, jnp.asarray(full))
    for j in range(K + 1):
        np.testing.assert_allclose(
            np.asarray(logits[0, j]), np.asarray(ref[P - 1 + j]),
            rtol=2e-4, atol=2e-4, err_msg=f"verify row {j}",
        )


def test_unified_model_forward_matches_no_cache_oracle():
    """llama.unified end-to-end (tiny model, XLA twin path): a full-prompt
    span's logits must match the no-cache greedy oracle's last-token
    logits."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    prompt = [5, 9, 2, 7, 11, 3]
    P = len(prompt)
    num_slots = 8 * BS
    kv_caches = [
        (
            jnp.zeros((num_slots, cfg.num_kv_heads, cfg.head_dim)),
            jnp.zeros((num_slots, cfg.num_kv_heads, cfg.head_dim)),
        )
        for _ in range(cfg.num_layers)
    ]
    T, S = 16, 2
    token_ids = np.zeros(T, np.int32)
    token_ids[:P] = prompt
    token_pos = np.full(T, -1, np.int32)
    token_pos[:P] = np.arange(P)
    slot_mapping = np.zeros(T, np.int32)
    slot_mapping[:P] = np.arange(BS, BS + P)  # block 1
    token_seq = np.zeros(T, np.int32)
    tables = np.zeros((S, 4), np.int32)
    tables[0, 0] = 1
    logits, _ = llama.unified(
        cfg, params, kv_caches,
        jnp.asarray(token_ids), jnp.asarray(token_pos),
        jnp.asarray(slot_mapping), jnp.asarray(token_seq),
        jnp.asarray(tables),
        jnp.asarray([0, 0], jnp.int32), jnp.asarray([P, 0], jnp.int32),
        jnp.asarray([P, 0], jnp.int32), jnp.asarray([0, 0], jnp.int32),
        BS,
    )
    want = llama.reference_forward(cfg, params, jnp.asarray(prompt))[-1]
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(want), rtol=2e-4, atol=2e-4
    )


# -- latent attention's expanded form over a cache held once (PR 53) ---------

# tiny latent widths (the tiny presets'): q_nope 16, rotated tail 8, latent
# 32 in a cache entry lane-padded to 128, values 16
L_NOPE, L_ROPE, L_RANK, L_V, L_DC = 16, 8, 32, 16, 128


def _latent_case(rng, spans, T, H=4, gaps=None, num_blocks=64, max_blocks=28):
    """One dispatch over a latent cache held once: spans ``[(prefix,
    rows), ...]`` with ``gaps[i]`` padding rows before span i; un-absorbed
    q, the one array, ``w_uk`` / ``w_uv``, span and token metadata."""
    S = len(spans)
    gaps = gaps or [0] * S
    q_start = np.array([p for p, _ in spans], np.int32)
    q_len = np.array([n for _, n in spans], np.int32)
    row_start = np.zeros(S, np.int32)
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    tables = np.zeros((S, max_blocks), np.int32)
    ids = rng.permutation(np.arange(1, num_blocks))
    cursor = used = 0
    for s, (p, n) in enumerate(spans):
        cursor += gaps[s]
        row_start[s] = cursor
        token_seq[cursor: cursor + n] = s
        token_pos[cursor: cursor + n] = np.arange(p, p + n)
        cursor += n
        nb = -(-(p + n) // BS)
        tables[s, :nb] = ids[used: used + nb]
        used += nb
    assert cursor <= T and used < num_blocks
    f32 = jnp.float32
    q = jnp.asarray(rng.standard_normal((T, H, L_NOPE + L_ROPE)), f32)
    cache = np.zeros((num_blocks * BS, 1, L_DC), np.float32)
    cache[..., : L_RANK + L_ROPE] = rng.standard_normal(
        (num_blocks * BS, 1, L_RANK + L_ROPE))
    w_uk = jnp.asarray(
        rng.standard_normal((H, L_NOPE, L_RANK)) / L_RANK**0.5, f32)
    w_uv = jnp.asarray(
        rng.standard_normal((H, L_V, L_RANK)) / L_RANK**0.5, f32)
    meta = tuple(jnp.asarray(a) for a in (
        tables, q_start, q_len, q_start + q_len, row_start))
    return (q, jnp.asarray(cache), w_uk, w_uv, meta,
            jnp.asarray(token_seq), jnp.asarray(token_pos))


def _absorbed(q, w_uk, scale):
    """The absorbed call's q: projected into the latent space, the scale
    folded in against the kernels' ``1 / sqrt(width)``, lane-padded."""
    q_lat = jnp.einsum("thn,hnc->thc", q[..., :L_NOPE], w_uk)
    q_abs = jnp.concatenate([q_lat, q[..., L_NOPE:]], -1) * (
        scale * L_DC**0.5)
    return jnp.pad(q_abs, ((0, 0), (0, 0), (0, L_DC - L_RANK - L_ROPE)))


@pytest.mark.parametrize("name,spans,gaps,T,min_rows,tile", [
    # (a) a long span from position 0, whole in one chunk
    ("from_zero", [(0, 100)], None, 128, 20, None),
    # (b) behind a prefix that is no multiple of the block (16) or the fold
    # (256), its keys in two folds, rows in pieces of 16, chunks of 32 and
    # tiles of 64
    ("behind_prefix", [(261, 150)], None, 256, 20, (16, 32, 64)),
    # (c) two long spans and decode lanes in one dispatch, padding rows
    # between them; the lanes and the 7-row span are not this body's
    ("mixed", [(5, 1), (21, 60), (9, 1), (130, 90), (4, 7)],
     [0, 2, 1, 5, 0], 256, 20, (16, 32, 64)),
    # a span of several tiles whose last tile is partial, from position 0
    ("tiles", [(0, 170)], [3], 256, 20, (16, 32, 64)),
    # the same dispatch in bfloat16: a step's heads leave the staged piece
    # two to a 32-bit word
    ("mixed_bf16", [(5, 1), (21, 60), (9, 1), (130, 90), (4, 7)],
     [0, 2, 1, 5, 0], 256, 20, (16, 32, 64)),
])
def test_expanded_body_equals_the_twin_and_the_absorbed_kernel(
    monkeypatch, name, spans, gaps, T, min_rows, tile
):
    """The expanded body (interpret mode, float32) over the same latent
    pages: against the jnp twin and against the absorbed kernel, both
    up-projected by ``w_uv`` afterwards, to 1e-5 on the long spans' rows;
    every other row zero. ``tile`` shrinks the staged piece, the chunk and
    the tile so that the piece loop, the chunk loop, the folds a chunk
    skips and the tile loop all run."""
    from dynamo_tpu.ops.pallas import latent_expanded as le

    if tile:
        monkeypatch.setattr(le, "EXPANDED_PIECE", tile[0])
        monkeypatch.setattr(le, "EXPANDED_CHUNK", tile[1])
        monkeypatch.setattr(le, "EXPANDED_TILE", tile[2])
    rng = np.random.default_rng(len(name))
    q, cache, w_uk, w_uv, meta, token_seq, token_pos = _latent_case(
        rng, spans, T, gaps=gaps)
    tol = 1e-5
    if name.endswith("bf16"):
        # bf16 operands against the float32 twin of the SAME rounded inputs
        tol = 2e-2
        q, cache, w_uk, w_uv = (
            a.astype(jnp.bfloat16) for a in (q, cache, w_uk, w_uv))
    tables, q_start, q_len, kv_len, row_start = meta
    scale = 1.3 * (L_NOPE + L_ROPE) ** -0.5
    long = q_len >= min_rows
    # un-jitted: the constants above are read while it is traced
    got = le.ragged_paged_attention_pallas_expanded.__wrapped__(
        q, cache, w_uk, w_uv, tables, q_start, jnp.where(long, q_len, 0),
        row_start, block_size=BS, scale=scale)
    f32 = jnp.float32
    q, cache, w_uk, w_uv = (a.astype(f32) for a in (q, cache, w_uk, w_uv))
    got = got.astype(f32)
    q_abs = _absorbed(q, w_uk, scale)
    twin = ragged_paged_attention(
        q_abs, cache, cache, tables, token_seq, token_pos, BS, kv_len=kv_len)
    kernel = ragged_paged_attention_pallas(
        q_abs, cache, None, *meta, block_size=BS)
    mine = np.asarray(long)[np.asarray(token_seq)] & (
        np.asarray(token_pos) >= 0)
    assert mine.sum() == int(jnp.where(long, q_len, 0).sum())
    for other in (twin, kernel):
        want = jnp.einsum("thc,hvc->thv", other[..., :L_RANK], w_uv)
        np.testing.assert_allclose(
            got[mine], want[mine], rtol=tol, atol=tol)
    assert not np.asarray(got)[~mine].any()


def test_the_hosts_rule_is_the_programs_rule():
    """``expanded_spans`` is ONE function: numpy int32 on the engine's
    thread and traced jnp int32 inside the step agree on 1,000 random span
    sets, at the thresholds of several width sets; and it is the
    arithmetic it says it is (exact rationals), a span from position 0
    passing at K rows and one behind a long prefix past K / 2."""
    from fractions import Fraction

    from dynamo_tpu.ops.pallas.latent_expanded import expanded_spans

    rng = np.random.default_rng(53)
    traced = jax.jit(expanded_spans, static_argnums=2)
    for _ in range(1000):
        k = int(rng.choice([96, 342, 512, 513, 1000]))
        n = int(rng.integers(1, 48))
        near = rng.random(n) < 0.5
        q_len = np.where(
            near, rng.integers(max(k // 2 - 3, 1), k + 4, n),
            rng.integers(1, 4096, n)).astype(np.int32)
        kv_len = (q_len + rng.integers(0, 40000, n) * (rng.random(n) < 0.8)
                  ).astype(np.int32)
        host = expanded_spans(q_len, kv_len, k)
        assert host.dtype == np.bool_
        np.testing.assert_array_equal(
            host, np.asarray(traced(jnp.asarray(q_len), jnp.asarray(kv_len), k)))
        for ql, kv, got in zip(q_len.tolist(), kv_len.tolist(), host):
            pairs = Fraction(ql * (2 * kv - ql + 1), 2)
            assert got == (pairs * 2 > kv * k), (ql, kv, k)
    k = 342
    at = lambda n, p: bool(expanded_spans(  # noqa: E731
        np.int32([n]), np.int32([p + n]), k)[0])
    assert not at(k - 1, 0) and at(k, 0)
    assert not at(k // 2, 10**6) and at(k // 2 + 1, 10**6)
    assert not at(1, 0) and not at(1, 10**6)
