"""The delta-rule linear-attention op (ops/linear_attention.py): the Pallas
kernels in interpret mode, the XLA twin and a naive per-token recurrence
agree; the state after a prompt does not depend on how it was cut."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import linear_attention as la

H, D, SLOTS = 2, 8, 5


def naive(q, k, v, g, beta, state):
    """One sequence, token by token, in numpy float64."""
    S = np.array(state, np.float64)
    out = []
    for t in range(len(q)):
        S = np.exp(g[t])[:, :, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hk,hkv->hv", q[t], S))
    return np.array(out), S


def draw(seed, n):
    r = np.random.default_rng(seed)
    q, k, v = (r.standard_normal((n, H, D)).astype(np.float32) for _ in "qkv")
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -5 * r.uniform(0.01, 0.3, (n, H, D)).astype(np.float32)
    beta = r.uniform(0.1, 0.9, (n, H)).astype(np.float32)
    return q, k, v, g, beta


def dispatch(spans, T, S_rows):
    """Metadata of one dispatch: spans as (slot, q_start, n)."""
    token_seq = np.zeros(T, np.int32)
    token_pos = np.full(T, -1, np.int32)
    q_start, q_len, row_start, slots = (np.zeros(S_rows, np.int32) for _ in "1234")
    at = 0
    for s, (slot, start, n) in enumerate(spans):
        token_seq[at : at + n] = s
        token_pos[at : at + n] = start + np.arange(n)
        q_start[s], q_len[s], row_start[s], slots[s] = start, n, at, slot
        at += n
    return tuple(map(jnp.asarray, (token_seq, token_pos, q_start, q_len, row_start, slots)))


def run(path, seqs, plan, T=16, S_rows=6, state=None):
    """``plan``: dispatches, each a list of (sequence, start, n); sequence
    ``b`` owns slot ``b + 1``. Returns (outputs by sequence, the state)."""
    if state is None:
        state = jnp.full((SLOTS, H, D, D), 7.0, jnp.float32)  # stale rows
    outs = {b: [] for b in range(len(seqs))}
    for spans in plan:
        flat = [np.zeros((T, H, D), np.float32) for _ in range(4)] + [
            np.zeros((T, H), np.float32)]
        at = 0
        for b, start, n in spans:
            for buf, src in zip(flat, seqs[b]):
                buf[at : at + n] = src[start : start + n]
            at += n
        meta = dispatch([(b + 1, start, n) for b, start, n in spans], T, S_rows)
        o, state = la.kda_ragged(
            *map(jnp.asarray, flat), state, *meta, use_pallas=path == "pallas",
            lower_bound=-5.0)
        at = 0
        for b, start, n in spans:
            outs[b].append(np.asarray(o[at : at + n]))
            at += n
    return {b: np.concatenate(v) for b, v in outs.items()}, np.asarray(state)


PLAN = [
    [(0, 0, 5), (1, 0, 7)],              # two prefill quanta
    [(0, 5, 1), (1, 7, 4), (2, 0, 1)],   # a decode lane, a quantum, a one-token prompt
    [(1, 11, 1), (0, 6, 1), (2, 1, 1)],  # decode lanes
]


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("stale", [7.0, float("nan")])
def test_paths_equal_the_naive_recurrence(path, stale):
    """Every slot starts out holding ``stale``: a span at position 0 selects
    zeros (it does not multiply the slot by 0), so even a NaN its last owner
    left there ends with the slot's reuse."""
    seqs = [draw(1, 7), draw(2, 12), draw(3, 2)]
    held = jnp.full((SLOTS, H, D, D), stale, jnp.float32)
    outs, state = run(path, seqs, PLAN, state=held)
    for b, seq in enumerate(seqs):
        want_o, want_s = naive(*seq, np.zeros((H, D, D)))
        np.testing.assert_allclose(outs[b], want_o, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(state[b + 1], want_s, rtol=2e-5, atol=2e-5)
    # the slot no sequence owned keeps what it held
    assert np.array_equal(state[4], held[4], equal_nan=True)


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("cut", [[12], [4, 8], [1, 5, 6], [3, 3, 3, 3]])
def test_state_does_not_depend_on_the_cut(path, cut):
    seq = draw(5, 12)
    plan, at = [], 0
    for n in cut:
        plan.append([(0, at, n)])
        at += n
    outs, state = run(path, [seq], plan)
    want_o, want_s = naive(*seq, np.zeros((H, D, D)))
    np.testing.assert_allclose(outs[0], want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(state[1], want_s, rtol=2e-5, atol=2e-5)


def test_causal_conv_continues_from_the_tail():
    r = np.random.default_rng(0)
    K, C, n = 4, 6, 11
    x = r.standard_normal((n, C)).astype(np.float32)
    w = r.standard_normal((K, C)).astype(np.float32)
    padded = np.concatenate([np.zeros((K - 1, C), np.float32), x])
    want = sum(w[i] * padded[i : i + n] for i in range(K))
    tail = jnp.full((SLOTS, K - 1, C), 9.0, jnp.float32)
    got = []
    for start, m in ((0, 2), (2, 1), (3, 5), (8, 3)):
        T = 8
        flat = np.zeros((T, C), np.float32)
        flat[1 : 1 + m] = x[start : start + m]
        # an idle span in row 0 of the metadata, the real one behind it
        meta = [np.zeros(T, np.int32), np.full(T, -1, np.int32)] + [
            np.zeros(3, np.int32) for _ in range(4)]
        meta[0][1 : 1 + m] = 1
        meta[1][1 : 1 + m] = start + np.arange(m)
        meta[2][1], meta[3][1], meta[4][1], meta[5][1] = start, m, 1, 2
        y, tail = la.causal_conv(jnp.asarray(flat), jnp.asarray(w), tail,
                                 *map(jnp.asarray, meta))
        got.append(np.asarray(y[1 : 1 + m]))
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail[2]), x[-3:], rtol=0, atol=0)
    assert (np.asarray(tail[3]) == 9.0).all()


def _prompt(seed, n, g=None, beta=None):
    q, k, v, g0, b0 = draw(seed, n)
    if g is not None:
        g0 = np.full_like(g0, g)
    if beta is not None:
        b0 = np.full_like(b0, beta)
    return q, k, v, g0, b0


def _cuts(cut):
    plan, at = [], 0
    for n in cut:
        plan.append([(0, at, n)])
        at += n
    return plan


# (sequences, plan): the chunk kernel's tile is 64 rows and, at the bound of
# -5 the glue assumes, its sub-chunk 16.
CHUNK_CASES = {
    **{
        f"span-{n}": ([_prompt(10 + n, 3 + n)], _cuts([3, n]))
        for n in (2, 15, 16, 17, 63, 64, 65, 130)
    },
    "cut-at-edges": ([_prompt(30, 150)], _cuts([16, 48, 64, 22])),
    "cut-across-edges": ([_prompt(31, 150)], _cuts([17, 46, 66, 21])),
    "cut-in-tiles": ([_prompt(32, 150)], _cuts([128, 22])),
    "g-at-the-bound": ([_prompt(33, 70, g=-5.0)], _cuts([3, 64, 3])),
    "g-zero": ([_prompt(34, 70, g=0.0)], _cuts([3, 64, 3])),
    "beta-zero": ([_prompt(35, 40, beta=0.0)], _cuts([2, 38])),
    "beta-one": ([_prompt(36, 40, beta=1.0)], _cuts([2, 38])),
    "three-spans-beside-lanes": (
        [_prompt(37, 80), _prompt(38, 30), _prompt(39, 10), _prompt(40, 4)],
        [
            [(0, 0, 10), (1, 0, 1), (3, 0, 2)],
            # three multi-row spans, the first over a tile, beside a lane
            [(0, 10, 70), (1, 1, 20), (2, 0, 9), (3, 2, 1)],
            [(3, 3, 1), (1, 21, 9), (2, 9, 1)],
        ],
    ),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_chunk_kernel_equals_the_naive_recurrence(case):
    """The chunkwise form (interpret mode) against the recurrence in
    float64: spans around the tile's and the sub-chunk's edges, a prompt
    cut at and across them, the decay at its bound and absent, beta at both
    ends, several spans beside decode lanes. The trash slot and a slot no
    sequence owns keep what they held."""
    seqs, plan = CHUNK_CASES[case]
    held = jnp.full((SLOTS, H, D, D), 7.0, jnp.float32)
    outs, state = run("pallas", seqs, plan, T=136, state=held)
    assert np.isfinite(state).all()
    for b, seq in enumerate(seqs):
        want_o, want_s = naive(*seq, np.zeros((H, D, D)))
        assert np.isfinite(outs[b]).all()
        np.testing.assert_allclose(outs[b], want_o, rtol=5e-5, atol=5e-5)
        np.testing.assert_allclose(state[b + 1], want_s, rtol=5e-5, atol=5e-5)
    for slot in range(len(seqs) + 1, SLOTS):
        assert np.array_equal(state[slot], held[slot])
    assert np.array_equal(state[0], held[0])
