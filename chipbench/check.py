"""The comparison that decides ``correct``: the served runner against the
plain reference, outside the timed window.

A seeded sample of sequences at the served widths goes through the
runner the way the engine drives it: every dispatch is a list of lanes
``(new_tokens, block_ids, prefix_len, sampling)`` handed to
``ModelRunner.unified_step`` — the served executables, the runner's own
operand building, its paged cache and the Pallas path in the served
dtype. Prompts are prefilled in chunks that share ragged dispatches at
the top budget rung, then come decode steps of one token a lane through
the cache. The tokens fed are the sample's own (seeded), never sampled
ones, so both sides see the same sequence.

Two things are read from each dispatch, one row for each span (at the
span's last position):

* the greedy token ``unified_step`` returns — the served program and its
  sampler;
* the logits, from the runner's model function jitted over the very
  operands ``ModelRunner._unified_operands`` built for that dispatch (the
  served program hands out tokens only).

The reference (``chipbench/reference/<family>.py``, float32, ``highest``,
weights drawn again from the seed) gives the logits of the same positions
from one full forward pass.

A reference of a new family is one module that imports nothing of the
program and provides

    logits(published, seed, tokens, rows, dtype, *, source_values=None,
           share=None) -> float32 [B, R, published["vocab_size"]]

* ``published`` is the configuration file's block, every key of it; the
  two keyword arguments are the file's ``source_values`` and ``share``
  blocks, passed only where the file has them (``share_arguments``; a
  family whose experts and heads are never held in part needs neither,
  as ``reference/mistral.py``);
* it draws the weights again from ``seed`` in the program's own order of
  splits and in the served ``dtype``, then computes in float32 at
  ``highest`` — it takes no array the program has made;
* it computes the same share and no more: the router at the source's
  width (``source_values``), the experts, heads and vocabulary rows that
  ``share`` says live here, what the absent ones would have added left
  out, as the program leaves it out. A sliced vocabulary is a smaller
  vocabulary: the sample's ids are drawn below ``published["vocab_size"]``
  and the logits are over the slice on both sides;
* it returns, for each sequence ``b``, the logits at positions ``rows[b]``.

Compared, each with its limit in the configuration's ``check`` block:

``rel_err``
    the ``quantile``-th percentile over the rows of ``|got - want|_2 /
    |want|_2``, held to ``limit``. The quantile is 100, the largest row,
    unless the block says otherwise: one wrong row fails it. A
    sparse-expert model compares a low quantile: with random weights a
    near-tied routing decision flips under any rounding and moves that row
    and the rows behind it (a row's error then reads 0.3-0.8 in sound
    runs), so its largest and its median follow those rows from seed to
    seed, while a low quantile follows the arithmetic's precision, which a
    lower-precision path changes in every row.
``rel_err_by_phase``
    where the block has a ``phase_limit``: the ``phase_quantile``-th
    percentile within the prefill rows and within the decode rows apart,
    so that a low quantile over all rows cannot pass while most of one
    phase's rows are wrong.
``token_mismatches``
    rows whose reference logits put the first token ``token_margin``
    logit-RMS or more ahead of the second, and where the served greedy
    token is not the reference's argmax. At most
    ``token_mismatch_limit``, 0 unless the block says otherwise.
"""

from __future__ import annotations

import inspect

import numpy as np

from chipbench import modelcfg

#: prompt lengths of the sample (every seed the same sizes) and decode steps
PROMPT_LENS = (5, 37, 80, 150, 230, 300, 450, 601)
DECODE_STEPS = 6
PAD_TO = 640
GREEDY = (0.0, 0, 1.0)


def sample_tokens(seed: int, vocab: int, lens=PROMPT_LENS,
                  decode_steps: int = DECODE_STEPS, pad_to: int = PAD_TO):
    rng = np.random.default_rng([int(seed), 7])
    tokens = np.zeros((len(lens), pad_to), np.int32)
    for b, n in enumerate(lens):
        tokens[b, : n + decode_steps] = rng.integers(1, vocab, n + decode_steps)
    return tokens


def plan_steps(lens, decode_steps: int, budget: int):
    """Dispatches as ``[(sequence, prefix_len, new_tokens), ...]``: prompts
    packed greedily into the budget, a long one split across dispatches
    (chunked prefill), then ``decode_steps`` dispatches of one token each."""
    steps, cur, room = [], [], budget
    for b, n in enumerate(lens):
        done = 0
        while done < n:
            take = min(n - done, room)
            cur.append((b, done, take))
            done += take
            room -= take
            if room == 0:
                steps.append(cur)
                cur, room = [], budget
    if cur:
        steps.append(cur)
    for i in range(decode_steps):
        steps.append([(b, n + i, 1) for b, n in enumerate(lens)])
    return steps


def runner_rows(runner, tokens, lens=PROMPT_LENS,
                decode_steps: int = DECODE_STEPS, seed: int = 0):
    """``(rows [B, R], decode [B, R] bool, logits [B, R, V] float32,
    served tokens [B, R])`` from the runner: one row per span per
    dispatch, at the span's last position; ``decode`` marks the rows of
    decode steps. Leaves the sample's keys and values in the runner's
    cache."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    cfg = runner.cfg
    bs, T = cfg.block_size, cfg.unified_token_budget
    rng = np.random.default_rng([int(seed), 8])
    need = -(-(max(lens) + decode_steps) // bs)
    assert need <= cfg.max_blocks_per_seq
    ids = rng.permutation(np.arange(1, cfg.num_blocks))[: need * len(lens)]
    tables = ids.reshape(len(lens), need).tolist()

    def logits_fn(params, kv, sc, token_ids, *meta):
        out = llama.unified(
            cfg.model, params, kv, token_ids, *meta, bs, attn=runner.attn,
            kv_scales=sc,
        )
        return (out[0].astype(jnp.float32), *out[1:])

    scales = runner.kv_scales
    kv_sh = jax.tree.map(lambda a: a.sharding, runner.kv_caches)
    out_sh = (None, kv_sh) if scales is None else (None, kv_sh, scales.sharding)
    fn = jax.jit(
        logits_fn, donate_argnums=(1,) if scales is None else (1, 2),
        out_shardings=out_sh,
    )
    rows = [[] for _ in lens]
    decode = [[] for _ in lens]
    got = [[] for _ in lens]
    served = [[] for _ in lens]
    for spans in plan_steps(lens, decode_steps, T):
        lanes = [
            (tokens[b, prefix : prefix + n].tolist(), tables[b], prefix, GREEDY)
            for b, prefix, n in spans
        ]
        toks = np.asarray(runner.unified_step(lanes).last)
        # The same dispatch again for its logits: the same keys and values
        # go to the same slots.
        base, meta, *_ = runner._unified_operands(lanes, None, T)
        out = fn(*base, *meta)
        runner.kv_caches = out[1]
        if scales is not None:
            runner.kv_scales = out[2]
        logits = np.asarray(out[0])
        for s, (b, prefix, n) in enumerate(spans):
            rows[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
            got[b].append(logits[s])
            served[b].append(int(toks[s]))
    width = max(len(r) for r in rows)
    # Pad the short sequences by repeating their last row: both sides
    # then hold the same (duplicated) rows.
    for b in range(len(lens)):
        while len(rows[b]) < width:
            for per_row in (rows, decode, got, served):
                per_row[b].append(per_row[b][-1])
    return (np.asarray(rows, np.int32), np.asarray(decode, bool),
            np.asarray(got, np.float32), np.asarray(served, np.int64))


def free(runner) -> None:
    """Give the runner's parameters and cache back to the device, so the
    float32 reference has the chip to itself."""
    import jax

    for tree in (runner.params, runner.kv_caches, runner.kv_scales):
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "delete"):
                leaf.delete()
    runner.params = runner.kv_caches = runner.kv_scales = None


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    g = got.reshape(-1, got.shape[-1]).astype(np.float64)
    w = want.reshape(-1, want.shape[-1]).astype(np.float64)
    return np.linalg.norm(g - w, axis=1) / np.linalg.norm(w, axis=1)


def token_margins(want: np.ndarray) -> np.ndarray:
    """By row: how far the reference's first token leads its second, in
    units of the row's logit RMS."""
    w = want.reshape(-1, want.shape[-1]).astype(np.float64)
    top2 = np.partition(w, -2, axis=1)[:, -2:]
    return (top2[:, 1] - top2[:, 0]) / np.sqrt(np.mean(w * w, axis=1))


def compare(data: dict, seed: int, runner, *, weights_seed: int,
            prompt_lens=PROMPT_LENS, decode_steps: int = DECODE_STEPS,
            pad_to: int = PAD_TO, quantile: float = 100,
            phase_quantile: float | None = None,
            token_margin: float = 0.0) -> dict:
    """The runner against the reference for configuration file ``data``;
    frees the runner's arrays on the way. ``weights_seed`` is what the
    served weights were drawn from."""
    import jax

    from chipbench import registry

    ref = registry.load("reference", data["reference"])
    vocab = data["published"]["vocab_size"]
    lens = tuple(prompt_lens)
    assert max(lens) + decode_steps <= pad_to
    tokens = sample_tokens(seed, vocab, lens, decode_steps, pad_to)
    rows, decode, got, served = runner_rows(
        runner, tokens, lens, decode_steps, seed
    )
    assert np.isfinite(got).all(), "the runner's logits are not finite"
    free(runner)
    cut = share_arguments(data, ref)
    with jax.default_device(jax.devices()[0]):
        want = np.asarray(ref.logits(
            data["published"], weights_seed, tokens, rows,
            dtype=data["dtype"], **cut,
        ))
    return verdict(
        got, want, served, decode, quantile, phase_quantile, token_margin
    )


def share_arguments(data: dict, ref) -> dict:
    """The file's ``source_values`` and ``share`` blocks as keyword
    arguments for ``ref.logits``; a file without them gets the call it
    always got. A reference that takes neither is told nothing where only
    the vocabulary is sliced (a sliced vocabulary is a smaller vocabulary,
    which ``published`` says in full), and is refused where experts or
    heads are held in part, which it would compute whole."""
    cut = {k: data[k] for k in ("source_values", "share") if k in data}
    takes = inspect.signature(ref.logits).parameters
    if all(k in takes for k in cut):
        return cut
    counts = sorted(set(data.get("reduced", [])) & set(modelcfg.COUNTS))
    if counts:
        raise ValueError(
            f"{data['name']}: {counts} are held in part, but reference/"
            f"{data['reference']}.py takes no source_values and share"
        )
    return {}


def verdict(got, want, served, decode, quantile: float = 100,
            phase_quantile: float | None = None,
            token_margin: float = 0.0) -> dict:
    """The numbers ``judge`` holds to the limits, from the runner's
    logits and served tokens and the reference's logits. A short
    sequence's last row stands in the sample as often as the longest
    sequence has rows (``runner_rows`` pads to a rectangle), so it weighs
    that much in a quantile."""
    if phase_quantile is None:
        phase_quantile = quantile
    errs = row_errors(got, want)
    decode = np.asarray(decode, bool).reshape(-1)
    agree = np.asarray(served).reshape(-1) == want.reshape(
        len(errs), -1).argmax(-1)
    judged = token_margins(want) >= token_margin
    return {
        "rows": int(errs.size),
        "quantile": quantile,
        "rel_err": float(np.percentile(errs, quantile)),
        "phase_quantile": phase_quantile,
        "rel_err_by_phase": {
            "prefill": float(np.percentile(errs[~decode], phase_quantile)),
            "decode": float(np.percentile(errs[decode], phase_quantile)),
        },
        "rel_err_quantiles": {
            f"p{q}": float(np.percentile(errs, q))
            for q in (5, 25, 50, 75, 100)
        },
        "token_rows": int(judged.sum()),
        "token_mismatches": int((judged & ~agree).sum()),
        "token_mismatches_all_rows": int((~agree).sum()),
        "largest_logit": float(np.abs(want).max()),
        "logit_width": [int(got.shape[-1]), int(want.shape[-1])],
        "largest_served_token": int(np.max(served)),
    }


def held(verdict: dict, limits: dict) -> dict[str, dict]:
    """Each number compared beside its limit, ``{"value", "limit"}`` under
    a short plain name: what ``judge`` decides by and what a run prints."""
    out = {
        f"rel_err_p{verdict['quantile']:g}":
            {"value": verdict["rel_err"], "limit": limits["limit"]},
    }
    if "phase_limit" in limits:
        for phase, value in verdict["rel_err_by_phase"].items():
            out[f"rel_err_{phase}_p{verdict['phase_quantile']:g}"] = {
                "value": value, "limit": limits["phase_limit"]}
    out["token_mismatches"] = {
        "value": verdict["token_mismatches"],
        "limit": limits.get("token_mismatch_limit", 0),
    }
    return out


def judge(verdict: dict, limits: dict) -> list[str]:
    """Why ``verdict`` is not correct under the configuration's ``check``
    block ``limits``: empty when it is. ``rel_err_*`` is a quantile of the
    rows' relative logit error against the reference; ``token_mismatches``
    counts served greedy tokens that are not the reference's argmax where
    its lead is clear."""
    return [
        f"{name} {c['value']:.6g} > {c['limit']}"
        for name, c in held(verdict, limits).items()
        if not c["value"] <= c["limit"]
    ]


#: the keys of a configuration's ``check`` block that ``compare`` takes
COMPARE_KEYS = ("prompt_lens", "decode_steps", "pad_to", "quantile",
                "phase_quantile", "token_margin")
#: and those that ``judge`` holds the result to
LIMIT_KEYS = ("limit", "phase_limit", "token_mismatch_limit")


def compare_kwargs(data: dict) -> dict:
    return {k: v for k, v in data["check"].items() if k in COMPARE_KEYS}
