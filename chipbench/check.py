"""The comparison that decides ``correct``: the served runner against the
plain reference, outside the timed window.

A seeded sample of sequences at the served widths goes through the
runner the way the engine drives it: every dispatch is a list of lanes
``(new_tokens, block_ids, prefix_len, sampling)`` handed to
``ModelRunner.unified_step`` — the served executables, the runner's own
operand building, its paged cache and the Pallas path in the served
dtype. Which dispatches, and which of their rows are read, is the
family's own step, a driver found by name: the configuration's ``check``
block names ``"step": "<module>"`` (``"span"`` where it names none: chunked
prefill, then decode steps of one token a lane through the cache, under a
causal mask) and gives it ``"step_params"``, ``{}`` where it gives none.

A step driver is one module, ``chipbench/steps/<name>.py``, that provides

    drive(runner, sample, lens, decode_steps, seed, /, **step_params)
        -> {"rows": int32 [B, R], "decode": bool [B, R],
            "logits": float32 [B, R, V], "served": int64 [B, R],
            "judged": bool [B, R]}
    sample_len(n, decode_steps, **step_params) -> int

* ``sample_len`` says how many of the sample's tokens a sequence of prompt
  length ``n`` consumes; ``sample [B, pad_to]`` holds that many seeded ids
  a sequence. The driver feeds the sample's own tokens, never sampled
  ones, so both sides see the same sequence;
* ``served`` holds the greedy tokens ``runner.unified_step`` returns (the
  served program and its sampler) and ``judged`` marks the rows in which
  it handed one out: only those are counted for ``token_mismatches``;
* ``logits`` comes from the program's model function jitted over the very
  operands ``ModelRunner._unified_operands`` built for that dispatch (the
  served program hands out tokens only), at positions ``rows``;
* ``decode`` marks the rows of the steps after the prompt. A sequence has
  a prefill row or more, and decode rows where its ``sample_len`` is above
  ``n``; the arrays are rectangular, a short sequence's last row repeated
  in all five;
* it imports nothing of ``chipbench/reference/``, and gives back itself,
  before it returns, whatever device arrays it put on the runner beside
  the parameters and the cache that ``free`` gives back.

Everything after the drive is common code. A step that yields several
tokens a dispatch changes nothing of what the window holds the server to:
the stream carries one SSE chunk a token and ``usage.completion_tokens``
equals ``max_tokens`` (``harness.run``, "every request returned exactly
the tokens it asked for"), as the speculative path streams today.

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
* it returns, for each sequence ``b``, the logits at positions ``rows[b]``,
  from one full forward pass under the family's own mask.

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
    phase's rows are wrong. A phase without rows reads ``null``; a file
    that sets a ``phase_limit`` and asks for no decode rows is refused
    where its ``check`` block is read (``compare_kwargs``, which
    ``manifest.check`` calls for every configuration), not after a drive.
``token_mismatches``
    ``judged`` rows whose reference logits put the first token
    ``token_margin`` logit-RMS or more ahead of the second, and where the
    served greedy token is not the reference's argmax. At most
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


def sample_tokens(seed: int, vocab: int, counts, pad_to: int = PAD_TO):
    """Seeded ids, ``counts[b]`` of them in row ``b`` and zeros behind."""
    rng = np.random.default_rng([int(seed), 7])
    tokens = np.zeros((len(counts), pad_to), np.int32)
    for b, n in enumerate(counts):
        tokens[b, :n] = rng.integers(1, vocab, n)
    return tokens


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
            token_margin: float = 0.0, step: str = "span",
            step_params: dict | None = None) -> dict:
    """The runner against the reference for configuration file ``data``;
    frees the runner's arrays on the way. ``weights_seed`` is what the
    served weights were drawn from."""
    import jax

    from chipbench import registry

    ref = registry.load("reference", data["reference"])
    driver = registry.load("steps", step)
    step_params = step_params or {}
    vocab = data["published"]["vocab_size"]
    lens = tuple(prompt_lens)
    counts = [driver.sample_len(n, decode_steps, **step_params) for n in lens]
    if max(counts) > pad_to:
        raise ValueError(
            f"{data['name']}: a sequence of the sample takes {max(counts)} "
            f"tokens, check.pad_to is {pad_to}")
    tokens = sample_tokens(seed, vocab, counts, pad_to)
    out = driver.drive(runner, tokens, lens, decode_steps, seed, **step_params)
    assert np.isfinite(out["logits"]).all(), \
        "the runner's logits are not finite"
    free(runner)
    cut = share_arguments(data, ref)
    with jax.default_device(jax.devices()[0]):
        want = np.asarray(ref.logits(
            data["published"], weights_seed, tokens, out["rows"],
            dtype=data["dtype"], **cut,
        ))
    return verdict(
        out["logits"], want, out["served"], out["decode"], out["judged"],
        quantile, phase_quantile, token_margin,
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


def verdict(got, want, served, decode, judged, quantile: float = 100,
            phase_quantile: float | None = None,
            token_margin: float = 0.0) -> dict:
    """The numbers ``judge`` holds to the limits, from the runner's
    logits and served tokens and the reference's logits. ``judged`` marks
    the rows in which the served program handed out a token. A short
    sequence's last row stands in the sample as often as the longest
    sequence has rows (the driver pads to a rectangle), so it weighs that
    much in a quantile."""
    if phase_quantile is None:
        phase_quantile = quantile
    errs = row_errors(got, want)
    decode = np.asarray(decode, bool).reshape(-1)
    agree = np.asarray(served).reshape(-1) == want.reshape(
        len(errs), -1).argmax(-1)
    judged = np.asarray(judged, bool).reshape(-1)
    clear = judged & (token_margins(want) >= token_margin)

    def within(phase):
        if not phase.any():
            return None
        return float(np.percentile(errs[phase], phase_quantile))

    return {
        "rows": int(errs.size),
        "quantile": quantile,
        "rel_err": float(np.percentile(errs, quantile)),
        "phase_quantile": phase_quantile,
        "rel_err_by_phase": {
            "prefill": within(~decode), "decode": within(decode),
        },
        "rel_err_quantiles": {
            f"p{q}": float(np.percentile(errs, q))
            for q in (5, 25, 50, 75, 100)
        },
        "token_rows": int(clear.sum()),
        "token_mismatches": int((clear & ~agree).sum()),
        "token_mismatches_all_rows": int((judged & ~agree).sum()),
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
                "phase_quantile", "token_margin", "step", "step_params")
#: and those that ``judge`` holds the result to
LIMIT_KEYS = ("limit", "phase_limit", "token_mismatch_limit")


def compare_kwargs(data: dict) -> dict:
    """What configuration file ``data`` asks of ``compare``. A file that
    sets a ``phase_limit`` over a sample without decode rows is refused
    here, before the drive."""
    from chipbench import registry

    block = data["check"]
    asked = {k: v for k, v in block.items() if k in COMPARE_KEYS}
    if "phase_limit" in block:
        driver = registry.load("steps", asked.get("step", "span"))
        steps = asked.get("decode_steps", DECODE_STEPS)
        if all(driver.sample_len(n, steps, **asked.get("step_params", {})) <= n
               for n in asked.get("prompt_lens", PROMPT_LENS)):
            raise ValueError(
                f"{data['name']}: check.phase_limit is set, but "
                f"decode_steps {steps} leaves the sample without decode rows")
    return asked
