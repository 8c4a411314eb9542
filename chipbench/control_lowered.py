"""``chipbench.control`` for a configuration whose control is the plain
REFERENCE computed in a precision below the one its file states, put in
the program's place: the reference's own float32 pass is what the limits
are held against, so the lowered pass need not serve or decode. For a
family whose program cannot serve int8 weights (``control.CONTROLS``
builds the program's own path), and for a state the file states in
float32.

    python3 -m chipbench.control_lowered --config <name> --seeds 6 \\
        --control-seeds 6 --controls int8_weights,bf16_state

The reference names what it can lower (``reference/<family>.py``
``LOWERED``, the values of its ``logits(..., lowered=...)``). The sample,
its rows and the limits are the configuration's ``check`` block's; the
rows are the last position of each span of the step's plan
(``steps/span.py`` ``plan_steps``, which ``recurrent_span`` shares), and
the lowered pass's argmax stands for the served token. Sound runs of the
program, the lines and the last line are ``chipbench.control``'s.
"""

import sys

import numpy as np

from chipbench import check, control, registry
from chipbench.steps.span import plan_steps


def plan_rows(lens, decode_steps: int, budget: int):
    """``(rows, decode)`` as a span driver returns them: one row a span
    at its last position, the short sequences' last row repeated."""
    rows = [[] for _ in lens]
    decode = [[] for _ in lens]
    for spans in plan_steps(lens, decode_steps, budget):
        for b, prefix, n in spans:
            rows[b].append(prefix + n - 1)
            decode[b].append(prefix >= lens[b])
    width = max(len(r) for r in rows)
    pad = lambda per_row: [r + [r[-1]] * (width - len(r)) for r in per_row]
    return np.asarray(pad(rows), np.int32), np.asarray(pad(decode), bool)


def lowered_one(data: dict, budget: int, seed: int, lowered: str) -> dict:
    """What ``check.compare`` returns, with the reference's ``lowered``
    pass where the program's logits and tokens would stand."""
    import jax

    ref = registry.load("reference", data["reference"])
    asked = check.compare_kwargs(data)
    assert asked.get("step", "span") in ("span", "recurrent_span"), asked
    lens = tuple(asked.get("prompt_lens", check.PROMPT_LENS))
    steps = asked.get("decode_steps", check.DECODE_STEPS)
    tokens = check.sample_tokens(
        seed, data["published"]["vocab_size"], [n + steps for n in lens],
        asked.get("pad_to", check.PAD_TO))
    rows, decode = plan_rows(lens, steps, budget)
    weights_seed = int(seed) % (2**31 - 1)
    with jax.default_device(jax.devices()[0]):
        want, got = (
            np.asarray(ref.logits(
                data["published"], weights_seed, tokens, rows,
                dtype=data["dtype"], **check.share_arguments(data, ref), **how,
            ))
            for how in ({}, {"lowered": lowered})
        )
    quantile = asked.get("quantile", 100)
    out = check.verdict(
        got, want, got.argmax(-1), decode, np.ones(rows.shape, bool),
        quantile, asked.get("phase_quantile"), asked.get("token_margin", 0.0),
    )
    out["not_correct"] = check.judge(out, data["check"])
    return out


program_read_one = control.read_one


def read_one(data: dict, ecfg, seed: int, lowered=None, **changes) -> dict:
    if lowered is None:
        return program_read_one(data, ecfg, seed, **changes)
    return lowered_one(data, ecfg.unified_token_budget, seed, lowered)


class _ByName(dict):
    """Every control named is a value of the reference's ``lowered``."""

    def __missing__(self, how):
        return {"lowered": how}


if __name__ == "__main__":
    control.read_one = read_one
    control.CONTROLS = _ByName()
    control.main(sys.argv[1:])
