"""Drive a runner's one step entry, ``unified_step``, from a test: one
span's sampled token, and a greedy prefill-then-decode loop."""

import numpy as np

GREEDY = (0.0, 0, 1.0)


def step_token(runner, tokens, blocks, prefix=0, mm=None) -> int:
    """The greedy token after one span of ``tokens`` at ``prefix``.
    ``mm``: the span's multimodal segments ((offset, [n, hidden]) pairs)."""
    out = runner.unified_step(
        [(list(tokens), list(blocks), prefix, GREEDY)],
        mm=None if mm is None else [mm],
    )
    return int(np.asarray(out.last)[0])


def greedy_tokens(runner, prompt, blocks, steps: int) -> list[int]:
    """The first token after ``prompt`` and ``steps`` greedy decode
    tokens, one dispatch each."""
    toks = [step_token(runner, prompt, blocks)]
    for i in range(steps):
        toks.append(step_token(runner, [toks[-1]], blocks, len(prompt) + i))
    return toks
