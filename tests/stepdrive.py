"""Drive a runner's one step entry, ``unified_step``, from a test: one
span's sampled token, and a greedy prefill-then-decode loop; beside it the
same loop through the no-cache oracle, ``llama.reference_forward``."""

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama

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


def reference_greedy(cfg, params, prompt, n: int, *, length: int) -> list[int]:
    """``n`` greedy tokens after ``prompt`` by full recompute through the
    no-cache oracle: the correctness reference of the paged engine. The
    tokens are right-padded to ONE ``length`` (the test's ``max_model_len``)
    and the logits read at the last real row: attention is causal, so no
    row sees the padding, and one shape compiles each operation once where
    a growing input compiled every operation again at every step."""
    tokens = list(prompt)
    assert len(tokens) + n <= length + 1, (len(tokens), n, length)
    padded = np.zeros(length, np.int32)
    for _ in range(n):
        padded[: len(tokens)] = tokens
        logits = llama.reference_forward(cfg, params, jnp.asarray(padded))
        tokens.append(int(jnp.argmax(logits[len(tokens) - 1])))
    return tokens[len(prompt):]


def slot_rows(layer) -> list:
    """A paged layer's entries as ``[num_slots, heads, D]`` arrays (keys,
    then values), whatever form its pages take on the device
    (ops/attention.py ``page_form``): a joined layer's one array taken
    apart, any other layer's arrays as they are."""
    from dynamo_tpu.ops.attention import page_form

    if page_form(*layer) != "joined":
        return [np.asarray(a) for a in layer]
    (pages,) = layer
    pages = np.asarray(pages)
    return [pages[:, j].reshape(-1, *pages.shape[3:]) for j in range(2)]

