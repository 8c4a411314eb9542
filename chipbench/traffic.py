"""The one general traffic generator: a pure function of a traffic file and
a seed. Needs numpy only, so the load generator's child can import it.

A traffic file (``chipbench/traffic/<name>.json``) gives::

    {"loop": "closed", "clients": 64, "ramp_s": 12, "block": 64,
     "prompt_tokens": {"distribution": "log_uniform", "low": 64, "high": 1024},
     "output_tokens": {"distribution": "log_uniform", "low": 128, "high": 512},
     "think_time_s":  {"distribution": "constant", "value": 0},
     "why": "..."}

Requests are stratified: the list is made of blocks of ``block`` requests,
and every block holds the same ``block`` requests — the distributions'
quantiles at ``block`` evenly spaced points, prompt, answer and think time
paired at random ONCE, by ``PAIRING_SEED`` and not by the run's seed. The
run's seed only orders each block (and draws the text). Every seed
therefore sends the same set of requests, block by block, in another
order, and any stretch of the list is a fair sample. (With the pairing
drawn from the run's seed too, the think cell's median first-token time
read 7 % apart between seeds and 1.5 % apart between two runs of one seed:
my chip runs, PR 25.)
"""

from __future__ import annotations

import json
import os

import numpy as np

from chipbench import registry

ROOT = os.path.dirname(os.path.abspath(__file__))
PAIRING_SEED = 0
LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)


def load(name: str) -> dict:
    with open(os.path.join(ROOT, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    if spec.get("loop") != "closed":
        raise ValueError(
            f"traffic {name!r}: only closed loops are generated so far"
        )
    return spec


def _stratum(spec: dict, n: int) -> list[float]:
    """The distribution's quantiles at the ``n`` midpoints of [0, 1]."""
    params = {k: v for k, v in spec.items() if k != "distribution"}
    dist = registry.load("distributions", spec["distribution"])
    return [dist.quantile((i + 0.5) / n, **params) for i in range(n)]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Seeds reach a little over 2**31: SeedSequence takes any size."""
    return np.random.default_rng([int(seed), stream])


def requests(spec: dict, seed: int, count: int) -> list[dict]:
    """The first ``count`` requests: ``prompt_tokens`` (bytes of user
    text: the toy tokenizer is byte-level), ``output_tokens`` (forced by
    ``max_tokens`` with ``ignore_eos``) and ``think_s`` (the pause a
    client makes BEFORE sending it)."""
    block = int(spec.get("block", spec["clients"]))
    prompts = [int(round(x)) for x in _stratum(spec["prompt_tokens"], block)]
    outputs = [int(round(x)) for x in _stratum(spec["output_tokens"], block)]
    thinks = _stratum(spec["think_time_s"], block)
    pairing = rng_for(PAIRING_SEED, 2)
    with_output = pairing.permutation(block)
    with_think = pairing.permutation(block)
    one_block = [
        {
            "prompt_tokens": prompts[i],
            "output_tokens": outputs[with_output[i]],
            "think_s": float(thinks[with_think[i]]),
        }
        for i in range(block)
    ]
    rng = rng_for(seed, 1)
    out: list[dict] = []
    while len(out) < count:
        out.extend(dict(one_block[i]) for i in rng.permutation(block))
    return out[:count]


def prompt_text(seed: int, index: int, nbytes: int) -> str:
    """Seeded lowercase text with no shared prefix between requests."""
    rng = rng_for(seed, 1000 + index)
    return bytes(rng.choice(LETTERS, nbytes)).decode()
