"""``chipbench.control`` with one more control, for a configuration that
keeps a recurrent state: the state table held in bfloat16, the nearest
precision below the float32 the configuration states for it. The program
has no option for that (it stores the state as stated), so the control
casts the runner's table itself, before the drive.

    python3 -m chipbench.control_state --config <name> --seeds 3 \\
        --control-seeds 2 --controls int8_weights,bf16_state

A seed is bounded: one that has not ended after ``SEED_LIMIT_S`` seconds
ends the process with a traceback of where it stood and exit code 1 (a
control that hangs the chip gave no number; ``chipbench.control`` counts
that as failed, and it sets no upper end). The lines read so far are out.
"""

import dataclasses
import faulthandler
import sys

from chipbench import check, control

#: the most one seed of one side may take (a sound seed takes 2-4 minutes
#: on the chip with every program compiled anew)
SEED_LIMIT_S = 900

control.CONTROLS["bf16_state"] = {"state_dtype": "bfloat16"}


def read_one(data: dict, ecfg, seed: int, state_dtype=None, **changes) -> dict:
    """``control.read_one``, the state table cast to ``state_dtype`` where
    one is given (the convolution tail is in the served dtype already)."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.runner import ModelRunner

    faulthandler.dump_traceback_later(SEED_LIMIT_S, exit=True)
    weights_seed = int(seed) % (2**31 - 1)
    runner = ModelRunner(
        dataclasses.replace(ecfg, seed=weights_seed, **changes),
        rng_seed=weights_seed,
    )
    if state_dtype:
        runner.rec_state = [
            (state.astype(jnp.dtype(state_dtype)), tail)
            for state, tail in runner.rec_state
        ]
    out = check.compare(
        data, seed, runner, weights_seed=weights_seed,
        **check.compare_kwargs(data),
    )
    faulthandler.cancel_dump_traceback_later()
    out["attention_path"] = runner.attention_path
    out["not_correct"] = check.judge(out, data["check"])
    return out


control.read_one = read_one

if __name__ == "__main__":
    control.main(sys.argv[1:])
