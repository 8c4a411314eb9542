"""Read, in one process, what the limit of ``correct`` is set from.

    python3 -m chipbench.control --config <name> --seeds 12 --control-seeds 3

For a configuration it builds the runner the cell serves with (the same
``EngineConfig`` the CLI makes from the configuration's ``serve_args``),
once per seed with weights drawn from that seed, and reads the numbers
``check.compare`` compares (the configuration's quantile of the rows'
relative logit error against the float32 reference, over all rows and by
phase, and the served tokens off the reference's argmax): first for sound
runs of the program, then for the control — the program's own path in the
nearest precision below the configuration's bfloat16: int8 weights
(``quant="int8"``) and int8 KV (``kv_quant="int8"``). It needs no timed
window and no server. The last line gives, for each number, the sound
runs' largest and each control's smallest, and whether every control run
came out not correct under the limits in the file; a control that crashes
or gives no number has failed and sets no upper end.

The benchmark's own runs never run this; ``tests/chipbench`` keeps it as a
test at a size a test run can hold (``--allow-cpu`` on the tiny
configuration, where the served dtype is float32 and the controls are the
same two paths).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from chipbench import check, manifest, modelcfg

CONTROLS = {
    "int8_weights": {"quant": "int8"},
    "int8_kv": {"kv_quant": "int8"},
}


def engine_config(data: dict):
    from dynamo_tpu import cli

    args = cli.build_parser().parse_args([
        "run", "--in", "http", "--out", "tpu",
        "--model-path", f"preset:{data['name']}", *data["serve_args"],
    ])
    _, ecfg = cli._tpu_local_and_cfg(args)
    return ecfg


def read_one(data: dict, ecfg, seed: int, **changes) -> dict:
    from dynamo_tpu.engine.runner import ModelRunner

    weights_seed = int(seed) % (2**31 - 1)
    runner = ModelRunner(
        dataclasses.replace(ecfg, seed=weights_seed, **changes),
        rng_seed=weights_seed,
    )
    out = check.compare(
        data, seed, runner, weights_seed=weights_seed,
        **check.compare_kwargs(data),
    )
    out["attention_path"] = runner.attention_path
    out["not_correct"] = check.judge(out, data["check"])
    return out


NUMBERS = ("rel_err", "prefill", "decode", "token_mismatches")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.control")
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--allow-cpu", action="store_true")
    opts = ap.parse_args(argv)

    from chipbench.harness import require_devices

    data = manifest.config(opts.config)
    device = require_devices(data["chips"], opts.allow_cpu)
    modelcfg.register(data)
    ecfg = engine_config(data)
    sides = [("program", {}, opts.seeds)] + [
        (name, CONTROLS[name], opts.control_seeds)
        for name in filter(None, opts.controls.split(","))
    ]
    read: dict[str, list[dict]] = {}
    for side, changes, count in sides:
        read[side] = []
        for i in range(count):
            seed = opts.first_seed + 7919 * i
            try:
                out = read_one(data, ecfg, seed, **changes)
            except Exception as exc:  # noqa: BLE001 — a crash is a result
                if side == "program":
                    raise
                print(json.dumps({
                    "side": side, "seed": seed,
                    "crashed": f"{type(exc).__name__}: {exc}"[:300],
                }), flush=True)
                continue
            read[side].append({**out, **out["rel_err_by_phase"]})
            print(json.dumps({"side": side, "seed": seed, **out}), flush=True)
    sound = read.pop("program")
    print(json.dumps({
        "config": opts.config, "device": device,
        "sound_max": {k: max(r[k] for r in sound) for k in NUMBERS},
        "sound_not_correct": sum(bool(r["not_correct"]) for r in sound),
        "control_min": {
            side: {k: min(r[k] for r in runs) for k in NUMBERS}
            for side, runs in read.items() if runs
        },
        "control_correct": {
            side: sum(not r["not_correct"] for r in runs)
            for side, runs in read.items()
        },
        "limits_in_file": {
            k: v for k, v in data["check"].items()
            if k in check.COMPARE_KEYS + check.LIMIT_KEYS
        },
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
