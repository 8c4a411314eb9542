"""The benchmark's data files, found by name, and their consistency.

``BENCHMARK.json`` at the root of the checkout is the view that the driver
reads; the files under ``chipbench/`` hold the same facts and what the
harness needs beside them, and ``check()`` holds the two together. A cell,
a metric, a traffic mix, a configuration — of a family the benchmark has
never seen, cut to one chip's share of a deployment — joins by new files
and new entries, never by an edit of a file that is there:

``configs/<name>.json``
    the ``preset`` of the program, the ``reference`` module, ``dtype``,
    ``published`` (any key of the source's ``config.json``), ``reduced``
    and, for a cut other than depth, ``source_values`` and ``share``; the
    optional ``fields`` block (published key -> ``ModelConfig`` field) and
    ``layer_period``; ``assumed``, ``deployment``, ``chips``,
    ``serve_args`` and the ``check`` block with the limits of ``correct``.
    ``modelcfg`` says how it resolves and what a cut is held to.
    The ``check`` block may name the family's step, ``"step":
    "<module>"`` with its ``"step_params"`` (``"span"``, one token a lane
    under a causal mask, where it names none).
``reference/<family>.py``
    the plain reference of a family: one ``logits`` function
    (``check``'s docstring has its signature and what it must do).
``steps/<name>.py``
    how the check drives a family's own step through the served runner:
    ``drive`` and ``sample_len`` (``check``'s docstring has the contract).
    ``steps/span.py`` is chunked prefill, then decode steps of one token a
    lane, under a causal mask; a family whose step feeds or yields a block
    of tokens brings its own. Whatever the step yields a dispatch, the
    window holds the server to one SSE chunk a token and
    ``usage.completion_tokens`` equal to ``max_tokens``.
``traffic/<name>.json`` (+ ``distributions/<name>.py``)
    a traffic mix's parameters, which ``traffic.py`` generates from.
``workloads/<config>.<traffic>.json``
    a cell: its configuration, traffic, chips, ``why`` and the metrics it
    reports.
``metrics/<name>.json`` (+ ``readers/<name>.py``)
    a metric's unit, layer, ``moves``, and its reader with its ``params``.
``costs/<kernel>.py``
    a kernel's operations and bytes, ``cost(lanes, *, model, engine)``,
    which a ``<kernel>_roofline`` metric names under ``params.cost``.
"""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(kind: str, name: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(ROOT, kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise KeyError(f"chipbench/{kind}/{name}.json does not exist") from None


def workload(name: str) -> dict:
    return _load("workloads", name)


def metric(name: str) -> dict:
    return _load("metrics", name)


def config(name: str) -> dict:
    return _load("configs", name)


def benchmark_json() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def check(bench: dict | None = None) -> list[str]:
    """Every inconsistency between ``BENCHMARK.json`` and the data files,
    as one line each (an empty list is a sound manifest)."""
    from chipbench import check as check_, registry, traffic

    bench = bench if bench is not None else benchmark_json()
    bad: list[str] = []
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}

    for c in bench["configs"]:
        if c["file"] != f"chipbench/configs/{c['name']}.json":
            bad.append(f"config {c['name']}: file is {c['file']}")
            continue
        data = config(c["name"])
        if data.get("source") != c["source"]:
            bad.append(f"config {c['name']}: source differs from its file")
        if sorted(data.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
        registry.load("reference", data["reference"])
        try:
            check_.compare_kwargs(data)
        except ValueError as e:
            bad.append(str(e))

    reported: dict[str, list[str]] = {}
    for name, w in cells.items():
        data = workload(name)
        for key in ("config", "traffic", "chips", "why"):
            if data.get(key) != w[key]:
                bad.append(f"cell {name}: {key} differs from its file")
        if name != f"{w['config']}.{w['traffic']}":
            bad.append(f"cell {name}: not named <config>.<traffic>")
        if w["config"] not in configs:
            bad.append(f"cell {name}: config {w['config']} not declared")
        traffic.load(w["traffic"])
        if "setup_s" not in data["end_to_end"]:
            bad.append(f"cell {name}: does not report setup_s")
        if len(data["end_to_end"]) < 2 or not data["per_layer"]:
            bad.append(f"cell {name}: too few metrics")
        for m in data["end_to_end"]:
            if m not in e2e:
                bad.append(f"cell {name}: end-to-end {m} not declared")
            reported.setdefault(m, []).append(name)
        for m in data["per_layer"]:
            if m not in per_layer:
                bad.append(f"cell {name}: per-layer {m} not declared")
                continue
            reported.setdefault(m, []).append(name)
            moves = per_layer[m]["moves"]
            if moves not in data["end_to_end"]:
                bad.append(
                    f"cell {name}: {m} moves {moves}, which the cell "
                    "does not report"
                )

    for name, m in {**e2e, **per_layer}.items():
        data = metric(name)
        keys = ("unit", "better", "source") + (
            ("layer", "moves") if name in per_layer else ()
        )
        for key in keys:
            if data.get(key) != m[key]:
                bad.append(f"metric {name}: {key} differs from its file")
        if not NAME.match(name) or not UNIT.match(m["unit"]):
            bad.append(f"metric {name}: name or unit outside the alphabet")
        if m["source"] not in SOURCES:
            bad.append(f"metric {name}: source {m['source']}")
        if name in e2e and m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {name}: an end-to-end source it cannot take")
        if name in per_layer and m["moves"] not in e2e:
            bad.append(f"metric {name}: moves {m['moves']}, not end to end")
        registry.load("readers", data["reader"])
        if "cost" in data.get("params", {}):
            registry.load("costs", data["params"]["cost"])
        cells_of = sorted(reported.get(name, []))
        declared = sorted(m.get("workloads", cells))
        if cells_of != declared:
            bad.append(
                f"metric {name}: BENCHMARK.json lists {declared}, the "
                f"cells' files report it in {cells_of}"
            )
    return bad
