"""Modules found by name: a reader, a length distribution, a reference
architecture, a kernel's cost function or the check's step driver joins
by being a module in its directory."""

from __future__ import annotations

import importlib
import re

KINDS = ("readers", "distributions", "reference", "costs", "steps")
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def load(kind: str, name: str):
    if kind not in KINDS:
        raise KeyError(f"no registry {kind!r}; have {KINDS}")
    if not _NAME.match(name):
        raise ValueError(f"bad {kind} module name {name!r}")
    try:
        return importlib.import_module(f"chipbench.{kind}.{name}")
    except ModuleNotFoundError as exc:
        if exc.name == f"chipbench.{kind}.{name}":
            raise KeyError(
                f"chipbench/{kind}/{name}.py does not exist"
            ) from exc
        raise
