"""DT013 — atomic durable writes: no raw write paths outside atomic_io.

The crash-consistency law (docs/architecture/integrity.md
"Crash-consistent persistence"): durable state is written tmp +
`os.replace` + fsync — the `utils/atomic_io.py` discipline the G3
sidecar and planner state ride.
A raw `open(path, "w")` / `json.dump` / `Path.write_text` torn by a
crash leaves half-written state that a restart then trusts; PR 18's
torn-sidecar drill exists precisely because this bug class was real.

This rule flags every raw durable-write shape in `dynamo_tpu/`,
`benchmarks/`, and `bench.py` outside `utils/atomic_io.py` itself:

- `open(..., "w"/"wb"/"x"...)` and `Path.open("w"...)` — write-mode
  opens (append and read/update modes pass: appends are journal-shaped
  and `r+b` is the mmap arena's in-place row write, whose consistency
  the sidecar protocol owns);
- `json.dump(...)` — serializing straight into a stream someone opened;
- `os.replace(...)` — hand-rolling the atomic rename outside the one
  blessed implementation (fsync of file AND parent dir is the part
  hand-rolls forget);
- `Path.write_text` / `Path.write_bytes` — one-shot raw writes.

Not every hit is durable state (a build artifact, a bench report
regenerated per run); those take a line suppression whose reason says
why a torn write is acceptable there. The default is: route it through
`atomic_write_text` / `atomic_write_bytes`.
"""

from __future__ import annotations

import ast

from tools.dynalint.core import FileContext, Finding, Rule, register

BLESSED = "dynamo_tpu/utils/atomic_io.py"
SCOPES = ("dynamo_tpu/", "benchmarks/")

_WRITE_ATTRS = ("write_text", "write_bytes")


def _mode_of(call: ast.Call) -> str | None:
    """The mode argument of an open()/Path.open() call, when literal."""
    mode = None
    args = call.args
    if isinstance(call.func, ast.Attribute):  # p.open(mode=...)
        if args and isinstance(args[0], ast.Constant):
            mode = args[0].value
    elif len(args) > 1 and isinstance(args[1], ast.Constant):
        mode = args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    return mode if isinstance(mode, str) else None


@register
class AtomicDurability(Rule):
    id = "DT013"
    name = "atomic-durability"
    summary = "raw durable write outside utils/atomic_io.py"

    def applies_to(self, path: str) -> bool:
        if not path.endswith(".py") or path == BLESSED:
            return False
        return path == "bench.py" or any(
            path.startswith(s) for s in SCOPES
        )

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            qual = ctx.qualname(node.func)
            msg = None
            if qual == "open" or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "open"
            ):
                mode = _mode_of(node)
                if mode is not None and ("w" in mode or "x" in mode):
                    msg = (
                        f"raw write-mode open({mode!r}) — a crash tears "
                        "the file; durable state goes through "
                        "utils/atomic_io.py (suppress with the reason "
                        "this state may legally tear)"
                    )
            elif qual == "json.dump":
                msg = (
                    "json.dump into a raw stream — serialize with "
                    "json.dumps and write via atomic_write_text so a "
                    "crash mid-serialize cannot leave torn JSON"
                )
            elif qual == "os.replace":
                msg = (
                    "hand-rolled os.replace — the blessed tmp+replace+"
                    "fsync lives in utils/atomic_io.py (hand-rolls skip "
                    "the file/parent-dir fsync that makes it durable)"
                )
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _WRITE_ATTRS
            ):
                msg = (
                    f"raw .{node.func.attr}() — one-shot write with no "
                    "atomicity; durable state goes through "
                    "utils/atomic_io.py"
                )
            if msg is not None:
                out.append(Finding(
                    ctx.path, node.lineno, node.col_offset, self.id, msg
                ))
        return out
