"""DT005 — host synchronization on the engine step path.

`np.asarray(device_array)`, `.block_until_ready()`, `.item()` and
`jax.device_get` force a device→host round trip, and the host waits for
the device; inside the per-step dispatch loop that serializes
the pipeline the async-dispatch design exists to hide (the engine issues
step N+1 while N executes — a host sync parks it). Keep step results
device-resident until a batch boundary, or batch the transfer
(`gather_many` exists for exactly this).

Scope: the step-path modules only. Host syncs in offline tools, tests,
or the HTTP edge are fine.
"""

from __future__ import annotations

import ast

from tools.dynalint.astutil import call_name, enclosing_name
from tools.dynalint.core import FileContext, Finding, Rule, register

#: Modules whose code runs per engine step (dispatch loop, runner, KV
#: bookkeeping, stepcast broadcast).
STEP_PATH_MODULES = (
    "dynamo_tpu/engine/engine.py",
    "dynamo_tpu/engine/runner.py",
    "dynamo_tpu/engine/kv_cache.py",
    "dynamo_tpu/engine/scheduler.py",
    "dynamo_tpu/parallel/stepcast.py",
)

_SYNC_CALLS = {
    "numpy.asarray": "np.asarray",
    "numpy.array": "np.array",
    "jax.device_get": "jax.device_get",
    "jax.block_until_ready": "jax.block_until_ready",
}

_SYNC_METHODS = {"block_until_ready", "item", "tolist"}


@register
class HostSyncInStepPath(Rule):
    id = "DT005"
    name = "host-sync-in-step-path"
    summary = "device→host sync (asarray/.item()/block_until_ready) per step"

    def applies_to(self, path: str) -> bool:
        return any(path.endswith(m) or path == m for m in STEP_PATH_MODULES)

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        stack: list[ast.AST] = []

        def visit(node: ast.AST) -> None:
            stack.append(node)
            if isinstance(node, ast.Call):
                label = self._sync_label(ctx, node)
                if label is not None:
                    out.append(Finding(
                        ctx.path, node.lineno, node.col_offset, self.id,
                        f"host sync {label} on the step path "
                        f"({enclosing_name(stack)}) — forces a device "
                        "round trip; keep device-resident or batch it",
                    ))
            for child in ast.iter_child_nodes(node):
                visit(child)
            stack.pop()

        visit(ctx.tree)
        return out

    def _sync_label(self, ctx: FileContext, node: ast.Call) -> str | None:
        qn = ctx.qualname(node.func)
        if qn in _SYNC_CALLS:
            return f"`{_SYNC_CALLS[qn]}(...)`"
        name = call_name(node)
        if (
            name in _SYNC_METHODS
            and isinstance(node.func, ast.Attribute)
            and not node.args
            and not node.keywords
        ):
            return f"`.{name}()`"
        return None
