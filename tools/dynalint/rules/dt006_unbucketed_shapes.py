"""DT006 — jit-visible shape built from raw `len()` instead of a bucket.

Every distinct array shape that reaches a jitted step function compiles
a fresh XLA program — mid-traffic, at seconds per shape for a whole-
model step. The compile-
lifecycle design therefore requires every data-dependent extent to snap
through the bucket helpers (`_bucket`, `token_budget`) so runtime shapes
land on the warmed grid. A shape-constructing call whose extent is a raw
`len(...)` (or arithmetic over one) re-opens the unbounded-shape-set
hazard: `np.zeros((len(tokens), D))` compiles once per prompt length.

Scope: the step-path modules, where constructed arrays feed the jitted
steps. `len()` is fine once it has passed through a bucket helper —
`np.zeros(_bucket(len(tokens)))` does not fire.
"""

from __future__ import annotations

import ast

from tools.dynalint.astutil import call_name, enclosing_name
from tools.dynalint.core import FileContext, Finding, Rule, register
from tools.dynalint.rules.dt005_host_sync import STEP_PATH_MODULES

#: Array/shape constructors whose integer extents become XLA shapes.
_SHAPE_FNS = {
    "zeros", "ones", "full", "empty", "arange",
    "broadcast_to", "reshape", "pad", "tile", "repeat",
}

#: Passing through any of these snaps the extent onto the warmed grid.
#: `token_budget` is the serving path's snap (engine/compile_cache.py):
#: flat-batch extents land on the budget ladder, not a raw token count.
#: (`lane_bucket` is gone with the phase-alternating lane ladder.)
BUCKET_HELPERS = {
    "_bucket", "bucket", "bucket_for", "token_budget",
}


def _raw_len_in(node: ast.AST) -> ast.Call | None:
    """First `len(...)` call under `node` NOT nested inside a bucket-helper
    call (which would snap it to the warmed shape grid)."""
    if isinstance(node, ast.Call):
        name = call_name(node)
        if name in BUCKET_HELPERS:
            return None  # snapped — don't descend
        if isinstance(node.func, ast.Name) and node.func.id == "len":
            return node
    for child in ast.iter_child_nodes(node):
        hit = _raw_len_in(child)
        if hit is not None:
            return hit
    return None


@register
class UnbucketedShape(Rule):
    id = "DT006"
    name = "unbucketed-shape"
    summary = "shape constructor fed raw len() — per-length XLA recompile"

    def applies_to(self, path: str) -> bool:
        return any(path.endswith(m) or path == m for m in STEP_PATH_MODULES)

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        stack: list[ast.AST] = []

        def visit(node: ast.AST) -> None:
            stack.append(node)
            if isinstance(node, ast.Call) and call_name(node) in _SHAPE_FNS:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    hit = _raw_len_in(arg)
                    if hit is not None:
                        out.append(Finding(
                            ctx.path, node.lineno, node.col_offset, self.id,
                            f"`{call_name(node)}` extent uses raw `len()` in "
                            f"{enclosing_name(stack)} — unbucketed shapes "
                            "compile one XLA program per length; snap "
                            "through _bucket()/token_budget()",
                        ))
                        break
            for child in ast.iter_child_nodes(node):
                visit(child)
            stack.pop()

        visit(ctx.tree)
        return out
