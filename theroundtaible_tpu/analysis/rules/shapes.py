"""RT-SHAPE-VALUE — runtime state must not flow raw into static shape
arguments (the RECOMPILE_STRICT discipline, provable before a device
exists).

The repo's whole shape discipline is that compiled-program shapes are
functions of CONFIG alone: occupancy drift, acceptance drift and
adapter mixes are VALUES. The seams where that discipline is decided
are the static parameters of `build_ragged_batch` (t_budget / s_max /
score_width / copy_slots — each distinct value is one compiled ragged
program) and the static kwargs of the decode dispatch seams (max_new /
greedy). A `len(rows)`-shaped expression or a traced `.shape` read
flowing DIRECTLY into one of those is a mid-serve recompile per
occupancy value — the exact bug class ROUNDTABLE_RECOMPILE_STRICT=1
exists to catch at runtime, caught here at parse time instead.

Runtime-derived values are fine once laundered through the sanctioned
config-bounded resolvers (`pow2_bucket`, `ragged_pick_shape`,
`clamp_max_new`): those map unbounded runtime values onto the small
warmed grid, which is the discipline, not a violation of it.
"""

from __future__ import annotations

import ast

from ..astlint import Finding, ProjectIndex, Rule, call_name

# callee -> static parameter names whose value expression is audited.
# (Since ISSUE 53 every step program also takes a static `layout`
# (engine/dispatch_pack.py). It is not audited here: a layout is built
# from the shapes of the arrays these same seams sized — the row
# bucket, build_ragged_batch's outputs — so it can take no value that
# the audited parameters could not; what remains per program are the
# statics below.)
STATIC_PARAMS: dict[str, frozenset[str]] = {
    "build_ragged_batch": frozenset(
        {"t_budget", "s_max", "score_width", "copy_slots",
         "propose_width"}),
    "_decode_dispatch_paged": frozenset({"max_new"}),
    "_ragged_step": frozenset({"score_width", "propose_width"}),
}

# Bounded resolvers: an audited expression wrapped in one of these is
# the sanctioned runtime->grid mapping. Deliberately ONLY the grid
# resolvers — int()/min() are identities/clamps on runtime values, not
# grid-bounding maps, and sanctioning them would let `int(len(rows))`
# lint clean while still compiling one program per occupancy.
SANCTIONED = frozenset({"pow2_bucket", "ragged_pick_shape",
                        "clamp_max_new"})

# Attribute/name fragments that mark a value as runtime serving state.
_RUNTIME_ATTRS = frozenset({"shape", "occupancy", "free_pages",
                            "pages_held", "valid"})


def _violations(expr: ast.AST) -> list[tuple[int, str]]:
    """(line, what) for each raw runtime-state read inside `expr`,
    skipping subtrees wrapped in a sanctioned resolver."""
    out: list[tuple[int, str]] = []

    def walk(node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            if call_name(node) in SANCTIONED:
                return      # laundered through the bounded grid
            if (isinstance(node.func, ast.Name)
                    and node.func.id == "len"):
                out.append((node.lineno, "len(...)"))
                return
            if call_name(node) in _RUNTIME_ATTRS:
                out.append((node.lineno, f"{call_name(node)}()"))
                return
        if (isinstance(node, ast.Attribute)
                and node.attr in _RUNTIME_ATTRS
                and not isinstance(node.ctx, ast.Store)):
            out.append((node.lineno, f".{node.attr}"))
            return
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(expr)
    return out


class ShapeValueRule(Rule):
    id = "RT-SHAPE-VALUE"
    severity = "error"
    description = ("runtime-derived value (len/.shape/occupancy) "
                   "flowing raw into a static shape argument — one "
                   "compile per runtime value")

    def run(self, index: ProjectIndex) -> list[Finding]:
        out: list[Finding] = []
        for rel in index.files():
            for node in ast.walk(index.tree(rel)):
                if not isinstance(node, ast.Call):
                    continue
                params = STATIC_PARAMS.get(call_name(node))
                if params is None:
                    continue
                for kw in node.keywords:
                    if kw.arg not in params:
                        continue
                    for line, what in _violations(kw.value):
                        out.append(self.finding(
                            rel, line,
                            f"{what} flows raw into static argument "
                            f"{kw.arg}= of {call_name(node)}() — every "
                            "distinct runtime value compiles a fresh "
                            "program mid-serve (RECOMPILE_STRICT "
                            "violation); route it through pow2_bucket/"
                            "ragged_pick_shape or derive it from "
                            "config"))
        return out
