"""Rule: executor-sovereignty.

`core/plan.execute` is the ONLY code in the system allowed to orchestrate
selection and merging: match kernels -> pad mask -> select_topk ->
merge_ragged / merge_topk.  Every other entry point (index, segments --
whose SEGMENTED host loop is the paper's multiple loading -- the mesh path,
serving) must build a `QueryPlan` and delegate, so
the (count desc, id asc) ordering, the pad-never-in-topk mask, and the
ragged per-part k clamp have exactly one implementation.

This replaces the pre-PR 9 string-grep test (tests/test_plan.py) with real
call-site analysis: re-exporting a helper, naming it in a docstring, or
commenting it out no longer trips the check -- *calling* it outside the
executor family does, anywhere under src/, not just in the four legacy
modules the grep watched.
"""
from __future__ import annotations

import ast
from typing import Iterable

from tools.genielint.config import LintConfig
from tools.genielint.core import Finding, LintModule, call_name, register


@register("executor-sovereignty")
def check(module: LintModule, config: LintConfig) -> Iterable[Finding]:
    if module.relpath in config.executor_modules:
        return
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name in config.governed_calls:
            yield Finding(
                rule="executor-sovereignty",
                path=module.relpath, line=node.lineno, col=node.col_offset,
                message=(
                    f"call to {name}() outside the executor family "
                    f"(core/plan.py owns selection/merging/pad-masking; "
                    f"build a QueryPlan and delegate to plan.execute)"
                ),
            )
