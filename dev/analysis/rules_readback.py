"""readback-discipline: device->host materializations of compiled-program
results in ballista_tpu/ops/ and ballista_tpu/parallel/ must pair with
record_readback (or the runtime.readback helper) in the same function —
otherwise readback_stats' rows/bytes (the benchmark's `readback`) undercount and the
paper's O(limit)-readback claim goes unmeasured."""

from __future__ import annotations

import ast
import re
from typing import List

from dev.analysis.common import (
    Taint,
    dotted,
    final_name,
    is_device_path,
    iter_functions,
    walk_no_nested_defs,
)
from dev.analysis.core import Finding, SourceFile, register

# project naming convention for compiled-program factories/handles: a call
# to one of these produces (or IS) a compiled device program whose results
# live on-device until materialized
_PROGRAM_NAME_RE = re.compile(
    r"(^program$|_program$|^_kernel$|_step$|^_build|^_compile_predicate$"
    r"|^sorted_grouped_sum$|^grouped_aggregate$)"
)

_MATERIALIZE = {"np.asarray", "numpy.asarray", "jax.device_get"}
_RECORDERS = {"record_readback", "readback"}


def _jit_assigned_names(func: ast.AST) -> set:
    out = set()
    for node in walk_no_nested_defs(func):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if dotted(node.value.func) in ("jax.jit", "jit"):
                for t in node.targets:
                    name = final_name(t)
                    if name:
                        out.add(name)
    return out


@register("readback-discipline")
def check(sf: SourceFile) -> List[Finding]:
    if not is_device_path(sf.path):
        return []
    findings: List[Finding] = []
    for func, _cls in iter_functions(sf.tree):
        jit_names = _jit_assigned_names(func)

        def is_source(call: ast.Call, taint: Taint) -> bool:
            name = final_name(call.func)
            if name in jit_names or (name and _PROGRAM_NAME_RE.search(name)):
                return True
            return False

        taint = Taint(func, is_source)
        sites = []
        records = False
        for node in walk_no_nested_defs(func):
            if not isinstance(node, ast.Call):
                continue
            if final_name(node.func) in _RECORDERS:
                records = True
                continue
            name = dotted(node.func)
            if name in _MATERIALIZE and node.args:
                target = node.args[0]
            elif (final_name(node.func) == "block_until_ready"
                  and isinstance(node.func, ast.Attribute)):
                target = node.func.value
            else:
                continue
            if taint.expr_tainted(target):
                sites.append(node)
        if sites and not records:
            for s in sites:
                findings.append(Finding(
                    "readback-discipline", sf.path, s.lineno, s.col_offset,
                    "device result materialized without record_readback in "
                    f"'{func.name}' — route through ops.runtime.readback() or "
                    "call record_readback(rows, nbytes) in this function so "
                    "bench readback stats stay truthful",
                ))
    return findings
