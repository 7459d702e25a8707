"""Arithmetic over other readings.

``{"reader": "derived", "inputs": {"inner": {<reader spec>}, "step":
{"metric": "step_ms"}}, "expr": "(inner - steps * step / 1000) / steps"}``.
Besides its inputs, an expression sees the cell's constants: ``steps``,
``batch``, ``sequence``, ``batch_tokens``, ``flops_per_token``,
``peak_flops`` (an unknown device kind leaves the metric out and says so on
stderr), ``span_s`` and ``in_step_s`` of the measured rounds. Only
arithmetic is allowed in it.
"""

from __future__ import annotations

import ast
import operator
import sys

from .. import flops

_OPS = {
    ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
    ast.Div: operator.truediv, ast.USub: operator.neg,
}


def evaluate(expr: str, names: dict) -> float:
    def walk(node):
        if isinstance(node, ast.Expression):
            return walk(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            return names[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](walk(node.left), walk(node.right))
        if isinstance(node, ast.UnaryOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](walk(node.operand))
        raise ValueError(f"not arithmetic: {ast.dump(node)}")

    return walk(ast.parse(expr, mode="eval"))


def constants(run, cell) -> dict:
    t = cell.traffic
    out = {
        "steps": t["inner_steps"], "batch": t["batch"], "sequence": t["sequence"],
        "batch_tokens": t["batch"] * t["sequence"],
        "flops_per_token": flops.flops_per_token(cell.config["flops"], t["sequence"]),
        "span_s": sum(r["wall"] for r in run.measured),
        "in_step_s": sum(r["steps"] * r["median_step_s"] for r in run.measured),
    }
    return out


def read(spec: dict, run, cell, values: dict) -> float | None:
    from . import read_spec

    names = constants(run, cell)
    for name, source in spec.get("inputs", {}).items():
        v = values.get(source["metric"]) if "metric" in source else read_spec(source, run, cell, values)
        if v is None:
            return None
        names[name] = v
    if "peak_flops" in spec["expr"]:
        try:
            names["peak_flops"] = flops.peak_flops(run.device["kind"]) * run.device["count"]
        except (KeyError, TypeError) as e:  # not in the table: an error, never a default
            print(f"perfbench: {e.args[0] if e.args else 'no device'}", file=sys.stderr)
            return None
    try:
        return float(evaluate(spec["expr"], names))
    except (KeyError, ZeroDivisionError):
        return None
