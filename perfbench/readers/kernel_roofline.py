"""A kernel's share of its roofline: ``kernel_counts`` over a measured time.

``{"reader": "kernel_roofline", "kernel": "grouped_swiglu" |
"flash_attention_window", "time_ms": {"metric": "moe_experts_ms"}}``. The
shapes come from the cell's configuration file (the source's keys) and its
mix; the grouped product's pairs from the worker's ``round N routing`` lines
of the measured rounds. ``None`` where the time, the counter or the device's
peaks are not there.
"""

from __future__ import annotations

import sys

from .. import flops, kernel_counts
from . import log_field


def counts(kernel: str, run, cell) -> dict | None:
    c, t = cell.config, cell.traffic
    if kernel == "flash_attention_window":
        run_layers = c.get("layers_run", range(c["num_hidden_layers"]))
        layers = sum(c["layer_types"][i] == "sliding_attention" for i in run_layers)
        one = kernel_counts.flash_attention(
            t["batch"], t["sequence"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["sliding_window"])
        return {k: layers * v for k, v in one.items()}
    if kernel == "grouped_swiglu":
        rows = log_field.rows({"role": "w0", "line": r"round \d+ routing: .*"}, run)
        rows = [r for r in rows if isinstance(r.get("pairs_computed"), int) and r.get("steps")]
        if not rows:
            return None
        pairs = sum(r["pairs_computed"] for r in rows) / sum(r["steps"] for r in rows)
        return kernel_counts.grouped_swiglu(
            pairs, c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
            c["num_hidden_layers"] - c["num_dense_layers"])
    raise ValueError(f"unknown kernel {kernel!r}")


def read(spec: dict, run, cell, values: dict) -> float | None:
    ms = values.get(spec["time_ms"]["metric"])
    if ms is None or ms <= 0:
        return None
    need = counts(spec["kernel"], run, cell)
    if need is None:
        return None
    try:
        kind = run.device["kind"]
        peaks = flops.peak_flops(kind), kernel_counts.peak_bytes_per_s(kind)
    except (KeyError, TypeError) as e:  # not in the tables: an error, never a default
        print(f"perfbench: {e.args[0] if e.args else 'no device'}", file=sys.stderr)
        return None
    return kernel_counts.roofline_share(need, ms / 1000.0, *peaks)
