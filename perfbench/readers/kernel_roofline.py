"""A kernel's share of its roofline: ``kernel_counts`` over a measured time.

``{"reader": "kernel_roofline", "kernel": "grouped_swiglu" |
"flash_attention_window", "time_ms": {"metric": "moe_experts_ms"}}``. The
shapes come from the ``kernels`` group of the cell's configuration file, one
entry for each kernel a metric of its cells names (``flash_attention_window``:
``layers``, ``heads``, ``kv_heads``, ``head_size``, ``window``;
``grouped_swiglu``: ``width``, ``expert_width``, ``held``, ``layers``), batch
and sequence from the mix, and the grouped product's pairs from the worker's
``round N routing`` lines of the measured rounds. No key of any source is
read here: a new configuration brings its numbers, not a reader. ``None``
where the time, the group or the entry, the counter or the device's peaks are
not there.
"""

from __future__ import annotations

import sys

from .. import flops, kernel_counts
from . import log_field


def _flash_attention_window(k: dict, run, cell) -> dict:
    t = cell.traffic
    one = kernel_counts.flash_attention(
        t["batch"], t["sequence"], k["heads"], k["kv_heads"], k["head_size"], k["window"])
    return {name: k["layers"] * v for name, v in one.items()}


def _grouped_swiglu(k: dict, run, cell) -> dict | None:
    rows = log_field.rows({"role": "w0", "line": r"round \d+ routing: .*"}, run)
    rows = [r for r in rows if isinstance(r.get("pairs_computed"), int) and r.get("steps")]
    if not rows:
        return None
    pairs = sum(r["pairs_computed"] for r in rows) / sum(r["steps"] for r in rows)
    return kernel_counts.grouped_swiglu(pairs, k["width"], k["expert_width"], k["held"], k["layers"])


# kernel -> (the keys its entry of the ``kernels`` group states, its count)
KERNELS = {
    "flash_attention_window": (("layers", "heads", "kv_heads", "head_size", "window"), _flash_attention_window),
    "grouped_swiglu": (("width", "expert_width", "held", "layers"), _grouped_swiglu),
}


def counts(kernel: str, run, cell) -> dict | None:
    """Operations and bytes of one step's calls of ``kernel`` in this cell."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}")
    keys, count = KERNELS[kernel]
    shapes = cell.config.get("kernels", {}).get(kernel)
    missing = [kernel] if shapes is None else [k for k in keys if k not in shapes]
    if missing:  # as a device without peaks: said, and the metric left out
        where = "kernels group" if shapes is None else f"kernels[{kernel!r}]"
        print(f"perfbench: the configuration's {where} has no {missing[0]!r}", file=sys.stderr)
        return None
    return count(shapes, run, cell)


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
