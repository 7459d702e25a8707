"""The share of its roofline of a kernel of the ``nemotron_h`` cells: a count
over a measured time.

``{"reader": "nemotron_h_roofline", "kernel": "ssd_scan" | "flash_attention_full"
| "grouped_relu2", "time_ms": {"metric": "nemo_ssd_ms"}}``. The shapes come
from the entry of that name in the configuration's ``kernels`` group
(``ssd_scan``: ``layers``, ``heads``, ``head_size``, ``state``, ``groups``,
``chunk``, counted by ``ssd_counts``; ``flash_attention_full``: the calls over
the whole causal triangle, counted by ``kernel_counts.flash_attention`` as
``kernel_roofline`` counts a window layer's; ``grouped_relu2``: ``width``,
``expert_width``, ``held``, ``layers``, counted by ``relu2_counts`` over the
pairs of the worker's ``round N routing`` lines, as ``kernel_roofline`` reads
them for the gated expert), batch and sequence from the mix. ``None`` where the
time (a program without the scope), the entry, one of its keys, the routing
lines or the device's peaks are not there, said on stderr as
``kernel_roofline`` says it. That reader's table of kernels is left alone: a
``benchmark`` PR may give these kernels an entry there and retire this module.
"""

from __future__ import annotations

import sys

from .. import flops, kernel_counts, relu2_counts, ssd_counts
from . import kernel_roofline, log_field


def _ssd_scan(k: dict, run, cell) -> dict:
    t = cell.traffic
    return ssd_counts.ssd_scan(t["batch"], t["sequence"], k["heads"], k["head_size"], k["state"],
                               k["groups"], k["chunk"], k["layers"])


def _grouped_relu2(k: dict, run, cell) -> dict | None:
    rows = log_field.rows({"role": "w0", "line": r"round \d+ routing: .*"}, run)
    rows = [r for r in rows if isinstance(r.get("pairs_computed"), int) and r.get("steps")]
    if not rows:
        return None
    pairs = sum(r["pairs_computed"] for r in rows) / sum(r["steps"] for r in rows)
    return relu2_counts.grouped_relu2(pairs, k["width"], k["expert_width"], k["held"], k["layers"])


# kernel -> (the keys its entry of the ``kernels`` group states, its count)
KERNELS = {
    "ssd_scan": (("layers", "heads", "head_size", "state", "groups", "chunk"), _ssd_scan),
    "flash_attention_full": kernel_roofline.KERNELS["flash_attention_window"],  # the same count, another entry
    "grouped_relu2": (("width", "expert_width", "held", "layers"), _grouped_relu2),
}


def counts(kernel: str, run, cell) -> dict | None:
    """Operations and bytes of one step's calls of ``kernel`` in this cell."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}")
    keys, count = KERNELS[kernel]
    shapes = cell.config.get("kernels", {}).get(kernel)
    missing = [kernel] if shapes is None else [k for k in keys if k not in shapes]
    if missing:
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
