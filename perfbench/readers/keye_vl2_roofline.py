"""The share of its roofline of a kernel of the ``keye_vl2`` cells: a count
over a measured time.

``{"reader": "keye_vl2_roofline", "kernel": "flash_attention_selected" |
"index_scores" | "grouped_swiglu", "time_ms": {"metric": "keye_flash_sel_ms"}}``.
The shapes come from the entry of that name in the configuration's ``kernels``
group (``flash_attention_selected``: ``layers``, ``heads``, ``kv_heads``,
``head_size``, ``topk``; ``index_scores``: ``layers``, ``heads``,
``head_size``, both counted by ``dsa_counts``; ``grouped_swiglu``: as
``kernel_roofline`` counts it, over the pairs of the worker's ``round N
routing`` lines), batch and sequence from the mix. ``None`` where the time (a
program without the scope), the entry, one of its keys, the routing lines or
the device's peaks are not there, said on stderr as ``kernel_roofline`` says
it. That reader's table of kernels is left alone: a ``benchmark`` PR may give
these kernels an entry there and retire this module.
"""

from __future__ import annotations

import sys

from .. import dsa_counts, flops, kernel_counts
from . import kernel_roofline


def _flash_attention_selected(k: dict, run, cell) -> dict:
    t = cell.traffic
    return dsa_counts.flash_attention_selected(
        t["batch"], t["sequence"], k["heads"], k["kv_heads"], k["head_size"], k["topk"], k["layers"])


def _index_scores(k: dict, run, cell) -> dict:
    t = cell.traffic
    return dsa_counts.index_scores(t["batch"], t["sequence"], k["heads"], k["head_size"], k["layers"])


# kernel -> (the keys its entry of the ``kernels`` group states, its count)
KERNELS = {
    "flash_attention_selected": (("layers", "heads", "kv_heads", "head_size", "topk"), _flash_attention_selected),
    "index_scores": (("layers", "heads", "head_size"), _index_scores),
    "grouped_swiglu": kernel_roofline.KERNELS["grouped_swiglu"],  # the same count, this cell's entry
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
