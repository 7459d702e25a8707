"""The share of its roofline of a kernel of the ``phi4flash`` cells: a count
over a measured time.

``{"reader": "phi4flash_roofline", "kernel": "selective_scan" |
"flash_attention_full", "time_ms": {"metric": "phi4_scan_ms"}}``. The shapes
come from the entry of that name in the configuration's ``kernels`` group
(``selective_scan``: ``layers``, ``width``, ``state``, counted by
``selective_scan_counts``; ``flash_attention_full``: ``layers``, ``heads``,
``kv_heads``, ``head_size``, ``window``, the calls over the whole causal
triangle, counted by ``kernel_counts.flash_attention`` as the window layer's
entry ``flash_attention_window`` is by ``kernel_roofline``), batch and
sequence from the mix. ``None`` where the time (a program without the scope),
the entry, one of its keys or the device's peaks are not there, said on
stderr as ``kernel_roofline`` says it. That reader's table of kernels is left
alone: a ``benchmark`` PR may give both kernels an entry there and retire this
module.
"""

from __future__ import annotations

import sys

from .. import flops, kernel_counts, selective_scan_counts
from . import kernel_roofline


def _selective_scan(k: dict, run, cell) -> dict:
    t = cell.traffic
    return selective_scan_counts.selective_scan(
        t["batch"], t["sequence"], k["width"], k["state"], k["layers"])


# kernel -> (the keys its entry of the ``kernels`` group states, its count)
KERNELS = {
    "selective_scan": (("layers", "width", "state"), _selective_scan),
    "flash_attention_full": kernel_roofline.KERNELS["flash_attention_window"],  # the same count, another entry
}


def counts(kernel: str, cell) -> dict | None:
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
    return count(shapes, None, cell)


def read(spec: dict, run, cell, values: dict) -> float | None:
    ms = values.get(spec["time_ms"]["metric"])
    if ms is None or ms <= 0:
        return None
    need = counts(spec["kernel"], cell)
    if need is None:
        return None
    try:
        kind = run.device["kind"]
        peaks = flops.peak_flops(kind), kernel_counts.peak_bytes_per_s(kind)
    except (KeyError, TypeError) as e:  # not in the tables: an error, never a default
        print(f"perfbench: {e.args[0] if e.args else 'no device'}", file=sys.stderr)
        return None
    return kernel_counts.roofline_share(need, ms / 1000.0, *peaks)
