"""The short convolution core's share of its roofline: ``short_conv_counts``
over a measured time.

``{"reader": "short_conv_roofline", "time_ms": {"metric":
"lfm2_short_conv_ms"}}``. The shapes come from the ``short_conv`` entry of the
configuration's ``kernels`` group (``layers``, ``width``, ``taps``), batch and
sequence from the mix. ``None`` where the time (a program without the scope),
the entry, one of its keys or the device's peaks are not there, said on stderr
as ``kernel_roofline`` says it. That reader's table of kernels is left alone:
a ``benchmark`` PR may give the kernel an entry there and retire this module.
"""

from __future__ import annotations

import sys

from .. import flops, kernel_counts, short_conv_counts

KERNEL = "short_conv"
KEYS = ("layers", "width", "taps")


def counts(cell) -> dict | None:
    """Operations and bytes of one step's calls of the core in this cell."""
    shapes = cell.config.get("kernels", {}).get(KERNEL)
    missing = [KERNEL] if shapes is None else [k for k in KEYS if k not in shapes]
    if missing:
        where = "kernels group" if shapes is None else f"kernels[{KERNEL!r}]"
        print(f"perfbench: the configuration's {where} has no {missing[0]!r}", file=sys.stderr)
        return None
    t = cell.traffic
    return short_conv_counts.short_conv(
        t["batch"], t["sequence"], shapes["width"], shapes["taps"], shapes["layers"])


def read(spec: dict, run, cell, values: dict) -> float | None:
    ms = values.get(spec["time_ms"]["metric"])
    if ms is None or ms <= 0:
        return None
    need = counts(cell)
    if need is None:
        return None
    try:
        kind = run.device["kind"]
        peaks = flops.peak_flops(kind), kernel_counts.peak_bytes_per_s(kind)
    except (KeyError, TypeError) as e:  # not in the tables: an error, never a default
        print(f"perfbench: {e.args[0] if e.args else 'no device'}", file=sys.stderr)
        return None
    return kernel_counts.roofline_share(need, ms / 1000.0, *peaks)
