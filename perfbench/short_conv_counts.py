"""Operations and bytes of the gated short convolution's core (the scope
``short_conv``: ``y = C * conv(B * x)``, depthwise and causal, a few taps a
channel), forward and backward, as ``kernel_counts.py`` counts its kernels:
what the algorithm needs, not what an implementation does.

Per token, channel and layer. Bytes: forward reads the three projected values
(``B``, ``C``, ``x``) and writes ``y``; backward reads the output's gradient
and the three values again and writes their three gradients: 11 elements.
Nothing is counted for a stored intermediate (``B * x`` or the convolution's
output), so the backward pass is counted as making them again, and the taps
and their gradient (``taps`` x ``width`` a layer) are a ten-thousandth.
Operations: forward the two gates (2) and the taps' multiply-adds (2 x taps);
backward ``B * x`` and the convolution again (1 + 2 x taps), ``dC`` and the
gradient through the ``C`` gate (2), the transposed convolution (2 x taps),
the taps' own gradient (2 x taps) and the ``B`` gate's two (2): 7 + 8 x taps
in all. At a
few operations a byte the core is bound by bandwidth on every chip in the
tables, and the share is bytes over the peak bytes/s over the scope's time.
"""

from __future__ import annotations


def short_conv(batch: int, sequence: int, width: int, taps: int, layers: int,
               element_bytes: int = 2) -> dict:
    """One step's calls: ``layers`` layers over ``batch`` x ``sequence`` tokens."""
    elements = batch * sequence * width * layers
    return {
        "flops": float((7 + 8 * taps) * elements),
        "bytes": float(11 * elements * element_bytes),
    }
