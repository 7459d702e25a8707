"""Bytes and operations of Mamba-1's selective scan (the scope
``selective_scan``: ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t .
h_t + D x_t``), forward and backward, as ``kernel_counts.py`` counts its
kernels: what the algorithm needs, not what an implementation does.

Bytes: a scan that keeps nothing but chunk boundaries reads ``x`` and ``dt``
(``width`` each) and ``B`` and ``C`` (``state`` each) and writes ``y``
(``width``) a token forward; backward it reads the four again and ``y``'s
gradient and writes the four's gradients: ``3 width + 2 state`` elements
forward, ``5 width + 4 state`` backward, of ``element_bytes`` each (2: the
projections that feed the scan are bfloat16). ``A``, ``D``, their gradients and
the kept boundary states (``width`` x ``state`` a chunk) are a few thousandths
and left out; no state inside a chunk is counted, so the backward pass is
counted as making them again. Operations, a token, channel and state: forward
the decay's product and exponential, the input's two products, the state's
multiply-add and the output's multiply-add (8); backward the states again (6)
and about twice the forward for the gradients (16): 30 in all, none of them on
the MXU.

**The share reads low by construction, and never over 100.** At 30 x 16
= 480 operations a token and channel against 16 bytes, the scan is bound by
the vector unit and, inside a chunk, by the sequential dependency from one
position to the next. No peak is published for the vector unit of a v5e, and
the MXU's 197 TFLOP/s does not apply, so the only honest roofline is the
memory's: ``roofline_share`` takes the larger of operations over the MXU peak
and bytes over 819 GB/s, and both are far below what the vector unit allows.
"""

from __future__ import annotations


def selective_scan(batch: int, sequence: int, width: int, state: int, layers: int,
                   element_bytes: int = 2) -> dict:
    """One step's calls: ``layers`` layers over ``batch`` x ``sequence`` tokens."""
    tokens = batch * sequence * layers
    return {
        "flops": float(30 * tokens * width * state),
        "bytes": float(tokens * (8 * width + 6 * state) * element_bytes),
    }
