"""Operations and bytes of the routed experts of two matrices (``W_2 relu(W_1
x)^2``, no gate; the scope ``moe_experts`` of a family whose ``expert_form`` is
``relu2``), beside ``kernel_counts.grouped_swiglu``, which counts the gated
expert's three and is left as it is.

Two products forward (``2 x width x expert_width`` each a pair), their data and
weight gradients backward: ``12 x width x expert_width`` a pair. Bytes: each
layer's two weight tables read forward and backward and their gradients
written once; a pair's input read, output written, and the same for gradients.
The squared ReLU is elementwise and not counted.
"""

from __future__ import annotations


def grouped_relu2(pairs: float, width: int, expert_width: int, held: int, layers: int,
                  element_bytes: int = 2) -> dict:
    """Forward and backward over ``pairs`` (token, expert) pairs in all,
    ``layers`` layers of ``held`` experts."""
    table = 2 * held * width * expert_width * element_bytes
    rows = pairs * width * element_bytes
    return {
        "flops": 12.0 * width * expert_width * pairs,
        "bytes": float(layers * 3 * table + 4 * rows),
    }
