"""Operations and bytes of a learned selection of keys (an indexer's scores
and attention over the keys it picks), beside ``kernel_counts``, which is
left as it is.

The counts are of the work the model asks for, whatever implements it. A
query ``t`` of a causal sequence keeps ``min(t + 1, topk)`` keys, so
:func:`picked_pairs` is what attention needs and a kernel that walks the
whole triangle under a mask reads a low share of its roofline: the headroom,
said honestly, and no share can pass 100. The indexer scores every earlier
key of every query, so :func:`index_scores` is over the causal triangle; the
choice itself (compare-and-count on the vector unit) is no matrix product and
is not counted, nor is the objective's walk, which makes the scores again.
"""

from __future__ import annotations

from .kernel_counts import band_pairs


def picked_pairs(sequence: int, topk: int) -> int:
    """(query, key) pairs of one causal sequence where query t keeps
    ``min(t + 1, topk)`` keys: a band's count, the keys scattered."""
    return band_pairs(sequence, topk)


def flash_attention_selected(batch: int, sequence: int, heads: int, kv_heads: int, head_size: int,
                             topk: int, layers: int, element_bytes: int = 2) -> dict:
    """Forward and backward of ``layers`` layers' attention over the picked
    keys, as ``kernel_counts.flash_attention`` counts a band's: 4 x head_size
    a pair and head forward, 10 x backward. Bytes: q, k, v read and o written,
    then q, k, v, o, dO read and dQ, dK, dV written, and the selection, a bit a
    causal pair, read by each of the three passes."""
    pairs = batch * heads * picked_pairs(sequence, topk)
    q = batch * sequence * heads * head_size * element_bytes
    kv = batch * sequence * kv_heads * head_size * element_bytes
    bits = 3 * batch * band_pairs(sequence, None) / 8
    return {
        "flops": layers * 14.0 * head_size * pairs,
        "bytes": float(layers * ((2 * q + 2 * kv) + (4 * q + 4 * kv) + bits)),
    }


def index_scores(batch: int, sequence: int, heads: int, head_size: int, layers: int,
                 element_bytes: int = 2) -> dict:
    """``layers`` layers' score products, forward alone (the choice passes no
    gradient): 2 x head_size a causal pair and index head. Bytes: the index
    queries, the one key head and the float32 head weights read; a bit a causal
    pair written. The scores themselves never reach memory in this count."""
    pairs = batch * band_pairs(sequence, None)
    read = batch * sequence * (heads * head_size * element_bytes + head_size * element_bytes + heads * 4)
    return {
        "flops": layers * 2.0 * head_size * heads * pairs,
        "bytes": float(layers * (read + pairs / 8)),
    }
