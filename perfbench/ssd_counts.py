"""Operations and bytes of Mamba-2's chunked scan (the scope ``ssd_scan``:
``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t``, a state of
``head_size`` x ``state`` a head), forward and backward, as ``kernel_counts.py``
counts its kernels.

Operations, as the program computes them (``hypha_tpu/ops/ssd_scan.py``). A
chunk of ``L`` positions is four batched products forward: ``C B^T`` (``2 L^2
state`` a group), that matrix under its decay mask times the inputs (``2 L^2
head_size`` a head), the chunk's state and the carried state's contribution
(``2 L head_size state`` a head each). The backward pass keeps the states at
the chunk boundaries alone, so it makes ``C B^T`` again (the mask needs it) and
takes two gradient products for each of the four: twelve products where the
forward pass has four, and ``C B^T`` a thirteenth time. The recurrence over
boundaries is a multiply-add of ``head_size x state`` a head and chunk forward
and two backward. The masks' exponentials and the elementwise work are not
counted (no peak is published for the vector unit).

Bytes: what a scan that keeps only chunk boundaries must move. Forward it reads
``x`` (``heads x head_size``), ``B`` and ``C`` (``groups x state`` each) in
``element_bytes`` and the step (one float32 a head) and writes ``y`` (float32,
as the layer's equations state it); it writes a boundary state (``heads x
head_size x state`` float32) a chunk. Backward it reads the four and ``y``'s
gradient again, and the boundary states, and writes the four's gradients. No
mask, no ``C B^T`` and no state inside a chunk is counted: they live on the chip.

At 64 heads of 64, a state of 128 in 8 groups and chunk 128 the bytes are the
larger bound: 1.03 ms a layer and sequence of 8192 at 819 GB/s against 0.44 ms
of products at 197 TFLOP/s.
"""

from __future__ import annotations


def ssd_scan(batch: int, sequence: int, heads: int, head_size: int, state: int, groups: int,
             chunk: int, layers: int, element_bytes: int = 2) -> dict:
    """One step's calls: ``layers`` layers over ``batch`` x ``sequence`` tokens."""
    chunks = batch * layers * -(-sequence // chunk)
    tokens = batch * layers * sequence
    scores = groups * 2 * chunk * chunk * state  # C B^T of a chunk
    per_head = 2 * chunk * chunk * head_size + 2 * 2 * chunk * head_size * state  # the other three
    recurrence = (2 + 4) * heads * head_size * state  # a boundary: forward, and backward's two
    inputs = heads * head_size + 2 * groups * state  # x, B, C: elements a token
    forward = inputs * element_bytes + 4 * heads + 4 * heads * head_size  # + the step, + y
    backward = 2 * inputs * element_bytes + 2 * 4 * heads + 4 * heads * head_size  # read, dy, the gradients
    boundary = 2 * 4 * heads * head_size * state  # written forward, read backward
    return {
        "flops": float(chunks * (4 * scores + 3 * heads * per_head + recurrence)),
        "bytes": float(tokens * (forward + backward) + chunks * boundary),
    }
