"""The main path's kernels compile for the chip — without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``jax.experimental.topologies``). These
AOT compiles raise what Mosaic would raise on a v5e at the widths the system
runs: tiling, VMEM and layout refusals that interpret mode cannot show. A
compile that passes is not a chip run: nothing executes here.

Skipped where the topology cannot be described (no libtpu).
"""

from __future__ import annotations

import hashlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from hypha_tpu.ops.flash_attention import flash_attention
from hypha_tpu.ops.paged_attention import PagedKV, paged_attention


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip's sharding; persistent compile cache off (an
    entry written for an unattached chip cannot be read back and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# GPT-2-small's step (B16 S1024 H12 D64), Mistral-7B's long-context GQA
# (B2 S4096 H32/Hkv8 D128), Trinity-Mini's two kinds of layer at S 8192
# (H32/Hkv4 D128): the window of 2048 that cuts, and the full layer; and
# LFM2-24B-A2B's full layer (B2 S8192 H32/Hkv8 D64): head size 64 with four
# query heads to a key head, half the 128 lanes a tile; and
# Phi-4-mini-flash-reasoning's differential attention as the program calls the
# kernel (B1 S8192 H40/Hkv20 D64, two query heads to a key head): the window
# of 512, which one forward tile of keys holds whole, and the full triangle;
# and the Nemotron-H causal tower's attention (B1 S8192 H32/Hkv2 D128): sixteen
# query heads to a key head. A seventh number is the value's width where it is
# not the keys': since PR 48 Phi-4's layers make one call each with the pair's
# value of 128 beside keys of 64 (the two entries at 64 are what it called until
# then, and LFM2's head size still).
FLASH_SHAPES = {
    "gpt2": (16, 1024, 12, 12, 64, None), "mistral": (2, 4096, 32, 8, 128, None),
    "trinity_window": (1, 8192, 32, 4, 128, 2048), "trinity_full": (1, 8192, 32, 4, 128, None),
    "lfm2_full": (2, 8192, 32, 8, 64, None),
    "phi4_window": (1, 8192, 40, 20, 64, 512), "phi4_full": (1, 8192, 40, 20, 64, None),
    "nemotron_full": (1, 8192, 32, 2, 128, None),
    "phi4_window_v128": (1, 8192, 40, 20, 64, 512, 128), "phi4_full_v128": (1, 8192, 40, 20, 64, None, 128),
}


def _flash_case(shape, direction, sharding=None, causal=True):
    """(function, its three arguments' shapes) of one entry and direction."""
    B, S, H, Hkv, D, window, *value = FLASH_SHAPES[shape]
    Dv = value[0] if value else D
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=sharding)
    k = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16, sharding=sharding)
    v = jax.ShapeDtypeStruct((B, S, Hkv, Dv), jnp.bfloat16, sharding=sharding)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False, window=window)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    return (fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))), (q, k, v)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(chip, shape, direction):
    fn, args = _flash_case(shape, direction, chip)
    text = _compiled_text(fn, *args)
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)


# --------------------------------------------------------------------------
# Where the value is as wide as the keys, the calls are PR 56's
# --------------------------------------------------------------------------

# The kernel learnt a value width of its own (PR 48). Every cell but Phi-4's
# hands it values as wide as its keys and must run the program it ran: the text
# of the traced calls (grid, blocks, scratch, compiler parameters, the kernels'
# bodies) and of every block's index map, hashed by this file run as a script
# (``PYTHONPATH=<a checkout> python tests/test_tpu_compile.py``). No line number
# or file path stands in that text. The table was taken on bb23a3c and held
# every PR to that commit's programs until PR 56 changed every causal call's
# grid on purpose (the needed tiles listed, ``_needed_tiles``): it is taken
# again on PR 56's tree and holds later PRs to PR 56's programs. A call that
# needs every tile is still the program it was: the non-causal hash below is
# a27e5e2's, the commit PR 56 started from, and this tree's.
CALLS_AT_THE_PARENT = {
    ("gpt2", "fwd"): "893298effade8281dcc595d76701ba982b30b512f787e03a59f4cfd6863b1c45",
    ("gpt2", "bwd"): "2e1ef2695f65e8172828f409855bb86dabfb30c09e1a061d4c90d2277e96a7db",
    ("mistral", "fwd"): "d1165babc702d32dbde1bec91638e4184ee958d2da8ca0e5dc92acb4a7a9f564",
    ("mistral", "bwd"): "8386f53e5021d46c2456b52b6958da104b2d3e563425370b4b73917e1e6c5232",
    ("trinity_window", "fwd"): "53444298fea23eea868130d91154e4e54431a273474064f7cb5cf12943982aef",
    ("trinity_window", "bwd"): "fb90a998e205eaa2b65cfaac3360fc8037c2fe1789564738a98b8bb2f1e69101",
    ("trinity_full", "fwd"): "dc2c488d228fc1f6a7deeb9af59dc93af9349923905481870a983c36af4d0831",
    ("trinity_full", "bwd"): "0436fd9fb2feb3ae8a5938bb0a214df732b527577fb408899a5db2f15334e2e1",
    ("lfm2_full", "fwd"): "d493080376ab63113b28e12ddfd6ad04ac634b96b9a9c4c351837aff15ae40ba",
    ("lfm2_full", "bwd"): "7af3165b6154ef5ef39788c01371ce6679406e17e47685460070dd945fcb7557",
    ("phi4_window", "fwd"): "6001fc70e528f5e442b8b3d6f69b0c3dd2fb1c8b67a1a4ae5e816e930bebbf88",
    ("phi4_window", "bwd"): "2fa7a43ba80a6ed94a898f1ec9958669f72bffd543307d56b70139f7cf2c9714",
    ("phi4_full", "fwd"): "2376ac9eb12b8c0362ae71e78fba5fbb867966315c11cf5a5faee2c5a1416e43",
    ("phi4_full", "bwd"): "5273aa08a0cc4d2b00565328ef7fff71c5b9d5202e8610efcd87e278d54587a3",
    ("nemotron_full", "fwd"): "f926de99ecc665ff00691242213405feefa46ec6bdd494bf700c1d69d7df3f55",
    ("nemotron_full", "bwd"): "a9004f433e754697f2cf0648851877c39a7a331fda6461f3ebd0a416cd183a9d",
}


EVERY_TILE_AT_THE_PARENT = "5b66ece72f6372bdc28a0a62ff0433e3d191cf3f7c237539df95d6bbb541e50b"


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(inner)


def _calls_hash(shape, direction, causal=True) -> str:
    fn, args = _flash_case(shape, direction, causal=causal)
    traced = jax.make_jaxpr(fn)(*args)
    maps = [str(m.index_map_jaxpr) for call in _pallas_calls(traced.jaxpr)
            for m in call.params["grid_mapping"].block_mappings]
    return hashlib.sha256("\n".join([str(traced), *maps]).encode()).hexdigest()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [s for s, dims in FLASH_SHAPES.items() if len(dims) == 6])
def test_equal_widths_trace_to_the_calls_of_the_parent_commit(shape, direction):
    assert _calls_hash(shape, direction) == CALLS_AT_THE_PARENT[shape, direction]


def test_a_call_that_needs_every_tile_traces_to_the_calls_of_the_parent_commit():
    """Mistral's shape without the causal mask, forward and the two backward
    kernels: the dense grid, as before PR 56."""
    assert _calls_hash("mistral", "bwd", causal=False) == EVERY_TILE_AT_THE_PARENT


# One rank's routed experts as three cells run them: tokens, width, expert
# width, experts held, choices a token, the expert's form; every choice of every
# token as the sorted pairs (the worst case), walked in chunks of 2048.
GROUPED_SHAPES = {
    "trinity": (8192, 2048, 1024, 8, 8, "swiglu"),
    "lfm2": (16384, 2048, 1536, 8, 4, "swiglu"),
    "nemotron": (8192, 2688, 1856, 8, 6, "relu2"),  # squared ReLU and no gate: two grouped products a chunk
}
# ``memory_analysis().temp_size_in_bytes`` of Nemotron's shape at the parent
# commit (c11993f: a trip's rows went onto the tokens a trip at a time), by the
# same compile: its cell stands at 15.54 GB of the chip's 15.75.
NEMOTRON_TEMP_AT_THE_PARENT = {"fwd": 83_091_968, "bwd": 928_245_248}


def _compiled_grouped(chip, shape, direction):
    from hypha_tpu.ops.grouped_matmul import grouped_experts

    T, D, F, G, K, form = GROUPED_SHAPES[shape]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ws = tuple([sds((G, D, F), jnp.bfloat16)] * (2 if form == "swiglu" else 1) + [sds((G, F, D), jnp.bfloat16)])
    args = (sds((T, D), jnp.bfloat16), ws, sds((T * K,), jnp.int32), sds((T * K,), jnp.float32), sds((G,), jnp.int32))

    def fwd(x, ws, tok, wt, sizes):
        return grouped_experts(x, ws, tok, wt, sizes, form=form)

    fn = fwd if direction == "fwd" else jax.grad(lambda *t: fwd(*t).sum(), argnums=(0, 1, 3))
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(GROUPED_SHAPES))
def test_the_grouped_expert_product_compiles_for_v5e(chip, shape, direction):
    """A loop the compiler keeps as a loop around its own grouped-matmul call,
    and the trips' rows onto the tokens in batches: 4096 rows of two trips at
    8192 tokens, 10240 of five at LFM2's 16384 (``_combine_rows``)."""
    from hypha_tpu.ops.grouped_matmul import _batch_rows

    T, D, _, _, K, _ = GROUPED_SHAPES[shape]
    compiled = _compiled_grouped(chip, shape, direction)
    text = compiled.as_text()
    assert "ragged-dot" in text and " while(" in text
    # the backward walk's two kinds of trip are two loops, and a combine is a
    # loop level: a conditional in a loop's body has the float32 sums copied in
    # and out of it every trip
    assert " conditional(" not in text
    # Which lowering the combine takes: the one scatter of the program (one
    # batch's rows, whatever the kind of its trips) is handed its indices
    # ascending, so the compiler sorts nothing itself: the one sort is
    # ``_by_token``'s, once a layer, outside the loops.
    assert _batch_rows(T, 2048, T * K) == (2048, {8192: 4096, 16384: 10240}[T])
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert len(scatters) == 1 and "indices_are_sorted=true" in scatters[0]
    assert text.count(" sort(") == 1
    if shape == "nemotron":  # the held rows, [4096, 2688] float32, and the order they go in; nothing else
        held_rows = 4096 * D * 4
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= NEMOTRON_TEMP_AT_THE_PARENT[direction] + held_rows + 2 * 2 ** 20


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_the_selective_scan_compiles_for_v5e(chip, direction):
    """Mamba-1's scan at Phi-4-mini-flash-reasoning's widths: one sequence of
    8192, d_inner 5120, state 16, bf16 inputs with the softplus taken inside:
    the two kernels, a [16, 512] state tile a grid step and 128 unrolled
    positions a chunk, which fit beside nothing but chunk boundaries (the
    states of every position would be 2.7 GB)."""
    from hypha_tpu.ops.selective_scan import selective_scan

    B, S, D, N = 1, 8192, 5120, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = (sds((B, S, D), jnp.bfloat16), sds((B, S, D), jnp.bfloat16), sds((D, N), jnp.float32),
            sds((B, S, N), jnp.bfloat16), sds((B, S, N), jnp.bfloat16), sds((D,), jnp.float32))

    def fwd(x, dt, a, b, c, d):
        return selective_scan(x, dt, a, b, c, d, dt_softplus=True, interpret=False)[0]

    fn = fwd if direction == "fwd" else jax.grad(lambda *t: fwd(*t).sum(), argnums=tuple(range(6)))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (1 if direction == "fwd" else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9  # not the 2.7 GB of every state


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("form", ["kernels", "einsum"])
def test_the_ssd_scan_compiles_for_v5e(chip, form, direction):
    """Mamba-2's scan at the Nemotron-H causal tower's widths: one sequence of
    8192, 64 heads of 64 with a state of 128 in 8 groups, bf16 inputs and a
    float32 step, chunk 128. The kernels (``interpret=False``: what the chip
    runs since PR 54): one Pallas call forward and one more backward, and no
    temporary but the 64 boundary states (134 MB) and the layouts around the
    calls (the steps transposed, the state's two layouts): 0.20 GB forward and
    0.34 GB with the backward pass, under a ceiling of 0.5 where the ``einsum``
    form's was 2.0 (a whole mixer's forward and backward, compiled the same way:
    1.15 GB of temporaries with the kernels, 2.36 with the ``einsum``s and their
    masks). The ``einsum`` form (``interpret=None`` off the chip) stays in
    the tree as the kernels' oracle and still compiles for the chip: batched
    products for the MXU under its old ceiling."""
    from hypha_tpu.ops.ssd_scan import ssd_scan

    B, S, H, P, G, N = 1, 8192, 64, 64, 8, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = (sds((B, S, H, P), jnp.bfloat16), sds((B, S, H), jnp.float32), sds((H,), jnp.float32),
            sds((B, S, G, N), jnp.bfloat16), sds((B, S, G, N), jnp.bfloat16))

    def fwd(x, dt, a, b, c):
        return ssd_scan(x, dt, a, b, c, interpret=False if form == "kernels" else None)[0]

    fn = fwd if direction == "fwd" else jax.grad(lambda *t: fwd(*t).sum(), argnums=tuple(range(5)))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    if form == "kernels":
        assert text.count("tpu_custom_call") == (1 if direction == "fwd" else 2)
        assert "reduce-window" not in text  # the running sums are the kernels'
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    else:
        assert "tpu_custom_call" not in text
        assert "convolution(" in text or "dot(" in text  # the products are the MXU's
        assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


# The leaves a worker's delta is made of, f32: Nemotron-H's Mamba-2
# in-projection and its experts' first matrices (a last dimension that is no
# multiple of 128 beside one that is: the v5e keeps them column-major), its
# experts' second matrices, LFM2's routers, Mistral's gate projection.
DELTA_LEAVES = {
    "nemotron_in_proj": ((2688, 10304), False), "nemotron_experts_up": ((8, 2688, 1856), False),
    "nemotron_experts_down": ((8, 1856, 2688), True), "lfm2_router": ((2048, 64), False),
    "mistral_gate": ((4096, 14336), True),
}


@pytest.mark.parametrize("leaf", list(DELTA_LEAVES))
def test_the_delta_is_compiled_to_leave_the_v5e_row_major(chip, leaf):
    """``extract_delta``'s program for a leaf that lies as the v5e's compiler
    lays that shape out by default, which is how a train step hands it back:
    the result is row-major, and where the default already is, the output's
    format is the default's: the program is the one it was."""
    from jax.experimental.layout import Format

    from hypha_tpu.executor import diloco

    shape, lies_row_major = DELTA_LEAVES[leaf]
    rows = tuple(range(len(shape)))
    plain = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    default = jax.jit(diloco._subtract).lower({"w": plain}, {"w": plain}).compile()
    (lies, _), _ = default.input_formats
    assert default.output_formats["w"] == lies["w"]
    assert (tuple(lies["w"].layout.major_to_minor) == rows) == lies_row_major
    tree = {"w": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=Format(lies["w"].layout, chip))}
    compiled = diloco._subtraction_for(tree).lower(tree, tree).compile()
    out = compiled.output_formats["w"]
    assert tuple(out.layout.major_to_minor) == rows and out.sharding == chip
    assert out.layout.tiling == lies["w"].layout.tiling
    if lies_row_major:
        assert out == default.output_formats["w"]
    else:
        # The transposing copy beside the subtraction, and room for it.
        assert compiled.cost_analysis()["bytes accessed"] > default.cost_analysis()["bytes accessed"]
        assert compiled.memory_analysis().temp_size_in_bytes <= 170e6


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_ragged_paged_attention_compiles_for_v5e(chip, quant):
    """The serving decode kernel at head_dim 128 (the Mosaic lane width),
    Sq=1, block_size 16 — bf16 blocks and int8 blocks with scales."""
    B, Hq, Hkv, D, bs, blocks, max_blocks = 8, 32, 8, 128, 16, 512, 64
    rows = (blocks + 1) * bs

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    payload = jnp.int8 if quant == "int8" else jnp.bfloat16
    scale = sds((rows, Hkv), jnp.float32) if quant == "int8" else None
    kv = PagedKV(
        sds((rows, Hkv, D), payload), sds((rows, Hkv, D), payload),
        scale, scale, sds((B, max_blocks), jnp.int32),
    )

    def decode(q, kv, q_offset):
        return paged_attention(
            q, kv, blocks=blocks, block_size=bs, q_offset=q_offset,
            use_kernel=True, interpret=False,
        )

    text = _compiled_text(
        decode, sds((B, 1, Hq, D), jnp.bfloat16), kv, sds((B,), jnp.int32)
    )
    assert "tpu_custom_call" in text


# Keye-VL-2.0's language model at its cell's shapes (B1 S16384, H32/Hkv4 D128,
# 16 index heads of 64, 2048 keys a query): the three flash kernels under a
# packed selection, whose words a key tile reads by a lane-wise shift; the
# choice of the keys (scores in tiles, the k-th value by bisection, the packing);
# and the KL's walk over the causal triangle with its three gradients.
KEYE = dict(S=16384, H=32, Hkv=4, D=128, J=16, Di=64, topk=2048)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_under_a_selection_compiles_for_v5e(chip, direction):
    S, H, Hkv, D = (KEYE[k] for k in ("S", "H", "Hkv", "D"))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    args = (sds((1, S, H, D), jnp.bfloat16), sds((1, S, Hkv, D), jnp.bfloat16), sds((1, S, Hkv, D), jnp.bfloat16),
            sds((1, S, S // 32), jnp.int32))

    def fwd(q, k, v, sel):
        return flash_attention(q, k, v, selection=sel, interpret=False)

    def loss(q, k, v, sel):
        return fwd(q, k, v, sel)[0].astype(jnp.float32).sum()

    text = _compiled_text(fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert text.count("tpu_custom_call") >= (1 if direction == "fwd" else 3)


@pytest.mark.parametrize("part", ["index_select", "index_kl"])
def test_the_selection_of_keys_and_its_objective_compile_for_v5e(chip, part):
    from hypha_tpu.ops.index_select import index_kl, index_select

    S, H, Hkv, D, J, Di, topk = (KEYE[k] for k in ("S", "H", "Hkv", "D", "J", "Di", "topk"))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    indexer = (sds((S, J, Di), jnp.bfloat16), sds((S, Di), jnp.bfloat16), sds((S, J), jnp.float32))
    if part == "index_select":
        compiled = jax.jit(lambda qi, ki, w: index_select(qi, ki, w, topk=topk)).lower(*indexer).compile()
        assert "sort" not in compiled.as_text().lower()  # the choice is by bisection: no sort, no top-k call
        assert "topk" not in compiled.as_text().lower()
    else:
        rest = (sds((S, H, D), jnp.bfloat16), sds((S, Hkv, D), jnp.bfloat16), sds((H, S), jnp.float32),
                sds((S, S // 32), jnp.int32), sds((S,), jnp.float32))
        walk = lambda qi, ki, w, q, k, lse, sel, lse_i: index_kl(qi, ki, w, q, k, lse, sel, lse_i, D**-0.5)
        compiled = jax.jit(jax.value_and_grad(walk, argnums=(0, 1, 2))).lower(*indexer, *rest).compile()
    # the [S, S] float32 score matrix (1.07 GB) never exists: the temporaries are tiles and rows
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


if __name__ == "__main__":  # the hashes, for CALLS_AT_THE_PARENT
    for name, dims in FLASH_SHAPES.items():
        for way in ("fwd", "bwd"):
            if len(dims) == 6:
                print(f'    ("{name}", "{way}"): "{_calls_hash(name, way)}",')
    print(f'EVERY_TILE_AT_THE_PARENT = "{_calls_hash("mistral", "bwd", causal=False)}"')
