"""The delta leaves the device in the file's order, whatever order the device keeps.

A backend lays an array out as it likes: a TPU v5e keeps an f32
``[2688, 10304]`` column-major, a jitted ``p - a`` returns it so, ``device_get``
keeps that order on the host, and ``compress.write_delta`` then transposes the
leaf on one thread before it can write the leaf's own memory (0.81 GB a round in
the Nemotron cell). ``extract_delta`` compiles the subtraction to return every
leaf row-major, whatever order its operands lie in. The
CPU backend honours a stated ``Layout`` too, so the case is built here: a
``[6, 5]`` placed ``major_to_minor=(1, 0)`` and a ``[2, 6, 5]`` placed
``(0, 2, 1)``, beside a row-major control. ``tests/test_tpu_compile.py`` holds
what the v5e's compiler makes of the cell's own shapes.
"""

from __future__ import annotations

import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from safetensors.numpy import load, save_file

from hypha_tpu import compress
from hypha_tpu.executor import diloco, training
from hypha_tpu.executor.diloco import extract_delta, relaid
from hypha_tpu.executor.serialization import flatten_tree
from hypha_tpu.telemetry import trace
from test_data_pipeline import _spec
from test_delta_recycled import _NodeSession

ORDERS = {"in_proj": (1, 0), "experts_up": (0, 2, 1), "control": (0, 1)}
SHAPES = {"in_proj": (6, 5), "experts_up": (2, 6, 5), "control": (4, 3)}
LEAVES = sorted(ORDERS)


def _placed(values: np.ndarray, order):
    sharding = jnp.zeros(()).sharding
    return jax.device_put(values, Format(Layout(major_to_minor=order), sharding))


def _tree(seed: int):
    rng = np.random.default_rng(seed)
    flat = {n: rng.standard_normal(SHAPES[n]).astype(np.float32) for n in LEAVES}
    return {"mixer": {n: _placed(flat[n], ORDERS[n]) for n in LEAVES if n != "control"},
            "control": jnp.asarray(flat["control"])}, flat


def _leaf(tree, name):
    return tree["control"] if name == "control" else tree["mixer"][name]


@pytest.fixture(scope="module")
def case():
    (p, p_host), (a, a_host) = _tree(0), _tree(1)
    return {"p": p, "a": a, "p_host": p_host, "a_host": a_host,
            "host": jax.device_get(extract_delta(p, a))}


@pytest.mark.parametrize("name", ["in_proj", "experts_up"])
def test_the_case_is_built_the_leaf_comes_to_the_host_in_the_devices_order(case, name):
    leaf = _leaf(case["p"], name)
    assert tuple(leaf.format.layout.major_to_minor) == ORDERS[name]
    on_host = np.asarray(leaf)
    assert not on_host.flags.c_contiguous
    assert np.array_equal(on_host, case["p_host"][name])


def test_relaid_counts_the_leaves_that_do_not_lie_row_major_and_their_f32_bytes(case):
    assert relaid(case["p"]) == (2, (6 * 5 + 2 * 6 * 5) * 4)
    assert relaid({"control": case["p"]["control"]}) == (0, 0)


@pytest.mark.parametrize("name", LEAVES)
def test_the_delta_comes_to_the_host_c_contiguous_and_is_p_less_a_bit_for_bit(case, name):
    got = _leaf(case["host"], name)
    assert got.dtype == np.float32 and got.shape == SHAPES[name]
    assert got.flags.c_contiguous
    want = case["p_host"][name] - case["a_host"][name]
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("name", LEAVES)
def test_every_leaf_of_the_result_lies_row_major_under_its_own_sharding(case, name):
    got = _leaf(extract_delta(case["p"], case["a"]), name)
    assert tuple(got.format.layout.major_to_minor) == tuple(range(got.ndim))
    assert got.sharding == _leaf(case["p"], name).sharding


def _compiles(caplog, fn):
    caplog.clear()
    with jax.log_compiles(), caplog.at_level(logging.DEBUG, logger="jax"):
        fn()
    return [r.getMessage() for r in caplog.records if "Compiling" in r.getMessage()]


@pytest.mark.parametrize("tree", ["relaid", "row_major"])
def test_a_second_call_compiles_nothing(caplog, tree):
    """One program per tree structure: a tree of its own here, so that the
    first call is this test's."""

    def pair(seed):
        trees = [_tree(seed)[0], _tree(seed + 1)[0]]
        if tree == "row_major":
            return [{"only": t["control"], "twice": t["control"] * 2} for t in trees]
        return [{"only": t["mixer"]} for t in trees]

    p, a = pair(2)
    assert _compiles(caplog, lambda: extract_delta(p, a))
    assert _compiles(caplog, lambda: extract_delta(p, a)) == []
    q, b = pair(4)  # other values, the same tree
    assert _compiles(caplog, lambda: extract_delta(q, b)) == []


def test_a_bf16_tree_comes_back_f32_and_row_major_too(case):
    """The layout asked for is the result's, not read off ``params``: a tree
    of another dtype, laid out otherwise, comes back as the file wants it."""
    p, a = (
        {"w": _placed(case[t]["in_proj"].astype(jnp.bfloat16), ORDERS["in_proj"])}
        for t in ("p_host", "a_host")
    )
    assert relaid(p) == (1, 6 * 5 * 4)
    out = extract_delta(p, a)["w"]
    assert out.dtype == jnp.float32 and tuple(out.format.layout.major_to_minor) == (0, 1)
    got = jax.device_get(out)
    assert got.flags.c_contiguous
    want = jax.device_get(jax.jit(diloco._subtract)(p, a))["w"]  # the order left to the device
    assert got.tobytes() == np.ascontiguousarray(want).tobytes()


@pytest.mark.parametrize("over", ["a spare", "no spare"])
def test_write_delta_writes_the_bytes_save_file_writes_and_copies_no_leaf(tmp_path, case, over):
    flat = flatten_tree(case["host"])
    assert all(v.flags.c_contiguous for v in flat.values())
    spare = None
    if over == "a spare":
        spare = tmp_path / "delta-0.safetensors"
        spare.write_bytes(b"\xff" * 4096)  # longer than what is written over it
    path = tmp_path / "delta-1.safetensors"
    written = compress.write_delta(path, flat, "none", over=spare)
    # No copy: what was written is the leaves' own memory.
    assert all(written[k] is flat[k] for k in flat)
    want = tmp_path / "want.safetensors"
    save_file({k: np.ascontiguousarray(v) for k, v in flat.items()}, str(want))
    assert path.read_bytes() == want.read_bytes()
    assert spare is None or not spare.exists()


# ---------------------------------------------------------------------------
# through run_training: the counters on the round's spans
# ---------------------------------------------------------------------------

ROUNDS = 2


def _spans(tmp, name):
    spans = [json.loads(x) for path in sorted((tmp / "spans").glob("spans-*.jsonl"))
             for x in path.read_text().splitlines()]
    return sorted((s for s in spans if s["name"] == name), key=lambda s: s["mono_start_ns"])


def _train(tmp_path, name):
    work = tmp_path / name
    work.mkdir()
    session = _NodeSession(work, ROUNDS)
    trace.enable(work / "spans", node="w0")
    try:
        result = training.run_training(session, work, _spec(work), max_batches=64)
    finally:
        trace.disable()
    assert result.rounds == ROUNDS
    return session, work


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    """Two jobs of two rounds. ``as it is``: the program. ``column-major``: an
    ``extract_delta`` that hands back every matrix as a v5e hands back the
    Nemotron cell's six, which is what the program's did before it asked."""

    def as_the_device_lies(params, anchor):
        delta = extract_delta(params, anchor)
        return jax.tree.map(
            lambda d: _placed(np.asarray(d), (1, 0)) if d.ndim == 2 and min(d.shape) > 1 else d,
            delta,
        )

    tmp = tmp_path_factory.mktemp("rounds")
    out = {"as it is": _train(tmp, "as-it-is")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(training, "extract_delta", as_the_device_lies)
        out["column-major"] = _train(tmp, "column-major")
    return out


@pytest.mark.parametrize("rnd", range(ROUNDS))
def test_the_rounds_spans_carry_the_three_counters(rounds, rnd):
    _, work = rounds["as it is"]
    extract, write = _spans(work, "encode.extract")[rnd], _spans(work, "encode.write")[rnd]
    # The CPU keeps every leaf row-major: nothing for the device to transpose,
    # and nothing left for the host.
    assert extract["attrs"]["relaid_leaves"] == 0 and extract["attrs"]["relaid_bytes"] == 0
    assert write["attrs"]["copied_bytes"] == 0
    assert write["attrs"]["pages"] == ("recycled" if rnd else "fresh")


@pytest.mark.parametrize("rnd", range(ROUNDS))
def test_a_leaf_that_reaches_the_write_in_another_order_is_counted_and_written_right(rounds, rnd):
    session, work = rounds["column-major"]
    write = _spans(work, "encode.write")[rnd]
    matrices = [v for v in load(session.sent[rnd]["bytes"]).values()
                if v.ndim == 2 and min(v.shape) > 1]
    assert matrices and write["attrs"]["copied_bytes"] == sum(v.nbytes for v in matrices)
    assert 0 < write["attrs"]["copied_bytes"] < write["attrs"]["bytes"]
    # The file is what the program's own round sends, byte for byte.
    assert session.sent[rnd]["bytes"] == rounds["as it is"][0].sent[rnd]["bytes"]
