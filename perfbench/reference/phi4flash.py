"""The plain reference of the ``phi4flash`` configurations (Microsoft's
Phi-4-mini-flash-reasoning, the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with the differential attention of arXiv:2410.05258): the
layers' forward pass and loss in ``jax.numpy`` and float32 at ``highest``
matmul precision, on weights it makes itself from the seed.

Nothing of the program is imported and nothing it made is read: no kernel, no
chunked scan, no ``hypha_tpu``. With ``n`` the source's layer count and ``i``
a layer's index in the source:

* ``h = E[ids]``; a layer is ``h += mixer(LN(h))``, then ``h += MLP(LN(h))``,
  LayerNorm with weight and bias (``layer_norm_eps``); ``MLP(x) = (u *
  silu(g)) W2`` with ``[g, u] = x W1``; no position encoding;
* the kind of layer ``i``: ``i % mb_per_layer == 0`` is a Mamba-kind layer, any
  other an attention-kind layer. ``i < n / 2``: Mamba, and attention with
  ``sliding_window``. ``i = n / 2``: Mamba, whose scan output is kept.
  ``i = n / 2 + 1``: full causal attention, whose keys and values are kept.
  Later: a GMU where Mamba-kind, cross-attention otherwise;
* Mamba: ``[x, z] = u W_in``; ``x = silu(conv(x) + b)``, depthwise and causal
  over ``d_conv`` taps (the last weighs the current position); ``[delta, B, C]
  = x W_x``; ``dt = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t . h_t + D x_t``, position
  by position; ``out = (y * silu(z)) W_out``; the kept export is ``y``;
* GMU: ``out = (m * silu(u W1)) W2`` with ``m`` layer ``n / 2``'s ``y``;
* differential attention: ``[q, k, v] = u W_qkv + b``; heads in adjacent
  pairs, ``q1, q2`` the first and second of each pair of query heads, ``k1,
  k2`` of key heads, a pair of value heads one value of twice the head size;
  consecutive query pairs share a key pair; ``A_j = softmax(q_j k_j^T /
  sqrt(head))`` under the causal mask (a window layer: key > query - window);
  ``o = (A1 - lambda A2) v``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``o = RMSNorm(o) w (1
  - lambda_init)`` over the doubled head; ``out = o W_o + b_o``;
* cross-attention: ``q = u W_q + b`` alone; keys and values are layer ``n / 2
  + 1``'s; its own lambda vectors and norm; causal;
* ``logits = LN(h) E^T``.

**What the configuration file gives**: the source's keys; ``layers_run`` (the
source indices of the layers that are run, ``source_values.num_hidden_layers``
the source's ``n``); ``head_dim`` and the ``mamba`` group (``d_state``,
``d_conv``, ``expand``, ``dt_rank``), which the source's row lacks and
``assumed`` accounts for; ids, logits and loss over the vocabulary's slice.

The loss is the program's: the mean cross-entropy of position t's logits
against token t + 1 over the first S - 1 positions of every row. Weights are
data: ``table`` says where the worker's flax module keeps each, in what shape
and from which initializer, and ``weights`` replays flax's key derivation
(``tests/perfbench/test_reference_phi4flash.py`` holds them to the module's within an
ulp). One sequence at a time, attention in blocks of queries and the loss
in blocks of positions, so that 8192 positions fit a chip beside 2.3 GB of
weights.
"""

from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024  # positions a block of logits
QUERY_BLOCK = 1024  # queries a block of attention scores: [pairs, group, 1024, S] f32


def source_layers(c: dict) -> int:
    return c.get("source_values", {}).get("num_hidden_layers", c["num_hidden_layers"])


def kind(c: dict, i: int) -> str:
    """The kind of source layer ``i``."""
    hinge = source_layers(c) // 2
    mamba_kind = c["mb_per_layer"] > 0 and i % c["mb_per_layer"] == 0
    if i < hinge:
        return "mamba" if mamba_kind else "window_attention"
    if i <= hinge + 1:
        return "mamba" if mamba_kind else "full_attention"
    return "gmu" if mamba_kind else "cross_attention"


def layers(c: dict) -> list[tuple[int, str]]:
    """(source index, kind) of each layer that is run."""
    run = c.get("layers_run", range(c["num_hidden_layers"]))
    return [(i, kind(c, i)) for i in run]


def table(c: dict) -> dict[str, tuple]:
    """weight -> (path in the worker's ``params`` tree, number among the
    parameters its flax scope draws, shape, initializer). The scope is the
    path without its last element."""
    d, hd, v, f = c["hidden_size"], c["head_dim"], c["vocab_size"], c["intermediate_size"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    m = c["mamba"]
    di, n, taps, rank = m["expand"] * d, m["d_state"], m["d_conv"], m["dt_rank"]
    out = {"embed": (("embed_tokens",), 1, (v, d), "normal_0.02")}

    def norm(name, *path):
        out[f"{name}.w"] = ((*path, "scale"), 1, (d,), "ones")
        out[f"{name}.b"] = ((*path, "bias"), 2, (d,), "zeros")

    def dense(name, width_in, width_out, *path, bias=None):
        out[name] = ((*path, "kernel"), 1, (width_in, width_out), "lecun_normal")
        if bias:
            out[f"{name}.b"] = ((*path, "bias"), 2, (width_out,), bias)

    for j, (_, what) in enumerate(layers(c)):
        layer = f"layers_{j}"
        norm(f"{j}.ln1", layer, "input_layernorm")
        if what == "mamba":
            mixer = (layer, "mamba")
            dense(f"{j}.in", d, 2 * di, *mixer, "in_proj")
            out[f"{j}.taps"] = ((*mixer, "conv_weight"), 1, (taps, di), "lecun_normal")
            out[f"{j}.taps.b"] = ((*mixer, "conv_bias"), 2, (di,), "zeros")
            dense(f"{j}.x", di, rank + 2 * n, *mixer, "x_proj")
            dense(f"{j}.dt", rank, di, *mixer, "dt_proj", bias="dt_bias")
            out[f"{j}.a_log"] = ((*mixer, "A_log"), 3, (di, n), "a_log")
            out[f"{j}.d"] = ((*mixer, "D"), 4, (di,), "ones")
            dense(f"{j}.out", di, d, *mixer, "out_proj")
        elif what == "gmu":
            dense(f"{j}.in", d, di, layer, "gmu", "in_proj")
            dense(f"{j}.out", di, d, layer, "gmu", "out_proj")
        else:
            mixer = (layer, "attn")
            if what == "cross_attention":
                dense(f"{j}.q", d, q, *mixer, "Wq", bias="zeros")
            else:
                dense(f"{j}.qkv", d, q + 2 * kv, *mixer, "Wqkv", bias="zeros")
            for count, name in enumerate(("q1", "k1", "q2", "k2"), start=1):
                out[f"{j}.lambda_{name}"] = ((*mixer, f"lambda_{name}"), count, (hd,), "normal_0.1")
            out[f"{j}.subln"] = ((*mixer, "subln"), 5, (2 * hd,), "ones")
            dense(f"{j}.out", q, d, *mixer, "out_proj", bias="zeros")
        norm(f"{j}.ln2", layer, "post_attention_layernorm")
        dense(f"{j}.w1", d, 2 * f, layer, "mlp", "gate_up_proj")
        dense(f"{j}.w2", f, d, layer, "mlp", "down_proj")
    norm("norm", "final_layernorm")
    return out


def _dt_bias(key, shape, dtype):
    """Mamba-1's: the inverse softplus of a step drawn log-uniformly in
    [0.001, 0.1] (floor 1e-4)."""
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype):
    """log(1 ... d_state) in every channel."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)


INITIALIZERS = {
    "normal_0.02": jax.nn.initializers.normal(0.02),
    "normal_0.1": jax.nn.initializers.normal(0.1),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "ones": jax.nn.initializers.ones,
    "zeros": jax.nn.initializers.zeros,
    "dt_bias": _dt_bias,
    "a_log": _a_log,
}


def _fold(root, scope: tuple, count: int):
    m = hashlib.sha1()
    for x in (*scope, count):
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def weights(config: dict, model_seed: int) -> dict:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    spec = table(config)

    def make(root):
        return {
            name: INITIALIZERS[init](_fold(root, path[:-1], count), shape, jnp.float32)
            for name, (path, count, shape, init) in spec.items()
        }

    return jax.jit(make)(jax.random.key(model_seed))


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _matmul(operands):
    def low(a):
        return a.astype(operands).astype(jnp.float32) if operands else a

    return low, lambda a, b: low(a) @ low(b)


def mamba(w: dict, j: int, u, c: dict, mm):
    """Layer ``j`` on ``u`` [S, width] -> (out [S, width], the scan's output
    ``y`` [S, d_inner], before the gate)."""
    n, rank = c["mamba"]["d_state"], c["mamba"]["dt_rank"]
    x, z = jnp.split(mm(u, w[f"{j}.in"]), 2, axis=-1)
    taps = w[f"{j}.taps"]  # [taps, d_inner]: the last weighs the current position
    last = taps.shape[0] - 1
    conv = jnp.zeros_like(x)
    for t in range(taps.shape[0]):
        back = last - t  # x_{t - back}, zero before the start
        conv += taps[t] * jnp.concatenate([jnp.zeros_like(x[:back]), x[: x.shape[0] - back]], axis=0)
    x = jax.nn.silu(conv + w[f"{j}.taps.b"])
    delta, b, cc = jnp.split(mm(x, w[f"{j}.x"]), [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(mm(delta, w[f"{j}.dt"]) + w[f"{j}.dt.b"])
    a = -jnp.exp(w[f"{j}.a_log"])  # [d_inner, d_state]

    def position(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(position, jnp.zeros_like(a), (x, dt, b, cc))
    y = y + w[f"{j}.d"] * x
    return mm(y * jax.nn.silu(z), w[f"{j}.out"]), y


def gmu(w: dict, j: int, u, memory, mm):
    return mm(memory * jax.nn.silu(mm(u, w[f"{j}.in"])), w[f"{j}.out"])


def attention(w: dict, j: int, source: int, what: str, u, kv, c: dict, low, mm):
    """Differential attention of layer ``j`` (source layer ``source``) on ``u``
    [S, width]; ``kv`` the kept keys and values, read by a cross layer ->
    (out, (k, v) [S, kv_heads, head] each)."""
    s = u.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    if what == "cross_attention":
        q = mm(u, w[f"{j}.q"]) + w[f"{j}.q.b"]
        k, v = kv
    else:
        qkv = mm(u, w[f"{j}.qkv"]) + w[f"{j}.qkv.b"]
        q, k, v = jnp.split(qkv, [heads * hd, (heads + kv_heads) * hd], axis=-1)
        k, v = k.reshape(s, kv_heads, hd), v.reshape(s, kv_heads, hd)
    pairs, group = kv_heads // 2, heads // kv_heads
    q = low(q).reshape(s, pairs, group, 2, hd)  # query pairs j*group.. share key pair j
    k12, vv = low(k).reshape(s, pairs, 2, hd), low(v).reshape(s, pairs, 2 * hd)
    init = 0.8 - 0.6 * math.exp(-0.3 * source)
    lam = (jnp.exp(jnp.sum(w[f"{j}.lambda_q1"] * w[f"{j}.lambda_k1"]))
           - jnp.exp(jnp.sum(w[f"{j}.lambda_q2"] * w[f"{j}.lambda_k2"])) + init)
    kpos = jnp.arange(s)
    outs = []
    for start in range(0, s, QUERY_BLOCK):
        stop = min(start + QUERY_BLOCK, s)
        qpos = jnp.arange(start, stop)[:, None]
        keep = kpos[None, :] <= qpos
        if what == "window_attention":
            keep &= kpos[None, :] > qpos - c["sliding_window"]
        maps = []
        for half in (0, 1):
            scores = jnp.einsum("qjgd,kjd->jgqk", q[start:stop, :, :, half], k12[:, :, half]) * hd**-0.5
            maps.append(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("jgqk,kje->qjge", low(maps[0]) - lam * low(maps[1]), vv))
    o = jnp.concatenate(outs, axis=0)  # [S, pairs, group, 2 head]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c["layer_norm_eps"])
    o = (o * w[f"{j}.subln"] * (1.0 - init)).reshape(s, heads * hd)
    return mm(o, w[f"{j}.out"]) + w[f"{j}.out.b"], (k, v)


def hidden(w: dict, ids, config: dict, operands: str | None = None):
    """The final norm's output for one sequence, [S, width]."""
    c = config
    low, mm = _matmul(operands)
    eps, hinge = c["layer_norm_eps"], source_layers(c) // 2
    h = w["embed"][ids]
    memory = kv = None
    for j, (source, what) in enumerate(layers(c)):
        u = _layer_norm(h, w[f"{j}.ln1.w"], w[f"{j}.ln1.b"], eps)
        if what == "mamba":
            out, y = mamba(w, j, u, c, mm)
            if source == hinge:
                memory = y
        elif what == "gmu":
            out = gmu(w, j, u, memory, mm)
        else:
            out, own = attention(w, j, source, what, u, kv, c, low, mm)
            if what == "full_attention":
                kv = own
        h = h + out
        g, up = jnp.split(mm(_layer_norm(h, w[f"{j}.ln2.w"], w[f"{j}.ln2.b"], eps), w[f"{j}.w1"]), 2, axis=-1)
        h = h + mm(up * jax.nn.silu(g), w[f"{j}.w2"])
    return _layer_norm(h, w["norm.w"], w["norm.b"], eps)


def sequence_nll(w: dict, ids, config: dict, operands: str | None = None):
    """Sum over t < S - 1 of -log p(ids[t + 1] | ids[..t]) for one sequence.
    ``operands`` is for the control alone: a type below float32 that both
    operands of every matrix product are rounded to, the sums staying float32
    (the scan is no matrix product and stays as it is)."""
    _, mm = _matmul(operands)
    h, s = hidden(w, ids, config, operands), ids.shape[0]
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = mm(h[start:stop], w["embed"].T)
        picked = jnp.take_along_axis(logits, ids[start + 1:stop + 1, None], axis=-1)[:, 0]
        total += jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)
    return total


def first_loss(config: dict, input_ids: np.ndarray, model_seed: int,
               operands: str | None = None) -> float:
    """The loss of the worker's first step: seeded weights, its first batch."""
    w = weights(config, model_seed)
    with jax.default_matmul_precision("highest"):
        nll = jax.jit(lambda w, ids: sequence_nll(w, ids, config, operands))
        total = sum(float(nll(w, jnp.asarray(row, jnp.int32))) for row in input_ids)
    rows, s = input_ids.shape
    return total / (rows * (s - 1))
