"""The plain reference of the ``mistral`` configurations: the published
block's forward pass and loss in ``jax.numpy`` and float32 at ``highest``
matmul precision, on weights it makes itself from the seed.

Nothing of the program is imported and nothing it made is read: no kernel,
no cache, no ``hypha_tpu``. The block is the one of the source's
``modeling_mistral.py``: token embedding, RMSNorm, grouped-query attention
with rotate-half RoPE at ``rope_theta``, a causal mask cut to
``sliding_window`` (query i sees keys in (i - window, i]), SwiGLU, the final
norm and the untied head. The loss is the program's: the mean cross-entropy
of position t's logits against token t + 1 over the first S - 1 positions of
every row (``executor/train.py``: ``hidden[:, :-1]`` against
``inputs[:, 1:]``), B x (S - 1) terms.

Weights are data. The worker starts from ``model.init(jax.random.key(
model_seed), ...)`` of its flax module; flax gives the parameter at scope
path p, the n-th its scope creates, the key ``fold_in(root, first four bytes
of sha1(p..., n))`` and draws it with jax's own initializer. ``TABLE`` says
for each of the reference's weights where the module keeps it, in what shape
and from which initializer, and ``weights`` replays that: the same numbers as
the worker's, made here (``tests/perfbench/test_reference.py`` holds them to
the module's bit for bit).

One sequence is computed at a time, attention one key-value head at a time
and the loss in blocks of positions, so that batch 4 x 4096 at 7B widths
fits a chip beside the 1.9 GB of weights.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 1024  # positions a block of logits: 1024 x 32000 f32 is 131 MB


def table(c: dict) -> dict[str, tuple]:
    """weight -> (flax scope path, number in its scope, shape, initializer)."""
    e, inter, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    out = {"embed": ((), 1, (v, e), "normal_0.02")}
    for i in range(c["num_hidden_layers"]):
        layer = f"layers_{i}"
        out |= {
            f"{i}.input_norm": ((layer, "input_layernorm"), 1, (e,), "ones"),
            f"{i}.q": ((layer, "self_attn", "q_proj"), 1, (e, q), "lecun_normal"),
            f"{i}.k": ((layer, "self_attn", "k_proj"), 1, (e, kv), "lecun_normal"),
            f"{i}.v": ((layer, "self_attn", "v_proj"), 1, (e, kv), "lecun_normal"),
            f"{i}.o": ((layer, "self_attn", "o_proj"), 1, (q, e), "lecun_normal"),
            f"{i}.post_norm": ((layer, "post_attention_layernorm"), 1, (e,), "ones"),
            f"{i}.gate": ((layer, "mlp", "gate_proj"), 1, (e, inter), "lecun_normal"),
            f"{i}.up": ((layer, "mlp", "up_proj"), 1, (e, inter), "lecun_normal"),
            f"{i}.down": ((layer, "mlp", "down_proj"), 1, (inter, e), "lecun_normal"),
        }
    out["norm"] = (("norm",), 1, (e,), "ones")
    out["head"] = ((), 2, (v, e), "normal_0.02")
    return out


INITIALIZERS = {
    "normal_0.02": jax.nn.initializers.normal(0.02),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "ones": jax.nn.initializers.ones,
}


def _fold(root, path: tuple, count: int):
    m = hashlib.sha1()
    for x in (*path, count):
        m.update(x.encode() if isinstance(x, str) else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(root, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def weights(config: dict, model_seed: int) -> dict:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    spec = table(config)

    def make(root):
        return {
            name: INITIALIZERS[init](_fold(root, path, count), shape, jnp.float32)
            for name, (path, count, shape, init) in spec.items()
        }

    return jax.jit(make)(jax.random.key(model_seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half: x [S, heads, head_dim], position = row index."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def sequence_nll(w: dict, ids, config: dict, operands: str | None = None):
    """Sum over t < S - 1 of -log p(ids[t + 1] | ids[..t]) for one sequence.
    ``operands`` is for the control alone (``tests/perfbench/test_reference.py``):
    a type below float32 that both operands of every matrix product are
    rounded to, the sums staying float32."""
    c = config

    def low(a):
        return a.astype(operands).astype(jnp.float32) if operands else a

    def mm(a, b):
        return low(a) @ low(b)

    if c.get("hidden_act", "silu") != "silu" or c.get("tie_word_embeddings"):
        raise ValueError("this reference is the silu, untied-head block")
    s = ids.shape[0]
    heads, kv_heads, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    group, eps = heads // kv_heads, c["rms_norm_eps"]
    pos = jnp.arange(s)
    keep = pos[None, :] <= pos[:, None]
    if c.get("sliding_window"):
        keep &= pos[None, :] > pos[:, None] - c["sliding_window"]
    x = w["embed"][ids]
    for i in range(c["num_hidden_layers"]):
        h = _rms(x, w[f"{i}.input_norm"], eps)
        q = _rope(mm(h, w[f"{i}.q"]).reshape(s, heads, hd), c["rope_theta"])
        k = _rope(mm(h, w[f"{i}.k"]).reshape(s, kv_heads, hd), c["rope_theta"])
        v = mm(h, w[f"{i}.v"]).reshape(s, kv_heads, hd)
        outs = []
        for j in range(kv_heads):  # query heads j*group .. (j+1)*group share key-value head j
            mine = low(q[:, j * group:(j + 1) * group])
            scores = jnp.einsum("qgd,kd->gqk", mine, low(k[:, j])) * hd**-0.5
            p = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("gqk,kd->qgd", low(p), low(v[:, j])))
        x = x + mm(jnp.concatenate(outs, axis=1).reshape(s, heads * hd), w[f"{i}.o"])
        h = _rms(x, w[f"{i}.post_norm"], eps)
        x = x + mm(jax.nn.silu(mm(h, w[f"{i}.gate"])) * mm(h, w[f"{i}.up"]), w[f"{i}.down"])
    x = _rms(x, w["norm"], eps)
    total = jnp.float32(0.0)
    for start in range(0, s - 1, LOSS_BLOCK):
        stop = min(start + LOSS_BLOCK, s - 1)
        logits = mm(x[start:stop], w["head"].T)
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
