"""Probes of the ``nemotron-twotower-ctx-d7.steps`` cell for the chip, not tests: what PERF.md quotes of the
first loss and of the scan alone comes from these (run from the root of a checkout, through the chip tool).

``first_loss <seed> ...``: a line a seed. The program's forward pass (bf16, the compiled flash kernel, seeded as
the worker seeds it) and the plain reference in float32 and with float8_e4m3fn operands, each with its own choice
of experts and with the program's forced on it: the loss of each, how many tokens' held experts differ between
the program's choice and the reference's, and where the program leaves the reference position by position.

``scan``: the scan alone at the cell's shapes, forward and forward with backward, at chunks of 128, 64 and 256.
"""
import functools
import json
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

CELL = "nemotron-twotower-ctx-d7.steps"


def first_loss(seeds):
    from hypha_tpu.models import build_model
    from hypha_tpu.models.llama import _RMSNorm
    from hypha_tpu.ops.flash_attention import flash_attention
    from perfbench import data, manifest
    from perfbench.reference import nemotron_h as ref

    cell = manifest.resolve(CELL)
    c = cell.config
    conf = dict(s.removeprefix("job.model_config.").split("=", 1) for s in c["job_sets"][1:])
    conf = {k: json.loads(v) for k, v in conf.items()}
    held, k = c["n_routed_experts"], c["num_experts_per_tok"]

    def positions(hidden, head, ids):  # [S - 1] losses, float32 sums
        out = []
        for start in range(0, ids.shape[0] - 1, 1024):
            stop = min(start + 1024, ids.shape[0] - 1)
            logits = jnp.einsum("sd,vd->sv", hidden[start:stop], head, preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(logits, ids[start + 1:stop + 1, None], axis=-1)[:, 0]
            out.append(jax.nn.logsumexp(logits, axis=-1) - picked)
        return jnp.concatenate(out)

    def held_differ(a, b):  # tokens whose held experts differ between two choices [S, k]
        mine = lambda idx: jnp.sort(jnp.where(idx < held, idx, -1), axis=-1)
        return int(jnp.sum(jnp.any(mine(a) != mine(b), axis=-1)))

    for seed in seeds:
        ids = jnp.asarray(data.first_batch(cell.traffic, seed), jnp.int32)
        ms = data.model_seed(seed)
        model, _ = build_model({"family": "nemotron_h", "config": conf}, functools.partial(flash_attention, interpret=False))
        variables = jax.jit(model.init)(jax.random.key(ms), ids)
        body = model.clone(with_head=False)
        (hidden, _), state = jax.jit(lambda v, i: body.apply(
            v, i, capture_intermediates=lambda m, _: isinstance(m, _RMSNorm), mutable=["intermediates"]))(variables, ids)
        head = variables["params"]["lm_head"].astype(hidden.dtype)
        nll_program = np.asarray(jax.jit(positions)(hidden[0], head, ids[0]))
        program = {}  # the program's choice: its norm's output at each expert layer, its router's formula
        for j, what in enumerate(ref.kinds(c)):
            if what == "experts":
                u = state["intermediates"][f"layers_{j}"]["norm"]["__call__"][0][0].astype(jnp.float32)
                scores = jax.nn.sigmoid(jnp.dot(u, variables["params"][f"layers_{j}"]["mixer"]["router"],
                                                precision=jax.lax.Precision.HIGHEST))
                program[j] = jax.lax.top_k(scores, k)[1]
        del variables, state
        w = ref.weights(c, ms)
        out = {"seed": seed, "program": float(nll_program.mean())}
        with jax.default_matmul_precision("highest"):
            own = jax.jit(lambda w, i: ref.chosen(w, i, c))(w, ids[0])
            out["tokens_whose_held_experts_differ"] = {j: held_differ(program[j], own[j]) for j in program}
            nll = jax.jit(lambda w, i, ch, operands: ref.sequence_nll(w, i, c, operands, ch), static_argnums=3)
            for name, operands in (("float32", None), ("float8_e4m3fn", "float8_e4m3fn")):
                for whose, choice in (("own", None), ("programs", program)):
                    out[f"{name}_{whose}_choice"] = float(nll(w, ids[0], choice, operands)) / (ids.shape[1] - 1)
            h_ref = jax.jit(lambda w, i: ref.hidden(w, i, c))(w, ids[0])
            d = nll_program - np.asarray(jax.jit(positions)(h_ref, w["head"], ids[0]))
        order = np.argsort(-np.abs(d))
        out["by_position"] = {
            "std": float(d.std()), "abs_mean": float(np.abs(d).mean()),
            "largest": [(int(i), float(d[i])) for i in order[:6]],
            "share_of_the_sum_in_the_largest_82": float(d[order[:82]].sum() / d.sum()),
            "hidden_off_in_norm": float(jnp.linalg.norm(hidden[0].astype(jnp.float32) - h_ref) / jnp.linalg.norm(h_ref))}
        print(json.dumps(out), flush=True)
        del w, hidden, h_ref


def scan():
    from hypha_tpu.ops.ssd_scan import ssd_scan

    b, s, h, p, g, n = 1, 8192, 64, 64, 8, 128
    keys = jax.random.split(jax.random.key(0), 4)
    args = (jax.random.normal(keys[0], (b, s, h, p), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) - 3.0), -jnp.arange(1.0, h + 1),
            jax.random.normal(keys[2], (b, s, g, n), jnp.bfloat16), jax.random.normal(keys[3], (b, s, g, n), jnp.bfloat16))

    def ms(f, n=10):
        jax.block_until_ready(f(*args))
        t = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / n * 1e3

    for chunk in (128, 64, 256):
        forward = jax.jit(lambda *t: ssd_scan(*t, chunk=chunk)[0])
        both = jax.jit(jax.grad(lambda *t: jnp.sum(ssd_scan(*t, chunk=chunk)[0] ** 2), argnums=(0, 1, 2, 3, 4)))
        print(json.dumps({"chunk": chunk, "forward_ms": ms(forward), "forward_and_backward_ms": ms(both)}), flush=True)


if __name__ == "__main__":
    if sys.argv[1:2] == ["first_loss"]:
        first_loss([int(s) for s in sys.argv[2:]])
    elif sys.argv[1:] == ["scan"]:
        scan()
    else:
        sys.exit(__doc__)
