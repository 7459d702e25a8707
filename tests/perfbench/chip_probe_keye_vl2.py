"""Probes of the ``keye-vl-2-30b-a3b-txt-d4.steps`` cell for the chip, not tests: what PERF.md quotes of the
selection's forms, of the masked kernels and of the first loss comes from these (run from the root of a checkout,
through the chip tool).

``forms``: at the cell's shapes (one sequence of 16384, 32 query heads to 4 key heads of 128, 16 index heads of 64,
2048 keys a query), a line each: the index scores alone; the exact choice of a chunk of 512 rows by ``lax.top_k``,
by a sort and by the bisection the op uses; the whole ``index_select``; the flash kernels with the packed
selection and without (the causal call), forward and forward with backward; the KL's walk; and the whole step.

``experts``: the routed layer alone, forward and backward, by the grouped product's chunk and the routing.

``dynamics [steps]``: the inner loop alone, the loss and the two objectives' gradient norms a step.

``record``: one warm step of the cell under jax's profiler, written under ``chiprun_out/recorded_keye_vl2/``:
what ``tests/perfbench/data/recorded_keye_vl2/`` was cut from (the device's ``XLA Ops`` line alone).

``first_loss <seed> ...``: a line a seed: the program's first loss and KL (bf16, the compiled kernels, seeded as
the worker seeds it) beside the plain reference's in float32 and with float8_e4m3fn operands.
"""
import functools
import json
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

CONFIG = "perfbench/configs/keye-vl-2-30b-a3b-txt-d4.json"
SIZES = json.load(open("perfbench/traffic/keye-vl-2-30b-a3b-txt-d4.steps.json"))


def _cell():
    import types

    return types.SimpleNamespace(config=json.load(open(CONFIG)), traffic=SIZES)


def ms(f, *args, n=5):
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def say(**kw):
    print(json.dumps(kw), flush=True)


def _model(conf):
    from hypha_tpu.models import build_model
    from hypha_tpu.ops.flash_attention import flash_attention

    return build_model({"family": "keye_vl2", "config": conf}, functools.partial(flash_attention, interpret=False))[0]


def _conf(c):
    conf = dict(s.removeprefix("job.model_config.").split("=", 1) for s in c["job_sets"][1:])
    return {k: json.loads(v) for k, v in conf.items()}


def forms(sequence=16384):
    from hypha_tpu.ops import index_select as op
    from hypha_tpu.ops.flash_attention import flash_attention

    s, h, hkv, d, j, di, topk = sequence, 32, 4, 128, 16, 64, 2048
    keys = jax.random.split(jax.random.key(0), 8)
    bf = jnp.bfloat16
    q, k, v = (jax.random.normal(keys[i], (1, s, n, d), bf) for i, n in ((0, h), (1, hkv), (2, hkv)))
    qi, ki = jax.random.normal(keys[3], (s, j, di), bf), jax.random.normal(keys[4], (s, di), bf)
    w = jax.random.normal(keys[5], (s, j), jnp.float32) * (j**-0.5 * di**-0.5)

    scores_only = jax.jit(lambda qi, ki, w: jax.lax.map(
        lambda a: jax.lax.map(lambda kt: op.score_tile(a[0], kt, a[1])[0], ki.reshape(-1, 512, di)).sum((0, 2)),
        (qi.reshape(-1, 512, j, di), w.reshape(-1, 512, j))))
    say(what="index scores alone, all tiles, one layer", ms=ms(scores_only, qi, ki, w))
    rows = jax.random.normal(keys[6], (512, s), jnp.float32)
    chunks = s // 512
    say(what="lax.top_k of 2048 of a chunk of 512 rows, times the chunks", ms=chunks * ms(jax.jit(lambda r: jax.lax.top_k(r, topk)[0][:, -1]), rows))
    say(what="sort of a chunk of 512 rows, times the chunks", ms=chunks * ms(jax.jit(lambda r: jnp.sort(r, axis=-1)[:, -topk]), rows))
    say(what="bisection (32 passes) of a chunk of 512 rows, times the chunks",
        ms=chunks * ms(jax.jit(lambda r: op._kth_largest(op._ordered(r), topk)), rows))
    select = jax.jit(lambda qi, ki, w: op.index_select(qi, ki, w, topk=topk))
    say(what="index_select whole (scores, choice, packing), one layer", ms=ms(select, qi, ki, w))
    packed, lse_i = select(qi, ki, w)
    say(what="keys picked share", value=float(jnp.sum(jax.lax.population_count(packed))) / (s * (s + 1) // 2))

    sel = lambda q, k, v: flash_attention(q, k, v, selection=packed[None], interpret=False)
    causal = lambda q, k, v: flash_attention(q, k, v, interpret=False)
    say(what="flash forward, packed selection", ms=ms(jax.jit(lambda *a: sel(*a)[0]), q, k, v))
    say(what="flash forward, causal alone", ms=ms(jax.jit(causal), q, k, v))
    grad = lambda f: jax.jit(jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(), (0, 1, 2)))
    say(what="flash forward and backward, packed selection", ms=ms(grad(lambda *a: sel(*a)[0]), q, k, v))
    say(what="flash forward and backward, causal alone", ms=ms(grad(causal), q, k, v))
    _, lse = jax.jit(sel)(q, k, v)
    walk = jax.jit(jax.value_and_grad(
        lambda qi, ki, w: op.index_kl(qi, ki, w, q[0], k[0], lse[0], packed, lse_i, d**-0.5), (0, 1, 2)))
    say(what="index_kl: the walk with its three gradients, one layer", ms=ms(walk, qi, ki, w))


def experts(chunks=(2048, 8192, 16384, 65536)):
    """The routed layer alone at the cell's widths (16384 tokens, 16 of 128 held, 8 a token), forward and backward,
    by the grouped product's chunk and by how many pairs the routers send here: the share's 1.0 a token, spread
    evenly, and 3.0 a token with every token on one held expert, as the worker's routing lines read after a round."""
    from hypha_tpu.ops.grouped_matmul import grouped_experts, sort_pairs

    t, d, f, g, k = 16384, 2048, 768, 16, 8
    keys = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    ws = tuple(jax.random.normal(keys[i], s, jnp.bfloat16) * 0.02 for i, s in ((1, (g, d, f)), (2, (g, d, f)), (3, (g, f, d))))
    even = jax.random.randint(keys[4], (t, k), 0, 128)  # 1.0 pairs a token held here, on average
    one = jnp.concatenate([jnp.zeros((t, 1), jnp.int32), jnp.ones((t, 1), jnp.int32), jnp.full((t, 1), 2, jnp.int32),
                           jax.random.randint(keys[4], (t, k - 3), 16, 128)], axis=1)  # 3.0: experts 0, 1, 2 see every token
    wts = jnp.full((t, k), 0.125, jnp.float32)
    for name, idx in (("1.0 pairs a token, even", even), ("3.0 pairs a token, three experts", one)):
        for chunk in chunks:
            def layer(x, ws):
                order, sizes = sort_pairs(idx, 0, g)
                return grouped_experts(x, ws, order // k, wts.reshape(-1)[order], sizes, chunk=chunk).astype(jnp.float32).sum()
            both = jax.jit(jax.grad(layer, (0, 1)))
            say(what="routed layer forward and backward", routing=name, chunk=chunk, ms=ms(both, x, ws))


def step(record=False):
    from hypha_tpu.executor.train import TrainState, build_optimizer, make_routed_train_step
    from hypha_tpu.messages import Adam
    from hypha_tpu.models.routed import STATE
    from perfbench import data

    cell = _cell()
    model = _model(_conf(cell.config))
    ids = jnp.asarray(data.first_batch(cell.traffic, 1), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(1), ids)
    state = TrainState.create({"params": variables["params"]}, build_optimizer(Adam(lr=1e-4)), {STATE: variables[STATE]})
    del variables
    run = make_routed_train_step(model)
    for i in range(3 if record else 6):
        t = time.perf_counter()
        state, m = run(state, {"input_ids": ids})
        host = np.asarray(m["host"]).tolist()
        say(what="step", i=i, s=time.perf_counter() - t, host=host,
            peak_gb=(jax.local_devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9)
    if record:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 1
        jax.profiler.start_trace("chiprun_out/recorded_keye_vl2/profile", profiler_options=opts)
        state, m = run(state, {"input_ids": ids})
        say(what="recorded step", host=np.asarray(m["host"]).tolist())
        jax.profiler.stop_trace()


def dynamics(steps=24):
    """The inner loop alone, no outer step: the loss a step, the whole gradient's norm, and the norms of the
    cross-entropy's and the KL's gradients apart (they live on disjoint leaves), at the job's optimizer."""
    import optax
    from hypha_tpu.executor.train import TrainState, _head_loss, build_optimizer, make_routed_train_step
    from hypha_tpu.messages import Adam
    from hypha_tpu.models.routed import STATE
    from perfbench import data

    cell = _cell()
    model = _model(_conf(cell.config))
    rows = data.slices(cell.traffic, 1)
    batch = next(rows)
    ids = jnp.asarray(batch[:1], jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(1), ids)
    state = TrainState.create({"params": variables["params"]}, build_optimizer(Adam(lr=1e-4)), {STATE: variables[STATE]})
    extras = {STATE: variables[STATE]}
    del variables
    body = model.clone(with_head=False)

    def parts(params, ids):
        def both(p):
            hidden, stats = body.apply({**p, **extras}, ids)
            return _head_loss(model, p, hidden, ids, 512), stats["aux_loss"].sum()
        g_ce = jax.grad(lambda p: both(p)[0])(params)
        g_kl = jax.grad(lambda p: both(p)[1])(params)
        return optax.global_norm(g_ce), optax.global_norm(g_kl)

    norms = jax.jit(parts)
    run = make_routed_train_step(model, donate=False)
    for i in range(steps):
        ids = jnp.asarray(batch[i % 32:i % 32 + 1], jnp.int32)
        if i in (0, 8, 16):
            ce, kl = norms(state.params, ids)
            say(what="gradient norms", step=i, cross_entropy=float(ce), kl=float(kl))
        state, m = run(state, {"input_ids": ids})
        say(what="step", i=i, loss=float(m["loss"]), kl=float(m["aux_loss"]), grad_norm=float(m["grad_norm"]))


def first_loss(seeds):
    from perfbench import data
    from perfbench.reference import keye_vl2 as ref

    cell = _cell()
    c = cell.config
    for seed in seeds:
        ids = jnp.asarray(data.first_batch(cell.traffic, seed), jnp.int32)
        seed_m = data.model_seed(seed)
        model = _model(_conf(c))
        variables = jax.jit(model.init)(jax.random.key(seed_m), ids)
        body = model.clone(with_head=False)

        def program(v, i):
            from hypha_tpu.executor.train import _head_loss

            hidden, stats = body.apply(v, i)
            return _head_loss(model, {"params": v["params"]}, hidden, i, 512), stats["aux_loss"].sum()

        ce, kl = jax.jit(program)(variables, ids)
        out = {"seed": seed, "program": float(ce), "program_kl": float(kl)}
        del variables
        w = ref.weights(c, seed_m)
        with jax.default_matmul_precision("highest"):
            for name, operands in (("float32", None), ("float8_e4m3fn", "float8_e4m3fn")):
                nll, rkl = jax.jit(lambda w, i: ref.sequence_losses(w, i, c, operands))(w, ids[0])
                out[name], out[name + "_kl"] = float(nll) / (ids.shape[1] - 1), float(rkl)
        print(json.dumps(out), flush=True)
        del w


if __name__ == "__main__":
    if sys.argv[1:2] == ["first_loss"]:
        first_loss([int(s) for s in sys.argv[2:]])
    elif sys.argv[1:2] == ["forms"]:
        forms(*[int(s) for s in sys.argv[2:]])
    elif sys.argv[1:] == ["experts"]:
        experts()
    elif sys.argv[1:2] == ["dynamics"]:
        dynamics(*[int(s) for s in sys.argv[2:]])
    elif sys.argv[1:] in (["step"], ["record"]):
        step(record=sys.argv[1] == "record")
    else:
        sys.exit(__doc__)
