"""Training cells: `make_gpt_train_step` through `easydist_compile` on the
cell's mesh, the state born sharded, a fresh seeded batch every step from a
host thread that runs ahead of the device.

Set-up builds ONE object — the compiled step with its state — drives it
through its first steps on the seeded batches (whose losses, first gradient
and parameter change the plain reference is asked about afterwards), and
hands that same object to the window."""

import gc
import queue
import statistics
import threading
import time

import numpy as np

from chipbench import compare, traffic_gen, weights

B1 = 0.9  # Adam's first-moment decay: after one step, mu = (1 - B1) * grad


class _Feed:
    """Seeded batches from a host thread, `depth` steps ahead."""

    def __init__(self, mix, seed, vocab, depth=2):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._args = (mix, seed, vocab)
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        step = 0
        while not self._stop.is_set():
            batch = traffic_gen.train_batch(self._args[0], self._args[1],
                                            step, self._args[2])
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    pass
            step += 1

    def next(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join()


def _call_step(compiled, state, tokens, targets):
    """The one call the first steps and the window both make (the tests
    break the timed path here)."""
    return compiled(state, tokens, targets)


def _mesh(cell, devices):
    from easydist_tpu.jaxfront import make_device_mesh

    t = cell["trainer"]
    return make_device_mesh(tuple(t["mesh_shape"]), tuple(t["mesh_axes"]),
                            devices=devices)


def reference_numbers(sizes, mix, seed, n_steps, lr, devices, quant=False):
    """The plain reference's losses, first-gradient norms and parameter
    change over the first `n_steps` seeded batches.  Leaves are spread over
    the chips with a plain sharding (last axis where it divides) only so
    that they fit; the batch is split by rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chipbench.reference import gpt2

    n = len(devices)
    mesh = Mesh(np.array(devices), ("x",))

    def spread(a):
        for ax in range(a.ndim - 1, -1, -1):
            if a.ndim >= 2 and a.shape[ax] % n == 0 and a.shape[ax] >= 1024:
                return NamedSharding(mesh, P(*([None] * ax + ["x"])))
        return NamedSharding(mesh, P())

    key = weights.seed_key(seed)
    make = lambda k: weights.gpt2_params(sizes, k, stacked=True)  # noqa: E731
    shardings = jax.tree.map(spread, jax.eval_shape(make, key))
    params0 = jax.jit(make, out_shardings=shardings)(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    state = (jax.jit(lambda p: jax.tree.map(jnp.copy, p),
                     out_shardings=shardings)(params0),
             zeros(params0), zeros(params0), jnp.zeros((), jnp.int32))
    rows = NamedSharding(mesh, P("x" if mix["global_batch"] % n == 0
                                 else None))
    kw = dict(heads=sizes["n_head"], eps=float(sizes["layer_norm_epsilon"]),
              lr=lr, quant=quant)
    losses, grad_norms = [], None
    for i in range(n_steps):
        tokens, targets = traffic_gen.train_batch(mix, seed, i,
                                                  sizes["vocab_size"])
        state, loss, gnorms = gpt2.train_step(
            state, jax.device_put(tokens, rows),
            jax.device_put(targets, rows), **kw)
        losses.append(float(loss))
        if i == 0:
            grad_norms = gpt2.flat_norms(gnorms)
    delta = gpt2.flat_norms(gpt2.delta_norms(state[0], params0))
    del state, params0
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from easydist_tpu.jaxfront import easydist_compile
    from easydist_tpu.models import GPTConfig, make_gpt_train_step

    from chipbench.reference import gpt2

    sizes, cell, mix = ctx.sizes, ctx.cell, ctx.mix
    trainer = cell["trainer"]
    devices = ctx.devices
    n_chips = len(devices)
    mesh = _mesh(cell, devices)
    vocab_padded = sizes.get("padded_vocab_size", sizes["vocab_size"])
    cfg = GPTConfig(vocab=vocab_padded, seq=sizes["n_positions"],
                    dim=sizes["n_embd"], heads=sizes["n_head"],
                    layers=sizes["n_layer"], dtype="bfloat16",
                    attention=trainer["attention"], remat=trainer["remat"],
                    scan_layers=bool(trainer["scan_layers"]))
    lr = float(trainer["lr"])
    step, _ = make_gpt_train_step(cfg, lr=lr)

    def init_state(key):
        params = weights.gpt2_params(sizes, key, stacked=cfg.scan_layers)
        return (params, weights.adam_zeros(params))

    key = weights.seed_key(ctx.seed)
    batch_shape = jax.ShapeDtypeStruct(
        (mix["global_batch"], mix["seq_len"]), jnp.int32)
    compiled = easydist_compile(step, mesh=mesh)
    with ctx.span("chipbench.plan"):
        result = compiled.get_compiled(jax.eval_shape(init_state, key),
                                       batch_shape, batch_shape)
    ctx.log(f"plan: phases {result.phase_seconds}, "
            f"replicated FLOPs share {result.replicated_flops_fraction:.4f}")
    with ctx.span("chipbench.make_state"):
        state = result.materialize(init_state, key)
        jax.block_until_ready(state)

    norms = jax.jit(gpt2.leaf_norms)
    feed = _Feed(mix, ctx.seed, sizes["vocab_size"])
    tokens_per_step = mix["global_batch"] * mix["seq_len"]

    def one_step(state):
        tokens, targets = feed.next()
        with ctx.span("chipbench.train_step"):
            state, loss = _call_step(compiled, state, tokens, targets)
            loss = float(jax.block_until_ready(loss))
        return state, loss

    # ---- the first steps, through the window's own call and feed
    n_warm = int(trainer["warm_steps"])
    program = {"losses": []}
    warm_s = []
    for i in range(n_warm):
        t0 = time.perf_counter()
        state, loss = one_step(state)
        warm_s.append(time.perf_counter() - t0)
        program["losses"].append(loss)
        if i == 0:
            program["grad_norms"] = {
                k: v / (1.0 - B1) for k, v in
                gpt2.flat_norms(norms(state[1]["mu"])).items()}
    with ctx.span("chipbench.delta_norms"):
        params0 = jax.jit(
            lambda k: weights.gpt2_params(sizes, k, stacked=cfg.scan_layers),
            out_shardings=jax.tree.map(lambda a: a.sharding, state[0]))(key)
        program["delta_norms"] = gpt2.flat_norms(
            gpt2.delta_norms(state[0], params0))
        del params0
    ctx.log(f"first steps: losses {program['losses']}, seconds "
            f"{[round(s, 2) for s in warm_s]}")

    # ---- the window: whole steps, each ending in block_until_ready
    ctx.window_opens()
    t_open = time.perf_counter()
    step_s, losses = [], []
    while time.perf_counter() - t_open < ctx.seconds:
        t0 = time.perf_counter()
        state, loss = one_step(state)
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
    wall = time.perf_counter() - t_open
    ctx.window_closed()
    rate = len(step_s) * tokens_per_step / wall / n_chips
    ctx.log(f"window: {len(step_s)} steps in {wall:.2f} s, median step "
            f"{1e3 * statistics.median(step_s):.1f} ms, loss "
            f"{losses[0]:.4f} -> {losses[-1]:.4f}")

    trace = None
    if ctx.trace:
        with ctx.profile() as prof:
            for _ in range(int(trainer["traced_steps"])):
                state, loss = one_step(state)
        trace = prof.result
        trace["steps"] = int(trainer["traced_steps"])
    peak = ctx.memory_peak()
    feed.close()
    del state, compiled, result.jitted, result.tree_jitted
    gc.collect()

    # ---- correct: against the plain reference, after the state is freed
    t0 = time.perf_counter()
    reference = reference_numbers(sizes, mix, ctx.seed, n_warm, lr, devices)
    check = compare.training(program, reference, cell["check"]["limits"],
                             ctx.log)
    finite = all(np.isfinite(losses))
    ctx.log(f"reference (float32, {n_warm} steps) took "
            f"{time.perf_counter() - t0:.1f} s; its losses "
            f"{reference['losses']}")
    if ctx.control:
        low = reference_numbers(sizes, mix, ctx.seed, n_warm, lr, devices,
                                quant=True)
        check["control"] = compare.train_numbers(low, reference)
        ctx.log(f"control (fp8 operands): {check['control']}")

    train = {   # what the per-layer readers take
        "phase_seconds": dict(result.phase_seconds),
        "first_steps_s": warm_s,
        "median_step_s": statistics.median(step_s),
    }
    return {"correct": check["correct"] and finite,
            "attempted": len(step_s), "failed": 0,
            "e2e": {"train_tokens_per_s_per_chip": rate},
            "trace": trace, "train": train, "memory_peak_bytes": peak,
            "check": check, "sizes": sizes}
