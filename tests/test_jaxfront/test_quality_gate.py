"""Sharded-path quality gate (VERDICT r1 #3): on the virtual 8-device mesh,
the auto-parallelized GPT step's emitted collectives must match (dp) or beat
(dp x tp) a hand-written GSPMD sharding of the same step, and the solver must
stay fast.  The single-chip bench cannot see any of this — a solver
regression that inserts extra collectives fails HERE."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
from easydist_tpu.models import GPTConfig, make_gpt_train_step
from easydist_tpu.utils.hlo import (collective_summary,
                                    total_collective_bytes,
                                    total_collective_count)


def _gpt_case():
    cfg = GPTConfig.tiny(seq=64, dim=64, heads=4, layers=2, vocab=256)
    step, init_state = make_gpt_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, cfg.seq), 0,
                                cfg.vocab)
    return step, state, tokens


def _hand_dp(step, state, tokens, mesh):
    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))
    state_sh = jax.tree_util.tree_map(lambda _: rep, state)
    return jax.jit(step, in_shardings=(state_sh, dp, dp)) \
        .lower(state, tokens, tokens).compile()


@pytest.mark.world_8
@pytest.mark.long_duration
def test_dp_collectives_match_hand_gspmd(cpu_devices):
    step, state, tokens = _gpt_case()
    mesh = make_device_mesh((8,), ("dp",), devices=cpu_devices)
    hand = collective_summary(
        _hand_dp(step, state, tokens, mesh).as_text())

    t0 = time.perf_counter()
    res = easydist_compile(step, mesh=mesh).get_compiled(
        state, tokens, tokens)
    solve_s = time.perf_counter() - t0
    ours = collective_summary(res.executable().as_text())

    # the plan keeps the Adam moments of some weights sharded over dp, and
    # a step hands every state leaf back as it took it (PR 38), so it ends
    # by gathering those weights' updated values: one all-gather a weight,
    # of the weight's bytes.  (Until then the gathers sat at the top of the
    # NEXT call's program, compiled for the state the first gave back,
    # which this census never saw.)  The hand-written step keeps all state
    # whole and has none.
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    mu_sharded = {jax.tree_util.keystr(path[2:]) for (path, _), sharding
                  in zip(flat, res.in_shardings)
                  if jax.tree_util.keystr(path[:2]) == "[1]['mu']"
                  and not sharding.is_fully_replicated}
    gathered = [leaf for path, leaf in flat
                if jax.tree_util.keystr(path[:1]) == "[0]"
                and jax.tree_util.keystr(path[1:]) in mu_sharded]
    assert ours.pop("all-gather", (0, 0)) == (
        len(gathered), sum(leaf.nbytes for leaf in gathered)), ours
    # pure DP is unambiguous: otherwise the same census, to the byte
    assert ours == hand, (ours, hand)
    # solver + emission must stay fast (this config solved in <1s; the
    # bound leaves 20x headroom before flagging a blowup)
    assert solve_s < 30, f"auto-parallel compile took {solve_s:.1f}s"


@pytest.mark.world_8
@pytest.mark.long_duration
def test_dp_tp_collectives_not_worse_than_hand(cpu_devices):
    """On (4,2) dp x tp the solver may pick a different layout than the
    hand megatron sharding — but never a more expensive one."""
    step, state, tokens = _gpt_case()
    mesh = make_device_mesh((4, 2), ("dp", "tp"), devices=cpu_devices)

    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))

    def spec(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if leaf.ndim == 2 and ("qkv" in name or "fc" in name):
            return NamedSharding(mesh, P(None, "tp"))
        if leaf.ndim == 2 and "proj" in name:
            return NamedSharding(mesh, P("tp", None))
        return rep

    params, opt = state
    psh = jax.tree_util.tree_map_with_path(spec, params)
    osh = jax.tree_util.tree_map_with_path(lambda p, l: spec(p[1:], l), opt)
    hand = collective_summary(
        jax.jit(step, in_shardings=((psh, osh), dp, dp))
        .lower(state, tokens, tokens).compile().as_text())

    res = easydist_compile(step, mesh=mesh).get_compiled(
        state, tokens, tokens)
    ours = collective_summary(res.executable().as_text())

    assert total_collective_bytes(ours) <= total_collective_bytes(hand), \
        (ours, hand)
    assert total_collective_count(ours) <= total_collective_count(hand), \
        (ours, hand)


@pytest.mark.world_8
@pytest.mark.long_duration
def test_solver_chooses_sequence_parallelism_for_long_seq(cpu_devices):
    """VERDICT r1 #6: on a long-seq batch-1 GPT over (8,)("sp") the ILP must
    choose sequence sharding on its own (batch is indivisible), emitting the
    gather-KV sequence-parallel plan (bytes-equivalent of a ring; the
    explicit ring_attention API is the O(T/n)-memory manual variant), and
    the compiled step must match dense attention."""
    import numpy as np

    from easydist_tpu.models import gpt_init
    from easydist_tpu.models.gpt import gpt_apply

    cfg = GPTConfig.tiny(seq=1024, dim=64, heads=4, layers=2, vocab=256)
    mesh = make_device_mesh((8,), ("sp",), devices=cpu_devices)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, cfg.seq), 0,
                                cfg.vocab)
    params = gpt_init(cfg, jax.random.PRNGKey(0))

    def fwd(params, tokens):
        return gpt_apply(params, cfg, tokens)

    res = easydist_compile(fwd, mesh=mesh, donate_state=False).get_compiled(
        params, tokens)

    # activations must be sequence-sharded: the embedding-sum output
    # ([1, seq, dim]) sharded on dim 1, and more seq-sharded interior
    # tensors than replicated ones among large activations
    n_seq_sharded = sum(
        1 for ns in res.strategies[0].values()
        for p in ns.out_placements
        if p is not None and p.is_shard() and p.dim in (1, 2))
    n_repl = sum(
        1 for ns in res.strategies[0].values()
        for p in ns.out_placements if p is not None and p.is_replicate())
    assert n_seq_sharded > n_repl, (n_seq_sharded, n_repl)

    # the plan must NOT fall back to replicated attention: total collective
    # traffic stays within a few gathered K/V blocks per layer
    summary = collective_summary(res.executable().as_text())
    kv_bytes_per_layer = 2 * cfg.seq * cfg.dim * 4
    assert total_collective_bytes(summary) <= \
        3 * cfg.layers * kv_bytes_per_layer, summary

    out = res.tree_jitted(params, tokens)
    ref = jax.jit(fwd)(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=1e-4)


@pytest.mark.world_8
def test_partial_deferral_reduces_collective_bytes(cpu_devices):
    """Global PARTIAL pools + deferred-reduction regions (VERDICT r2 #4):
    on a pinned contracted-sharded mm -> elementwise -> mm -> sum chain the
    emitted program must move STRICTLY fewer collective bytes than the
    no-partial plan (the fence reduces a scalar instead of the intermediate
    matrix), with identical numerics."""
    from easydist_tpu import config as edconfig
    from easydist_tpu.jaxfront.scope import fix_sharding
    from easydist_tpu.utils.hlo import collective_summary

    mesh = make_device_mesh((8,), ("tp",), devices=cpu_devices)
    # Geometry matters: deferral must be the unambiguous optimum.  The
    # deferred all-reduce (y, B*k*4 bytes) has to dwarf both one psum
    # launch AND whatever compute the roofline solver could save by
    # resolving early (or reduce-scattering) and sharding the DOWNSTREAM
    # ops — so the batch is large (big y) and the second matmul is narrow
    # (little downstream compute to shard).  At B=4/k2=k both trades tie
    # and the gate would pin a coin flip.
    k, k2 = 512, 64
    x = jnp.ones((256, k))
    w1 = jax.random.normal(jax.random.PRNGKey(0), (k, k)) / k ** 0.5
    w2 = jax.random.normal(jax.random.PRNGKey(1), (k, k2)) / k ** 0.5

    def step(x, w1, w2):
        x = fix_sharding(x, None, "tp")
        w1 = fix_sharding(w1, "tp", None)
        y = x @ w1
        z = -y  # elementwise P-linear link in the chain
        return jnp.sum(z @ w2)

    def total_bytes(summary):
        return sum(b for _, b in summary.values())

    saved = edconfig.enable_partial_pools
    try:
        edconfig.enable_partial_pools = False
        r0 = easydist_compile(step, mesh=mesh, state_io={}) \
            .get_compiled(x, w1, w2)
        base = collective_summary(r0.executable().as_text())

        edconfig.enable_partial_pools = True
        r1 = easydist_compile(step, mesh=mesh, state_io={}) \
            .get_compiled(x, w1, w2)
        part = collective_summary(r1.executable().as_text())
    finally:
        edconfig.enable_partial_pools = saved

    assert total_bytes(part) < total_bytes(base), (part, base)
    import numpy as np

    np.testing.assert_allclose(float(r0.tree_jitted(x, w1, w2)),
                               float(r1.tree_jitted(x, w1, w2)), rtol=1e-5)


@pytest.mark.world_8
def test_partial_deferral_on_hybrid_dp_tp_mesh(cpu_devices):
    """ROADMAP #1: deferred-reduction regions on a HYBRID (dp x tp) mesh —
    the tp-partial chain is simultaneously batch-sharded over dp (riding
    the shard_map `auto` axes).  The fence reduces a (batch,) vector where
    the eager plan all-reduces the (batch, k) intermediate: strictly fewer
    collective bytes, identical numerics."""
    import numpy as np

    from easydist_tpu import config as edconfig
    from easydist_tpu.jaxfront.scope import fix_sharding

    mesh = make_device_mesh((4, 2), ("dp", "tp"), devices=cpu_devices)
    k = 512
    x = jax.random.normal(jax.random.PRNGKey(0), (16, k)) / k ** 0.5
    w1 = jax.random.normal(jax.random.PRNGKey(1), (k, k)) / k ** 0.5
    w2 = jax.random.normal(jax.random.PRNGKey(2), (k, k)) / k ** 0.5

    def step(x, w1, w2):
        x = fix_sharding(x, "dp", "tp")  # batch over dp, contraction over tp
        w1 = fix_sharding(w1, "tp", None)
        y = x @ w1  # tp-PARTIAL, dp-sharded
        z = -y  # elementwise P-linear link in the chain
        return jnp.sum(z @ w2, axis=1)  # fence only needs the (batch,) sums

    def total_bytes(summary):
        return sum(b for _, b in summary.values())

    saved = edconfig.enable_partial_pools
    try:
        edconfig.enable_partial_pools = False
        r0 = easydist_compile(step, mesh=mesh, state_io={}) \
            .get_compiled(x, w1, w2)
        base = collective_summary(r0.executable().as_text())

        edconfig.enable_partial_pools = True
        r1 = easydist_compile(step, mesh=mesh, state_io={}) \
            .get_compiled(x, w1, w2)
        part = collective_summary(r1.executable().as_text())
    finally:
        edconfig.enable_partial_pools = saved

    assert total_bytes(part) < total_bytes(base), (part, base)
    np.testing.assert_allclose(np.asarray(r0.tree_jitted(x, w1, w2)),
                               np.asarray(r1.tree_jitted(x, w1, w2)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.world_8
def test_partial_region_psum_scatter_fence(cpu_devices):
    """A fence whose consumers all want S(dim) pays psum_scatter (half the
    all_reduce wire bytes) and exits sharded — exactness against the
    unsharded program."""
    import numpy as np

    from easydist_tpu.jaxfront.inline import inline_calls
    from easydist_tpu.jaxfront.partial_regions import (PartialRegion,
                                                       emit_region)

    mesh = make_device_mesh((8,), ("tp",), devices=cpu_devices)
    k = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (16, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, 32))

    def chain(x, w):
        y = x @ w
        return y * 2.0

    closed = inline_calls(jax.make_jaxpr(chain)(x, w))
    jaxpr = closed.jaxpr
    dot_eqn = next(i for i, e in enumerate(jaxpr.eqns)
                   if e.primitive.name == "dot_general")
    mul_eqn = next(i for i, e in enumerate(jaxpr.eqns)
                   if e.primitive.name == "mul")
    region = PartialRegion(start=dot_eqn, end=mul_eqn, axis_idx=0,
                           axis_name="tp")
    xv, wv = jaxpr.eqns[dot_eqn].invars[0], jaxpr.eqns[dot_eqn].invars[1]
    region.source_specs = {xv: {1: "tp"}, wv: {0: "tp"}}  # contracted dims
    out_var = jaxpr.eqns[mul_eqn].outvars[0]
    region.fence_partial = {out_var}
    region.fence_scatter = {out_var: 0}  # consumers want row shards

    def run(x, w):
        env = {xv: x, wv: w}
        emit_region(region, jaxpr, env, mesh)
        return env[out_var]

    jitted = jax.jit(run)
    got = np.asarray(jitted(x, w))
    want = np.asarray(chain(x, w))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    hlo = jitted.lower(x, w).compile().as_text()
    assert "reduce-scatter" in hlo, "fence did not lower to reduce-scatter"
    assert "all-reduce" not in hlo


@pytest.mark.world_8
@pytest.mark.long_duration
def test_solver_chooses_sequence_parallel_attention(cpu_devices):
    """VERDICT r3 #3 gate: with the solver-visible attention composite
    (attention="auto"), a long-sequence model on an sp axis must (a) have
    the ILP CHOOSE a sequence-parallel variant (ring/Ulysses — priced
    ppermute/all_to_all intrinsic vs compute saving), and (b) emit a
    program moving far fewer collective bytes than the einsum path's
    gather-KV sequence parallelism (measured r4: 8.5MB vs 276MB)."""
    from easydist_tpu.models.gpt import GPTConfig as _Cfg

    # heads (4) < axis (8): head-sharding cannot cover the axis, the
    # regime where sequence parallelism is actually needed (with heads >=
    # axis the solver rightly picks free head-sharding instead)
    mesh = make_device_mesh((8,), ("sp",), devices=cpu_devices)
    kw = dict(vocab=256, seq=8192, dim=64, heads=4, layers=1)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, kw["seq"]), 0, 256)

    bytes_by = {}
    res_auto = None
    for attn in ("einsum", "auto"):
        cfg = _Cfg(**kw, attention=attn)
        step, init_state = make_gpt_train_step(cfg)
        state = init_state(jax.random.PRNGKey(0))
        res = easydist_compile(step, mesh=mesh, compile_only=True)(
            state, tok, tok)
        bytes_by[attn] = total_collective_bytes(
            collective_summary(res.executable().as_text()))
        if attn == "auto":
            res_auto = res

    # (a) the solver picked a seq-parallel variant for the attention eqns
    attn_names = {n.name for n in res_auto.graph.ops
                  if n.op_key.startswith("ed_attention")}
    variants = [s.meta.get("variant")
                for chosen in res_auto.strategies
                for name, s in chosen.items()
                if name in attn_names and getattr(s, "meta", None)]
    assert variants, "no attention eqn carries a seq-parallel variant"
    assert set(variants) <= {"ring", "ulysses"}, variants
    # (b) half the bytes of the gather-KV plan, with huge margin
    assert bytes_by["auto"] * 2 < bytes_by["einsum"], bytes_by
