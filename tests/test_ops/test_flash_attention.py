"""Pallas flash attention vs reference attention (interpret mode on CPU)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu.ops import flash_attention
from easydist_tpu.ops.flash_attention import _reference_attention


def _fa():
    import importlib

    return importlib.import_module("easydist_tpu.ops.flash_attention")


@pytest.fixture
def vmem_budget(monkeypatch):
    """`vmem_budget(n)` sets `_TRAIN_VMEM_BUDGET` for the test (0: nothing
    is held whole, every block streams); the calls the module keeps a
    signature are dropped on the way in and on the way out."""
    fa = _fa()

    def clear():
        fa._forward_call.cache_clear()
        fa._backward_calls.cache_clear()

    def set_budget(budget):
        monkeypatch.setattr(fa, "_TRAIN_VMEM_BUDGET", budget)
        clear()

    yield set_budget
    monkeypatch.undo()
    clear()


def make_qkv(key, b=2, h=3, t=64, d=32):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (b, h, t, d)),
            jax.random.normal(k2, (b, h, t, d)),
            jax.random.normal(k3, (b, h, t, d)))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = make_qkv(jax.random.PRNGKey(0))
    got = flash_attention(q, k, v, causal, None, 16, 16, True)
    scale = 1.0 / math.sqrt(q.shape[-1])
    want = _reference_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flash_uneven_blocks():
    # seq not divisible by requested block: block auto-shrinks
    q, k, v = make_qkv(jax.random.PRNGKey(1), t=48)
    got = flash_attention(q, k, v, True, None, 32, 32, True)
    want = _reference_attention(q, k, v, True, 1.0 / math.sqrt(32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.long_duration
def test_flash_gradients():
    q, k, v = make_qkv(jax.random.PRNGKey(2), t=32, d=16)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, True, None, 16, 16, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.mean(_reference_attention(q, k, v, True,
                                             1.0 / math.sqrt(16)) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.long_duration
def test_flash_fused_backward_matches_reference(causal):
    """The Pallas backward kernels (dq + dkdv, lse/delta recompute) must
    reproduce einsum-attention gradients, including uneven tail blocks."""
    q, k, v = make_qkv(jax.random.PRNGKey(5), t=48, d=16)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal, None, 16, 16, True)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = _reference_attention(q, k, v, causal, 1.0 / math.sqrt(16))
        return jnp.sum(out * jnp.cos(out))

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_backward_has_no_quadratic_residual():
    """O(T) training memory: no [T, T] tensor may appear anywhere in the
    differentiated program (VERDICT r1 #10 — the old backward rebuilt the
    full score matrix in plain jax)."""
    t = 64
    q, k, v = make_qkv(jax.random.PRNGKey(6), t=t, d=16)

    def loss(q, k, v):
        return jnp.mean(flash_attention(q, k, v, True, None, 16, 16, True)
                        ** 2)

    def scan_jaxpr(jaxpr, found):
        for eqn in jaxpr.eqns:
            for v_ in eqn.outvars:
                shape = tuple(getattr(v_.aval, "shape", ()))
                if len(shape) >= 2 and shape[-1] == t and shape[-2] == t:
                    found.append((eqn.primitive.name, shape))
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    scan_jaxpr(sub.jaxpr, found)
                elif hasattr(sub, "eqns"):
                    scan_jaxpr(sub, found)
        return found

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    found = scan_jaxpr(closed.jaxpr, [])
    assert not found, f"quadratic intermediates: {found}"


@pytest.mark.long_duration
def test_flash_long_context_streams_kv():
    """Long-context exactness (VERDICT r2 #6): with K/V streamed through the
    grid, a 4k sequence runs with the same per-program VMEM as a 256-token
    one.  Interpret mode; blocks 512 keep the grid small enough for CI."""
    q, k, v = make_qkv(jax.random.PRNGKey(7), b=1, h=1, t=4096, d=16)

    got = flash_attention(q, k, v, True, None, 512, 512, True)
    want = _reference_attention(q, k, v, True, 1.0 / math.sqrt(16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.long_duration
def test_flash_vmem_budget_seq_independent(monkeypatch, vmem_budget):
    """Per-program VMEM residency must not grow with sequence length and
    must stay under the ~16 MiB TPU VMEM budget at seq 32k (the regime
    flash exists for).  Asserts on the ACTUAL BlockSpec/scratch shapes each
    pallas_call receives — a kernel regressing to whole-sequence residency
    fails here even if the analytic estimate is stale."""
    import importlib

    fa = importlib.import_module("easydist_tpu.ops.flash_attention")

    calls = []
    orig = fa.pl.pallas_call

    def spy(kernel, **kw):
        specs = list(kw.get("in_specs", []))
        outs = kw.get("out_specs")
        specs += list(outs) if isinstance(outs, (list, tuple)) else [outs]
        block_bytes = sum(
            4 * int(np.prod([b for b in s.block_shape if b is not None]))
            for s in specs)
        scratch_bytes = sum(4 * int(np.prod(sh.shape))
                            for sh in kw.get("scratch_shapes", []))
        calls.append(block_bytes + scratch_bytes)
        return orig(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    # rows this short would be held whole (`_step_shape`): the guarantee is
    # the STREAMED form's, which is what a row too long to hold gets
    vmem_budget(0)

    def run(t):
        q, k, v = make_qkv(jax.random.PRNGKey(8), b=1, h=1, t=t, d=16)
        jax.grad(lambda q, k, v: jnp.mean(
            fa.flash_attention(q, k, v, True, None, 128, 128, True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        total = max(calls)
        calls.clear()
        return total

    at_short, at_long = run(256), run(2048)
    assert at_long == at_short, (
        f"per-program VMEM grew with sequence: {at_short} -> {at_long}")

    from easydist_tpu.ops.flash_attention import estimate_vmem_bytes
    assert estimate_vmem_bytes(32768, 32768, 64) < 16 * 2**20
    vmem_budget(8 * 2**20)
    assert estimate_vmem_bytes(32768, 32768, 64) < 16 * 2**20
    assert estimate_vmem_bytes(32768, 32768, 128, dtype=jnp.bfloat16) \
        < 16 * 2**20


# ------------------------------------------- operands as stored, walked rows

def _qkv(key, shape_q, shape_k, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, shape_q).astype(dtype),
            jax.random.normal(k2, shape_k).astype(dtype),
            jax.random.normal(k3, shape_k).astype(dtype))


def _losses(causal, bq, bk, with_lse):
    """(flash loss, float32 reference loss) of one scalar that weighs the
    output and, `with_lse`, the logsumexp too (ring attention's use)."""
    fa = _fa()

    def weigh(out, lse):
        out = out.astype(jnp.float32)
        loss = jnp.sum(out * jnp.cos(out))
        return loss + jnp.sum(jnp.sin(lse)) if with_lse else loss

    def flash(q, k, v):
        out, lse = fa.flash_attention_lse(q, k, v, causal, None, bq, bk,
                                          True)
        return weigh(out, lse.reshape(q.shape[:3]))

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
        out = _reference_attention(q, k, v, causal, scale)
        return weigh(out, jax.nn.logsumexp(s, axis=-1))

    return flash, reference


def _assert_bf16_close(got, want, what):
    """Within bf16's rounding of the reference's largest value (operands,
    `p`, `ds` and the results are each rounded to 8 bits of mantissa)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6)
    assert err < 2e-2, f"{what}: {err:.2e} of the reference's largest value"


# the three training kernels, and the products a block step of each makes
PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# (t, blocks, budget): 256 and 1,024 positions held whole, the same 1,024
# with nothing held (every block streamed through the grid), and with room
# for half a row (two grid steps of two blocks on the walked side)
BF16_WALKS = [pytest.param(256, 128, None, id="t256-resident"),
              pytest.param(1024, 256, None, id="t1024-resident"),
              pytest.param(1024, 256, 0, id="t1024-streamed"),
              pytest.param(1024, 256, 0.5, id="t1024-two-blocks-a-step")]


@pytest.mark.parametrize("t,block,budget", BF16_WALKS)
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_bf16_operands_match_float32_reference(vmem_budget, causal, d,
                                                     t, block, budget):
    """bf16 operands reach the MXU as stored; the forward and all three
    gradients stay within bf16's rounding of the float32 reference."""
    fa = _fa()
    if budget == 0.5:   # what half a row takes the kernel that holds most
        budget = fa._train_vmem_bytes("flash_bwd_dkv", block, block, t // 2,
                                      d, jnp.bfloat16)
    if budget is not None:
        vmem_budget(budget)
    _, held = fa._step_shape("flash_bwd_dkv", block, block, t, t, d,
                             jnp.bfloat16)
    assert held == (t if budget is None else t // 2 if budget else block)
    q, k, v = _qkv(jax.random.PRNGKey(t + d), (1, 2, t, d), (1, 2, t, d),
                   jnp.bfloat16)
    flash, reference = _losses(causal, block, block, with_lse=False)
    out = fa.flash_attention(q, k, v, causal, None, block, block, True)
    assert out.dtype == jnp.bfloat16
    _assert_bf16_close(out, _reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal,
        1.0 / math.sqrt(d)), "out")
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(reference, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.bfloat16
        _assert_bf16_close(a, b, f"d{name}")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_lse_gradient_and_uneven_lengths(causal, dtype):
    """Ring attention's use: a loss through `lse` too, t_q != t_k, blocks
    of unequal size (the diagonal then crosses two K blocks a Q block)."""
    fa = _fa()
    q, k, v = _qkv(jax.random.PRNGKey(11), (1, 2, 64, 32), (1, 2, 128, 32),
                   dtype)
    flash, reference = _losses(causal, 32, 16, with_lse=True)
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(reference, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        if dtype == jnp.bfloat16:
            _assert_bf16_close(a, b, f"d{name}")
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
    out, lse = fa.flash_attention_lse(q, k, v, causal, None, 32, 16, True)
    assert lse.shape == (2, 64) and lse.dtype == jnp.float32


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_resident_and_streamed_walks_agree(vmem_budget, causal):
    """One body, two geometries: a row's other side held whole and walked
    by the loop, or streamed a block a grid step — the same block steps in
    the same order, so float32 results agree to rounding."""
    fa = _fa()
    q, k, v = _qkv(jax.random.PRNGKey(12), (1, 2, 96, 16), (1, 2, 64, 16),
                   jnp.float32)
    flash, _ = _losses(causal, 32, 16, with_lse=True)
    grads = jax.grad(flash, argnums=(0, 1, 2))
    resident = grads(q, k, v)
    vmem_budget(0)
    streamed = grads(q, k, v)
    for a, b in zip(resident, streamed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


@functools.lru_cache(maxsize=None)
def _kernel_jaxprs(dtype, causal=True, t=64, block=16, d=16):
    """{kernel name: its body's jaxpr} of the three training kernels."""
    from easydist_tpu.analyze.jaxpr_rules import _sub_jaxprs

    fa = _fa()
    x = jax.ShapeDtypeStruct((1, 2, t, d), dtype)
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn.params["jaxpr"]
            for _, sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal, None, block, block, True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x).jaxpr)
    assert sorted(found) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    return found


def _eqns(jaxpr, name=None):
    """Every equation of `jaxpr` and of the jaxprs nested in it."""
    from easydist_tpu.analyze.jaxpr_rules import _sub_jaxprs

    for eqn in jaxpr.eqns:
        if name is None or eqn.primitive.name == name:
            yield eqn
        for _, sub in _sub_jaxprs(eqn):
            yield from _eqns(sub, name)


# `_kernel_jaxprs`' two geometries, blocks of 16: 64 positions are 4 blocks
# a side, all in ONE grid step, so the walks' bounds are static and the
# body holds its n (n + 1) / 2 = 10 block steps unrolled; 128 positions
# are 8 blocks, 4 a grid step, so the bounds hang on the grid position and
# each of a step's 4 own blocks has a loop under the diagonal and one on it
STATIC, BY_POSITION = 64, 128


@pytest.mark.parametrize("kernel", sorted(PRODUCTS))
def test_flash_products_take_operands_as_stored(kernel):
    """With bf16 operands every product of the three bodies has bf16
    operands and a float32 result; nothing read from a ref is widened on
    its way to the MXU.  With float32 operands nothing is narrowed: no
    bf16 value exists in the body at all."""
    for t, steps in ((STATIC, 10), (BY_POSITION, 4 * 2)):
        body = _kernel_jaxprs(jnp.bfloat16, t=t)[kernel]
        dots = list(_eqns(body, "dot_general"))
        assert len(dots) == steps * PRODUCTS[kernel]
        for eqn in dots:
            assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
            assert eqn.outvars[0].aval.dtype == jnp.float32
        widened = [eqn for eqn in _eqns(body, "convert_element_type")
                   if eqn.invars[0].aval.dtype == jnp.bfloat16]
        assert not widened, widened

    body = _kernel_jaxprs(jnp.float32)[kernel]
    for eqn in _eqns(body):
        assert all(getattr(v.aval, "dtype", None) != jnp.bfloat16
                   for v in list(eqn.invars) + list(eqn.outvars)), eqn


@pytest.mark.parametrize("kernel", sorted(PRODUCTS))
def test_flash_scale_goes_where_it_is_exact(kernel):
    """float32 operands take the scale BEFORE the scores' product, as the
    kernels always did (float32 callers read what they read: checked
    against the parent commit by hand, CHANGES.md PR 47), and so do bf16
    operands when the scale is a power of two (heads of 16: 1/4), which
    moves no mantissa bit; at any other scale (heads of 32) bf16 operands
    go to the MXU as stored and the scale multiplies the float32 scores."""
    def scores_scaled(dtype, d):
        """Per block step: is the scores' product (the first of the step)
        multiplied before anything else reads it?"""
        body = _kernel_jaxprs(dtype, d=d)[kernel]
        readers = {}
        for eqn in _eqns(body):
            for v in eqn.invars:
                if not hasattr(v, "val"):   # a literal has no readers
                    readers.setdefault(v, []).append(eqn.primitive.name)
        dots = list(_eqns(body, "dot_general"))[::PRODUCTS[kernel]]
        return {readers[eqn.outvars[0]] == ["mul"] for eqn in dots}

    assert scores_scaled(jnp.float32, 32) == {False}
    assert scores_scaled(jnp.bfloat16, 16) == {False}
    assert scores_scaled(jnp.bfloat16, 32) == {True}


@pytest.mark.parametrize("kernel", sorted(PRODUCTS))
def test_flash_mask_is_built_on_the_diagonal_blocks_alone(kernel):
    """The mask (two iotas, a compare, a select) is built on the block
    steps the diagonal crosses and on no other: n of a static row's
    n (n + 1) / 2 steps; the loop on the diagonal and not the loop under
    it where the bounds hang on the grid position; nowhere in a full
    (not causal) body."""
    def iotas(jaxpr):
        return len(list(_eqns(jaxpr, "iota")))

    body = _kernel_jaxprs(jnp.bfloat16, t=STATIC)[kernel]
    assert not [e for e in body.eqns if e.primitive.name in ("while", "scan")]
    assert iotas(body) == 2 * 4

    body = _kernel_jaxprs(jnp.bfloat16, t=BY_POSITION)[kernel]
    loops = [e for e in body.eqns if e.primitive.name == "while"]
    assert len(loops) == 2 * 4
    masked = [sum(iotas(sub.jaxpr) for sub in e.params.values()
                  if hasattr(sub, "jaxpr")) for e in loops]
    assert sorted(masked) == [0] * 4 + [2] * 4 and iotas(body) == 2 * 4

    for t in (STATIC, BY_POSITION):
        assert iotas(_kernel_jaxprs(jnp.bfloat16, False, t=t)[kernel]) == 0


@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
@pytest.mark.parametrize("held", ["resident", "streamed"])
def test_flash_walks_visit_the_live_blocks_once(held, bq, bk):
    """The loops' trip counts over a causal row: every (Q block, K block)
    pair that holds a visible score is visited once and no other, by the
    K walk (forward, dQ) and by the Q walk (dK/dV) alike — n (n + 1) / 2
    of n^2 at equal blocks — and the mask is built on the pairs the
    diagonal crosses, n of them at equal blocks."""
    fa = _fa()
    t = 96
    n_q, n_k = t // bq, t // bk
    live = {(qi, ki) for qi in range(n_q) for ki in range(n_k)
            if ki * bk <= (qi + 1) * bq - 1}
    crossed = {(qi, ki) for qi, ki in live
               if (ki + 1) * bk - 1 > qi * bq}

    def visits(walk, own, n_own, n_other):
        sub = n_other if held == "resident" else 1
        seen = []
        for i in range(n_own):
            for step in range(n_other // sub):
                for lo, hi, masked in walk(True, i, step * sub, sub, bq, bk):
                    seen += [(own(i, step * sub + j), masked)
                             for j in range(int(lo), int(hi))]
        return seen

    for seen in (visits(fa._k_walk, lambda qi, ki: (qi, ki), n_q, n_k),
                 visits(fa._q_walk, lambda ki, qi: (qi, ki), n_k, n_q)):
        assert len(seen) == len(live) and {p for p, _ in seen} == live
        assert {p for p, masked in seen if masked} == crossed
    if bq == bk:
        assert len(live) == n_q * (n_q + 1) // 2 and len(crossed) == n_q


def test_estimate_vmem_bytes_knows_the_dtype_and_the_resident_row():
    """The estimate is of the kernels as they are built: a row held whole
    where that fits `_TRAIN_VMEM_BUDGET` (the train cell: 1,024 x 64 bf16,
    four own blocks a grid step), as many of its blocks as fit beyond —
    the same however long the row; bf16 blocks are half of float32's."""
    fa = _fa()
    cell = fa.estimate_vmem_bytes(1024, 1024, 64, dtype=jnp.bfloat16)
    assert all(fa._step_shape(k, 256, 256, 1024, 1024, 64, jnp.bfloat16)
               == (4, 1024) for k in PRODUCTS)
    # K and V of a row, 1,024 x 128 lanes x 2 B each, double-buffered: 1 MiB
    assert 2 ** 20 < cell <= fa._TRAIN_VMEM_BUDGET
    assert fa.estimate_vmem_bytes(1024, 1024, 64, dtype=jnp.float32) > cell
    long = [fa.estimate_vmem_bytes(t, t, 128, dtype=jnp.bfloat16)
            for t in (8192, 16384, 32768)]
    assert all(fa._step_shape(k, 256, 256, t, t, 128, jnp.bfloat16)
               == (1, 4096) for k in PRODUCTS for t in (8192, 32768))
    assert cell < long[0] == long[1] == long[2] <= fa._TRAIN_VMEM_BUDGET
    # each side by its own length: long keys stream while short queries
    # are held whole by the dK/dV kernel
    assert fa._step_shape("flash_fwd", 256, 256, 512, 32768, 128,
                          jnp.bfloat16) == (1, 4096)
    assert fa._step_shape("flash_bwd_dkv", 256, 256, 512, 32768, 128,
                          jnp.bfloat16) == (4, 512)


def test_flash_train_calls_counter():
    """`flash_train_calls{kernel, kv, operands}`: one a traced call."""
    from easydist_tpu.runtime import spans

    fa = _fa()
    x = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.bfloat16)
    spans.clear()
    jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, True, None, 16, 16, True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(x, x, x)
    counted = {k: v for k, v in spans.snapshot()["counters"].items()
               if k.startswith("flash_train_calls{")}
    assert counted == {
        f"flash_train_calls{{kernel={k},kv=resident,operands=bfloat16}}": 1
        for k in PRODUCTS}
