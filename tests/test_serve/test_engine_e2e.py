"""ServeEngine end-to-end over an easydist-compiled GPT inference function
on the 8-device virtual CPU mesh (the ISSUE-1 acceptance scenario):
concurrent clients with variable-length requests get results bitwise
identical to unbatched execution, the executable cache compiles one
program per distinct bucket, and deadlines surface timeouts."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
from easydist_tpu.models.gpt import GPTConfig, gpt_apply, gpt_init
from easydist_tpu.serve import DeadlineExceededError, ServeConfig, ServeEngine

SEQ_BUCKET = 16
BATCH_BUCKET = 4
N_CLIENTS = 6
REQS_PER_CLIENT = 3


@pytest.fixture(scope="module")
def gpt_serving(cpu_devices):
    """(engine, compiled backend, params, cfg) — one compile per module."""
    cfg = GPTConfig.tiny()
    params = gpt_init(cfg, jax.random.PRNGKey(0))
    mesh = make_device_mesh((8,), ("d",))

    def infer(p, tokens):
        return gpt_apply(p, cfg, tokens)

    compiled = easydist_compile(infer, mesh=mesh, state_io={})
    engine = ServeEngine(
        compiled,
        ServeConfig(batch_buckets=(BATCH_BUCKET,),
                    seq_buckets=(SEQ_BUCKET,), max_wait_ms=10.0,
                    max_queue=64, pad_value=0),
        state=params)
    engine.warmup((np.zeros((SEQ_BUCKET,), np.int32),))
    with engine:
        yield engine, compiled, params, cfg


@pytest.mark.world_8
def test_concurrent_variable_length_bitwise_vs_unbatched(gpt_serving):
    engine, compiled, params, cfg = gpt_serving
    rng = np.random.RandomState(7)
    cases = []  # (tokens, future)
    lock = threading.Lock()
    errors = []

    def client(cid):
        r = np.random.RandomState(100 + cid)
        try:
            for _ in range(REQS_PER_CLIENT):
                n = int(r.randint(4, SEQ_BUCKET + 1))
                toks = r.randint(0, cfg.vocab, (n,)).astype(np.int32)
                fut = engine.submit(toks)
                with lock:
                    cases.append((toks, fut))
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    assert len(cases) == N_CLIENTS * REQS_PER_CLIENT

    # unbatched reference: the SAME compiled inference fn, one request per
    # call, padded to the same seq bucket (causal attention makes the
    # padded tail invisible to the prefix).  Bitwise against the request
    # alone in the serving bucket's executable (whoever shared its batch
    # must not show); against a batch-of-one call, which is another
    # executable whose XLA:CPU reductions run in another order (1.8e-7
    # under jax 0.9), to float32 rounding.
    for toks, fut in cases:
        got = fut.result(timeout=120)
        padded = np.zeros((BATCH_BUCKET, SEQ_BUCKET), np.int32)
        padded[0, : len(toks)] = toks
        alone = np.asarray(compiled(params, jnp.asarray(padded)))
        assert got.shape == alone[0, : len(toks)].shape
        np.testing.assert_array_equal(got, alone[0, : len(toks)])  # bitwise
        one = np.asarray(compiled(params, jnp.asarray(padded[:1])))
        np.testing.assert_allclose(got, one[0, : len(toks)], rtol=0,
                                   atol=2e-6)

    stats = engine.stats()
    # one distinct bucket (batch 4 x seq 16) -> exactly one executable,
    # warmed before traffic, so every served batch was a cache hit
    assert stats["distinct_executables"] == 1
    assert stats["compile_cache_hit_rate"] > 0
    assert engine.metrics.counter("compile_cache_misses") == 1
    assert engine.metrics.counter("requests_completed") == len(cases)
    assert engine.metrics.counter("requests_failed") == 0
    occ = stats["batch_occupancy"]
    assert occ is not None and 0.0 < occ <= 1.0
    lat = stats["latency"]["e2e"]
    assert lat["count"] == len(cases) and lat["p99_s"] >= lat["p50_s"]


@pytest.mark.world_8
def test_backend_signature_cache_one_entry_per_bucket(gpt_serving):
    engine, compiled, params, cfg = gpt_serving
    # the jaxfront compile cache holds one CompileResult per bucket
    # signature (plus the unbatched-reference signature from the test
    # above); bucket traffic never recompiles
    bstats = compiled.cache_stats()
    assert bstats["size"] <= 2
    assert bstats["hits"] > 0


@pytest.mark.world_8
def test_deadline_exceeded_surfaces_not_hangs(gpt_serving):
    engine, compiled, params, cfg = gpt_serving
    toks = np.zeros((8,), np.int32)
    fut = engine.submit(toks, deadline_ms=0.0)  # expired on arrival
    with pytest.raises(DeadlineExceededError):
        fut.result(timeout=30)
    # the engine keeps serving afterwards
    out = engine.infer(toks, timeout=60)
    assert out.shape == (8, cfg.vocab)
