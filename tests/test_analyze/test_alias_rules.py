"""Layer 11 donation/aliasing goldens: ALIAS001-004 each fire exactly
once on a seeded known-bad fixture (jaxpr use-after-donate, double
donation, unhonorable state pair, host-held donated buffer), the AST
host lint flags a retained reference and accepts the rebind idiom, and
the real artifacts — an auto-solved preset compile, the serving
session, the repo's own host code — produce zero false positives."""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from easydist_tpu import config as edconfig
from easydist_tpu.analyze import (audit_donation_pairs,
                                  audit_host_aliases,
                                  audit_jaxpr_donation,
                                  check_donation_pairs,
                                  check_host_aliases,
                                  lint_file_donation,
                                  lint_host_donation)
from easydist_tpu.analyze.findings import AnalysisError

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _rule_ids(findings):
    return [f.rule_id for f in findings]


# ---------------------------------------------------------- jaxpr pass


class TestJaxprDonation:
    def test_use_after_donate_fires_once(self):
        inner = jax.jit(lambda s: s * 2.0, donate_argnums=0)

        def prog(x):
            y = inner(x)
            return y + x          # x read AFTER its donating dispatch

        jaxpr = jax.make_jaxpr(prog)(jnp.zeros((4,), jnp.float32))
        findings = audit_jaxpr_donation(jaxpr.jaxpr)
        assert _rule_ids(findings) == ["ALIAS001"]

    def test_double_donation_fires_once(self):
        inner = jax.jit(lambda a, b: a + b, donate_argnums=0)

        def prog(x):
            return inner(x, x)    # one buffer at two invar positions

        jaxpr = jax.make_jaxpr(prog)(jnp.zeros((4,), jnp.float32))
        findings = audit_jaxpr_donation(jaxpr.jaxpr)
        assert _rule_ids(findings) == ["ALIAS002"]

    def test_unhonorable_donation_fires_once(self):
        inner = jax.jit(lambda s: jnp.sum(s), donate_argnums=0)

        def prog(x):
            return inner(x)       # scalar out: nothing can alias x

        jaxpr = jax.make_jaxpr(prog)(jnp.zeros((4,), jnp.float32))
        findings = audit_jaxpr_donation(jaxpr.jaxpr)
        assert _rule_ids(findings) == ["ALIAS003"]

    def test_check_unhonored_flag_gates_alias003(self):
        inner = jax.jit(lambda s: jnp.sum(s), donate_argnums=0)
        jaxpr = jax.make_jaxpr(lambda x: inner(x))(
            jnp.zeros((4,), jnp.float32))
        assert audit_jaxpr_donation(jaxpr.jaxpr,
                                    check_unhonored=False) == []

    def test_donate_then_rebind_is_clean(self):
        inner = jax.jit(lambda s: s * 2.0, donate_argnums=0)

        def prog(x):
            y = inner(x)
            return y              # the donated var dies at its dispatch

        jaxpr = jax.make_jaxpr(prog)(jnp.zeros((4,), jnp.float32))
        assert audit_jaxpr_donation(jaxpr.jaxpr) == []


# -------------------------------------------------- CompileResult pass


def _mock_result(pairs, donate, in_sigs, out_sigs):
    avals = [jax.ShapeDtypeStruct(s, d) for s, d in in_sigs]
    outs = [jax.ShapeDtypeStruct(s, d) for s, d in out_sigs]
    return types.SimpleNamespace(
        state_pairs=pairs, donated_invars=donate, in_avals=avals,
        closed_jaxpr=types.SimpleNamespace(out_avals=outs))


class TestDonationPairs:
    SIG = ((8, 4), jnp.float32)

    def test_clean_pair(self):
        r = _mock_result({0: 0}, (0,), [self.SIG], [self.SIG])
        assert audit_donation_pairs(r) == []

    def test_two_outputs_one_donated_input_fires_once(self):
        r = _mock_result({0: 0, 1: 0}, (0,), [self.SIG],
                         [self.SIG, self.SIG])
        assert _rule_ids(audit_donation_pairs(r)) == ["ALIAS002"]

    def test_sig_mismatch_fires_once(self):
        r = _mock_result({0: 0}, (0,), [self.SIG],
                         [((8, 2), jnp.float32)])
        assert _rule_ids(audit_donation_pairs(r)) == ["ALIAS003"]

    def test_out_of_range_pair_fires(self):
        r = _mock_result({5: 0}, (0,), [self.SIG], [self.SIG])
        assert _rule_ids(audit_donation_pairs(r)) == ["ALIAS003"]

    def test_undonated_pairs_are_free(self):
        # mismatch on a NON-donated input is not a donation hazard
        r = _mock_result({0: 0}, (), [self.SIG],
                         [((8, 2), jnp.float32)])
        assert audit_donation_pairs(r) == []

    def test_hook_raises_and_demotes(self, monkeypatch):
        r = _mock_result({0: 0, 1: 0}, (0,), [self.SIG],
                         [self.SIG, self.SIG])
        monkeypatch.setattr(edconfig, "analyze_raise", True)
        with pytest.raises(AnalysisError, match="ALIAS002"):
            check_donation_pairs(r)
        monkeypatch.setattr(edconfig, "analyze_raise", False)
        assert _rule_ids(check_donation_pairs(r)) == ["ALIAS002"]


# ------------------------------------------------------ host-alias pass


class TestHostAliases:
    def test_shared_array_fires_once_per_holder(self):
        arr = np.zeros((4, 4), np.float32)
        findings = audit_host_aliases(
            {"cache": {"k": arr}},
            {"snapshot": [arr], "trie": [arr]})
        assert sorted(_rule_ids(findings)) == ["ALIAS004", "ALIAS004"]
        assert {f.node for f in findings} == {"session"}

    def test_copies_are_clean(self):
        arr = np.zeros((4, 4), np.float32)
        assert audit_host_aliases({"cache": arr},
                                  {"snapshot": [arr.copy()]}) == []

    def test_non_array_leaves_ignored(self):
        # interned ints / page-id dicts must not identity-collide
        assert audit_host_aliases({"arena": {"ids": 7}},
                                  {"trie": [{"page": 7}]}) == []

    def test_hook_raises_and_demotes(self, monkeypatch):
        arr = np.zeros((2,), np.float32)
        monkeypatch.setattr(edconfig, "analyze_raise", True)
        with pytest.raises(AnalysisError, match="ALIAS004"):
            check_host_aliases({"cache": arr}, {"snapshot": arr})
        monkeypatch.setattr(edconfig, "analyze_raise", False)
        assert _rule_ids(check_host_aliases(
            {"cache": arr}, {"snapshot": arr})) == ["ALIAS004"]


# ------------------------------------------------------- AST host lint


def _lint_src(src):
    return lint_file_donation("mem.py", rel="mem.py", source=src)


class TestHostLint:
    def test_retained_reference_fires_once_with_location(self):
        src = (
            "def step(self, pool):\n"
            "    tok = self._decode_c(pool.cache, 3)\n"
            "    return export(pool.cache)\n")
        findings = _lint_src(src)
        assert _rule_ids(findings) == ["ALIAS001"]
        assert findings[0].path == "mem.py"
        assert findings[0].line == 3

    def test_rebind_idiom_is_clean(self):
        src = (
            "def step(self, pool):\n"
            "    pool.cache, tok = self._decode_c(pool.cache, 3)\n"
            "    return export(pool.cache)\n")
        assert _lint_src(src) == []

    def test_compile_bound_name_donates(self):
        src = (
            "def run(state):\n"
            "    runner = easydist_compile(step, mesh=mesh)\n"
            "    out = runner(state)\n"
            "    return state\n")
        assert _rule_ids(_lint_src(src)) == ["ALIAS001"]

    def test_factory_call_donates(self):
        src = (
            "def flush(self, pool):\n"
            "    out = self._paged_c('export')(pool.arena, idx)\n"
            "    return pool.arena\n")
        assert _rule_ids(_lint_src(src)) == ["ALIAS001"]

    def test_multiline_call_args_not_stale(self):
        # args on the call's own continuation lines ARE the call
        src = (
            "def flush(self, pool):\n"
            "    pool.arena = self._paged_c('export')(\n"
            "        pool.arena, idx)\n"
            "    return 1\n")
        assert _lint_src(src) == []

    def test_nested_scopes_independent(self):
        # the load lives in a DIFFERENT scope: no scope-local hazard
        src = (
            "def outer(self, pool):\n"
            "    tok = self._decode_c(pool.cache, 3)\n"
            "    def inner(pool):\n"
            "        return pool.cache\n"
            "    return inner\n")
        assert _lint_src(src) == []

    def test_syntax_error_returns_empty(self):
        assert _lint_src("def broken(:\n") == []

    def test_repo_host_code_is_clean(self):
        # the acceptance gate: the shipped package + examples carry no
        # retained-donated-reference hazards
        assert lint_host_donation(REPO) == []


# --------------------------------------------- zero FP on real artifacts


class TestRealArtifactsClean:
    def test_preset_compile_no_alias_findings(self):
        from easydist_tpu.jaxfront import easydist_compile, make_device_mesh
        from easydist_tpu.models import mlp_apply, mlp_init

        mesh = make_device_mesh((4, 2), ("dp", "tp"))
        params = mlp_init(jax.random.PRNGKey(0), sizes=(64, 128, 64))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
        y = jax.random.normal(jax.random.PRNGKey(2), (64, 64))

        def step(p, xb, yb):
            def loss_fn(p):
                return jnp.mean((mlp_apply(p, xb) - yb) ** 2)
            loss, grads = jax.value_and_grad(loss_fn)(p)
            return jax.tree_util.tree_map(
                lambda a, g: a - 0.05 * g, p, grads), loss

        compiled = easydist_compile(step, mesh=mesh, compile_only=True)
        compiled(params, x, y)
        report = compiled.analyze(raise_on_error=False, export=False)
        alias = [f for f in report.findings
                 if f.rule_id.startswith("ALIAS")]
        assert alias == []

    def test_session_host_aliases_clean(self):
        from easydist_tpu.models import gpt
        from easydist_tpu.serve import (GenerationSession, ServeConfig)

        cfg = gpt.GPTConfig.tiny()
        params = gpt.gpt_init(cfg, jax.random.PRNGKey(0))
        # max_decode_slots/buckets match the other serve tests' sessions
        # so the process memo shares ONE set of compiled programs
        sc = ServeConfig(decode_buckets=(32,), max_decode_slots=2,
                         prefill_chunk=8, prefill_batch=2)
        sess = GenerationSession.for_gpt(params, cfg, config=sc)
        for p in ([1, 2, 3], list(range(1, 12))):
            sess.submit(p, max_new_tokens=4)
        # the first-decode audit path runs check_host_aliases itself
        # (analyze_raise on by default in tests): draining clean IS the
        # zero-false-positive assertion
        sess.run_until_drained()
        pool = next(iter(sess._pools.values()))
        if pool.trie is not None:
            holders = {"trie": [n.kv for n in pool.trie._walk()]}
            assert audit_host_aliases({"arena": pool.arena},
                                      holders) == []
