"""Per-site approximation policy API: rules, segmentation, dispatch, models."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import policy as P
from repro.configs import get_config
from repro.core import Backend, DaismConfig, Variant
from repro.models.registry import build_model

RNG = jax.random.PRNGKey(0)
PC3_TR = DaismConfig(variant=Variant.PC3_TR, backend=Backend.JNP)
FLA = DaismConfig(variant=Variant.FLA, backend=Backend.JNP)




# ---------------------------------------------------------------------------
# Rule precedence / parsing
# ---------------------------------------------------------------------------

def test_first_match_wins_and_default_fallback():
    pol = P.ApproxPolicy(rules=(
        P.Rule("*/attn/*", P.EXACT),
        P.Rule("*/attn/wq", FLA),      # shadowed: the broader rule is first
        P.Rule("*/ffn/*", FLA),
    ), default=PC3_TR)
    assert pol.resolve("decoder/layer_0/attn/wq") is P.EXACT
    assert pol.resolve("decoder/layer_1/ffn/wi") is FLA
    assert pol.resolve("decoder/lm_head") is PC3_TR  # no rule -> default


def test_kind_pattern_and_kind_restriction():
    pol = P.ApproxPolicy(rules=(
        P.Rule("@lm_head", P.EXACT),
        P.Rule("*", FLA, kind=P.OpKind.CONV),
    ), default=PC3_TR)
    assert pol.resolve("decoder/lm_head", P.OpKind.LM_HEAD) is P.EXACT
    # same path, different kind: the @ rule must not fire
    assert pol.resolve("decoder/lm_head", P.OpKind.DENSE) is PC3_TR
    assert pol.resolve("cnn/c1", P.OpKind.CONV) is FLA
    assert pol.resolve("cnn/f1", P.OpKind.DENSE) is PC3_TR


def test_parse_policy_spec():
    pol = P.parse_policy("*/attn/*=exact,*/ffn/*=pc3_tr:pallas,*=fla")
    assert pol.resolve("x/attn/wq").exact
    ffn = pol.resolve("x/ffn/wi")
    assert ffn.variant is Variant.PC3_TR and ffn.backend is Backend.PALLAS
    assert pol.resolve("anything/else").variant is Variant.FLA
    with pytest.raises(ValueError, match="unknown variant"):
        P.parse_policy("*=bogus")
    for backend in ("bogus", "lut"):
        with pytest.raises(ValueError, match="unknown backend"):
            P.parse_policy(f"*=fla:{backend}")
    with pytest.raises(ValueError, match="pattern=variant"):
        P.parse_policy("justapattern")


def test_parse_policy_catch_all_is_ordered():
    """A '*=' entry is a regular rule: written first it shadows later
    rules; 'default=' sets the fallback without entering the rule order."""
    pol = P.parse_policy("*=exact,*/ffn/*=pc3_tr")
    assert pol.resolve("x/ffn/wi").exact  # '*' fires first
    trailing = P.parse_policy("*/ffn/*=pc3_tr,*=fla")
    assert trailing.resolve("x/ffn/wi").variant is Variant.PC3_TR
    assert trailing.resolve("x/attn/wq").variant is Variant.FLA
    dflt = P.parse_policy("default=fla,*/ffn/*=pc3_tr")
    assert dflt.resolve("x/attn/wq").variant is Variant.FLA
    assert dflt.resolve("x/ffn/wi").variant is Variant.PC3_TR


def test_rule_precedence_property():
    """Property test: resolve() == first matching rule in order, else
    default — over randomized rule lists and paths."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    segs = st.sampled_from(["attn", "ffn", "wq", "wi", "layer_0", "layer_1"])
    path_st = st.lists(segs, min_size=1, max_size=4).map("/".join)
    pattern_st = st.one_of(
        path_st,
        st.lists(st.sampled_from(["*", "attn", "ffn", "layer_0"]),
                 min_size=1, max_size=3).map("/".join))
    cfg_st = st.sampled_from([P.EXACT, PC3_TR, FLA])
    rules_st = st.lists(st.tuples(pattern_st, cfg_st), max_size=5)

    @hyp.given(rules=rules_st, path=path_st)
    @hyp.settings(max_examples=200, deadline=None)
    def check(rules, path):
        pol = P.ApproxPolicy(
            rules=tuple(P.Rule(p, c) for p, c in rules), default=PC3_TR)
        import fnmatch
        expected = PC3_TR
        for p, c in rules:
            if fnmatch.fnmatchcase(path, p):
                expected = c
                break
        assert pol.resolve(path) == expected

    check()


def test_policy_is_jit_static():
    pol = P.ApproxPolicy.first_last_exact(PC3_TR, 4)
    assert hash(pol) == hash(P.ApproxPolicy.first_last_exact(PC3_TR, 4))

    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, policy):
        return x * (0.0 if policy.resolve("a/b").exact else 1.0)

    assert float(f(jnp.ones(()), pol)) == 1.0


# ---------------------------------------------------------------------------
# Segmentation
# ---------------------------------------------------------------------------

def _sites(i):
    return [(f"decoder/layer_{i}/attn/wq", P.OpKind.DENSE),
            (f"decoder/layer_{i}/ffn/wi", P.OpKind.DENSE)]


def test_plan_segments_uniform_single_segment():
    pol = P.ApproxPolicy.uniform(PC3_TR)
    assert P.plan_segments(pol, _sites, 0, 6) == ((0, 6),)


def test_plan_segments_first_last_exact():
    pol = P.ApproxPolicy.first_last_exact(PC3_TR, 6)
    assert P.plan_segments(pol, _sites, 0, 6) == ((0, 1), (1, 5), (5, 6))


def test_plan_segments_depth_schedule():
    pol = P.ApproxPolicy.depth_schedule([P.EXACT, P.EXACT, PC3_TR, FLA])
    assert P.plan_segments(pol, _sites, 0, 4) == ((0, 2), (2, 3), (3, 4))


# ---------------------------------------------------------------------------
# Construction-time / resolve-time validation
# ---------------------------------------------------------------------------

def test_daism_config_construction_validation():
    with pytest.raises(ValueError, match="accum_dtype"):
        DaismConfig(accum_dtype="int32")
    with pytest.raises(ValueError, match="k_chunk"):
        DaismConfig(k_chunk=0)
    with pytest.raises(ValueError, match="block"):
        DaismConfig(block_m=0)
    with pytest.raises(ValueError, match="backward"):
        DaismConfig(backend=Backend.PALLAS, backward="approx")


def test_backend_dtype_validation_at_arch_construction():
    cfg = get_config("lenet5")  # float32 compute
    pallas = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    with pytest.raises(ValueError, match="bfloat16-only"):
        dataclasses.replace(cfg, daism=pallas)
    with pytest.raises(ValueError, match="bfloat16-only"):
        cfg.with_policy("cnn/c1=pc3_tr:pallas")
    # jnp backend supports float32: must construct fine
    cfg.with_policy("cnn/c1=pc3_tr")


def test_validate_for_dtype_names_site():
    pallas = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS)
    with pytest.raises(ValueError, match="decoder/layer_0/attn/wq"):
        P.validate_for_dtype(pallas, jnp.float32,
                             site="decoder/layer_0/attn/wq")
    P.validate_for_dtype(pallas, jnp.bfloat16)  # ok
    P.validate_for_dtype(P.EXACT, jnp.int8)  # exact: anything goes


# ---------------------------------------------------------------------------
# Kernel cache
# ---------------------------------------------------------------------------

def test_kernel_cache_no_retrace_for_same_config():
    cfg = DaismConfig(variant=Variant.PC2, backend=Backend.JNP, k_chunk=17)
    k1 = P.matmul_kernel(cfg)
    k2 = P.matmul_kernel(DaismConfig(variant=Variant.PC2,
                                     backend=Backend.JNP, k_chunk=17))
    assert k1 is k2  # equal configs share one jitted kernel
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(3, 17)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(17, 5)), jnp.bfloat16)
    t0 = P.kernel_stats()["kernel_traces"]
    o1 = k1(a, w)
    t1 = P.kernel_stats()["kernel_traces"]
    o2 = k2(a, w)
    assert P.kernel_stats()["kernel_traces"] == t1  # second call: cache hit
    assert t1 == t0 + 1
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_mixed_policy_shares_kernels_across_sites():
    """Two different sites resolving to the same config reuse one kernel;
    repeated forwards do not re-trace."""
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    pol = P.ApproxPolicy(rules=(P.Rule("*/attn/*", PC3_TR),
                                P.Rule("*/ffn/*", PC3_TR)),
                         default=P.EXACT)
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (1, 4), 0, cfg.vocab)}
    model.forward(params, batch)
    traces = P.kernel_stats()["kernel_traces"]
    model.forward(params, batch)  # same shapes, same resolved configs
    assert P.kernel_stats()["kernel_traces"] == traces


# ---------------------------------------------------------------------------
# End-to-end model behavior
# ---------------------------------------------------------------------------

def test_uniform_policy_matches_legacy_daism_shim():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    model = build_model(cfg)
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 6), 0, cfg.vocab)}

    legacy = build_model(dataclasses.replace(cfg, daism=PC3_TR))
    shim, _ = legacy.forward(params, batch)
    explicit = build_model(cfg.with_policy(P.ApproxPolicy.uniform(PC3_TR)))
    pol, _ = explicit.forward(params, batch)
    np.testing.assert_array_equal(np.asarray(shim, np.float32),
                                  np.asarray(pol, np.float32))


def test_all_exact_policy_matches_plain_exact():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    model = build_model(cfg)
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 6), 0, cfg.vocab)}
    ref, _ = model.forward(params, batch)
    pol, _ = build_model(
        cfg.with_policy("*=exact")).forward(params, batch)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(pol, np.float32))


def test_mixed_policy_segments_and_fidelity():
    """first/last layer + lm_head exact must sit between all-exact and
    all-approx in logit fidelity, and the scan must split into 3 segments."""
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=4, vocab=64)
    model = build_model(cfg)
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 6), 0, cfg.vocab)}
    exact, _ = model.forward(params, batch)

    mixed_pol = P.ApproxPolicy.first_last_exact(FLA, cfg.n_layers)
    mixed_model = build_model(cfg.with_policy(mixed_pol))
    assert mixed_model.segments == ((0, 1), (1, 3), (3, 4))
    mixed, _ = mixed_model.forward(params, batch)
    uniform, _ = build_model(
        cfg.with_policy(P.ApproxPolicy.uniform(FLA))).forward(params, batch)

    e = np.asarray(exact, np.float32).ravel()
    c_mixed = np.corrcoef(e, np.asarray(mixed, np.float32).ravel())[0, 1]
    c_unif = np.corrcoef(e, np.asarray(uniform, np.float32).ravel())[0, 1]
    assert np.isfinite(np.asarray(mixed, np.float32)).all()
    assert c_mixed > c_unif  # protecting sensitive sites helps
    assert c_mixed < 1.0     # but the middle really is approximate


def test_mixed_policy_decode_matches_forward():
    """Segmented cached forward (cache slicing + concat) must agree with the
    teacher-forced forward under a mixed policy."""
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=4, vocab=64)
    pol = P.ApproxPolicy.first_last_exact(PC3_TR, cfg.n_layers)
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab)
    full_logits, _ = model.forward(params, {"tokens": toks})

    cache = model.init_cache(1, 8)
    outs = []
    for t in range(6):
        lg, cache = model.decode_step(params, toks[:, t:t + 1], cache)
        outs.append(np.asarray(lg[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(dec, np.asarray(full_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_site_paths_stable_across_build_model_reruns():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    pol = P.ApproxPolicy.uniform(PC3_TR, name="stability-probe")
    batch = {"tokens": jax.random.randint(RNG, (1, 4), 0, cfg.vocab)}

    def traced_sites():
        P.clear_log(pol)
        model = build_model(cfg.with_policy(pol))
        params, _ = model.init(RNG)
        model.forward(params, batch)
        return set(P.resolution_log(pol))

    first = traced_sites()
    second = traced_sites()
    assert first and first == second
    paths = {p for p, _ in first}
    assert "decoder/layer_0/attn/wq" in paths
    assert "decoder/lm_head" in paths


def test_conv_sites_resolve_by_kind():
    cfg = get_config("lenet5")
    pol = P.parse_policy("@conv=exact,*=pc3_tr", name="conv-exact")
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    P.clear_log(pol)
    images = jnp.zeros((2, 28, 28, 1), jnp.float32)
    logits, _ = model.forward(params, {"images": images})
    assert logits.shape == (2, 10)
    log = P.resolution_log(pol)
    by_path = {p: (k, c) for (p, k), (c, _, _) in log.items()}
    assert by_path["cnn/c1"][0] is P.OpKind.CONV
    assert by_path["cnn/c1"][1].exact
    assert by_path["cnn/f1"][1].variant is Variant.PC3_TR
    assert by_path["cnn/out"][0] is P.OpKind.LM_HEAD


def test_deprecation_shim_builds_uniform_policy():
    cfg = get_config("tinyllama_1_1b").smoke()
    shim = dataclasses.replace(cfg, daism=PC3_TR).approx_policy
    assert shim.rules == ()
    assert shim.default == PC3_TR
    # explicit policy takes precedence over the legacy field
    both = dataclasses.replace(cfg, daism=PC3_TR,
                               policy=P.ApproxPolicy.uniform(FLA))
    assert both.approx_policy.default == FLA


def test_moe_expert_sites_route_through_policy():
    cfg = get_config("qwen3_moe_235b").smoke(n_layers=2, vocab=64)
    pol = P.ApproxPolicy.uniform(PC3_TR, name="moe-probe")
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 4), 0, cfg.vocab)}
    P.clear_log(pol)
    logits, _ = model.forward(params, batch)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # the dense reference MoE (no mesh here) must route expert GEMMs
    # through the policy, not silently fall back to exact einsums
    log = P.resolution_log(pol)
    moe = {p: c for (p, k), (c, _, _) in log.items()
           if k is P.OpKind.MOE_EXPERT}
    assert "decoder/layer_0/ffn/w_in" in moe
    assert moe["decoder/layer_0/ffn/w_in"].variant is Variant.PC3_TR

    exact_logits, _ = build_model(cfg).forward(params, batch)
    assert not np.array_equal(np.asarray(logits, np.float32),
                              np.asarray(exact_logits, np.float32))


def test_energy_estimate_orders_policies():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=4, vocab=64)
    batch = {"tokens": jax.random.randint(RNG, (1, 4), 0, cfg.vocab)}
    pols = [P.ApproxPolicy.uniform(PC3_TR, name="e-uni"),
            P.ApproxPolicy.first_last_exact(PC3_TR, cfg.n_layers,
                                            name="e-mixed")]
    savings = []
    for pol in pols:
        P.clear_log(pol)
        model = build_model(cfg.with_policy(pol))
        params, _ = model.init(RNG)
        model.forward(params, batch)
        used, exact = P.estimated_energy_uj(pol)
        assert 0 < used < exact
        savings.append(1 - used / exact)
        assert "estimated multiply energy" in P.site_report(pol)
    assert savings[0] > savings[1]  # uniform approx saves more than mixed


def test_parse_policy_rejects_duplicate_patterns():
    with pytest.raises(ValueError, match=r"duplicate policy rule .* "
                                         r"rules 0 .* and 1 "):
        P.parse_policy("*/attn/*=exact,*/attn/*=pc3_tr,*=fla")


def test_parse_policy_duplicate_default_key_still_allowed():
    # "default" is a key, not a rule: last assignment wins, no dup error
    pol = P.parse_policy("*/attn/*=exact,default=pc3_tr")
    assert pol.default is not None


# ---------------------------------------------------------------------------
# Pallas interpret auto-selection
# ---------------------------------------------------------------------------

def test_auto_interpret_explicit_setting_wins(monkeypatch):
    # explicit interpret beats the platform, both ways
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert P.auto_interpret(
        dataclasses.replace(PC3_TR, interpret=True)) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert P.auto_interpret(
        dataclasses.replace(PC3_TR, interpret=False)) is False


def test_auto_interpret_none_selects_by_platform(monkeypatch):
    assert PC3_TR.interpret is None
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert P.auto_interpret(PC3_TR) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert P.auto_interpret(PC3_TR) is False


def test_interpret_mode_keys_kernel_cache():
    """interpret is part of DaismConfig, so the jitted-kernel lru_cache
    distinguishes auto (None) from forced modes — no cross-contamination
    when the same variant runs interpreted and compiled in one process."""
    base = DaismConfig(variant=Variant.PC2, backend=Backend.JNP, k_chunk=23)
    forced = dataclasses.replace(base, interpret=True)
    k_auto = P.matmul_kernel(base)
    k_forced = P.matmul_kernel(forced)
    assert k_auto is not k_forced
    assert P.matmul_kernel(dataclasses.replace(base, interpret=True)) \
        is k_forced
    assert P.matmul_kernel(dataclasses.replace(base)) is k_auto


# ---------------------------------------------------------------------------
# Flash-attention dispatch (OpKind.ATTN_QK sites)
# ---------------------------------------------------------------------------

def test_parse_config_flash_token():
    cfg = P.parse_config("pc3_tr:flash")
    assert cfg.variant is Variant.PC3_TR
    assert cfg.backend is Backend.JNP
    assert cfg.attn_kernel == "flash"
    assert P.describe_config(cfg) == "pc3_tr:jnp:flash"
    ex = P.parse_config("exact:flash")
    assert ex.exact and ex.attn_kernel == "flash"
    assert P.describe_config(ex) == "exact:flash"
    with_backend = P.parse_config("fla:pallas:flash")
    assert with_backend.backend is Backend.PALLAS
    assert with_backend.attn_kernel == "flash"
    assert P.parse_config("pc3_tr").attn_kernel == "jnp"
    with pytest.raises(ValueError, match="too many"):
        P.parse_config("fla:jnp:pallas:flash")
    with pytest.raises(ValueError):
        DaismConfig(attn_kernel="bogus")


def test_effective_attn_config_is_opt_in():
    """Catch-all numerics rules must not leak into attention: only the
    ':flash' token changes what an ATTN_QK site runs."""
    assert P.effective_attn_config(PC3_TR) is P.EXACT
    flash = dataclasses.replace(PC3_TR, attn_kernel="flash")
    assert P.effective_attn_config(flash) is flash
    assert P.effective_attn_config(flash, eligible=False) is P.EXACT


def test_attn_site_resolves_effective_config():
    pol = P.parse_policy("*=pc3_tr")  # catch-all, no flash opt-in
    with P.site_scope("decoder"), P.site_scope("layer_0"), \
            P.site_scope("attn"):
        cfg = P.resolve_site(pol, "kernel", P.OpKind.ATTN_QK, jnp.bfloat16,
                             record=False)
    assert cfg is P.EXACT
    flash_pol = P.parse_policy("*/attn/kernel=pc3_tr:flash,*=exact")
    with P.site_scope("decoder"), P.site_scope("layer_0"), \
            P.site_scope("attn"):
        cfg = P.resolve_site(flash_pol, "kernel", P.OpKind.ATTN_QK,
                             jnp.bfloat16, record=False)
        assert cfg.attn_kernel == "flash" and cfg.variant is Variant.PC3_TR
        # ineligible shapes (windowed / per-row / cached decode) fall back
        assert P.resolve_site(flash_pol, "kernel", P.OpKind.ATTN_QK,
                              jnp.bfloat16, record=False,
                              attn_eligible=False) is P.EXACT
        with pytest.raises(ValueError, match="bfloat16-only"):
            P.resolve_site(flash_pol, "kernel", P.OpKind.ATTN_QK,
                           jnp.float32, record=False)


def test_flash_exact_policy_token_identical_to_jnp_path():
    """attend must route through flash_attention_bhsd under a requesting
    policy, and the exact variant must not change a single logit argmax."""
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    model = build_model(cfg.with_policy("*=exact"))
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 64), 0, cfg.vocab)}
    ref, _ = model.forward(params, batch)
    flash_model = build_model(
        cfg.with_policy("*/attn/kernel=exact:flash,*=exact"))
    out, _ = flash_model.forward(params, batch)
    r = np.asarray(ref, np.float32)
    o = np.asarray(out, np.float32)
    np.testing.assert_array_equal(r.argmax(-1), o.argmax(-1))
    np.testing.assert_allclose(o, r, rtol=2e-2, atol=2e-3)


def test_flash_approx_policy_runs_and_records():
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    pol = P.parse_policy("*/attn/kernel=pc3_tr:flash,*=exact", name="fa")
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    batch = {"tokens": jax.random.randint(RNG, (2, 64), 0, cfg.vocab)}
    P.clear_log(pol)
    out, _ = model.forward(params, batch)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    log = P.resolution_log(pol)
    attn_sites = {path: c for (path, kind), (c, _, _) in log.items()
                  if kind is P.OpKind.ATTN_QK}
    assert attn_sites, log.keys()
    assert all(c.attn_kernel == "flash" and c.variant is Variant.PC3_TR
               for c in attn_sites.values())
    # approximate attention must actually change the logits
    ref, _ = build_model(cfg.with_policy("*=exact")).forward(params, batch)
    assert np.abs(np.asarray(out, np.float32)
                  - np.asarray(ref, np.float32)).max() > 1e-3


def test_cached_decode_keeps_exact_fallback():
    """Decode steps use the KV-cache branches, which never pass a policy:
    a flash-requesting policy must not disturb cached decoding (the exact
    flash variant is bit-compatible with the jnp path, so decode-vs-forward
    agreement shows the decode side ignored the flash request)."""
    cfg = get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)
    pol = P.parse_policy("*/attn/kernel=exact:flash,*=exact", name="fa2")
    model = build_model(cfg.with_policy(pol))
    params, _ = model.init(RNG)
    toks = jax.random.randint(RNG, (1, 8), 0, cfg.vocab)
    full, _ = model.forward(params, {"tokens": toks})
    cache = model.init_cache(1, 8)
    logits = []
    for t in range(toks.shape[1]):
        step, cache = model.decode_step(params, toks[:, t:t + 1], cache)
        logits.append(step)
    dec = jnp.concatenate(logits, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-3)
