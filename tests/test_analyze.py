"""daism-lint: the static analyzer's site graph and every checker family.

Each checker must fire on a crafted bad (model, policy, engine) triple and
stay silent (no error findings) on every shipped config's defaults — the
same invariant the CI `lint-policies` job enforces end to end.
"""
import dataclasses
import json

import pytest

from repro.analyze import (analyze, check_backend, check_policy,
                           check_recompile, check_serving, check_tiling,
                           engine_config_finding, format_json, format_text,
                           preflight, trace_site_graph)
from repro.configs import ARCH_IDS, PAPER_IDS, get_config
from repro.core import Backend, DaismConfig, Variant
from repro.serve import EngineConfig

PC3_TR = DaismConfig(variant=Variant.PC3_TR, backend=Backend.JNP)


def codes(findings):
    return {f.code for f in findings}


def smoke_lm():
    return get_config("tinyllama_1_1b").smoke(n_layers=2, vocab=64)


# ---------------------------------------------------------------------------
# Site-graph tracing (eval_shape only — no weights, no kernels)
# ---------------------------------------------------------------------------

def test_trace_site_graph_covers_all_sites_without_weights():
    graph = trace_site_graph(smoke_lm(), "*/attn/*=exact,*=pc3_tr")
    paths = graph.paths()
    assert any("attn" in p for p in paths)
    assert any("ffn" in p for p in paths)
    assert any("lm_head" in p for p in paths)
    assert all(s.macs > 0 for s in graph.sites)
    used, exact = graph.energy_uj()
    assert 0 < used < exact  # mixed policy lands strictly below all-exact


def test_trace_site_graph_matches_runtime_segmentation():
    graph = trace_site_graph(smoke_lm(), "*/layer_0/*=exact,*=pc3_tr")
    # layer_0 exact / layer_1 approx must shatter the decoder scan in two
    assert any(len(spans) == 2 for spans in graph.segments.values())
    assert any("layer_0" in p for p in graph.paths())


def test_trace_handles_illegal_candidate_policy():
    """Policies the ArchConfig would reject (bf16-only backend on an fp32
    model) still trace — legality is a finding, not a crash."""
    graph = trace_site_graph(get_config("lenet5"), "*=pc3_tr:pallas")
    assert graph.sites  # traced anyway
    bck = check_backend(graph)
    assert bck and all(f.code == "BCK001" and f.severity == "error"
                       for f in bck)


# ---------------------------------------------------------------------------
# Policy checkers
# ---------------------------------------------------------------------------

def test_zero_match_rule_is_an_error():
    report = analyze(smoke_lm(), "*/bogus/*=exact,*=pc3_tr")
    assert "POL001" in codes(report.errors)
    assert report.exit_code == 1


def test_shadowed_and_catch_all_ordering_warn():
    graph = trace_site_graph(smoke_lm(), "*=pc3_tr,*/attn/*=exact")
    found = codes(check_policy(graph))
    assert {"POL002", "POL003"} <= found  # shadowed + catch-all-first


def test_deprecated_daism_shim_warns():
    cfg = dataclasses.replace(smoke_lm(), daism=PC3_TR, policy=None)
    found = check_policy(trace_site_graph(cfg))
    assert "POL004" in codes(found)


# ---------------------------------------------------------------------------
# Tiling / recompile checkers
# ---------------------------------------------------------------------------

def test_tiling_padding_and_vmem_warnings():
    from repro.policy import EXACT, ApproxPolicy, Rule
    # spec grammar has no block syntax: build the policy programmatically.
    # The fused plane sweep keeps the live slabs at (K_FUSE, bm, bn), so
    # only very large tiles blow the budget — block_k enters through the
    # staged int32 fields and the streamed bf16 tiles. N pads to 128 lanes
    # whatever block_n is, so the smoke model's narrow N gives bn = 128 and
    # block_k alone has to carry the footprint past 16 MiB.
    bad = DaismConfig(variant=Variant.PC3_TR, backend=Backend.PALLAS,
                      block_m=2048, block_n=1000, block_k=16384)
    pol = ApproxPolicy(rules=(Rule("*/ffn/*", bad),), default=EXACT)
    graph = trace_site_graph(smoke_lm(), pol)
    found = codes(check_tiling(graph))
    assert {"TIL001", "TIL002"} <= found


def test_tiling_padding_reports_the_fitted_row_tile():
    # one sequence of 4 tokens: every GEMM site has 4 rows, which the
    # kernel pads to an 8-row tile, not to block_m = 32
    graph = trace_site_graph(smoke_lm(), "*=pc3_tr:pallas", seq=4)
    til001 = [f for f in check_tiling(graph) if f.code == "TIL001"]
    assert til001
    assert all("m: 4 -> 8" in f.message for f in til001)
    assert not any("-> 32" in f.message for f in til001)


@pytest.mark.parametrize("rows", [8, 128], ids=["decode", "prefill"])
def test_default_tiles_fit_vmem_at_sc2_widths(rows):
    """StarCoder2-15B's GEMMs under the default tiles ((8, 2048) at 8
    rows, (32, 512) at 128) stay within the VMEM budget, and K and N pad
    nowhere."""
    cfg = dataclasses.replace(get_config("starcoder2_15b"), n_layers=1)
    graph = trace_site_graph(cfg, "*=pc3_tr:pallas", seq=rows)
    assert {s.dims[0] for s in graph.sites if not s.config.exact} == {rows}
    found = codes(check_tiling(graph))
    assert not {"TIL001", "TIL002"} & found


def test_tiling_interpret_fallback_info_on_cpu():
    graph = trace_site_graph(smoke_lm(), "*=pc3_tr:pallas")
    til = check_tiling(graph)
    assert "TIL003" in codes(til)
    assert all(f.severity in ("info", "warning") for f in til)


def test_attention_checker_flags_ragged_flash_tiles():
    from repro.analyze import check_attention
    # seq=8 pads to the 128-wide flash tiles; head_dim 64 is off-lane too
    graph = trace_site_graph(smoke_lm(),
                             "*/attn/kernel=exact:flash,*=exact")
    found = check_attention(graph)
    assert any(f.code == "TIL004" and f.severity == "warning"
               and f.site.endswith("attn/kernel") for f in found)
    # without the ':flash' opt-in the ATTN_QK sites run exact jnp — silent
    assert not check_attention(trace_site_graph(smoke_lm(), "*=pc3_tr"))


def test_attention_checker_flags_non_bf16_flash_variant():
    from repro.analyze import check_attention
    cfg = dataclasses.replace(smoke_lm(), compute_dtype="float32",
                              param_dtype="float32")
    graph = trace_site_graph(cfg, "*/attn/kernel=pc3_tr:flash,*=exact")
    found = check_attention(graph)
    assert any(f.code == "TIL005" and f.severity == "error" for f in found)


def test_recompile_hazards_on_depth_schedule():
    from repro.policy import ApproxPolicy, Rule
    cfg = get_config("tinyllama_1_1b")  # full depth: 22 layers
    rules = tuple(
        Rule(f"*/layer_{i}/*", dataclasses.replace(PC3_TR, k_chunk=64 + i))
        for i in range(cfg.n_layers))
    graph = trace_site_graph(cfg, ApproxPolicy(rules=rules, default=PC3_TR))
    found = codes(check_recompile(graph))
    assert {"RCP001", "RCP002"} <= found  # segment shatter + kernel variants


# ---------------------------------------------------------------------------
# Serving checkers
# ---------------------------------------------------------------------------

def test_serving_window_incompatibility_is_an_error():
    cfg = dataclasses.replace(smoke_lm(), window=16)
    graph = trace_site_graph(cfg)
    found = check_serving(graph, EngineConfig())
    assert any(f.code == "SRV001" and f.severity == "error" for f in found)


def test_serving_pool_capacity_and_oversubscription():
    graph = trace_site_graph(smoke_lm())
    small = EngineConfig(num_blocks=4, block_size=16)  # 64 < max_seq=128
    found = check_serving(graph, small)
    assert "SRV002" in codes(found)
    tiered = EngineConfig(num_blocks=32, block_size=16,
                          tiers=(("free", "*=pc3_tr"),
                                 ("paid", "*/attn/*=exact,*=pc3_tr")))
    found = check_serving(graph, tiered)
    assert "SRV003" in codes(found)  # 512 blocks*size < slots*tiers*max_seq


def test_serving_duplicate_tier_groups_and_bad_tier_spec():
    graph = trace_site_graph(smoke_lm())
    dup = EngineConfig(tiers=(("free", "*=pc3_tr"), ("paid", "*=pc3_tr")))
    assert "SRV004" in codes(check_serving(graph, dup))
    broken = EngineConfig(tiers=(("free", "*/xx/*=exact,*=pc3_tr"),))
    found = check_serving(graph, broken)
    assert "SRV005" in codes(found)


def test_serving_shard_divisibility_srv007():
    graph = trace_site_graph(smoke_lm())
    # 30 pages / 4 slots over 4 shards: pages don't divide
    bad = EngineConfig(num_slots=4, num_blocks=30, block_size=16, shards=4)
    found = check_serving(graph, bad)
    assert any(f.code == "SRV007" and f.severity == "error" for f in found)
    # rows don't divide either
    bad_rows = EngineConfig(num_slots=3, num_blocks=32, block_size=16,
                            shards=4)
    assert "SRV007" in codes(check_serving(graph, bad_rows))
    ok = EngineConfig(num_slots=4, num_blocks=32, block_size=16, shards=4)
    assert "SRV007" not in codes(check_serving(graph, ok))
    # advisory mode caps it to a warning like the other structural errors
    found = check_serving(graph, bad, advisory=True)
    assert any(f.code == "SRV007" and f.severity == "warning" for f in found)


def test_serving_undersized_swap_buffer_srv008():
    graph = trace_site_graph(smoke_lm())
    # max_seq=128 / block_size=16 -> 8 pages per max-length request
    small = EngineConfig(preempt=True, swap_blocks=4)
    found = check_serving(graph, small)
    assert any(f.code == "SRV008" and f.severity == "warning" for f in found)
    # 0 = auto (one full request) and >= one request are both fine
    assert "SRV008" not in codes(
        check_serving(graph, EngineConfig(preempt=True)))
    assert "SRV008" not in codes(
        check_serving(graph, EngineConfig(preempt=True, swap_blocks=8)))
    # without preemption the swap buffer is never used
    assert "SRV008" not in codes(
        check_serving(graph, EngineConfig(swap_blocks=4)))


def test_serving_advisory_mode_caps_severity():
    cfg = dataclasses.replace(smoke_lm(), window=16)
    graph = trace_site_graph(cfg)
    found = check_serving(graph, EngineConfig(), advisory=True)
    assert any(f.code == "SRV001" for f in found)
    assert all(f.severity != "error" for f in found)


def test_serving_skipped_for_non_servable_family():
    graph = trace_site_graph(get_config("lenet5"))
    found = check_serving(graph, EngineConfig())
    assert codes(found) == {"SRV006"}
    assert all(f.severity == "info" for f in found)


def test_serving_spec_draft_srv009():
    """SRV009: speculative draft vs target compatibility — energy, dtype,
    window, spec parse; silent when the draft is genuinely cheaper."""
    graph = trace_site_graph(smoke_lm())  # target: exact base policy

    def srv9(ecfg, **kw):
        return [f for f in check_serving(graph, ecfg, **kw)
                if f.code == "SRV009"]

    # a genuinely cheaper draft is clean
    ok = EngineConfig(spec_draft="*=pc3_tr", spec_k=3)
    assert srv9(ok) == []
    # ... and spec_k=0 never runs the checker at all
    assert srv9(EngineConfig()) == []

    # draft == target numerics: speculation can never pay for itself
    found = srv9(EngineConfig(spec_draft="*=exact", spec_k=3))
    assert [f.severity for f in found] == ["error"]
    assert "not cheaper" in found[0].message

    # draft names a registered tier (resolved through EngineConfig.tiers)
    named = EngineConfig(tiers=(("cheap", "*=pc3_tr"),),
                         spec_draft="cheap", spec_k=3)
    assert srv9(named) == []

    # draft not cheaper than another tier: warning, not error
    found = srv9(EngineConfig(tiers=(("cheap", "*=pc3_tr"),),
                              spec_draft="*=pc2", spec_k=3))
    assert any(f.severity == "warning" and "tier 'cheap'" in f.message
               for f in found)

    # unparseable draft spec
    found = srv9(EngineConfig(spec_draft="*=bogus", spec_k=3))
    assert [f.severity for f in found] == ["error"]
    assert "rejected" in found[0].message

    # windowed model: draft writes ahead of the committed length
    wg = trace_site_graph(dataclasses.replace(smoke_lm(), window=16))
    found = [f for f in check_serving(wg, ok) if f.code == "SRV009"]
    assert any("window" in f.message and f.severity == "error"
               for f in found)

    # dtype illegality: Pallas draft on an f32 model
    f32 = dataclasses.replace(smoke_lm(), compute_dtype="float32",
                              param_dtype="float32")
    fg = trace_site_graph(f32)
    found = [f for f in check_serving(
        fg, EngineConfig(spec_draft="*=pc3_tr:pallas", spec_k=3))
        if f.code == "SRV009"]
    assert any(f.severity == "error" for f in found)

    # advisory mode downgrades the structural errors to warnings
    found = srv9(EngineConfig(spec_draft="*=exact", spec_k=3),
                 advisory=True)
    assert found and all(f.severity == "warning" for f in found)


def test_engine_config_finding_wraps_construction_error():
    try:
        EngineConfig(tiers=(("free",),))  # malformed pair
    except ValueError as e:
        f = engine_config_finding(e)
        assert f.code == "SRV000" and f.severity == "error"
    else:
        pytest.fail("malformed tiers must not construct")


# ---------------------------------------------------------------------------
# Reports, preflight, and the shipped-config sweep
# ---------------------------------------------------------------------------

def test_report_formats_and_exit_codes():
    report = analyze(smoke_lm(), "*/attn/*=exact,*=pc3_tr")
    assert report.exit_code == 0
    text = format_text(report)
    assert "daism-lint" in text and "ENE001" in text
    data = json.loads(format_json(report))
    assert data["exit_code"] == 0
    assert data["sites"] and data["findings"]
    assert set(data["energy_uj"]) == {"policy", "exact"}


def test_preflight_raises_on_error_findings(capsys):
    with pytest.raises(SystemExit, match="daism-lint found"):
        preflight(smoke_lm(), "*/bogus/*=exact,*=pc3_tr", label="train t")
    out = capsys.readouterr().out
    assert "POL001" in out


def test_preflight_passes_clean_config():
    report = preflight(smoke_lm(), serving=False, label="train t")
    assert report.exit_code == 0


@pytest.mark.parametrize("name", list(ARCH_IDS) + list(PAPER_IDS))
def test_all_shipped_configs_lint_clean(name):
    """The CI sweep invariant: every registered config's defaults produce
    zero error findings (serving advisory, as nothing is deployed)."""
    report = analyze(name, advisory_serving=True)
    assert report.errors == [], [str(f) for f in report.errors]
    assert report.graph.sites
