"""Energy / cycle / area model tests (paper Fig 7-9 + headline claims)."""
import importlib.util
import pathlib

import pytest

from repro.core import Variant
from repro.core import arch_model as A
from repro.core import energy as E


def test_concurrent_mults_match_paper_numbers():
    """Paper §5.2.2: 32 kB/512-bit bank, bf16 => 32 truncated / 16 full."""
    assert E.concurrent_mults("bfloat16", True, 512) == 32
    assert E.concurrent_mults("bfloat16", False, 512) == 16


def test_active_wordlines_match_paper():
    """Paper §5.2.1: PC2_tr bf16 => at most 7 active wordlines."""
    assert E.active_wordlines(Variant.PC2_TR, "bfloat16") == 7
    assert E.active_wordlines(Variant.PC3_TR, "bfloat16") == 6
    assert E.active_wordlines(Variant.FLA, "bfloat16") == 8


def test_fig7_observations():
    base = E.total(E.eyeriss_energy_per_mult("bfloat16", truncated=True))
    hla = E.total(E.daism_energy_per_mult(Variant.HLA, "bfloat16",
                                          bank_kb=32, bus_bits=512))
    pc3t = E.total(E.daism_energy_per_mult(Variant.PC3_TR, "bfloat16",
                                           bank_kb=32, bus_bits=512))
    pc3 = E.total(E.daism_energy_per_mult(Variant.PC3, "bfloat16",
                                          bank_kb=32, bus_bits=512))
    pc2t = E.total(E.daism_energy_per_mult(Variant.PC2_TR, "bfloat16",
                                           bank_kb=32, bus_bits=512))
    assert hla >= base                      # observation 3: HLA not viable
    assert pc3t < base                      # DAISM wins
    assert pc3t < 0.6 * pc3                 # truncation ~2x ops per read
    assert pc3t < pc2t                      # PC3 fewer active wordlines


def test_fig9_geometry():
    layer = A.ConvLayer()
    assert layer.inputs == 150_528          # paper: VGG-8 L1 inputs
    assert layer.kernel_elements == 1_728   # paper: kernel elements
    ey = A.eyeriss_cycles(layer)["cycles"]
    res = {(b.num_banks, b.bank_kbytes): A.daism_cycles(layer, b)["cycles"]
           for b in A.FIG9_CONFIGS}
    assert res[(1, 512)] > max(res[(4, 128)], res[(16, 32)], res[(16, 8)])
    assert res[(16, 8)] == res[(4, 128)]    # paper §5.3.2 observation
    assert res[(16, 32)] < ey               # banked DAISM beats Eyeriss
    d = A.daism_cycles(layer, A.BankConfig(16, 32))
    assert d["pe_equivalent"] == 512        # paper: "512 processing elements"


def test_headline_direction():
    """-25% energy / -43% cycles (paper) — our constants must reproduce the
    sign and beat the claimed magnitudes' floor at comparable area."""
    layer = A.ConvLayer()
    ey_cycles = A.eyeriss_cycles(layer)["cycles"]
    ey_energy = A.eyeriss_layer_energy_uj(layer)
    bc = A.BankConfig(16, 8)                # smaller area than Eyeriss
    assert A.daism_area_mm2(bc) < A.eyeriss_area_mm2()
    cyc = A.daism_cycles(layer, bc)["cycles"]
    en = A.daism_layer_energy_uj(layer, bc)
    assert (ey_cycles - cyc) / ey_cycles > 0.25
    assert (ey_energy - en) / ey_energy > 0.25


def test_capacity_refills():
    """A kernel bigger than all banks triggers reload passes."""
    big = A.ConvLayer(h=14, w=14, cin=512, cout=512)  # 2.36M elements
    small_banks = A.BankConfig(1, 8)
    d = A.daism_cycles(big, small_banks)
    assert d["refills"] > 1


@pytest.mark.parametrize("name", ["error_distance", "energy", "arch_cycles"])
def test_paper_table_claims_hold(name):
    """Every boolean claim a paper-table module under ``benchmarks/``
    returns holds (the modules that train models stay out)."""
    path = pathlib.Path(__file__).parents[1] / "benchmarks" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"paper_table_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rows, claims = mod.run()
    assert rows
    checked = {k: v for k, v in claims.items() if isinstance(v, bool)}
    assert checked, claims
    assert all(checked.values()), {k for k, v in checked.items() if not v}
