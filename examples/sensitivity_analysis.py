"""Reproduce the paper's Fig.-1 exploratory experiment (Eqs. 2-3).

    PYTHONPATH=src python examples/sensitivity_analysis.py

Fine-tunes decomposed-LoRA per downstream task vs the all-task mixture
and reports the direction/magnitude sensitivity of the A and B factors —
the observation motivating the whole method (A-direction ≫, B-magnitude ≫).
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from benchmarks import fig1_sensitivity  # noqa: E402


def main():
    rep = fig1_sensitivity.run(steps=60, log=print)
    print("\nper-task breakdown:")
    for t, row in rep["per_task"].items():
        print(f"  {t:8s} ΔD_A={row['dD_A']:.4f} ΔD_B={row['dD_B']:.4f} "
              f"ΔM_A={row['dM_A']:.4f} ΔM_B={row['dM_B']:.4f}")
    print(f"\nObs.1 (paper 1.7×): direction ratio A/B = "
          f"{rep['obs1_dir_ratio_A_over_B']:.2f}")
    print(f"Obs.2 (paper 41×) : magnitude ratio B/A = "
          f"{rep['obs2_mag_ratio_B_over_A']:.2f}")


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
