"""Quickstart: FedLoRA-Optimizer on synthetic heterogeneous tasks (CPU).

    PYTHONPATH=src python examples/quickstart.py

Pretrains a small backbone (cached), runs a few federated rounds of the
paper's pipeline, and prints global vs personalized accuracy against the
plain-LoRA (FedIT) baseline.
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)


from benchmarks.common import BENCH_CFG, bench_base, build_setting  # noqa: E402
from repro.core.fedlora import run_federated  # noqa: E402
from repro.fed.simulate import FedHyper  # noqa: E402


def main():
    print("== FedLoRA-Optimizer quickstart ==")
    base = bench_base("dolly", steps=400, log=print)
    cds, sds, eg, el = build_setting("dolly")
    for method in ("fedlora_opt", "lora"):
        hp = FedHyper(method=method, n_clients=len(cds), rounds=5,
                      local_steps=4, batch=8, seq_len=48, lr=2e-3,
                      personal_steps=10, global_steps=3)
        res = run_federated(BENCH_CFG, hp, cds, sds, eg, el, base=base,
                            log=print)
        print(f"--> {method:12s} global_acc={res.global_acc:.3f} "
              f"local_acc={res.local_acc:.3f} "
              f"comm={res.comm_bytes/1e6:.2f} MB")


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
