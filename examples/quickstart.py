"""Quickstart: one adaptive-offloading round + a few FL rounds, end to end.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.compat import setup_compile_cache
from repro.core import build_default_sagin, optimize_offloading
from repro.core.latency import round_latency_no_offload
from repro.fl import FLConfig, run_fl


def main():
    print(setup_compile_cache())
    # --- 1. the paper's core: one adaptive data-offloading decision -------
    sagin = build_default_sagin(n_devices=10, n_air=2, seed=0)
    baseline = round_latency_no_offload(sagin)
    plan = optimize_offloading(sagin)
    print(f"round latency without offloading : {baseline:10.0f} s")
    print(f"round latency with adaptive plan : {plan.round_latency:10.0f} s"
          f"  (case {plan.case}, {baseline / plan.round_latency:.1f}x faster)")
    g, a, s = plan.new_sizes(sagin)
    total = sum(g) + sum(a) + s
    print(f"data placement  ground/air/space : "
          f"{sum(g)/total:.0%} / {sum(a)/total:.0%} / {s/total:.0%}")

    # --- 2. a short federated training run with the orchestrator ----------
    cfg = FLConfig(dataset="mnist", n_rounds=4, n_devices=10, n_air=2,
                   h_local=3, train_fraction=0.02, eval_size=512,
                   strategy="adaptive")
    res = run_fl(cfg)
    print("\nFL run (adaptive offloading):")
    for r, (t, acc) in enumerate(zip(res.times, res.accuracies)):
        print(f"  round {r}: training time {t:8.0f} s   accuracy {acc:.3f}")


if __name__ == "__main__":
    main()
