"""Cohort-engine scaling: bucketed vs global-Bmax vs sequential rounds.

Drives three round engines over synthetic federated pools:

* ``bucketed``   — the size-bucketed, device-resident cohort engine
  (``repro.fl.cohort_engine.CohortEngine``): one compiled dispatch per
  geometric width bucket, single device-side aggregation.
* ``global``     — the PR-1 batched path (every client padded to the
  round's global ``Bmax``), kept as ``cohort_bucketing="global"``.
* ``sequential`` — the reference loop: one jitted dispatch per node.

Two pool regimes:

* ``uniform`` — lognormal ragged pools, mild spread: the regime PR 1
  optimized, where global-``Bmax`` padding is already cheap.  Bucketing
  must not regress here.
* ``skewed``  — mega_constellation-style offloading skew: one pool holds
  ~10x the samples of each of the many small ones, so the global layout
  pads every small client to the big client's batch width.  This is the
  regime the paper's adaptive offloading deliberately creates, and where
  bucketing must deliver >= 2x per-round speedup over the global layout
  at engine scale (C >= 64; below that the round is dispatch-bound, not
  padding-bound, and both batched layouts cost microseconds — those rows
  stay informational).

Pools DRIFT between rounds (offloading churn).  Round 1 is the
warmup/compile round; headline numbers are means over the remaining
rounds.  Rows feed ``BENCH_cohort.json`` via ``benchmarks.run --json``.

Gates (non-smoke): skewed-regime bucketed-vs-global speedup must stay
>= 2x at engine scale, and small uniform cohorts (C <= 32) must never
regress below 1x — there the planner's collapse pass folds
near-uniform plans into a single global-shaped bucket, so bucketing
costs nothing where it cannot win (larger uniform cohorts sit at
parity within timing noise and are tracked, not gated).

A fourth row family, ``cohort.sharded.D{n}``, measures the mesh-sharded
engine (clients/sec at 1/2/4/8 forced host devices on the
mega_constellation skewed shape, C=256 mlp by default).  Each device
count runs in a ``--sharded-worker`` subprocess because
``--xla_force_host_platform_device_count`` binds at jax import.  These
are CPU-only virtual-device rows (the workers are pinned to
``JAX_PLATFORMS=cpu``), and a worker that fails fails the module; rows
carry per-shard padding/imbalance metrics from
``CohortEngineStats``.  The D8 gate requires >= 1.5x round throughput
over D1 wherever >= 2 usable cores exist; a 1-core host serializes the
shard programs (the residual ~1.2-1.4x is per-shard working-set and
fusion effects only), so there the gate records the number and skips.

The bucketed engine runs with ``guard=True``: every round whose bucket
layout is already warm executes under
``repro.analysis.contracts.no_recompile()``, so a recompile regression
on the steady-state path fails the bench lane with a
``ContractViolation`` naming the round instead of silently inflating
the timings.  (The guard is exact — zero lowerings allowed — and
self-gating: rounds that legitimately introduce a new bucket signature
under drift stay unguarded.)

Usage:
  PYTHONPATH=src python -m benchmarks.cohort_scaling
  PYTHONPATH=src python -m benchmarks.cohort_scaling --regime skewed \
      --cohorts 64 --rounds 5
  PYTHONPATH=src python -m benchmarks.cohort_scaling --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import batch_width_for_pool, plan_buckets
from repro.fl.cohort_engine import CohortEngine
from repro.fl.rounds import FLConfig, _round_batched, _round_sequential

from .common import row


# --------------------------------------------------------------------------
# FL payloads (client models)
# --------------------------------------------------------------------------
def _logreg(key, din, nc=10):
    params = {"w": jax.random.normal(key, (din, nc)) * 0.05,
              "b": jnp.zeros(nc)}

    def apply_fn(p, x):
        return x.reshape(x.shape[0], -1) @ p["w"] + p["b"]

    return params, apply_fn


def _mlp(key, din, dh=64, nc=10):
    k1, k2 = jax.random.split(key)
    params = {"w1": jax.random.normal(k1, (din, dh)) * 0.05,
              "b1": jnp.zeros(dh),
              "w2": jax.random.normal(k2, (dh, nc)) * 0.05,
              "b2": jnp.zeros(nc)}

    def apply_fn(p, x):
        h = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    return params, apply_fn


def _cnn(key, din):
    from repro.models.cnn import build_model
    return build_model("mnist", key, image_shape=(28, 28, 1))


PAYLOADS = {"logreg": _logreg, "mlp": _mlp, "cnn": _cnn}
PAYLOAD_DIN = {"logreg": 64, "mlp": 784, "cnn": None}


# --------------------------------------------------------------------------
# Pool regimes
# --------------------------------------------------------------------------
def _make_pools_uniform(n_samples, c, h, rng):
    """Ragged client pools: lognormal sizes, every client non-empty."""
    sizes = np.maximum(h, rng.lognormal(3.0, 0.8, c).astype(int))
    sizes = np.minimum(sizes, max(h, n_samples // max(1, c)))
    perm = rng.permutation(n_samples)
    pools, pos = [], 0
    for s in sizes:
        pools.append(perm[pos:pos + s].copy())
        pos += s
    return pools


def _make_pools_skewed(n_samples, c, h, rng):
    """Offloading skew: c-1 sensor-class pools plus ONE pool holding
    ~10x the combined mass of the rest (the satellite after adaptive
    offloading concentrates data on the best-placed node)."""
    small = np.maximum(h, rng.integers(24, 56, c - 1))
    big = 10 * int(small.sum())
    total = int(small.sum()) + big
    if total > n_samples:
        raise ValueError(f"need {total} samples, have {n_samples}")
    perm = rng.permutation(n_samples)
    pools, pos = [], 0
    for s in small:
        pools.append(perm[pos:pos + s].copy())
        pos += s
    pools.append(perm[pos:pos + big].copy())
    return pools


def _drift(pools, rng, frac=0.15):
    """Move ~frac of a few clients' samples to others (offloading churn)."""
    pools = [p.copy() for p in pools]
    c = len(pools)
    for _ in range(max(1, c // 4)):
        src, dst = rng.integers(0, c, 2)
        if src == dst or len(pools[src]) <= 2:
            continue
        k = max(1, int(frac * len(pools[src])))
        pools[dst] = np.concatenate([pools[dst], pools[src][:k]])
        pools[src] = pools[src][k:]
    return pools


REGIMES = {"uniform": _make_pools_uniform, "skewed": _make_pools_skewed}


# --------------------------------------------------------------------------
# Round drivers
# --------------------------------------------------------------------------
def _padding_ratios(schedule, h, batch_cap, align, pad_clients):
    """Mean layout/real element ratios of both batched layouts over the
    pool schedule — pure arithmetic over the per-pool batch widths
    (``batch_width_for_pool`` is the sizing rule both builders share),
    no tensors materialized."""
    buck, glob = [], []
    for pools in schedule:
        widths = [batch_width_for_pool(len(p), h, batch_cap)
                  for p in pools if len(p)]
        real = sum(widths)
        plans = plan_buckets(widths, batch_align=align)
        buck.append(sum(p.c_bucket * p.b_bucket for p in plans) / real)
        b_max = int(np.ceil(max(widths) / align) * align)
        glob.append(max(len(widths), pad_clients) * b_max / real)
    return float(np.mean(buck)), float(np.mean(glob))


def bench_cohort(c, payload="logreg", regime="skewed", h=5, batch_cap=8,
                 rounds=5, seed=0, seq=True):
    rng = np.random.default_rng(seed)
    din = PAYLOAD_DIN[payload]
    n = max(4096, c * 48)
    if regime == "skewed":
        n = max(n, 11 * 56 * c)          # room for the 10x pool
    if payload == "cnn":
        x = rng.normal(size=(n, 28, 28, 1)).astype(np.float32)
    else:
        x = rng.normal(size=(n, din)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    ds = SimpleNamespace(x_train=x, y_train=y)

    params, apply_fn = PAYLOADS[payload](jax.random.PRNGKey(seed), din)
    cfg = FLConfig(n_devices=c, n_air=0, h_local=h, lr=0.05,
                   batch_cap=batch_cap, seed=seed,
                   cohort_batch_align=max(8, batch_cap))

    # identical pool schedule for every engine
    pools0 = REGIMES[regime](n, c, h, rng)
    schedule = [pools0]
    for _ in range(rounds - 1):
        schedule.append(_drift(schedule[-1], rng))
    total = sum(len(p) for p in pools0)

    def run(engine, run_cfg):
        times = []
        eng_rng = np.random.default_rng(seed + 1)
        p = jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), params)
        for pools in schedule:
            t0 = time.perf_counter()
            p = engine(run_cfg, apply_fn, p, ds, pools, total, eng_rng)[0]
            jax.block_until_ready(p)
            times.append(time.perf_counter() - t0)
        return times

    cfg_buck = dataclasses.replace(cfg, cohort_bucketing="geometric")
    cfg_glob = dataclasses.replace(cfg, cohort_bucketing="global")
    # persistent engine with the recompile contract armed: warm-layout
    # rounds that lower anything fail the bench (module docstring)
    guarded = CohortEngine(apply_fn, batch_align=cfg.cohort_batch_align,
                           client_align=cfg.cohort_client_align,
                           guard=True)
    t_buck = run(functools.partial(_round_batched, engine=guarded),
                 cfg_buck)
    t_glob = run(_round_batched, cfg_glob)
    t_seq = run(_round_sequential, cfg) if seq else None
    # the timed global path pads clients to n_devices + n_air + 1 = c + 1
    ratios = _padding_ratios(schedule, h, batch_cap, max(8, batch_cap),
                             c + 1)
    return t_buck, t_glob, t_seq, ratios, guarded.stats


def _steady(times):
    """Best-of over the post-warmup rounds — the ``timeit_min``
    statistic (see ``benchmarks.common``): scheduler noise only ever
    ADDS time, so the minimum is the right basis for speedup ratios of
    deterministic code at millisecond round times."""
    return float(np.min(times[1:])) if len(times) > 1 else float(times[0])


# --------------------------------------------------------------------------
# Mesh-sharded rows (cohort.sharded.*): one subprocess per device count
# --------------------------------------------------------------------------
def bench_sharded_round(c, payload="mlp", rounds=6, h=5, batch_cap=8,
                        seed=0):
    """Engine-only sharded round timing over a drifting skewed schedule.

    Cohorts are prebuilt so the row isolates what the tentpole changed —
    the engine's ``round()`` dispatch (local updates + in-mesh
    aggregation) — from the host-side pipeline work that is identical
    at every device count.  Runs under whatever device count
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` forced before
    the jax import; the parent process launches one worker per count.
    """
    rng = np.random.default_rng(seed)
    din = PAYLOAD_DIN[payload]
    n = max(4096, c * 48, 11 * 56 * c)
    x = rng.normal(size=(n, din)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    params, apply_fn = PAYLOADS[payload](jax.random.PRNGKey(seed), din)

    schedule = [_make_pools_skewed(n, c, h, rng)]
    for _ in range(rounds - 1):
        schedule.append(_drift(schedule[-1], rng))
    total = sum(len(p) for p in schedule[0])

    eng = CohortEngine(apply_fn, batch_align=max(8, batch_cap),
                       client_align=4, guard=True, sharding="auto")
    build_rng = np.random.default_rng(seed + 1)
    cohorts = [eng.build(x, y, ps, h, build_rng, batch_cap)
               for ps in schedule]

    p, times = params, []
    for co in cohorts:
        t0 = time.perf_counter()
        p, _ = eng.round(p, co, 0.05, total)
        jax.block_until_ready(p)
        times.append(time.perf_counter() - t0)
    return _steady(times), eng


def _sharded_worker(args) -> int:
    """``--sharded-worker`` mode: run one device count, print one JSON
    line (the parent parses stdout's last line)."""
    import json
    c = (args.cohorts or [256])[0]
    rounds = args.rounds or 6
    steady, eng = bench_sharded_round(c, payload=args.payload,
                                      rounds=rounds, h=args.h_local,
                                      batch_cap=args.batch_cap)
    st = eng.stats
    print(json.dumps({
        "devices": len(jax.devices()), "shards": eng.shards,
        "clients": c, "steady_s": steady,
        "clients_per_s": c / steady,
        "padding_ratio": round(st.padding_ratio, 4),
        "shard_pad_clients": st.shard_pad_clients,
        "max_shard_imbalance": round(st.max_shard_imbalance, 4),
        "sharded_dispatches": st.sharded_dispatches,
        "compiled_signatures": st.compiled_signatures,
    }))
    return 0


def _sharded_rows(args) -> int:
    """Emit the ``cohort.sharded.D{n}`` row family and apply the D8
    scaling gate.  Each device count runs in its own subprocess because
    ``--xla_force_host_platform_device_count`` only takes effect before
    the first jax import.  These are CPU-only rows: every worker is
    forced onto virtual host devices (``JAX_PLATFORMS=cpu``), so they
    measure the sharded program's structure, never a chip."""
    import json
    import subprocess
    devices = args.sharded_devices or ([1, 2] if args.smoke
                                       else [1, 2, 4, 8])
    c = (args.cohorts or [None])[0] or (64 if args.smoke else 256)
    rounds = args.rounds or (3 if args.smoke else 6)
    payload = args.payload if args.payload != "logreg" else "mlp"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = {}
    for n in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        env["JAX_PLATFORMS"] = "cpu"  # virtual host devices only
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(root, "src")]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        cmd = [sys.executable, "-m", "benchmarks.cohort_scaling",
               "--sharded-worker", "--cohorts", str(c),
               "--rounds", str(rounds), "--payload", payload,
               "--h-local", str(args.h_local),
               "--batch-cap", str(args.batch_cap)]
        proc = subprocess.run(cmd, env=env, cwd=root,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            # a missing device count is a failed measurement, not a
            # smaller sweep: fail the module so no partial row family
            # becomes a baseline
            print(f"sharded D{n} worker failed:\n{proc.stderr}",
                  file=sys.stderr)
            return 1
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        results[n] = res
        speed = (results[1]["steady_s"] / res["steady_s"]
                 if 1 in results else 1.0)
        print(f"sharded  D={n:2d} C={c:5d}  round {res['steady_s']:7.3f}s"
              f"  ({res['clients_per_s']:8.1f} clients/s, {speed:4.2f}x D1)",
              flush=True)
        row(f"cohort.sharded.D{n}.{payload}.round",
            res["steady_s"] * 1e6,
            f"clients_per_s={res['clients_per_s']:.1f};"
            f"speedup_vs_D1={speed:.2f}x;shards={res['shards']}",
            metrics={"cohort.shards": res["shards"],
                     "cohort.padding_ratio": res["padding_ratio"],
                     "cohort.shard_pad_clients": res["shard_pad_clients"],
                     "cohort.shard_imbalance": res["max_shard_imbalance"],
                     "cohort.sharded_dispatches":
                     res["sharded_dispatches"],
                     "cohort.recompiled_signatures":
                     res["compiled_signatures"]})
    top = max(results) if results else 0
    if args.smoke or top < 8 or 1 not in results:
        return 0
    speed = results[1]["steady_s"] / results[top]["steady_s"]
    cores = len(os.sched_getaffinity(0))
    if cores < 2:
        # a 1-core box serializes the 8 shard programs: the residual
        # speedup is per-shard working-set/fusion only, so the thread-
        # scaling gate is not meaningful here — record, don't fail
        print(f"sharded D{top} speedup {speed:.2f}x on {cores} usable "
              f"core(s): scaling gate skipped (needs >=2)",
              file=sys.stderr)
        return 0
    if speed < 1.5:
        print(f"cohort_scaling: sharded D{top} round speedup "
              f"{speed:.2f}x below the 1.5x target", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    smoke_env = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"
    ap.add_argument("--payload", default="logreg", choices=sorted(PAYLOADS))
    ap.add_argument("--regime", default="both",
                    choices=["uniform", "skewed", "both"])
    ap.add_argument("--cohorts", type=int, nargs="+", default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--h-local", type=int, default=5)
    ap.add_argument("--batch-cap", type=int, default=8)
    ap.add_argument("--skip-seq-above", type=int, default=1024,
                    help="skip the sequential engine beyond this C")
    ap.add_argument("--smoke", action="store_true", default=smoke_env,
                    help="tiny sizes for CI")
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)   # internal: one device count
    ap.add_argument("--sharded-devices", type=int, nargs="+", default=None,
                    help="forced host device counts for cohort.sharded.*")
    args, _ = ap.parse_known_args()

    if args.sharded_worker:
        return _sharded_worker(args)

    cohorts = args.cohorts or ([16] if args.smoke else [16, 64, 256])
    rounds = args.rounds or (3 if args.smoke else 8)
    regimes = (["uniform", "skewed"] if args.regime == "both"
               else [args.regime])

    print(f"# cohort_scaling payload={args.payload} h={args.h_local} "
          f"batch_cap={args.batch_cap} rounds={rounds} smoke={args.smoke}")
    print("# regime, C: bucketed | global | sequential steady round "
          "seconds; speedups vs bucketed; padding ratios")
    worst_skewed_speedup = None
    worst_uniform_speedup = None
    for regime in regimes:
        for c in cohorts:
            seq = c <= args.skip_seq_above
            # small cohorts run millisecond rounds where scheduler noise
            # swamps an 8-round best-of; give the min more samples
            c_rounds = (max(rounds, 20) if c <= 32 and not args.smoke
                        else rounds)
            t_buck, t_glob, t_seq, (r_buck, r_glob), stats = bench_cohort(
                c, payload=args.payload, regime=regime, h=args.h_local,
                batch_cap=args.batch_cap, rounds=c_rounds, seq=seq)
            buck_s, glob_s = _steady(t_buck), _steady(t_glob)
            speed_glob = glob_s / buck_s
            line = (f"{regime:8s} C={c:5d}  bucketed {buck_s:7.3f}s"
                    f"  global {glob_s:7.3f}s ({speed_glob:4.1f}x)")
            derived = (f"speedup_vs_global={speed_glob:.2f}x;"
                       f"pad_bucketed={r_buck:.2f};pad_global={r_glob:.2f}")
            if t_seq is not None:
                seq_s = _steady(t_seq)
                line += f"  seq {seq_s:7.3f}s ({seq_s / buck_s:4.1f}x)"
                derived += f";speedup_vs_seq={seq_s / buck_s:.2f}x"
            print(line, flush=True)
            # the bucketed engine's cumulative stats ride along as row
            # metrics (same names as the repro.obs cohort.* counters)
            row(f"cohort.{regime}.C{c}.{args.payload}.bucketed_round",
                buck_s * 1e6, derived,
                metrics={"cohort.bucket_dispatches":
                         stats.bucket_dispatches,
                         "cohort.recompiled_signatures":
                         stats.compiled_signatures,
                         "cohort.padding_ratio":
                         round(stats.padding_ratio, 4)})
            row(f"cohort.{regime}.C{c}.{args.payload}.global_round",
                glob_s * 1e6, f"pad_global={r_glob:.2f}")
            if regime == "skewed" and c >= 64:   # engine scale (docstring)
                worst_skewed_speedup = (speed_glob
                                        if worst_skewed_speedup is None
                                        else min(worst_skewed_speedup,
                                                 speed_glob))
            if regime == "uniform" and c <= 32:
                # bucketing must never LOSE to the global layout in the
                # regime it did not target: at small C the planner's
                # collapse pass folds near-uniform plans into one
                # global-shaped bucket, so the bound is structural.
                # Larger uniform cohorts legitimately split buckets and
                # sit at parity — tracked in the rows, not gated (the
                # worst observed is ~0.98x, i.e. timing noise)
                worst_uniform_speedup = (speed_glob
                                         if worst_uniform_speedup is None
                                         else min(worst_uniform_speedup,
                                                  speed_glob))
    rc = _sharded_rows(args)
    if (not args.smoke and worst_skewed_speedup is not None
            and worst_skewed_speedup < 2.0):
        # return instead of sys.exit: benchmarks.run must survive one
        # module's failure and keep printing the remaining rows
        print(f"cohort_scaling: skewed-regime speedup "
              f"{worst_skewed_speedup:.2f}x below the 2x target",
              file=sys.stderr)
        return 1
    if (not args.smoke and worst_uniform_speedup is not None
            and worst_uniform_speedup < 1.0):
        print(f"cohort_scaling: uniform-regime speedup "
              f"{worst_uniform_speedup:.2f}x — bucketed rounds regressed "
              f"below the global layout", file=sys.stderr)
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
