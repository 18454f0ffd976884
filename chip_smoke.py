"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py             # one chip: train, check eq. (13), serve
    python chip_smoke.py --chips 4   # four chips: sharded vs single-device

One chip: federated training of the paper's VGG-11 at published widths
over the ``multi_region`` scenario (``SAGINEngine`` -> ``RegionTrainer``
-> ``CohortEngine`` -> eq.-(13) aggregate -> cross-region merge) for
three batched rounds with the paper's population, a check that the
round's aggregate compiles to the Pallas ``fedavg_agg`` kernel and agrees
with the jnp reference, then a short serving window from the trained
region models through ``ServeGateway``.

``--chips 4`` runs only the multi-chip check: one bucketed round of the
same VGG-11 cohort with its client axis sharded over four chips, and the
same round on chip 0 alone, both as one whole-bucket program and as the
per-chip slices, compared leaf by leaf and client by client.

Everything runs in this one process.  Any failure exits non-zero with
its traceback.  With no TPU the script fails at its first step.  On
success the last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: FLConfig of the training phase: the paper's CIFAR-10 VGG-11 and its
#: population (FLConfig defaults: 50 devices, 5 air nodes, H=5, B<=32);
#: 20% of the training set gives each device about 180 samples, enough
#: to fill every local batch.  VGG-11 has no batch norm: at FLConfig's
#: default lr of 0.05 its first local steps diverge to NaN on any
#: backend (initial gradient norm ~67), while 0.01 trains.
FL = dict(dataset="cifar10", train_fraction=0.2, execution="batched",
          lr=0.01, seed=0)
SCENARIO = "multi_region"
ROUNDS = 3
SERVE_SECONDS = 120.0
#: the tolerance of the sharded-vs-single-device lock in
#: tests/test_mesh_cohort.py
RTOL, ATOL = 1e-5, 1e-6
#: what a compiled program holds where a Pallas kernel runs on the TPU
KERNEL_MARKER = "tpu_custom_call"


def say(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int) -> dict:
    """The devices JAX sees, or an error when they are not TPU chips."""
    devs = jax.devices()
    d = devs[0]
    say(f"# jax {jax.__version__}: platform={d.platform} "
        f"kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); this script runs on the chip only")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips needed, "
                         f"{len(devs)} visible")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileClock:
    """Sums XLA backend-compile seconds reported by ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def train(clock: CompileClock):
    """``ROUNDS`` batched federated rounds of ``SCENARIO``; returns the
    engine.  One ``run(1)`` per round is ``run(ROUNDS)`` split at round
    boundaries (the continuation contract of ``SAGINEngine.run``)."""
    from repro.fl import FLConfig
    from repro.models.cnn import param_count
    from repro.sim import SAGINEngine

    t0 = time.perf_counter()
    engine = SAGINEngine(SCENARIO, fl=FLConfig(**FL))
    trainers = engine.trainers
    say(f"# engine: {SCENARIO}, {len(trainers)} regions, "
        f"dataset={FL['dataset']}, params={param_count(trainers[0].params)}, "
        f"set-up {time.perf_counter() - t0:.1f} s")
    for r in range(ROUNDS):
        c0, t0 = clock.seconds, time.perf_counter()
        engine.run(1, final_merge=(r == ROUNDS - 1))
        jax.block_until_ready([t.params for t in trainers])
        wall = time.perf_counter() - t0
        label = " (includes compilation)" if r == 0 else ""
        say(f"round {r}: wall {wall:.2f} s{label}, "
            f"compile {clock.seconds - c0:.2f} s")
        for name, res in engine.fl_results.items():
            say(f"  {name}: loss {res.losses[-1]:.4f} "
                f"acc {res.accuracies[-1]:.4f}")
            if not np.isfinite(res.losses[-1]):
                raise RuntimeError(f"round {r}: non-finite loss in {name}")
        dispatches = sum(t.cohort_engine.stats.bucket_dispatches
                         for t in trainers)
        say(f"  bucket dispatches so far: {dispatches}")
    if engine.global_params is None or not _all_finite(engine.global_params):
        raise RuntimeError("the global model is missing or non-finite")
    say(f"# global model after {len(engine.merges)} merge(s): finite")
    return engine


def check_aggregate(engine) -> None:
    """Compile the round's ``fedavg_stacked_multi`` at its real shapes,
    require the Pallas kernel in the program, and compare its result on
    random client models with the jnp reference."""
    from repro.fl.aggregation import fedavg_stacked_multi
    from repro.kernels.fedavg_agg import ref

    eng = engine.trainers[0].cohort_engine
    bucket_sigs, _ = max(eng.round_signatures,
                         key=lambda s: sum(b[0] for b in s[0]))
    clients = [sig[0] for sig in bucket_sigs]
    leaves, treedef = jax.tree_util.tree_flatten(engine.global_params)
    keys = iter(jax.random.split(jax.random.PRNGKey(FL["seed"]),
                                 len(clients) * len(leaves) + 1))
    parts = tuple(
        treedef.unflatten([jax.random.normal(next(keys), (c,) + a.shape)
                           for a in leaves])
        for c in clients)
    c_total = sum(clients)
    weights = jax.random.uniform(next(keys), (c_total,))
    text = (jax.jit(fedavg_stacked_multi)
            .lower(parts, weights).compile().as_text())
    if KERNEL_MARKER not in text:
        raise RuntimeError(f"the compiled aggregate holds no "
                           f"{KERNEL_MARKER}: the Pallas kernel did not run")
    say(f"# aggregate over buckets {clients} (C={c_total}) compiles with "
        f"{KERNEL_MARKER}")
    got = fedavg_stacked_multi(parts, weights)
    w = weights / jnp.sum(weights)
    worst = 0.0
    for g, *leaves in zip(jax.tree_util.tree_leaves(got),
                          *(jax.tree_util.tree_leaves(p) for p in parts)):
        want = ref.weighted_aggregate(jnp.concatenate(leaves), w)
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)
        worst = max(worst, float(jnp.max(jnp.abs(g - want))))
    say(f"# aggregate matches the jnp reference (max |diff| {worst:.3g})")


def serve(engine) -> None:
    """A short serving window from the trained region models."""
    from repro.serve.gateway import ServeGateway, resolve_serve

    fl = engine.fl_config
    cfg = resolve_serve(fl.serve if fl.serve is not None
                        else engine.scenario.serve)
    gw = ServeGateway(engine, serve=cfg)
    t0 = time.perf_counter()
    report = gw.run(SERVE_SECONDS)
    say(f"serve: {report.served} requests in {SERVE_SECONDS:.0f} simulated "
        f"s, wall {time.perf_counter() - t0:.2f} s (includes compilation), "
        f"p50 {report.latency_p50:.3f} s p99 {report.latency_p99:.3f} s "
        f"(simulated), served acc {report.served_accuracy:.4f}")
    if report.served == 0:
        raise RuntimeError("the gateway answered no request")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def _sliced_round(apply_fn, params, cohort, lr, n_slices):
    """The sharded round's arithmetic on one chip: every bucket cut into
    ``n_slices`` client slices, each trained by the same ``(C / n)``-client
    program a chip runs under ``shard_map``, then one eq.-(13) aggregate.
    Returns (params, per-client losses in canonical order)."""
    from repro.fl.aggregation import fedavg_stacked_multi
    from repro.fl.client import cohort_local_update
    from repro.fl.cohort_engine import CohortEngine

    parts, loss_parts = [], []
    for cb in cohort.buckets:
        width = cb.xs.shape[0] // n_slices
        bucket_losses = []
        for s in range(0, cb.xs.shape[0], width):
            cut = slice(s, s + width)
            stacked, losses = cohort_local_update(
                apply_fn, params, cb.xs[cut], cb.ys[cut], cb.mask[cut],
                np.float32(lr))
            parts.append(stacked)
            bucket_losses.append(losses)
        loss_parts.append(jnp.concatenate(bucket_losses))
    w = np.concatenate([cb.sizes for cb in cohort.buckets])
    new = fedavg_stacked_multi(parts, (w / w.sum()).astype(np.float32))
    return new, CohortEngine._scatter_losses(cohort.plans, cohort.n_clients,
                                             loss_parts)


def _max_diffs(a, b) -> tuple:
    """Max |a - b| over the client losses, the same relative to ``b``'s
    losses, and max |a - b| over every parameter of two
    ``(params, losses)`` results."""
    (pa, la), (pb, lb) = a, b
    d = np.abs(la - lb)
    return (float(np.max(d)), float(np.max(d / np.abs(lb))),
            max(float(np.max(np.abs(x - y)))
                for x, y in zip(jax.tree_util.tree_leaves(pa),
                                jax.tree_util.tree_leaves(pb))))


def sharded_round(n_chips: int) -> None:
    """One bucketed VGG-11 round with the client axis sharded over
    ``n_chips`` chips against the same round on chip 0 alone, all at
    float32 matmul precision.

    On one chip, the same round run as one 64-client program and as four
    16-client programs already differ in the last float32 bits, and five
    SGD steps of VGG-11 amplify that to ~1e-5 in a few client losses.  So
    the sharded round is held, at the float32 tolerance of
    tests/test_mesh_cohort.py, to the single-chip round at the width each
    chip runs; its distance from the whole-bucket round is printed beside
    the single chip's own distance between the two widths."""
    from repro.data import FederatedPools, make_dataset, partition
    from repro.fl import FLConfig
    from repro.fl.cohort_engine import CohortEngine
    from repro.launch.mesh import make_cohort_mesh
    from repro.models.cnn import build_model

    cfg = FLConfig(**FL)
    ds = make_dataset(cfg.dataset, seed=cfg.seed,
                      train_fraction=cfg.train_fraction)
    pools = FederatedPools.from_partitions(
        partition(ds, n_devices=cfg.n_devices, seed=cfg.seed), cfg.n_air)
    node_pools = [pools.ground_all(k) for k in range(cfg.n_devices)]
    total = sum(len(p) for p in node_pools)
    params, apply_fn = build_model(ds.name, jax.random.PRNGKey(cfg.seed),
                                   image_shape=ds.x_train.shape[1:])

    def fresh():
        return jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True),
                                      params)

    def host(new, losses):
        return (jax.tree_util.tree_map(np.asarray, new), np.asarray(losses))

    results = {}
    with jax.default_matmul_precision("float32"):
        for mode in ("mesh", "off"):
            kw = dict(sharding=mode)
            if mode == "mesh":
                kw["mesh"] = make_cohort_mesh(n_chips)
            eng = CohortEngine(apply_fn, batch_align=cfg.cohort_batch_align,
                               client_align=cfg.cohort_client_align, **kw)
            cohort = eng.build(ds.x_train, ds.y_train, node_pools,
                               cfg.h_local, np.random.default_rng(7),
                               max_batch=cfg.batch_cap)
            t0 = time.perf_counter()
            new, losses = eng.round(fresh(), cohort, cfg.lr, total)
            results[mode] = host(*jax.block_until_ready((new, losses)))
            say(f"{mode}: shards={eng.shards} buckets="
                f"{[cb.xs.shape[0] for cb in cohort.buckets]} wall "
                f"{time.perf_counter() - t0:.2f} s (includes compilation)")
            if mode == "mesh":
                # the host tensors the sharded round consumed, sliced as
                # its shards were, trained on chip 0
                t0 = time.perf_counter()
                results["sliced"] = host(*jax.block_until_ready(
                    _sliced_round(apply_fn, fresh(), cohort, cfg.lr,
                                  n_chips)))
                say(f"off, {n_chips} client slices per bucket: wall "
                    f"{time.perf_counter() - t0:.2f} s (includes "
                    f"compilation)")
    # NaN == NaN passes assert_allclose: require finite results first
    for mode, (p, losses) in results.items():
        if not (np.all(np.isfinite(losses)) and _all_finite(p)):
            raise RuntimeError(f"{mode}: non-finite losses or params")
    # every distance is printed before any is held to the tolerance
    for name in ("mesh", "sliced"):
        dl, rl, dp = _max_diffs(results[name], results["off"])
        say(f"{name} vs the whole-bucket single-device round: losses max "
            f"|diff| {dl:.3g} (rel {rl:.3g}), params max |diff| {dp:.3g}")
    dl, rl, dp = _max_diffs(results["mesh"], results["sliced"])
    say(f"mesh vs sliced: losses max |diff| {dl:.3g} (rel {rl:.3g}), "
        f"params max |diff| {dp:.3g}")
    (p_mesh, l_mesh), (p_cut, l_cut) = results["mesh"], results["sliced"]
    np.testing.assert_allclose(l_mesh, l_cut, rtol=RTOL, atol=ATOL)
    for a, b in zip(jax.tree_util.tree_leaves(p_cut),
                    jax.tree_util.tree_leaves(p_mesh)):
        np.testing.assert_allclose(b, a, rtol=RTOL, atol=ATOL)
    say(f"sharded == single-device at the shard width within rtol={RTOL} "
        f"atol={ATOL}: {len(l_mesh)} client losses and every parameter")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-vs-single-device check")
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    from repro.compat import setup_compile_cache
    say(f"# {setup_compile_cache()}")
    if args.chips == 4:
        sharded_round(args.chips)
    else:
        clock = CompileClock()
        engine = train(clock)
        check_aggregate(engine)
        serve(engine)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
