"""Serving traffic: the gateway answers every region's arrivals from the
region models, advancing simulated time slot by slot as fast as the host
goes.

Set-up builds the engine from the cell's data (no training: the gateway
only reads the models), puts the evaluation rows the requests draw from in
the seed's order, installs each region's weights from the seed (one stream
per region, so a request answered by the wrong region's model reads
wrong), and compiles every padded batch width of every region's
predict.  The window runs ``ServeGateway.run`` over successive chunks of
simulated time; a chunk is one link-refresh period, so the chunks join
into one continuous session.  After the window the plain reference
computes the logits of a seeded sample of the answered requests, each
with the weights of the region that answered it.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench.harness import core, inputs
from bench.harness.readings import Readings
from bench.harness.scenario import build_fl_config, build_scenario
from bench.reference import compare, convnet

UNITS = {"serve_requests_per_s": "requests/s"}
#: requests compared with the reference per run
SAMPLE = 4096
BLOCK = 512
#: evaluation rows whose mean logit the classifier's bias cancels
CENTRE_ROWS = 256


def serving_weights(config, seed, region: int, rows):
    """Region ``region``'s served model: He-normal weights from
    ``(seed, region + 1)``, with the classifier's bias set so that the
    mean logit over the region's first ``CENTRE_ROWS`` evaluation rows is
    zero.  At He initialisation this deep ReLU net answers one class for
    nearly every input, with margins of a logit or more; centred, it
    answers every class and has near-ties, which is what a comparison of
    served classes reads."""
    params = inputs.weights(config, seed, region + 1)
    mean = jax.jit(lambda p, x: convnet.forward(config, p, x).mean(0))(
        params, np.asarray(rows[:CENTRE_ROWS], np.float32))
    params[-1] = dict(params[-1], b=params[-1]["b"] - mean)
    return params


def recording_backend(trainers):
    """The program's CNN backend, keeping each batch's answers and the
    index of its first request in the gateway's completion order."""
    from repro.serve.backends import CNNBackend

    class Recording(CNNBackend):
        gateway = None

        def __init__(self, trainers):
            super().__init__(trainers)
            self.batches = []

        def predict(self, model_region, x, samples):
            start = len(self.gateway.completed)
            preds = super().predict(model_region, x, samples)
            self.batches.append((start, model_region, preds))
            return preds

    return Recording(trainers)


def set_up(cell, config, seed):
    from repro.data.pipeline import next_geometric
    from repro.serve.gateway import ServeGateway
    from repro.sim import SAGINEngine
    import jax.numpy as jnp

    scn = build_scenario(cell["scenario"])
    engine = SAGINEngine(scn, fl=build_fl_config(cell, config))
    rng = np.random.default_rng(seed)
    for j, t in enumerate(engine.trainers):
        x, y = inputs.shuffle_rows(rng, t.x_eval, t.y_eval)
        t.params = inputs.to_program(serving_weights(config, seed, j, x),
                                     t.params)
        t.x_eval, t.y_eval = jnp.asarray(x), jnp.asarray(y)
    backend = recording_backend(engine.trainers)
    gw = ServeGateway(engine, serve=scn.serve, backend=backend)
    backend.gateway = gw
    shape = tuple(config["input_shape"])
    grid = sorted({next_geometric(n, scn.serve.batch_align)
                   for n in range(1, scn.serve.max_batch + 1)})
    for j in range(len(engine.trainers)):
        for w in grid:
            backend.predict(j, np.zeros((w,) + shape, np.float32),
                            np.zeros(w, np.int64))
    backend.batches.clear()
    return engine, gw, backend


def serve_for(gw, seconds: float, chunk: float, t_sim: float):
    """Chunks of simulated time until ``seconds`` of wall time passed;
    (chunks, elapsed s, next simulated start)."""
    t0 = time.perf_counter()
    chunks = 0
    while True:
        gw.run(chunk, t0=t_sim)
        t_sim += chunk
        chunks += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return chunks, elapsed, t_sim


def answered(gw, backend, first: int):
    """``[(request, model region, served class)]`` for every request
    completed from position ``first`` on."""
    done = gw.completed
    starts = [b[0] for b in backend.batches] + [len(done)]
    out = []
    for (start, region, preds), end in zip(backend.batches, starts[1:]):
        for k, req in enumerate(done[start:end]):
            if start + k >= first:
                out.append((req, region, int(preds[k])))
    return out


def reference_gap(config, seed, sample, x_rows, prec="f32"):
    """Widest gap of the sampled answers against the reference's logits;
    with ``prec`` other than f32 (a control), the answers are the
    control's own argmax instead of the program's."""
    by_region = {}
    for i, (req, region, cls) in enumerate(sample):
        by_region.setdefault(region, []).append(i)
    logits = np.zeros((len(sample), config["n_classes"]), np.float64)
    control = np.zeros(len(sample), np.int64)
    fwd = jax.jit(lambda p, x: convnet.forward(config, p, x, "f32"))
    low = (jax.jit(lambda p, x: convnet.forward(config, p, x, prec))
           if prec != "f32" else None)
    for region, rows in by_region.items():
        params = serving_weights(config, seed, region, x_rows[region])
        for k in range(0, len(rows), BLOCK):
            idx = rows[k:k + BLOCK]
            x = np.stack([x_rows[sample[i][0].region][sample[i][0].sample]
                          for i in idx])
            pad = BLOCK - len(idx)
            xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                             x.dtype)]) if pad else x
            logits[idx] = np.asarray(fwd(params, xp))[:len(idx)]
            if low is not None:
                control[idx] = np.argmax(
                    np.asarray(low(params, xp), np.float32)[:len(idx)], -1)
    answers = (control if low is not None
               else np.asarray([c for _, _, c in sample]))
    return compare.served_gap(logits, answers)


def draw(records, seed: int, n: int = SAMPLE):
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5E4E])
    if len(records) <= n:
        return list(records)
    idx = np.sort(rng.choice(len(records), size=n, replace=False))
    return [records[i] for i in idx]


def run(ctx):
    cell, config = ctx.cell, ctx.config
    engine, gw, backend = set_up(cell, config, ctx.seed)
    chunk = float(cell["chunk_s"])
    ctx.set_up_done()

    first = len(gw.completed)
    admitted0 = gw._rid
    readings = None
    e2e = {}
    if ctx.trace:
        from bench.harness.trace import TraceData, capture, find_xplane
        wall0, batches0 = gw.wall_infer, gw.n_batches
        with capture(ctx.trace_dir):
            chunks, elapsed, _ = serve_for(gw, cell["trace_seconds"], chunk,
                                           0.0)
        readings = Readings(
            kind="serve", chips=ctx.chips, config=config, peaks=ctx.peaks,
            trace=TraceData.load(find_xplane(ctx.trace_dir)),
            counts=dict(served=len(gw.completed) - first,
                        batches=gw.n_batches - batches0,
                        wall_infer=gw.wall_infer - wall0))
    else:
        c0 = ctx.compiles.mark()
        chunks, elapsed, _ = serve_for(gw, ctx.seconds, chunk, 0.0)
        c1 = ctx.compiles.mark()
        core.say(f"# window: {chunks} chunks of {chunk} simulated s, "
                 f"{len(gw.completed) - first} requests in {elapsed!r} s; "
                 f"compiles inside it: {c1[1] - c0[1]} "
                 f"({c1[0] - c0[0]!r} s)")
        e2e["serve_requests_per_s"] = (len(gw.completed) - first) / elapsed
    admitted = gw._rid - admitted0
    served = len(gw.completed) - first
    records = answered(gw, backend, first)
    memory = core.memory_peak_bytes(ctx.devices)
    x_rows = [np.asarray(x) for x in gw._x]

    del engine, gw, backend
    gc.collect()
    sample = draw(records, ctx.seed)
    t0 = time.perf_counter()
    gap = reference_gap(config, ctx.seed, sample, x_rows)
    core.say(f"# reference: {time.perf_counter() - t0!r} s over "
             f"{len(sample)} answers")
    checks = [core.check("served_gap", gap, cell["limits"]["served_gap"]),
              core.check("unanswered", float(admitted - len(records)), 0)]
    return dict(e2e=e2e, readings=readings, attempted=admitted,
                failed=admitted - served, memory=memory, checks=checks)
