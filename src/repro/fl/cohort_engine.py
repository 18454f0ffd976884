"""Size-bucketed, device-resident cohort execution engine.

The PR-1 batched path padded every client to the round's global
``Bmax``: under the paper's adaptive offloading — which deliberately
concentrates samples on the best-placed node — the cohort tensor
becomes mostly zero-mask padding and the vmapped step burns its FLOPs
on masked slots.  :class:`CohortEngine` replaces that layout with the
geometric width buckets of
:func:`repro.data.pipeline.build_bucketed_cohort`:

* one compiled ``cohort_local_update`` dispatch per OCCUPIED bucket
  (clients padded only to their own bucket's width, so padded elements
  stay within a constant factor of real elements at any skew);
* ONE device-side aggregate over the union of all buckets' stacked
  params (:func:`repro.fl.aggregation.fedavg_stacked_multi`, the Pallas
  ``fedavg_agg`` kernel path on TPU) — parameters never round-trip
  through the host between local update and aggregation, and the
  stacked buffers are donated on accelerator backends;
* a bucket-signature cache keyed on ``(C_bucket, H, B_bucket,
  sample_shape, dtype)``: because both bucket axes are quantized to
  geometric grids, churn/offloading drift lands on already-seen
  signatures and recompiles stay at ZERO after warm-up (the
  ``signatures`` set is the engine's own bookkeeping; the actual
  compilation cache is jax's jit cache, which the stable signatures
  keep hitting).

With donation enabled, the single-bucket case (uniform pools) takes a
fused fast path — ``cohort_round_step_donated`` — that runs local
update + aggregate in one compiled call with the params buffer donated,
so the global model updates in place.

Donation contract: with ``donate=True`` (default on non-CPU backends)
:meth:`CohortEngine.round` CONSUMES the params argument — callers must
replace their reference with the returned params and must not hand the
same buffer to two consumers (``RegionTrainer`` keeps a private device
copy for exactly this reason).

Mesh-sharded mode (``sharding="mesh"``, or ``"auto"`` with more than
one visible device) additionally shards every bucket's CLIENT axis over
the mesh's ``data`` axis: the planner pads client counts to multiples
of the shard count (:func:`repro.data.pipeline.plan_buckets`'s
``client_multiple``), each occupied bucket dispatches through
``shard_map`` (version-stable ``repro.compat.shard_map``) running the
per-shard local updates, and the shards' partial eq.-(13) sums combine
in-mesh via :func:`repro.fl.aggregation.shard_weighted_aggregate`
(stacked ``fedavg_agg`` + ``psum``) — parameters still never round-trip
through the host between local update and aggregate.  Bucket signatures
extend with the shard count (signature ⊕ mesh shape) so the
``no_recompile`` guard covers the sharded path too.  On a 1-device mesh
the engine degrades to the exact single-device code path — bit-identical
to ``sharding="off"`` by construction (golden-locked in
``tests/test_mesh_cohort.py``).
"""
from __future__ import annotations

import collections.abc
import dataclasses
import functools
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.data.pipeline import BucketedCohort, build_bucketed_cohort

from .aggregation import (client_finite_mask, fedavg_stacked_multi,
                          shard_weighted_aggregate)
from .client import cohort_local_update, cohort_round_step_donated

SHARDING_MODES = ("auto", "mesh", "off")


@jax.jit
def _tree_sum(parts):
    """Sum a tuple of per-bucket partial-aggregate pytrees leaf-wise."""
    return jax.tree_util.tree_map(
        lambda *leaves: functools.reduce(jnp.add, leaves), *parts)


def h2d_bytes(cohort: BucketedCohort) -> int:
    """Bytes of host arrays one round of ``cohort`` hands to the device:
    every bucket's ``xs``, ``ys`` and ``mask``, and the float32 eq.-(13)
    weight of each of its client slots."""
    return sum(cb.xs.nbytes + cb.ys.nbytes + cb.mask.nbytes
               + cb.sizes.size * np.dtype(np.float32).itemsize
               for cb in cohort.buckets)


@dataclasses.dataclass
class CohortEngineStats:
    """Cumulative counters over an engine's lifetime (all rounds)."""
    rounds: int = 0
    bucket_dispatches: int = 0
    compiled_signatures: int = 0   # distinct bucket shapes seen so far
    real_elements: int = 0         # batch elements actually drawn
    layout_elements: int = 0       # batch elements the padded layout ran
    h2d_bytes: int = 0             # host array bytes handed to the device
    # mesh-sharded path only (all zero / 1.0 on a 1-shard engine):
    sharded_dispatches: int = 0    # bucket dispatches through shard_map
    shard_pad_clients: int = 0     # padding client slots in sharded layouts
    last_shard_imbalance: float = 1.0  # max/mean real elements per shard
    max_shard_imbalance: float = 1.0   # worst round so far

    @property
    def padding_ratio(self) -> float:
        """layout / real batch elements — padded-FLOPs overhead factor."""
        return (self.layout_elements / self.real_elements
                if self.real_elements else 1.0)


class CohortEngine:
    """Executes FL rounds over size-bucketed cohorts, device-resident.

    One engine instance per FL job (``RegionTrainer`` owns one); the
    instance carries the signature bookkeeping and perf counters across
    rounds.  The compiled steps themselves live in jax's global jit
    cache, so even a throwaway engine benefits from previously compiled
    bucket signatures.
    """

    def __init__(self, apply_fn: Callable, batch_align: int = 32,
                 client_align: int = 4, donate: Optional[bool] = None,
                 guard: bool = False, tracer=None, mesh=None,
                 sharding: str = "auto"):
        from repro.obs import NULL_TRACER
        from repro.sharding.specs import data_axis_size
        self.apply_fn = apply_fn
        # repro.obs tracer (RegionTrainer shares its own); the disabled
        # default costs one branch per round + one per bucket dispatch
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.batch_align = max(1, int(batch_align))
        self.client_align = max(1, int(client_align))
        # buffer donation is unsupported on CPU (jax warns and ignores);
        # default it off there and on everywhere else
        self.donate = (jax.default_backend() != "cpu"
                       if donate is None else bool(donate))
        # with guard=True, any round whose full bucket layout has been
        # executed before runs under contracts.no_recompile(): a lowering
        # on a warm signature raises instead of silently re-tracing
        self.guard = bool(guard)
        # client-axis mesh sharding: "off" never shards, "mesh" shards
        # over the given (or default) mesh's data axis, "auto" shards
        # only when more than one device is visible
        if sharding not in SHARDING_MODES:
            raise ValueError(f"sharding={sharding!r} not in "
                             f"{SHARDING_MODES}")
        self.sharding = sharding
        if sharding == "off":
            mesh = None
        elif mesh is None and (sharding == "mesh"
                               or len(jax.devices()) > 1):
            from repro.launch.mesh import make_cohort_mesh
            mesh = make_cohort_mesh()
        if mesh is not None and data_axis_size(mesh) < 1:
            raise ValueError(f"mesh {mesh} has no usable 'data' axis")
        self.mesh = mesh
        # number of client-axis shards each bucket dispatch splits into;
        # 1 (including any 1-device mesh) routes through the exact
        # single-device code path — the bit-identical degrade contract
        self.shards = data_axis_size(mesh)
        self._sharded_step = (self._make_sharded_step()
                              if self.shards > 1 else None)
        self.signatures: set = set()
        self.round_signatures: set = set()
        self.stats = CohortEngineStats()
        # clients quarantined (non-finite update dropped before the
        # aggregate) in the most recent round() call
        self.last_quarantined = 0

    # -- cohort construction ------------------------------------------------
    def build(self, x: np.ndarray, y: np.ndarray,
              pools: Sequence[np.ndarray], n_steps: int,
              rng: np.random.Generator, max_batch: int
              ) -> Optional[BucketedCohort]:
        """Plan + materialize this round's bucketed cohort (host side).

        On a sharded engine the planner additionally pads every bucket's
        client axis to a multiple of the shard count so ``shard_map``
        splits it without a remainder shard."""
        with self.tracer.phase("cohort.build"):
            return build_bucketed_cohort(x, y, pools, n_steps, rng,
                                         max_batch=max_batch,
                                         batch_align=self.batch_align,
                                         client_align=self.client_align,
                                         client_multiple=self.shards)

    # -- execution ----------------------------------------------------------
    def _bucket_signature(self, cb) -> tuple:
        """Shard-stable compilation key for one bucket dispatch: the
        bucket's shape/dtype ⊕ the mesh shape (shard count).  The same
        bucket layout compiles separately per mesh, so both must key the
        signature cache."""
        return cb.xs.shape + (str(cb.xs.dtype), self.shards)

    def _round_signature(self, cohort: BucketedCohort) -> tuple:
        """Everything jax's jit caches key on for one round of this
        engine: the per-bucket shapes/dtypes (local-update dispatches)
        plus the donate flag (selects the fused vs. split program) and
        the shard count (selects the sharded vs. single-device program).
        """
        return (tuple(self._bucket_signature(cb) for cb in cohort.buckets),
                self.donate)

    def _shard_real_elements(self, cohort: BucketedCohort) -> np.ndarray:
        """Real (unmasked) batch elements each shard executes this round.

        ``shard_map`` splits every bucket's client axis into
        ``self.shards`` contiguous blocks; padding clients sit at the
        tail, so the trailing shards run the masked slack.
        """
        per = np.zeros(self.shards, dtype=np.int64)
        for cb in cohort.buckets:
            c = cb.mask.shape[0]
            per_client = cb.mask.reshape(c, -1).sum(axis=1)
            per += per_client.reshape(self.shards,
                                      c // self.shards).sum(axis=1).astype(
                                          np.int64)
        return per

    def _record(self, cohort: BucketedCohort):
        for cb in cohort.buckets:
            self.signatures.add(self._bucket_signature(cb))
        self.round_signatures.add(self._round_signature(cohort))
        st = self.stats
        st.rounds += 1
        st.bucket_dispatches += len(cohort.buckets)
        st.compiled_signatures = len(self.signatures)
        st.real_elements += cohort.real_elements
        st.layout_elements += cohort.layout_elements
        st.h2d_bytes += h2d_bytes(cohort)
        if self.shards > 1:
            st.sharded_dispatches += len(cohort.buckets)
            st.shard_pad_clients += sum(
                cb.xs.shape[0] - len(plan.members)
                for cb, plan in zip(cohort.buckets, cohort.plans))
            per = self._shard_real_elements(cohort)
            imb = (float(per.max() * self.shards / per.sum())
                   if per.sum() else 1.0)
            st.last_shard_imbalance = imb
            st.max_shard_imbalance = max(st.max_shard_imbalance, imb)
            if self.tracer.enabled:
                self.tracer.metrics.histogram(
                    "cohort.shard_imbalance").observe(imb)
                self.tracer.metrics.gauge(
                    "cohort.shard_pad_clients").set(st.shard_pad_clients)

    def round(self, params, cohort: BucketedCohort, lr: float,
              total: int, corrupt: Sequence[int] = (),
              quarantine: bool = False
              ) -> Tuple[object, Sequence[float]]:
        """Train every bucket and aggregate — one FL round on device.

        Returns ``(new_global_params, losses)`` with ``losses`` the real
        clients' mean local losses in canonical cohort order: a
        :class:`ClientLosses` that reads them from the device on first
        access, so the call returns once the round is dispatched.  With
        ``self.donate`` the params argument is consumed (see module
        docstring).

        ``corrupt`` (fault injection: canonical client positions whose
        trained models are NaN-filled AFTER the local update — RNG
        streams untouched) and ``quarantine`` (drop non-finite client
        updates before aggregation, renormalizing the eq.-(13) weights
        over the survivors; the drop count lands in
        :attr:`last_quarantined`) route the round through the split
        single-device path — the fused donated and mesh-sharded programs
        have no masking hook — so a faulted round on a sharded engine
        degrades to one device for that round (documented trade: chaos
        rounds are rare and correctness beats throughput under faults).

        With ``self.guard``, a round whose layout signature is already
        warm runs under :func:`repro.analysis.contracts.no_recompile`;
        a recompile there raises ``ContractViolation`` instead of
        silently burning compile time every round.
        """
        tr = self.tracer
        faulted = bool(corrupt) or quarantine
        self.last_quarantined = 0
        if tr.enabled:
            # recompiles = bucket shapes not yet in the signature cache
            # (the PR-6 no_recompile contract's counter, as a metric)
            fresh = sum(1 for cb in cohort.buckets
                        if self._bucket_signature(cb)
                        not in self.signatures)
            m = tr.metrics
            m.counter("cohort.recompiled_signatures").inc(fresh)
            m.counter("cohort.bucket_dispatches").inc(len(cohort.buckets))
        # a faulted round may select a different compiled program than
        # the warm one (fused -> split), so the guard stands down for it
        warm = (self.guard and not faulted
                and self._round_signature(cohort) in self.round_signatures)
        self._record(cohort)
        if tr.enabled:
            tr.metrics.gauge("cohort.padding_ratio").set(
                self.stats.padding_ratio)
        if faulted:
            def execute(p, c, l, t):
                return self._execute(p, c, l, t, corrupt=corrupt,
                                     quarantine=quarantine)
        else:
            execute = (self._execute_sharded if self.shards > 1
                       else self._execute)
        if warm:
            with contracts.no_recompile(label="CohortEngine.round"):
                return execute(params, cohort, lr, total)
        return execute(params, cohort, lr, total)

    def _trace_dispatch(self, cb, result, t0: float):
        """Emit one ``bucket_dispatch`` span (enabled tracer only).

        ``dur_wall`` is host dispatch time; with
        ``ObsConfig.device_timing`` the result is fenced with
        ``jax.block_until_ready`` first, so it is true device time
        (changes performance, never values — the fence only forces the
        synchronization that would happen later anyway).
        """
        tr = self.tracer
        if tr.device_timing:
            jax.block_until_ready(result)
        c, h, b = cb.xs.shape[0], cb.xs.shape[1], cb.xs.shape[2]
        attrs = dict(clients=c, batch_width=b,
                     real=int(np.count_nonzero(cb.mask)),
                     layout=int(cb.mask.size),
                     mesh_shape=[self.shards])
        if self.shards > 1:
            # per-shard real elements of THIS bucket: shard i runs
            # clients [i*c/n, (i+1)*c/n) — the report's per-shard
            # dispatch-time breakdown apportions dur_wall by these
            per_client = cb.mask.reshape(c, -1).sum(axis=1)
            attrs["shard_real"] = [
                int(v) for v in per_client.reshape(
                    self.shards, c // self.shards).sum(axis=1)]
        tr.span("bucket_dispatch", f"C{c}xH{h}xB{b}",
                dur_wall=time.perf_counter() - t0, **attrs)

    def _execute(self, params, cohort: BucketedCohort, lr: float,
                 total: int, corrupt: Sequence[int] = (),
                 quarantine: bool = False
                 ) -> Tuple[object, Sequence[float]]:
        # host numpy tensors and scalars go into the jitted steps as-is:
        # jit commits them through the C++ shard_args path, which is one
        # copy and no python dispatch — an explicit jnp.asarray per
        # tensor costs ~70us of pure overhead per call at small C (and
        # produces the very same committed f32 buffers)
        lr = np.float32(lr)
        trace = self.tracer.enabled
        corrupt = set(corrupt)
        # eq.-(13) weights over the concatenated client axis, bucket
        # order; padding clients hold size 0 and therefore weight 0
        w = np.concatenate([cb.sizes for cb in cohort.buckets])
        weights = (w / max(1, total)).astype(np.float32)
        dropped: List[int] = []

        with self.tracer.phase("cohort.dispatch"):
            if len(cohort.buckets) == 1 and self.donate and not (
                    corrupt or quarantine):
                # fused fast path: local update + aggregate in ONE dispatch
                # with the params buffer donated (in-place model update).
                # Without donation the split path below wins — XLA:CPU
                # schedules the two smaller programs better than one fused
                # one, and there is no buffer to reuse anyway.
                cb = cohort.buckets[0]
                t0 = time.perf_counter() if trace else 0.0
                new_params, losses = cohort_round_step_donated(
                    self.apply_fn, params, cb.xs, cb.ys, cb.mask, weights, lr)
                if trace:
                    self._trace_dispatch(cb, (new_params, losses), t0)
                loss_parts = [losses]
            else:
                stacked_parts, loss_parts = [], []
                for bi, cb in enumerate(cohort.buckets):
                    t0 = time.perf_counter() if trace else 0.0
                    stacked, losses = cohort_local_update(
                        self.apply_fn, params, cb.xs, cb.ys, cb.mask, lr)
                    if trace:
                        self._trace_dispatch(cb, (stacked, losses), t0)
                    if corrupt:
                        # fault injection: NaN-fill the victims' trained
                        # models AFTER the update — every RNG draw is the
                        # one the clean run makes
                        rows = [row for row, m in
                                enumerate(cohort.plans[bi].members)
                                if m in corrupt]
                        for row in rows:
                            stacked = jax.tree_util.tree_map(
                                lambda a: a.at[row].set(jnp.nan), stacked)
                            losses = losses.at[row].set(jnp.nan)
                    stacked_parts.append(stacked)
                    loss_parts.append(losses)
                if quarantine:
                    weights, dropped = self._quarantine_weights(
                        cohort, stacked_parts, weights)
                    self.last_quarantined = len(dropped)
                if quarantine and weights.sum() <= 0:
                    # every real update was non-finite: keep the previous
                    # model (the split path never donated params)
                    new_params = params
                else:
                    new_params = fedavg_stacked_multi(stacked_parts, weights,
                                                      donate=self.donate)

        return new_params, ClientLosses(cohort, loss_parts, self.tracer,
                                        dropped)

    def _quarantine_weights(self, cohort: BucketedCohort,
                            stacked_parts: List, weights: np.ndarray
                            ) -> Tuple[np.ndarray, List[int]]:
        """Zero the aggregation weight of every non-finite client update.

        One fused :func:`client_finite_mask` reduction per bucket; the
        zeroed weights renormalize inside ``fedavg_stacked`` (it divides
        by the weight sum), so the eq.-(13) mass redistributes over the
        finite survivors.  Returns the adjusted weights and the
        quarantined clients' canonical positions.
        """
        w = np.array(weights, copy=True)
        dropped: List[int] = []
        off = 0
        for cb, stacked, plan in zip(cohort.buckets, stacked_parts,
                                     cohort.plans):
            finite = np.asarray(client_finite_mask(stacked))
            for row in np.nonzero(~finite)[0]:
                if row < len(plan.members):  # real client (not padding)
                    w[off + row] = 0.0
                    dropped.append(int(plan.members[row]))
            off += cb.xs.shape[0]
        return w, dropped

    # -- mesh-sharded execution ---------------------------------------------
    def _make_sharded_step(self):
        """Compile-once factory for the sharded bucket dispatch: a jitted
        ``shard_map`` program running the per-shard local updates and the
        in-mesh eq.-(13) partial aggregate — one program per bucket
        signature ⊕ mesh shape (jax's jit cache keys the shapes).

        The jit carries explicit ``in_shardings`` so the host numpy
        bucket tensors are committed straight into their mesh layout by
        the call itself (no staging ``device_put`` round-trip), and
        donates them — they are rebuilt from the drifted pools every
        round, so XLA may reuse their buffers for the program's
        temporaries instead of allocating a second bucket-sized
        working set.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import shard_map
        from repro.sharding.specs import cohort_step_specs
        apply_fn = self.apply_fn
        in_specs, out_specs = cohort_step_specs()
        repl = NamedSharding(self.mesh, P())
        split = NamedSharding(self.mesh, P("data"))

        def bucket_step(params, xs, ys, mask, weights, lr):
            # per-shard slice of the bucket: local updates over this
            # shard's clients, then the shard's weighted partial sum
            # combined across the data axis — no host round-trip.  The
            # replicated params are cast to varying over "data" first:
            # the gradient of an unvarying value is psum'd over the axis,
            # which would fold every shard's gradients into each client
            params = jax.lax.pcast(params, "data", to="varying")
            stacked, losses = cohort_local_update(apply_fn, params, xs,
                                                  ys, mask, lr)
            part = shard_weighted_aggregate(stacked, weights,
                                            axis_names=("data",))
            return part, losses

        return jax.jit(
            shard_map(bucket_step, mesh=self.mesh, in_specs=in_specs,
                      out_specs=out_specs),
            in_shardings=(repl, split, split, split, split, repl),
            donate_argnums=(1, 2, 3, 4))

    def _execute_sharded(self, params, cohort: BucketedCohort, lr: float,
                         total: int) -> Tuple[object, "ClientLosses"]:
        """Dispatch every bucket through the sharded step.

        Weights are GLOBALLY normalized on the host (padding clients
        carry weight 0), so each bucket's shard_map call returns that
        bucket's partial eq.-(13) sum; multi-bucket rounds combine the
        partials with one extra leaf-wise add.  The model stays
        replicated across the mesh between rounds — only the first
        round (or an externally installed model) pays the broadcast.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        trace = self.tracer.enabled
        lr = jnp.float32(lr)
        repl = NamedSharding(self.mesh, P())
        params = jax.device_put(params, repl)
        w = np.concatenate([cb.sizes for cb in cohort.buckets])
        w = w.astype(np.float64)
        weights = (w / max(1.0, w.sum())).astype(np.float32)

        parts, loss_parts = [], []
        off = 0
        with self.tracer.phase("cohort.dispatch"):
            for cb in cohort.buckets:
                c = cb.xs.shape[0]
                wb = weights[off:off + c]
                off += c
                t0 = time.perf_counter() if trace else 0.0
                # host numpy tensors go in directly: the step's
                # in_shardings commit them onto the mesh, and the buffers
                # are donated
                part, losses = self._sharded_step(
                    params, cb.xs, cb.ys, cb.mask, wb, lr)
                if trace:
                    self._trace_dispatch(cb, (part, losses), t0)
                parts.append(part)
                loss_parts.append(losses)
            new_params = parts[0] if len(parts) == 1 else _tree_sum(
                tuple(parts))
        return new_params, ClientLosses(cohort, loss_parts, self.tracer)

    @staticmethod
    def _scatter_losses(plans, n_clients: int,
                        loss_parts: List) -> List[float]:
        """Map per-bucket loss vectors back to canonical client order."""
        out = np.zeros(n_clients, dtype=np.float64)
        for plan, losses in zip(plans, loss_parts):
            vals = np.asarray(losses)[:len(plan.members)]
            out[list(plan.members)] = vals
        return [float(v) for v in out]


class ClientLosses(collections.abc.Sequence):
    """A round's real clients' mean local losses in canonical cohort
    order, less the quarantined ones, read from the device on first
    access.

    ``len()`` is known on the host and reads nothing, so a caller can
    launch the next round before this one's results exist.  The first
    index, iteration or ``numpy.asarray`` reads every bucket's loss
    vector at once, inside the phase ``cohort.wait`` tagged with the
    region and round that launched the round, and keeps the floats;
    later accesses read nothing.  ``jax.block_until_ready`` waits for
    the vectors without reading them."""

    def __init__(self, cohort: BucketedCohort, loss_parts: List, tracer,
                 dropped: Sequence[int] = ()):
        # the plans only: the cohort's host tensors are freed on return
        self._plans = cohort.plans
        self._n_clients = cohort.n_clients
        self._dropped = frozenset(dropped)
        self._parts = loss_parts
        self._values: Optional[List[float]] = None
        self._tracer = tracer
        self._tag = dict(region=tracer.ctx_region, round=tracer.ctx_round)

    def _read(self) -> List[float]:
        if self._values is None:
            with self._tracer.phase("cohort.wait", **self._tag):
                values = CohortEngine._scatter_losses(
                    self._plans, self._n_clients, self._parts)
            self._values = [v for i, v in enumerate(values)
                            if i not in self._dropped]
            self._parts = None
        return self._values

    def __len__(self) -> int:
        return self._n_clients - len(self._dropped)

    def __getitem__(self, i):
        return self._read()[i]

    def __iter__(self):
        return iter(self._read())

    def __array__(self, dtype=None, copy=None):
        return np.array(self._read(), dtype=dtype)

    def __eq__(self, other):
        # as the list of floats it stands for: round losses compare with
        # ``==`` (the mesh engine's bit-identity lock does)
        if isinstance(other, (ClientLosses, list)):
            return self._read() == list(other)
        return NotImplemented

    def block_until_ready(self) -> "ClientLosses":
        if self._parts is not None:
            jax.block_until_ready(self._parts)
        return self
