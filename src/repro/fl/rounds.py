"""End-to-end FL simulation driver (Section VI).

Couples the analytic SAGIN orchestration (latency, offloading, handover)
with *real* federated training on a (synthetic) dataset: every node that
holds samples runs H local SGD iterations, models are aggregated with the
eq.-(13) lambda weights, and the wall clock advances by the optimized round
latency. Produces accuracy-versus-training-time curves (Figs. 4, 6, 7).

The unit of execution is :class:`RegionTrainer` — ONE region's complete
FL job (dataset, pools, model, orchestrator), advanced one round at a
time via :meth:`RegionTrainer.step` (:meth:`~RegionTrainer.launch`, then
:meth:`~RegionTrainer.settle`).  :func:`run_fl` is the thin
single-region wrapper that steps a trainer ``n_rounds`` times; the
multi-region :class:`~repro.sim.engine.SAGINEngine` launches many
trainers' rounds through its event heap, each before it settles the one
launched before it, and merges their models across regions
(``fl.aggregation.staleness_weighted_merge``).

Region addressing: with a scenario, all of a region's streams — dataset
sample draw, partition shuffle, orchestrator satellite draws, dynamics
events — are rooted at ``region_seed(cfg.seed, cfg.region_index)``
(see :func:`repro.sim.engine.region_streams`), so
``run_fl(FLConfig(scenario=s, region_index=i))`` reproduces engine
region ``i`` exactly.  The MODEL INIT alone stays keyed on the global
``cfg.seed``: hierarchical FL requires every region to descend from one
broadcast initial model for cross-region merges to be meaningful.

Execution modes (``FLConfig.execution``):

* ``"batched"`` — the cohort engine
  (:class:`repro.fl.cohort_engine.CohortEngine`). Every data-holding
  node's (H, B) batch stack is drawn through the shared RNG stream and
  partitioned into geometric batch-width buckets
  (``repro.data.pipeline.build_bucketed_cohort``): each occupied bucket
  trains in one compiled ``cohort_local_update`` dispatch padded only
  to ITS OWN width, and all buckets' stacked params aggregate in a
  single device-side ``fedavg_stacked_multi`` call (the Pallas
  ``fedavg_agg`` kernel path on TPU) — no host round-trip of
  parameters inside the round, stacked buffers donated on accelerator
  backends. Both bucket axes are quantized to geometric grids
  (``cohort_batch_align * 2^k`` batch slots,
  ``cohort_client_align * 2^k`` clients), so churn/offloading drift
  re-lands on already-compiled bucket signatures and recompiles stay at
  zero after warm-up; padded FLOPs stay within a constant factor of
  real FLOPs at ANY pool skew (the PR-1 global-``Bmax`` layout, kept as
  ``cohort_bucketing="global"`` for comparison, degrades with skew
  instead).  With more than one visible device (or
  ``cohort_sharding="mesh"``) each bucket's client axis additionally
  shards over the mesh's ``data`` axis through ``shard_map`` with
  in-mesh psum aggregation — see the cohort-engine module docstring.
* ``"sequential"`` — the reference loop: one ``local_update`` dispatch
  per node, host-side ``fedavg`` over a model list.
* ``"auto"`` (default) — ``"batched"`` on accelerator backends where the
  vmapped cohort step is the whole point, ``"sequential"`` on CPU where
  XLA's grouped per-client conv gradients make the vmapped step slower
  than the loop for conv payloads (see ``benchmarks/cohort_scaling.py``
  for the regimes where batched wins even on CPU).

Both modes draw mini-batches from the same RNG stream in the same node
order (ground 0..K-1, then air, then satellite), so at equal seeds they
produce the same accuracy trajectory up to float reduction-order noise.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional, Sequence, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import SAGINOrchestrator, build_default_sagin
from repro.core.handover import replan_after_loss
from repro.core.network import SAGIN
from repro.data import FederatedPools, make_dataset, partition
from repro.models.cnn import build_model, model_bits

from .aggregation import fedavg, fedavg_stacked
from .client import cohort_local_update, evaluate, local_update
from .federation import FederationConfig, RegionFedState

if TYPE_CHECKING:  # pragma: no cover - circular-import guard
    from repro.core.constellation import AccessInterval
    from repro.obs import ObsConfig, Tracer
    from repro.scenarios.registry import Scenario
    from repro.serve.workload import ServeConfig


@dataclasses.dataclass
class FLConfig:
    dataset: str = "mnist"
    iid: bool = True
    alpha: float = 0.8
    n_devices: int = 50
    n_air: int = 5
    n_rounds: int = 30
    h_local: int = 5
    lr: float = 0.05
    batch_cap: int = 32
    strategy: str = "adaptive"     # adaptive|none|air_ground|ground_space|static|proportional
    rayleigh: bool = True
    train_fraction: float = 0.05   # shrink dataset for CPU-speed runs
    eval_size: int = 1024
    seed: int = 0
    use_constellation: bool = False  # True: drive T_i from Walker-Star
    scenario: Optional[str] = None   # named preset from repro.scenarios
    region_index: int = 0            # which scenario region this FL job serves
    execution: str = "auto"        # auto|batched|sequential (module docstring)
    cohort_batch_align: int = 32   # batched mode: bucket-width grid unit
    cohort_bucketing: str = "geometric"  # geometric|global (module docstring)
    cohort_client_align: int = 4   # batched mode: bucket client-count grid
    # batched mode: arm contracts.no_recompile() around every round whose
    # bucket layout is already warm — a recompile on a seen signature
    # raises ContractViolation instead of silently re-tracing each round
    guard_recompiles: bool = False
    # batched mode: shard each bucket's client axis over the device
    # mesh's "data" axis ("mesh"), never shard ("off"), or shard exactly
    # when more than one device is visible ("auto", the default — a
    # single-device host keeps the bit-identical legacy path)
    cohort_sharding: str = "auto"  # auto|mesh|off
    # Cross-region federation override for SAGINEngine FL mode: a
    # FederationConfig replaces the scenario's wholesale; a bare policy
    # name (e.g. "soft_async") keeps the scenario's cadence/topology/
    # half-life and swaps only the policy; None defers to the scenario.
    # Ignored by single-region run_fl (nothing to merge with).
    federation: Optional["FederationConfig | str"] = None
    # Observability (repro.obs): an ObsConfig, a bare JSONL output path
    # string, or None (disabled — the default, a no-op null tracer).
    # Wins over Scenario.obs when both are set.  The tracer only
    # observes: trajectories are bit-identical with obs on or off.
    obs: Optional["ObsConfig | str"] = None
    # Serving-gateway wiring (repro.serve): a ServeConfig shaping the
    # request workload / router / batching a ServeGateway attached to
    # this run uses.  Wins over Scenario.serve; None defers to the
    # scenario (and ultimately to ServeConfig() defaults).  Training
    # itself never reads this — serving is strictly read-only.
    serve: Optional["ServeConfig"] = None
    # Quarantine non-finite client updates before aggregation (weights
    # renormalize over the finite survivors).  None (default) arms it
    # exactly when a fault injector is attached (the chaos path) and
    # keeps the clean path free of the per-client finiteness sync;
    # True/False force it either way.
    quarantine: Optional[bool] = None

    def resolved_execution(self) -> str:
        if self.execution == "auto":
            return ("batched" if jax.default_backend() != "cpu"
                    else "sequential")
        return self.execution


@dataclasses.dataclass
class FLResult:
    config: FLConfig
    times: List[float]             # cumulative training time (s); under the
    #                              engine's merge barriers this also includes
    #                              barrier wait + ISL merge costs
    accuracies: List[float]        # on this region's held-out eval batch
    losses: List[float]            # mean TRAIN loss across this round's
    #                              training nodes; NaN for a round in which
    #                              no node trained (never silently the eval
    #                              loss).  The NaN sentinel is kept for
    #                              backward compatibility — consult
    #                              ``participated`` instead of nan-sniffing.
    latencies: List[float]         # realized per-round latency
    cases: List[int]
    layer_portions: List[Dict[str, float]]  # data share per layer per round
    # True when >= 1 node trained in the round (equivalently: losses[r]
    # is finite).  The explicit mask downstream consumers should use for
    # participation instead of inferring it from the NaN loss sentinel.
    participated: List[bool] = dataclasses.field(default_factory=list)

    def time_to_accuracy(self, target: float) -> Optional[float]:
        for t, a in zip(self.times, self.accuracies):
            if a >= target:
                return t
        return None


@dataclasses.dataclass
class _Launched:
    """A launched round awaiting :meth:`RegionTrainer.settle`: what the
    host decided at launch, and the device values still to read."""
    round: int
    rec: object                    # the round's RoundRecord
    clock: float                   # the region's clock after the round
    losses: Sequence[float]        # client losses (ClientLosses or floats)
    acc: object                    # device scalar: accuracy on the eval set
    portions: Dict[str, float]     # data share per layer


def _build_orchestrator(cfg: FLConfig, sagin: SAGIN,
                        scenario: Optional["Scenario"] = None,
                        intervals: Optional[Sequence["AccessInterval"]] = None
                        ) -> SAGINOrchestrator:
    """Orchestrator from the config: scenario preset, bare Walker-Star, or
    the static satellite list, in that order of precedence.

    With a scenario, coverage windows come from the vectorized
    multi-region propagation pass and the preset's stochastic dynamics
    are attached, so the wall clock advances by *realized* latencies.
    The engine passes ``scenario``/``intervals`` explicitly to share one
    propagation pass (and to support unregistered ad-hoc scenarios); a
    standalone job resolves the preset by name and propagates only its
    own region.
    """
    if cfg.scenario is not None or scenario is not None:
        from repro.sim.engine import region_streams
        from repro.sim.propagation import access_intervals_multi

        scn = scenario if scenario is not None else _resolve_scenario(cfg)
        try:
            region = scn.regions[cfg.region_index]
        except IndexError:
            raise ValueError(
                f"scenario {scn.name!r} has {len(scn.regions)} region(s); "
                f"region_index={cfg.region_index} is out of range") from None
        if intervals is None:
            # propagate only this job's region (the engine shares one pass
            # across regions; a single-region FL job shouldn't pay for all)
            intervals = access_intervals_multi(
                scn.build_constellation(), [region], t_end=scn.horizon,
                dt=scn.dt)[region.name]
        rng, dynamics = region_streams(cfg.seed, cfg.region_index,
                                       scn.dynamics)
        # an explicitly non-default FLConfig.strategy wins; otherwise the
        # scenario's declared scheme applies (as in SAGINEngine)
        strategy = (cfg.strategy if cfg.strategy != "adaptive"
                    else scn.strategy)
        return SAGINOrchestrator(sagin, intervals=intervals, rng=rng,
                                 dynamics=dynamics, strategy=strategy)
    constellation = None
    if cfg.use_constellation:
        from repro.core import WalkerStar
        constellation = WalkerStar()
    return SAGINOrchestrator(sagin, constellation=constellation,
                             sat_f_seed=cfg.seed, strategy=cfg.strategy)


def _resolve_scenario(cfg: FLConfig) -> "Scenario":
    from repro.scenarios import get_scenario
    return get_scenario(cfg.scenario)


def _train_node(apply_fn, params, ds, idx, h, lr, batch_cap, rng):
    from repro.data.pipeline import batch_for_local_steps
    batches = batch_for_local_steps(ds.x_train, ds.y_train, idx, h, rng,
                                    max_batch=batch_cap)
    if batches is None:
        return None
    xs, ys = batches
    new_params, loss = local_update(apply_fn, params, jnp.asarray(xs),
                                    jnp.asarray(ys), lr)
    return new_params, float(loss)


def _node_pools(cfg: FLConfig, pools, offline=()) -> List[np.ndarray]:
    """Index pools of every data-holding node, in canonical node order
    (ground 0..K-1, air 0..N-1, satellite) — the order both execution
    modes must share for RNG-stream equivalence.  Devices churned out
    for the round (``offline``) sit out of training entirely."""
    out = []
    offline = set(offline)
    for k in range(cfg.n_devices):
        if k in offline:
            continue
        idx = pools.ground_all(k)
        if len(idx):
            out.append(idx)
    for n in range(cfg.n_air):
        if len(pools.air[n]):
            out.append(pools.air[n])
    if len(pools.sat):
        out.append(pools.sat)
    return out


def _round_sequential(cfg: FLConfig, apply_fn, params, ds, node_pools,
                      total, rng, corrupt=(), quarantine=False):
    """Reference engine: one jitted dispatch per node, host-side fedavg.

    Returns ``(params, losses, n_quarantined)``.  ``corrupt`` holds the
    canonical node positions whose trained models are NaN-filled AFTER
    training (fault injection; RNG draws untouched); with ``quarantine``
    any non-finite model is dropped before ``fedavg`` — the weights
    renormalize over the survivors, and a round whose every update was
    dropped keeps the previous global model.
    """
    from .aggregation import tree_all_finite
    corrupt = set(corrupt)
    new_models, weights, losses = [], [], []
    n_quarantined = 0
    for pos, idx in enumerate(node_pools):
        out = _train_node(apply_fn, params, ds, idx, cfg.h_local,
                          cfg.lr, cfg.batch_cap, rng)
        if out is None:
            continue
        model, loss = out
        if pos in corrupt:
            model = jax.tree_util.tree_map(
                lambda a: jnp.full_like(a, jnp.nan), model)
            loss = float("nan")
        if quarantine and not tree_all_finite(model):
            n_quarantined += 1
            continue
        new_models.append(model)
        weights.append(len(idx) / total)
        losses.append(loss)
    if new_models:
        params = fedavg(new_models, weights)
    return params, losses, n_quarantined


def _round_batched(cfg: FLConfig, apply_fn, params, ds, node_pools,
                   total, rng, engine=None, corrupt=(), quarantine=False):
    """Cohort engine: size-bucketed compiled dispatches + one device-side
    stacked eq.-(13) aggregation (Pallas ``fedavg_agg`` path on TPU).

    ``engine`` is the job's persistent
    :class:`~repro.fl.cohort_engine.CohortEngine` (``RegionTrainer``
    owns one; ``None`` builds a throwaway — jax's jit cache still
    de-duplicates compilation across throwaways).
    ``cfg.cohort_bucketing="global"`` keeps the PR-1 single-cohort
    global-``Bmax`` layout for comparison benchmarks.

    Returns ``(params, losses, n_quarantined)``; ``corrupt`` /
    ``quarantine`` are the fault-injection and non-finite-update gates
    of :meth:`~repro.fl.cohort_engine.CohortEngine.round` (geometric
    bucketing only — the comparison-grade global layout has no
    quarantine hook).
    """
    if cfg.cohort_bucketing == "global":
        if corrupt or quarantine:
            raise ValueError(
                "fault injection / quarantine require "
                "cohort_bucketing='geometric'; the 'global' comparison "
                "layout has no masking hook")
        from repro.data.pipeline import build_cohort
        cohort = build_cohort(ds.x_train, ds.y_train, node_pools,
                              cfg.h_local, rng, max_batch=cfg.batch_cap,
                              pad_clients=cfg.n_devices + cfg.n_air + 1,
                              batch_align=cfg.cohort_batch_align)
        if cohort is None:
            return params, [], 0
        stacked, client_losses = cohort_local_update(
            apply_fn, params, jnp.asarray(cohort.xs),
            jnp.asarray(cohort.ys), jnp.asarray(cohort.mask), cfg.lr)
        weights = jnp.asarray(cohort.sizes / total, jnp.float32)
        params = fedavg_stacked(stacked, weights)
        valid = cohort.sizes > 0
        losses = [float(l) for l in np.asarray(client_losses)[valid]]
        return params, losses, 0
    if cfg.cohort_bucketing != "geometric":
        raise ValueError(f"FLConfig.cohort_bucketing must be 'geometric' "
                         f"or 'global', got {cfg.cohort_bucketing!r}")
    if engine is None:
        from .cohort_engine import CohortEngine
        engine = CohortEngine(apply_fn, batch_align=cfg.cohort_batch_align,
                              client_align=cfg.cohort_client_align,
                              guard=cfg.guard_recompiles,
                              sharding=cfg.cohort_sharding)
    cohort = engine.build(ds.x_train, ds.y_train, node_pools, cfg.h_local,
                          rng, max_batch=cfg.batch_cap)
    if cohort is None:
        return params, [], 0
    params, losses = engine.round(params, cohort, cfg.lr, total,
                                  corrupt=corrupt, quarantine=quarantine)
    return params, losses, engine.last_quarantined


class RegionTrainer:
    """One region's complete FL job, advanced one round at a time.

    Owns the region's dataset, index pools, model parameters, and SAGIN
    orchestrator; :meth:`step` executes one full round (orchestration,
    data placement, local training, aggregation, evaluation) and appends
    to :attr:`result`.  It is :meth:`launch`, which does the host's work
    and dispatches the device's, then :meth:`settle`, which reads the
    round's losses and accuracy back; the engine launches the next round
    before it settles this one.  Construction is the exact sequence the
    historic ``run_fl`` body performed, so stepping a trainer
    ``n_rounds`` times is trajectory-identical to the pre-refactor loop
    at equal seeds.

    The engine passes ``scenario``/``intervals`` so every region shares
    one propagation pass; standalone use needs only the config.  After a
    cross-region merge the engine calls :meth:`install_global` to adopt
    the global model and the post-merge wall clock.
    """

    def __init__(self, cfg: FLConfig,
                 scenario: Optional["Scenario"] = None,
                 intervals: Optional[Sequence["AccessInterval"]] = None,
                 tracer: Optional["Tracer"] = None):
        from repro.obs import resolve_obs
        self.cfg = cfg
        scn = scenario
        if scn is None and cfg.scenario is not None:
            scn = _resolve_scenario(cfg)
        # an explicit tracer (the engine's shared one) wins over the
        # config; scenario-level obs applies when the config is silent
        if tracer is None:
            obs = cfg.obs
            if obs is None and scn is not None:
                obs = scn.obs
            tracer = resolve_obs(obs)
        self.tracer = tracer
        if scn is not None:
            from repro.sim.engine import region_seed
            rseed = region_seed(cfg.seed, cfg.region_index)
            self.region = (scn.regions[cfg.region_index]
                           if cfg.region_index < len(scn.regions) else None)
        else:
            rseed = cfg.seed
            self.region = None
        self.region_seed = rseed
        self.rng = np.random.default_rng(rseed)
        # regions share the TASK (class prototypes keyed on the global
        # seed) but draw disjoint-by-construction sample streams
        self.ds = make_dataset(cfg.dataset, seed=cfg.seed,
                               train_fraction=cfg.train_fraction,
                               sample_seed=rseed)
        parts = partition(self.ds, n_devices=cfg.n_devices, iid=cfg.iid,
                          alpha=cfg.alpha, seed=rseed)
        self.pools = FederatedPools.from_partitions(parts, cfg.n_air)

        # model init is keyed on the GLOBAL seed: every region descends
        # from the same broadcast initial model (merge prerequisite)
        key = jax.random.PRNGKey(cfg.seed)
        self.params, self.apply_fn = build_model(
            self.ds.name, key, image_shape=self.ds.x_train.shape[1:])
        q_bits = self.ds.sample_bits
        self.sagin = build_default_sagin(
            n_devices=cfg.n_devices, n_air=cfg.n_air, alpha=cfg.alpha,
            q_bits=q_bits, model_bits=model_bits(self.params),
            rayleigh=cfg.rayleigh, seed=rseed)
        # sync actual per-device sizes into the network model
        for k, p in enumerate(parts):
            self.sagin.devices[k].n_samples = p.n_samples
            self.sagin.devices[k].n_sensitive = p.n_sensitive

        self.orch = _build_orchestrator(cfg, self.sagin, scenario=scn,
                                        intervals=intervals)
        self._region_name = (self.region.name if self.region is not None
                             else f"region{cfg.region_index}")
        # fault injection (repro.resilience): the engine attaches its
        # shared FaultInjector here; None = clean run, zero overhead
        self.faults = None
        # last realized ISL scale, mirrored out of the round record so
        # federation snapshots survive checkpoint/resume (orchestrator
        # records are not checkpointed)
        self._last_isl_scale = 1.0
        # dynamics emits `outage` events against the tracer's round
        # context (set in launch()) instead of plumbing region
        # identity through the orchestrator call chain
        if self.orch.dynamics is not None:
            self.orch.dynamics.tracer = self.tracer

        self.execution = cfg.resolved_execution()
        if self.execution not in ("batched", "sequential"):
            raise ValueError(
                f"FLConfig.execution must be 'auto', 'batched' or "
                f"'sequential', got {cfg.execution!r}")
        # Params live on device for the whole job (host conversion only
        # at merge barriers and eval readouts).  The batched path gets a
        # persistent cohort engine: its signature bookkeeping spans
        # rounds, and with donation enabled (non-CPU backends) the round
        # step consumes the params buffer — device_put up front makes
        # that buffer privately owned by this trainer.
        self.params = jax.device_put(self.params)
        self.cohort_engine = None
        if self.execution == "batched" and cfg.cohort_bucketing != "global":
            from .cohort_engine import CohortEngine
            self.cohort_engine = CohortEngine(
                self.apply_fn, batch_align=cfg.cohort_batch_align,
                client_align=cfg.cohort_client_align,
                guard=cfg.guard_recompiles, tracer=self.tracer,
                sharding=cfg.cohort_sharding)

        self.result = FLResult(cfg, [], [], [], [], [], [])
        # launched rounds not yet settled, oldest first (launch / settle)
        self._pending: Deque[_Launched] = collections.deque()
        eval_idx = self.rng.choice(len(self.ds.x_test),
                                   size=min(cfg.eval_size,
                                            len(self.ds.x_test)),
                                   replace=False)
        self.x_eval = jnp.asarray(self.ds.x_test[eval_idx])
        self.y_eval = jnp.asarray(self.ds.y_test[eval_idx])

    @property
    def wall_clock(self) -> float:
        return self.orch.wall_clock

    @property
    def total_samples(self) -> int:
        """This region's data mass (constant: offloading conserves it)."""
        return self.pools.total()

    def federation_snapshot(self, index: int) -> RegionFedState:
        """This region's view for federation-policy planning: clock,
        data mass, model payload, and the ISL state its dynamics
        realized in the last completed round.  The trainer emits state;
        merge SEMANTICS live entirely in ``repro.fl.federation``."""
        return RegionFedState(
            index=index,
            name=self.region.name if self.region is not None else str(index),
            wall_clock=self.orch.wall_clock,
            data_mass=float(self.total_samples),
            model_bits=float(self.sagin.model_bits),
            z_isl=float(self.sagin.z_isl),
            isl_scale=self._last_isl_scale,
            rounds_done=len(self.result.times))

    def install_global(self, params, wall_clock: float):
        """Adopt the post-merge global model and post-merge clock; the
        next :meth:`step` resumes local training from the global model.

        The engine hands the SAME merged pytree to every region; when
        this trainer's cohort engine donates buffers, its next round
        would consume a buffer siblings still reference, so take a
        private device copy first."""
        if self.cohort_engine is not None and self.cohort_engine.donate:
            params = jax.tree_util.tree_map(
                lambda a: jnp.array(a, copy=True), params)
        self.params = params
        self.orch.wall_clock = wall_clock

    def step(self, r: int):
        """Execute FL round ``r``: orchestrate, place data, train every
        data-holding node, aggregate, evaluate.  Returns the round's
        :class:`~repro.core.scheduler.RoundRecord` and appends the
        training metrics to :attr:`result` (:meth:`launch`, then
        :meth:`settle`)."""
        rec = self.launch(r)
        while self._pending:
            self.settle()
        return rec

    def launch(self, r: int):
        """Run round ``r`` up to and including the dispatch of its local
        update, aggregate and evaluation, and return its
        :class:`~repro.core.scheduler.RoundRecord`.

        Everything the host decides (plan, pools, clock, the next round's
        start model as a device future) is done on return; the round's
        client losses and accuracy stay on the device until
        :meth:`settle`, so the caller can orchestrate and dispatch other
        rounds while this one trains."""
        cfg = self.cfg
        tr = self.tracer
        # context BEFORE orch.step: dynamics samples (and emits `outage`
        # events) inside it, at this round's start clock; the phases'
        # annotations carry it whether or not the tracer is enabled
        tr.set_context(region=self._region_name, round=r,
                       t_sim=self.orch.wall_clock)
        with tr.phase("region.orchestrate"):
            rec = self.orch.step(r)
        specs = (self.faults.at(r, cfg.region_index)
                 if self.faults is not None else ())
        crash = self._apply_latency_faults(rec, specs)
        _apply_plan_to_pools(rec.plan, self.pools, self.sagin)
        _sync_sizes(self.pools, self.sagin)

        # ---- local training at every node that holds data ----------------
        total = self.pools.total()
        node_pools = _node_pools(cfg, self.pools,
                                 offline=rec.offline_devices)
        # nan_update: the first ceil(severity) canonical nodes' trained
        # models are NaN-filled AFTER training (the RNG stream is
        # untouched, so the chaos trajectory stays seed-reproducible)
        nan_spec = next((s for s in specs if s.kind == "nan_update"), None)
        corrupt: Sequence[int] = ()
        if nan_spec is not None and node_pools:
            n_bad = min(max(1, int(nan_spec.severity)), len(node_pools))
            corrupt = tuple(range(n_bad))
        quarantine = (cfg.quarantine if cfg.quarantine is not None
                      else self.faults is not None)
        if crash is not None:
            # trainer process died mid-round: the round's training is
            # lost, recovery warm-restarts from the last committed model
            # (params unchanged) after a restart penalty on the clock
            penalty = crash.severity * rec.realized_latency
            self.faults.record_injected("trainer_crash",
                                        penalty_s=penalty)
            rec.realized_latency += penalty
            self.orch.wall_clock += penalty
            losses, n_quar = [], 0
            self.faults.record_recovered("trainer_crash",
                                         penalty_s=penalty)
        elif self.execution == "batched":
            self.params, losses, n_quar = _round_batched(
                cfg, self.apply_fn, self.params, self.ds, node_pools,
                total, self.rng, engine=self.cohort_engine,
                corrupt=corrupt, quarantine=quarantine)
        else:
            self.params, losses, n_quar = _round_sequential(
                cfg, self.apply_fn, self.params, self.ds, node_pools,
                total, self.rng, corrupt=corrupt, quarantine=quarantine)
        if corrupt and self.faults is not None:
            self.faults.record_injected("nan_update",
                                        n_corrupt=len(corrupt))
            if quarantine and n_quar >= len(corrupt):
                self.faults.record_recovered("nan_update",
                                             quarantined=n_quar)
        if n_quar:
            tr.metrics.counter("quarantine.updates").inc(n_quar)

        with tr.phase("region.evaluate"):
            _, acc = evaluate(self.apply_fn, self.params, self.x_eval,
                              self.y_eval)
        n_ground = sum(len(self.pools.ground_all(k))
                       for k in range(cfg.n_devices))
        n_air = sum(len(a) for a in self.pools.air)
        self._pending.append(_Launched(
            round=r, rec=rec, clock=self.orch.wall_clock, losses=losses,
            acc=acc, portions={"ground": n_ground / total,
                               "air": n_air / total,
                               "space": len(self.pools.sat) / total}))
        self._last_isl_scale = (float(rec.events.isl_scale)
                                if rec.events is not None else 1.0)
        return rec

    def settle(self):
        """Read back the oldest launched round's client losses and
        accuracy (phase ``region.settle``) and append its training
        metrics to :attr:`result`, as Python floats; returns its
        :class:`~repro.core.scheduler.RoundRecord`."""
        p = self._pending.popleft()
        tr = self.tracer
        with tr.phase("region.settle", region=self._region_name,
                      round=p.round):
            losses = list(p.losses)
            acc = float(p.acc)
        res = self.result
        res.times.append(p.clock)
        res.accuracies.append(acc)
        res.losses.append(float(np.mean(losses)) if losses
                          else float("nan"))
        res.participated.append(bool(losses))
        res.latencies.append(p.rec.realized_latency)
        res.cases.append(p.rec.plan.case)
        res.layer_portions.append(p.portions)
        if tr.enabled:
            self._emit_round_spans(p.round, p.rec, res)
        return p.rec

    def _apply_latency_faults(self, rec, specs):
        """Apply this round's latency-shaped faults to the round record
        and the wall clock; returns the ``trainer_crash`` spec (handled
        at the training dispatch) or ``None``.

        ``sat_loss`` kills the serving satellite at
        ``severity * tau_S`` into the space schedule and re-plans onto
        the successor chain (:func:`repro.core.handover.replan_after_loss`
        — the unplanned mid-window handover); ``straggler`` stretches
        the realized round latency by ``severity``x.  Both are absorbed
        as extra realized latency — the round still completes, which IS
        the recovery."""
        crash = None
        for spec in specs:
            if spec.kind == "sat_loss":
                loss_t = spec.severity * rec.schedule.total_latency
                recovered, _ = replan_after_loss(rec.schedule, loss_t,
                                                 self.sagin)
                delta = max(0.0, recovered.total_latency
                            - rec.schedule.total_latency)
                self.faults.record_injected("sat_loss", loss_time=loss_t,
                                            delta_s=delta)
                rec.schedule = recovered
                rec.realized_latency += delta
                self.orch.wall_clock += delta
                self.faults.record_recovered("sat_loss", delta_s=delta)
            elif spec.kind == "straggler":
                delta = max(0.0, (spec.severity - 1.0)
                            * rec.realized_latency)
                self.faults.record_injected("straggler",
                                            slowdown=spec.severity)
                rec.realized_latency += delta
                self.orch.wall_clock += delta
                self.faults.record_recovered("straggler", delta_s=delta)
            elif spec.kind == "trainer_crash":
                crash = spec
        return crash

    def _emit_round_spans(self, r: int, rec, res: FLResult):
        """Trace one settled round: offload transfer, handover legs,
        and the round span itself (``repro.obs``; enabled path only).
        Purely observational — reads the round record, writes spans
        tagged with this round's region and round (by the time a round
        settles, the tracer's context may belong to a later launch)."""
        tr = self.tracer
        t0 = rec.wall_clock_start
        plan = rec.plan
        q_bits = float(self.sagin.q_bits)
        up = sum(sum(cp.d_ground_air.values()) + cp.d_air_space
                 for cp in plan.clusters)
        down = sum(sum(cp.d_air_ground.values()) + cp.d_space_air
                   for cp in plan.clusters)
        at = dict(region=self._region_name, round=r)
        tr.span("offload", f"offload case{plan.case}", t_sim=t0, **at,
                case=plan.case, up_samples=up, down_samples=down,
                bytes_moved=(up + down) * q_bits / 8.0)
        tr.metrics.counter("offload.bytes").inc((up + down) * q_bits / 8.0)
        tr.metrics.counter("offload.samples_up").inc(up)
        tr.metrics.counter("offload.samples_down").inc(down)

        sched = rec.schedule
        prev = None
        for leg in sched.legs:
            if prev is not None and leg.handover_delay > 0:
                tr.span("handover", f"sat{prev}->sat{leg.sat_index}", **at,
                        t_sim=t0 + leg.start_time - leg.handover_delay,
                        dur_sim=leg.handover_delay,
                        samples=leg.samples_processed)
            prev = leg.sat_index
        if sched.n_handovers:
            tr.metrics.counter("handover.count").inc(sched.n_handovers)

        ev = rec.events
        uplink_delay = (sum(ev.uplink_delays.values())
                        if ev is not None else 0.0)
        tr.span("round", f"{self._region_name}/r{r}", t_sim=t0, **at,
                dur_sim=rec.realized_latency,
                case=plan.case, latency_analytic=rec.latency,
                # the no-participant loss sentinel is NaN — not valid
                # strict JSON, so map it to None in the trace
                loss=(res.losses[-1] if res.participated[-1] else None),
                acc=res.accuracies[-1],
                participated=res.participated[-1],
                n_handovers=sched.n_handovers, t_space=sched.total_latency,
                uplink_delay=uplink_delay)
        tr.metrics.histogram("round.realized_latency_s").observe(
            rec.realized_latency)
        tr.metrics.histogram("round.overhead_s").observe(
            rec.realized_latency - rec.latency)


def run_fl(cfg: FLConfig, tracer=None) -> FLResult:
    """Single-region FL job: a :class:`RegionTrainer` stepped to the end.

    ``tracer`` (a :class:`repro.obs.Tracer`) overrides ``cfg.obs`` —
    ``run_fl_all_regions`` shares one tracer across regions this way;
    when this function owns the tracer (built from ``cfg.obs``) it also
    flushes the trace at the end of the run.
    """
    own_tracer = tracer is None
    trainer = RegionTrainer(cfg, tracer=tracer)
    for r in range(cfg.n_rounds):
        trainer.step(r)
    if own_tracer:
        trainer.tracer.flush()
    return trainer.result


def _apply_plan_to_pools(plan, pools: FederatedPools, sagin: SAGIN):
    """Mirror the optimizer's (fractional) plan as integer index moves."""
    for cp in plan.clusters:
        n = cp.n
        # downward: satellite -> air -> ground
        if cp.d_space_air > 0:
            pools.move_sat_to_air(n, int(round(cp.d_space_air)))
        for k, d in sorted(cp.d_air_ground.items()):
            pools.move_air_to_ground(n, k, int(round(d)))
        # upward: ground -> air -> satellite
        for k, d in sorted(cp.d_ground_air.items()):
            pools.move_ground_to_air(k, n, int(round(d)))
        if cp.d_air_space > 0:
            pools.move_air_to_sat(n, int(round(cp.d_air_space)))


def _sync_sizes(pools: FederatedPools, sagin: SAGIN):
    """Make the analytic model's sizes match the realized pools."""
    for k, dev in enumerate(sagin.devices):
        dev.n_samples = len(pools.ground_all(k))
        dev.n_sensitive = len(pools.ground_sensitive[k])
    for n, air in enumerate(sagin.air_nodes):
        air.n_samples = len(pools.air[n])
    sagin.n_sat_samples = len(pools.sat)
