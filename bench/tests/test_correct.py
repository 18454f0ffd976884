"""``correct`` on the CPU at a size a test run holds: the program passes
its cell's limits, and each fault the cell can have, planted in the timed
path underneath an otherwise whole run, and the control (the reference in
a lower precision in the program's place) fail them.  The serving cell is
in no benchmark (``PERF.md``); its tests keep its driver whole.

A run here skips only the harness's look for a chip; everything else is
the run a cell makes, on a cut-down copy of the cell's data.
"""
import copy

import jax
import jax.numpy as jnp
import pytest

from bench.harness import core, runner

SEED = 2**33 + 5


def _tiny(name: str) -> dict:
    cell = copy.deepcopy(core.workload(name))
    cell["population"].update(n_devices=3, n_air=1, train_fraction=0.01,
                              eval_size=64)
    scn = cell["scenario"]
    scn["regions"] = scn["regions"][:2]
    scn["horizon"] = 6 * 3600.0
    cell["warm_layouts"] = []
    if cell["driver"] == "train":
        # the program's per-client convolutions are slow on the CPU
        cell["population"].update(n_devices=2, h_local=2,
                                  cohort_batch_align=8, train_fraction=0.002)
        cell["checked_rounds"] = 2
        cell["trace_rounds"] = 1
    if cell["driver"] == "serve":
        cell["chunk_s"] = 10.0
    return cell


def _run(cell, seconds=0.1):
    config = core.config(cell["config"])
    ctx = runner.Context(
        cell=cell, config=config, seed=SEED, seconds=seconds, trace=False,
        chips=1, devices=jax.devices()[:1],
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        clock=core.Clock(), compiles=core.CompileClock())
    result, checks = runner.measure(ctx)
    return result, {c["name"]: c for c in checks}


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


TRAIN = ["vgg11.train.multi_region", "mnist-cnn.train.multi_region"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_program_is_correct(name):
    result, checks = _run(_tiny(name))
    assert result["correct"], checks
    assert result["attempted"] >= 2


@pytest.mark.parametrize("name", TRAIN)
def test_train_state_left_unchanged_is_not_correct(name, monkeypatch,
                                                   fresh_jit):
    from repro.fl.cohort_engine import CohortEngine
    inner = CohortEngine._execute

    def unchanged(self, params, cohort, lr, total, **kw):
        _, losses = inner(self, params, cohort, lr, total, **kw)
        return params, losses

    monkeypatch.setattr(CohortEngine, "_execute", unchanged)
    result, checks = _run(_tiny(name))
    assert not result["correct"]
    assert not checks["update_gap"]["ok"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_half_batch_left_out_is_not_correct(name, monkeypatch,
                                                  fresh_jit):
    from repro.fl import client
    inner = client.masked_cross_entropy

    def half(logits, labels, mask):
        keep = jnp.cumsum(mask) <= jnp.ceil(jnp.sum(mask) / 2)
        return inner(logits, labels, mask * keep)

    monkeypatch.setattr(client, "masked_cross_entropy", half)
    result, checks = _run(_tiny(name))
    assert not result["correct"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_altered_sample_is_not_correct(name, monkeypatch):
    """One sample of each cohort replaced by another row where the cohort
    is built: the reference would train on it too, the rows do not."""
    from repro.fl.cohort_engine import CohortEngine
    inner = CohortEngine.build

    def altered(self, *args, **kw):
        cohort = inner(self, *args, **kw)
        if cohort is not None:
            xs = cohort.buckets[0].xs
            xs[0, 0, 0] = xs[0, 0, 0] + 1.0
        return cohort

    monkeypatch.setattr(CohortEngine, "build", altered)
    result, checks = _run(_tiny(name))
    assert not result["correct"]
    assert not checks["cohort_faults"]["ok"]


def _halved(cohort):
    """The cohort with the second half of each step's valid samples
    masked out, the recorded cohort left whole."""
    import dataclasses
    drv = core.driver("train")
    return dataclasses.replace(cohort, buckets=[
        dataclasses.replace(cb, mask=drv._faulted(cb.mask, "half_batch"))
        for cb in cohort.buckets])


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", TRAIN)
def test_train_fault_in_round_2_only_is_not_correct(name, fault,
                                                    monkeypatch):
    """A fault planted in the second checked round alone, where round 1,
    which is not gap-compared, runs sound."""
    from repro.fl.cohort_engine import CohortEngine
    inner = CohortEngine._execute
    cell = _tiny(name)
    n_regions = len(cell["scenario"]["regions"])
    calls = []

    def second_round(self, params, cohort, lr, total, **kw):
        calls.append(None)
        if not n_regions < len(calls) <= 2 * n_regions:
            return inner(self, params, cohort, lr, total, **kw)
        if fault == "half_batch":
            return inner(self, params, _halved(cohort), lr, total, **kw)
        _, losses = inner(self, params, cohort, lr, total, **kw)
        return params, losses

    monkeypatch.setattr(CohortEngine, "_execute", second_round)
    result, checks = _run(cell)
    assert len(calls) > 2 * n_regions
    assert not result["correct"], checks


@pytest.mark.parametrize("name", TRAIN)
def test_train_model_kept_between_rounds_is_not_correct(name, monkeypatch):
    """A region that trains its round but keeps its old model: a replay
    from the recorded start would copy that, the hand-off check does
    not."""
    from repro.fl import rounds
    inner = rounds._round_batched

    def kept(cfg, apply_fn, params, *args, **kw):
        _, losses, n_quar = inner(cfg, apply_fn, params, *args, **kw)
        return params, losses, n_quar

    monkeypatch.setattr(rounds, "_round_batched", kept)
    result, checks = _run(_tiny(name))
    assert not result["correct"]
    assert not checks["handoff_faults"]["ok"]


@pytest.mark.parametrize("name", TRAIN)
def test_train_control_fails_the_limits(name):
    """The bf16 reference in the program's place, on the inputs the
    program's checked rounds consumed."""
    drv = core.driver("train")
    cell = _tiny(name)
    config = core.config(cell["config"])
    engine = drv.set_up(cell, config, SEED)
    rec = drv.checked_rounds(engine, cell["checked_rounds"], SEED)
    del engine
    ref = drv.replay(config, cell, rec)
    low = drv.replay(config, cell, rec, prec="bf16")
    gaps = drv.gaps(low, ref)
    assert "client_gap" in gaps
    assert any(gaps[k] > v for k, v in cell["limits"].items()
               if k in gaps), gaps


SERVE = "vgg11.serve.flash_crowd"


def test_serve_program_is_correct():
    result, checks = _run(_tiny(SERVE))
    assert result["correct"], checks
    assert result["attempted"] > 0


def test_serve_altered_answer_is_not_correct(monkeypatch):
    from repro.serve.backends import CNNBackend
    inner = CNNBackend.predict

    def altered(self, model_region, x, samples):
        return (inner(self, model_region, x, samples) + 1) % 10

    monkeypatch.setattr(CNNBackend, "predict", altered)
    result, checks = _run(_tiny(SERVE))
    assert not result["correct"]
    assert not checks["served_gap"]["ok"]


def test_serve_answer_from_the_wrong_region_is_not_correct(monkeypatch):
    from repro.serve.backends import CNNBackend
    inner = CNNBackend.predict

    def wrong_model(self, model_region, x, samples):
        other = (model_region + 1) % len(self.trainers)
        return inner(self, other, x, samples)

    monkeypatch.setattr(CNNBackend, "predict", wrong_model)
    result, _ = _run(_tiny(SERVE))
    assert not result["correct"]
