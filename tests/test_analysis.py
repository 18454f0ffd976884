"""The linter linted: every rule gets >= 1 positive and >= 1 negative
fixture, plus golden file:line findings, a clean realistic file, the
baseline round trip, and the CLI's exit-code semantics."""
import json
import textwrap

import pytest

from repro.analysis import (DEFAULT_BASELINE, apply_baseline, classify,
                            load_baseline, scan, write_baseline)
from repro.analysis.__main__ import main as cli


def lint(tmp_path, src, name="lib/mod.py", rules=None):
    """Write ``src`` under tmp_path and scan it; returns findings."""
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(src))
    return scan([f], root=tmp_path, rule_ids=rules)


def rules_hit(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# RNG001 — legacy global numpy RNG
# ---------------------------------------------------------------------------
def test_rng001_positive(tmp_path):
    out = lint(tmp_path, """
        import numpy as np
        def draw(n):
            return np.random.rand(n)
    """)
    assert rules_hit(out) == ["RNG001"]
    assert out[0].line == 4


def test_rng001_negative_generator_and_aliases(tmp_path):
    out = lint(tmp_path, """
        import numpy as np
        import numpy.random as npr
        def draw(n, seed):
            rng = np.random.default_rng(seed)   # construction is fine
            gen = npr.Generator(npr.PCG64(seed))
            return rng.normal(size=n) + gen.normal(size=n)
    """)
    assert out == []


def test_rng001_skipped_in_tests(tmp_path):
    out = lint(tmp_path, """
        import numpy as np
        def fixture(n):
            return np.random.rand(n)
    """, name="tests/test_x.py")
    assert out == []


# ---------------------------------------------------------------------------
# RNG002 — jax key reuse
# ---------------------------------------------------------------------------
def test_rng002_positive_two_consumers(tmp_path):
    out = lint(tmp_path, """
        import jax
        def init(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
    """)
    assert rules_hit(out) == ["RNG002"]
    assert out[0].line == 5          # flagged at the SECOND consumer
    assert "'key'" in out[0].message


def test_rng002_positive_loop_reuse(tmp_path):
    out = lint(tmp_path, """
        import jax
        def draws(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (2,)))
            return out
    """)
    assert rules_hit(out) == ["RNG002"]
    assert "loop" in out[0].message


def test_rng002_negative_split_and_fold_in(tmp_path):
    # the repo's layers.py idiom: one split + fold_in derivations
    out = lint(tmp_path, """
        import jax
        def init(key):
            ks = jax.random.split(key, 3)
            a = jax.random.normal(ks[0], (3,))
            b = jax.random.uniform(ks[1], (3,))
            c = jax.random.normal(jax.random.fold_in(key, 99), (3,))
            d = jax.random.normal(jax.random.fold_in(key, 98), (3,))
            return a + b + c + d
    """)
    assert out == []


def test_rng002_positive_split_index_reused(tmp_path):
    out = lint(tmp_path, """
        import jax
        def init(key):
            ks = jax.random.split(key, 2)
            a = jax.random.normal(ks[0], (3,))
            b = jax.random.uniform(ks[0], (3,))
            return a + b
    """)
    assert rules_hit(out) == ["RNG002"]


def test_rng002_negative_rebind_in_loop(tmp_path):
    out = lint(tmp_path, """
        import jax
        def draws(key, n):
            out = []
            for i in range(n):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (2,)))
            return out
    """)
    assert out == []


def test_rng002_negative_branches_are_exclusive(tmp_path):
    out = lint(tmp_path, """
        import jax
        def pick(key, flag):
            if flag:
                return jax.random.normal(key, (3,))
            else:
                return jax.random.uniform(key, (3,))
    """)
    assert out == []


def test_rng002_skipped_in_tests(tmp_path):
    out = lint(tmp_path, """
        import jax
        def helper(key):
            return (jax.random.normal(key, (2,)),
                    jax.random.normal(key, (2,)))
    """, name="tests/test_y.py")
    assert out == []


# ---------------------------------------------------------------------------
# RNG003 — hard-coded PRNGKey literal
# ---------------------------------------------------------------------------
def test_rng003_positive(tmp_path):
    out = lint(tmp_path, """
        import jax
        def build():
            return jax.random.PRNGKey(42)
    """)
    assert rules_hit(out) == ["RNG003"]
    assert out[0].severity == "warning"


def test_rng003_negative_threaded_seed_and_test_kind(tmp_path):
    assert lint(tmp_path, """
        import jax
        def build(seed):
            return jax.random.PRNGKey(seed)
    """) == []
    assert lint(tmp_path, """
        import jax
        KEY = jax.random.PRNGKey(0)
    """, name="tests/test_z.py") == []


# ---------------------------------------------------------------------------
# JIT001 — jit constructed in a loop
# ---------------------------------------------------------------------------
def test_jit001_positive(tmp_path):
    out = lint(tmp_path, """
        import jax
        def run(fs, x):
            for f in fs:
                x = jax.jit(f)(x)
            return x
    """)
    assert "JIT001" in rules_hit(out)
    assert any(f.line == 5 for f in out)


def test_jit001_negative_module_level_and_nested_def(tmp_path):
    out = lint(tmp_path, """
        import jax

        step = jax.jit(lambda x: x + 1)

        def run(xs):
            for x in xs:
                def inner(y):
                    return jax.jit(lambda z: z)(y)   # not per-iteration
            return step(xs[0])
    """, rules=["JIT001"])
    assert out == []


# ---------------------------------------------------------------------------
# JIT002 — immediately-invoked jit
# ---------------------------------------------------------------------------
def test_jit002_positive(tmp_path):
    out = lint(tmp_path, """
        import jax
        def f(x):
            return jax.jit(lambda y: y * 2)(x)
    """)
    assert rules_hit(out) == ["JIT002"]


def test_jit002_negative_bound_once(tmp_path):
    out = lint(tmp_path, """
        import jax
        double = jax.jit(lambda y: y * 2)
        def f(x):
            return double(x)
    """)
    assert out == []


# ---------------------------------------------------------------------------
# JIT003 — unhashable static args
# ---------------------------------------------------------------------------
def test_jit003_positive_mutable_default(tmp_path):
    out = lint(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def reshape(x, dims=[1, 2]):
            return x.reshape(dims)
    """)
    assert rules_hit(out) == ["JIT003"]


def test_jit003_positive_literal_at_static_position(tmp_path):
    out = lint(tmp_path, """
        import jax

        def _impl(x, dims):
            return x.reshape(dims)

        shaped = jax.jit(_impl, static_argnums=(1,))

        def call(x):
            return shaped(x, [4, 2])
    """)
    assert rules_hit(out) == ["JIT003"]


def test_jit003_negative_hashable(tmp_path):
    out = lint(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def reshape(x, dims=(1, 2)):
            return x.reshape(dims)

        def call(x):
            return reshape(x, (4, 2))
    """)
    assert out == []


# ---------------------------------------------------------------------------
# DON001 — use-after-donate
# ---------------------------------------------------------------------------
def test_don001_positive_same_module(tmp_path):
    out = lint(tmp_path, """
        import jax

        step = jax.jit(lambda p, x: p, donate_argnums=(0,))

        def train(params, x):
            new = step(params, x)
            return params, new
    """)
    assert rules_hit(out) == ["DON001"]
    assert out[0].line == 8


def test_don001_positive_cross_module_donor(tmp_path):
    # the repo's real layout: the donating jit lives in one module,
    # the caller in another — the donor table is project-wide
    (tmp_path / "lib").mkdir(parents=True, exist_ok=True)
    (tmp_path / "lib" / "kernels.py").write_text(textwrap.dedent("""
        import jax
        fused_step = jax.jit(lambda p, x: p, donate_argnums=(0,))
    """))
    (tmp_path / "lib" / "driver.py").write_text(textwrap.dedent("""
        from lib.kernels import fused_step

        def train(params, x):
            new = fused_step(params, x)
            return params["w"], new
    """))
    out = scan([tmp_path / "lib"], root=tmp_path)
    assert [(f.rule, f.path) for f in out] == [("DON001", "lib/driver.py")]


def test_don001_negative_rebind(tmp_path):
    out = lint(tmp_path, """
        import jax

        step = jax.jit(lambda p, x: p, donate_argnums=(0,))

        def train(params, x):
            params = step(params, x)
            return params
    """)
    assert out == []


def test_don001_negative_branch_not_taken_pattern(tmp_path):
    # CohortEngine.round's shape: donate only in one branch, the result
    # rebinds; reading the ORIGINAL afterwards is still an error only
    # if any branch donated without rebinding
    out = lint(tmp_path, """
        import jax

        step = jax.jit(lambda p, x: p, donate_argnums=(0,))

        def train(params, x, fused):
            if fused:
                out = step(params, x)
            else:
                out = (params, x)
            return out
    """)
    assert out == []


# ---------------------------------------------------------------------------
# HOST001 — host sync in round/step loops
# ---------------------------------------------------------------------------
def test_host001_positive(tmp_path):
    out = lint(tmp_path, """
        def run(cfg, arr):
            losses = []
            for r in range(cfg.n_rounds):
                arr = arr * 2
                losses.append(float(arr))
        """)
    assert rules_hit(out) == ["HOST001"]
    assert out[0].severity == "warning"


def test_host001_positive_item(tmp_path):
    out = lint(tmp_path, """
        def run(n_steps, arr):
            tot = 0.0
            for step in range(n_steps):
                tot += arr.sum().item()
            return tot
    """)
    assert rules_hit(out) == ["HOST001"]


def test_host001_negative_outside_round_loop(tmp_path):
    out = lint(tmp_path, """
        def run(xs, arr):
            for x in xs:            # not a round/step loop
                arr = arr + float(x)
            return float(arr)       # after the loop: fine
    """)
    assert out == []


# ---------------------------------------------------------------------------
# OBS001 — tracer/metrics call inside a jitted function
# ---------------------------------------------------------------------------
def test_obs001_positive_decorated(tmp_path):
    out = lint(tmp_path, """
        import jax

        @jax.jit
        def step(tracer, x):
            tracer.span("round", "r")     # runs at trace time only
            return x * 2
    """)
    assert rules_hit(out) == ["OBS001"]
    assert out[0].line == 6


def test_obs001_positive_partial_and_attribute_receiver(tmp_path):
    out = lint(tmp_path, """
        import functools
        import jax

        class Engine:
            @functools.partial(jax.jit, static_argnums=(0,))
            def step(self, x):
                self.tracer.event("outage", "isl")
                self.metrics.counter("n").inc()
                return x
    """)
    assert rules_hit(out) == ["OBS001"]
    assert len(out) == 2


def test_obs001_positive_module_level_jit(tmp_path):
    out = lint(tmp_path, """
        import jax
        from repro.obs import NULL_TRACER

        def _inner(x):
            NULL_TRACER.span("round", "r")
            return x + 1

        step = jax.jit(_inner)
    """)
    assert rules_hit(out) == ["OBS001"]


def test_obs001_positive_phase_inside_jit(tmp_path):
    out = lint(tmp_path, """
        import jax

        @jax.jit
        def step(tracer, x):
            with tracer.phase("cohort.dispatch"):   # opens at trace time
                return x * 2
    """)
    assert rules_hit(out) == ["OBS001"]


def test_obs001_negative_outside_jit(tmp_path):
    out = lint(tmp_path, """
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def round_driver(tracer, x):
            y = step(x)
            tracer.span("round", "r")     # host side: fine
            tracer.metrics.histogram("h").observe(1.0)
            return y
    """)
    assert out == []


def test_obs001_negative_unrelated_receiver_methods(tmp_path):
    out = lint(tmp_path, """
        import jax

        @jax.jit
        def step(layout, cfg, x):
            w = layout.span("a", "b")     # not a tracer/metrics object
            cfg.set(3)
            return x * w
    """)
    assert out == []


# ---------------------------------------------------------------------------
# SHARD001 — collective with literal axis outside shard_map context
# ---------------------------------------------------------------------------
def test_shard001_positive_unwired_function(tmp_path):
    out = lint(tmp_path, """
        import jax

        def agg(x):
            return jax.lax.psum(x, "data")
    """)
    assert rules_hit(out) == ["SHARD001"]
    assert out[0].line == 5


def test_shard001_positive_pmean_tuple_axes_and_kwarg(tmp_path):
    out = lint(tmp_path, """
        import jax
        from jax import lax

        def a(x):
            return lax.pmean(x, ("data", "pod"))

        def b(x):
            return jax.lax.all_gather(x, axis_name="data")
    """)
    assert rules_hit(out) == ["SHARD001"]
    assert len(out) == 2


def test_shard001_negative_wired_by_name(tmp_path):
    out = lint(tmp_path, """
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map

        def agg(x):
            return jax.lax.psum(x, "data")

        def build(mesh):
            return jax.jit(shard_map(agg, mesh=mesh, in_specs=P("data"),
                                     out_specs=P()))
    """)
    assert out == []


def test_shard001_negative_closure_factory(tmp_path):
    # the CohortEngine._make_sharded_step idiom: the traced body is a
    # nested def inside the function that calls shard_map
    out = lint(tmp_path, """
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map

        def make_step(mesh):
            def body(x):
                return jax.lax.psum(x, "data")
            return jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                                     out_specs=P()))
    """)
    assert out == []


def test_shard001_negative_axis_from_parameter(tmp_path):
    # hierarchical_weighted_psum takes the axes as a parameter — the
    # binding mesh lives in the caller's module, out of static reach
    out = lint(tmp_path, """
        import jax

        def weighted_psum(tree, lam, axis_names):
            def agg(leaf):
                contrib = lam * leaf
                for ax in axis_names:
                    contrib = jax.lax.psum(contrib, ax)
                return contrib
            return jax.tree_util.tree_map(agg, tree)
    """)
    assert out == []


def test_shard001_skipped_in_tests(tmp_path):
    out = lint(tmp_path, """
        import jax
        def agg(x):
            return jax.lax.psum(x, "data")
    """, name="tests/test_x.py")
    assert out == []


# ---------------------------------------------------------------------------
# RES001 — bare assert in library code
# ---------------------------------------------------------------------------
def test_res001_positive(tmp_path):
    out = lint(tmp_path, """
        def restore(state, n_regions):
            assert len(state) == n_regions, "region count mismatch"
            return list(state)
    """)
    assert rules_hit(out) == ["RES001"]
    assert out[0].line == 3
    assert "python -O" in out[0].message


def test_res001_negative_raise(tmp_path):
    out = lint(tmp_path, """
        def restore(state, n_regions):
            if len(state) != n_regions:
                raise ValueError("region count mismatch")
            return list(state)
    """)
    assert out == []


def test_res001_skipped_in_tests_and_benchmarks(tmp_path):
    src = """
        def check(xs):
            assert xs, "empty"
    """
    assert lint(tmp_path, src, name="tests/test_x.py") == []
    assert lint(tmp_path, src, name="benchmarks/bench_x.py") == []


# ---------------------------------------------------------------------------
# TIME001 — time.time() where a measurement is implied
# ---------------------------------------------------------------------------
def test_time001_positive_duration(tmp_path):
    out = lint(tmp_path, """
        import time
        def measure(fn):
            t0 = time.time()
            fn()
            return time.time() - t0
    """)
    assert rules_hit(out) == ["TIME001"]
    assert [f.line for f in out] == [4, 6]
    assert "perf_counter" in out[0].message


def test_time001_positive_from_import_alias(tmp_path):
    out = lint(tmp_path, """
        from time import time as now
        def stamp():
            return now()
    """)
    assert rules_hit(out) == ["TIME001"]


def test_time001_negative_perf_counter(tmp_path):
    out = lint(tmp_path, """
        import time
        def measure(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
    """)
    assert out == []


def test_time001_negative_unrelated_time_name(tmp_path):
    # a local callable named `time` from another module is not the
    # stdlib wall clock
    out = lint(tmp_path, """
        from simclock import time
        def stamp():
            return time()
    """)
    assert out == []


def test_time001_skipped_in_tests(tmp_path):
    src = """
        import time
        def test_fresh():
            assert time.time() > 0
    """
    assert lint(tmp_path, src, name="tests/test_x.py") == []
    # ...but benchmarks ARE covered: measurement code is the point
    out = lint(tmp_path, """
        import time
        def bench():
            t0 = time.time()
            return time.time() - t0
    """, name="benchmarks/bench_x.py")
    assert rules_hit(out) == ["TIME001"]


# ---------------------------------------------------------------------------
# golden findings, clean file, parse errors
# ---------------------------------------------------------------------------
def test_golden_file_line_rule_triples(tmp_path):
    out = lint(tmp_path, """
        import numpy as np
        import jax

        def draw(n):
            return np.random.rand(n)

        def init(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b

        def hot(fs, x):
            for f in fs:
                x = jax.jit(f)(x)
            return x
    """)
    triples = [(f.rule, f.line) for f in out]
    assert triples == [("RNG001", 6), ("RNG002", 10),
                       ("JIT001", 15), ("JIT002", 15)]
    assert all(f.path == "lib/mod.py" for f in out)


def test_clean_realistic_file(tmp_path):
    out = lint(tmp_path, """
        import numpy as np
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnums=(0,))
        def local_update(apply_fn, params, xs, ys, lr):
            grads = jax.grad(lambda p: apply_fn(p, xs).sum())(params)
            return jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads)

        def run(cfg, apply_fn, params, data, seed):
            rng = np.random.default_rng(seed)
            key = jax.random.PRNGKey(seed)
            for r in range(cfg.n_rounds):
                key, sub = jax.random.split(key)
                noise = jax.random.normal(sub, (4,))
                xs = jnp.asarray(rng.normal(size=(8, 4)))
                params = local_update(apply_fn, params, xs + noise,
                                      None, cfg.lr)
            return params
    """)
    assert out == []


def test_unparseable_file_reports_parse_finding(tmp_path):
    out = lint(tmp_path, "def broken(:\n")
    assert [f.rule for f in out] == ["PARSE"]
    assert out[0].severity == "error"


def test_classify():
    from pathlib import Path
    assert classify(Path("tests/test_x.py")) == "test"
    assert classify(Path("benchmarks/run.py")) == "bench"
    assert classify(Path("examples/demo.py")) == "example"
    assert classify(Path("src/repro/fl/rounds.py")) == "library"


# ---------------------------------------------------------------------------
# baseline round trip + CLI exit codes
# ---------------------------------------------------------------------------
BAD_SRC = """
import numpy as np
def draw(n):
    return np.random.rand(n)
"""


def test_baseline_round_trip(tmp_path):
    f = tmp_path / "lib.py"
    f.write_text(BAD_SRC)
    found = scan([f], root=tmp_path)
    assert len(found) == 1

    bl = tmp_path / "baseline.json"
    write_baseline(bl, found)
    suppressed = load_baseline(bl)
    assert suppressed == {found[0].key}
    assert apply_baseline(found, suppressed) == []

    # a NEW violation is not suppressed by the old baseline
    f.write_text(BAD_SRC + "\ndef more(n):\n    return np.random.rand(n)\n")
    again = scan([f], root=tmp_path)
    fresh = apply_baseline(again, suppressed)
    assert [g.rule for g in fresh] == ["RNG001"]
    assert fresh[0].line > found[0].line


def test_baseline_version_check(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 99, "suppressed": []}))
    with pytest.raises(ValueError):
        load_baseline(bl)


def test_cli_exit_codes(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "lib.py"
    bad.write_text(BAD_SRC)
    clean = tmp_path / "ok.py"
    clean.write_text("import numpy as np\n\n\ndef f(rng):\n"
                     "    return rng.normal()\n")

    assert cli([str(clean)]) == 0
    assert cli([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "RNG001" in out and "1 error(s)" in out

    # json format round-trips through json.loads
    assert cli([str(bad), "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["findings"][0]["rule"] == "RNG001"

    # write-baseline accepts everything; next run is clean via default
    # baseline discovery in cwd
    assert cli([str(bad), "--write-baseline"]) == 0
    assert (tmp_path / DEFAULT_BASELINE).exists()
    capsys.readouterr()
    assert cli([str(bad)]) == 0
    assert cli([str(bad), "--no-baseline"]) == 1

    # warnings don't fail unless --strict
    warn = tmp_path / "warn.py"
    warn.write_text("import jax\n\n\ndef build():\n"
                    "    return jax.random.PRNGKey(7)\n")
    capsys.readouterr()
    assert cli([str(warn), "--no-baseline"]) == 0
    assert cli([str(warn), "--no-baseline", "--strict"]) == 1

    # usage errors
    assert cli(["missing_dir_xyz"]) == 2
    assert cli([str(bad), "--select", "NOPE01"]) == 2


def test_cli_select_rules(tmp_path, capsys):
    f = tmp_path / "lib.py"
    f.write_text(BAD_SRC)
    assert cli([str(f), "--select", "JIT001", "--no-baseline"]) == 0
    assert cli([str(f), "--select", "RNG001", "--no-baseline"]) == 1
    capsys.readouterr()


def test_cli_list_rules(capsys):
    assert cli(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("RNG001", "RNG002", "RNG003", "JIT001", "JIT002",
                "JIT003", "DON001", "HOST001"):
        assert rid in out
