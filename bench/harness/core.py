"""What every cell shares: finding its files by name, the chip, the compile
cache, the clocks, and the result line.

A cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/<config>.json``) and its driver
(``bench/drivers/<driver>.py``).  Per-layer metrics are the files of
``bench/metrics/``.  Nothing here lists them: a file added there is found.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: JAX's persistent compile cache: a fixed path inside the checkout, so
#: that only the first run of a cell in a checkout compiles.
CACHE_DIR = ROOT / ".bench_jax_cache"


class BenchError(RuntimeError):
    """A run that cannot give a result: it exits non-zero, printing none."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(kind: str, name: str, suffix: str) -> Path:
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        have = sorted(p.stem for p in (BENCH / kind).glob(f"*{suffix}"))
        raise BenchError(f"no {kind[:-1]} {name!r} (bench/{kind}/ has "
                         f"{have})")
    return path


def workload(name: str) -> dict:
    cell = load_json(_named("workloads", name, ".json"))
    cell.setdefault("name", name)
    return cell


def config(name: str) -> dict:
    cfg = load_json(_named("configs", name, ".json"))
    cfg.setdefault("name", name)
    return cfg


def _module(path: Path, prefix: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str) -> ModuleType:
    return _module(_named("drivers", name, ".py"), "bench_driver")


def metric_modules() -> Dict[str, ModuleType]:
    """Every per-layer metric, by name (the file's stem)."""
    out = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        if path.name.startswith("_"):
            continue
        out[path.stem] = _module(path, "bench_metric")
    return out


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def program_path() -> None:
    """Put the system under test (``src/``) on the import path; a
    checkout without it cannot run a cell."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program under {src}: nothing to measure")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def setup_jax() -> None:
    """The compile cache in the checkout, every program written to it."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_tpu(chips: int) -> dict:
    """The device record of the result line; an error where JAX finds no
    TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: JAX's platform is {d.platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, {len(devs)} visible")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileClock:
    """XLA backend-compile seconds and count, from ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self) -> tuple:
        return self.seconds, self.count


def say(msg: str) -> None:
    """A progress line on standard error."""
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Seconds since the process started the benchmark."""

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def now(self) -> float:
        return time.perf_counter() - self.t0


def check(name: str, value: float, limit: float) -> dict:
    """One compared number beside its limit; ``ok`` where it is finite and
    at most the limit."""
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(math.isfinite(value) and value <= limit)}


def emit(result: dict, checks: List[dict]) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output, the checks under its last key."""
    for c in checks:
        say(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)

