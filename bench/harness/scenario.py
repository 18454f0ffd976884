"""Program objects built from a cell's data: its ``Scenario`` and ``FLConfig``.

The cell file holds the whole scenario as data (constellation, regions,
dynamics, federation, serving, horizon), so an edit to the program's
preset registry does not move the benchmark's traffic.
"""
from __future__ import annotations

import dataclasses


def _tuples(d: dict) -> dict:
    """JSON lists back to the tuples the program's dataclasses hold."""
    return {k: tuple(v) if isinstance(v, list) else v for k, v in d.items()}


def build_scenario(spec: dict):
    from repro.fl.federation import FederationConfig
    from repro.scenarios.registry import Scenario
    from repro.serve.workload import ServeConfig
    from repro.sim.dynamics import DynamicsConfig
    from repro.sim.propagation import Region

    spec = dict(spec)
    spec["regions"] = tuple(Region(*r) for r in spec["regions"])
    for key, cls in (("dynamics", DynamicsConfig),
                     ("federation", FederationConfig),
                     ("serve", ServeConfig)):
        if spec.get(key) is not None:
            spec[key] = cls(**_tuples(spec[key]))
    return Scenario(**spec)


def build_fl_config(cell: dict, config: dict):
    """The region trainers' configuration: the cell's population, the
    config's dataset (which selects the program's model) and learning
    rate, and the cell's ``structure_seed``, which fixes the network, the
    offloading plans and hence the bucket layouts, whatever ``--seed``."""
    from repro.fl import FLConfig
    fields = {f.name for f in dataclasses.fields(FLConfig)}
    pop = dict(cell["population"])
    unknown = set(pop) - fields
    if unknown:
        raise ValueError(f"{cell['name']}: unknown population keys "
                         f"{sorted(unknown)}")
    return FLConfig(dataset=config["dataset"], lr=config["lr"],
                    seed=cell["structure_seed"], execution="batched",
                    **pop)
