"""A benchmark cell from its files: ``BENCHMARK.json`` names the cell's
configuration and traffic mix, ``configs/<config>.json`` holds the
deployment (pages, machines, workload components) and
``traffic/<cell>.json`` the policy panel and a sweep's intervals.  ``Cell`` builds the program's
specs from those copies through its public constructors, so a change of
a program default cannot move what is measured.
"""
from __future__ import annotations

import dataclasses
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict

    @classmethod
    def load(cls, name: str, bench: dict | None = None) -> "Cell":
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        ent = cells.get(name)
        if ent is None:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        return cls(name=name, chips=int(ent["chips"]),
                   config=_load("configs", ent["config"]),
                   traffic=_load("traffic", ent["traffic"]))

    def scaled(self, pages: int, fast_pages: int, intervals: int,
               lanes_per_policy: int | None = None) -> "Cell":
        """The same cell at another size (tests on the CPU)."""
        cfg = dict(self.config, pages=pages, fast_pages=fast_pages)
        tr = json.loads(json.dumps(self.traffic))
        tr["intervals"] = intervals
        if lanes_per_policy is not None:
            tr["check"] = dict(tr["check"], lanes_per_policy=lanes_per_policy)
        return dataclasses.replace(self, config=cfg, traffic=tr)

    # -------------------------------------------------------------- sizes
    @property
    def n(self) -> int:
        return int(self.config["pages"])

    @property
    def k(self) -> int:
        return int(self.config["fast_pages"])

    @property
    def T(self) -> int:
        """Intervals a sweep runs (the traffic mix's choice of window)."""
        return int(self.traffic["intervals"])

    @property
    def policies(self) -> list:
        return self.traffic["policies"]

    @property
    def workloads(self) -> list:
        by = {w["name"]: w for w in self.config["workloads"]}
        return [by[nm] for nm in self.traffic["workloads"]]

    @property
    def machines(self) -> list:
        return list(self.traffic["machines"])

    @property
    def lanes(self) -> int:
        return len(self.policies) * len(self.workloads) * len(self.machines)

    # ------------------------------------------------- the program's specs
    def workload_specs(self):
        import jax.numpy as jnp
        from repro.simulator import workload_spec as ws
        out = []
        for w in self.workloads:
            cols = {}
            for f in ws.WorkloadSpec.__dataclass_fields__:
                vals = [c[f] for c in w["components"]]
                dt = jnp.int32 if isinstance(vals[0], int) else jnp.float32
                cols[f] = jnp.asarray(vals, dt)
            out.append(ws.with_label(ws.WorkloadSpec(**cols), w["name"]))
        return out

    def machine_specs(self):
        from repro.simulator import machine_spec
        out = []
        for nm in self.machines:
            m = self.config["machines"][nm]
            out.append(machine_spec.make(
                nm, m["lat_ns"], m["bw_read"], m["bw_write"],
                capacity_pages=m["capacity_pages"], mlp=m["mlp"]))
        return out

    def policy_specs(self):
        from repro.simulator import experiment
        out = []
        for p in self.policies:
            default = experiment.policy_spec(p["family"])
            cls, knobs = type(default), dict(p["knobs"])
            if not knobs:
                out.append(default)
            elif hasattr(default, "base_cfg"):
                cfg = dataclasses.replace(default.base_cfg, **knobs)
                out.append(cls.make(base_cfg=cfg))
            else:
                out.append(cls.make(**knobs))
        return out

    def sweep_args(self, seed: int) -> dict:
        """Keyword arguments of the cell's ``experiment.sweep`` call."""
        return dict(policies=self.policy_specs(),
                    workloads=self.workload_specs(),
                    machines=self.machine_specs(), seeds=(0,), k=self.k,
                    T=self.T, n=self.n, sim_seed=seed, wl_seed=seed)
