"""A cell's inputs and the program under test, built from the run seed.

``seeds(seed, traffic)`` gives the data and weight seeds of a run and the
cell's fixed scheduler and server seeds. ``build`` makes the client data and the initial weights
(benchmark code, so the reference can take them too) and hands them to
the program's driver, ``FusedPAOTA`` on one chip or ``ShardedPAOTA`` over
a client mesh, through the constructor a user calls.
"""
from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

import jax
import numpy as np

import data
from reference import Seeds

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def model_module(cfg):
    return importlib.import_module(f"models.{cfg['model']}")


@dataclass
class RunSeeds:
    data: int
    weights: int
    fl: Seeds


def seeds(seed: int, traffic: dict) -> RunSeeds:
    """The data and weight seeds from the run seed (any size); the
    federation's random streams (latencies, channel, noise, minibatches)
    from the traffic's ``schedule_seed``. The program compiles its stream
    keys into its programs as constants, so a key that followed the run
    seed would compile every run anew; with it fixed, every seed runs
    the same arrivals on other data and weights."""
    s = np.random.SeedSequence(int(seed)).generate_state(2) & 0x7FFFFFFF
    fixed = int(traffic["schedule_seed"])
    return RunSeeds(data=int(s[0]), weights=int(s[1]),
                    fl=Seeds(sched=fixed, server=fixed))


@dataclass
class World:
    fed: data.Federation
    w0: object            # initial weights, on the device
    driver: object        # the program's FusedPAOTA / ShardedPAOTA
    seeds: RunSeeds


def build(cfg: dict, traffic: dict, seed: int, devices) -> World:
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.pipeline import ClientData
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig, ShardedPAOTA

    model = model_module(cfg)
    rs = seeds(seed, traffic)
    k = traffic["clients"]
    fed = data.make(rs.data, rs.fl.sched, k, traffic["data"], cfg)
    w0 = model.init(jax.random.PRNGKey(rs.weights), cfg)
    loss = model.program_loss(cfg)
    clients = [FLClient(ClientData(*fed.client(i), i), loss,
                        batch_size=traffic["batch"], lr=traffic["lr"],
                        local_steps=traffic["local_steps"])
               for i in range(k)]
    lo, hi = traffic["latency_s"]
    kw = dict(params_mode=traffic["params_mode"],
              pending_dtype=traffic["pending_dtype"])
    for key in ("cohort_size", "compress", "compress_ratio", "slot_dtype"):
        if key in traffic:
            kw[key] = traffic[key]
    cls = FusedPAOTA
    if traffic["driver"] == "sharded":
        from repro.launch.mesh import make_client_mesh
        cls = ShardedPAOTA
        kw["mesh"] = make_client_mesh(len(devices))
    driver = cls(w0, clients, ChannelConfig(),
                 SchedulerConfig(n_clients=k, delta_t=traffic["delta_t"],
                                 lat_lo=lo, lat_hi=hi, seed=rs.fl.sched),
                 PAOTAConfig(solver="waterfill_jnp", seed=rs.fl.server,
                             transmit=traffic["transmit"]), **kw)
    return World(fed=fed, w0=w0, driver=driver, seeds=rs)
