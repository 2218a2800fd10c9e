"""Driver of `repro.fed.distributed.make_round` for an LM configuration
stated with its RMSNorm eps and sliding window, one round per timed call.

It is `make_round.Driver` (mesh, weights, traffic, plain reference) with
three changes: the program's ArchConfig carries the configuration's
`rms_norm_eps` and `sliding_window`; a timed call counts one round
(`units_per_step = 1`, so the harness reports rounds/s); and the host
spans are `repro.track.span`s from `track.HOST_SPANS`, each with the
round's index as its `round` id.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.drivers import make_round as base


def arch_config(cfg):
    """The program's ArchConfig for a configuration file."""
    return base.arch_config(cfg).replace(
        norm_eps=cfg["rms_norm_eps"], sliding_window=cfg.get("sliding_window"))


class Driver(base.Driver):
    def __init__(self, run):
        from repro.fed import distributed
        from repro.fed.methods import MethodConfig, Task
        from repro.models import api

        arch = arch_config(run.config)
        super().__init__(run)
        self.units_per_step = 1
        m = self.m
        mc = MethodConfig(name="fedncv", ncv_alpha0=m["alpha0"],
                          ncv_alpha_lr=m["alpha_lr"], ncv_beta=m["beta"])
        self.round_fn = distributed.make_round(
            "fedncv", Task(loss=lambda p, b: api.loss(arch, p, b)),
            self.mesh, mc, server_lr=m["server_lr"])

    def step(self):
        from repro import track
        r = self.r
        with track.span(track.DRAW_BATCH, round=r):
            batch = jax.device_put(
                traffic.silo_round(self.t, self.toks, self.seed, r), self.csh)
        with track.span(track.DISPATCH, round=r):
            p, st, metrics = self.round_fn(self.p, self.state, batch,
                                           self.n_samples, jnp.int32(r))
        with track.span(track.BLOCK, round=r):
            jax.block_until_ready(p)
            metrics = jax.device_get(metrics)
        self.p, self.state, self.r = p, st, r + 1
        self.metrics = metrics
        return all(np.isfinite(v) for v in metrics.values())
