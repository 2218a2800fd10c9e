"""Driver of `repro.fed.Simulator.run_rounds`, the paper protocol.

One timed call is `run_rounds(rounds_per_call)`: a scan of that many
rounds in one dispatch, whose per-round diagnostics come back to the
host (so each call is blocked on).  The reference replays the
Simulator's documented draws from its seed: round i's key is
fold_in(PRNGKey(seed), i), split into the cohort key and the microbatch
key, the cohort a uniform draw without replacement, each client's rows
uniform with replacement from its shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.counts import lenet5 as counts
from chipbench.reference import fedncv, lenet5 as ref_model


class Driver:
    def __init__(self, run):
        from repro.fed import FLConfig, Simulator
        from repro.fed.methods import Task
        from repro.models import lenet

        self.cfg, self.t, self.cell = run.config, run.traffic, run.cell
        t, m = self.t, run.cell["method"]
        self.sim_seed = run.seed % (2 ** 31 - 1)
        self.train, self.heldout = traffic.image_clients(t)
        self.units_per_step = self.rounds_per_step = t["rounds_per_call"]
        samples = t["cohort"] * t["k_micro"] * t["micro_batch"] \
            * m["local_epochs"]
        self.flops_per_step = self.units_per_step * samples \
            * counts.train_flops_per_sample(self.cfg)
        self.key, self.m = run.key, m
        self._init = jax.jit(lambda k: ref_model.init(self.cfg, k))
        self._loss = jax.jit(ref_model.loss)
        params = self._init(self.key)
        lcfg = lenet.LeNetConfig(n_classes=self.cfg["n_classes"],
                                 image_size=self.cfg["image_size"],
                                 channels=self.cfg["channels"])
        task = Task(loss=lambda p, b: lenet.loss_fn(lcfg, p, b))
        self.fl = dict(n_clients=t["n_clients"], cohort=t["cohort"],
                       k_micro=t["k_micro"], micro_batch=t["micro_batch"],
                       server_lr=m["server_lr"], local_lr=m["local_lr"],
                       local_epochs=m["local_epochs"],
                       ncv_alpha0=m["alpha0"], ncv_alpha_lr=m["alpha_lr"],
                       ncv_beta=m["beta"])
        fl = FLConfig.make(method="fedncv", sampler="uniform",
                           codec="identity", store="device",
                           tracker="none", **self.fl)
        self.sim = Simulator(task, params, self.train, fl,
                             seed=self.sim_seed)

    def step(self):
        diag = self.sim.run_rounds(self.units_per_step)
        return all(np.isfinite(v).all() for v in diag.values())

    def init_params(self):
        """The seed's initial parameters, as the program was given them."""
        return self._init(self.key)

    def params(self):
        return jax.device_get(self.sim.params)

    def alphas(self):
        """The clients' alphas, the method's state, copied to the host."""
        return np.asarray(jax.device_get(self.sim._state["alphas"]))

    def release(self):
        for x in jax.tree.leaves((self.sim.params, self.sim._state)):
            x.delete()
        self.sim = None

    # ---- the plain reference ----------------------------------------

    def _draw(self, i):
        """Round i's cohort and rows, as the Simulator draws them."""
        t = self.t
        key = jax.random.fold_in(jax.random.PRNGKey(self.sim_seed), i)
        kd, _ = jax.random.split(key)
        kc, kp = jax.random.split(kd)
        idx = jax.random.choice(kc, t["n_clients"], (t["cohort"],),
                                replace=False)
        sizes = jnp.asarray(self.train["client_sizes"])[idx].astype(
            jnp.float32)
        pool = jnp.asarray(self.train["client_idx"])[idx]
        need = t["k_micro"] * t["micro_batch"]
        u = jax.random.uniform(kp, (t["cohort"], need))
        pos = jnp.minimum((u * sizes[:, None]).astype(jnp.int32),
                          sizes[:, None].astype(jnp.int32) - 1)
        sel = jnp.take_along_axis(pool, jnp.maximum(pos, 0), axis=1)
        sel = jnp.maximum(sel, 0).reshape(t["cohort"], t["k_micro"],
                                          t["micro_batch"])
        return np.asarray(idx), np.asarray(sizes), np.asarray(sel)

    def reference(self, n_steps, dtype, fault=None):
        """fault="half_batch" plants a fault in the reference: each
        client's later half of microbatches replaced by its first half."""
        t, fl = self.t, self.fl
        k_used = t["k_micro"] // 2 if fault == "half_batch" else t["k_micro"]
        grad_fn = jax.grad(ref_model.loss)

        @jax.jit
        def upload(params, alpha, images, labels):
            batches = [dict(images=images[k % k_used],
                            labels=labels[k % k_used])
                       for k in range(t["k_micro"])]
            return fedncv.client_upload(grad_fn, params, batches,
                                        alpha.astype(dtype),
                                        fl["local_epochs"], fl["local_lr"])

        @jax.jit
        def server(params, alphas, uploads, s1, idx, sizes):
            agg = fedncv.aggregate(uploads, list(sizes), fl["ncv_beta"])
            params = jax.tree.map(
                lambda p, g: (p - fl["server_lr"] * g).astype(p.dtype),
                params, agg)
            new = fedncv.alpha_update(alphas[idx], jnp.stack(s1),
                                      fl["ncv_alpha_lr"]).astype(alphas.dtype)
            return params, alphas.at[idx].set(new)

        params = jax.jit(lambda k: ref_model.init(self.cfg, k, dtype))(
            self.key)
        alphas = jnp.full((t["n_clients"],), fl["ncv_alpha0"], dtype)
        out, i = [], 0
        for _ in range(n_steps):
            for _ in range(self.units_per_step):
                idx, sizes, sel = self._draw(i)
                ups, s1 = zip(*[upload(params, alphas[idx[c]],
                                       self.train["images"][sel[c]],
                                       self.train["labels"][sel[c]])
                                for c in range(t["cohort"])])
                params, alphas = server(params, alphas, list(ups), list(s1),
                                        idx, sizes)
                i += 1
            out.append(jax.device_get(params))
            if len(out) == 1:
                self.ref_alphas = np.asarray(alphas, np.float32)
        return out

    def ref_loss(self, params):
        batch = jax.tree.map(jnp.asarray, self.heldout)
        p = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        return float(self._loss(p, batch))
