"""Driver of `repro.fed.distributed.make_round`, cross-silo fine-tuning.

One silo per chip on `fed_mesh(silos, 1)`; one timed call is one round:
the harness draws the round's token windows from the seed (host span
`draw_batch`), places them, dispatches `round_fn` and blocks on the new
parameters.  The reference computes each silo's upload on that silo's
chip, then Eq. 10-12 and the server step leaf by leaf on the first chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from chipbench import traffic
from chipbench.counts import dense_lm as counts
from chipbench.reference import dense_lm as ref_model, fedncv


def arch_config(cfg):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg.get("head_dim"), rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])


class Driver:
    rounds_per_step = 1

    def __init__(self, run):
        from repro.fed.api import get_method
        from repro.fed.distributed import init_distributed_state, make_round
        from repro.fed.methods import MethodConfig, Task
        from repro.models import api
        from repro.sharding import make_mesh

        self.cfg, self.t, self.cell = run.config, run.traffic, run.cell
        self.seed, self.key, self.devices = run.seed, run.key, run.devices
        t, m = self.t, run.cell["method"]
        n = t["silos"]
        self.units_per_step = n * t["k_micro"] * t["batch"] * t["seq"]
        self.flops_per_step = self.units_per_step \
            * counts.train_flops_per_token(self.cfg, t["seq"])
        self.mesh = make_mesh((n, 1), ("cohort", "model"),
                              devices=self.devices[:n])
        self.rep = NamedSharding(self.mesh, P())
        self.csh = NamedSharding(self.mesh, P("cohort"))
        arch = arch_config(self.cfg)
        task = Task(loss=lambda p, b: api.loss(arch, p, b))
        self.m = m
        mc = MethodConfig(name="fedncv", ncv_alpha0=m["alpha0"],
                          ncv_alpha_lr=m["alpha_lr"], ncv_beta=m["beta"])
        self.round_fn = make_round("fedncv", task, self.mesh, mc,
                                   server_lr=m["server_lr"])
        self._init = jax.jit(lambda k: ref_model.init(self.cfg, k),
                             out_shardings=self.rep)
        self._loss = jax.jit(lambda p, b: ref_model.loss(self.cfg, p, b))
        self.p = self._init(self.key)
        self.state = jax.device_put(init_distributed_state(
            get_method("fedncv"), jax.eval_shape(lambda: self.p), task, mc,
            n_clients=n), self.csh)
        self.toks = traffic.token_stream(t, self.cfg["vocab_size"],
                                         self.seed)
        self.weights = traffic.silo_weights(t)
        self.n_samples = jax.device_put(jnp.asarray(self.weights), self.csh)
        self.r = 0

    def batch(self, r):
        with jax.profiler.TraceAnnotation("draw_batch"):
            b = traffic.silo_round(self.t, self.toks, self.seed, r)
            return jax.device_put(b, self.csh)

    def step(self):
        batch = self.batch(self.r)
        with jax.profiler.TraceAnnotation("dispatch"):
            p, st, metrics = self.round_fn(self.p, self.state, batch,
                                           self.n_samples, jnp.int32(self.r))
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready(p)
        self.p, self.state, self.r = p, st, self.r + 1
        return bool(np.isfinite(float(metrics["agg_norm"])))

    def init_params(self):
        """The seed's initial parameters, as the program was given them."""
        return self._init(self.key)

    def params(self):
        return jax.device_get(self.p)

    def alphas(self):
        """The silos' alphas, the method's state, copied to the host."""
        return np.asarray(jax.device_get(self.state["alphas"]))

    def release(self):
        for x in jax.tree.leaves((self.p, self.state)):
            x.delete()
        self.p = self.state = None

    # ---- the plain reference ----------------------------------------

    def reference(self, n_steps, dtype, fault=None):
        """fault plants a fault in the reference: "half_batch" replaces
        each silo's later half of microbatches by its first half;
        "no_exchange" leaves out the exchange between chips, so the
        first chip steps with its own silo's upload alone."""
        t, m, cfg = self.t, self.m, self.cfg
        n, devs = t["silos"], self.devices[:t["silos"]]
        k_used = t["k_micro"] // 2 if fault == "half_batch" else t["k_micro"]
        grad_fn = jax.grad(lambda p, b: ref_model.loss(cfg, p, b))

        @jax.jit
        def upload(params, batch, alpha):
            mbs = [jax.tree.map(lambda x: x[k % k_used], batch)
                   for k in range(t["k_micro"])]
            return fedncv.client_upload(grad_fn, params, mbs,
                                        alpha.astype(dtype), 1, 0.0)

        weights = [float(w) for w in self.weights]
        beta, lr = m["beta"], m["server_lr"]
        if fault == "no_exchange":
            n, devs, weights = 1, devs[:1], weights[:1]

        @jax.jit
        def server_leaf(p, *gs):
            agg = fedncv.aggregate(list(gs), weights, beta)
            return (p - lr * agg).astype(p.dtype)

        params = jax.jit(lambda k: ref_model.init(cfg, k, dtype),
                         out_shardings=SingleDeviceSharding(devs[0]))(
            self.key)
        alphas = jnp.full((n,), m["alpha0"], dtype)
        out = []
        for r in range(n_steps):
            b = traffic.silo_round(t, self.toks, self.seed, r)
            ups, s1 = [], []
            for u, dev in enumerate(devs):
                pu = params if u == 0 else jax.device_put(params, dev)
                bu = jax.device_put(jax.tree.map(lambda x: x[u], b), dev)
                g, s = upload(pu, bu, jax.device_put(alphas[u], dev))
                ups.append(g)
                s1.append(s)
            del pu
            leaves = [jax.tree.leaves(g) for g in ups]
            del ups
            new = []
            for i, p in enumerate(jax.tree.leaves(params)):
                gs = [jax.device_put(lv[i], devs[0]) for lv in leaves]
                new.append(server_leaf(p, *gs))
                for lv in leaves:
                    lv[i] = None
            params = jax.tree.unflatten(jax.tree.structure(params), new)
            alphas = alphas.at[:n].set(fedncv.alpha_update(
                alphas[:n], jnp.stack([jax.device_get(s) for s in s1]),
                m["alpha_lr"]).astype(dtype))
            out.append(jax.device_get(params))
            if len(out) == 1:
                self.ref_alphas = np.asarray(alphas, np.float32)
        return out

    def ref_loss(self, params):
        batch = traffic.heldout_tokens(self.t, self.toks, self.seed)
        p = jax.device_put(jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), params), self.devices[0])
        return float(self._loss(p, jax.device_put(batch, self.devices[0])))
