"""In-process FL simulator: device-resident, the cohort dimension is vmapped.

Reproduces the paper's experimental protocol: M clients with Dirichlet(α)
non-IID shards, a sampled cohort per round, local training, server
aggregation per method, and pre-/post-personalization evaluation
("test before" / "test after" in Table 1).

The whole round lives on device: the cohort draw (a registered
`repro.fed.sampling.CohortSampler` — uniform by default, importance/
similarity for variance-aware selection, DESIGN.md §8), microbatch gather
(`jnp.take` into the resident dataset), the vmapped client pass, and the
per-method server update all run inside one jit.  Multi-round
driving goes through `run_rounds(n)`, which `lax.scan`s the round body with
donated params/state buffers so an n-round benchmark pays one dispatch + one
host sync instead of n.  Evaluation is a single padded, vmapped pass over
all clients (padded positions are masked with label -1 and corrected by the
true shard size) instead of one trace per client.

`FLConfig.codec` selects the client->server wire format (repro.comm): the
uploaded gradients leave each client compressed, the servers aggregate
straight off the wire (fused dequantize-aggregate for int8/int4), per-client
codec state (top-k error-feedback residuals) is carried like `alphas`,
and every round reports `bytes_up` (DESIGN.md §5).

Multi-device (DESIGN.md §6): constructed with a 1-d `mesh`
(`sharding.cohort_mesh()`), the cohort section of the round — microbatch
gather, vmapped client passes, wire encode, and the fused Eq. 10-12
reduction — runs inside a `shard_map` over the cohort dimension: each
device touches only its 1/D slice of the (cohort, ...) stacks and the
partial weighted sums meet in a single psum (fed/sharded.py).  Cohorts
that do not divide the device count are padded with zero-weight slots
(exact no-ops).  Per-client EF residual storage is kept sharded over the
mesh when M divides the device count.

Async rounds (DESIGN.md §6, §12): `FLConfig.staleness = 1` double-buffers
the cohort — round r's client passes are issued against the params that
round r-1's server update has not yet touched, and that server update
completes in the same scan step, giving one-round-staleness overlap.
Round 1 is the pipeline bubble (no update is applied; its diagnostics row
reads zero).  Bounded staleness: every applied update is exactly one round
old — `theta_r = server(theta_{r-1}, clients(theta_{r-2}, cohort_{r-1}))`.
`staleness = K >= 2` generalizes the double buffer to a **ring of K
pending cohorts** (DESIGN.md §12): the cohort issued at round r is applied
at round r+K, the first K rounds are warmup bubbles (zeroed diagnostics
rows, gated by the ring's per-slot valid flags), and every applied update
is exactly K rounds old.  K=0 and K=1 take the historical sync/async round
bodies unchanged — their trajectories are bit-identical to prior releases.

Methods are `fed.api.FedMethod` strategies resolved from the registry
(DESIGN.md §7): all per-client/global state handling — init, cohort
gather/scatter, checkpointing — is driven by the method's `state_spec()`,
so the round body here is method-agnostic.  The same strategies are reused
by the mesh-distributed runtime (fed/distributed.py), so what this
simulator validates is exactly what runs on the pod.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import comm
from repro import track
from repro.fed import aggregators
from repro.fed import api
from repro.fed import faults
from repro.fed import methods as M
from repro.fed import sampling
from repro.fed import sharded
from repro.fed import store as store_lib
from repro.fed.api import FLConfig  # noqa: F401  (re-export: public API)
from repro.utils.tree_math import (
    flat_spec, ravel_stack, tree_bytes, tree_norm_sq, unravel,
)


def _tree_where(flag, new, old):
    """Elementwise select over a pytree: `new` where flag > 0, else `old`."""
    return jax.tree.map(lambda a, b: jnp.where(flag > 0, a, b), new, old)


class Simulator:
    def __init__(self, task: M.Task, params, data, fl: FLConfig, seed=0,
                 mesh=None, tracker=None):
        """data: dict(images (N,...), labels (N,), client_idx (M, n_max) int32
        padded with -1, client_sizes (M,)).

        mesh: optional 1-d device mesh (`sharding.cohort_mesh()`): the
        cohort dimension of the round is shard_map'd over it (DESIGN.md §6).

        tracker: optional `repro.track.Tracker` *instance* overriding
        `fl.tracker`/`fl.tracker_opts` — for programmatic sinks (a composite
        built by a server loop, a memory sink a test inspects).
        """
        self.task, self.fl = task, fl
        self.method = api.get_method(fl.method)
        self._fields = self.method.state_spec(task, fl.mc)
        # backing store for per-client state + data (fed/store.py, §11):
        # "device" keeps the historical fully-resident layout, bit-identical;
        # "host" keeps the (M, ...) tables host-side and stages only the
        # cohort slice on device each round, prefetch-overlapped
        self.store = store_lib.get_store(fl.store)
        self._store_opts = store_lib.resolve_opts(self.store, fl.store_opts)
        self._host_mode = self.store.host_resident
        self.mesh = mesh
        if mesh is not None:
            # 1-d (cohort,) or 2-d (cohort, model) fed mesh (DESIGN.md §13):
            # the FIRST axis is always the manually-collective cohort axis;
            # any further axes are GSPMD ("auto") model axes the shard_map
            # regions never mention — parameter leaves shard over them via
            # `param_spec` and every collective here reduces over the
            # cohort axis alone.
            assert len(mesh.axis_names) >= 1, mesh.axis_names
            self.caxis = mesh.axis_names[0]
            self.maxes = tuple(mesh.axis_names[1:])
            self._auto = frozenset(self.maxes)
            self.n_devices = int(mesh.shape[self.caxis])
            rep = NamedSharding(mesh, P())
            if self.maxes:
                from repro.sharding import params_shardings
                params = jax.device_put(
                    params,
                    params_shardings(jax.eval_shape(lambda: params), mesh))
            else:
                params = jax.device_put(params, rep)
            if not self._host_mode:
                data = {k: jax.device_put(jnp.asarray(v), rep)
                        for k, v in data.items()}
        else:
            self.maxes = ()
            self._auto = frozenset()
        self.params = params
        if self._host_mode:
            # data tensors live in the host tables; the cohort draw is an
            # M-wide device computation, so client_sizes (O(M) scalars, not
            # an O(M·N) table) stays device-resident for the select jit
            self._host = self.store.make_tables(self._store_opts)
            for k, v in data.items():
                if k != "client_sizes":
                    self._host.adopt("data:" + k, v)
            self._pool_np = self._host.get("data:client_idx")
            self._sizes_dev = jnp.asarray(np.asarray(data["client_sizes"]))
            self.data = None
        else:
            self._host = None
            self.data = {k: jnp.asarray(v) for k, v in data.items()}
        self.base_key = jax.random.PRNGKey(seed)
        m = fl.n_clients

        # client->server wire format (grads share the params' structure)
        self._grad_spec = flat_spec(params, stacked=False)
        self.codec = comm.get_codec(fl.codec, n=self._grad_spec.n,
                                    spec=self._grad_spec, **fl.codec_opts)
        # partial averaging (DESIGN.md §13.4): the combined federated_slice
        # mask over the param pytree, or None when no field declares one
        self._fed_mask = api.federated_mask(self._fields, params, task,
                                            fl.mc)
        from repro.kernels import default_interpret
        self._use_pallas = not default_interpret()

        # cohort selection strategy (repro.fed.sampling, DESIGN.md §8):
        # the draw runs inside jit each round; sampler state (if any) lives
        # under the "sampler" key of the run state dict, and samplers that
        # consume per-client statistics get them via the client-pass
        # wrapper (sampling.with_stats) riding the aux dict
        self.smp = sampling.get_sampler(fl.sampler)
        self._smp_opts = sampling.resolve_opts(self.smp, fl.sampler_opts)
        d_sketch = self.smp.sketch_dim(self._smp_opts)
        self._sketch_proj = sampling.sketch_projection(
            self._grad_spec.n, d_sketch) if d_sketch else None

        # server-side aggregation strategy (fed.aggregators, DESIGN.md §9):
        # "mean" keeps every historical fused Eq. 10-12 path bit-identical;
        # robust aggregators reduce the decoded flat stack instead
        self.agg = aggregators.get_aggregator(fl.aggregator)
        self._agg_opts = aggregators.resolve_opts(self.agg, fl.agg_opts)

        # client fault injection (fed.faults, DESIGN.md §9): the plan is
        # drawn inside jit each round; the capability flags below are
        # static per-configuration facts the build branches on once —
        # fault="none" takes the exact pre-fault round body
        self.fm = faults.get_fault(fl.fault)
        self._fm_opts = faults.resolve_opts(self.fm, fl.fault_opts)
        self._fault_on = self.fm.plan is not None
        self._fm_drops = self._fault_on and self.fm.drops(self._fm_opts)
        self._fm_corrupts = self._fault_on and \
            self.fm.corrupts(self._fm_opts)
        self._fm_flips = self._fault_on and self.fm.flips(self._fm_opts)
        self._n_classes = int(np.max(np.asarray(data["labels"]))) + 1 \
            if self._fm_flips else None

        # streaming telemetry (repro.track, DESIGN.md §10): the sink is a
        # host-side object the jitted round emits into through one ordered
        # io_callback appended AFTER the server section — always outside
        # the shard_map region, on already-replicated scalars.  The
        # default "none" sink wires nothing: no callback op enters the
        # graph, so an untracked run's trajectory and HLO are unchanged.
        self.tracker = tracker if tracker is not None \
            else track.make_tracker(fl.tracker, **fl.tracker_opts)
        self._track_on = not isinstance(self.tracker, track.NullTracker)
        # ordered token-threaded emission off-mesh; on a mesh the jit also
        # holds shard_map collectives, where jax 0.9.0's partitioner
        # rejects the ordered-effect token (track.emitter docstring) — the
        # unordered callback is pinned to one device and rows carry the
        # round index
        self._emit = track.emitter(self.tracker, ordered=mesh is None) \
            if self._track_on else None
        self._track_var = bool(fl.track_variance)

        # method + codec state, built from the declarative state_spec():
        # per-client fields live in (M, ...) buffers gathered/scattered at
        # the cohort indices, global fields are plain pytrees.  The codec's
        # per-client error-feedback residuals ride under "ef"; under a mesh
        # the (M, N) buffer is stored sharded over clients (scatter/gather
        # at the cohort indices is resolved by GSPMD).
        self._host_state_names: list = []
        if self._host_mode:
            # host store: per-client tables are built host-side from ONE
            # init row (every client starts from the same row — exactly
            # what the device store's vmapped init produces), so no
            # M-sized device buffer is ever materialized.  Global fields
            # stay in the device-resident state dict.
            self._state = {}
            for f in self._fields:
                if f.per_client:
                    row = jax.tree.map(np.asarray, f.init(params, task,
                                                          fl.mc))
                    self._host.add(f.name, row, m)
                    self._host_state_names.append(f.name)
                else:
                    self._state[f.name] = f.init(params, task, fl.mc)
            if self.codec.stateful:
                self._host.add(
                    "ef", jax.tree.map(np.asarray, self.codec.init_state()),
                    m)
                self._host_state_names.append("ef")
        else:
            self._state = api.init_state(self._fields, params, task, fl.mc,
                                         m, codec=self.codec)
            if self.codec.stateful and mesh is not None \
                    and m % self.n_devices == 0:
                # codec state may be a pytree (lowrank's residual + bases);
                # every leaf carries the (M, ...) client-leading dim
                self._state["ef"] = jax.device_put(
                    self._state["ef"], NamedSharding(mesh, P(self.caxis)))
            if self.maxes:
                self._place_pspec_fields(m)
        # stateful samplers carry their tables in the same state dict
        # ("sampler" key): scanned, checkpointed, restored like alphas/EF.
        # Stateless samplers (uniform) leave the dict untouched, so the
        # state layout — and pre-sampling checkpoints — are unchanged.
        if self.smp.stateful:
            if any(f.name == "sampler" for f in self._fields):
                raise ValueError(
                    "method state field 'sampler' collides with the cohort "
                    "sampler's state key; rename the StateField")
            self._state["sampler"] = self.smp.init_state(self._smp_opts, m)
        # stateful fault models (the Markov availability trace) carry
        # their per-client state the same way, under the "faults" key
        if self._fault_on and self.fm.stateful:
            if any(f.name == "faults" for f in self._fields):
                raise ValueError(
                    "method state field 'faults' collides with the fault "
                    "model's state key; rename the StateField")
            self._state["faults"] = self.fm.init_state(self._fm_opts, m)

        # async pipeline buffers (round in flight; None until first round).
        # staleness=1 carries (pending, valid); staleness>=2 carries the
        # depth-K ring (`_ring` = (ring, rvalid, pos), DESIGN.md §12).
        self._pending = None
        self._valid = jnp.float32(0.0)
        self._ring = None

        self.round_idx = 0
        self._round_jit = jax.jit(self._round_core)
        # donate params + state: the scanned buffers are consumed in place,
        # multi-round driving never copies the model between rounds.
        self._scan_jit = jax.jit(self._scan_rounds, donate_argnums=(0, 1))
        self._round_async_jit = jax.jit(self._round_async_core)
        self._scan_async_jit = jax.jit(self._scan_rounds_async,
                                       donate_argnums=(0, 1, 2))
        self._round_pipe_jit = jax.jit(self._round_pipe_core)
        self._scan_pipe_jit = jax.jit(self._scan_rounds_pipe,
                                      donate_argnums=(0, 1, 2))
        self._eval_jit = jax.jit(self._eval_core,
                                 static_argnames=("personalize_steps",))
        # host-store pipeline (fed/store.py §11.3): the select jit draws
        # round r+1's cohort one step ahead of the round jit (the
        # staleness-pipeline carry idiom), the prefetch worker stages its
        # slice while round r executes
        if self._host_mode:
            self._select_jit = jax.jit(self._select_core)
            self._round_host_jit = jax.jit(self._round_host_core)
            self._round_host_async_jit = jax.jit(self._round_host_async_core)
            self._prefetcher = None
            # in-flight ring, oldest first: list of (pending, idx_np) with
            # at most `staleness` entries (empty list == fresh pipeline)
            self._host_async = None

        # state-field names double as attributes (__getattr__/__setattr__
        # redirection): a field shadowing a real instance attribute would
        # silently split reads from writes — refuse it loudly instead
        clash = sorted(({f.name for f in self._fields} |
                        set(self._host_state_names)) & set(self.__dict__))
        if clash:
            raise ValueError(
                f"state_spec() field name(s) {clash} collide with "
                f"Simulator attributes; rename the StateField(s)")

    # ------------------------------------------------------------------
    # method state plumbing: one spec-shaped dict; the field names double
    # as read-only simulator attributes (sim.alphas, sim.personal, sim.ef)
    # ------------------------------------------------------------------
    def _get_state(self):
        """Full state dict: under the host store the per-client tables are
        merged in as their (numpy) host views, so checkpointing and the
        attribute redirection see one spec-shaped dict either way."""
        state = dict(self._state)
        for n in self._host_state_names:
            state[n] = self._host.get(n)
        return state

    def _set_state(self, state):
        if not self._host_state_names:
            self._state = dict(state)
            return
        dev = {}
        for k, v in state.items():
            if k in self._host_state_names:
                # in-place into the host tables (memmap spill preserved)
                self._host.set(k, jax.tree.map(np.asarray, v))
            else:
                dev[k] = jax.tree.map(jnp.asarray, v)
        self._state = dev

    def __getattr__(self, name):
        state = self.__dict__.get("_state")
        if state is not None and name in state:
            return state[name]
        host = self.__dict__.get("_host")
        if host is not None and name in self.__dict__.get(
                "_host_state_names", ()):
            return host.get(name)
        raise AttributeError(
            f"{type(self).__name__!s} has no attribute {name!r}")

    def __setattr__(self, name, value):
        # writes to spec-field names update the live state dict, so
        # `sim.alphas = x` keeps its pre-PR4 meaning instead of leaving a
        # stale shadow the run would silently ignore
        state = self.__dict__.get("_state")
        if state is not None and name in state:
            self._state = dict(state, **{name: value})
            return
        if name in self.__dict__.get("_host_state_names", ()):
            self._host.set(name, jax.tree.map(np.asarray, value))
            return
        super().__setattr__(name, value)

    # ------------------------------------------------------------------
    # one round, fully on device
    # ------------------------------------------------------------------
    def _draw_cohort_sel(self, state, key):
        """Device-side cohort + sample selection (indices only, no gather).

        The cohort is drawn by the configured `CohortSampler` (DESIGN.md
        §8) — without replacement, inside jit; microbatch samples are drawn
        uniformly (with replacement) from each client's shard via a padded
        index-table lookup — no host round-trip.  Returns (idx (cohort,),
        sel (cohort, K, b) dataset rows, sizes (cohort,) true sample
        counts, weights (cohort,) effective counts for the Eq. 10-12
        aggregation).  `weights` is `sizes` scaled by the sampler's
        inverse-probability factors (§8.2 unbiasedness); for samplers with
        no reweighting (uniform) it is `sizes` itself, bit-identical.
        """
        fl, data = self.fl, self.data
        kc, kp = jax.random.split(key)
        idx, invp = self.smp.draw(self._smp_opts, state.get("sampler"), kc,
                                  fl.n_clients, fl.cohort)
        sizes = data["client_sizes"][idx].astype(jnp.float32)
        weights = sizes if invp is None else sizes * invp
        pool = data["client_idx"][idx]                   # (cohort, n_max)
        need = fl.k_micro * fl.micro_batch
        u = jax.random.uniform(kp, (fl.cohort, need))
        pos = jnp.minimum((u * sizes[:, None]).astype(jnp.int32),
                          sizes[:, None].astype(jnp.int32) - 1)
        sel = jnp.take_along_axis(pool, jnp.maximum(pos, 0), axis=1)
        sel = jnp.maximum(sel, 0).reshape(fl.cohort, fl.k_micro,
                                          fl.micro_batch)
        return idx, sel, sizes, weights, invp

    def _gather_batch(self, data, sel):
        """sel (cohort', K, b) dataset rows -> batch pytree (cohort', K, b, ...)."""
        return {k: jnp.take(v, sel, axis=0) for k, v in data.items()
                if k not in ("client_idx", "client_sizes")}

    def _place_pspec_fields(self, m):
        """2-d mesh placement for `StateField.pspec == "params"` fields
        (DESIGN.md §13.1): leaves take the parameters' `param_spec` model
        sharding, and per-client tables additionally shard their leading
        (M, ...) client dim over the cohort axis when M divides it — a
        SCAFFOLD c_u table or FedNCV+ h table never replicates a full
        model copy per client slot."""
        from repro.sharding import param_spec
        for f in self._fields:
            if f.pspec != "params" or f.name not in self._state:
                continue

            def one(kp, leaf, per_client=f.per_client):
                path = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                                for p in kp)
                shape = leaf.shape[1:] if per_client else leaf.shape
                spec = param_spec(path, shape, self.mesh)
                if per_client:
                    lead = self.caxis \
                        if leaf.shape[0] % self.n_devices == 0 else None
                    spec = P(lead, *spec)
                return NamedSharding(self.mesh, spec)

            sh = jax.tree_util.tree_map_with_path(one, self._state[f.name])
            self._state[f.name] = jax.device_put(self._state[f.name], sh)

    def _cohort_cstates(self, state, idx):
        cs = api.gather_cohort_states(self._fields, state, idx)
        if self.codec.stateful:
            cs["ef"] = jax.tree.map(lambda t: t[idx], state["ef"])
        return cs

    @staticmethod
    def _slot_keys(key, n):
        """Per-cohort-slot PRNG keys by fold_in of the slot index: slot u's
        key is independent of how many *padding* slots follow it, so mesh
        and single-device runs see identical client/codec randomness."""
        return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n))

    def _client_fn(self):
        client_fn = self.method.client_update
        # fault corruption wraps innermost: the adversary controls the raw
        # upload (and its training labels), and the honest protocol —
        # sampler stats, codec compression — then applies to the corrupted
        # gradient exactly as it would on a real fleet (fed.faults §9)
        if self._fm_corrupts or self._fm_flips:
            client_fn = faults.wrap_client(client_fn, self._n_classes)
        # partial averaging (DESIGN.md §13.4): the federated_slice mask
        # zeroes non-federated leaves BEFORE the sampler stats and the
        # codec see the upload — the wire carries only the federated slice
        # (a no-op for methods whose clients already upload masked grads,
        # e.g. fedper/fedrep body masking — bit-identical under identity)
        if self._fed_mask is not None:
            client_fn = api.with_federated_slice(client_fn, self._fed_mask)
        # sampler statistics (upload norm / sketch) are computed on the raw
        # f32 upload, so the stats wrapper goes on before the codec
        if self.smp.needs_norms or self._sketch_proj is not None:
            client_fn = sampling.with_stats(client_fn,
                                            norm=self.smp.needs_norms,
                                            proj=self._sketch_proj)
        # telemetry upload (track_variance): ||raw upload||^2 rides aux
        # like the sampler stats — computed pre-codec, counted in bytes_up
        if self._track_var:
            client_fn = track.with_grad_stats(client_fn)
        # non-identity codecs compress the upload at the end of the client fn
        # and the servers aggregate straight off the wire (DESIGN.md §5)
        if self.codec.name != "identity":
            client_fn = api.with_codec(client_fn, self.codec)
        return client_fn

    def _fault_plan(self, state, key, idx, weights, invp):
        """Draw the round's fault plan and fold honest-dropout inclusion
        factors into the Eq. 10-12 weights (DESIGN.md §9).

        Returns (plan, evolved fault state, weights, invp, live) — all
        None/unchanged when fault="none" (the bit-identical path).  `live`
        is the all-dropped guard: when every sampled client drops, the
        weights are replaced by ones (so `ncv_coefficients` stays finite)
        and the server section zeroes the aggregate with this flag — a
        no-op round instead of NaN params.
        """
        if not self._fault_on:
            return None, None, weights, invp, None
        fstate = state.get("faults")
        kf = jax.random.fold_in(key, faults.FAULT_SALT)
        if self.fm.step is not None:
            fstate = self.fm.step(self._fm_opts, fstate,
                                  jax.random.fold_in(kf, 1))
        plan = self.fm.plan(self._fm_opts, fstate, jax.random.fold_in(kf, 2),
                            idx, self.fl.n_clients)
        live = None
        if self._fm_drops:
            weights = weights * plan["invp"]
            invp = plan["invp"] if invp is None else invp * plan["invp"]
            live = (jnp.sum(weights) > 0).astype(jnp.float32)
            weights = jnp.where(live > 0, weights, jnp.ones_like(weights))
        return plan, fstate, weights, invp, live

    def _fault_pending(self, pending, plan, fstate, live):
        """Attach the fault plan's server-side pieces to the pending dict.
        Key presence is a static per-configuration fact, so the async
        pending carry stays type-stable across rounds."""
        if self._fm_drops:
            pending["alive"] = plan["alive"]
            pending["live"] = live
        # corrupted-cohort fraction for the telemetry stream — only built
        # when a sink is wired (tracker="none" keeps the graph unchanged)
        if self._track_on and (self._fm_corrupts or self._fm_flips):
            bad = (plan["gscale"] != 1.0) | (plan["flip"] > 0)
            pending["corrupt_frac"] = jnp.mean(bad.astype(jnp.float32))
        if self._fault_on and self.fm.stateful:
            pending["fault_state"] = fstate
        return pending

    def _client_section(self, params, state, key):
        """Cohort draw + client passes (+ wire encode [+ sharded reduce]).

        Returns the round's "pending" dict: idx/sizes/cstates/aux with
        exact (cohort,) leading dims, plus either the stacked uploads
        (`grads`) or — in mesh mode, for aggregate-then-correct methods —
        the already-reduced flat aggregate (`agg_vec`, `agg_norm`) computed
        by the sharded fused path.  `_server_section` consumes this dict;
        the async pipeline carries it across rounds.
        """
        if self.mesh is None:
            return self._client_section_local(params, state, key)
        return self._client_section_sharded(params, state, key)

    def _client_section_local(self, params, state, key):
        fl = self.fl
        client_fn = self._client_fn()
        ctx = api.MethodCtx(self.task, fl.mc)
        kd, kk = jax.random.split(key)
        idx, sel, sizes, weights, invp = self._draw_cohort_sel(state, kd)
        plan, fstate, weights, invp, live = self._fault_plan(
            state, key, idx, weights, invp)
        batches = self._gather_batch(self.data, sel)
        cstates = self._cohort_cstates(state, idx)
        if self._fm_corrupts or self._fm_flips:
            cstates[faults.FAULT_KEY] = dict(gscale=plan["gscale"],
                                             flip=plan["flip"])
        keys = self._slot_keys(kk, fl.cohort)
        with track.scope(track.CLIENT_PASS):
            outs = jax.vmap(
                lambda cs, b, k: client_fn(ctx, params, cs, b, k)
            )(cstates, batches, keys)
        pending = dict(idx=idx, sizes=sizes, weights=weights,
                       grads=outs.grad, cstates=outs.cstate, aux=outs.aux)
        # reweighting samplers carry the raw 1/(M q_u) factors for the
        # dense-grad server path; the key's presence is a static,
        # per-configuration fact, so scan/async carries stay type-stable
        if invp is not None:
            pending["invp"] = invp
        return self._fault_pending(pending, plan, fstate, live)

    def _client_section_sharded(self, params, state, key):
        """Mesh mode: the cohort work runs in a shard_map over the cohort
        dim — each device gathers, trains and encodes only its local slice
        of the (padded) cohort, and the Eq. 10-12 reduction is the sharded
        fused path (local kernel pass + one psum, fed/sharded.py)."""
        fl, codec = self.fl, self.codec
        client_fn, mc = self._client_fn(), self.fl.mc
        ctx = api.MethodCtx(self.task, mc)
        axis, dcount = self.caxis, self.n_devices
        use_wire = codec.name != "identity"
        # dense-grad methods (FedNCV+'s per-client h_u) need the per-client
        # uploads at the server, not just the aggregate; aggregators
        # without a sharded_reduce hook (the order-statistic pair — a
        # robust reduction is not a psum of partials) take the same dense
        # fallback: the stack leaves the shard_map and the reduction runs
        # on the replicated copy in the server section (DESIGN.md §9).
        # On a 2-d mesh only "mean" stays in-region: norm_clip's hook
        # all-gathers the per-client norms and slices by axis_index, both
        # rejected by the partitioner in a partially-manual region.
        agg_path = not self.method.needs_dense_grads and \
            self.agg.sharded_reduce is not None and \
            (not self.maxes or fl.aggregator == "mean")
        # 2-d mesh + identity wire + mean reduction: aggregate leaf-by-leaf
        # (sharded.sharded_aggregate_tree) so the model-sharded gradient
        # leaves are weighted-summed and psum'd WITHOUT the ravel into one
        # (N,) buffer — raveling a model-sharded leaf would force GSPMD to
        # all-gather it, defeating the model axis (DESIGN.md §13.1).  Wire
        # codecs keep the flat path: their payloads are already r(p+q)- or
        # byte-sized, and the factor/int8 stacks gather cheaply.
        tree_path = agg_path and bool(self.maxes) and not use_wire \
            and fl.aggregator == "mean"
        beta = self.method.beta(mc)

        kd, kk = jax.random.split(key)
        idx, sel, sizes, weights, invp = self._draw_cohort_sel(state, kd)
        plan, fstate, weights, invp, live = self._fault_plan(
            state, key, idx, weights, invp)
        cp = sharded.padded_cohort_size(fl.cohort, dcount)
        pad = cp - fl.cohort
        # zero-weight padding slots (n_u = 0 -> w_u = 0 exactly, §6): the
        # padded rows alias client 0's pool but contribute nothing
        idx_p = jnp.pad(idx, (0, pad))
        sel_p = sharded.pad_cohort(sel, dcount)
        # the sampler's effective counts (not the raw sizes) drive the
        # sharded Eq. 10-12 coefficients — zero-padded like everything else
        weights_p = jnp.pad(weights, (0, pad))
        cstates_p = self._cohort_cstates(state, idx_p)
        if self._fm_corrupts or self._fm_flips:
            # padded slots get gscale=1/flip=0: their weight is already 0
            cstates_p[faults.FAULT_KEY] = dict(
                gscale=jnp.pad(plan["gscale"], (0, pad), constant_values=1.0),
                flip=jnp.pad(plan["flip"], (0, pad)))
        keys_p = self._slot_keys(kk, cp)

        def body(params, data, cstates_l, sel_l, weights_l, keys_l):
            batch = self._gather_batch(data, sel_l)
            with track.scope(track.CLIENT_PASS):
                outs = jax.vmap(
                    lambda cs, b, k: client_fn(ctx, params, cs, b, k)
                )(cstates_l, batch, keys_l)
            ret = dict(cstates=outs.cstate, aux=outs.aux)
            if tree_path:
                with track.scope(track.AGGREGATE):
                    ret["agg_tree"], ret["agg_norm"] = \
                        sharded.sharded_aggregate_tree(
                            outs.grad, weights_l, beta, axis_name=axis)
            elif agg_path:
                stack_l = outs.grad
                if not use_wire:
                    stack_l, _ = ravel_stack(stack_l)
                with track.scope(track.AGGREGATE):
                    ret["agg_vec"], ret["agg_norm"] = \
                        self.agg.sharded_reduce(
                            self._agg_opts, stack_l, weights_l, beta, axis,
                            codec if use_wire else None, self._use_pallas)
            else:
                ret["grads"] = outs.grad
            return ret

        cspec, rspec = P(axis), P()
        out_specs = dict(cstates=cspec, aux=cspec)
        if tree_path:
            out_specs["agg_tree"] = rspec
            out_specs["agg_norm"] = rspec
        elif agg_path:
            out_specs["agg_vec"] = rspec
            out_specs["agg_norm"] = rspec
        else:
            out_specs["grads"] = cspec
        fn = sharded.shard_map(
            body, self.mesh,
            in_specs=(rspec, rspec, cspec, cspec, cspec, cspec),
            out_specs=out_specs, auto=self._auto)
        out = fn(params, self.data, cstates_p, sel_p, weights_p, keys_p)

        # strip the padding slots so the pending dict always carries exact
        # (cohort,) leading dims (scatter at padded idx would corrupt
        # client 0's state)
        unpad = (lambda t: jax.tree.map(lambda x: x[:fl.cohort], t)) \
            if pad else (lambda t: t)
        pending = dict(idx=idx, sizes=sizes, weights=weights,
                       cstates=unpad(out["cstates"]), aux=unpad(out["aux"]))
        if invp is not None:
            pending["invp"] = invp
        if tree_path:
            pending["agg_tree"] = out["agg_tree"]
            pending["agg_norm"] = out["agg_norm"]
        elif agg_path:
            pending["agg_vec"] = out["agg_vec"]
            pending["agg_norm"] = out["agg_norm"]
        else:
            pending["grads"] = unpad(out["grads"])
        return self._fault_pending(pending, plan, fstate, live)

    def _server_section(self, params, state, pending, r):
        """Generic server half of a round, driven entirely by the method's
        state_spec() and server_update: codec EF scatter, the fused
        Eq. 10-12 aggregation with the method's beta, cohort state
        write-back, then the method's server update.  Pure; jit/scan-able.
        No per-method branches — a registered method never touches this."""
        fl, codec, method = self.fl, self.codec, self.method
        mc = fl.mc
        use_wire = codec.name != "identity"
        idx, sizes = pending["idx"], pending["sizes"]
        weights = pending["weights"]
        # fault-injection plan pieces (absent under fault="none"): the 0/1
        # survival mask, the all-dropped guard flag, and the evolved fault
        # state (fed.faults, DESIGN.md §9)
        alive = pending.get("alive")
        live = pending.get("live")
        grads, aux = pending.get("grads"), pending["aux"]
        new_cstates = pending["cstates"]

        new_state = dict(state)
        if "fault_state" in pending:
            new_state["faults"] = pending["fault_state"]
        if codec.stateful:
            # codec state is a pytree in general (topk: one (M, N) residual;
            # lowrank: dict of residual + warm bases) — gather/scatter and
            # the sharding constraint map over its leaves uniformly
            ef_rows = new_cstates["ef"]
            if alive is not None:
                # a dropped client's EF residual never made it back either
                ef_rows = faults.where_rows(
                    alive, ef_rows,
                    jax.tree.map(lambda t: t[idx], state["ef"]))
            new_state["ef"] = jax.tree.map(
                lambda t, rows: t.at[idx].set(rows), state["ef"], ef_rows)
            if self.mesh is not None and not self._host_mode and \
                    jax.tree.leaves(state["ef"])[0].shape[0] \
                    % self.n_devices == 0:
                csh = NamedSharding(self.mesh, P(self.caxis))
                new_state["ef"] = jax.tree.map(
                    lambda t: jax.lax.with_sharding_constraint(t, csh),
                    new_state["ef"])

        # sampler-state refresh from the cohort's uploaded statistics
        # (importance EMA norms, similarity sketches/ages) — under the
        # async pipeline this lands one round late, like alpha adaptation
        # `idx` is where the round's rows live in the per-client tables the
        # jit sees: global client ids under the device store, window
        # positions (arange(cohort)) under the host store, where the
        # pending dict carries the global ids separately as "gidx" for the
        # consumers that genuinely need them (DESIGN.md §11.2)
        if self.smp.update is not None:
            new_state["sampler"] = self.smp.update(
                self._smp_opts, new_state["sampler"],
                pending.get("gidx", idx), sizes, aux)

        # dense per-client uploads, decoded once, only if the method asks
        dense = None
        if method.needs_dense_grads:
            dense = comm.decode_stack(codec, grads, self._grad_spec) \
                if use_wire else grads
        ctx = api.RoundCtx(task=self.task, mc=mc, fl=fl, r=r, idx=idx,
                           sizes=sizes, aux=aux, grads=dense,
                           weights=weights, invp=pending.get("invp"),
                           alive=alive)

        # per-client state write-back at the cohort indices (spec-driven);
        # the method may transform the cohort slice first (pFedSim's
        # similarity mixing of the uploaded heads); dropped clients keep
        # their previous rows (they never reported — fed.faults §9)
        if method.cohort_state_update is not None:
            new_cstates = method.cohort_state_update(ctx, new_cstates)
        new_state = api.scatter_cohort_states(self._fields, new_state, idx,
                                              new_cstates, alive=alive)

        # the configured aggregation strategy (fed.aggregators §9) over the
        # Eq. 10-12 effective counts — sampler- and dropout-adjusted, §8.2
        # keeps the estimator unbiased under non-uniform selection/honest
        # dropout; the sharded path already reduced inside shard_map with
        # the same weights ("mean" is the historical fused path verbatim)
        if method.needs_dense_grads:
            agg = None
        elif "agg_tree" in pending:       # 2-d tree path: already a pytree
            agg = (pending["agg_tree"], pending["agg_norm"])
        elif "agg_vec" in pending:        # sharded path already reduced
            agg = (unravel(pending["agg_vec"], self._grad_spec),
                   pending["agg_norm"])
        else:
            with track.scope(track.AGGREGATE):
                agg = aggregators.aggregate_stack(
                    self.agg, self._agg_opts, grads, weights,
                    method.beta(mc), codec if use_wire else None,
                    self._grad_spec, use_pallas=self._use_pallas)
        if agg is not None and self._fed_mask is not None and use_wire:
            # hard mask after a lossy codec: uploads were masked pre-codec,
            # but reconstruction (lowrank factors, stochastic rounding)
            # may leak into masked leaves — partial averaging promises
            # exactly-zero updates there (DESIGN.md §13.4).  Identity wire
            # skips this: the aggregate is provably already masked, and
            # the fused kernel's norm stays bit-identical.
            agg = api.apply_federated_mask(agg[0], self._fed_mask)
        if agg is not None and live is not None:
            # all-dropped guard: nobody reported -> zero update, not NaN
            agg = (jax.tree.map(lambda g: g * live, agg[0]), agg[1] * live)

        with track.scope(track.SERVER_UPDATE):
            params, new_state, diag = method.server_update(ctx, params, agg,
                                                           new_state)
        diag = {k: v for k, v in diag.items()
                if getattr(v, "ndim", None) == 0}
        # total uploaded bytes this round: gradient wire + auxiliary uploads
        # (FedNCV's 4 scalars, SCAFFOLD's delta_c, pFedSim's head vectors —
        # aux leaves already carry the cohort dim, so tree_bytes covers all)
        if alive is None:
            diag["bytes_up"] = jnp.float32(
                fl.cohort * codec.bytes_per_client() + tree_bytes(aux))
        else:
            # dropped clients uploaded nothing — report honest wire bytes
            diag["bytes_up"] = jnp.sum(alive) \
                * jnp.float32(codec.bytes_per_client()) \
                + jnp.float32(tree_bytes(aux))
            diag["live"] = jnp.sum(alive)
        # tracker-only diagnostics: the fault layer's corrupted fraction
        # (already computed inside the client section when a tracker is on)
        # and the cohort gradient-variance proxy Var[g] ~ E_w||g_u||^2 -
        # ||E_w g_u||^2, the estimator bench_sampling.py plots, promoted
        # into the round stream behind fl.track_variance (one extra
        # reduction; the per-client ||g_u||^2 scalar rides aux pre-codec)
        if "corrupt_frac" in pending:
            diag["corrupt_frac"] = pending["corrupt_frac"]
        if self._track_var and track.GNORM_KEY in aux:
            gns = aux[track.GNORM_KEY]
            p_w = weights / jnp.maximum(jnp.sum(weights), 1e-30)
            e2 = jnp.sum(p_w * gns)
            if agg is not None:
                # agg_norm is ||sum_u w_u g_u||^2 over normalized weights
                diag["gvar_proxy"] = jnp.maximum(e2 - agg[1], 0.0)
            elif dense is not None:
                gbar = jax.tree.map(
                    lambda g: jnp.tensordot(p_w, g, axes=1), dense)
                diag["gvar_proxy"] = jnp.maximum(
                    e2 - tree_norm_sq(gbar), 0.0)
        return params, new_state, diag

    def _round_core(self, params, state, key, r):
        """params, method state, PRNG key, 1-based round number -> updated
        (params, state, scalar diagnostics).  Pure; jit/scan-able (the
        tracker emission is an *ordered* io_callback, so it is legal and
        stays in-order inside `lax.scan`; with tracker="none" no callback
        op is staged and the HLO is bit-identical to an untracked build).

        The emitted token is tethered into the carry: without the
        `track.tether` below, the CPU runtime schedules every callback
        after the whole scan's compute and the rows burst out at dispatch
        end instead of streaming one-per-round (see track.emitter)."""
        pending = self._client_section(params, state, key)
        params, state, diag = self._server_section(params, state, pending, r)
        if self._emit is not None:
            params = track.tether(params, self._emit(r, diag))
        return params, state, diag

    def _round_async_core(self, params, state, pending, valid, key, r):
        """One async pipeline step: issue round r's client passes against
        the current (stale) params while round r-1's server update and
        state refresh complete.  The two halves have no data dependency, so
        XLA overlaps them; `valid` gates the warmup bubble.

        Bubble invariant: the pipeline's first step (round 1 of a fresh
        run, `valid == 0`) has no completed cohort to apply, so the server
        half runs on all-zero pending buffers.  Its outputs are garbage and
        must never escape: params/state are `_tree_where`-gated back to
        their inputs, and **every** diag key is `jnp.where`-zeroed — not
        dropped — so the diagnostics pytree keeps a static structure across
        scan iterations and the tracker streams round 1 as an all-zero row
        with the correct round index (round numbering stays aligned with
        the sync path; see tests/test_track.py's bubble regression)."""
        new_pending = self._client_section(params, state, key)
        params2, state2, diag = self._server_section(params, state, pending,
                                                     r)
        params = _tree_where(valid, params2, params)
        state = _tree_where(valid, state2, state)
        diag = {k: jnp.where(valid > 0, v, jnp.zeros_like(v))
                for k, v in diag.items()}
        if self._emit is not None:
            params = track.tether(params, self._emit(r, diag))
        return params, state, new_pending, jnp.float32(1.0), diag

    def _round_pipe_core(self, params, state, ring, rvalid, pos, key, r):
        """One depth-K pipeline step (`staleness = K >= 2`, DESIGN.md §12).

        The ring holds the K in-flight cohorts, stacked on a leading K
        axis; `pos` points at the oldest slot.  Each step (a) issues round
        r's client passes against the current params, (b) applies the
        oldest pending cohort — issued K rounds ago — through the server
        half, (c) overwrites the oldest slot with the new cohort and
        advances `pos`.  `rvalid[pos]` gates the K warmup bubbles with the
        exact `_round_async_core` invariant: params/state `_tree_where`-
        gated, every diag key zeroed (never dropped), static pytree
        structure across scan steps."""
        oldest = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, pos, 0,
                                                   keepdims=False), ring)
        ovalid = jax.lax.dynamic_index_in_dim(rvalid, pos, 0,
                                              keepdims=False)
        new_pending = self._client_section(params, state, key)
        params2, state2, diag = self._server_section(params, state, oldest,
                                                     r)
        params = _tree_where(ovalid, params2, params)
        state = _tree_where(ovalid, state2, state)
        diag = {k: jnp.where(ovalid > 0, v, jnp.zeros_like(v))
                for k, v in diag.items()}
        ring = jax.tree.map(
            lambda buf, x: jax.lax.dynamic_update_index_in_dim(buf, x, pos,
                                                               0),
            ring, new_pending)
        rvalid = jax.lax.dynamic_update_index_in_dim(
            rvalid, jnp.float32(1.0), pos, 0)
        pos = jnp.mod(pos + 1, self.fl.staleness)
        if self._emit is not None:
            params = track.tether(params, self._emit(r, diag))
        return params, state, ring, rvalid, pos, diag

    # ------------------------------------------------------------------
    # host-store round path (fed/store.py, DESIGN.md §11): the (M, ...)
    # per-client tables and data tensors live host-side; each round the
    # prefetch worker stages only the cohort slice on device, the round
    # jit computes on cohort-sized windows, and the updated rows scatter
    # back host-side off the critical path.
    # ------------------------------------------------------------------
    def _select_core(self, state, key):
        """Cohort selection for the host store, drawn one step ahead of
        the round jit (the staleness-pipeline carry idiom): mirrors
        `_draw_cohort_sel`'s exact key splits and integer ops but returns
        in-pool *positions* instead of gathered dataset rows — the row
        gather happens host-side against the resident index table, so both
        stores draw bit-identical cohorts and microbatches."""
        fl = self.fl
        kd, _ = jax.random.split(key)
        kc, kp = jax.random.split(kd)
        idx, invp = self.smp.draw(self._smp_opts, state.get("sampler"), kc,
                                  fl.n_clients, fl.cohort)
        sizes = self._sizes_dev[idx].astype(jnp.float32)
        weights = sizes if invp is None else sizes * invp
        need = fl.k_micro * fl.micro_batch
        u = jax.random.uniform(kp, (fl.cohort, need))
        pos = jnp.minimum((u * sizes[:, None]).astype(jnp.int32),
                          sizes[:, None].astype(jnp.int32) - 1)
        sel = dict(idx=idx, pos=jnp.maximum(pos, 0), sizes=sizes,
                   weights=weights)
        if invp is not None:
            sel["invp"] = invp
        return sel

    def _host_client_section(self, params, state, key, sel, batch):
        """Client half of a host-store round.  The per-client state arrives
        as cohort-sized *windows* merged into `state`, so rows are
        addressed by window position — `ctx.idx`/`pending["idx"]` is
        arange(cohort) and every registered method's gather/scatter works
        unmodified — while the global client ids ride `pending["gidx"]`
        for the sampler update and the fault plan.  In mesh mode the
        pre-gathered batch and windows arrive padded and sharded over the
        cohort axis; padding follows the device path's rules bitwise
        (zero-index slots, zero weights)."""
        fl = self.fl
        gidx, sizes, weights = sel["idx"], sel["sizes"], sel["weights"]
        invp = sel.get("invp")
        client_fn = self._client_fn()
        ctx = api.MethodCtx(self.task, fl.mc)
        _, kk = jax.random.split(key)
        lidx = jnp.arange(fl.cohort, dtype=gidx.dtype)
        plan, fstate, weights, invp, live = self._fault_plan(
            state, key, gidx, weights, invp)
        if self.mesh is None:
            cstates = self._cohort_cstates(state, lidx)
            if self._fm_corrupts or self._fm_flips:
                cstates[faults.FAULT_KEY] = dict(gscale=plan["gscale"],
                                                 flip=plan["flip"])
            keys = self._slot_keys(kk, fl.cohort)
            with track.scope(track.CLIENT_PASS):
                outs = jax.vmap(
                    lambda cs, b, k: client_fn(ctx, params, cs, b, k)
                )(cstates, batch, keys)
            pending = dict(idx=lidx, gidx=gidx, sizes=sizes,
                           weights=weights, grads=outs.grad,
                           cstates=outs.cstate, aux=outs.aux)
            if invp is not None:
                pending["invp"] = invp
            return self._fault_pending(pending, plan, fstate, live)

        # mesh: same shard_map body as _client_section_sharded minus the
        # in-body data gather (the batch was staged host-side, sharded)
        codec = self.codec
        axis = self.caxis
        use_wire = codec.name != "identity"
        agg_path = not self.method.needs_dense_grads and \
            self.agg.sharded_reduce is not None and \
            (not self.maxes or fl.aggregator == "mean")
        beta = self.method.beta(fl.mc)
        cp = sharded.padded_cohort_size(fl.cohort, self.n_devices)
        pad = cp - fl.cohort
        weights_p = jnp.pad(weights, (0, pad))
        cstates_p = self._cohort_cstates(state,
                                         jnp.arange(cp, dtype=gidx.dtype))
        if self._fm_corrupts or self._fm_flips:
            cstates_p[faults.FAULT_KEY] = dict(
                gscale=jnp.pad(plan["gscale"], (0, pad),
                               constant_values=1.0),
                flip=jnp.pad(plan["flip"], (0, pad)))
        keys_p = self._slot_keys(kk, cp)

        def body(params, cstates_l, batch_l, weights_l, keys_l):
            with track.scope(track.CLIENT_PASS):
                outs = jax.vmap(
                    lambda cs, b, k: client_fn(ctx, params, cs, b, k)
                )(cstates_l, batch_l, keys_l)
            ret = dict(cstates=outs.cstate, aux=outs.aux)
            if agg_path:
                stack_l = outs.grad
                if not use_wire:
                    stack_l, _ = ravel_stack(stack_l)
                with track.scope(track.AGGREGATE):
                    ret["agg_vec"], ret["agg_norm"] = \
                        self.agg.sharded_reduce(
                            self._agg_opts, stack_l, weights_l, beta, axis,
                            codec if use_wire else None, self._use_pallas)
            else:
                ret["grads"] = outs.grad
            return ret

        cspec, rspec = P(axis), P()
        out_specs = dict(cstates=cspec, aux=cspec)
        if agg_path:
            out_specs["agg_vec"] = rspec
            out_specs["agg_norm"] = rspec
        else:
            out_specs["grads"] = cspec
        fn = sharded.shard_map(
            body, self.mesh,
            in_specs=(rspec, cspec, cspec, cspec, cspec),
            out_specs=out_specs, auto=self._auto)
        out = fn(params, cstates_p, batch, weights_p, keys_p)
        unpad = (lambda t: jax.tree.map(lambda x: x[:fl.cohort], t)) \
            if pad else (lambda t: t)
        pending = dict(idx=lidx, gidx=gidx, sizes=sizes, weights=weights,
                       cstates=unpad(out["cstates"]), aux=unpad(out["aux"]))
        if invp is not None:
            pending["invp"] = invp
        if agg_path:
            pending["agg_vec"] = out["agg_vec"]
            pending["agg_norm"] = out["agg_norm"]
        else:
            pending["grads"] = unpad(out["grads"])
        return self._fault_pending(pending, plan, fstate, live)

    def _round_host_core(self, params, dstate, windows, batch, sel, key, r):
        """Sync host-store round: windows in, windows out.  The returned
        `wout` windows (alive-gating already applied by the generic server
        section) scatter back into the host tables on the prefetch worker;
        under a dropping fault model `alive` rides along so the host-side
        scatter skips dropped clients entirely."""
        state = {**dstate, **windows}
        pending = self._host_client_section(params, state, key, sel, batch)
        params, state, diag = self._server_section(params, state, pending, r)
        wout = {n: state.pop(n) for n in self._host_state_names}
        if self._emit is not None:
            params = track.tether(params, self._emit(r, diag))
        out = dict(params=params, dstate=state, wout=wout, diag=diag)
        if "alive" in pending:
            out["alive"] = pending["alive"]
        return out

    def _round_host_async_core(self, params, dstate, cwin, batch, sel,
                               swin, pending, valid, key, r):
        """One async (staleness=1) host-store step: round r's client
        passes run on its own staged windows (`cwin`) while round r-1's
        server half completes on the *pending* cohort's windows (`swin`,
        re-gathered after the r-2 scatter so their rows match what the
        device store's table would hold).  Same bubble gating as
        `_round_async_core`; `wout` is applied host-side only when the
        step was valid."""
        new_pending = self._host_client_section(
            params, {**dstate, **cwin}, key, sel, batch)
        params2, state2, diag = self._server_section(
            params, {**dstate, **swin}, pending, r)
        wout = {n: state2.pop(n) for n in self._host_state_names}
        params = _tree_where(valid, params2, params)
        dstate = _tree_where(valid, state2, dstate)
        diag = {k: jnp.where(valid > 0, v, jnp.zeros_like(v))
                for k, v in diag.items()}
        if self._emit is not None:
            params = track.tether(params, self._emit(r, diag))
        out = dict(params=params, dstate=dstate, pending=new_pending,
                   wout=wout, diag=diag)
        if "alive" in pending:
            out["alive"] = pending["alive"]
        return out

    def _host_gather(self, idx_np, pos_np, pad_to=None):
        """Host-side staging of one round's cohort slice: microbatch rows
        from the resident data tables plus the per-client state windows at
        the cohort indices.  `pad_to` (mesh) pads with index 0 — the same
        slots the device path's `pad_cohort` zero-padding gathers."""
        fl = self.fl
        sel = np.take_along_axis(self._pool_np[idx_np], pos_np, axis=1)
        sel = np.maximum(sel, 0).reshape(idx_np.shape[0], fl.k_micro,
                                         fl.micro_batch)
        widx = idx_np
        if pad_to is not None and pad_to > sel.shape[0]:
            pad = pad_to - sel.shape[0]
            sel = np.concatenate(
                [sel, np.zeros((pad,) + sel.shape[1:], sel.dtype)])
            widx = np.concatenate([widx, np.zeros(pad, widx.dtype)])
        batch = {n[len("data:"):]: self._host.get(n)[sel]
                 for n in self._host.names()
                 if n.startswith("data:") and n != "data:client_idx"}
        windows = self._host.gather(self._host_state_names, widx)
        return batch, windows

    def _host_stage(self, sel_dev, swin_idx=False):
        """One prefetch-worker staging step: pull the device-side
        selection, gather the slice, `device_put` it into the standby
        buffer (sharded over the cohort axis in mesh mode).  `swin_idx`
        (async): indices of the *pending* cohort whose windows the server
        half needs — None stages an all-zero bubble window."""
        idx_np = np.asarray(sel_dev["idx"])
        pos_np = np.asarray(sel_dev["pos"])
        cp = sharded.padded_cohort_size(self.fl.cohort, self.n_devices) \
            if self.mesh is not None else None
        batch, windows = self._host_gather(idx_np, pos_np, pad_to=cp)
        if self.mesh is not None:
            cshard = NamedSharding(self.mesh, P(self.caxis))
            batch = jax.device_put(batch, cshard)
            windows = jax.device_put(windows, cshard)
        else:
            batch = jax.device_put(batch)
            windows = jax.device_put(windows)
        buf = dict(idx=idx_np, batch=batch, windows=windows)
        if swin_idx is not False:
            if swin_idx is None:
                swin = self._host.gather(self._host_state_names,
                                         np.zeros(self.fl.cohort, np.int32))
                swin = jax.tree.map(np.zeros_like, swin)
            else:
                swin = self._host.gather(self._host_state_names, swin_idx)
            rep = NamedSharding(self.mesh, P()) if self.mesh is not None \
                else None
            buf["swin"] = jax.device_put(swin, rep) if rep is not None \
                else jax.device_put(swin)
        return buf

    def _host_scatter(self, idx_np, wout, alive):
        """Scatter one round's updated windows back into the host tables
        (runs on the prefetch worker; `np.asarray` blocks on the round's
        device outputs, releasing the GIL while XLA computes).  Dropped
        clients' rows are skipped outright."""
        c = self.fl.cohort
        rows = jax.tree.map(np.asarray, wout)
        alive_np = None if alive is None else np.asarray(alive)
        for n in self._host_state_names:
            self._host.scatter(
                n, idx_np, jax.tree.map(lambda x: x[:c], rows[n]), alive_np)

    def _sel_args(self, sel):
        return {k: v for k, v in sel.items() if k != "pos"}

    def _host_metrics(self):
        return dict(
            host_mem_peak=float(store_lib.host_mem_peak()),
            prefetch_overlap_frac=float(self._prefetcher.overlap_frac()))

    def _zero_pending_host(self):
        """Host-mode twin of `_zero_pending`: all-zero pending buffers for
        the async bubble, shaped by tracing the host client section."""
        fl = self.fl
        idxz = np.zeros(fl.cohort, np.int32)
        posz = np.zeros((fl.cohort, fl.k_micro * fl.micro_batch), np.int32)
        cp = sharded.padded_cohort_size(fl.cohort, self.n_devices) \
            if self.mesh is not None else None
        batch, windows = self._host_gather(idxz, posz, pad_to=cp)
        state = {**self._state,
                 **jax.tree.map(jnp.asarray, dict(windows))}
        shp = jax.eval_shape(self._select_core, self._state, self.base_key)
        sel = {k: jnp.zeros(v.shape, v.dtype) for k, v in shp.items()
               if k != "pos"}
        shapes = jax.eval_shape(self._host_client_section, self.params,
                                state, self.base_key, sel, batch)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def _run_host(self, n, keys):
        """Drive n host-store rounds through the double-buffered prefetch
        pipeline: select(r+1) is dispatched a step ahead (before round r
        for stateless samplers — fully overlapped; after it when the
        sampler state must settle first), the worker stages round r+1's
        slice while round r executes, and round r's windows scatter back
        on the worker, off the critical path.  `block_until_ready` only at
        the chunk boundary.  Same per-round keys and round numbering as
        the device drivers — the trajectories are bit-identical."""
        if self._emit is not None:
            self._emit.reset()
        if self._prefetcher is None:
            # prefetch depth follows the pipeline depth: K in-flight
            # cohorts want K+1 staged slices (the K pendings' server
            # windows plus the next client window) before backpressure
            self._prefetcher = store_lib.CohortPrefetcher(
                enabled=bool(self._store_opts.get("prefetch", True)),
                depth=max(2, self.fl.staleness + 1))
        pf = self._prefetcher
        rs = self.round_idx + np.arange(1, n + 1)
        # select ahead of the round only when the draw is key-only: a
        # stateful/updating sampler's round-r+1 draw consumes round r's
        # sampler table, so its select is dispatched after round r instead
        sel_ahead = not self.smp.stateful and self.smp.update is None
        sels = [None] * n
        waits = [None] * n
        diags = []

        def dispatch_select(i):
            sels[i] = self._select_jit(self._state, keys[i])

        if self.fl.staleness:
            return self._run_host_async(n, keys, rs, pf, sels, waits, diags,
                                        dispatch_select, sel_ahead)

        def make_job(i, scatter_prev):
            sel = sels[i]

            def job():
                if scatter_prev is not None:
                    self._host_scatter(*scatter_prev)
                return self._host_stage(sel)
            return job

        dispatch_select(0)
        waits[0] = pf.submit(make_job(0, None))
        prev = None
        for i in range(n):
            if sel_ahead and i + 1 < n:
                dispatch_select(i + 1)
            if self._emit is not None:
                self._emit.set_host_metrics(self._host_metrics())
            buf = waits[i]()
            out = self._round_host_jit(
                self.params, self._state, buf["windows"], buf["batch"],
                self._sel_args(sels[i]), keys[i], jnp.int32(int(rs[i])))
            self.params = out["params"]
            self._state = out["dstate"]
            prev = (buf["idx"], out["wout"], out.get("alive"))
            if i + 1 < n:
                if not sel_ahead:
                    dispatch_select(i + 1)
                waits[i + 1] = pf.submit(make_job(i + 1, prev))
            diags.append(out["diag"])
        # chunk boundary: settle the last scatter-back before handing the
        # tables to the caller (checkpointing/eval see consistent state)
        pf.submit(lambda: self._host_scatter(*prev))()
        self.round_idx += n
        jax.block_until_ready(self.params)
        if self._emit is not None:
            jax.effects_barrier()
        return {k: np.stack([np.asarray(d[k]) for d in diags])
                for k in diags[0]}

    def _run_host_async(self, n, keys, rs, pf, sels, waits, diags,
                        dispatch_select, sel_ahead):
        """staleness = K >= 1 on the host store: the in-flight pendings
        stay device carries across chunks exactly like the device async
        drivers, held in a ring list (oldest first, at most K entries)
        together with each pending cohort's host-side indices so the next
        step's worker job can re-gather its server windows after the
        previous scatter.  A step with a full ring pops + applies the
        oldest pending (issued K rounds ago); a mid-warmup step (ring
        shorter than K) runs the server half on an all-zero bubble, gated
        off by `valid` — the exact `_round_async_core` bubble invariant.
        For K=1 this issues the same jit calls in the same order as the
        historical double-buffered driver (bit-identical trajectories)."""
        k = self.fl.staleness
        ring = [] if self._host_async is None else self._host_async
        zero = None

        def make_job(i, scatter_prev, swin_idx):
            sel = sels[i]

            def job():
                if scatter_prev is not None:
                    self._host_scatter(*scatter_prev)
                return self._host_stage(sel, swin_idx=swin_idx)
            return job

        dispatch_select(0)
        # swin for step i is the cohort applied at step i == ring head
        # when the ring is full, else a zero bubble window (idx None)
        waits[0] = pf.submit(make_job(
            0, None, ring[0][1] if len(ring) == k else None))
        last_scatter = None
        for i in range(n):
            if sel_ahead and i + 1 < n:
                dispatch_select(i + 1)
            if self._emit is not None:
                self._emit.set_host_metrics(self._host_metrics())
            buf = waits[i]()
            if len(ring) == k:
                pending, pidx = ring.pop(0)
                valid = True
            else:
                if zero is None:
                    zero = self._zero_pending_host()
                pending, pidx, valid = zero, None, False
            out = self._round_host_async_jit(
                self.params, self._state, buf["windows"], buf["batch"],
                self._sel_args(sels[i]), buf["swin"], pending,
                jnp.float32(1.0 if valid else 0.0), keys[i],
                jnp.int32(int(rs[i])))
            self.params = out["params"]
            self._state = out["dstate"]
            scatter_prev = (pidx, out["wout"], out.get("alive")) \
                if valid else None
            ring.append((out["pending"], buf["idx"]))
            if i + 1 < n:
                if not sel_ahead:
                    dispatch_select(i + 1)
                waits[i + 1] = pf.submit(make_job(
                    i + 1, scatter_prev,
                    ring[0][1] if len(ring) == k else None))
            elif scatter_prev is not None:
                last_scatter = scatter_prev
            diags.append(out["diag"])
        if last_scatter is not None:
            pf.submit(lambda: self._host_scatter(*last_scatter))()
        self._host_async = ring
        self.round_idx += n
        jax.block_until_ready(self.params)
        if self._emit is not None:
            jax.effects_barrier()
        return {k2: np.stack([np.asarray(d[k2]) for d in diags])
                for k2 in diags[0]}

    def device_state_bytes(self):
        """Bytes of device-resident run state: params + the state dict
        (+ the resident data under the device store).  Under the host
        store this scales with the cohort slice and M-sized *scalar*
        tables only, never with M x params — the §11 regression contract
        (tests/test_store.py asserts it)."""
        trees = [self.params, self._state]
        if not self._host_mode:
            trees.append(self.data)
        return int(sum(x.nbytes for t in trees for x in jax.tree.leaves(t)))

    def host_state_bytes(self):
        """Bytes held by the host tables (0 under the device store)."""
        return 0 if self._host is None else int(self._host.nbytes())

    def _scan_rounds(self, params, state, keys, rs):
        def body(carry, kr):
            p, st = carry
            p, st, diag = self._round_core(p, st, kr[0], kr[1])
            return (p, st), diag
        (params, state), diags = jax.lax.scan(body, (params, state),
                                              (keys, rs),
                                              unroll=self._scan_unroll(keys))
        return params, state, diags

    def _scan_rounds_async(self, params, state, pending, valid, keys, rs):
        def body(carry, kr):
            p, st, pend, v = carry
            p, st, pend, v, diag = self._round_async_core(p, st, pend, v,
                                                          kr[0], kr[1])
            return (p, st, pend, v), diag
        (params, state, pending, valid), diags = jax.lax.scan(
            body, (params, state, pending, valid), (keys, rs),
            unroll=self._scan_unroll(keys))
        return params, state, pending, valid, diags

    def _scan_rounds_pipe(self, params, state, ring, rvalid, pos, keys, rs):
        def body(carry, kr):
            p, st, rg, rv, po = carry
            p, st, rg, rv, po, diag = self._round_pipe_core(
                p, st, rg, rv, po, kr[0], kr[1])
            return (p, st, rg, rv, po), diag
        (params, state, ring, rvalid, pos), diags = jax.lax.scan(
            body, (params, state, ring, rvalid, pos), (keys, rs),
            unroll=self._scan_unroll(keys))
        return params, state, ring, rvalid, pos, diags

    def _scan_unroll(self, keys):
        # XLA:CPU compiles while-loop bodies without the fusion/parallelism
        # the straight-line version gets (~3-4x slower per round here), so
        # unroll the scan on CPU; TPU keeps the rolled loop (cheap compile).
        n = keys.shape[0]
        return max(1, min(n, 16)) if jax.default_backend() == "cpu" else 1

    def _zero_pending(self):
        """All-zero pending buffers for the async pipeline's first round
        (the warmup bubble; gated off by `valid`, never applied)."""
        shapes = jax.eval_shape(self._client_section, self.params,
                                self._get_state(), self.base_key)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def _zero_ring(self):
        """Fresh depth-K ring: K stacked all-zero pending slots, all-zero
        per-slot valid flags, write cursor at slot 0."""
        k = self.fl.staleness
        shapes = jax.eval_shape(self._client_section, self.params,
                                self._get_state(), self.base_key)
        ring = jax.tree.map(
            lambda s: jnp.zeros((k,) + s.shape, s.dtype), shapes)
        return ring, jnp.zeros((k,), jnp.float32), jnp.int32(0)

    # ------------------------------------------------------------------
    # pipeline carry snapshot/restore (checkpoint/ckpt.py): a mid-pipeline
    # save keeps the in-flight cohorts, so a crash-restart resumes the
    # exact trajectory instead of re-warming the bubble (DESIGN.md §12)
    # ------------------------------------------------------------------
    def pipeline_state(self):
        """The in-flight pipeline carry as a checkpointable pytree, or
        None when nothing is in flight (sync mode, or a fresh pipeline).
        Layouts by mode — `checkpoint.save_sim` stores whichever appears:
          staleness=1, device store:  dict(pending=..., valid=...)
          staleness>=2, device store: dict(ring=..., rvalid=..., pos=...)
          host store (any K>=1):      dict(ring=[pending...],
                                           pidx=(L, cohort) int32)
        """
        if self.fl.staleness == 0:
            return None
        if self._host_mode:
            ring = self._host_async
            if not ring:
                return None
            return dict(ring=[p for p, _ in ring],
                        pidx=jnp.asarray(
                            np.stack([np.asarray(ix) for _, ix in ring])))
        if self.fl.staleness == 1:
            if self._pending is None:
                return None
            return dict(pending=self._pending, valid=self._valid)
        if self._ring is None:
            return None
        ring, rvalid, pos = self._ring
        return dict(ring=ring, rvalid=rvalid, pos=pos)

    def pipeline_template(self, n_inflight=None):
        """Shape/dtype template matching `pipeline_state()` for msgpack
        restore.  `n_inflight` (host store) is the saved ring length L."""
        if self._host_mode:
            zero = self._zero_pending_host()
            ring = [jax.tree.map(jnp.zeros_like, zero)
                    for _ in range(int(n_inflight))]
            return dict(ring=ring,
                        pidx=jnp.zeros((int(n_inflight), self.fl.cohort),
                                       jnp.int32))
        if self.fl.staleness == 1:
            return dict(pending=self._zero_pending(),
                        valid=jnp.float32(0.0))
        ring, rvalid, pos = self._zero_ring()
        return dict(ring=ring, rvalid=rvalid, pos=pos)

    def set_pipeline_state(self, pipe):
        """Install a restored pipeline carry (None == fresh bubble)."""
        if pipe is None:
            self._pending, self._valid = None, jnp.float32(0.0)
            self._ring = None
            if self._host_mode:
                self._host_async = None
            return
        if self._host_mode:
            pidx = np.asarray(pipe["pidx"]).astype(np.int32)
            self._host_async = [(p, pidx[i])
                                for i, p in enumerate(pipe["ring"])]
        elif self.fl.staleness == 1:
            self._pending = pipe["pending"]
            self._valid = jnp.asarray(pipe["valid"], jnp.float32)
        else:
            self._ring = (pipe["ring"],
                          jnp.asarray(pipe["rvalid"], jnp.float32),
                          jnp.asarray(pipe["pos"], jnp.int32))

    def _track_resume(self, round_idx):
        """Re-arm the tracker after a checkpoint restore: sinks discard
        rows past `round_idx` (a crash mid-chunk may have streamed rounds
        the checkpoint never saw) and the emitter's cumulative counters
        are restored from the last surviving row, so a resumed run
        continues the jsonl at the right round index with a continuous
        `bytes_up_cum`.  Called by `checkpoint.restore_sim`."""
        if not self._track_on:
            return
        last = self.tracker.resume(int(round_idx))
        if self._emit is not None:
            self._emit.resume(last)

    # ------------------------------------------------------------------
    def run_round(self, key=None):
        if key is None:
            key = jax.random.fold_in(self.base_key, self.round_idx)
        if self._host_mode:
            diags = self._run_host(1, jnp.asarray(key)[None])
            return {k: float(v[0]) for k, v in diags.items()}
        if self._emit is not None:
            self._emit.reset()
        self.round_idx += 1
        if self.fl.staleness >= 2:
            if self._ring is None:
                self._ring = self._zero_ring()
            ring, rvalid, pos = self._ring
            params, state, ring, rvalid, pos, diag = self._round_pipe_jit(
                self.params, self._get_state(), ring, rvalid, pos,
                key, jnp.int32(self.round_idx))
            self._ring = (ring, rvalid, pos)
        elif self.fl.staleness:
            if self._pending is None:
                self._pending = self._zero_pending()
            params, state, pending, valid, diag = self._round_async_jit(
                self.params, self._get_state(), self._pending, self._valid,
                key, jnp.int32(self.round_idx))
            self._pending, self._valid = pending, valid
        else:
            params, state, diag = self._round_jit(
                self.params, self._get_state(), key,
                jnp.int32(self.round_idx))
        self.params = params
        self._set_state(state)
        if self._emit is not None:
            jax.effects_barrier()
        return {k: float(v) for k, v in diag.items()}

    def run_rounds(self, n, key=None):
        """Scan n rounds in one dispatch (donated buffers, no host sync).

        Equivalent to n `run_round()` calls: same per-round keys, same
        trajectory.  Returns stacked per-round scalar diagnostics.  In
        async mode (`staleness = K >= 1`) the in-flight cohort(s) are
        carried on the simulator across calls, so chunked driving
        (`run_rounds(5)` x 4) follows the same pipelined trajectory as one
        `run_rounds(20)`.
        """
        if n <= 0:
            return {}
        start = self.round_idx
        if self._host_mode:
            return self._run_host(n, self._round_keys(start, n, key))
        # host spans (track.HOST_SPANS) on the profiler's clock: a device
        # idle stretch between two scans lands in the phase that held it
        with track.span(track.DRAW_BATCH, round=start):
            keys = self._round_keys(start, n, key)
            rs = start + jnp.arange(1, n + 1, dtype=jnp.int32)
        with track.span(track.DISPATCH, round=start, rounds=n):
            if self._emit is not None:
                self._emit.reset()
            if self.fl.staleness >= 2:
                if self._ring is None:
                    self._ring = self._zero_ring()
                ring, rvalid, pos = self._ring
                params, state, ring, rvalid, pos, diags = \
                    self._scan_pipe_jit(self.params, self._get_state(), ring,
                                        rvalid, pos, keys, rs)
                self._ring = (ring, rvalid, pos)
            elif self.fl.staleness:
                if self._pending is None:
                    self._pending = self._zero_pending()
                params, state, pending, valid, diags = self._scan_async_jit(
                    self.params, self._get_state(), self._pending,
                    self._valid, keys, rs)
                self._pending, self._valid = pending, valid
            else:
                params, state, diags = self._scan_jit(
                    self.params, self._get_state(), keys, rs)
            self.round_idx += n
            self.params = params
            self._set_state(state)
        with track.span(track.BLOCK, round=start, arrays=len(diags)):
            if self._emit is not None:
                # every per-round callback has run before we hand back
                # control (io_callback is ordered but asynchronous w.r.t.
                # the host)
                jax.effects_barrier()
            return {k: np.asarray(v) for k, v in diags.items()}

    def _round_keys(self, start, n, key):
        """The n round keys of a call: fold_in(base_key, round index), or
        n splits of an explicit key."""
        if key is None:
            return jax.vmap(lambda i: jax.random.fold_in(self.base_key, i))(
                start + jnp.arange(n))
        return jax.random.split(key, n)

    # ------------------------------------------------------------------
    # evaluation: one padded, vmapped pass over all clients
    # ------------------------------------------------------------------
    def _eval_core(self, params, personal, feats, labels_eval, sizes, *,
                   personalize_steps: int):
        task, fl = self.task, self.fl
        n_max = labels_eval.shape[1]

        def per_client(pers_u, feats_u, lab_eval, size):
            p = M._split_update(task, params, pers_u) \
                if pers_u is not None else params
            # personalization runs on the cyclically padded batch: each real
            # sample appears floor/ceil(n_max/size) times, so sample weights
            # differ by at most one repetition (exact when size | n_max)
            for _ in range(personalize_steps):
                g = jax.grad(task.loss)(p, feats_u)
                p = jax.tree.map(lambda pi, gi: pi - fl.mc.local_lr * gi,
                                 p, g)
            # padded positions carry label -1 (argmax never matches), so the
            # padded-mean accuracy rescales exactly to the true shard mean.
            acc = task.accuracy(p, dict(feats_u, labels=lab_eval))
            return acc * n_max / jnp.maximum(size, 1).astype(jnp.float32)

        if personal is not None:
            accs = jax.vmap(per_client)(personal, feats, labels_eval, sizes)
        else:
            accs = jax.vmap(lambda f, le, s: per_client(None, f, le, s))(
                feats, labels_eval, sizes)
        valid = (sizes > 0).astype(jnp.float32)
        return jnp.sum(accs * valid), jnp.sum(valid)

    def evaluate(self, eval_data, personalize_steps=0, chunk: int = 32):
        """Mean per-client accuracy; personalize_steps>0 == "test after".

        Clients are evaluated in vmapped chunks (instead of one trace per
        client): each client's shard is cyclically padded to the global n_max
        (repeated real samples for the personalization steps), and padded
        slots are excluded from the accuracy by the -1-label mask + size
        rescale.  `chunk` bounds the gathered working set to
        (chunk, n_max, ...) so large-M simulations do not materialize an
        M-times copy of the eval set.

        In async mode the in-flight round has not been applied yet: the
        evaluated params are the ones every client pass issued so far has
        seen (the bounded-staleness contract, DESIGN.md §6).
        """
        if self._host_mode:
            # same ops in numpy (exact integer gathers, identical values):
            # the full eval set stays host-side, only (chunk, n_max, ...)
            # windows ever reach the device — the store contract (§11)
            pool = np.asarray(eval_data["client_idx"])       # (M, n_max)
            m, n_max = pool.shape
            sizes_all = np.asarray(
                eval_data["client_sizes"]).astype(np.int32)
            data = {k: np.asarray(v) for k, v in eval_data.items()
                    if k not in ("client_idx", "client_sizes")}
        else:
            pool = jnp.asarray(eval_data["client_idx"])      # (M, n_max)
            m, n_max = pool.shape
            sizes_all = jnp.asarray(
                eval_data["client_sizes"]).astype(jnp.int32)
            data = {k: jnp.asarray(v) for k, v in eval_data.items()
                    if k not in ("client_idx", "client_sizes")}
        xp = np if self._host_mode else jnp
        acc_sum, n_valid = 0.0, 0.0
        for lo in range(0, m, chunk):
            hi = min(lo + chunk, m)
            sizes = sizes_all[lo:hi]
            pos = xp.arange(n_max)[None, :] % xp.maximum(sizes[:, None], 1)
            sel = xp.take_along_axis(xp.maximum(pool[lo:hi], 0), pos,
                                     axis=1)
            feats = {k: xp.take(v, sel, axis=0) for k, v in data.items()}
            labels_eval = xp.where(
                xp.arange(n_max)[None, :] < sizes[:, None],
                feats["labels"], -1)
            personal = jax.tree.map(lambda x: x[lo:hi], self.personal) \
                if self.method.personal else None
            s, v = self._eval_jit(self.params, personal, feats, labels_eval,
                                  sizes, personalize_steps=personalize_steps)
            acc_sum += float(s)
            n_valid += float(v)
        return acc_sum / max(n_valid, 1.0)
