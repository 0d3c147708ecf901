"""The distributed execution engine: shard_map'd train / prefill / serve
steps with the paper's compressed gradient aggregation wired in.

train_step (per device, inside shard_map over the full mesh):
  1. forward/backward on the local batch shard (TP collectives inside;
     FSDP leaves aggregate their grads in the backward hook with Q_W)
  2. paper's Algorithm 1 on the remaining gradient leaves:
     Q_W per worker -> collective over the DP axes -> Q_M
  3. Q_M on the FSDP-scattered leaves (layer-wise, deterministic key)
  4. optimizer update (state sharded like the params)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P



def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


from repro.core.aggregation import CompressionConfig, compressed_allreduce
from repro.core.granularity import Granularity
from repro.core.plan import UnitPlan, build_plan
from repro.models.config import InputShape, ModelConfig
from repro.models.dist import DistConfig
from repro.models.model import Model
from repro.optim import OptConfig, apply_updates, init_opt_state

Array = jax.Array


def _partition(tree, mask):
    """Split tree into (true_subtree, false_subtree) with None placeholders."""
    t = jax.tree_util.tree_map(lambda x, m: x if m else None, tree, mask)
    f = jax.tree_util.tree_map(lambda x, m: None if m else x, tree, mask)
    return t, f


def _merge(t, f):
    return jax.tree_util.tree_map(lambda a, b: a if b is None else b, t, f,
                                  is_leaf=lambda x: x is None)


class Engine:
    def __init__(self, cfg: ModelConfig, mesh, *,
                 comp: Optional[CompressionConfig] = None,
                 opt: Optional[OptConfig] = None,
                 remat: bool = True):
        self.cfg = cfg
        self.mesh = mesh
        self.sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        has_pod = "pod" in self.sizes
        dp = (("pod", "data") if has_pod else ("data",))
        self.dist = DistConfig(tp="model",
                               fsdp="data" if cfg.use_fsdp else None,
                               dp=dp, sp=True)
        self.model = Model(cfg, self.dist, self.sizes)
        self.comp = comp
        self.opt = opt or OptConfig()
        self.remat = remat
        self.dp_size = 1
        for a in dp:
            self.dp_size *= self.sizes[a]

    # ------------------------------------------------------------------
    # input specs (ShapeDtypeStruct stand-ins, no allocation)
    # ------------------------------------------------------------------
    def batch_shapes(self, shape: InputShape) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            out = {"token": jax.ShapeDtypeStruct((B,), jnp.int32),
                   "pos": jax.ShapeDtypeStruct((), jnp.int32)}
            return out
        out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if shape.kind == "train":
            out["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.arch_type == "vlm":
            out["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        if cfg.arch_type == "audio":
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        return out

    def _dpp(self, shape: InputShape):
        """Batch-dim partition: the dp axes, or None (replicated) when the
        global batch does not divide them (long_500k, batch=1)."""
        if shape.global_batch % self.dp_size != 0:
            return None
        dp = tuple(self.dist.dp)
        return dp if len(dp) > 1 else dp[0]

    def batch_pspecs(self, shape: InputShape) -> Dict[str, P]:
        dpp = self._dpp(shape)
        if shape.kind == "decode":
            return {"token": P(dpp), "pos": P()}
        out = {"tokens": P(dpp, None)}
        if shape.kind == "train":
            out["targets"] = P(dpp, None)
        if self.cfg.arch_type == "vlm":
            out["patch_embeds"] = P(dpp, None, None)
        if self.cfg.arch_type == "audio":
            out["frames"] = P(dpp, None, None)
        return out

    def _sharded_sds(self, sds_tree, pspec_tree):
        def attach(s, p):
            if s is None:
                return None
            return jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(self.mesh, p))
        return jax.tree_util.tree_map(attach, sds_tree, pspec_tree,
                                      is_leaf=lambda x: x is None)

    def input_specs(self, shape: InputShape):
        """(args_sds, in_specs) for the step of this shape's kind."""
        if shape.kind == "train":
            return self.train_input_specs(shape)
        if shape.kind == "prefill":
            b = self._sharded_sds(self.batch_shapes(shape),
                                  self.batch_pspecs(shape))
            return (b,), (self.batch_pspecs(shape),)
        b = self._sharded_sds(self.batch_shapes(shape),
                              self.batch_pspecs(shape))
        sb = shape.global_batch % self.dp_size == 0
        cache = self._sharded_sds(
            self.model.cache_shapes(shape.seq_len, shape.global_batch),
            self.model.cache_pspecs(sb))
        return (b, cache), (self.batch_pspecs(shape),
                            self.model.cache_pspecs(sb))

    def train_input_specs(self, shape: InputShape):
        params = self._sharded_sds(self.model.param_shapes(),
                                   self.model.param_pspecs())
        opt_sds = jax.eval_shape(partial(init_opt_state, self.opt),
                                 self.model.param_shapes())
        opt_ps = self._opt_pspecs()
        opt = self._sharded_sds(opt_sds, opt_ps)
        batch = self._sharded_sds(self.batch_shapes(shape),
                                  self.batch_pspecs(shape))
        step = jax.ShapeDtypeStruct((), jnp.int32)
        return (params, opt, batch, step), (
            self.model.param_pspecs(), opt_ps, self.batch_pspecs(shape), P())

    def _opt_pspecs(self):
        pp = self.model.param_pspecs()
        if self.opt.name == "sgd":
            return {}
        if self.opt.name == "momentum":
            return {"m": pp}
        return {"m": pp, "v": pp, "count": P()}

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------
    def _local_sds(self, sds, pspec):
        """Per-device shard ShapeDtypeStruct for one leaf (the shapes the
        train step sees INSIDE shard_map)."""
        shape = list(sds.shape)
        if pspec is not None:
            for i, ax in enumerate(pspec):
                if ax is None or i >= len(shape):
                    continue
                names = ax if isinstance(ax, tuple) else (ax,)
                f = 1
                for nm in names:
                    f *= self.sizes.get(nm, 1)
                shape[i] //= f
        return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)

    def _local_param_sds(self):
        """Per-device shard ShapeDtypeStructs of the full parameter tree
        (the gradient shapes the train step sees inside shard_map)."""
        return jax.tree_util.tree_map(self._local_sds,
                                      self.model.param_shapes(),
                                      self.model.param_pspecs())

    def measurement_plan(self):
        """The layer-wise UnitPlan telemetry is measured over (the full
        local gradient tree, independent of the active execution
        granularity — so a controller's TelemetryState keeps its shape
        across decisions). Cached: the same object the traced step uses.
        """
        from repro.control.telemetry import measurement_plan
        return measurement_plan(self._local_param_sds(),
                                self.model.stacked())

    def comm_plans(self, comp: Optional[CompressionConfig] = None):
        """(rest_plan, fsdp_plan): the static UnitPlans the train step
        executes compression through.

        Built from per-device SHARD ShapeDtypeStructs (param shapes with
        the tp/fsdp partition applied) — the same shapes _aggregate_grads
        traces inside shard_map — and cached on (structure, shapes,
        granularity), so the first train-step trace and any pre-trace
        caller (train.py summary, bits.comm_report, comm_sched) share one
        plan object. `comp` overrides the engine config (the decision →
        step path). fsdp_plan is None when no leaf is fsdp-aggregated or
        the master compressor is identity (no Q_M pass runs on those
        leaves).
        """
        comp = comp or self.comp or CompressionConfig(strategy="dense")
        stacked = self.model.stacked()
        fsdp_mask = self.model.fsdp_mask()
        shapes = self._local_param_sds()
        g_fsdp, g_rest = _partition(shapes, fsdp_mask)
        s_fsdp, s_rest = _partition(stacked, fsdp_mask)
        rest_plan = (build_plan(g_rest, s_rest, comp.granularity)
                     if jax.tree_util.tree_leaves(g_rest) else None)
        master_runs = (comp.qm is not None and comp.qm.name != "identity")
        fsdp_plan = (build_plan(g_fsdp, s_fsdp, comp.granularity)
                     if master_runs and jax.tree_util.tree_leaves(g_fsdp)
                     else None)
        return rest_plan, fsdp_plan

    def _aggregate_grads(self, grads, key,
                         comp: Optional[CompressionConfig] = None,
                         schedule=None, wire: bool = False,
                         recorder=None):
        """Paper's Algorithm 1 over the DP axes, executed through the
        static UnitPlans (one batched compressor dispatch per unit size
        class — built once at jit-trace time, cached thereafter). With
        `schedule` (a CommSchedule for the rest plan) or comp.fusion_bytes
        set, the rest leaves stream through the backward-ordered fused
        message schedule — bit-identical numerics. `wire=True`
        materializes the rest leaves' worker compression as real
        bit-packed message buffers (core.wire; the FSDP backward-hook
        leaves are untouched — their Q_W runs inside the hook)."""
        model, dist = self.model, self.dist
        comp = comp if comp is not None else self.comp
        stacked = model.stacked()
        fsdp_mask = model.fsdp_mask()
        g_fsdp, g_rest = _partition(grads, fsdp_mask)
        s_fsdp, s_rest = _partition(stacked, fsdp_mask)

        if comp is None or comp.strategy == "dense":
            agg_rest, _ = compressed_allreduce(
                g_rest, s_rest,
                comp or CompressionConfig(strategy="dense"),
                dist.dp, key, self.dp_size, wire=wire)
            return _merge(g_fsdp, agg_rest)

        rest_plan = build_plan(g_rest, s_rest, comp.granularity)
        # rest leaves: full bidirectional pipeline
        agg_rest, _ = compressed_allreduce(g_rest, s_rest, comp, dist.dp,
                                           key, self.dp_size,
                                           plan=rest_plan,
                                           schedule=schedule, wire=wire,
                                           recorder=recorder)
        # fsdp leaves: Q_W already applied in the backward hook; grads are
        # scattered+averaged. Apply Q_M layer-wise (identical key on every
        # device -> consistent master compression).
        if comp.qm is not None and comp.qm.name != "identity":
            mkey = jax.random.fold_in(key, 0x5EED)

            def master(x, ukey):
                return comp.qm.sim(x, ukey)
            fsdp_plan = build_plan(g_fsdp, s_fsdp, comp.granularity)
            g_fsdp = fsdp_plan.execute(master, g_fsdp, mkey,
                                       recorder=recorder)
        return _merge(g_fsdp, agg_rest)

    def build_train_step(self, lr_schedule=None, *,
                         comp: Optional[CompressionConfig] = None,
                         telemetry: bool = False,
                         telemetry_entire_model: bool = True,
                         schedule=None, wire: bool = False,
                         collective: Optional[str] = None,
                         tracer=None, metrics=None,
                         step_guard: bool = False):
        """The sharded, jitted train step.

        `comp` overrides the engine's CompressionConfig for THIS step
        (the controller's decision → step path; `None` keeps engine
        default — identical graph to the pre-controller behavior).
        `schedule` streams the DP gradient aggregation through a
        CommSchedule: pass a fusion-bytes number (compiled against the
        engine's cached rest plan; 0 = per-bucket messages, math.inf =
        one fused message) or a prebuilt CommSchedule from
        launch.comm_sched.engine_schedule. Scheduling is bit-identical —
        it changes program order and wire-message accounting, never
        numerics (the comp.fusion_bytes field is the decision-carried
        equivalent; an explicit `schedule` wins). With
        `telemetry=True` the step takes and returns a
        control.telemetry.TelemetryState as an extra (replicated)
        argument: (params, opt, batch, step, telem) -> (params, opt,
        metrics, telem'), where telem' accumulates this step's
        measurement pmean'd over ALL devices. Semantics of that mean:
        each device measures its LOCAL shard, so absolute second moments
        are per-device-shard averages, not global sums — ratio statistics
        (omega_hat, rel_err — all any policy consumes) are exact, since
        the uniform 1/n_devices factor cancels.
        `telemetry_entire_model=False` drops the flat counterfactual
        compression pass (only GranularitySwitchPolicy reads it).
        `wire=True` routes the DP gradient aggregation through REAL
        bit-packed wire buffers (core.wire; requires a codec-bearing
        worker compressor and the simulated/allgather strategy) —
        bit-identical numerics, but every wire message is a materialized
        uint8 buffer whose size*8 is the wire truth.
        `collective` picks the wire collective's topology: None keeps the
        config's strategy; 'allgather' forces the serialized
        gather-all-payloads stream; 'ring' routes the same messages
        through the streaming chunked-ppermute ring
        (CommSchedule.execute_streaming — bit-identical to 'allgather',
        with real compress/collective overlap in program order). Both
        require `wire=True` and a compression config (the dense path has
        no wire messages to stream).
        `tracer` (duck-typed, obs.trace.TraceRecorder) instruments the
        gradient-aggregation pipeline with per-message/stage spans (the
        step's marks fire per executed step; block on the step's outputs
        then call tracer.finalize_step). Note marks fire once per DEVICE
        under shard_map — trace on a 1-device mesh for a clean timeline.
        `metrics` (obs.metrics.MetricsRegistry) receives build counters
        and static plan/schedule gauges. Both default to None — the
        traced graph is then bit-identical to the uninstrumented one.
        `step_guard=True` makes the update self-protecting: if the loss
        or ANY aggregated-gradient leaf is non-finite, the whole update
        is dropped (params and optimizer state keep their pre-step
        values) and the returned metrics carry `skipped=1.0`. The flag
        is pmin-reduced over ALL mesh axes so every rank (including TP
        peers that would otherwise diverge) takes the same branch.
        """
        model, cfg, opt = self.model, self.cfg, self.opt
        dist = self.dist
        comp_eff = comp if comp is not None else self.comp
        if collective is not None:
            if collective not in ("allgather", "ring"):
                raise ValueError(
                    f"collective must be None, 'allgather' or 'ring'; "
                    f"got {collective!r}")
            if not wire or comp_eff is None or comp_eff.strategy == "dense":
                raise ValueError(
                    "collective= picks the wire collective's topology: it "
                    "requires wire=True and a compression config")
            comp_eff = dataclasses.replace(comp_eff, strategy=collective)
        if schedule is not None:
            from repro.launch.comm_sched import resolve_schedule
            rest_plan, _ = self.comm_plans(comp_eff)
            schedule = resolve_schedule(rest_plan, schedule)
        sched = lr_schedule or (lambda s: jnp.float32(self.opt.lr))
        all_axes = tuple(self.mesh.axis_names)
        if telemetry:
            from repro.control.telemetry import accumulate, measure
            mplan = self.measurement_plan()

        mb = max(1, cfg.train_microbatch)

        def step_fn(params, opt_state, batch, step, telem=None):
            key = jax.random.fold_in(jax.random.key(42), step)
            comp_hook = comp_eff if dist.fsdp is not None else None

            def loss_fn(p, b):
                return model.loss(p, b, key, comp=comp_hook,
                                  remat=self.remat)

            mb_eff = min(mb, batch["tokens"].shape[0])
            if mb_eff == 1:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            else:
                # gradient accumulation: split the LOCAL batch into mb
                # microbatches; grads accumulate in param dtype. The FSDP
                # backward hook compresses + reduce-scatters per microbatch
                # (a finer worker partition — covered by Lemma 1).
                mbatch = jax.tree_util.tree_map(
                    lambda x: x.reshape((mb_eff, x.shape[0] // mb_eff)
                                        + x.shape[1:]), batch)

                def mb_body(carry, b_i):
                    acc, lsum = carry
                    l, g = jax.value_and_grad(loss_fn)(params, b_i)
                    acc = jax.tree_util.tree_map(jnp.add, acc, g)
                    return (acc, lsum + l), None

                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                (grads, lsum), _ = jax.lax.scan(
                    mb_body, (zeros, jnp.zeros((), jnp.float32)), mbatch)
                inv = 1.0 / mb_eff
                grads = jax.tree_util.tree_map(
                    lambda g: (g * jnp.asarray(inv, g.dtype)), grads)
                loss = lsum * inv
            agg = self._aggregate_grads(grads, key, comp_eff,
                                        schedule=schedule, wire=wire,
                                        recorder=tracer)
            if telemetry:
                qw = (comp_eff or CompressionConfig(strategy="dense")).qw
                inc = measure(mplan, qw, grads, key, grads_hat=agg,
                              entire_model=telemetry_entire_model)
                inc = jax.tree_util.tree_map(
                    lambda v: jax.lax.pmean(v, all_axes), inc)
                telem = accumulate(telem, inc)
            lr = sched(step)
            new_params, new_opt = apply_updates(opt, params, agg,
                                                opt_state, lr)
            if step_guard:
                finite = jnp.isfinite(loss)
                for leaf in jax.tree_util.tree_leaves(agg):
                    finite = finite & jnp.all(jnp.isfinite(leaf))
                # every rank must take the same branch: a TP peer with a
                # finite shard would otherwise diverge from one that saw
                # the NaN
                finite = jax.lax.pmin(finite.astype(jnp.int32),
                                      all_axes) > 0
                keep = lambda n, o: jnp.where(finite, n, o)
                new_params = jax.tree_util.tree_map(keep, new_params,
                                                    params)
                new_opt = jax.tree_util.tree_map(keep, new_opt, opt_state)
            params, opt_state = new_params, new_opt
            loss = jax.lax.pmean(loss, dist.dp)
            metrics = {"loss": loss, "lr": lr}
            if step_guard:
                metrics["skipped"] = 1.0 - finite.astype(jnp.float32)
            if telemetry:
                return params, opt_state, metrics, telem
            return params, opt_state, metrics

        pp = self.model.param_pspecs()
        ops = self._opt_pspecs()
        # training batches always shard over the dp axes (global batch is a
        # multiple of the dp degree for every assigned train shape)
        bs = self.batch_pspecs(
            InputShape("train", 1, self.dp_size, "train"))
        metrics_spec = {"loss": P(), "lr": P()}
        if step_guard:
            metrics_spec["skipped"] = P()
        if telemetry:
            mapped = shard_map(
                step_fn, self.mesh,
                in_specs=(pp, ops, bs, P(), P()),
                out_specs=(pp, ops, metrics_spec, P()))
        else:
            mapped = shard_map(
                step_fn, self.mesh,
                in_specs=(pp, ops, bs, P()),
                out_specs=(pp, ops, metrics_spec))
        if metrics is not None and getattr(metrics, "enabled", False):
            metrics.inc("engine/step_builds")
            rest_plan, _ = self.comm_plans(comp_eff)
            if rest_plan is not None:
                metrics.gauge("engine/n_dispatches",
                              rest_plan.num_dispatches)
                metrics.gauge("engine/n_units", rest_plan.num_units)
                sched_eff = schedule   # explicit schedule wins; else the
                if sched_eff is None and comp_eff is not None and \
                        comp_eff.fusion_bytes is not None:
                    from repro.core.schedule import \
                        build_schedule  # decision-carried fusion_bytes
                    sched_eff = build_schedule(rest_plan,
                                               comp_eff.fusion_bytes)
                if sched_eff is not None:
                    metrics.gauge("engine/n_messages",
                                  sched_eff.num_messages)
                    metrics.gauge("engine/fusion_bytes",
                                  min(sched_eff.fusion_bytes, 2.0 ** 63))
                if comp_eff is not None and comp_eff.strategy != "dense":
                    from repro.control.telemetry import \
                        payload_bits_per_step
                    metrics.gauge(
                        "engine/wire_bits_per_step",
                        payload_bits_per_step(rest_plan, comp_eff.qw))
        return jax.jit(mapped, donate_argnums=(0, 1))

    # ------------------------------------------------------------------
    # inference steps
    # ------------------------------------------------------------------
    def build_prefill(self, shape: InputShape, cache_len: int = None):
        """The sharded prefill step. `cache_len` sizes the returned KV
        cache beyond the prompt (generation slots for a following
        decode loop — the serve launcher's path); default: prompt
        length. Must be used instead of a bare jit(model.prefill): the
        model's TP collectives only have their axes bound inside
        shard_map."""
        model = self.model
        dpp = self._dpp(shape)

        def step_fn(params, batch):
            return model.prefill(params, batch, jax.random.key(0),
                                 remat=self.remat, cache_len=cache_len)

        pp = model.param_pspecs()
        bs = self.batch_pspecs(shape)
        sb = shape.global_batch % self.dp_size == 0
        mapped = shard_map(
            step_fn, self.mesh, in_specs=(pp, bs),
            out_specs=((P(dpp, "model"), model.cache_pspecs(sb))))
        return jax.jit(mapped)

    def build_serve_step(self, shape: InputShape):
        model = self.model
        dpp = self._dpp(shape)

        def step_fn(params, batch, cache):
            logits, new_cache = model.decode_step(params, batch["token"],
                                                  batch["pos"], cache)
            return logits, new_cache

        pp = model.param_pspecs()
        cs = model.cache_pspecs(shape.global_batch % self.dp_size == 0)
        bs = self.batch_pspecs(shape)
        mapped = shard_map(step_fn, self.mesh, in_specs=(pp, bs, cs),
                           out_specs=(P(dpp, "model"), cs))
        return jax.jit(mapped, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def memory_estimate(self, shape: InputShape) -> Dict[str, float]:
        """Analytic per-device HBM estimate for the TPU target.

        The CPU backend's buffer assignment promotes bf16 compute to f32
        (no native bf16 on CPU), inflating temp_size ~2-3x; this estimate
        is the documented fits-in-HBM proof, with the CPU number reported
        alongside as a (loose) upper bound. Terms:
          params + optimizer state + gradients (train) + saved residual
          stack (train, seq-parallel) + per-layer transients (FSDP
          weight gathers, gathered activations, loss chunks) + KV cache.
        """
        cfg = self.cfg
        bt = 2 if cfg.dtype == "bfloat16" else 4
        tp = self.sizes.get("model", 1)
        dpn = self.dp_size
        chips = tp * dpn
        n_params = cfg.param_count()
        shard = tp * (dpn if cfg.use_fsdp else 1)
        params = n_params * bt / shard
        opt_mult = {"sgd": 0, "momentum": 1, "adam": 2}[self.opt.name]
        opt = n_params * 4 * opt_mult / shard
        B_l = max(1, shape.global_batch // dpn)
        d = cfg.d_model
        est = {"params": params, "opt_state": opt}
        if shape.kind == "train":
            est["grads"] = params
            mb = max(1, cfg.train_microbatch)
            B_mb = max(1, B_l // mb)
            S_l = shape.seq_len // tp  # sequence-parallel residual stack
            est["residual_stack"] = cfg.n_layers * B_mb * S_l * d * bt
            # transients: gathered per-layer weights (fsdp) + ~4 copies of
            # the gathered (B,S,d) activation + one loss chunk
            layer_params = (n_params - 2 * cfg.vocab * d) / max(1, cfg.n_layers)
            gathered_w = (layer_params * bt / tp) if cfg.use_fsdp else 0
            est["layer_transients"] = gathered_w + 4 * B_mb * shape.seq_len * d * bt
            est["loss_chunk"] = 8192 * (self.model.vocab_padded // tp) * 4 * 2
        elif shape.kind == "prefill":
            est["activations"] = 4 * B_l * shape.seq_len * d * bt
            cache = self.model.cache_shapes(shape.seq_len, shape.global_batch)
            est["cache"] = sum(
                (x.size * x.dtype.itemsize) / chips
                for x in jax.tree_util.tree_leaves(cache) if x is not None)
            if cfg.use_fsdp:
                est["layer_transients"] =                     (n_params - 2 * cfg.vocab * d) / max(1, cfg.n_layers)                     * bt / tp
        else:  # decode: weights stay sharded (2D TP), cache dominates
            cache = self.model.cache_shapes(shape.seq_len, shape.global_batch)
            est["cache"] = sum(
                (x.size * x.dtype.itemsize) / chips
                for x in jax.tree_util.tree_leaves(cache) if x is not None)
            est["activations"] = 8 * B_l * d * 4
        est["total"] = sum(est.values())
        est["fits_16g"] = est["total"] <= 16e9
        return est

    def init_state(self, seed: int = 0):
        """Materialize params + optimizer state, placed on the mesh as the
        train step returns them (so the second step reuses the first
        step's compiled program)."""
        params = self.model.init(jax.random.key(seed))
        opt_state = init_opt_state(self.opt, params)

        def place(tree, specs):
            return jax.tree_util.tree_map(
                lambda x, p: jax.device_put(x, NamedSharding(self.mesh, p)),
                tree, specs)
        return (place(params, self.model.param_pspecs()),
                place(opt_state, self._opt_pspecs()))
