"""Trainer — the training driver.

TPU-native analog of the reference's trainer stack (ref: paddle/trainer/
Trainer.{h,cpp}: train/trainOnePass/trainOneDataBatch :264-520;
TrainerInternal.cpp trainOneBatch :65-173; Tester.{h,cpp}).

Re-design: the reference's per-batch choreography (startBatch → forward →
per-parameter update callbacks pipelined into backward → finishBatch) becomes
ONE jitted `train_step` = loss + grad + optimizer apply, compiled by XLA with
the same overlap the reference engineered by hand.  The pass loop, periodic
logging/eval/checkpointing and the --job=time benchmark mode mirror the
reference's driver behavior.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.config.schema import DataConfig, TrainerConfig
from paddle_tpu.data.feeder import DataFeeder
from paddle_tpu.graph.builder import GraphExecutor
from paddle_tpu.graph.context import TEST, TRAIN
from paddle_tpu.optim.updater import ParameterUpdater
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.trainer import checkpoint as ckpt
from paddle_tpu.trainer.evaluators import EvaluatorSet
from paddle_tpu.utils import FLAGS, get_logger, global_stat

log = get_logger("trainer")


def load_provider(data_cfg: DataConfig, fresh: bool = False):
    """Instantiate a @provider from a DataConfig
    (ref: gserver/dataproviders/PyDataProvider2.cpp createPyDataProvider).

    fresh=True clones the module-level wrapper and its settings before
    initialize() — required when several sub-sources reference the same
    @provider object with different args (the init_hook mutates settings,
    which would otherwise be shared)."""
    import importlib

    mod = importlib.import_module(data_cfg.load_data_module)
    prov = getattr(mod, data_cfg.load_data_object)
    if fresh:
        import copy
        prov = copy.copy(prov)
        prov.settings = copy.deepcopy(prov.settings)
    files: list[str] = []
    if data_cfg.files:
        if os.path.exists(data_cfg.files):
            with open(data_cfg.files) as f:
                files = [ln.strip() for ln in f if ln.strip()]
        else:
            files = [data_cfg.files]
    kwargs = json.loads(data_cfg.load_data_args) if data_cfg.load_data_args else {}
    if not isinstance(kwargs, dict):
        kwargs = {"args": kwargs}
    prov.initialize(files, **kwargs)
    return prov, files


class Trainer:
    """Drives training/testing of one TrainerConfig
    (ref: Trainer.h:48; jobs train/test/time)."""

    def __init__(
        self,
        config: TrainerConfig,
        seed: int = 1,
        mesh: Optional[Any] = None,
        updater: Optional[Any] = None,
    ):
        """`updater` swaps the parameter-update strategy (ref: the
        local/thread/remote ParameterUpdater family): None builds the
        local fused-into-the-train-step ParameterUpdater; an
        optim.remote_updater.RemoteParameterUpdater (is_remote=True)
        makes the train step GRAD-ONLY and routes every batch through
        the parameter-server tier (paddle_tpu/pserver/)."""
        assert config.model_config is not None and config.opt_config is not None
        self.config = config
        self.model = config.model_config
        self.opt = config.opt_config
        cdt = FLAGS.compute_dtype or self.opt.compute_dtype
        from paddle_tpu.parallel.mesh import PIPE_AXIS, axis_size
        if mesh is not None and axis_size(mesh, PIPE_AXIS) > 1 \
                and any(l.device >= 0 for l in self.model.layers):
            # config-driven pipeline parallelism: device=N layer annotations
            # map onto pipe-axis stages (ref: ParallelNeuralNetwork.h:35-70)
            from paddle_tpu.parallel.pipeline_config import PipelineExecutor
            self.executor = PipelineExecutor(
                self.model, mesh,
                n_micro=self.opt.pipeline_micro_batches, compute_dtype=cdt,
                schedule=self.opt.pipeline_schedule or "gpipe",
                virtual_stages=self.opt.pipeline_virtual_stages or 1)
        else:
            self.executor = GraphExecutor(self.model, mesh=mesh,
                                          compute_dtype=cdt)
        self.updater = updater if updater is not None \
            else ParameterUpdater(self.model, self.opt)
        self._remote = bool(getattr(self.updater, "is_remote", False))
        self.evaluators = EvaluatorSet(self.model)
        # under pipeline parallelism stage-internal activations never
        # surface, so evaluators referencing them are skipped rather than
        # failing; the plain path keeps missing layers a loud error
        self.evaluators.allow_missing = not isinstance(self.executor,
                                                       GraphExecutor)
        self.seed = seed
        self.mesh = mesh
        self.rng = jax.random.PRNGKey(seed)

        self.params = self.executor.init_params(jax.random.PRNGKey(seed))
        # updater hooks (pruning masks) bind to the initial values
        # (ref: ParameterUpdaterHook.cpp StaticPruningHook::init)
        self.params = self.updater.apply_init_hooks(self.params)
        self.opt_state = self.updater.init_state(self.params)
        self.net_state: dict[str, Any] = {}
        self.pass_id = 0
        if self._remote:
            # join the pserver fleet and adopt the authoritative
            # parameters (the first trainer seeds them from this very
            # seed-deterministic init, so a cold fleet start is a no-op)
            synced = self.updater.connect_and_sync(
                {n: np.asarray(jax.device_get(v))
                 for n, v in self.params.items()},
                config_json=self.config.to_json())
            self.params = {n: jnp.asarray(np.asarray(v))
                           for n, v in synced.items()}

        if mesh is not None:
            from paddle_tpu.parallel.dp import (effective_zero_stage,
                                                shard_train_objects)
            self.zero_stage = effective_zero_stage(self.opt)
            self.params, self.opt_state = shard_train_objects(
                mesh, self.model, self.params, self.opt_state,
                shard_opt=self.opt.shard_optimizer_state,
                zero_stage=self.zero_stage)
        else:
            self.zero_stage = 0
        self._train_step_fn = self._build_train_step_fn()
        # every trainer jit reports to the compile watcher (obs/
        # compile_watch.py): a data pipeline that churns batch signatures
        # shows up as a recompile storm instead of a silent slowdown.  The
        # wrapper proxies .lower()/._cache_size() introspection unchanged.
        from paddle_tpu.obs.compile_watch import get_compile_watch
        _cw = get_compile_watch()
        self._train_step = _cw.wrap_jit(
            "trainer.train_step", self._jit_step(self._train_step_fn))
        self._fused_step = _cw.wrap_jit("trainer.fused_step",
                                        self._build_fused_step())
        # benchmark twin: same scanned step, losses only (no [iters, ...]
        # evaluator/host buffers stacked on device)
        self._fused_step_losses = _cw.wrap_jit(
            "trainer.fused_step", self._build_fused_step(
                collect_outputs=False))
        # fused-dispatch oracles: tests assert exactly ceil(n/k) compiled
        # scan executions for n same-signature batches
        self._n_fused_dispatches = 0
        self._settled_sigs: set = set()
        self._test_step = _cw.wrap_jit("trainer.eval_step",
                                       self._build_test_step())
        # device-side losses buffered between host syncs (VERDICT: the
        # reference pays a per-batch cost check but not an XLA pipeline
        # stall; here finiteness is checked in bulk every
        # nonfinite_check_period batches, or per batch under --detect_nan)
        self._loss_buf: list[jax.Array] = []
        self._drained_cost = 0.0
        self._last_batch: Optional[dict[str, Argument]] = None
        # BarrierStat analog: per-step dispatch/sync timing + straggler skew,
        # logged every log_period on mesh runs (ref: utils/BarrierStat.h:
        # 198-389, REGISTER_BARRIER_TIMER_SERVER).  The windows also route
        # through the process-global span tracer (paddle_tpu/obs) when
        # tracing is enabled, so per-dispatch phases land in the same
        # Perfetto timeline as serving request lifecycles.
        from paddle_tpu.obs.trace import get_tracer
        from paddle_tpu.parallel.barrier_stat import BarrierTimer
        self._tracer = get_tracer()
        self.barrier_stat = BarrierTimer(tracer=self._tracer)
        # unified metrics registry (obs.metrics): training progress gauges
        # plus read-time collectors over the pre-existing stat systems
        # (global_stat host phases, the barrier windows, tracer ring
        # accounting).  Snapshots append to <save_dir>/metrics.jsonl next
        # to the checkpoints (append_metrics, called per pass by train()).
        from paddle_tpu.obs import (MetricsRegistry, barrier_collector,
                                    statset_collector, tracer_collector)
        self.metrics = MetricsRegistry(strict=True)
        self._m_pass = self.metrics.gauge("trainer_pass_id")
        self._m_cost = self.metrics.gauge("trainer_cost")
        self._m_sps = self.metrics.gauge("trainer_samples_per_sec")
        self._m_batches = self.metrics.counter("trainer_batches_total")
        self._m_samples = self.metrics.counter("trainer_samples_total")
        self.metrics.register_collector(statset_collector(
            global_stat, "trainer_host_phase_seconds",
            "trainer_host_phase_count", label="phase",
            total_metric="trainer_host_phase_seconds_total"))
        self.metrics.register_collector(barrier_collector(self.barrier_stat))
        # what the compiled step does about its collectives, where the mesh
        # asks the compiler for something (parallel/dp.py:
        # step_compile_options): a new signature leaves its shapes here,
        # and the executable is read when the gauges are first collected
        self._step_collectives = None
        from paddle_tpu.parallel.dp import step_compile_options
        if step_compile_options(mesh):
            from paddle_tpu.parallel.schedule import StepCollectives
            self._step_collectives = StepCollectives()
            self.metrics.register_collector(self._step_collectives)
        self.metrics.register_collector(tracer_collector(self._tracer))
        # compile events + device-memory accounting ride the same registry
        # (and therefore metrics.jsonl): per-site jit compile counters from
        # the process-global watcher, HBM/param-byte gauges with the
        # CPU-safe fallbacks of obs/hbm.py
        from paddle_tpu.obs.compile_watch import compile_collector
        from paddle_tpu.obs.hbm import hbm_collector
        self.metrics.register_collector(compile_collector())
        self.metrics.register_collector(
            hbm_collector(params_fn=lambda: self.params))
        # immutable after construction; _validate_batch uses it per batch
        self._data_layers = {l.name: l for l in self.model.layers
                             if l.type == "data"}
        # shard-traffic balance check for vocab-sharded tables (ref:
        # pserver/SparseParameterDistribution; --check_sparse_distribution)
        self.sparse_stats = None
        if mesh is not None and FLAGS.check_sparse_distribution:
            from paddle_tpu.parallel.sparse import (SparseShardStats,
                                                    sharded_table_feeds)
            feeds = sharded_table_feeds(mesh, self.model)
            if feeds:
                self.sparse_stats = SparseShardStats(
                    feeds,
                    batches=int(FLAGS.check_sparse_distribution_batches),
                    unbalance_degree=float(
                        FLAGS.check_sparse_distribution_unbalance_degree),
                    ratio=float(FLAGS.check_sparse_distribution_ratio),
                    show_log=bool(FLAGS.show_check_sparse_distribution_log))

    # -- compiled steps ---------------------------------------------------
    @property
    def _probe_names(self) -> list[str]:
        """Layers whose OUTPUT GRADIENT a gradient_printer evaluator wants
        (ref: Evaluator.cpp GradientPrinter reads getOutputGrad()); only
        supported on the plain GraphExecutor path."""
        if not isinstance(self.executor, GraphExecutor):
            return []
        names: list[str] = []
        for cfg in self.model.evaluators:
            if cfg.type != "gradient_printer":
                continue
            for n in cfg.input_layer_names:
                # probes are injected by forward()'s root layer loop only —
                # a silent zero for group-internal layers would masquerade
                # as a real gradient, so reject loudly
                if n in self.executor._sub_of:
                    raise NotImplementedError(
                        f"gradient_printer on {n!r}: the layer runs inside "
                        f"recurrent group "
                        f"{self.executor._sub_of[n].name!r}, where output-"
                        f"grad probes are not injected — probe a layer "
                        f"outside the group (e.g. the group's consumer)")
                if n not in self.executor.layer_map or \
                        self.executor.layer_map[n].type == "data":
                    raise ValueError(
                        f"gradient_printer on {n!r}: not a computed layer")
                if n not in names:
                    names.append(n)
        return names

    def _jit_step(self, fn):
        """jit of a step that updates (params, opt_state) in place.  Every
        step built on `_train_step_fn` compiles with the options its mesh
        asks for (parallel/dp.py:step_compile_options -- the gradient
        all-reduces asynchronous under a `data` axis of TPUs; none anywhere
        else, and then this is `jax.jit(fn, donate_argnums=(0, 1))`)."""
        from paddle_tpu.parallel.dp import step_compile_options
        return jax.jit(fn, donate_argnums=(0, 1),
                       compiler_options=step_compile_options(self.mesh)
                       or None)

    def _build_train_step_fn(self):
        executor, updater, evaluators = self.executor, self.updater, self.evaluators
        remote = self._remote
        probe_names = self._probe_names
        grad_shardings = None
        if self.mesh is not None and self.zero_stage >= 2:
            # ZeRO-2: pin each eligible gradient to the data axis so XLA
            # emits a reduce-scatter instead of an all-reduce and the
            # optimizer update runs on 1/N shards (the pserver addGradient
            # contract — each server receives only its own blocks)
            from paddle_tpu.parallel.dp import zero_grad_shardings
            grad_shardings = zero_grad_shardings(self.mesh, self.model,
                                                 self.params)

        def constrain_grads(grads):
            if grad_shardings is None:
                return grads
            return {n: jax.lax.with_sharding_constraint(g, grad_shardings[n])
                    if grad_shardings.get(n) is not None else g
                    for n, g in grads.items()}

        def train_step(params, opt_state, net_state, batch, rng):
            if probe_names:
                # additive zeros at the probed layers: d(loss)/d(probe) is
                # exactly the layer's output gradient
                shapes = jax.eval_shape(
                    lambda p: executor.forward(p, batch, net_state, TRAIN,
                                               rng)[0], params)
                for n in probe_names:
                    assert shapes[n].value is not None, (
                        f"gradient_printer on {n!r}: the layer's output has "
                        f"no dense value to probe (ids-only output)")
                probes = {n: jnp.zeros(shapes[n].value.shape,
                                       shapes[n].value.dtype)
                          for n in probe_names}

                def loss_fn(p, pr):
                    loss, aux = executor.loss(p, batch, net_state, TRAIN, rng,
                                              probes=pr)
                    return loss, aux
                (loss, (outputs, costs, new_net)), (grads, probe_grads) = \
                    jax.value_and_grad(loss_fn, argnums=(0, 1),
                                       has_aux=True)(params, probes)
                grads = constrain_grads(grads)
                outputs = dict(outputs)
                for n, g in probe_grads.items():
                    outputs["__grad__" + n] = Argument(value=g)
            elif getattr(executor, "schedule", None) in ("1f1b",
                                                         "interleaved"):
                # hand-scheduled pipeline backward (1F1B, plain or over
                # interleaved virtual stages) — the executor returns grads
                # itself instead of sitting behind jax.value_and_grad;
                # net_state may carry loaded frozen-BN stats (embedded as
                # stage-body constants, never updated)
                loss, grads = executor.loss_and_grad(params, batch,
                                                     TRAIN, rng,
                                                     state=net_state)
                outputs, costs, new_net = {}, {}, net_state
                grads = constrain_grads(grads)
            else:
                def loss_fn(p):
                    loss, aux = executor.loss(p, batch, net_state, TRAIN, rng)
                    return loss, aux
                (loss, (outputs, costs, new_net)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                grads = constrain_grads(grads)
            # under a mesh GSPMD sums the gradients over the data shards
            # here, between the backward and the update; _jit_step's compile
            # options decide whether those all-reduces wait or run beside it
            bsz = _batch_size(batch)
            if remote:
                # parameter-server mode: the jitted step computes
                # gradients only — the optimizer applies SERVER-side
                # (ref: RemoteParameterUpdater — the update leaves the
                # gradient machine), so params/opt_state pass through
                # and the grads ride out for _dispatch_step to push
                new_params, new_opt = params, opt_state
            else:
                new_params, new_opt = updater.step(params, grads,
                                                   opt_state, bsz)
            partials = evaluators.batch_partials(outputs, batch)
            host_out = {n: outputs[n].flatten_image()
                        for n in evaluators.host_layer_names if n in outputs}
            if remote:
                return (new_params, new_opt, new_net, loss, partials,
                        host_out, grads)
            return new_params, new_opt, new_net, loss, partials, host_out

        return train_step

    def _build_fused_step(self, collect_outputs: bool = True):
        """Jitted k-step fused dispatch: `lax.scan` of the IDENTICAL
        per-batch train step over k batches stacked on a leading step axis,
        with pre-split per-step rng keys — one Python dispatch and one XLA
        program launch for k optimizer updates (the whole-loop-compilation
        execution model of arXiv:1810.09868).  Per-step losses, evaluator
        partials and host fetches come back stacked along the step axis so
        every host-side contract (the `_drain_losses` nonfinite check, the
        float64 evaluator accumulation, host evaluators) replays unchanged
        and the trajectory is bit-identical to the per-batch loop.  Used by
        train_one_pass(steps_per_dispatch=k) and benchmark(scan=True) —
        the benchmark's scan mode IS the production path.

        The scan length is the stacked leading dim: each distinct
        (k, batch-signature) pair compiles once, like the per-batch step
        compiles per length bucket.  grad_accum (num_batches_per_send_
        parameter > 1) needs no special casing: the accumulate-or-apply
        lax.cond lives inside the per-batch step and scans unchanged.

        collect_outputs=False drops the per-step partials/host fetches
        from the scan outputs — the benchmark scans HUNDREDS of steps in
        one dispatch and consumes only losses, so stacking [iters, ...]
        evaluator/host buffers (e.g. printer-evaluator layer outputs)
        would burn HBM for nothing.  The training path (small k) keeps
        them."""
        from jax import lax

        step_fn = self._train_step_fn

        def fused_step(params, opt_state, net_state, stacked, keys):
            def body(carry, xs):
                p, o, n = carry
                batch, key = xs
                p, o, n, loss, partials, host_out = step_fn(p, o, n, batch,
                                                            key)
                if not collect_outputs:
                    partials, host_out = {}, {}
                return (p, o, n), (loss, partials, host_out)

            (p, o, n), (losses, partials, host_outs) = lax.scan(
                body, (params, opt_state, net_state), (stacked, keys))
            return p, o, n, losses, partials, host_outs

        return self._jit_step(fused_step)

    def _build_test_step(self):
        executor, evaluators = self.executor, self.evaluators

        @jax.jit
        def test_step(params, net_state, batch, rng):
            loss, (outputs, costs, _) = executor.loss(params, batch, net_state, TEST, rng)
            partials = evaluators.batch_partials(outputs, batch)
            host_out = {n: outputs[n].flatten_image()
                        for n in evaluators.host_layer_names if n in outputs}
            return loss, partials, host_out

        return test_step

    # -- data -------------------------------------------------------------
    def _feeder(self, data_cfg: DataConfig, train: bool):
        if data_cfg.type == "ptsh":
            # binary shards via the native C++ loader (io/feeder.py)
            from paddle_tpu.io.feeder import ShardFeeder
            kwargs = (json.loads(data_cfg.load_data_args)
                      if data_cfg.load_data_args else {})
            return ShardFeeder(
                data_cfg.files, input_names=self.model.input_layer_names,
                batch_size=self.opt.batch_size, seed=self.seed,
                drop_last=train, shuffle=train,
                names=kwargs.get("names"))
        if data_cfg.type == "multi":
            # ratio-mixed sub-providers (ref: MultiDataProvider.{h,cpp})
            from paddle_tpu.data.provider import MultiProviderWrapper
            subs, sub_files = [], []
            for sub_cfg in data_cfg.sub_configs:
                p, f = load_provider(sub_cfg, fresh=True)
                subs.append(p)
                sub_files.append(f)
            prov = MultiProviderWrapper(subs, sub_files,
                                        ratios=data_cfg.data_ratios or None,
                                        is_test=not train)
            files: list[str] = []
        else:
            prov, files = load_provider(data_cfg)
        return DataFeeder(
            prov, files, input_names=self.model.input_layer_names,
            batch_size=self.opt.batch_size, seed=self.seed,
            drop_last=train, shuffle=None if train else False,
            constant_slots=data_cfg.constant_slots)

    def train_batches(self) -> Iterator[dict[str, Argument]]:
        assert self.config.data_config is not None, "config has no data source"
        feeder = self._feeder(self.config.data_config, True)
        if not self.config.data_config.async_load_data:
            # ref: --async_load_data=false / DataConfig.async_load_data —
            # assemble batches synchronously on the training thread
            return feeder.batches()
        return feeder.prefetched_batches()

    # -- loops ------------------------------------------------------------
    def _batch_signature(self, batch: dict[str, Argument]) -> tuple:
        """Shape/dtype signature of a batch plus the net_state structure —
        the retrace key of the compiled step.  The per-batch path uses it
        to keep compile time out of the barrier windows; the fused path
        (steps_per_dispatch > 1) groups consecutive same-signature batches
        by it (a length-bucketed feeder emits few distinct signatures)."""
        return (str(jax.tree.map(
                    lambda a: (jnp.shape(a), str(jnp.result_type(a))), batch)),
                str(jax.tree_util.tree_structure(self.net_state)))

    def _seen_sigs(self) -> set:
        seen = getattr(self, "_dispatch_sigs", None)
        if seen is None:
            seen = self._dispatch_sigs = set()
        return seen

    def _dispatch_step(self, batch: dict[str, Argument], key=None):
        """Dispatch one compiled train step (async — no host sync); returns
        (loss, partials, host_out) device values.  `key` overrides the
        internal rng split with a pre-split per-step key (the fused path's
        settling dispatch must consume the key already drawn for batch 0)."""
        with self._tracer.span("pt.train.stage", track="trainer"):
            if self.mesh is not None:
                from paddle_tpu.parallel.dp import shard_batch
                batch = shard_batch(self.mesh, batch)
            if key is None:
                self.rng, key = jax.random.split(self.rng)
            self._last_rng = key
            sig = self._batch_signature(batch)
        # any UNSEEN (batch-shape, net_state-structure) signature likely
        # retraces+recompiles — seconds of XLA work, not queue backpressure;
        # keep those dispatches out of the barrier timing windows (this
        # covers the first batch, every new length bucket, and the
        # net_state pytree change after batch 1)
        seen = self._seen_sigs()
        with self.barrier_stat.time_dispatch(windowed=sig in seen):
            out = self._call_step(
                "trainer.train_step", self._train_step, sig, self.params,
                self.opt_state, self.net_state, batch, key)
        (self.params, self.opt_state, new_net, loss, partials,
         host_out) = out[:6]
        if new_net:
            self.net_state = new_net
        if self._remote:
            # parameter-server round trip (ref: RemoteParameterUpdater::
            # finishBatch): fetch this batch's gradients to the host,
            # contribute them to every shard, and adopt the post-window
            # parameters (sync mode returns them every batch; async on
            # the num_batches_per_get_parameter cadence)
            grads = out[6]
            with global_stat.time("remoteUpdate"):
                t_c0 = time.perf_counter()
                grads_host = {n: np.asarray(jax.device_get(g))
                              for n, g in grads.items()}
                # the grad fetch blocks until the dispatched step's
                # gradients exist — its wall IS the window's compute
                # part; the updater folds it into the per-window
                # attribution and the window span
                fresh = self.updater.remote_step(
                    grads_host, _batch_size(batch),
                    compute=(t_c0, time.perf_counter() - t_c0))
            if fresh is not None:
                self.params = {n: jnp.asarray(np.asarray(v))
                               for n, v in fresh.items()}
        return loss, partials, host_out

    def _dispatch_fused(self, staged, keys, sig: tuple):
        """Dispatch ONE compiled k-step scan over a staged same-signature
        group (async); returns stacked (losses, partials, host_outs).  The
        first dispatch of a (k, signature) pair compiles — kept out of the
        `scan` barrier window like _dispatch_step's first-seen logic."""
        self._last_rng = keys[-1]
        fsig = ("fused", int(keys.shape[0]), sig)
        seen = self._seen_sigs()
        with self.barrier_stat.time_scan(windowed=fsig in seen):
            out = self._call_step(
                "trainer.fused_step", self._fused_step, fsig, self.params,
                self.opt_state, self.net_state, staged, keys)
        (self.params, self.opt_state, new_net, losses, partials, host_outs) = out
        if new_net:
            self.net_state = new_net
        self._n_fused_dispatches += 1
        return losses, partials, host_outs

    def _call_step(self, site: str, step, sig, *args):
        """Call a compiled step.  A signature's FIRST call, where the mesh
        gives the step compile options, leaves the arguments' shapes with
        `_step_collectives` (before the call donates the arrays); nothing
        is lowered or read here."""
        seen = self._seen_sigs()
        if sig not in seen:
            seen.add(sig)
            if self._step_collectives is not None:
                self._step_collectives.note(site, step, args)
        return step(*args)

    def _validate_batch(self, batch: dict[str, Argument]) -> None:
        """Clear errors for the common feed mistakes BEFORE tracing: a
        missing/misspelled key would otherwise silently skip downstream
        layers (the generation-path skip in builder.forward) and surface as
        'model has no cost layers'; out-of-range ids would gather garbage
        and train on NaNs.  Host-side numpy checks only — device arrays are
        not synced."""
        data_layers = self._data_layers
        missing = sorted(set(data_layers) - set(batch))
        if missing:
            raise KeyError(
                f"batch is missing feed(s) for data layer(s) {missing}; "
                f"fed keys: {sorted(batch)}")
        unknown = sorted(set(batch) - set(data_layers))
        if unknown:
            raise KeyError(
                f"batch feeds unknown key(s) {unknown} — not data layers "
                f"(expected: {sorted(data_layers)}); a feed shadowing a "
                f"computed layer would silently override it")
        sizes = {}
        for name, arg in batch.items():
            if arg.value is None and arg.ids is None:
                raise ValueError(f"feed {name!r} carries neither dense "
                                 f"values nor ids")
            sizes[name] = arg.batch_size
            cfg = data_layers[name]
            ids = arg.ids
            if (isinstance(ids, np.ndarray) and arg.sparse_dim == 0
                    and cfg.size > 0 and ids.size):
                hi, lo = int(ids.max()), int(ids.min())
                if hi >= cfg.size or lo < 0:
                    raise ValueError(
                        f"feed {name!r}: id {hi if hi >= cfg.size else lo} "
                        f"out of range for data layer size {cfg.size} — "
                        f"this would gather garbage and train on NaNs")
        if len(set(sizes.values())) > 1:
            raise ValueError(f"feeds disagree on batch size: {sizes}")

    def train_one_batch(self, batch: dict[str, Argument]):
        """(ref: TrainerInternal::trainOneBatch).

        Returns the step's loss as a DEVICE scalar — no host sync.  Under
        --detect_nan (the reference's feenableexcept analog,
        TrainerMain.cpp:97) the loss is fetched and checked every batch with
        layer-level localisation; otherwise losses buffer on device and are
        bulk-checked every nonfinite_check_period batches, so dispatch
        pipelines with device compute."""
        self._validate_batch(batch)
        if self.sparse_stats is not None:
            self.sparse_stats.probe_batch(batch)
        loss, partials, host_out = self._dispatch_step(batch)
        self._acc = self.evaluators.accumulate(getattr(self, "_acc", {}), partials)
        if self.evaluators.host_configs:
            if not hasattr(self, "_host_acc") or self._host_acc is None:
                self._host_acc = self.evaluators.new_host_state()
            self.evaluators.host_update(self._host_acc, host_out)
        return self._account_loss(loss, batch)

    def _account_loss(self, loss, batch: dict[str, Argument]):
        """Per-step loss bookkeeping shared by the per-batch and fused
        loops: under --detect_nan fetch+check immediately; otherwise buffer
        the device scalar and bulk-drain every nonfinite_check_period."""
        if FLAGS.detect_nan:
            loss_f = float(loss)
            if not np.isfinite(loss_f):
                # layer-level localisation, the gLayerStackTrace-on-crash
                # analog (ref: utils/CustomStackTrace.h;
                # NeuralNetwork.cpp:280-286)
                raise FloatingPointError(
                    f"non-finite loss {loss_f}; {self.diagnose_nonfinite(batch)}")
            self._drained_cost += loss_f
            return loss_f
        self._last_batch = batch
        self._loss_buf.append(loss)
        if len(self._loss_buf) >= max(int(FLAGS.nonfinite_check_period), 1):
            self._drained_cost += self._drain_losses()
        return loss

    def _drain_losses(self) -> float:
        """One host sync for all buffered device losses: bulk finiteness
        check + their sum (for cost accounting)."""
        if not self._loss_buf:
            return 0.0
        with self.barrier_stat.time_sync():
            losses = np.asarray(jax.device_get(jnp.stack(self._loss_buf)))
        n = len(self._loss_buf)
        self._loss_buf.clear()
        if not np.isfinite(losses).all():
            bad = int(np.flatnonzero(~np.isfinite(losses))[0])
            diag = (self.diagnose_nonfinite(self._last_batch)
                    if self._last_batch is not None else "")
            raise FloatingPointError(
                f"non-finite loss {losses[bad]} ({n - bad - 1} batches before "
                f"the last dispatched; run with --detect_nan for exact "
                f"per-batch localisation); {diag}")
        return float(losses.sum())

    def train_one_pass(self, batches: Optional[Iterator] = None,
                       log_period: int = 0,
                       steps_per_dispatch: Optional[int] = None
                       ) -> dict[str, float]:
        """(ref: Trainer::trainOnePass).

        steps_per_dispatch=k > 1 (default: --steps_per_dispatch) runs the
        pass through the fused dispatch path: consecutive same-signature
        batches stack into k-groups, each executed as ONE compiled k-step
        lax.scan while a background thread device-stages the NEXT group
        (see _train_one_pass_fused).  Trajectory, evaluator results and
        the nonfinite-check contract are identical to the k=1 loop."""
        t0 = time.time()
        self._acc = self.evaluators.new_accumulator()
        self._host_acc = self.evaluators.new_host_state() if \
            self.evaluators.host_configs else None
        self._drained_cost = 0.0
        self._loss_buf.clear()
        if batches is None:
            batches = self.train_batches()
        k = int(FLAGS.steps_per_dispatch if steps_per_dispatch is None
                else steps_per_dispatch)
        if k > 1 and self._remote:
            # the fused scan hosts the optimizer INSIDE the compiled
            # dispatch; remote mode applies it server-side per batch —
            # the two cannot compose, and the sync barrier is per batch
            # anyway, so the scan would buy nothing
            log.warning("remote updater forces steps_per_dispatch=1 "
                        "(the pserver barrier is per batch)")
            k = 1
        if k > 1 and FLAGS.detect_nan:
            # --detect_nan promises PER-BATCH halting + localisation with
            # the failing step's rng/params; a fused group would apply the
            # remaining k-1 updates before the check and replay diagnosis
            # with the group's last key.  Debug mode wins over dispatch
            # overhead: fall back to the per-batch loop.
            log.warning("--detect_nan forces steps_per_dispatch=1 "
                        "(per-batch nonfinite localisation)")
            k = 1
        if k > 1:
            return self._train_one_pass_fused(batches, log_period, k, t0)
        n_batches, n_samples = 0, 0
        stats_period = FLAGS.show_parameter_stats_period
        for batch in self._timed_batches(batches):
            with self._step_span("trainOneBatch"):
                self.train_one_batch(batch)
            n_batches += 1
            n_samples += _batch_size(batch)
            if log_period and n_batches % log_period == 0:
                self._log_progress(n_batches)
            if stats_period and n_batches % stats_period == 0:
                self.log_param_stats()
        return self._finish_pass_stats(t0, n_batches, n_samples)

    def _timed_batches(self, batches):
        """`batches`, with the wait for each under `pt.train.next_batch`
        (the trainer loop's input wait, on the profiler's clock)."""
        it = iter(batches)
        end = object()
        while True:
            with self._tracer.span("pt.train.next_batch", track="trainer"):
                batch = next(it, end)
            if batch is end:
                return
            yield batch

    def _step_span(self, stat: str):
        """`pt.train.step` around one train step (or one fused k-group),
        feeding the `global_stat` timer of the same site from its clock."""
        return self._tracer.span("pt.train.step", track="trainer",
                                 sink=global_stat.get(stat).add)

    def _log_progress(self, n_batches: int) -> None:
        self._drained_cost += self._drain_losses()
        log.info("pass %d batch %d: cost=%.5f %s", self.pass_id, n_batches,
                 self._drained_cost / n_batches,
                 _fmt(self.evaluators.finalize(self._acc)))
        if self.mesh is not None:
            log.info("barrier: %s", self.barrier_stat.render())

    def _finish_pass_stats(self, t0: float, n_batches: int,
                           n_samples: int) -> dict[str, float]:
        self._drained_cost += self._drain_losses()
        total_cost = self._drained_cost
        self.opt_state = self.updater.finish_pass(self.opt_state)
        stats = self.evaluators.finalize(self._acc)
        if self._host_acc is not None:
            stats.update(self.evaluators.finalize_host(self._host_acc))
        dt = time.time() - t0
        stats.update(cost=total_cost / max(n_batches, 1), batches=n_batches,
                     samples=n_samples, seconds=dt,
                     samples_per_sec=n_samples / dt if dt > 0 else 0.0)
        if self._remote and hasattr(self.updater, "pass_timing"):
            # remote-updater attribution riding the pass row: where this
            # pass's wall went (push/barrier_wait/pull/apply ms + async
            # staleness rejects) — metrics.jsonl and TRAIN_JSON inherit
            # these next to the throughput gauges, so a distributed run's
            # single-file pass history answers "where did my
            # scaling_efficiency go" without a trace viewer
            stats.update(self.updater.pass_timing())
        log.info("pass %d done: %s", self.pass_id, _fmt(stats))
        if self._tracer.enabled:
            self._tracer.add("train_pass", time.perf_counter() - dt, dt,
                             track="trainer",
                             attrs={"pass": self.pass_id,
                                    "batches": n_batches})
        self.pass_id += 1
        self._m_pass.set(self.pass_id)             # = passes completed
        self._m_cost.set(stats["cost"])
        self._m_sps.set(stats["samples_per_sec"])
        if n_batches:
            self._m_batches.inc(n_batches)
            self._m_samples.inc(n_samples)
        return stats

    # -- fused k-step dispatch (--steps_per_dispatch) ---------------------
    def _net_state_settled(self, batch: dict[str, Argument], key) -> bool:
        """True if dispatching `batch` cannot change the net_state pytree
        STRUCTURE.  A stateful model (training-mode batch norm) grows its
        state on the first-ever dispatch; a lax.scan carry must be
        structure-stable, so the fused path routes that one batch through
        the per-batch step first — exactly what the k=1 loop's batch 0
        does.  Shape-level tracing only (jax.eval_shape); cached per batch
        signature."""
        sig = self._batch_signature(batch)
        if sig in self._settled_sigs:
            return True
        try:
            out = jax.eval_shape(self._train_step_fn, self.params,
                                 self.opt_state, self.net_state, batch, key)
        except Exception:
            return False     # conservatively settle via a per-batch dispatch
        new_net = out[2]
        settled = (not new_net) or (
            jax.tree_util.tree_structure(new_net)
            == jax.tree_util.tree_structure(self.net_state))
        if settled:
            self._settled_sigs.add(sig)
        return settled

    def _stage_group(self, group):
        """DeviceDoubleBuffer place_fn: stack a same-signature k-group on a
        leading step axis and move it to device (batch dim sharded over
        `data` under a mesh) — runs on the prefetch thread, so the H2D
        transfer of group i+1 overlaps the scan of group i."""
        host_batches, keys, sig = group
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *host_batches)
        if self.mesh is not None:
            from paddle_tpu.parallel.dp import stage_stacked_batch
            stacked = stage_stacked_batch(self.mesh, stacked)
        else:
            stacked = jax.device_put(stacked)
        return stacked, jnp.stack(keys), host_batches, sig

    def _train_one_pass_fused(self, batches: Iterator, log_period: int,
                              k: int, t0: float) -> dict[str, float]:
        """Fused pass body: k train steps per compiled dispatch + device
        double-buffered input staging.

        Parity with the k=1 loop is exact, not approximate:
          - batches group by the _batch_signature length-bucket key but
            only CONSECUTIVE same-signature batches fuse (a group flushes
            early on signature change), so optimizer updates apply in
            arrival order;
          - per-step rng keys are pre-split from self.rng in arrival
            order — step i consumes the very key the k=1 loop would;
          - grad_accum (optim/updater.py) rides inside the scanned step;
          - per-step losses come back stacked and feed the same
            _loss_buf/_drain_losses cadence, and evaluator partials
            accumulate per step in the same float64 order.
        Dispatch count for n same-signature batches is exactly ceil(n/k)
        (+1 per-batch settling dispatch for stateful models, mirroring the
        k=1 loop's structure-changing first batch)."""
        from paddle_tpu.data.feeder import DeviceDoubleBuffer
        stats_period = FLAGS.show_parameter_stats_period
        n_batches, n_samples = 0, 0

        def host_groups():
            pending: list = []
            keys: list = []
            sig = None
            for batch in batches:
                self._validate_batch(batch)
                if self.sparse_stats is not None:
                    self.sparse_stats.probe_batch(batch)
                s = self._batch_signature(batch)
                if pending and (s != sig or len(pending) == k):
                    yield pending, keys, sig
                    pending, keys = [], []
                sig = s
                self.rng, sub = jax.random.split(self.rng)
                pending.append(batch)
                keys.append(sub)
            if pending:
                yield pending, keys, sig

        groups = host_groups()
        first = next(groups, None)
        if first is None:
            return self._finish_pass_stats(t0, 0, 0)
        if not self._net_state_settled(first[0][0], first[1][0]):
            b0, key0 = first[0][0], first[1][0]
            with self._step_span("trainOneBatch"):
                loss, partials, host_out = self._dispatch_step(b0, key=key0)
                self._acc = self.evaluators.accumulate(self._acc, partials)
                if self._host_acc is not None:
                    self.evaluators.host_update(self._host_acc, host_out)
                self._account_loss(loss, b0)
            n_batches += 1
            n_samples += _batch_size(b0)
            first = (first[0][1:], first[1][1:], first[2])

        def chain():
            if first[0]:
                yield first
            yield from groups

        staged = DeviceDoubleBuffer(chain(), self._stage_group,
                                    timer=self.barrier_stat.time_h2d)
        try:
            for stacked, keys, host_batches, sig in \
                    self._timed_batches(staged):
                j = len(host_batches)
                with self._step_span("trainKSteps"):
                    losses, partials, host_outs = self._dispatch_fused(
                        stacked, keys, sig)
                self._acc = self.evaluators.accumulate_stacked(
                    self._acc, partials, j)
                if self._host_acc is not None and host_outs:
                    host_np = jax.tree.map(np.asarray,
                                           jax.device_get(host_outs))
                    for i in range(j):
                        self.evaluators.host_update(
                            self._host_acc,
                            jax.tree.map(lambda a: a[i], host_np))
                for i in range(j):
                    self._account_loss(losses[i], host_batches[i])
                n_batches += j
                n_samples += sum(_batch_size(b) for b in host_batches)
                if log_period and (n_batches // log_period) != \
                        ((n_batches - j) // log_period):
                    self._log_progress(n_batches)
                if stats_period and (n_batches // stats_period) != \
                        ((n_batches - j) // stats_period):
                    self.log_param_stats()
        finally:
            # a mid-pass exception (nonfinite drain, feed validation) must
            # not leave the producer thread blocked holding staged groups
            staged.close()
        return self._finish_pass_stats(t0, n_batches, n_samples)

    def train(self, num_passes: int = 1, log_period: int = 100,
              save_dir: Optional[str] = None, keep_last: int = 0,
              saving_period: int = 1) -> list[dict]:
        """Full training job (ref: Trainer::train).  A checkpoint is written
        every `saving_period` passes (--saving_period) and after the last
        one; the metrics row is appended every pass."""
        history = []
        for i in range(num_passes):
            stats = self.train_one_pass(log_period=log_period)
            if self.config.test_data_config is not None:
                test_stats = self.test()
                log.info("pass %d test: %s", self.pass_id - 1, _fmt(test_stats))
                stats["test"] = test_stats
            if save_dir:
                if (i + 1) % max(int(saving_period), 1) == 0 \
                        or i + 1 == num_passes:
                    self.save(save_dir, keep_last=keep_last)
                # the metrics sink rides next to the checkpoints: one
                # registry snapshot per pass, JSON-lines, append-only
                self.append_metrics(save_dir, extra=stats)
            history.append(stats)
        return history

    def append_metrics(self, save_dir: str, extra: Optional[dict] = None
                       ) -> str:
        """Append one metrics record to `<save_dir>/metrics.jsonl` — the
        trainer-side counterpart of the serving server's `metrics` frame:
        {ts, pass_id, extra scalar pass stats, metrics: registry snapshot
        (progress gauges + host-phase/barrier quantiles)}.  Process 0
        only, like checkpoint writes."""
        if jax.process_index() != 0:
            return ""
        import datetime

        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, "metrics.jsonl")
        rec = {"ts": datetime.datetime.now(datetime.timezone.utc)
                       .isoformat(timespec="seconds"),
               "pass_id": self.pass_id}
        for k, v in (extra or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                rec[k] = v
        rec["metrics"] = self.metrics.snapshot()
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        return path

    def test(self, batches: Optional[Iterator] = None) -> dict[str, float]:
        """(ref: Tester::testOnePeriod)."""
        if batches is None:
            if self.config.test_data_config is None:
                raise ValueError(
                    "test needs a test data source, but this config "
                    "declares none — add define_py_data_sources2("
                    "test_list=...) to the config, or pass batches= "
                    "explicitly (ref: --job=test requires a test source, "
                    "TrainerMain.cpp)")
            batches = self._feeder(self.config.test_data_config, False).batches()
        params = self.updater.averaged_params(self.params, self.opt_state)
        acc = self.evaluators.new_accumulator()
        host_acc = self.evaluators.new_host_state() if \
            self.evaluators.host_configs else None
        total, n = 0.0, 0
        self.rng, sub = jax.random.split(self.rng)
        with self._tracer.span("eval", track="trainer"):
            for batch in batches:
                loss, partials, host_out = self._test_step(
                    params, self.net_state, batch, sub)
                bsz = _batch_size(batch)
                total += float(loss) * bsz
                n += bsz
                acc = self.evaluators.accumulate(acc, partials)
                if host_acc is not None:
                    self.evaluators.host_update(host_acc, host_out)
        stats = self.evaluators.finalize(acc)
        if host_acc is not None:
            stats.update(self.evaluators.finalize_host(host_acc))
        stats["cost"] = total / max(n, 1)
        return stats

    # -- diagnostics ------------------------------------------------------
    def param_stats(self) -> dict[str, dict[str, float]]:
        """Per-parameter health dump (ref: TrainerInternal.cpp:187-217
        showParameterStats: avg/max abs value logged every
        show_parameter_stats_period batches)."""
        out = {}
        for name, v in self.params.items():
            a = np.abs(np.asarray(jax.device_get(v)))
            out[name] = {"shape": tuple(v.shape), "mean_abs": float(a.mean()),
                         "max_abs": float(a.max())}
        return out

    def log_param_stats(self) -> None:
        for name, s in self.param_stats().items():
            log.info("param %s shape=%s mean_abs=%.3e max_abs=%.3e",
                     name, s["shape"], s["mean_abs"], s["max_abs"])

    def diagnose_nonfinite(self, batch: dict[str, Argument],
                           rng: Optional[jax.Array] = None) -> str:
        """Layer-level NaN/Inf localisation — the analog of the reference's
        gLayerStackTrace dump on crash (ref: utils/CustomStackTrace.h;
        NeuralNetwork.cpp:241,280-286): re-run forward uncompiled and report
        the first layer whose output is non-finite.

        The jitted train step donates its param buffers, so this runs on the
        POST-update parameters — the report says which case applies."""
        if rng is None:
            rng = getattr(self, "_last_rng", None)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        outputs, costs, _ = self.executor.forward(
            self.params, batch, self.net_state, TRAIN, rng)
        for l in self.model.layers:
            arg = outputs.get(l.name)
            if arg is None or arg.value is None:
                continue
            a = np.asarray(jax.device_get(arg.value))
            if not np.isfinite(a).all():
                return (f"first non-finite output at layer {l.name!r} "
                        f"(type={l.type}): nan={np.isnan(a).sum()} "
                        f"inf={np.isinf(a).sum()} of {a.size} "
                        f"(forward re-run with post-update parameters)")
        for cname, c in costs.items():
            if not np.isfinite(np.asarray(jax.device_get(c))).all():
                return (f"non-finite cost {cname!r} with finite layer outputs "
                        f"(forward re-run with post-update parameters)")
        return ("forward with post-update parameters is finite — the "
                "non-finite value arose in the gradient/optimizer update of "
                "the failing step")

    def check_gradient(self, batch: dict[str, Argument],
                       epsilon: float = 1e-3,
                       max_entries: int = 4,
                       refine_threshold: float = 0.02) -> dict[str, float]:
        """Finite-difference gradient check on a real batch — the --job=
        checkgrad mode (ref: Trainer::checkGradient, Trainer.cpp:303+):
        perturb sampled entries of every parameter, compare numeric
        d(loss)/d(w) against the analytic gradient.  Returns per-parameter
        max relative error.

        Two-stage precision: a fast fp32 screen over every parameter, then
        (CPU backends only) a float64 re-adjudication of just the
        parameters the screen flagged above `refine_threshold`.  fp32
        central differences carry multi-ulp rounding noise through a deep
        net — on the VGG configs the noise floor sits around |grad| ~1e-2,
        spuriously flagging every smaller-gradient parameter — while f64
        (the test_layer_grad.py pattern) is noise-free but ~100x slower,
        so it only re-checks the screen's failures.  TPU has no f64; there
        the fp32 noise-aware denominator is the whole story."""
        errors = self._checkgrad_pass(batch, epsilon, max_entries,
                                      x64=False)
        if jax.default_backend() == "cpu":
            flagged = [n for n, e in errors.items() if e > refine_threshold]
            if flagged:
                log.info("checkgrad: re-adjudicating %d flagged parameters "
                         "in float64: %s", len(flagged), flagged)
                errors.update(self._checkgrad_pass(
                    batch, epsilon, max_entries, x64=True, names=flagged,
                    detect_kinks=True))
        return errors

    def _checkgrad_pass(self, batch, epsilon, max_entries, x64: bool,
                        names=None, detect_kinks: bool = False
                        ) -> dict[str, float]:
        import contextlib

        rng = jax.random.PRNGKey(7)
        # full precision: a central difference of 1e-3 is below bf16
        # resolution, so the check must bypass any mixed-precision cast
        saved_dtype = self.executor.compute_dtype
        self.executor.compute_dtype = ""
        try:
            from paddle_tpu.utils.jax_compat import enable_x64
            with (enable_x64() if x64 else contextlib.nullcontext()):
                if x64:
                    def to_f64(x):
                        x = jnp.asarray(np.asarray(jax.device_get(x)))
                        if jnp.issubdtype(x.dtype, jnp.floating):
                            return x.astype(jnp.float64)
                        return x
                    params = {k: to_f64(v) for k, v in self.params.items()}
                    cbatch = jax.tree.map(to_f64, batch)
                    state = jax.tree.map(to_f64, self.net_state)
                else:
                    # no dtype change: keep the arrays (and any sharding)
                    # exactly as training holds them
                    params, cbatch, state = self.params, batch, self.net_state
                # jit once: every perturbed evaluation reuses the executable
                loss_fn = jax.jit(lambda p: self.executor.loss(
                    p, cbatch, state, TEST, rng)[0])
                if getattr(self.executor, "schedule", None) in (
                        "1f1b", "interleaved"):
                    # audit the grads TRAINING actually uses: the hand-
                    # scheduled loss_and_grad backward, not the autodiff of
                    # loss() that only the gpipe schedule trains with
                    _, grads = jax.jit(lambda p: self.executor.loss_and_grad(
                        p, cbatch, TEST, rng))(params)
                else:
                    grads = jax.jit(jax.grad(lambda p: self.executor.loss(
                        p, cbatch, state, TEST, rng)[0]))(params)
                return self._check_gradient_inner(loss_fn, grads, epsilon,
                                                  max_entries, params, names,
                                                  detect_kinks)
        finally:
            self.executor.compute_dtype = saved_dtype

    def _check_gradient_inner(self, loss_fn, grads, epsilon,
                              max_entries, params=None,
                              names=None,
                              detect_kinks=False) -> dict[str, float]:
        errors: dict[str, float] = {}
        params = self.params if params is None else params
        nrng = np.random.default_rng(0)
        L0 = float(loss_fn(params)) if detect_kinks else 0.0
        for name, w in params.items():
            if name in self.executor.static_param_names:
                continue
            if names is not None and name not in names:
                # keep drawing from nrng so the SAME entries are sampled
                # whether or not the parameter is in this pass's subset
                # (the f64 re-adjudication must probe what fp32 flagged);
                # .size reads shape metadata — no device transfer
                size = int(np.size(w))
                nrng.choice(max(size, 1), size=min(max_entries, size),
                            replace=False)
                continue
            flat = np.asarray(jax.device_get(w)).reshape(-1)
            gflat = np.asarray(jax.device_get(grads[name])).reshape(-1)
            idxs = nrng.choice(flat.size, size=min(max_entries, flat.size),
                               replace=False)
            worst = 0.0
            n_validated = n_kink = 0
            for i in idxs:
                eps_i = epsilon

                def fd_sides(h):
                    out = []
                    for sign in (+1, -1):
                        pert = flat.copy()
                        pert[i] += sign * h
                        p2 = dict(params)
                        p2[name] = jnp.asarray(pert.reshape(w.shape))
                        out.append(float(loss_fn(p2)))
                    return out

                sides = fd_sides(eps_i)
                if detect_kinks:
                    # a ReLU-style kink inside [w-h, w+h] makes the central
                    # difference measure the subgradient average, not the
                    # analytic one-sided derivative — mismatched forward/
                    # backward one-sided differences expose it (only
                    # meaningful in the f64 pass, where FD noise ~1e-12).
                    # First response: shrink h 100x — the kink usually
                    # falls outside the tighter interval and the entry
                    # stays validated; only a point RIGHT AT the kink is
                    # skipped.
                    def kinked(s, h):
                        fwd = (s[0] - L0) / h
                        bwd = (L0 - s[1]) / h
                        return abs(fwd - bwd) > 0.1 * max(
                            abs(fwd), abs(bwd), 1e-12), fwd, bwd
                    bad, fwd, bwd = kinked(sides, eps_i)
                    if bad:
                        eps_i = epsilon / 100.0
                        sides = fd_sides(eps_i)
                        bad, fwd, bwd = kinked(sides, eps_i)
                    if bad:
                        n_kink += 1
                        log.info(
                            "checkgrad %s[%d]: straddles a non-smooth point "
                            "even at h=%.1e (one-sided fwd %.3e vs bwd "
                            "%.3e) — entry skipped", name, i, eps_i, fwd,
                            bwd)
                        continue
                numeric = (sides[0] - sides[1]) / (2 * eps_i)
                n_validated += 1
                # central differences cancel catastrophically once the true
                # gradient drops below the loss's own rounding noise —
                # measured on the 13-layer VGG configs at ~up to 100 ulp of
                # |L| per evaluation (each perturbation re-rounds the whole
                # forward, not just the final sum), i.e. an absolute FD
                # resolution of ~100*|L|*dtype_eps/(2h).  fp32 screens
                # clamp the denominator there: gradients under the floor
                # carry no finite-difference signal either way (rel_err ~1
                # spuriously, the pre-r5 behavior), while a genuinely wrong
                # gradient of visible magnitude still flags — and anything
                # that DOES flag is re-adjudicated in f64, where the floor
                # is ~1e-11 and the check is strict.
                noise = (abs(sides[0]) + abs(sides[1])) * \
                    float(np.finfo(flat.dtype).eps) / (2 * eps_i)
                denom = max(abs(numeric), abs(gflat[i]), 100.0 * noise, 1e-8)
                worst = max(worst, abs(numeric - gflat[i]) / denom)
            if detect_kinks and n_validated == 0 and n_kink > 0:
                # every sampled entry sat exactly on a non-smooth point —
                # the refine pass ADJUDICATED NOTHING.  Omit the key so
                # check_gradient's errors.update() keeps the fp32 screen's
                # flagged value (a flagged-but-unadjudicated parameter must
                # still fail the --job=checkgrad exit-code contract, not
                # exit 0 on a silent 0.0; ADVICE r5)
                log.warning(
                    "checkgrad %s: 0 of %d sampled entries validated (all "
                    "straddle non-smooth points) — inconclusive; the fp32 "
                    "screen's flagged error stands for this parameter",
                    name, n_kink)
                continue
            errors[name] = worst
            log.info("checkgrad %s: max_rel_err=%.3e", name, worst)
        return errors

    def benchmark(self, batches: Iterator, warmup: int = 3, iters: int = 30,
                  scan: bool = False) -> dict:
        """--job=time analog (ref: TrainerBenchmark.cpp).

        Default mode dispatches the jitted step per batch asynchronously —
        no per-step host sync — and blocks once at the end; this includes
        host dispatch + any host->device input transfer in the measured
        time, like the reference's end-to-end --job=time loop.

        scan=True stages all batches in device memory and runs the SAME
        per-batch training step inside one `lax.scan` — a single dispatch
        for the whole run, via the PRODUCTION fused-dispatch path
        (_build_fused_step, what train_one_pass(steps_per_dispatch=k)
        executes).  This is the TPU-native shape of a production input
        pipeline (data prefetched to HBM ahead of compute) and measures
        pure device throughput.

        Every step's loss is checked finite after the final sync (a mid-run
        divergence fails the benchmark rather than being silently timed).
        """
        batch_list = []
        it = iter(batches)
        for _ in range(warmup + iters):
            try:
                batch_list.append(next(it))
            except StopIteration:
                break
        n_samples = sum(_batch_size(b) for b in batch_list[warmup:])
        if scan:
            if self._remote:
                raise ValueError(
                    "benchmark(scan=True) hosts the optimizer inside one "
                    "compiled dispatch — incompatible with the remote "
                    "(parameter-server) updater; benchmark with "
                    "scan=False or tools/train_dist.py")
            return self._benchmark_scan(batch_list, warmup, n_samples)
        for b in batch_list[:warmup]:
            self._dispatch_step(b)
        jax.block_until_ready(self.params)

        t0 = time.time()
        losses = []
        for b in batch_list[warmup:]:
            loss, _, _ = self._dispatch_step(b)
            losses.append(loss)
        # a real device->host fetch is the sync point
        lo = np.asarray(jax.device_get(jnp.stack(losses))) if losses else None
        dt = time.time() - t0
        if lo is not None:
            assert np.isfinite(lo).all(), \
                f"non-finite loss at bench step {int(np.flatnonzero(~np.isfinite(lo))[0])}"
        return {"seconds": dt, "samples": n_samples,
                "samples_per_sec": n_samples / dt if dt else 0.0,
                "batches": len(batch_list) - warmup}

    def _benchmark_scan(self, batch_list: list, warmup: int, n_samples: int) -> dict:
        """Scan-of-steps benchmark body: one XLA dispatch for all iters —
        DELEGATES to the production fused-dispatch program (_build_fused_
        step), so the benchmark measures exactly what train_one_pass(
        steps_per_dispatch=k) executes: same scanned step, same pre-split
        per-step key contract, same staging layout."""
        iters = len(batch_list) - warmup
        assert iters > 0, "need at least one timed iteration"
        # stage on device, stacked along a leading step axis; on a mesh the
        # per-batch axis (dim 1) is sharded over `data`, matching what
        # _dispatch_step's shard_batch does per step
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list[warmup:])
        if self.mesh is not None:
            from paddle_tpu.parallel.dp import stage_stacked_batch
            stacked = stage_stacked_batch(self.mesh, stacked)
        else:
            stacked = jax.device_put(stacked)
        jax.block_until_ready([a.value if a.value is not None else a.ids
                               for a in stacked.values()])

        for b in batch_list[:warmup]:
            self._dispatch_step(b)
        jax.block_until_ready(self.params)
        keys = []
        for _ in range(iters):
            self.rng, sub = jax.random.split(self.rng)
            keys.append(sub)
        keys = jnp.stack(keys)

        def run():
            (self.params, self.opt_state, new_net, losses, _, _) = \
                self._fused_step_losses(self.params, self.opt_state,
                                        self.net_state, stacked, keys)
            if new_net:
                self.net_state = new_net
            return losses

        # one untimed warmup EXECUTION (which also compiles): forces the
        # staged batches' host->device transfers to actually complete (a
        # device->host fetch is the sync point) and settles donation buffers
        np.asarray(jax.device_get(run()))

        t0 = time.time()
        losses = run()
        # the loss fetch is the honest end-of-run sync point
        lo = np.asarray(jax.device_get(losses))
        dt = time.time() - t0
        assert np.isfinite(lo).all(), \
            f"non-finite loss at bench step {int(np.flatnonzero(~np.isfinite(lo))[0])}"
        return {"seconds": dt, "samples": n_samples,
                "samples_per_sec": n_samples / dt if dt else 0.0,
                "batches": iters}


    # -- checkpointing ----------------------------------------------------
    def save(self, save_dir: str, keep_last: int = 0) -> str:
        """(ref: ParamUtil::saveParametersOnePass; only trainer 0 saves —
        here process 0 under multi-host jax.distributed)."""
        # every process participates in the gather of non-addressable
        # shards (ZeRO-1 slots span hosts); only process 0 writes
        params = _host_tree(self.params)
        opt_state = _host_tree(self.opt_state)
        net_state = _host_tree(self.net_state)
        if jax.process_index() != 0:
            return ""
        # pass_id 0 = nothing completed yet: label the snapshot pass-init
        # instead of clamping into the pass-00000 slot (which the real
        # end-of-pass-0 save owns; resuming from a clamped one would
        # silently skip training pass 0)
        return ckpt.save_checkpoint(
            save_dir, self.pass_id - 1, params, opt_state, net_state,
            config_json=self.config.to_json(), keep_last=keep_last,
            rng=np.asarray(self.rng))

    def load(self, path: str) -> None:
        """(ref: ParamUtil::loadParameters / --init_model_path)."""
        data = ckpt.load_checkpoint(path)
        loaded = data["params"]
        ref_fmt = data.get("reference_format", False)
        self.params = dict(self.params)
        for name in self.params:
            assert name in loaded, f"checkpoint missing parameter {name!r}"
            cur = self.params[name]
            arr = jnp.asarray(loaded[name])
            if ref_fmt:
                # reference files are flat fp32 (Parameter.cpp:309-313):
                # restore this model's shape/dtype
                assert arr.size == cur.size, (
                    f"parameter {name!r}: reference file has {arr.size} "
                    f"values, model expects {cur.size}")
                arr = arr.reshape(cur.shape).astype(cur.dtype)
            self.params[name] = arr
        # rebuild pruning masks from the loaded magnitudes (the reference
        # reloads its mask file on --init_model_path too)
        self.params = self.updater.apply_init_hooks(self.params)
        if data.get("opt"):
            # rebuild optimizer state with loaded leaves where shapes match
            tmpl = self.updater.init_state(self.params)
            self.opt_state = _merge_state(tmpl, data["opt"])
        if data.get("net"):
            self.net_state = jax.tree.map(jnp.asarray, data["net"])
        if data.get("rng") is not None:
            # continue the PRNG stream where the saving run left it —
            # resume is then exact for stochastic (dropout) models too
            self.rng = jnp.asarray(data["rng"])
        if self.mesh is not None:
            # restore mesh placement (incl. ZeRO-1 slot sharding) — the
            # loaded host arrays would otherwise train replicated, silently
            # undoing the sharded-optimizer memory saving
            from paddle_tpu.parallel.dp import shard_train_objects
            self.params, self.opt_state = shard_train_objects(
                self.mesh, self.model, self.params, self.opt_state,
                shard_opt=self.opt.shard_optimizer_state,
                zero_stage=self.zero_stage)
        if "pass_id" in data:
            # continue the pass numbering: the snapshot is named after its
            # last completed pass, so the resumed run trains (and next
            # saves) pass N+1 instead of colliding with pass-00000
            self.pass_id = data["pass_id"] + 1


def _host_tree(tree):
    """Device -> host copy that works for arrays spanning non-addressable
    devices (multi-host ZeRO-1 slot shards): gather those across processes;
    plain device_get for everything else."""
    def fetch(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(jax.device_get(x))
    return jax.tree.map(fetch, tree)


def _merge_state(template, loaded):
    if isinstance(template, dict):
        return {k: _merge_state(v, loaded.get(k)) if loaded and k in loaded else v
                for k, v in template.items()}
    if loaded is None:
        return template
    arr = jnp.asarray(loaded)
    return arr if arr.shape == jnp.shape(template) else template


def _batch_size(batch: dict[str, Argument]) -> int:
    for arg in batch.values():
        return int(arg.batch_size)
    return 0


def _fmt(stats: dict) -> str:
    parts = []
    for k, v in stats.items():
        if isinstance(v, float):
            parts.append(f"{k}={v:.5g}")
        elif isinstance(v, (int, np.integer)):
            parts.append(f"{k}={v}")
    return " ".join(parts)
