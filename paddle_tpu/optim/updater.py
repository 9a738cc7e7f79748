"""ParameterUpdater — applies optimizer + schedule + regularization.

TPU-native collapse of the reference's updater family (ref:
paddle/trainer/ParameterUpdater.h SgdLocalUpdater,
ThreadParameterUpdater.h SgdThreadUpdater, RemoteParameterUpdater.h — local,
thread-sharded, and parameter-server variants).  On TPU all three become one
pure `step()` fused into the jitted train step: the optimizer math runs
sharded next to the gradients, and data-parallel gradient reduction is an XLA
psum (see parallel/), not a ring of threads or a remote server.

Handles, per parameter (ref: parameter/ParameterConfig + OptimizationConfig):
  - per-parameter learning-rate multipliers and momentum overrides
  - L1/L2 weight decay (global default, per-param override)
  - elementwise gradient clipping (global or per-param threshold)
  - the LR schedule by processed-sample count
  - model averaging (ref: AverageOptimizer) as an extra slot
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import ModelConfig, OptimizationConfig, ParameterConfig
from paddle_tpu.optim.optimizers import get_optimizer
from paddle_tpu.optim.schedulers import learning_rate_at

Array = jax.Array


class ParameterUpdater:
    def __init__(self, model: ModelConfig, opt: OptimizationConfig):
        self.model = model
        self.opt = opt
        self.param_cfgs: dict[str, ParameterConfig] = {p.name: p for p in model.parameters}
        self.init_slots_fn, self.update_fn = get_optimizer(opt.learning_method)
        self.use_average = opt.average_window > 0
        self._masks: dict[str, Array] = {}   # built by apply_init_hooks

    # -- updater hooks (ref: ParameterUpdaterHook.cpp:32,167) --------------
    def apply_init_hooks(self, params: dict[str, Array]) -> dict[str, Array]:
        """Build pruning masks and apply them to the initial values — the
        StaticPruningHook's init() (mask the parameter) + the per-update
        gradient masking happens in step().  Mask sources:
          - sparsity_ratio r: zero the r-fraction smallest-|w| entries of
            the initial value (the magnitude criterion later Paddle uses);
          - mask_filename: a .npy 0/1 array of the parameter's shape (the
            re-design of the reference's packed-bit mask file format)."""
        import numpy as np

        out = dict(params)
        for name, cfg in self.param_cfgs.items():
            for hook in cfg.update_hooks:
                if hook.get("type") != "pruning":
                    raise ValueError(f"unknown updater hook {hook!r}")
                p = np.asarray(out[name])
                if "mask_filename" in hook:
                    mask = np.load(hook["mask_filename"]).astype(p.dtype)
                    assert mask.shape == p.shape, (
                        f"mask {mask.shape} vs param {p.shape}")
                else:
                    r = float(hook.get("sparsity_ratio", 0.0))
                    k = int(r * p.size)
                    mask = np.ones(p.size, p.dtype)
                    if k > 0:
                        order = np.argsort(np.abs(p.reshape(-1)),
                                           kind="stable")
                        mask[order[:k]] = 0.0
                    mask = mask.reshape(p.shape)
                self._masks[name] = jnp.asarray(mask)
                out[name] = jnp.asarray(p * mask)
        return out

    def init_state(self, params: dict[str, Array]) -> dict[str, Any]:
        slots = {name: self.init_slots_fn(p, self.opt)
                 for name, p in params.items()
                 if not self.param_cfgs[name].is_static}
        state: dict[str, Any] = {
            "slots": slots,
            "num_samples": jnp.zeros((), jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
            "num_updates": jnp.zeros((), jnp.int32),
            "pass_id": jnp.zeros((), jnp.int32),
        }
        if self._masks:
            # masks travel INSIDE the optimizer state so a mask rebuilt
            # after checkpoint load reaches the already-compiled train step
            # (a closure read would bake the first trace's values in as
            # constants)
            state["masks"] = dict(self._masks)
        if self.use_average:
            state["average"] = {name: jnp.array(p) for name, p in params.items()}
            state["average_count"] = jnp.zeros((), jnp.int32)
        if self.accum_n > 1:
            # accumulate in >= fp32: summing N low-precision gradients with
            # a rounding per add would break the concatenated-batch
            # equivalence exactly for the configs accumulation targets
            def acc_zeros(p):
                dt = jnp.promote_types(p.dtype, jnp.float32) if \
                    jnp.issubdtype(p.dtype, jnp.floating) else p.dtype
                return jnp.zeros(p.shape, dt)
            state["grad_accum"] = {
                name: acc_zeros(p) for name, p in params.items()
                if not self.param_cfgs[name].is_static}
            state["grad_accum_count"] = jnp.zeros((), jnp.int32)
            state["grad_accum_samples"] = jnp.zeros((), jnp.int32)
        return state

    @property
    def accum_n(self) -> int:
        """Gradient-accumulation window (ref: RemoteParameterUpdater.cpp:206
        num_batches_per_send_parameter — gradients accumulate locally for N
        batches before one parameter update)."""
        return max(int(self.opt.num_batches_per_send_parameter), 1)

    def step(
        self,
        params: dict[str, Array],
        grads: dict[str, Array],
        state: dict[str, Any],
        batch_size: int,
    ) -> tuple[dict[str, Array], dict[str, Any]]:
        """One training-step update; pure, call under jit.  With
        num_batches_per_send_parameter = N > 1, gradients accumulate and
        the optimizer applies once per N batches on their mean — identical
        math to training on the N batches concatenated.

        Scan-fusion contract (trainer --steps_per_dispatch > 1 hosts this
        whole function inside a lax.scan body): the returned (params,
        state) pytrees must keep the INPUT structure and shapes — the
        accumulate-or-apply branch below is a lax.cond, never a Python
        if, so a window boundary inside a fused k-group stays a single
        compiled program and the k=1 trajectory is reproduced exactly."""
        N = self.accum_n
        if N == 1:
            return self._apply(params, grads, state, batch_size)

        # sample-weighted: each micro-batch's MEAN gradient re-scales by its
        # size, so unequal micro-batches (drop_last=False tails,
        # calc_batch_size mode) still reproduce the concatenated-batch mean
        acc = {name: state["grad_accum"][name]
               + batch_size * grads[name].astype(state["grad_accum"][name].dtype)
               for name in state["grad_accum"] if name in grads}
        for name in state["grad_accum"]:       # params without grads this step
            acc.setdefault(name, state["grad_accum"][name])
        cnt = state["grad_accum_count"] + 1
        n_samples = state["grad_accum_samples"] + batch_size
        core = {k: v for k, v in state.items()
                if k not in ("grad_accum", "grad_accum_count",
                             "grad_accum_samples")}

        def apply_branch(_):
            denom = n_samples.astype(jnp.float32)
            mean = {n: (a / denom).astype(a.dtype) for n, a in acc.items()}
            p2, s2 = self._apply(params, mean, core, n_samples)
            s2 = dict(s2)
            s2["grad_accum"] = jax.tree.map(jnp.zeros_like, acc)
            s2["grad_accum_count"] = jnp.zeros((), jnp.int32)
            s2["grad_accum_samples"] = jnp.zeros((), jnp.int32)
            return p2, s2

        def skip_branch(_):
            s2 = dict(core)
            s2["grad_accum"] = acc
            s2["grad_accum_count"] = cnt
            s2["grad_accum_samples"] = n_samples
            return dict(params), s2

        return jax.lax.cond(cnt >= N, apply_branch, skip_branch, None)

    def _apply(
        self,
        params: dict[str, Array],
        grads: dict[str, Array],
        state: dict[str, Any],
        batch_size: int,
    ) -> tuple[dict[str, Array], dict[str, Any]]:
        """One optimizer application; pure, call under jit."""
        opt = self.opt
        num_samples = state["num_samples"] + batch_size
        t = state["num_updates"] + 1
        base_lr = learning_rate_at(opt, num_samples, state["pass_id"])

        new_params: dict[str, Array] = {}
        new_slots: dict[str, Any] = {}
        for name, p in params.items():
            cfg = self.param_cfgs[name]
            if cfg.is_static or name not in grads:
                new_params[name] = p
                if name in state["slots"]:
                    new_slots[name] = state["slots"][name]
                continue
            g = grads[name]
            # pruning-mask hook: masked entries receive no gradient and the
            # value is re-masked after the update (ref: StaticPruningHook::
            # update — grad dotMul mask)
            mask = state.get("masks", {}).get(name)
            if mask is not None:
                g = g * mask.astype(g.dtype)
            # gradient clipping (elementwise, ref: ParameterOptimizer clipping);
            # per-param None inherits the global, 0.0 disables explicitly
            thr = (cfg.gradient_clipping_threshold
                   if cfg.gradient_clipping_threshold is not None
                   else opt.gradient_clipping_threshold)
            if thr:
                g = jnp.clip(g, -thr, thr)
            # weight decay (ref: Regularizer.cpp applied at update time)
            l2 = cfg.decay_rate if cfg.decay_rate is not None else opt.l2_weight
            if l2:
                g = g + l2 * p
            l1 = cfg.decay_rate_l1 if cfg.decay_rate_l1 is not None else opt.l1_weight
            if l1:
                g = g + l1 * jnp.sign(p)
            lr = base_lr * cfg.learning_rate
            mom_override = cfg.momentum
            new_p, slots = self.update_fn(
                p, g, state["slots"][name], lr, opt, t,
                **({"mom_override": mom_override} if mom_override is not None
                   and opt.learning_method in ("momentum", "sgd", "sparse_momentum")
                   else {}))
            if mask is not None:
                # weight decay / averaging must not resurrect pruned weights
                new_p = new_p * mask.astype(new_p.dtype)
            new_params[name] = new_p
            new_slots[name] = slots

        new_state: dict[str, Any] = {
            "slots": new_slots,
            "num_samples": num_samples,
            "num_updates": t,
            "pass_id": state["pass_id"],
        }
        if "masks" in state:
            new_state["masks"] = state["masks"]
        if self.use_average:
            # cumulative average with window reset
            # (ref: AverageOptimizer — maintains an averaged copy for eval)
            cnt = state["average_count"] + 1
            max_win = opt.max_average_window or 0
            if max_win:
                reset = cnt > max_win
                cnt = jnp.where(reset, 1, cnt)
            avg = {}
            for name, p in new_params.items():
                prev = state["average"][name]
                if max_win:
                    prev = jnp.where(reset, p, prev)
                avg[name] = prev + (p - prev) / cnt.astype(p.dtype)
            new_state["average"] = avg
            new_state["average_count"] = cnt
        return new_params, new_state

    def start_pass(self, state):
        return state

    def finish_pass(self, state):
        state = dict(state)
        state["pass_id"] = state["pass_id"] + 1
        if "grad_accum" in state:
            # a partially-filled accumulation window does not straddle the
            # pass boundary (its batches would otherwise apply under the
            # next pass's LR schedule); the trailing < N batches are
            # dropped, the same convention as the feeder's drop_last
            state["grad_accum"] = jax.tree.map(jnp.zeros_like,
                                               state["grad_accum"])
            # zeros_like, not fresh zeros: under a mesh the counters are
            # placed on it (parallel/dp.py:shard_train_objects), and a fresh
            # scalar would give the next pass's step another signature
            state["grad_accum_count"] = jnp.zeros_like(
                state["grad_accum_count"])
            state["grad_accum_samples"] = jnp.zeros_like(
                state["grad_accum_samples"])
        return state

    def averaged_params(self, params, state):
        """Parameters to evaluate with (ref: AverageOptimizer::setupBeforeLoad)."""
        if self.use_average:
            return state["average"]
        return params
