"""Cell kind `train`: Trainer.train_one_pass, per-batch dispatch, the
program's own provider running, on 1 chip or a data-parallel mesh."""

from __future__ import annotations

import gc
import math
import time

from benchmark.lib.check import rel_err_tree
from benchmark.lib.common import (ProfilerWindow, check_weights_fit,
                                  compiles_total, log, memory_bytes,
                                  setup_jax)


def config_args(cfg: dict, traffic: dict) -> str:
    return (f"vocab={cfg['vocab_size']},dim={cfg['hidden_size']},"
            f"layers={cfg['num_hidden_layers']},"
            f"heads={cfg['num_attention_heads']},"
            f"kv_heads={cfg['num_key_value_heads']},"
            f"ffn={cfg['intermediate_size']},rope_theta={cfg['rope_theta']},"
            f"batch_size={traffic['sequences_per_step']},"
            f"compute_dtype={cfg['compute_dtype']},"
            f"attn_impl={cfg['attn_impl']},seq_len={traffic['seq_len'] + 1}")


class BatchStream:
    """The provider's batches, pass after pass, with the time the trainer
    loop waited for each (the benchmark's own span around the iterator it
    hands to train_one_pass)."""

    def __init__(self, trainer, annotate=None):
        self.trainer = trainer
        self.it = iter(())
        self.wait_s = 0.0
        self.annotate = annotate

    def next(self):
        t = time.perf_counter()
        try:
            batch = next(self.it)
        except StopIteration:
            self.it = iter(self.trainer.train_batches())
            batch = next(self.it)
        self.wait_s += time.perf_counter() - t
        return batch

    def take(self, n: int):
        for _ in range(n):
            if self.annotate is not None:
                with self.annotate("bench.input_wait"):
                    b = self.next()
            else:
                b = self.next()
            yield b


def _ids(batch, name):
    import numpy as np
    return np.asarray(batch[name].ids)


def _mesh(jax, ctx):
    """The cell's mesh over its own chips (--mesh_shape's parser), or None."""
    spec = ctx.traffic.get("mesh_shape")
    if not spec:
        return None
    from paddle_tpu.parallel.mesh import mesh_from_flag
    return mesh_from_flag(spec, devices=jax.devices()[:ctx.chips])


def _sample(toks, labs, mesh):
    """The first sequences of a batch as the program's own feed (sharded
    over the data axis under a mesh)."""
    import jax.numpy as jnp

    from paddle_tpu.parameter.argument import Argument

    lens = jnp.full((toks.shape[0],), toks.shape[1], jnp.int32)
    sample = {"tokens": Argument(ids=jnp.asarray(toks), lengths=lens),
              "next_tokens": Argument(ids=jnp.asarray(labs), lengths=lens)}
    if mesh is not None:
        from paddle_tpu.parallel.dp import shard_batch
        sample = shard_batch(mesh, sample)
    return sample


def run(ctx) -> dict:
    jax, device = setup_jax(ctx)
    import jax.numpy as jnp

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph.context import TRAIN
    from paddle_tpu.trainer.trainer import Trainer

    cfg, tf = ctx.cfg, ctx.traffic
    ref = ctx.bench.reference(cfg["reference"])
    mesh = _mesh(jax, ctx)
    pc = parse_config(cfg["dsl"], config_args(cfg, tf))
    tr = Trainer(pc, seed=ctx.seed32, mesh=mesh)

    w = ref.make_weights(cfg, ctx.seed32,
                         jax.tree.map(lambda x: x.sharding, tr.params))
    check_weights_fit(tr.params, w)
    tr.params = w
    del w
    n_params = sum(int(v.size) for v in tr.params.values())
    log(f"MODEL {n_params} parameters, {cfg['num_hidden_layers']} layers, "
        f"mesh {tf.get('mesh_shape') or 'none'}")

    annotate = jax.profiler.TraceAnnotation if ctx.trace else None
    stream = BatchStream(tr, annotate)
    tokens_per_step = tf["sequences_per_step"] * tf["seq_len"]

    # warm-up: every program the window uses, through the real train step;
    # the first step's loss is held against the reference later
    b0 = stream.next()
    losses, step_s = [], []
    for i in range(int(tf["warm_steps"])):
        b = b0 if i == 0 else stream.next()
        t = time.perf_counter()
        st = tr.train_one_pass(batches=iter([b]))
        jax.block_until_ready(tr.params)
        step_s.append(time.perf_counter() - t)
        losses.append(float(st["cost"]))
    log(f"WARM step seconds {[round(s, 3) for s in step_s]} losses "
        f"{[round(x, 4) for x in losses]}")
    step_time = min(step_s[1:]) if len(step_s) > 1 else step_s[0]
    n_steps = max(2, int(math.ceil(ctx.seconds / step_time)))
    n_traced = min(int(tf["trace_steps"]), n_steps - 1) if ctx.trace else 0

    # the dp cell: batch and optimizer state on distinct devices
    if mesh is not None:
        from paddle_tpu.parallel.dp import shard_batch
        sb = shard_batch(mesh, b0)
        n_batch = len({s.device for s in sb["tokens"].ids.addressable_shards})
        slot = jax.tree.leaves(tr.opt_state["slots"])[0]
        n_opt = len({s.device for s in slot.addressable_shards})
        ctx.check("dp_batch_devices_missing", ctx.chips - n_batch, 0)
        ctx.check("dp_opt_state_devices_missing", ctx.chips - n_opt, 0)

    # ---- the measured window ------------------------------------------
    stream.wait_s = 0.0
    c0 = compiles_total()
    jw0 = dict(ctx.counters["jit_work"])
    setup_s = time.perf_counter() - ctx.t_process
    t0 = time.perf_counter()
    st = tr.train_one_pass(batches=stream.take(n_steps - n_traced))
    jax.block_until_ready(tr.params)
    t1 = time.perf_counter()
    rate = (n_steps - n_traced) * tokens_per_step / (t1 - t0) / ctx.chips
    if n_traced:
        prof = ProfilerWindow(ctx)
        prof.start()
        tr.train_one_pass(batches=stream.take(n_traced))
        jax.block_until_ready(tr.params)
        prof.stop()
    t2 = time.perf_counter()
    ctx.counters["compiles_in_window"] = compiles_total() - c0
    jw = {k: ctx.counters["jit_work"][k] - jw0[k] for k in jw0}
    ctx.counters["backend_compiles_in_window"] = jw["backend_compiles"]
    log(f"JIT WORK IN WINDOW {jw}")
    ctx.counters["steps"] = n_steps
    ctx.counters["traced_steps"] = n_traced
    ctx.counters["tokens_per_step"] = tokens_per_step
    ctx.spans["input_wait_s"] = stream.wait_s
    ctx.spans["window_s"] = t2 - t0
    ctx.e2e = {"train_tokens_per_s_per_chip": rate, "setup_s": setup_s}
    log(f"WINDOW {n_steps} steps ({n_traced} traced) of {tokens_per_step} "
        f"tokens in {t2 - t0:.3f}s; untraced rate {rate:.1f} tokens/s/chip; "
        f"pass cost {st['cost']:.4f}; compiles in window "
        f"{ctx.counters['compiles_in_window']}; compile cache "
        f"{ctx.counters['compile_cache']}")
    peak = memory_bytes(jax, ctx.chips)
    if n_traced:
        prof.reduce()

    # ---- correct: the reference on the same seeded weights ------------
    executor = tr.executor
    limits = cfg["limits"]
    del tr, stream, st
    gc.collect()
    toks, labs = _ids(b0, "tokens"), _ids(b0, "next_tokens")
    with jax.default_matmul_precision("highest"):
        # weights again from the seed: the trained ones were donated
        w = ref.make_weights(
            cfg, ctx.seed32,
            None if mesh is None else jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        ref_loss = float(ref.jitted("loss", cfg)(
            w, jnp.asarray(toks), jnp.asarray(labs)))
        ok = ctx.check("train_loss_rel", abs(losses[0] - ref_loss) / ref_loss,
                       limits["train_loss_rel"])
        n = int(tf["check_sequences"])
        st_, sl_ = toks[:n], labs[:n]
        _, g_ref = ref.jitted("loss_grad", cfg)(w, jnp.asarray(st_),
                                                jnp.asarray(sl_))
    key = jax.random.PRNGKey(0)
    g_prog = jax.jit(jax.grad(
        lambda p, b: executor.loss(p, b, {}, TRAIN, key)[0]))(
            w, _sample(st_, sl_, mesh))
    ok &= ctx.check("train_grad_rel", rel_err_tree(jax, g_prog, g_ref),
                    limits["train_grad_rel"])
    ok &= ctx.check("loss_not_finite",
                    0.0 if all(math.isfinite(x) for x in losses) else 1.0, 0)
    ok &= ctx.check("compiles_in_window",
                    ctx.counters["compiles_in_window"], 0)
    ok &= all(c["ok"] for c in ctx.checks)
    device["memory_peak_bytes"] = peak
    return {"correct": bool(ok), "attempted": n_steps, "failed": 0,
            "device": device}


def calibrate(ctx, seeds: list[int]) -> None:
    """On the chip, at the cell's own size, no timed window: for each seed
    the program's numbers and the control's (the reference in fp8, put in
    the program's place), read in one process.  Prints one CAL line a seed."""
    jax, _ = setup_jax(ctx)
    import json

    import jax.numpy as jnp

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph.context import TRAIN
    from paddle_tpu.trainer.trainer import Trainer

    cfg, tf = ctx.cfg, ctx.traffic
    ref = ctx.bench.reference(cfg["reference"])
    mesh = _mesh(jax, ctx)
    pc = parse_config(cfg["dsl"], config_args(cfg, tf))
    tr = Trainer(pc, seed=1, mesh=mesh)
    shard = jax.tree.map(lambda x: x.sharding, tr.params)
    rep = None if mesh is None else jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    stream = BatchStream(tr)
    rows = {}
    for seed in seeds:
        tr.params = ref.make_weights(cfg, seed % (2 ** 31 - 1), shard)
        b = stream.next()
        st = tr.train_one_pass(batches=iter([b]))
        rows[seed] = {"seed": seed, "program_loss": float(st["cost"]),
                      "batch": (_ids(b, "tokens"), _ids(b, "next_tokens"))}
    executor = tr.executor
    del tr, stream
    gc.collect()
    n = int(tf["check_sequences"])
    key = jax.random.PRNGKey(0)
    g_fn = jax.jit(jax.grad(
        lambda p, bt: executor.loss(p, bt, {}, TRAIN, key)[0]))
    for seed in seeds:
        row = rows[seed]
        toks, labs = row.pop("batch")
        w = ref.make_weights(cfg, seed % (2 ** 31 - 1), rep)
        with jax.default_matmul_precision("highest"):
            t, l = jnp.asarray(toks), jnp.asarray(labs)
            ref_loss = float(ref.jitted("loss", cfg)(w, t, l))
            ctl_loss = float(ref.jitted("loss", cfg, "fp8")(w, t, l))
            _, g_ref = ref.jitted("loss_grad", cfg)(w, t[:n], l[:n])
            _, g_ctl = ref.jitted("loss_grad", cfg, "fp8")(w, t[:n], l[:n])
            row["control_grad_rel"] = rel_err_tree(jax, g_ctl, g_ref)
            del g_ctl
        g_prog = g_fn(w, _sample(toks[:n], labs[:n], mesh))
        row["program_grad_rel"] = rel_err_tree(jax, g_prog, g_ref)
        del g_prog, g_ref, w
        row["reference_loss"] = ref_loss
        row["program_loss_rel"] = abs(row["program_loss"] - ref_loss) / ref_loss
        row["control_loss_rel"] = abs(ctl_loss - ref_loss) / ref_loss
        log("CAL " + json.dumps(row))
