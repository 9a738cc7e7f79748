#!/usr/bin/env python3
"""Compile each cell's programs at the REAL size for a described TPU v5e,
here, without the chip (on-chip-measurement guide, section 2, the third
rehearsal).  Run by hand; nothing runs on a device, so no number printed
here is a measurement — only whether Mosaic/XLA accept the program and what
`memory_analysis()` says it needs.

  JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py train [--layers 3]
  JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py dp4   [--layers 3]
  JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py serve [--layers 8]
  JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py kernels
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"   # kernels "supported" here

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

import jax                                        # noqa: E402
import jax.numpy as jnp                           # noqa: E402
import numpy as np                                # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from benchmark.lib.spec import Benchmark          # noqa: E402


def steer_kernels():
    """The kernels ask jax.default_backend() (cpu here) whether to run in
    interpret mode; a compile for the chip must not."""
    from paddle_tpu.ops import pallas_attention, pallas_paged
    for mod in (pallas_attention, pallas_paged):
        mod._interpret = lambda: False


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    out = {"program": name, "compile_s": round(time.time() - t0, 1),
           "tpu_custom_calls": text.count("tpu_custom_call"),
           "all_reduce": text.count("all-reduce("),
           "argument_GB": round(ma.argument_size_in_bytes / 1e9, 3),
           "output_GB": round(ma.output_size_in_bytes / 1e9, 3),
           "alias_GB": round(ma.alias_size_in_bytes / 1e9, 3),
           "temp_GB": round(ma.temp_size_in_bytes / 1e9, 3),
           "code_MB": round(ma.generated_code_size_in_bytes / 1e6, 1)}
    out["live_GB"] = round(out["argument_GB"] + out["output_GB"]
                           - out["alias_GB"] + out["temp_GB"], 3)
    print(json.dumps(out), flush=True)
    return out


def sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def train(bench, layers, dp):
    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.graph.builder import GraphExecutor
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    kind = bench.kind("train")
    cfg = bench.config("starcoder2-3b-train")
    tf = bench.traffic("seq4k-dp4" if dp else "seq4k")
    if layers:
        cfg["num_hidden_layers"] = layers
    topo = jax.experimental.topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    pc = parse_config(cfg["dsl"], kind.config_args(cfg, tf))
    tr = Trainer(pc, seed=1)
    if dp:
        from paddle_tpu.parallel.mesh import mesh_from_flag
        mesh = mesh_from_flag(tf["mesh_shape"], devices=topo.devices)
        tr.mesh = mesh
        tr.executor = GraphExecutor(tr.model, mesh=mesh,
                                    compute_dtype=cfg["compute_dtype"])
        rep, bat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    else:
        rep = bat = SingleDeviceSharding(topo.devices[0])
    fn = tr._build_train_step_fn()
    b, t = tf["sequences_per_step"], tf["seq_len"]
    arg = lambda: Argument(
        ids=jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=bat),
        lengths=jax.ShapeDtypeStruct((b,), jnp.int32, sharding=bat))
    batch = {"tokens": arg(), "next_tokens": arg()}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    t0 = time.time()
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(
        sds(tr.params, rep), sds(tr.opt_state, rep), {}, batch, key).compile()
    report(f"train step, {cfg['num_hidden_layers']} layers, "
           f"{b} x {t} tokens, {'data:4' if dp else '1 chip'}", compiled, t0)


def serve(bench, layers):
    kind = bench.kind("serve")
    cfg = bench.config("starcoder2-3b-serve")
    if layers:
        cfg["num_hidden_layers"] = layers
    from benchmark.lib.spec import load_module
    tool = load_module(os.path.join(ROOT, "tools", "serve.py"), "tools_serve")
    args = kind.parse_server_flags(tool, kind.server_argv(cfg, 1))
    eng = tool.build_engine(args)
    topo = jax.experimental.topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    from paddle_tpu.serving import Request

    class Captured(Exception):
        pass

    got = {}

    def capture(name):
        def f(*a):
            got[name] = a
            raise Captured(name)
        return f

    real = {"decode": eng._decode_step, "mixed": eng._mixed_step}
    eng._mixed_step = capture("mixed")
    eng.add_request(Request("a", np.arange(2, 200, dtype=np.int32), max_new=4))
    try:
        eng.step()
    except Captured:
        pass
    eng._sync_run_mask(range(len(eng.slots)))
    eng._sync_device_state()
    got["decode"] = (eng.params, eng._build_state(), eng._d_run)
    for name in ("decode", "mixed"):
        t0 = time.time()
        compiled = real[name].lower(*sds(got[name], one)).compile()
        report(f"serve {name} step, {cfg['num_hidden_layers']} layers, "
               f"{len(eng.slots)} slots, step tokens {eng.max_step_tokens}",
               compiled, t0)


def kernels(bench):
    from paddle_tpu.ops.pallas_attention import flash_attention
    topo = jax.experimental.topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 4096, 24, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 4096, 2, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    t0 = time.time()
    report("flash fwd+bwd B2 T4096 H24 KV2 D128",
           jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
           .compile(), t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "dp4", "serve", "kernels"))
    ap.add_argument("--layers", type=int, default=0)
    a = ap.parse_args()
    import jax.experimental.topologies  # noqa: F401
    jax.config.update("jax_enable_compilation_cache", False)
    steer_kernels()
    bench = Benchmark(ROOT)
    if a.what == "train":
        train(bench, a.layers, False)
    elif a.what == "dp4":
        train(bench, a.layers, True)
    elif a.what == "serve":
        serve(bench, a.layers)
    else:
        kernels(bench)


if __name__ == "__main__":
    main()
