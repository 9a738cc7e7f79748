"""Worker for the real multi-process jax.distributed test (launched by
tests/test_multiprocess.py, one subprocess per simulated host).

Each process boots via init_distributed (the pserver-fleet bootstrap
analog), builds the SAME model from the same seed, feeds its OWN local
batch shard (per-host data-parallel input, like each trainer reading its
own file list), trains a few steps over a data-parallel mesh, and prints
the per-step losses — which must agree bit-for-bit across processes since
the loss is computed from the global batch.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# force the CPU backend BEFORE jax import
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np


def main() -> int:
    coord = sys.argv[1]
    num_procs = int(sys.argv[2])
    pid = int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "dp"   # 'dp' | 'tpdp'

    from paddle_tpu.parallel.mesh import init_distributed, make_mesh
    init_distributed(coord, num_procs, pid)
    assert jax.process_count() == num_procs, jax.process_count()

    from paddle_tpu.config.parser import parse_config_callable
    from paddle_tpu.parameter.argument import Argument
    from paddle_tpu.trainer.trainer import Trainer

    model_par = 2 if mode == "tpdp" else 1
    data_par = num_procs // model_par

    def conf():
        from paddle_tpu.dsl import (MomentumOptimizer, ParameterAttribute,
                                    SoftmaxActivation, TanhActivation,
                                    classification_cost, data_layer,
                                    fc_layer, settings)
        settings(batch_size=8 * data_par, learning_rate=0.1,
                 learning_method=MomentumOptimizer(momentum=0.9))
        x = data_layer(name="x", size=16)
        tp = (ParameterAttribute(partition_spec=[None, "model"])
              if model_par > 1 else None)
        tp2 = (ParameterAttribute(partition_spec=["model", None])
               if model_par > 1 else None)
        h = fc_layer(input=x, size=32, act=TanhActivation(), param_attr=tp)
        out = fc_layer(input=h, size=4, act=SoftmaxActivation(),
                       param_attr=tp2)
        classification_cost(input=out, label=data_layer(name="y", size=4))

    cfg = parse_config_callable(conf)
    if model_par > 1:
        # devices laid out [data, model]: device i -> data row i // model_par
        mesh = make_mesh(data=data_par, model=model_par)
    else:
        mesh = make_mesh()      # data axis spans every process's devices
    tr = Trainer(cfg, seed=7, mesh=mesh)

    if model_par > 1:
        # tp params must REALLY shard across processes: each process holds
        # 1/model_par of the annotated weights
        w0 = tr.params["___fc_layer_0__.w0"]
        assert not w0.is_fully_addressable
        local = w0.addressable_shards[0].data
        assert local.shape[1] * model_par == w0.shape[1], (
            local.shape, w0.shape)
        print(f"RESULT pid={pid} tp_shard_ok local={local.shape} "
              f"global={w0.shape}", flush=True)

    # per-process data: one stream per DATA ROW (processes replicating the
    # same data shard across `model` must feed identical rows), global
    # batch = concatenation over data rows
    data_row = pid // model_par
    rng = np.random.default_rng(100 + data_row)
    W = np.random.default_rng(0).normal(size=(16, 4)).astype(np.float32)
    losses = []
    for _ in range(4):
        x = rng.normal(size=(8, 16)).astype(np.float32)
        y = np.argmax(x @ W, -1).astype(np.int32)
        loss = tr.train_one_batch({"x": Argument(value=x),
                                   "y": Argument(ids=y)})
        # the step loss is computed from the GLOBAL batch and fully
        # replicated, so float() is legal multi-process and every process
        # must see the same value
        losses.append(float(loss))
    tr._drain_losses()
    print("RESULT pid={} losses={}".format(
        pid, ",".join(f"{l:.10f}" for l in losses)), flush=True)
    # final parameters, for the single-process equivalence oracle in the
    # test (ref: test_CompareSparse.cpp — multi-trainer == local training)
    from paddle_tpu.trainer.trainer import _host_tree
    host_params = _host_tree(tr.params)
    for name in sorted(host_params):
        flat = np.asarray(host_params[name]).ravel()
        print(f"RESULT pid={pid} param {name} "
              f"sum={flat.sum():.8f} asum={np.abs(flat).sum():.8f}",
              flush=True)

    # barrier stats straggler table exercises process_allgather
    bt = tr.barrier_stat
    strag = bt.straggler_summary()
    assert strag is not None and strag["skew"] >= 1.0, strag
    print(f"RESULT pid={pid} straggler_ok skew={strag['skew']:.3f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
