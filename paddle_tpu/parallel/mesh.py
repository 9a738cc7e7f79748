"""Device mesh construction.

The TPU replacement for the reference's entire parallel topology
configuration: `--trainer_count` worker threads + `--pservers` host lists
(ref: paddle/trainer/TrainerMain.cpp:47-92, paddle/pserver/LightNetwork.cpp)
collapse into one `jax.sharding.Mesh` whose axes name the parallelism kinds:

  data   — batch sharding (ref: MultiGradientMachine thread DP + pserver DP)
  model  — tensor/parameter sharding (ref: ParallelNeuralNetwork device=N)
  seq    — sequence/context parallelism (ring attention; parallel/context.py)
             — NEW capability, the reference handles long sequences on one
             device only (SURVEY.md §5 long-context)
  pipe   — pipeline parallelism over layer stages (parallel/pipeline.py)
             — the scaled-out analog of ParallelNeuralNetwork's per-layer
             device= placement

All four axes are always present (size 1 when unused) so partition specs
naming any of them stay valid on any mesh.  Collectives ride ICI within a
slice and DCN across slices; multi-host setup is jax.distributed instead of
a pserver fleet.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"

AXIS_ORDER = (DATA_AXIS, SEQ_AXIS, PIPE_AXIS, MODEL_AXIS)


def make_mesh(data: int = 0, model: int = 1, seq: int = 1, pipe: int = 1,
              devices=None) -> Mesh:
    """Build a mesh over (data, seq, pipe, model); data=0 = 'all remaining'.

    Axis order puts `model` innermost (fastest-varying devices = closest ICI
    neighbors — tensor-parallel collectives are the most latency-sensitive)
    and `data` outermost, matching standard TPU practice."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    n = devs.size
    rest = model * seq * pipe
    if data <= 0:
        assert n % rest == 0, f"{n} devices not divisible by {rest}"
        data = n // rest
    sizes = {DATA_AXIS: data, SEQ_AXIS: seq, PIPE_AXIS: pipe, MODEL_AXIS: model}
    total = data * rest
    assert total == n, f"mesh {sizes} = {total} devices != {n} available"
    # every axis is always present — size-1 axes cost nothing and keep
    # partition specs naming any canonical axis valid on any mesh
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    return Mesh(devs.reshape(shape), AXIS_ORDER)


def axis_size(mesh: Optional[Mesh], axis: str) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def model_mesh(n: int, devices=None) -> Optional[Mesh]:
    """A SERVING tensor-parallel mesh: exactly the first `n` devices on the
    `model` axis, every other axis 1 (the `--mesh model=N` flag of
    tools/serve.py).  Unlike make_mesh's data=0 remainder
    rule this never swallows spare devices into a data axis — replicating
    the KV pools over an unused data axis would defeat the per-chip HBM
    win sharding exists for.  n <= 1 returns None (no mesh: the engine
    keeps its single-device path)."""
    n = int(n)
    if n <= 1:
        return None
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < n:
        raise ValueError(
            f"--mesh model={n} needs {n} devices, have {len(devs)} — on a "
            f"CPU host use XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={n} (set BEFORE jax initializes)")
    return make_mesh(data=1, model=n, seq=1, pipe=1, devices=devs[:n])


def mesh_from_flag(spec: str, devices=None) -> Optional[Mesh]:
    """Parse 'data:8' / 'data:4,model:2' / 'data:2,seq:2,model:2'
    (the --mesh_shape flag)."""
    if not spec:
        return None
    sizes = {"data": 0, "model": 1, "seq": 1, "pipe": 1}
    for part in spec.split(","):
        name, _, num = part.partition(":")
        name = name.strip()
        assert name in sizes, \
            f"unknown mesh axis {name!r}; valid: {sorted(sizes)}"
        sizes[name] = int(num)
    return make_mesh(sizes["data"], sizes["model"], sizes["seq"],
                     sizes["pipe"], devices)


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap (ref: the pserver fleet + --trainer_id/--pservers
    startup protocol → jax.distributed coordinator)."""
    kwargs = {}
    if coordinator_address:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)
