"""Sequence/context parallelism — shard the TOKEN axis over the mesh.

First-class long-context support, going beyond the reference (whose longest-
sequence story is zero-padding ragged batching + SequenceToBatch re-bucketing
on ONE device — SURVEY.md §5 "long-context"; ref: paddle/gserver/layers/
SequenceToBatch.h:20-40).  Here a sequence too long for one chip's HBM is
split over the `seq` mesh axis and attention runs as a ring
(ops/attention.py:ring_attention): K/V shards rotate via `lax.ppermute`
around ICI neighbors while each device folds incoming blocks into an
online-softmax accumulator — compute overlaps communication, and per-device
memory is O(T / seq_parallelism).

`ring_attention_sharded` is the mesh-level entry: it shard_maps the ring
kernel with batch on `data` and time on `seq`, usable directly or through the
`multi_head_attention` graph layer (graph/layers_attn.py) which picks the
ring path automatically when the executor's mesh has a seq axis > 1.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops.attention import ring_attention
from paddle_tpu.utils.jax_compat import shard_map
from paddle_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, axis_size

Array = jax.Array


def seq_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the seq axis, 1 if absent/no mesh."""
    return axis_size(mesh, SEQ_AXIS)


def _data_axis(mesh: Mesh) -> Optional[str]:
    return DATA_AXIS if DATA_AXIS in mesh.axis_names else None


def shard_sequence(mesh: Mesh, x: Array) -> Array:
    """Place [B, T, ...] with batch on `data` and time on `seq`.  Works on
    multi-process meshes too (each process holds the full host copy)."""
    from paddle_tpu.parallel.dp import global_put
    spec = [_data_axis(mesh), SEQ_AXIS] + [None] * (x.ndim - 2)
    return global_put(x, NamedSharding(mesh, P(*spec)))


def _sharded_ctx_call(mesh, wrapped, q, k, v, q_valid, k_valid,
                      use_flash: bool):
    """Shared shard_map scaffolding for the context-parallel entries:
    batch on `data`, tokens on `seq`, optional masks threaded with
    placeholder args (shard_map needs every arg speced).  check_vma stays
    ON for the pure-jnp paths, where it validates the collective
    plumbing; pallas_call outputs carry no varying-mesh-axes annotation,
    so the flash path must opt out."""
    d = _data_axis(mesh)
    qkv_spec = P(d, SEQ_AXIS, None, None)
    val_spec = P(d, SEQ_AXIS)
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    args = [q, k, v]
    for m in (q_valid, k_valid):
        in_specs.append(val_spec if m is not None else P())
        args.append(m if m is not None else jnp.zeros((), q.dtype))
    fn = shard_map(wrapped, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=qkv_spec, check_vma=not use_flash)
    return fn(*args)


def ring_attention_sharded(
    mesh: Mesh,
    q: Array, k: Array, v: Array,          # [B, T, H, Dh], T % seq_axis == 0
    q_valid: Optional[Array] = None,       # [B, T]
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> Array:
    """Context-parallel attention over the mesh: batch sharded on `data`,
    time sharded on `seq`, ring over the seq axis.  Works under an outer
    jit — shard_map composes with the surrounding compiled step."""
    # resolve the flash choice OUTSIDE shard_map (see _sharded_ctx_call)
    from paddle_tpu.ops import pallas_attention
    use_flash = pallas_attention.supported()

    def wrapped(q, k, v, qm, km):
        qv = qm if q_valid is not None else None
        kv = km if k_valid is not None else None
        return ring_attention(q, k, v, SEQ_AXIS, q_valid=qv, k_valid=kv,
                              causal=causal, scale=scale,
                              use_flash=use_flash, window=window)

    return _sharded_ctx_call(mesh, wrapped, q, k, v, q_valid, k_valid,
                             use_flash)


def ring_attn_fn(mesh: Mesh, causal_default: bool = False):
    """An `attn_fn` for ops.attention.multi_head_attention that routes through
    the sharded ring. Signature matches dot_product_attention."""
    def fn(q, k, v, q_valid=None, k_valid=None, causal=causal_default,
           scale=None, window=None):
        return ring_attention_sharded(mesh, q, k, v, q_valid=q_valid,
                                      k_valid=k_valid, causal=causal,
                                      scale=scale, window=window)
    return fn


def flash_attn_fn(mesh: Mesh, flash):
    """An `attn_fn` that runs the Pallas flash kernel `flash` on each
    device's batch shard.  GSPMD cannot partition a Mosaic kernel (lowering
    for a multi-chip mesh raises "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — interpret mode on
    the CPU mesh never does), so under a mesh the call sits in shard_map:
    batch on `data`, everything else whole.  A `seq` axis > 1 belongs to
    ring/ulysses, which combine across token shards."""
    if seq_axis_size(mesh) > 1:
        raise ValueError(
            "attn_impl='flash' attends within one device's tokens; a mesh "
            "with a `seq` axis > 1 needs attn_impl='ring' or 'ulysses'")

    def fn(q, k, v, q_valid=None, k_valid=None, causal=False, scale=None,
           window=None):
        def wrapped(q, k, v, qm, km):
            return flash(q, k, v,
                         q_valid=qm if q_valid is not None else None,
                         k_valid=km if k_valid is not None else None,
                         causal=causal, scale=scale, window=window)

        return _sharded_ctx_call(mesh, wrapped, q, k, v, q_valid, k_valid,
                                 use_flash=True)
    return fn


def ulysses_attention_sharded(
    mesh: Mesh,
    q: Array, k: Array, v: Array,          # [B, T, H, Dh], T % seq_axis == 0
    q_valid: Optional[Array] = None,       # [B, T]
    k_valid: Optional[Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    block_k: Optional[int] = None,
    block_k_min: Optional[int] = None,
) -> Array:
    """All-to-all sequence parallelism (the DeepSpeed-Ulysses design) —
    the OTHER standard context-parallel layout beside the ring:

      tokens sharded [B, T/P, H, D]
        --all_to_all-->  heads sharded [B, T, H/P, D]
        --local full-sequence attention (flash on TPU)-->
        --all_to_all-->  tokens sharded [B, T/P, H, D]

    Two activation exchanges per layer instead of the ring's P-1 K/V
    rotations: communication is O(T*H*D/P) regardless of P, and the
    attention itself is a plain full-sequence call (any impl, no
    online-softmax combine).  Prefer it when heads >= the seq-axis size
    and ICI all-to-all bandwidth is plentiful; prefer the ring when
    per-device memory for the full [B, T, H/P] sequence is the binding
    constraint or H < P.  Requires H (and kv heads) % seq_axis == 0.
    """
    Pseq = axis_size(mesh, SEQ_AXIS)
    H, H_kv = q.shape[2], k.shape[2]
    assert H % Pseq == 0, (
        f"ulysses needs num_heads {H} divisible by the seq axis ({Pseq})")
    assert H_kv % Pseq == 0, (
        f"ulysses needs num_kv_heads {H_kv} divisible by the seq axis "
        f"({Pseq}); use attn_impl='ring' for narrower GQA")
    import functools

    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.ops.attention import (blockwise_attention,
                                          dot_product_attention)
    use_flash = pallas_attention.supported()
    T = q.shape[1]
    if block_k_min is None:
        # the ONE measured dense/blockwise crossover constant
        from paddle_tpu.graph.layers_attn import _BLOCKWISE_MIN_KEYS
        block_k_min = _BLOCKWISE_MIN_KEYS
    if use_flash:
        attn = (functools.partial(pallas_attention.flash_attention,
                                  block_k=block_k)
                if block_k else pallas_attention.flash_attention)
    elif T >= block_k_min:
        attn = (functools.partial(blockwise_attention, block_k=block_k)
                if block_k else blockwise_attention)
    else:
        attn = dot_product_attention

    def wrapped(q, k, v, qm, km):
        # token-shard -> head-shard: split heads (axis 2) over the seq
        # axis, concatenate token shards (axis 1) — tiled all_to_all
        # preserves the device order, so tokens land in GLOBAL order
        def a2a_fwd(x):
            return jax.lax.all_to_all(x, SEQ_AXIS, split_axis=2,
                                      concat_axis=1, tiled=True)

        qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
        qvg = (jax.lax.all_gather(qm, SEQ_AXIS, axis=1, tiled=True)
               if q_valid is not None else None)
        kvg = (jax.lax.all_gather(km, SEQ_AXIS, axis=1, tiled=True)
               if k_valid is not None else None)
        out = attn(qg, kg, vg, q_valid=qvg, k_valid=kvg, causal=causal,
                   **({"scale": scale} if scale is not None else {}),
                   **({"window": window} if window is not None else {}))
        # head-shard -> token-shard
        return jax.lax.all_to_all(out, SEQ_AXIS, split_axis=1,
                                  concat_axis=2, tiled=True)

    return _sharded_ctx_call(mesh, wrapped, q, k, v, q_valid, k_valid,
                             use_flash)


def ulysses_attn_fn(mesh: Mesh, causal_default: bool = False,
                    block_k: Optional[int] = None,
                    block_k_min: Optional[int] = None):
    """An `attn_fn` for ops.attention.multi_head_attention that routes
    through the all-to-all resharding. Signature matches
    dot_product_attention."""
    def fn(q, k, v, q_valid=None, k_valid=None, causal=causal_default,
           scale=None, window=None):
        return ulysses_attention_sharded(mesh, q, k, v, q_valid=q_valid,
                                         k_valid=k_valid, causal=causal,
                                         scale=scale, window=window,
                                         block_k=block_k,
                                         block_k_min=block_k_min)
    return fn
