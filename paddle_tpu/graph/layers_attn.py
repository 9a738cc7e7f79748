"""Multi-head scaled-dot-product attention layer.

NEW capability beyond the reference (whose attention story is the additive
`simple_attention` composite of fc/expand/sequence_softmax/scaling layers,
ref: python/paddle/trainer_config_helpers/networks.py:1257) — first-class
long-context attention with three execution paths picked automatically:

  * dense   — one fused einsum-softmax-einsum (short sequences),
  * flash   — fused pallas online-softmax kernel, score tiles resident in
    VMEM (long sequences on TPU; ops/pallas_attention.py),
  * blockwise — lax.scan online-softmax over key blocks, O(T) memory (the
    portable long-sequence fallback; ops/attention.py:blockwise_attention),
  * ring    — context parallelism when the executor's mesh has a `seq` axis
    of size > 1: each device holds a sequence shard and K/V rotate around
    the ICI ring (parallel/context.py:ring_attention_sharded),
  * ulysses — the all-to-all context-parallel alternative (explicit
    attn_impl='ulysses'): tokens reshard to heads, local full-sequence
    attention, reshard back (parallel/context.py:ulysses_attention_sharded)
    — prefer when heads >= the seq-axis size.
"""

from __future__ import annotations

import jax

from paddle_tpu.config.schema import LayerConfig
from paddle_tpu.graph.common import finish_layer
from paddle_tpu.graph.context import ForwardContext
from paddle_tpu.graph.registry import register_layer
from paddle_tpu.ops.attention import (
    blockwise_attention,
    dot_product_attention,
    multi_head_attention,
    project_out,
)
from paddle_tpu.parameter.argument import Argument

# beyond this many key positions, prefer the O(T)-memory flash/blockwise
# path.  The crossover (dense below 2k keys, dense out of memory by 16k)
# comes from a sweep since withdrawn, see ROADMAP S5 — not measured on this
# round's chip; override per layer with block_k_min
_BLOCKWISE_MIN_KEYS = 2048


def _project(ctx: ForwardContext, cfg: LayerConfig) -> dict:
    """The keywords of ops/attention.py:project_qkv a layer's attrs give,
    the same in every path: grouped-query heads, the rotation, and with
    `qk_norm` the two per-head RMSNorm scales (parameters 4 and 5)."""
    a = cfg.attrs
    kw = dict(num_kv_heads=int(a.get("num_kv_heads", 0) or a["num_heads"]),
              use_rope=bool(a.get("use_rope", False)),
              rope_theta=float(a.get("rope_theta", 10000.0)))
    # a partly rotated head, YaRN's frequencies, its factor on cos and sin
    if "rotary_dim" in a:
        kw["rotary_dim"] = int(a["rotary_dim"])
    if a.get("rope_scaling"):
        kw["rope_scaling"] = dict(a["rope_scaling"])
    if "attention_factor" in a:
        kw["attention_factor"] = float(a["attention_factor"])
    if a.get("qk_norm"):
        kw["qk_norm"] = (ctx.param_of(cfg, 4), ctx.param_of(cfg, 5),
                         float(a.get("rms_eps", 1e-6)))
    return kw


def _window(cfg: LayerConfig):
    """The layer's sliding window (keys i - window < j <= i), or None."""
    return int(cfg.attrs["window"]) if "window" in cfg.attrs else None


def _scope(cfg: LayerConfig):
    """The named scope of the layer's attention proper — `attn.window` or
    `attn.full` — so a device trace tells the two kinds' ops apart."""
    return jax.named_scope(
        "attn.window" if "window" in cfg.attrs else "attn.full")


def _table(cache: dict) -> dict:
    """The page table a paged step reads: the slots' logical table, or for
    a window layer the slots' RINGS (`ring_table`: serving/paged_kv.py
    "WINDOW LAYERS") with `ring` set."""
    if "ring_table" in cache:
        return {"page_table": cache["ring_table"], "ring": True}
    return {"page_table": cache["page_table"]}


def _gate(ctx: ForwardContext, cfg: LayerConfig):
    """The output gate's matrix where the layer has one (`out_gate`: its
    parameter's index; [d, heads * head_dim], or [d, heads] for the gate a
    head), else None."""
    gate = cfg.attrs.get("out_gate")
    return None if gate is None else ctx.param_of(cfg, int(gate))


def _out(ctx: ForwardContext, cfg: LayerConfig, o, x):
    """The end of the three cached paths (ops/attention.py:project_out, as
    the whole-sequence path's): the output projection and bias, behind the
    sigmoid gate where the layer has one."""
    return project_out(o, x, ctx.param_of(cfg, 3), ctx.bias_of(cfg),
                       _gate(ctx, cfg))


def _flash_blocks(cfg: LayerConfig) -> dict:
    """Flash-kernel block sizes a layer pins: its `block_q` / `block_k`
    attrs, else nothing — the kernel then derives its blocks from the shape
    (ops/pallas_attention.py:derive_blocks).  Used by BOTH the training
    path and the cached-decode prefill."""
    return {key: int(cfg.attrs[key]) for key in ("block_q", "block_k")
            if key in cfg.attrs}


@register_layer("multi_head_attention")
def multi_head_attention_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """inputs: [query, key, value, (query again carrying the out-proj param)];
    attrs: num_heads, causal, block_k, block_k_min, attn_impl,
    num_kv_heads (grouped-query), window (sliding-window),
    use_rope/rope_theta (rotary position embeddings)."""
    q_arg, k_arg, v_arg = (ctx.get_input(cfg, i) for i in range(3))
    w_q, w_k, w_v, w_o = (ctx.param_of(cfg, i) for i in range(4))
    num_heads = int(cfg.attrs["num_heads"])
    causal = bool(cfg.attrs.get("causal", False))

    cache = ctx.state_in.get(cfg.name)
    if isinstance(cache, dict) and "k_pages" in cache:
        # continuous-batching decode against the serving engine's paged KV
        # pool (serving/paged_kv.py): context read through the per-slot
        # page table — the fixed-signature step the engine compiles once
        # and reuses for the whole workload.  A cache carrying `row_slot`
        # is the MIXED prefill/decode step: query tokens packed into one
        # ragged row dimension (decode rows + prompt chunks), each row
        # addressing its own table row at its own position
        assert causal, f"layer {cfg.name!r}: paged decode requires causal"
        if "row_slot" in cache:
            return _paged_ragged_step(ctx, cfg, q_arg, w_q, w_k, w_v, num_heads,
                                      cache)
        return _paged_step(ctx, cfg, q_arg, w_q, w_k, w_v, num_heads, cache)
    if isinstance(cache, dict) and "k" in cache:
        # incremental decode against a KV cache (lm_decode use_cache path):
        # the input carries only NEW tokens; per-row positions come from the
        # cache, so caches ride the same state threading as BN moving stats
        assert causal, f"layer {cfg.name!r}: KV-cache decode requires causal"
        return _cached_step(ctx, cfg, q_arg, w_q, w_k, w_v, num_heads, cache)

    q_valid = q_arg.mask()
    k_valid = k_arg.mask()

    import functools

    mesh = ctx.mesh
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.parallel.context import (flash_attn_fn, ring_attn_fn,
                                             seq_axis_size, ulysses_attn_fn)
    impl = str(cfg.attrs.get("attn_impl", "auto"))
    if impl not in ("auto", "ring", "ulysses", "flash", "blockwise",
                    "dense"):
        raise ValueError(
            f"layer {cfg.name!r}: unknown attn_impl {impl!r} "
            f"(expected auto/ring/ulysses/flash/blockwise/dense)")
    if impl == "auto":
        if mesh is not None and seq_axis_size(mesh) > 1:
            impl = "ring"
        elif k_arg.max_len >= int(cfg.attrs.get("block_k_min",
                                                _BLOCKWISE_MIN_KEYS)):
            impl = "flash" if pallas_attention.supported() else "blockwise"
        else:
            impl = "dense"
    if impl in ("ring", "ulysses"):
        if mesh is None or seq_axis_size(mesh) < 2:
            raise ValueError(
                f"layer {cfg.name!r}: attn_impl={impl!r} needs the executor "
                f"mesh to have a `seq` axis of size >= 2 (got "
                f"{'no mesh' if mesh is None else dict(zip(mesh.axis_names, mesh.devices.shape))})")
        attn_fn = (ulysses_attn_fn(
                       mesh,
                       block_k=(int(cfg.attrs["block_k"])
                                if "block_k" in cfg.attrs else None),
                       block_k_min=(int(cfg.attrs["block_k_min"])
                                    if "block_k_min" in cfg.attrs else None))
                   if impl == "ulysses" else ring_attn_fn(mesh))
    elif impl == "flash":
        if not pallas_attention.supported():
            raise ValueError(
                f"layer {cfg.name!r}: attn_impl='flash' needs a TPU backend "
                f"(or PADDLE_TPU_PALLAS_INTERPRET=1 to opt into the slow "
                f"interpret mode); current backend is "
                f"{jax.default_backend()!r}")
        attn_fn = functools.partial(pallas_attention.flash_attention,
                                    **_flash_blocks(cfg))
        if mesh is not None and mesh.devices.size > 1:
            attn_fn = flash_attn_fn(mesh, attn_fn)
    elif impl == "blockwise":
        attn_fn = functools.partial(
            blockwise_attention, block_k=int(cfg.attrs.get("block_k", 512)))
    else:
        attn_fn = dot_product_attention

    out = multi_head_attention(
        q_arg.value, k_arg.value, v_arg.value,
        w_q, w_k, w_v, w_o, num_heads,
        q_valid=q_valid, k_valid=k_valid, causal=causal,
        bias_o=ctx.bias_of(cfg), attn_fn=attn_fn,
        window=_window(cfg), w_g=_gate(ctx, cfg),
        **_project(ctx, cfg))
    return finish_layer(ctx, cfg, out, like=q_arg)


def _cached_step(ctx: ForwardContext, cfg: LayerConfig, x_arg: Argument,
                 w_q, w_k, w_v, num_heads: int, cache: dict) -> Argument:
    """One incremental self-attention call: project the new tokens, fold
    them into this layer's KV cache, attend causally on global positions.
    Emits the updated cache through ctx.state_out."""
    import functools

    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.ops.attention import (blockwise_attention,
                                          cached_attention_step,
                                          dot_product_attention,
                                          project_qkv)

    x = x_arg.value                                   # [B, Tn, model_dim]
    B, Tn, _ = x.shape
    pos = cache["pos"]
    qpos = pos[:, None] + jnp.arange(Tn)[None, :]
    q, k, v = project_qkv(x, x, x, w_q, w_k, w_v, num_heads, q_pos=qpos,
                          k_pos=qpos, **_project(ctx, cfg))
    n_new = (x_arg.lengths.astype(jnp.int32) if x_arg.lengths is not None
             else jnp.full((B,), Tn, jnp.int32))
    window = _window(cfg)
    if Tn > 1:
        # prefill contract: a multi-token cached call starts from an EMPTY
        # cache (lm_decode feeds the whole prompt once), so attention over
        # the cache degenerates to plain causal self-attention — run it
        # through the impl-selected kernel (flash for long prompts) rather
        # than cached_attention_step, whose O(Tn*Tmax) dense scores and
        # one-hot scatter would defeat the cache at exactly the long
        # contexts it exists for; k/v land in the cache as a static slice.
        valid = (jnp.arange(Tn)[None, :] < n_new[:, None])
        # honor an explicit attn_impl like the regular forward does (a
        # config pinned to dense — e.g. to sidestep a pallas issue or for
        # a dense-vs-flash bench — must not silently get flash prefill);
        # 'ring'/'ulysses' have no cached-decode analog, so they fall
        # through to the local auto-selection
        impl = str(cfg.attrs.get("attn_impl", "auto"))
        if impl not in ("auto", "ring", "ulysses", "flash", "blockwise",
                        "dense"):
            raise ValueError(
                f"layer {cfg.name!r}: unknown attn_impl {impl!r} "
                f"(expected auto/ring/ulysses/flash/blockwise/dense)")
        long_prompt = Tn >= int(cfg.attrs.get("block_k_min",
                                              _BLOCKWISE_MIN_KEYS))
        if impl == "flash":
            if not pallas_attention.supported():
                raise ValueError(
                    f"layer {cfg.name!r}: attn_impl=flash needs a TPU "
                    f"backend (or PADDLE_TPU_PALLAS_INTERPRET=1 for "
                    f"interpret-mode tests)")
            attn = functools.partial(pallas_attention.flash_attention,
                                     **_flash_blocks(cfg))
        elif impl == "blockwise":
            attn = blockwise_attention
        elif impl == "dense":
            attn = dot_product_attention
        elif long_prompt and pallas_attention.supported():
            attn = functools.partial(pallas_attention.flash_attention,
                                     **_flash_blocks(cfg))
        elif long_prompt:
            attn = blockwise_attention
        else:
            attn = dot_product_attention
        out = attn(q, k, v, q_valid=valid, k_valid=valid, causal=True,
                   **({} if window is None else {"window": window}))
        ck = cache["k"].at[:, :Tn].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, :Tn].set(v.astype(cache["v"].dtype))
        newpos = pos + n_new
    else:
        out, ck, cv, newpos = cached_attention_step(
            q, k, v, cache["k"], cache["v"], pos, n_new, window=window)
    ctx.state_out[cfg.name] = {"k": ck, "v": cv, "pos": newpos}
    return finish_layer(ctx, cfg, _out(ctx, cfg, out, x), like=x_arg)


def _paged_step(ctx: ForwardContext, cfg: LayerConfig, x_arg: Argument,
                w_q, w_k, w_v, num_heads: int, cache: dict) -> Argument:
    """One serving decode micro-step: project each slot's single new token,
    scatter its k/v into the slot's current page of the shared pool, attend
    causally over the slot's paged context (ops/attention.py:
    paged_attention_step — page-table gather, or the Pallas ragged-paged
    kernel when supported).  Emits the updated pool through ctx.state_out;
    the page table itself is host-managed and passes through untouched."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import paged_attention_step, project_qkv

    x = x_arg.value                                   # [S, 1, model_dim]
    Tn = x.shape[1]
    assert Tn == 1, (f"layer {cfg.name!r}: paged decode feeds exactly one "
                     f"new token per slot (got {Tn}); prompts prefill "
                     f"through the dense per-request cache")
    pos = cache["pos"]
    qpos = pos[:, None]
    q, k, v = project_qkv(x, x, x, w_q, w_k, w_v, num_heads, q_pos=qpos,
                          k_pos=qpos, **_project(ctx, cfg))
    # a mesh with a `model` axis > 1 = tensor-parallel serving: the op
    # runs the write+read core under shard_map over the head shards
    with _scope(cfg):
        out, ck, cv = paged_attention_step(
            q, k, v, cache["k_pages"], cache["v_pages"], pos=pos,
            window=_window(cfg),
            use_kernel=(False if str(cfg.attrs.get("attn_impl", "auto"))
                        in ("dense", "blockwise") else None),
            mesh=ctx.mesh, **_table(cache))
    ctx.state_out[cfg.name] = dict(cache, k_pages=ck, v_pages=cv,
                                   pos=pos + 1)
    return finish_layer(ctx, cfg, _out(ctx, cfg, out, x), like=x_arg)


def _paged_ragged_step(ctx: ForwardContext, cfg: LayerConfig, x_arg: Argument,
                       w_q, w_k, w_v, num_heads: int,
                       cache: dict) -> Argument:
    """One MIXED prefill/decode step against the paged pool: the input is
    a packed ragged token list [1, T, model_dim] where row r is one token
    of page-table row `cache["row_slot"][r]` at global position
    `cache["row_pos"][r]` — live decode rows and in-flight prompt chunks
    in one dispatch (ops/attention.py:ragged_paged_attention_step; the
    Pallas row-indirected kernel when supported).  Emits the updated pool
    through ctx.state_out; table and row maps are host-managed and pass
    through untouched."""
    from paddle_tpu.ops.attention import (project_qkv,
                                          ragged_paged_attention_step)

    x = x_arg.value                                   # [1, T, model_dim]
    B = x.shape[0]
    assert B == 1, (f"layer {cfg.name!r}: the mixed paged step packs all "
                    f"query rows into one ragged batch row (got B={B})")
    row_pos = cache["row_pos"]                        # [T] global positions
    q, k, v = project_qkv(x, x, x, w_q, w_k, w_v, num_heads, q_pos=row_pos,
                          k_pos=row_pos, **_project(ctx, cfg))
    # mesh `model` axis > 1 = tensor-parallel mixed step (shard_map core)
    with _scope(cfg):
        out, ck, cv = ragged_paged_attention_step(
            q[0], k[0], v[0], cache["k_pages"], cache["v_pages"],
            row_slot=cache["row_slot"], row_pos=row_pos,
            window=_window(cfg),
            use_kernel=(False if str(cfg.attrs.get("attn_impl", "auto"))
                        in ("dense", "blockwise") else None),
            mesh=ctx.mesh, **_table(cache))
    ctx.state_out[cfg.name] = dict(cache, k_pages=ck, v_pages=cv)
    return finish_layer(ctx, cfg, _out(ctx, cfg, out, x), like=x_arg)


@register_layer("additive_attention_step")
def additive_attention_step_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """One fused Bahdanau attention step inside a decoder scan (the
    reference's simple_attention composite collapsed into a single layer —
    ref: networks.py:1257 fc/expand/addto/sequence-softmax/scaling/pool).

    inputs: [decoder_state [B,Ds] (carries W [Ds,D]),
             encoded_proj [B,T,D] static link (carries v [D,1]),
             encoded_sequence [B,T,Dv] static link];
    output: context [B, Dv].
    """
    dec = ctx.get_input(cfg, 0)
    proj = ctx.get_input(cfg, 1)
    seq = ctx.get_input(cfg, 2)
    w = ctx.param_of(cfg, 0)
    v = ctx.param_of(cfg, 1)
    lengths = proj.lengths if proj.lengths is not None else seq.lengths

    from paddle_tpu.ops.attention import additive_attention_step
    from paddle_tpu.ops import pallas_additive
    if pallas_additive.supported() and \
            str(cfg.attrs.get("attn_impl", "auto")) != "dense":
        # lengths flow straight into the kernel: the mask here is always a
        # length prefix, so the kernel's runtime contiguity guard (which
        # costs an O(B*T) check + lax.cond inside the decoder scan) is
        # statically unnecessary
        out = pallas_additive.additive_attention_step(
            dec.value, w, v.reshape(-1), proj.value, seq.value,
            lengths=lengths)
    else:
        mask = None
        if lengths is not None:
            mask = (proj.mask() if proj.lengths is not None else seq.mask())
        out = additive_attention_step(dec.value, w, v.reshape(-1),
                                      proj.value, seq.value, mask)
    return finish_layer(ctx, cfg, out, like=dec)


@register_layer("mla_attention")
def mla_attention_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Multi-head latent attention (ops/mla.py) — causal self-attention
    whose cache row is one latent `[c_kv, k_pe]` a token.

    inputs (all the one data input): w_qa [d, q_rank], q_norm [1, q_rank],
    w_qb [q_rank, H*(nope+rope)] — or, with q_lora_rank 0, the one matrix
    w_q [d, H*(nope+rope)] and no norm — then w_kva [d, kv_rank+rope],
    kv_norm [1, kv_rank], w_kvb [kv_rank, H*(nope+v)], w_o [H*v, d].
    attrs: num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim, rope_theta, rope_scaling (YaRN dict or
    absent), use_rope (false: the rope columns of the query and of the
    cache row are carried unrotated — NoPE), rms_eps, attn_impl.

    Three paths, picked by the state the executor hands in (as
    multi_head_attention): none = the whole sequence in the EXPANDED form;
    a paged latent pool (`kv_pages`, with `row_slot` for the mixed step) or
    a dense latent cache (`kv`) = the ABSORBED form over the cache."""
    import jax.numpy as jnp

    from paddle_tpu.graph.layers_misc import rms_norm
    from paddle_tpu.ops import mla

    x_arg = ctx.get_input(cfg, 0)
    a = cfg.attrs
    q_params = 3 if int(a.get("q_lora_rank", 0) or 0) else 1
    w_kva, g_kv, w_kvb, w_o = (
        ctx.param_of(cfg, q_params + i) for i in range(4))
    H = int(a["num_heads"])
    kv_rank = int(a["kv_lora_rank"])
    nope, rdim = int(a["qk_nope_head_dim"]), int(a["qk_rope_head_dim"])
    vdim = int(a["v_head_dim"])
    eps = float(a.get("rms_eps", 1e-6))
    scaling = a.get("rope_scaling") or None
    inv_freq = mla.yarn_inv_freq(rdim, float(a.get("rope_theta", 10000.0)),
                                 scaling)
    amp = mla.rope_amplitude(scaling)
    scale = mla.softmax_scale(nope + rdim, scaling)
    rotate = mla.rotate if bool(a.get("use_rope", True)) \
        else (lambda pe, *_: pe)
    assert bool(a.get("causal", True)), \
        f"layer {cfg.name!r}: latent attention is causal self-attention"

    x = x_arg.value                                        # [B, T, d]
    B, T, _ = x.shape
    cache = ctx.state_in.get(cfg.name)
    paged = isinstance(cache, dict) and "kv_pages" in cache
    dense = isinstance(cache, dict) and "kv" in cache
    if paged and "row_slot" in cache:
        pos = cache["row_pos"][None, :]                    # [1, T]
    elif paged or dense:
        pos = cache["pos"][:, None] + jnp.arange(T)[None, :]
    else:
        pos = jnp.arange(T)[None, :]

    with jax.named_scope("mla.project"):
        if q_params == 3:
            w_qa, g_q, w_qb = (ctx.param_of(cfg, i) for i in range(3))
            q = rms_norm(x @ w_qa, g_q, eps) @ w_qb
        else:
            q = x @ ctx.param_of(cfg, 0)
        q = q.reshape(B, T, H, nope + rdim)
        q_nope = q[..., :nope]
        q_pe = rotate(q[..., nope:], pos[..., None], inv_freq, amp)
        ckv = x @ w_kva
        c_kv = rms_norm(ckv[..., :kv_rank], g_kv, eps)
        k_pe = rotate(ckv[..., kv_rank:], pos, inv_freq, amp)
        rows = jnp.concatenate([c_kv, k_pe], axis=-1)      # [B, T, W]
        if paged or dense:       # a cache stores the row at whole lane tiles
            rows = mla.pad_lanes(
                rows, cache["kv_pages" if paged else "kv"].shape[-1])

    n_new = (x_arg.lengths.astype(jnp.int32) if x_arg.lengths is not None
             else jnp.full((B,), T, jnp.int32))
    whole = not paged and (not dense or T > 1)
    if whole:
        # the expanded form: plain H-head attention at width nope + rope
        # (v padded to that width so every impl, flash included, takes it)
        with jax.named_scope("mla.project"):
            kv = (c_kv @ w_kvb).reshape(B, T, H, nope + vdim)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe[:, :, None, :], (B, T, H, rdim))], -1)
            assert vdim <= nope + rdim, f"layer {cfg.name!r}: v_head_dim " \
                f"{vdim} exceeds the qk head width {nope + rdim}"
            v = mla.pad_lanes(kv[..., nope:], nope + rdim)
        valid = x_arg.mask() if not dense else \
            (jnp.arange(T)[None, :] < n_new[:, None])
        attend = _mla_whole_attn(ctx, cfg, T)
        with jax.named_scope("mla.attend"):
            o = attend(jnp.concatenate([q_nope, q_pe], -1), k, v,
                       q_valid=valid, k_valid=valid, causal=True,
                       scale=scale)[..., :vdim]
        if dense:
            ctx.state_out[cfg.name] = {
                "kv": cache["kv"].at[:, :T].set(
                    rows.astype(cache["kv"].dtype)),
                "pos": cache["pos"] + n_new}
    else:
        q_full = mla.pad_lanes(mla.absorb_query(q_nope, q_pe, w_kvb, nope),
                               rows.shape[-1])             # [B, T, H, W]
        with jax.named_scope("mla.attend"):
            if paged:
                ragged = "row_slot" in cache
                assert (B == 1) if ragged else (T == 1), \
                    f"layer {cfg.name!r}: a paged step feeds one token a " \
                    f"slot, or one packed ragged row list (got {x.shape})"
                R = B * T
                row_slot = cache["row_slot"] if ragged \
                    else jnp.arange(R, dtype=jnp.int32)
                row_pos = pos.reshape(R)
                o_lat, pool = mla.paged_latent_step(
                    q_full.reshape(R, H, -1), rows.reshape(R, -1),
                    cache["kv_pages"], cache["page_table"], row_slot,
                    row_pos, scale, kv_rank,
                    use_kernel=_mla_use_kernel(cfg))
                o_lat = o_lat.reshape(B, T, H, kv_rank)
                out_state = dict(cache, kv_pages=pool)
                if not ragged:
                    out_state["pos"] = cache["pos"] + 1
                ctx.state_out[cfg.name] = out_state
            else:
                o_lat, new = mla.cached_latent_step(
                    q_full, rows, cache["kv"], cache["pos"], n_new, scale,
                    kv_rank)
                ctx.state_out[cfg.name] = {"kv": new,
                                           "pos": cache["pos"] + n_new}
        with jax.named_scope("mla.project"):
            o = mla.expand_value(o_lat, w_kvb, nope)       # [B, T, H, v]
    with jax.named_scope("mla.project"):
        out = o.reshape(B, T, H * vdim).astype(x.dtype) @ w_o
    return finish_layer(ctx, cfg, out, like=x_arg)


def _mla_use_kernel(cfg: LayerConfig) -> bool:
    """The Pallas latent paged kernel unless the config pins the jnp path
    (attn_impl dense/blockwise, as for multi_head_attention)."""
    from paddle_tpu.ops import pallas_paged

    return pallas_paged.supported() and \
        str(cfg.attrs.get("attn_impl", "auto")) not in ("dense", "blockwise")


def _mla_whole_attn(ctx: ForwardContext, cfg: LayerConfig, T: int):
    """The whole-sequence attention impl for the expanded form: the
    config's attn_impl (dense / blockwise / flash), or by length."""
    import functools

    from paddle_tpu.ops import pallas_attention

    impl = str(cfg.attrs.get("attn_impl", "auto"))
    if impl not in ("auto", "flash", "blockwise", "dense"):
        raise ValueError(
            f"layer {cfg.name!r}: attn_impl {impl!r} is not one of "
            f"auto/flash/blockwise/dense (latent attention has no "
            f"context-parallel path)")
    if ctx.mesh is not None and ctx.mesh.devices.size > 1:
        raise ValueError(
            f"layer {cfg.name!r}: latent attention does not run on a mesh "
            f"yet (tensor- and context-parallel paths: ROADMAP)")
    if impl == "auto":
        impl = "dense" if T < _BLOCKWISE_MIN_KEYS else \
            ("flash" if pallas_attention.supported() else "blockwise")
    if impl == "flash":
        if not pallas_attention.supported():
            raise ValueError(
                f"layer {cfg.name!r}: attn_impl='flash' needs a TPU backend "
                f"(or PADDLE_TPU_PALLAS_INTERPRET=1)")
        return functools.partial(pallas_attention.flash_attention,
                                 **_flash_blocks(cfg))
    return blockwise_attention if impl == "blockwise" \
        else dot_product_attention
