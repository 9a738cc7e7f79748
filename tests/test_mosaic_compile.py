"""Ask the TPU v5e's compiler (Mosaic, via a DESCRIBED chip — nothing runs)
whether the Pallas kernels compile at the widths the demos use.

Interpret mode never checks Mosaic's block, tiling and VMEM rules, so a
kernel can pass every CPU test and be refused on first contact with the
chip.  These cases cost ~1-3 s each and no chip time.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture, never at import time; shapes and
shardings are built in fixtures/tests; compiles run in the test's own
process with the persistent compile cache off; all cases live in THIS one
file so a single xdist worker owns the TPU library.  The one exception is
the first test: Mosaic's dump flag is read when the library loads, so
`tools/kernel_lowering.py` is a process of its own — run BEFORE this
worker's `topo` fixture takes the library's lock (file order), and skipped
where the child cannot describe a topology either.
"""

import gc
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.mark.parametrize("shape,heads,stored,pages", [
    ("", 24, 2, 16),
    ("heads=48,kv_heads=8,rows=320,max_pages=512,pages=32769", 48, 8, 8),
    ("rows=280,heads=30,kv_heads=30,max_pages=576,pages=13825", 32, 32, 8),
    ("mla_paged_attn", 64, 1, 8),
], ids=["decode-saturated", "laguna-full-mixed-320-rows",
        "olmo-hybrid-mixed-280-rows", "latent-one-dense-operand"])
def test_paged_kernel_reads_a_block_as_dense_tiles(shape, heads, stored,
                                                   pages):
    """The lowering itself, at decode-saturated's shape (64 rows, 24 / 2
    heads of 128, page 16, bf16), at the Laguna full layer's mixed step
    (320 rows, 48 / 8 heads, 512 pages a table row) and at Olmo-Hybrid's
    (280 rows, 30 / 30 heads stored as 32, 576 pages): a block's pages land
    once in the matmul operand's own rows (the copies of PR 42: a page of 16
    x 2 heads is 2 + 2 vregs) and the kernel stores nothing but its output.
    The program holds two walks.  A ROW's reads K and V once as the dense
    operand they land as, its scores [heads, tokens x stored heads] with
    the other groups' columns masked.  A RUN of one slot's rows does the
    same with the tile's rows where a token's row holds four heads or fewer
    (decode-saturated's two: the parent's loads and stores plus the select's
    read-back); where it holds more it reads K and
    V once ONE STORED HEAD AT A TIME — sublane-strided loads of the
    buffer's uint32 view, every one a whole vreg of whole 32-bit rows (a
    pair of heads' even tokens, or odd) — and scores a block's tokens a
    dot: no value of the program is a tile's rows by the dense columns
    (Olmo's would be [1,024, 4,096] float32).  Olmo's K and V buffers are
    4 MB (two of 128 tokens x 32 heads x 128 lanes a pool) of the 16 MB
    Mosaic scopes a kernel on the v5e: it compiles.  ONE stored head (the
    latent kernel at GigaChat's 64 heads of 640 lanes) is one dense operand
    in both walks and no strided load: the parent's loads, stores and
    copies, 216 / 80 / 24, and a run's output stored under a select beside
    the tile's other runs' (the tile's 64 vregs read back)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "kernel_lowering.py")
    argv = [shape] if shape == "mla_paged_attn" else \
        ["paged_attn"] + [shape] * bool(shape)
    p = subprocess.run([sys.executable, tool] + argv, text=True,
                       capture_output=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = p.stdout.strip().splitlines()
    assert lines, p.stderr[-2000:]
    got = json.loads(lines[-1])
    if p.returncode == 3:
        pytest.skip(got["skipped"])
    assert p.returncode == 0, got
    assert got["pages_per_block"] == pages, got
    if stored == 1:
        tile = 4 * 64 * 512 * 2 // 4096         # 4 rows x 64 heads x 512
        assert (got["tpu.load"] - tile, got["tpu.store"],
                got["tpu.enqueue_dma"], got["strided_loads"],
                got["tile_rows"]) == (216, 80, 24, 0, 4), got
        return
    tokens, hp = pages * 16, -(-heads // 16) * 16
    kv = 2 * tokens * stored * 128 * 2 // 4096      # K and V of a block
    if stored == 2:
        # one dense operand in both walks, as the parent's program: the
        # run's scores are the tile's rows by every column, K and V are
        # loaded once a walk, q a row and a tile, and the tile's output is
        # read back under the runs' select
        assert (got["tile_rows"], got["strided_loads"]) == (8, 0), got
        assert got["tpu.enqueue_dma"] == 3 * 2 * pages, got
        assert (got["f32_cols"], got["f32_rows_at_cols"]) == \
            (tokens * stored, 8 * hp), got
        assert got["tpu.store"] <= 9 * hp * 128 * 4 // 4096, got
        assert got["vregs_loaded"] <= 2 * kv + (9 + 8) * hp * 128 * 2 \
            // 4096, got
        assert got["tpu.load"] <= 2 * got["vregs_loaded"], got
        return
    # the rows that fill a dot's 128 (32 in Olmo's budget), 8 at least
    bq = got["tile_rows"]
    assert bq == min(32, max(8, 1 << (128 * stored // heads).bit_length()
                             - 1)), got
    # K's and V's copies of a block, at the three places a fetch starts:
    # the call's first, and each walk's next
    assert got["tpu.enqueue_dma"] == 3 * 2 * pages, got
    assert (got["f32_cols"], got["f32_rows_at_cols"]) == \
        (tokens * stored, hp), got
    # vregs (4 KB): K and V of a block; a row's heads of q and of the
    # output, a run's groups — each the tile's rows x the group's heads in
    # whole tiles of 16 —, stored under a select beside the tile's other
    # runs' (read back)
    run = stored * -(-bq * heads // stored // 16) * 16
    # the output's tiles (half a vreg a store at most), nothing through
    # scratch
    assert got["tpu.store"] <= (hp + run) * 128 * 4 // 4096, got
    # a block once a walk; the run's in whole vregs
    assert got["strided_loads"] == got["strided_loads_whole"] == kv, got
    assert got["vregs_loaded"] <= 2 * kv + \
        (hp + 2 * run) * 128 * 2 // 4096, got
    assert got["tpu.load"] <= kv + 2 * (got["vregs_loaded"] - kv), got


@pytest.mark.parametrize("heads", [32, 64])
def test_kda_seg_kernel_moves_whole_vregs_and_only_its_state(heads):
    """What Mosaic made of `kda_seg` at the two KDA cells' chunk rows (192 x
    32 heads, x 64; tools/kernel_lowering.py, a process of its own as
    above): two copies and no more — a run's state in, the state out —, every
    load a whole vreg (the chunk's windows at a run's own first row are
    dynamic sublane offsets of one-lane-tile operands: a relayout would show
    as hundreds of loads of single sublanes), the loads a chunk's operands,
    its sums' ones and the solve's rows and columns, and the stores the
    call's zeros (the output block, a run's state from position 0) plus a
    chunk's results and the solve's rows."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "kernel_lowering.py")
    p = subprocess.run([sys.executable, tool, "kda_seg", f"heads={heads}"],
                       text=True, capture_output=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = p.stdout.strip().splitlines()
    assert lines, p.stderr[-2000:]
    got = json.loads(lines[-1])
    if p.returncode == 3:
        pytest.skip(got["skipped"])
    assert p.returncode == 0, got
    assert got["tpu.enqueue_dma"] == 2, got
    # whole vregs but for the solve's 63 final rows u_j, one sublane each
    assert got["tpu.load"] - got["vregs_loaded"] <= 63 * 7 / 8 + 1e-6, got
    # 5 operands of 8 vregs, the state 16, the sums' ones 56, and a step of
    # the solve its rows below, their column of A and u_j: 751 at this
    # writing
    assert got["tpu.load"] <= 800, got
    # the output block's zeros 512, a state's 256, the ones 56, a chunk's
    # A, state and output 32, the solve's rows 288: 1,144 at this writing
    assert got["tpu.store"] <= 1200, got


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip — keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(one_chip, no_persistent_cache, monkeypatch):
    """Compile `fn` for one described v5e chip and require a Mosaic kernel
    in the result.  The kernels ask `jax.default_backend()` (sees `cpu`
    here) to pick interpret mode — steer that from the test."""
    from paddle_tpu.ops import (pallas_additive, pallas_attention,
                                pallas_hyper_conn, pallas_kda, pallas_paged,
                                pallas_rnn)
    for mod in (pallas_additive, pallas_attention, pallas_hyper_conn,
                pallas_kda, pallas_paged, pallas_rnn):
        monkeypatch.setattr(mod, "_interpret", lambda: False)

    def compile_(fn, *shapes, donate=()):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return compile_


bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


def kernel_names(compiled) -> list[str]:
    """The instruction names of the Mosaic custom calls in a compiled
    program: what a profiler trace shows as the device op's name (and what
    benchmark/layer_metrics/flash_*_ms_per_step.train.py match)."""
    import re
    return sorted(re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call",
                             compiled.as_text()))


# ---------------------------------------------------------------------------
# flash attention — the LM trainer's kernel (B=64, H=8, T=512, D=64, bf16)
# ---------------------------------------------------------------------------

FLASH_CASES = {
    "lm": dict(B=64, T=512, H=8, H_kv=8),
    "gqa": dict(B=64, T=512, H=8, H_kv=2),
    "ragged_T500": dict(B=64, T=500, H=8, H_kv=8),
    # the train cells' own shape (benchmark/configs/starcoder2-3b-train.json:
    # 2 sequences of 4,096 a chip, 24 query heads over 2 KV heads of 128)
    "train_cell": dict(B=2, T=4096, H=24, H_kv=2, D=128),
    # graph/layers_attn.py:mla_attention's expanded whole-sequence path:
    # 64 heads, qk 128 + 64 = 192 and v padded to it (256 lanes in VMEM)
    "latent_head": dict(B=2, T=4096, H=64, H_kv=64, D=192),
    # fp32 inputs: fp32 operands at Precision.HIGHEST, (8, 128) tiles
    "fp32": dict(B=2, T=4096, H=24, H_kv=2, D=128, dtype=f32),
}
#: the two ways a caller sizes the blocks: pinned (a layer's attrs, the
#: kernel's old default) and derived from the shape (no argument)
BLOCKS = {"pinned128": dict(block_q=128, block_k=128), "derived": {}}
#: the cases that predate the derived rule stay pinned at 128 as they were;
#: every case also compiles at the blocks the rule gives its shape
PINNED = ["lm", "gqa", "ragged_T500", "train_cell"]
FLASH_PARAMS = [(c, "pinned128") for c in PINNED] \
    + [(c, "derived") for c in FLASH_CASES]


def _flash_shapes(B, T, H, H_kv, D=64, dtype=bf16):
    return [((B, T, H, D), dtype), ((B, T, H_kv, D), dtype),
            ((B, T, H_kv, D), dtype)]


@pytest.mark.parametrize("case,blocks", FLASH_PARAMS)
def test_flash_forward(mosaic, case, blocks):
    from paddle_tpu.ops.pallas_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, **BLOCKS[blocks])

    mosaic(fwd, *_flash_shapes(**FLASH_CASES[case]))


@pytest.mark.parametrize("case,blocks", FLASH_PARAMS)
def test_flash_backward(mosaic, case, blocks):
    from paddle_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, **BLOCKS[blocks])
        return jnp.sum(o.astype(f32))

    mosaic(jax.grad(loss, argnums=(0, 1, 2)),
           *_flash_shapes(**FLASH_CASES[case]))


@pytest.mark.parametrize("blocks", list(BLOCKS))
@pytest.mark.parametrize("case,batch", [("lm", 64), ("train_cell", 8)])
def test_flash_under_data_mesh(topo, no_persistent_cache, monkeypatch,
                               case, batch, blocks):
    """`--mesh_shape=data:4` with attn_impl=flash: lowering the bare kernel
    over a 4-chip mesh raises "Mosaic kernels cannot be automatically
    partitioned"; parallel/context.py:flash_attn_fn wraps it in shard_map.
    Forward and backward for four described chips, at the LM trainer's
    shape and at the seq4k-dp4 cell's (8 sequences a step, 2 a chip)."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.parallel.context import flash_attn_fn
    from paddle_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    mesh = make_mesh(data=4, devices=topo.devices)
    attn = flash_attn_fn(mesh, functools.partial(
        pallas_attention.flash_attention, **BLOCKS[blocks]))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, causal=True).astype(f32))

    sh = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for s, d in _flash_shapes(**dict(FLASH_CASES[case], B=batch))]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # under shard_map too each kernel is told apart by its own name
    names = kernel_names(compiled)
    assert len(names) == 3
    for want in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(want in n for n in names) == 1, names


def test_flash_ring_shard_pair_with_traced_offsets(mosaic):
    """ops/attention.py:ring_attention's call: traced q_offset / k_offset
    (prefetched scalars the index maps read), return_lse, an lse cotangent,
    derived blocks — forward and backward for the chip."""
    from paddle_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v, q_off, k_off):
        o, lse = flash_attention(q, k, v, causal=True, q_offset=q_off[0],
                                 k_offset=k_off[0], return_lse=True)
        return jnp.sum(o.astype(f32)) + jnp.sum(
            jnp.where(jnp.isfinite(lse), lse, 0.0))

    mosaic(jax.grad(loss, argnums=(0, 1, 2)),
           *_flash_shapes(B=2, T=2048, H=8, H_kv=2, D=128),
           ((1,), i32), ((1,), i32))


def test_flash_sliding_window(mosaic):
    """Causal + sliding window at the train cells' shape (the published
    window of 4,096 over a longer sequence is ROADMAP B8): the index maps
    clamp both edges of the band; forward and backward, derived blocks."""
    from paddle_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=1024)
        return jnp.sum(o.astype(f32))

    mosaic(jax.grad(loss, argnums=(0, 1, 2)),
           *_flash_shapes(**FLASH_CASES["train_cell"]))


def test_flash_kernels_carry_their_names(mosaic):
    """Forward, dq and dk/dv are three Pallas calls with three names, so a
    trace's device_ops say which kernel took the time (they were `jvp__` /
    `transpose_jvp__`, two calls under one name)."""
    from paddle_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
        return jnp.sum(o.astype(f32))

    names = kernel_names(mosaic(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                                *_flash_shapes(**FLASH_CASES["gqa"])))
    assert len(names) == 3
    for want in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(want in n for n in names) == 1, names


# ---------------------------------------------------------------------------
# paged decode — the serving engine's kernel (16 slots, page 16, context 768)
# ---------------------------------------------------------------------------

S, PAGE, CTX, H_KV, DH = 16, 16, 768, 8, 64
MAXP = CTX // PAGE
POOL = S * MAXP + 1               # worst-case pool + the trash page


def _pool_shapes():
    return [((POOL, PAGE, H_KV, DH), bf16), ((POOL, PAGE, H_KV, DH), bf16),
            ((S, MAXP), i32)]


def test_paged_decode(mosaic):
    from paddle_tpu.ops import pallas_paged

    def step(q, kp, vp, table, lengths):
        return pallas_paged.paged_attention(q, kp, vp, table, lengths)

    mosaic(step, ((S, 8, DH), bf16), *_pool_shapes(), ((S,), i32))


def test_paged_decode_through_attention_step(mosaic):
    """The call the engine makes (ops/attention.py:paged_attention_step):
    scatter the new token's k/v, then the kernel reads the pool."""
    from paddle_tpu.ops.attention import paged_attention_step

    def step(q, k, v, kp, vp, table, pos):
        return paged_attention_step(q, k, v, kp, vp, table, pos,
                                    use_kernel=True)

    mosaic(step, ((S, 1, 8, DH), bf16), ((S, 1, H_KV, DH), bf16),
           ((S, 1, H_KV, DH), bf16), *_pool_shapes(), ((S,), i32))


def test_paged_mixed_rows(mosaic):
    """Row-indirected mixed prefill/decode (and spec-verify) form at the
    engine's default max_step_tokens = prefill_chunk + slots = 4*16 + 16."""
    from paddle_tpu.ops.attention import ragged_paged_attention_step
    T = 4 * PAGE + S

    def step(q, k, v, kp, vp, table, row_slot, row_pos):
        return ragged_paged_attention_step(q, k, v, kp, vp, table, row_slot,
                                           row_pos, use_kernel=True)

    mosaic(step, ((T, 8, DH), bf16), ((T, H_KV, DH), bf16),
           ((T, H_KV, DH), bf16), *_pool_shapes(), ((T,), i32), ((T,), i32))


# the serve cells' own shapes (benchmark/configs/starcoder2-3b-serve.json:
# 64 slots, page 16, context 4,096, 24 query / 2 KV heads of 128, bf16)
CELL = dict(S=64, PAGE=16, MAXP=4096 // 16, H=24, H_KV=2, D=128,
            POOL=16384)


@pytest.mark.parametrize("form", ["decode", "mixed-128-rows"])
def test_paged_kernel_at_the_serve_cells_shapes(mosaic, form):
    """Decode (64 rows) and the mixed step (prefill_chunk 64 + 64 slots =
    128 rows, the table carrying its virtual trash row) through the calls
    the engine makes, pools in HBM as the engine holds them."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    c = CELL
    pools = [((c["POOL"], c["PAGE"], c["H_KV"], c["D"]), bf16)] * 2
    if form == "decode":
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, c["H"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, c["MAXP"]), i32), ((S,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        T = 64 + c["S"]
        compiled = mosaic(
            step, ((T, c["H"], c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, c["MAXP"]), i32), ((T,), i32), ((T,), i32),
            donate=(3, 4))
    # one Pallas call a layer a step, and — the pools donated, as the
    # engine's steps donate their state — no copy of a pool on its way in
    # (a layout the kernel could not take would cost 2 x 134 MB a layer)
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    import re
    made_by = re.findall(r"= bf16\[16384,16,2,128\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


# lfm2-24b-serve.long-output-256's own shapes (benchmark/configs/
# lfm2-24b-a2b-serve.json: 256 slots, page 16, context 4,096, 32 query heads
# in 8 groups of 4 over 8 KV heads of 64 — stored two heads a 128-lane tile,
# ops/pallas_paged.py:kv_row_shape — bf16)
NARROW = dict(S=256, PAGE=16, MAXP=4096 // 16, H=32, H_KV=8, D=64,
              POOL=256 * 256 + 1)


@pytest.mark.parametrize("rows", [256, 512], ids=["decode", "mixed-512-rows"])
def test_paged_kernel_at_head_64_in_groups_of_4(mosaic, rows):
    """Decode (256 rows) and the mixed step (256 single rows + two chunks
    of 128) through the calls the engine makes, the pool of 64-wide heads
    packed [P, 16, 4, 128] as the cache manager holds it: one kernel, the
    pools donated and never copied or padded, lane-dense in HBM."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    c = NARROW
    row = kv_row_shape(c["H_KV"], c["D"])
    assert row == (4, 128)
    pools = [((c["POOL"], c["PAGE"]) + row, bf16)] * 2
    if rows == c["S"]:
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, c["H"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, c["MAXP"]), i32), ((S,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        T = rows
        compiled = mosaic(
            step, ((T, c["H"], c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, c["MAXP"]), i32), ((T,), i32), ((T,), i32),
            donate=(3, 4))
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    import re
    text = compiled.as_text()
    made_by = re.findall(r"= bf16\[65537,16,4,128\]\S* ([\w-]+)\(", text)
    assert made_by and "copy" not in made_by and "pad" not in made_by, made_by
    # the pool's HBM layout holds its elements' bytes and no more
    assert "bf16[65537,16,4,128]{3,2,1,0:T(4,128)(2,1)}" in text
    pool_bytes = 65537 * 16 * 4 * 128 * 2
    assert compiled.memory_analysis().argument_size_in_bytes < \
        2 * pool_bytes * 1.01


@pytest.mark.parametrize("rows,form", [(512, "grouped"), (256, "dense")],
                         ids=["mixed-512-rows", "decode-256-rows"])
def test_expert_block_at_the_cells_shapes(one_chip, no_persistent_cache,
                                          rows, form):
    """The LFM2 cell's expert block as parallel/moe.py's rule forms it —
    the 512-row mixed step grouped (2,048 routed pairs, 64 held experts of
    3 x 2048 x 1536, bf16), the 256-row decode step dense — compiled for
    the chip: the 1.2 GB weight stack is taken as it is stored (no copy,
    transpose or pad of it), the grouped form's buffers are tens of MB, and
    NO Pallas call is added (the benchmark's paged-kernel reader counts
    every custom call of a serve step as the paged kernel)."""
    import re
    from paddle_tpu.parallel import moe
    h, D, H, k = 64, 2048, 1536, 4
    assert moe.expert_form(rows, k, h, 2) == form

    def block(x, w_r, experts):
        return moe.moe_ffn(x, w_r, experts, top_k=k, scoring="sigmoid")[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(block).lower(
        arg((rows, D), bf16), arg((D, h), f32),
        (arg((h, D, H), bf16), arg((h, D, H), bf16), arg((h, H, D), bf16))
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert ("while(" in text) == (form == "grouped")
    stack = [line for line in text.splitlines() if re.search(
        rf"= bf16\[{h},(?:{D},{H}|{H},{D})\]", line)]
    made_by = {re.search(r"\} ([\w-]+)\(", line).group(1) for line in stack}
    assert made_by and not made_by & {"copy", "transpose", "pad"}, made_by
    assert all("bitcast_fusion" in line for line in stack
               if " fusion(" in line), stack
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 128 * 2 ** 20, temp


def test_overflow_tile_reads_one_experts_weights(one_chip,
                                                 no_persistent_cache):
    """The Xing cell's mixed step through the grouped form (1,088 rows, 64
    held experts of 3 x 3584 x 1024, bf16: a first round of 192 slots an
    expert, then the loop over the overflow's tiles), compiled for the
    chip: inside the loop the 1.41 GB of stacked weights are operands of
    dynamic slices alone — no copy, transpose, pad or whole-stack product a
    tile — and a tile's own buffers are an expert's size, not the stack's."""
    import re
    from paddle_tpu.parallel import moe
    rows, h, D, H, k = 1088, 64, 3584, 1024, 4
    assert moe.expert_form(rows, k, h, 2) == "grouped"
    assert moe.first_round_slots(rows, k, h) == 192

    def block(x, w_r, experts):
        return moe.moe_ffn(x, w_r, experts, top_k=k, scoring="sigmoid")[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(block).lower(
        arg((rows, D), bf16), arg((D, h), f32),
        (arg((h, D, H), bf16), arg((h, D, H), bf16), arg((h, H, D), bf16))
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and text.count(" while(") == 1
    stack = rf"bf16\[{h},(?:{D},{H}|{H},{D})\]"
    # the computations of the loop: its body, and the fusions whose ops
    # were traced inside it
    comps = re.split(r"\n(?=%|ENTRY )", text)
    body = re.search(r" while\(.*body=(%[\w.-]+)", text).group(1)
    in_loop = [c for c in comps if c.startswith(body + " ")
               or "/while/body/" in c and not c.startswith("ENTRY")]
    assert len(in_loop) > 3
    sliced = 0
    for comp in in_loop:
        lines = comp.splitlines()[1:]
        for line in lines:
            made = re.search(rf"(%[\w.-]+) = {stack}\S* ([\w-]+)\(", line)
            if made is None:
                continue
            # the stack is handed on as it is (the loop's tuple, a fusion's
            # parameter, a bitcast): nothing in the loop MAKES one, and
            # what takes one slices it or hands it to a fusion that does
            name, op = made.groups()
            assert op in ("parameter", "get-tuple-element", "bitcast"), line
            for user in lines:
                took = re.search(rf"= \S+ ([\w-]+)\(.*{re.escape(name)}[,)]",
                                 user)
                if took is not None and user is not line:
                    assert took.group(1) in ("dynamic-slice", "bitcast",
                                             "fusion", "tuple"), user
                    sliced += took.group(1) == "dynamic-slice"
    assert sliced >= 3, sliced              # gate, up and down, one expert's
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 160 * 2 ** 20, temp


# the latent cell's own shapes (benchmark/configs/
# gigachat3.1-702b-a36b-serve.json: 64 slots, page 16, context 4,096, 64
# query heads against ONE 576-wide latent row stored 640 wide, its first 512
# columns the value, bf16)
LATENT = dict(S=64, PAGE=16, MAXP=4096 // 16, H=64, W=576, WP=640, V=512,
              POOL=16385)


@pytest.mark.parametrize("rows", [64, 128], ids=["decode", "mixed-128-rows"])
def test_latent_paged_kernel_at_the_cells_shape(mosaic, rows):
    """The decode step (64 rows) and the mixed step (128 rows) of the
    latent pool through the call the layer makes (ops/mla.py:
    paged_latent_step): one `mla_paged_attn` call, and — the pool donated —
    no copy of the pool on its way in (its rows are taken as they are
    stored)."""
    from paddle_tpu.ops import mla
    c = LATENT

    def step(q, new, pool, table, row_slot, row_pos):
        return mla.paged_latent_step(q, new, pool, table, row_slot, row_pos,
                                     0.1, c["V"], use_kernel=True)

    compiled = mosaic(
        step, ((rows, c["H"], c["WP"]), bf16), ((rows, c["WP"]), bf16),
        ((c["POOL"], c["PAGE"], c["WP"]), bf16),
        ((c["S"] + 1, c["MAXP"]), i32), ((rows,), i32), ((rows,), i32),
        donate=(2,))
    assert kernel_names(compiled) == ["mla_paged_attn.1"], \
        kernel_names(compiled)
    import re
    made_by = re.findall(r"= bf16\[16385,16,640\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


# the hybrid linear-attention cell's own shapes (benchmark/configs/
# kimi-linear-48b-a3b-serve.json: 128 slots, 32 KDA heads whose state is
# 128 x 128 float32; 32 query heads against the 576-wide latent row)
KDA = dict(S=128, H=32, D=128)


@pytest.mark.parametrize("rows", ["decode", "mixed-rows"])
def test_kda_step_kernel_at_the_cells_shape(mosaic, rows):
    """The KDA decode step through the call the layer makes
    (ops/kda.py: step_rows): one `kda_step` call, and — the state pool
    donated and aliased — no copy of the 270 MB pool on its way in."""
    from paddle_tpu.ops import kda
    c = KDA
    R, H, D = c["S"], c["H"], c["D"]

    def step(state, slot, live, q, k, v, g, beta):
        return kda.step_rows(state, None if rows == "decode" else slot,
                             live, q, k, v, g, beta, use_kernel=True)

    vec = ((R, H, D), f32)
    compiled = mosaic(step, ((c["S"] + 1, H, D, D), f32), ((R,), i32),
                      ((R,), jnp.bool_), vec, vec, vec, vec, ((R, H), f32),
                      donate=(0,))
    assert kernel_names(compiled) == ["kda_step.1"], kernel_names(compiled)
    import re
    made_by = re.findall(r"= f32\[129,32,128,128\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


def test_latent_paged_kernel_at_32_heads(mosaic):
    """`mla_paged_attn` at the hybrid cell's head count: 128 decode rows of
    32 query heads against the same 640-lane latent row."""
    from paddle_tpu.ops import mla
    c = dict(LATENT, S=128, H=32, POOL=128 * 256 + 1)

    def step(q, new, pool, table, row_slot, row_pos):
        return mla.paged_latent_step(q, new, pool, table, row_slot, row_pos,
                                     0.1, c["V"], use_kernel=True)

    compiled = mosaic(
        step, ((128, c["H"], c["WP"]), bf16), ((128, c["WP"]), bf16),
        ((c["POOL"], c["PAGE"], c["WP"]), bf16),
        ((c["S"] + 1, c["MAXP"]), i32), ((128,), i32), ((128,), i32),
        donate=(2,))
    assert kernel_names(compiled) == ["mla_paged_attn.1"], \
        kernel_names(compiled)


@pytest.mark.parametrize("form", ["decode", "mixed"])
def test_paged_kernel_carries_its_name(mosaic, form):
    """The paged kernel is `paged_attn` in both forms (it was
    `_decode_impl` / `_mixed_impl`, the jitted step's name)."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    T = 4 * PAGE + S
    if form == "decode":
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        compiled = mosaic(step, ((S, 1, 8, DH), bf16),
                          ((S, 1, H_KV, DH), bf16), ((S, 1, H_KV, DH), bf16),
                          *_pool_shapes(), ((S,), i32))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        compiled = mosaic(step, ((T, 8, DH), bf16), ((T, H_KV, DH), bf16),
                          ((T, H_KV, DH), bf16), *_pool_shapes(),
                          ((T,), i32), ((T,), i32))
    names = kernel_names(compiled)
    assert names and all("paged_attn" in n for n in names), names


# ---------------------------------------------------------------------------
# fused recurrent kernels — sentiment LSTM, seq2seq GRU
# ---------------------------------------------------------------------------

def _lstm_loss(x4, lengths, w, peeps, h0, c0):
    from paddle_tpu.ops.pallas_rnn import lstm_fused
    hs, h_last, c_last = lstm_fused(
        x4, lengths, w, peeps, h0, c0, active_type="tanh",
        gate_active_type="sigmoid", state_active_type="tanh", reverse=False)
    return jnp.sum(hs) + jnp.sum(h_last) + jnp.sum(c_last)


def _lstm_shapes(B, T, D):
    return [((B, T, 4 * D), f32), ((B,), i32), ((D, 4 * D), f32),
            ((3, D), f32), ((B, D), f32), ((B, D), f32)]


def test_lstm_forward(mosaic):
    mosaic(_lstm_loss, *_lstm_shapes(128, 100, 512))


def test_lstm_backward(mosaic):
    mosaic(jax.grad(_lstm_loss, argnums=(0, 2, 3, 4, 5)),
           *_lstm_shapes(128, 100, 512))


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ... "
    "Scoped allocation with size 23.02M and limit 16.00M — the [D,4D] fp32 "
    "weight plus the per-step [B,4D] blocks at D=1024, B=128 (ROADMAP S1)"))
def test_lstm_forward_d1024_exceeds_scoped_vmem(mosaic):
    mosaic(_lstm_loss, *_lstm_shapes(128, 100, 1024))


def _gru_loss(x3, lengths, wg, wc, h0):
    from paddle_tpu.ops.pallas_rnn import gru_fused
    hs, h_last = gru_fused(x3, lengths, wg, wc, h0, active_type="tanh",
                           gate_active_type="sigmoid", reverse=False)
    return jnp.sum(hs) + jnp.sum(h_last)


def _gru_shapes(B, T, D):
    return [((B, T, 3 * D), f32), ((B,), i32), ((D, 2 * D), f32),
            ((D, D), f32), ((B, D), f32)]


def test_gru_forward(mosaic):
    mosaic(_gru_loss, *_gru_shapes(64, 30, 512))


def test_gru_backward(mosaic):
    mosaic(jax.grad(_gru_loss, argnums=(0, 2, 3, 4)),
           *_gru_shapes(64, 30, 512))


# ---------------------------------------------------------------------------
# fused additive-attention step — seq2seq decoder (B=64, T=30, D=512)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [bf16, f32], ids=["bf16", "fp32"])
def test_additive_step(mosaic, dtype):
    from paddle_tpu.ops.pallas_additive import additive_attention_step
    B, T, D = 64, 30, 512

    def step(dec, w, v, proj, seq, lengths):
        return additive_attention_step(dec, w, v, proj, seq, lengths=lengths)

    mosaic(step, ((B, D), dtype), ((D, D), dtype), ((D,), dtype),
           ((B, T, D), dtype), ((B, T, D), dtype), ((B,), i32))


# nemotron3-nano-30b-serve.long-output-256's own shapes (benchmark/configs/
# nemotron3-nano-30b-a3b-serve.json: 256 slots; 64 Mamba-2 heads in 8 groups
# whose state is 64 x 128 float32; 32 query heads in 2 groups of 16 over 2
# KV heads of 128, page 16, context 4,096, bf16)
SSD = dict(S=256, H=64, P=64, N=128, G=8)
WIDE_GROUPS = dict(S=256, PAGE=16, MAXP=4096 // 16, H=32, H_KV=2, D=128,
                   POOL=256 * 256 + 1)


@pytest.mark.parametrize("rows", ["decode", "mixed-rows"])
def test_ssd_step_kernel_at_the_cells_shape(mosaic, rows):
    """The Mamba-2 decode step through the call the layer makes
    (ops/ssd.py: step_rows): one call, under the name `ssd_step` — the
    body it shares with `kda_step` shows under its own name, which the
    benchmark's `ssd_step_roofline.serve` matches — and, the 539 MB state
    pool donated and aliased, no copy of it on its way in."""
    from paddle_tpu.ops import ssd
    c = SSD
    R, H, P, N, G = c["S"], c["H"], c["P"], c["N"], c["G"]

    def step(state, slot, live, x, Bm, Cm, dt, A):
        return ssd.step_rows(state, None if rows == "decode" else slot,
                             live, x, Bm, Cm, dt, A, use_kernel=True)

    compiled = mosaic(step, ((c["S"] + 1, H, P, N), f32), ((R,), i32),
                      ((R,), jnp.bool_), ((R, H, P), f32), ((R, G, N), f32),
                      ((R, G, N), f32), ((R, H), f32), ((H,), f32),
                      donate=(0,))
    assert kernel_names(compiled) == ["ssd_step.1"], kernel_names(compiled)
    import re
    made_by = re.findall(r"= f32\[257,64,64,128\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


@pytest.mark.parametrize("rows", [256, 512], ids=["decode", "mixed-512-rows"])
def test_paged_kernel_at_head_128_in_groups_of_16(mosaic, rows):
    """`paged_attn` at 32 query heads over 2 KV heads of 128 (groups of 16;
    StarCoder2 runs 12), at the decode step's 256 rows and the mixed step's
    512: one kernel, the pools donated and never copied or padded."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    c = WIDE_GROUPS
    row = kv_row_shape(c["H_KV"], c["D"])
    assert row == (2, 128)
    pools = [((c["POOL"], c["PAGE"]) + row, bf16)] * 2
    if rows == c["S"]:
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, c["H"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, c["MAXP"]), i32), ((S,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        T = rows
        compiled = mosaic(
            step, ((T, c["H"], c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, c["MAXP"]), i32), ((T,), i32), ((T,), i32),
            donate=(3, 4))
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    import re
    text = compiled.as_text()
    made_by = re.findall(r"= bf16\[65537,16,2,128\]\S* ([\w-]+)\(", text)
    assert made_by and "copy" not in made_by and "pad" not in made_by, made_by


# jamba2-3b-serve.long-output-256's own shapes (benchmark/configs/
# jamba2-3b-serve.json: 256 slots, d_in 5,120 channels of 16 state elements,
# float32 state [16, 5120] a slot; chunks of 128 in the 256 chunk rows of a
# 512-token mixed step; page 16, context 4,096, 20 query heads over ONE KV
# head of 128, bf16)
SCAN = dict(S=256, N=16, D_IN=5120, P=256)
MQA = dict(S=256, PAGE=16, MAXP=4096 // 16, H=20, H_KV=1, D=128,
           POOL=256 * 256 + 1)


@pytest.mark.parametrize("rows", ["decode", "mixed-rows"])
def test_selective_scan_step_at_the_cells_shape(mosaic, rows):
    """256 runs of one token through the call the layer makes
    (ops/selective_scan.py: step_rows; the kernel asks ops/pallas_kda.py's
    interpret switch, which `mosaic` steers): one kernel under the name
    `selective_scan_step`, the 1.35 GB state pool donated and aliased —
    no copy of it on its way in."""
    from paddle_tpu.ops import selective_scan as ss
    c = SCAN
    R, N, d = c["S"], c["N"], c["D_IN"]

    def step(state, slot, live, x, Bm, Cm, dt, A):
        return ss.step_rows(state, None if rows == "decode" else slot, live,
                            x, Bm, Cm, dt, A, use_kernel=True)

    compiled = mosaic(
        step, ((c["S"] + 1, N, d), f32), ((R,), i32), ((R,), jnp.bool_),
        ((R, d), f32), ((R, N), f32), ((R, N), f32), ((R, d), f32),
        ((N, d), f32), donate=(0,))
    assert kernel_names(compiled) == ["selective_scan_step.1"], \
        kernel_names(compiled)
    import re
    made_by = re.findall(r"= f32\[257,16,5120\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by
    # the pool is lane-dense in HBM: the bytes of its elements and no more
    assert "f32[257,16,5120]{2,1,0:T(8,128)}" in compiled.as_text()


def test_selective_scan_segments_at_the_cells_shape(mosaic):
    """The 256 chunk rows of a 512-token mixed step (2-4 runs of up to 128
    tokens, wherever they start) through the call the layer makes
    (ops/selective_scan.py: segment_rows): the kernel with the time loop
    inside it, under the name `selective_scan_seg`, inside a loop over the
    step's runs four at a time; the state pool donated, never copied."""
    from paddle_tpu.ops import selective_scan as ss
    c = SCAN
    P, N, d = c["P"], c["N"], c["D_IN"]

    def seg(state, seg_slot, seg_pos, x, Bm, Cm, dt, A):
        return ss.segment_rows(state, seg_slot, seg_pos, x, Bm, Cm, dt, A,
                               use_kernel=True)

    compiled = mosaic(
        seg, ((c["S"] + 1, N, d), f32), ((P,), i32), ((P,), i32),
        ((P, d), f32), ((P, N), f32), ((P, N), f32), ((P, d), f32),
        ((N, d), f32), donate=(0,))
    names = kernel_names(compiled)
    assert len(names) == 1 and names[0].startswith("selective_scan_seg"), \
        names
    import re
    made_by = re.findall(r"= f32\[257,16,5120\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


@pytest.mark.parametrize("rows", [256, 512], ids=["decode", "mixed-512-rows"])
def test_paged_kernel_at_one_kv_head_under_20_query_heads(mosaic, rows):
    """`paged_attn` at 20 query heads over ONE KV head of 128 (one group of
    20, padded to 32 query rows in the kernel), at the decode step's 256
    rows and the mixed step's 512: one kernel, the pools donated and never
    copied or padded.  Stored [P, 16, 1, 128] the bf16 pool's `(2,128)(2,1)`
    tile pads the lone head to two rows (2,048 B a token a layer where 512
    are stored) and Mosaic refuses the page's copy ("slice shape along
    dimension 2 must be aligned to tiling (2), but is 1"); stored two
    tokens a row, [P, 8, 2, 128] (ops/pallas_paged.py:kv_page_shape), it is
    the bytes of its elements and no more."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_page_shape
    c = MQA
    page = kv_page_shape(c["PAGE"], c["H_KV"], c["D"], 2)
    assert page == (8, 2, 128)          # two tokens a sublane row
    pools = [((c["POOL"],) + page, bf16)] * 2
    if rows == c["S"]:
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, c["H"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, c["MAXP"]), i32), ((S,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        T = rows
        compiled = mosaic(
            step, ((T, c["H"], c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, c["MAXP"]), i32), ((T,), i32), ((T,), i32),
            donate=(3, 4))
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    import re
    text = compiled.as_text()
    shape = ",".join(str(n) for n in (c["POOL"],) + page)
    made_by = re.findall(r"= bf16\[%s\]\S* ([\w-]+)\(" % shape, text)
    assert made_by and "copy" not in made_by and "pad" not in made_by, made_by
    assert "bf16[%s]{3,2,1,0:T(2,128)(2,1)}" % shape in text
    pool_bytes = c["POOL"] * c["PAGE"] * c["H_KV"] * c["D"] * 2
    assert compiled.memory_analysis().argument_size_in_bytes < \
        2 * pool_bytes * 1.02


# solar-open2-250b-serve.long-output-128's own shapes (benchmark/configs/
# solar-open2-250b-serve.json: 128 slots; 64 KDA heads whose state is
# 128 x 128 float32, 4 MiB a slot a layer; 64 query heads in 8 groups of 8
# over 8 KV heads of 128, page 16, context 4,096, bf16; 320 step tokens)
KDA_64 = dict(S=128, H=64, D=128)
GQA_64_8 = dict(S=128, PAGE=16, MAXP=4096 // 16, H=64, H_KV=8, D=128,
                POOL=128 * 256 + 1)


def _kda_step_shapes(c):
    R, H, D = c["S"], c["H"], c["D"]
    vec = ((R, H, D), f32)
    return (((c["S"] + 1, H, D, D), f32), ((R,), i32), ((R,), jnp.bool_),
            vec, vec, vec, vec, ((R, H), f32))


def test_kda_step_kernel_at_64_heads(mosaic):
    """`kda_step` at 128 rows x 64 heads (`head_block(64)` = 16: four grid
    steps a row, where Kimi's 32 heads take two): one call, the 541 MB
    state pool [129, 64, 128, 128] donated, aliased and never copied."""
    from paddle_tpu.ops import kda, pallas_kda
    assert pallas_kda.head_block(KDA_64["H"]) == 16

    def step(state, slot, live, q, k, v, g, beta):
        return kda.step_rows(state, None, live, q, k, v, g, beta,
                             use_kernel=True)

    compiled = mosaic(step, *_kda_step_shapes(KDA_64), donate=(0,))
    assert kernel_names(compiled) == ["kda_step.1"], kernel_names(compiled)
    import re
    made_by = re.findall(r"= f32\[129,64,128,128\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


@pytest.mark.parametrize("c", [KDA, KDA_64], ids=["32-heads", "64-heads"])
def test_kda_mixed_step_holds_both_kernels_and_no_loop(mosaic, c):
    """A KDA layer's part of the ragged mixed step at both cells' shapes
    (128 decode rows and 192 chunk rows of 32 heads, of 64; 129 slot states
    of 128 x 128 float32) as graph/layers_kda.py makes it — `step_rows` then
    `segment_rows` through the kernels — in ONE compiled program: `kda_step`
    and `kda_seg` each under its own name (the roofline reader sums
    `kda_step.*`: the segment kernel's name must not hold it), the state
    pool donated, aliased through both and made by no copy, and nothing of
    the jnp chunkwise form left: no loop, no branch, no triangular solve."""
    from paddle_tpu.ops import kda
    S, H, D, P = c["S"], c["H"], c["D"], 192

    def mixed(state, row_slot, live, seg_slot, seg_pos, q, k, v, g, beta):
        o_d, state = kda.step_rows(state, row_slot, live, q[:S], k[:S],
                                   v[:S], g[:S], beta[:S], use_kernel=True)
        o_c, state, n_seg = kda.segment_rows(
            state, seg_slot, seg_pos, q[S:], k[S:], v[S:], g[S:], beta[S:],
            use_kernel=True)
        return jnp.concatenate([o_d, o_c]), state, n_seg

    vec = ((S + P, H, D), f32)
    compiled = mosaic(mixed, ((S + 1, H, D, D), f32), ((S,), i32),
                      ((S,), jnp.bool_), ((P,), i32), ((P,), i32), vec, vec,
                      vec, vec, ((S + P, H), f32), donate=(0,))
    names = kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == ["kda_seg", "kda_step"], names
    assert not any("kda_step" in n for n in names if n.startswith("kda_seg"))
    import re
    text = compiled.as_text()
    made_by = re.findall(r"= f32\[129,%d,128,128\]\S* ([\w-]+)\(" % H, text)
    assert made_by and "copy" not in made_by, made_by
    assert "input_output_alias" in text
    for gone in (" while(", " conditional(", "InvertDiagBlocks",
                 "triangular"):
        assert gone not in text, gone


@pytest.mark.parametrize("rows", [128, 320], ids=["decode", "mixed-320-rows"])
def test_paged_kernel_at_64_query_heads_over_8_kv_heads(mosaic, rows):
    """`paged_attn` at 64 query heads over 8 KV heads of 128 (8 groups of
    8, 64 query rows a slot; the cells so far ran 24 / 2, 32 / 8 at head
    64, 32 / 2 and 20 / 1), at the decode step's 128 rows and the mixed
    step's 320 — beside `kda_step` in ONE compiled program, as the model's
    step holds them: two kernels under their own names, the K/V pools and
    the state pool donated and never copied or padded."""
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    c = GQA_64_8
    row = kv_row_shape(c["H_KV"], c["D"])
    assert row == (8, 128)
    pools = [((c["POOL"], c["PAGE"]) + row, bf16)] * 2
    recurrent = _kda_step_shapes(KDA_64)

    def kda_part(state, slot, live, q, k, v, g, beta):
        return kda.step_rows(state, None, live, q, k, v, g, beta,
                             use_kernel=True)

    if rows == c["S"]:
        def step(q, k, v, kp, vp, table, pos, *rec):
            return (paged_attention_step(q, k, v, kp, vp, table, pos,
                                         use_kernel=True), kda_part(*rec))
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, c["H"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, c["MAXP"]), i32), ((S,), i32), *recurrent,
            donate=(3, 4, 7))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos, *rec):
            return (ragged_paged_attention_step(q, k, v, kp, vp, table,
                                                row_slot, row_pos,
                                                use_kernel=True),
                    kda_part(*rec))
        T = rows
        compiled = mosaic(
            step, ((T, c["H"], c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, c["MAXP"]), i32), ((T,), i32), ((T,), i32),
            *recurrent, donate=(3, 4, 8))
    assert kernel_names(compiled) == ["kda_step.1", "paged_attn.1"], \
        kernel_names(compiled)
    import re
    text = compiled.as_text()
    made_by = re.findall(r"= bf16\[32769,16,8,128\]\S* ([\w-]+)\(", text)
    assert made_by and "copy" not in made_by and "pad" not in made_by, made_by
    made_by = re.findall(r"= f32\[129,64,128,128\]\S* ([\w-]+)\(", text)
    assert made_by and "copy" not in made_by, made_by


# ---------------------------------------------------------------------------
# the data-parallel train step's SCHEDULE: which gradient all-reduces the
# trainer's compile options (parallel/dp.py:step_compile_options) make
# asynchronous, read by parallel/schedule.py from a compile for four
# described chips.  One StarCoder2 layer at dim 1024, where every matrix is
# at or over the combiner's 1 MiB (tools/step_schedule.py is the same at the
# dp4 cell's own shape)
# ---------------------------------------------------------------------------

SCHEDULE_SHAPE = dict(vocab=8192, dim=1024, layers=1, heads=8, kv_heads=4,
                      ffn=4096, batch_size=8, seq_len=1025,
                      compute_dtype="bfloat16", attn_impl="flash")
#: the gradients that must cross beside work, by their matrix's dims
CROSSING = {"lm_head": "[1024,8192]", "embedding": "[8192,1024]",
            "ffn1": "[1024,4096]", "ffn2": "[4096,1024]",
            "attention q/o": "[1024,1024]"}


@pytest.mark.parametrize("mesh", ["data:4", "none", "model:4"])
def test_train_step_all_reduces_run_beside_work(topo, no_persistent_cache,
                                                monkeypatch, mesh):
    """Under `data:4` each matrix's gradient crosses in an
    `async-collective-start/-done` pair with work scheduled between, and
    what stays synchronous is the combiner's tuples of vectors and scalars;
    one chip and a `model`-only mesh get no options and nothing
    asynchronous."""
    from paddle_tpu.ops import pallas_attention
    from paddle_tpu.parallel.dp import (ASYNC_ALL_REDUCE_OPTIONS,
                                        step_compile_options)
    from paddle_tpu.parallel.schedule import read_collectives, summarize
    from tools.step_schedule import compile_step, described_trainer

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")  # "supported" here
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    config = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", "starcoder2.py")
    tr, args = described_trainer(config, dict(SCHEDULE_SHAPE), mesh,
                                 topo.devices)
    options = step_compile_options(tr.mesh)
    text = compile_step(tr, args).as_text()
    # the trainer holds 0.2 GB of host parameters and Adam slots in cycles:
    # give them back before the next case builds its own
    del tr, args
    gc.collect()
    assert "tpu_custom_call" in text
    found = read_collectives(text)
    forms = summarize(found)
    if mesh != "data:4":
        assert options == {}
        assert forms["async"] == {"count": 0, "bytes": 0}, found
        return
    assert options == ASYNC_ALL_REDUCE_OPTIONS
    beside = {c["shape"].split("{")[0].split("[", 1)[1]: c for c in found
              if c["form"] == "async" and c["kind"] == "all-reduce"
              and c["between"]["work"] >= 1 and "done" not in c["between"]}
    for what, dims in CROSSING.items():
        assert dims[1:] in beside, (what, found)
    total = forms["async"]["bytes"] + forms["sync"]["bytes"]
    assert forms["sync"]["bytes"] < 0.03 * total, forms
    assert all(c["bytes"] <= 1 << 20 for c in found if c["form"] == "sync"), \
        [c for c in found if c["form"] == "sync"]


# laguna-xs2-33b-serve.long-context-64's own shapes (benchmark/configs/
# laguna-xs2-33b-serve.json: 64 slots, page 16, context 8,192, a window of
# 512 in rings of 53 pages a slot; 64 query heads over 8 KV heads of 128 in
# a window layer, 48 in a full layer; 320 rows a mixed step, bf16)
LAGUNA = dict(S=64, PAGE=16, MAXP=8192 // 16, RING=53, WINDOW=512, H_KV=8,
              D=128, ROWS=320)


@pytest.mark.parametrize("kind,heads", [("window", 64), ("full", 48)])
@pytest.mark.parametrize("form", ["decode", "mixed-320-rows"])
def test_paged_kernels_at_the_laguna_cells_shapes(mosaic, form, kind, heads):
    """A window layer's call — `window_attn`, over the slots' rings: one
    Pallas call a layer a step under a name of its own, and the ring's pool
    (1 + 64 x 53 pages) donated without a copy — and a full layer's
    `paged_attn` at a table of 512 pages a slot, by the calls the engine
    makes."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    c = LAGUNA
    window = kind == "window"
    n_pages = 1 + c["S"] * (c["RING"] if window else c["MAXP"])
    cols = c["RING"] if window else c["MAXP"]
    kw = dict(window=c["WINDOW"], ring=True) if window else {}
    pools = [((n_pages, c["PAGE"], c["H_KV"], c["D"]), bf16)] * 2
    if form == "decode":
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True, **kw)
        S = c["S"]
        compiled = mosaic(
            step, ((S, 1, heads, c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16),
            ((S, 1, c["H_KV"], c["D"]), bf16), *pools,
            ((S, cols), i32), ((S,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True, **kw)
        T = c["ROWS"]
        compiled = mosaic(
            step, ((T, heads, c["D"]), bf16), ((T, c["H_KV"], c["D"]), bf16),
            ((T, c["H_KV"], c["D"]), bf16), *pools,
            ((c["S"] + 1, cols), i32), ((T,), i32), ((T,), i32),
            donate=(3, 4))
    name = "window_attn.1" if window else "paged_attn.1"
    assert kernel_names(compiled) == [name], kernel_names(compiled)
    import re
    made_by = re.findall(r"= bf16\[%d,16,8,128\]\S* ([\w-]+)\(" % n_pages,
                         compiled.as_text())
    assert made_by and "copy" not in made_by, made_by


# the TILE form of the paged kernels (ops/pallas_paged.py:tile_rows): where a
# call's rows may share a slot it carries the tiles' RUNS as two prefetched
# operands more, one entry a row (a run's rows and its longest row's length
# at its first row)
TILE_CASES = {
    # name: (rows, heads, kv heads, pages a table row, slots, tile)
    "laguna-full-mixed": (320, 48, 8, 512, 64, 16),
    "decode-saturated-mixed": (128, 24, 2, 256, 64, 8),
}


def _row_operands(compiled, rows) -> int:
    """The s32[rows] operands of the program's one Pallas call."""
    import re
    call, = re.findall(r"custom_call_target=\"tpu_custom_call\", "
                       r"operand_layout_constraints=(.*?), frontend_attr",
                       compiled.as_text())
    return len(re.findall(r"s32\[%d\]" % rows, call))


@pytest.mark.parametrize("case", list(TILE_CASES))
def test_paged_kernel_tiles_at_the_cells_shapes(mosaic, case):
    """The Laguna full layer's mixed step (48 / 8 heads of 128, 320 rows,
    512 pages a table row: a run in a tile of 16 rows is 8 score blocks of
    [96, 128] float32, a stored head's each) and decode-saturated's (24 / 2
    heads, 128 rows): the program with both walks compiles, in 20 and 16
    tiles, under the one name — q in a second time by stored head where a
    run reads a block so, once where a few heads stay one dense operand."""
    import re
    from paddle_tpu.ops import pallas_paged
    R, H, h_kv, maxp, S, tile = TILE_CASES[case]
    bt = pallas_paged.block_tokens(16, h_kv, 128, 2, maxp)
    assert pallas_paged.tile_rows(R, *pallas_paged.query_tile(
        H, h_kv, (h_kv, 128), bt, bf16)) == tile

    def call(q, kp, vp, table, lengths, row_slot):
        return pallas_paged.paged_attention(q, kp, vp, table, lengths,
                                            row_slot=row_slot)

    pool = ((1 + S * maxp, 16, h_kv, 128), bf16)
    compiled = mosaic(call, ((R, H, 128), bf16), pool, pool,
                      ((S + 1, maxp), i32), ((R,), i32), ((R,), i32))
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    # lengths, row -> slot, and the runs' two
    assert _row_operands(compiled, R) == 4
    by_group = re.search(r"bf16\[%d,%d,%d,128\]" % (
        R // tile, h_kv, tile * H // h_kv), compiled.as_text())
    assert bool(by_group) == pallas_paged.split_heads(h_kv, 2) == \
        (h_kv > 4), "q of a group's rows together, where runs split"


def test_latent_kernel_tiles_at_gigachats_shape(mosaic):
    """The latent call's tile comes from its shapes too: 64 heads against
    rows of 640 lanes leave room for 4 query rows a tile (GigaChat's mixed
    step of 128 rows: 32 tiles), Kimi's 32 heads for 8."""
    from paddle_tpu.ops import pallas_paged
    assert pallas_paged.tile_rows(128, 64, 128, 640, bf16) == 4
    assert pallas_paged.tile_rows(320, 32, 128, 640, bf16) == 8

    def call(q, pool, table, lengths, row_slot):
        return pallas_paged.latent_paged_attention(
            q, pool, table, lengths, 0.1, row_slot=row_slot, v_width=512)

    compiled = mosaic(call, ((128, 64, 640), bf16),
                      ((16385, 16, 640), bf16), ((65, 256), i32),
                      ((128,), i32), ((128,), i32))
    assert kernel_names(compiled) == ["mla_paged_attn.1"]
    assert _row_operands(compiled, 128) == 4


# xing4.0-29b-serve.long-prompt-48's own shapes (benchmark/configs/
# xing4.0-29b-a4b-serve.json: 48 slots, page 16, context 8,192 = 512 pages a
# table row, a mixed step of 1,088 rows; 4 residual streams of 3,584; 32
# query heads against the 640-lane latent row)
XING = dict(S=48, ROWS=1088, N=4, C=3584, H=32, WP=640, V=512, PAGE=16,
            MAXP=512, POOL=48 * 512 + 1)


@pytest.mark.parametrize("rows", [XING["ROWS"], XING["S"]],
                         ids=["mixed-1088-rows", "decode-48-rows"])
def test_mhc_mix_at_the_xing_cells_shapes(mosaic, rows):
    """The hyper-connections' stream pass through the call the layer makes
    (ops/hyper_conn.py: write): ONE `mhc_mix` call, tiles of 32 rows where
    they divide the step (34 tiles of 1,088) and of 16 at the 48 decode
    rows; the streams, the sublayer's output and the float32 maps in, the
    new streams out."""
    from paddle_tpu.ops import hyper_conn, pallas_hyper_conn
    c = XING
    assert pallas_hyper_conn.tile_rows(rows) == (32 if rows == 1088 else 16)

    def step(x, y, m):
        return hyper_conn.write(x, y, m, c["N"], kernel=True)

    compiled = mosaic(step, ((rows, c["N"] * c["C"]), bf16),
                      ((rows, c["C"]), bf16),
                      ((rows, hyper_conn.map_width(c["N"])), f32))
    assert kernel_names(compiled) == ["mhc_mix.1"], kernel_names(compiled)


@pytest.mark.parametrize("rows", [XING["ROWS"], XING["S"]],
                         ids=["mixed-1088-rows", "decode-48-rows"])
def test_latent_paged_kernel_at_the_xing_cells_shapes(mosaic, rows):
    """`mla_paged_attn` at a size no other cell reaches: 1,088 rows a mixed
    step (tiles of 8 rows: 136 a call) of 32 heads against tables of 512
    pages a slot, and the 48 decode rows."""
    from paddle_tpu.ops import mla, pallas_paged
    c = XING
    assert pallas_paged.tile_rows(c["ROWS"], c["H"], 128, c["WP"], bf16) == 8

    def step(q, new, pool, table, row_slot, row_pos):
        return mla.paged_latent_step(q, new, pool, table, row_slot, row_pos,
                                     0.1, c["V"], use_kernel=True)

    compiled = mosaic(
        step, ((rows, c["H"], c["WP"]), bf16), ((rows, c["WP"]), bf16),
        ((c["POOL"], c["PAGE"], c["WP"]), bf16),
        ((c["S"] + 1, c["MAXP"]), i32), ((rows,), i32), ((rows,), i32),
        donate=(2,))
    assert kernel_names(compiled) == ["mla_paged_attn.1"], \
        kernel_names(compiled)


# the Olmo-Hybrid cell (benchmark/configs/olmo-hybrid-7b-serve.json): 24
# slots; Gated DeltaNet layers of 30 heads whose float32 state is 96 x 192
# (neither a lane tile: the pool holds a row of 192 as two tiles), ONE decay
# a head; a mixed step's 1,024 chunk rows; full layers of 30 heads on 30 KV
# heads of 128 under contexts of 9,216
OLMO = dict(S=24, H=30, DK=96, DV=192, P=1024, PAGE=16, MAXP=9216 // 16,
            POOL=24 * 576 + 1, D=128)


def test_gdn_mixed_step_holds_both_kernels_and_moves_only_its_state(mosaic):
    """A Gated DeltaNet layer's part of the ragged mixed step at the Olmo-
    Hybrid cell's shapes (24 decode rows, 1,024 chunk rows, 30 heads — which
    neither 16 nor 8 divides: `head_block` takes 15 — of 96 x 192) as
    graph/layers_kda.py makes it, in ONE compiled program: `gdn_step` and
    `gdn_seg` under their own names (the KDA readers' patterns `kda_step.*`
    and `kda_seg.*` match neither), the [25, 30, 96, 192] pool donated,
    aliased through both and made by no copy, nothing of the jnp chunkwise
    form left, and the decode step alone."""
    from paddle_tpu.ops import kda, pallas_kda, pallas_kda_seg
    c = OLMO
    S, H, dk, dv, P = c["S"], c["H"], c["DK"], c["DV"], c["P"]
    assert pallas_kda.head_block(H) == 15
    assert pallas_kda.head_block(32) == pallas_kda.head_block(64) == 16
    # q, k, v (+ g, beta) and o in pieces one lane tile wide: 6 tiles a row
    assert pallas_kda_seg.head_block(H, P + 64, 128 * 6) == 5
    assert pallas_kda_seg.head_block(32, 192 + 64, 6 * 128) == 16   # Kimi's

    def mixed(state, row_slot, live, seg_slot, seg_pos, q, k, v, g, beta):
        o_d, state = kda.step_rows(state, row_slot, live, q[:S], k[:S],
                                   v[:S], g[:S], beta[:S], use_kernel=True)
        o_c, state, n_seg = kda.segment_rows(
            state, seg_slot, seg_pos, q[S:], k[S:], v[S:], g[S:], beta[S:],
            use_kernel=True)
        return jnp.concatenate([o_d, o_c]), state, n_seg

    qk, head = ((S + P, H, dk), f32), ((S + P, H), f32)
    pool = ((S + 1, H, dk, dv), f32)
    compiled = mosaic(mixed, pool, ((S,), i32), ((S,), jnp.bool_),
                      ((P,), i32), ((P,), i32), qk, qk, ((S + P, H, dv), f32),
                      head, head, donate=(0,))
    names = kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == ["gdn_seg", "gdn_step"], names
    import re
    text = compiled.as_text()
    made_by = re.findall(r"= f32\[25,30,96,192\]\S* ([\w-]+)\(", text)
    assert made_by and "copy" not in made_by, made_by
    assert "input_output_alias" in text
    for gone in (" while(", " conditional(", "InvertDiagBlocks",
                 "triangular"):
        assert gone not in text, gone

    def decode(state, slot, live, q, k, v, g, beta):
        return kda.step_rows(state, None, live, q, k, v, g, beta,
                             use_kernel=True)

    qk, head = ((S, H, dk), f32), ((S, H), f32)
    compiled = mosaic(decode, pool, ((S,), i32), ((S,), jnp.bool_), qk, qk,
                      ((S, H, dv), f32), head, head, donate=(0,))
    assert kernel_names(compiled) == ["gdn_step.1"], kernel_names(compiled)


@pytest.mark.parametrize("rows", [24, 1048], ids=["decode", "mixed-1048-rows"])
def test_paged_kernel_at_30_query_heads_on_30_kv_heads(mosaic, rows):
    """`paged_attn` at group size ONE — 30 query heads on 30 KV heads of 128
    (15,360 B of K or V a token: 3.75 times the 8-KV-head cells'), tables of
    576 pages a slot — at the decode step's 24 rows and a mixed step's
    1,048: one call under its own name, the 3.4 GB pools donated and never
    copied."""
    from paddle_tpu.ops.attention import (paged_attention_step,
                                          ragged_paged_attention_step)
    from paddle_tpu.ops.pallas_paged import kv_row_shape
    c = OLMO
    row = kv_row_shape(c["H"], c["D"])
    assert row == (32, 128)             # whole tiles of 8 heads
    assert kv_row_shape(8, 128) == (8, 128) and kv_row_shape(2, 128) == \
        (2, 128) and kv_row_shape(8, 64) == (4, 128)
    pools = [((c["POOL"], c["PAGE"]) + row, bf16)] * 2
    if rows == c["S"]:
        def step(q, k, v, kp, vp, table, pos):
            return paged_attention_step(q, k, v, kp, vp, table, pos,
                                        use_kernel=True)
        hd = ((rows, 1, c["H"], c["D"]), bf16)
        compiled = mosaic(step, hd, hd, hd, *pools, ((rows, c["MAXP"]), i32),
                          ((rows,), i32), donate=(3, 4))
    else:
        def step(q, k, v, kp, vp, table, row_slot, row_pos):
            return ragged_paged_attention_step(q, k, v, kp, vp, table,
                                               row_slot, row_pos,
                                               use_kernel=True)
        hd = ((rows, c["H"], c["D"]), bf16)
        compiled = mosaic(step, hd, hd, hd, *pools,
                          ((c["S"] + 1, c["MAXP"]), i32), ((rows,), i32),
                          ((rows,), i32), donate=(3, 4))
    assert kernel_names(compiled) == ["paged_attn.1"], kernel_names(compiled)
    import re
    made_by = re.findall(r"= bf16\[13825,16,32,128\]\S* ([\w-]+)\(",
                         compiled.as_text())
    assert made_by and "copy" not in made_by and "pad" not in made_by, made_by
