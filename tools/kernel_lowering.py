"""Count what Mosaic made of a Pallas kernel: compile it for a DESCRIBED
v5e (no chip, nothing runs) with `--xla_mosaic_dump_to` and count the
`tpu.load` / `tpu.store` / `tpu.enqueue_dma` ops (and the vregs the loads'
live sublanes add up to) of the `post-apply-vector-layout` dump — the program after Mosaic has chosen every
value's vreg layout, where a relayout shows as loads and stores that move
no information (PR 42: a block of 16 pages read through a `(2,128)` tiled
buffer cost 512 one-sublane loads and 512 strided stores through
`internal_scratch`).

    python tools/kernel_lowering.py paged_attn                 # decode-saturated
    python tools/kernel_lowering.py paged_attn rows=128        # its mixed step
    python tools/kernel_lowering.py paged_attn heads=48,kv_heads=8,rows=320,max_pages=512,pages=32769  # Laguna's full layer
    python tools/kernel_lowering.py paged_attn kv_heads=8,head_dim=64,heads=32,rows=256
    python tools/kernel_lowering.py paged_attn rows=280,heads=30,kv_heads=30,max_pages=576,pages=13825  # Olmo-Hybrid's mixed step
    python tools/kernel_lowering.py mla_paged_attn             # GigaChat's decode
    python tools/kernel_lowering.py kda_seg                    # Kimi's chunk rows
    python tools/kernel_lowering.py kda_seg heads=64           # Solar-Open2's

One line of JSON.  A process of its own: the dump flag is read when the
TPU's library loads (`LIBTPU_INIT_ARGS`), so it cannot be set around one
compile of a process that compiles others.  Exit 3 with `{"skipped": why}`
where no topology can be described.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the serve cells' decode shapes (benchmark/configs/starcoder2-3b-serve.json,
#: gigachat3.1-702b-a36b-serve.json); `k=v,...` on the command line overrides
SHAPES = {
    "paged_attn": dict(rows=64, heads=24, kv_heads=2, head_dim=128, page=16,
                       max_pages=256, pages=16384),
    "mla_paged_attn": dict(rows=64, heads=64, width=640, v_width=512,
                           page=16, max_pages=256, pages=16385),
    # the KDA cells' chunk rows (kimi-linear-48b-a3b-serve.json: 320 step
    # tokens - 128 slots; float32)
    "kda_seg": dict(rows=192, heads=32, head_dim=128, slots=128),
}
#: op families counted, by the name the count is printed under: every kind
#: of vector load (`tpu.load`, `tpu.shuffled_load`, `tpu.strided_load`) is
#: a load
COUNTED = {"tpu.load": r"\btpu\.(?:\w+_)?load\b",
           "tpu.store": r"\btpu\.(?:\w+_)?store\b",
           "tpu.enqueue_dma": r"\btpu\.enqueue_dma\b"}


def _build(kernel: str, s: dict):
    """(fn, argument shapes) of one bare kernel call (bf16; the KDA
    segment kernel float32)."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_paged
    bf16, i32 = jnp.bfloat16, jnp.int32
    if kernel == "kda_seg":
        from paddle_tpu.ops import pallas_kda_seg
        f32 = jnp.float32
        P, H, d = s["rows"], s["heads"], s["head_dim"]

        def fn(state, slot, pos, q, k, v, g, beta):
            return pallas_kda_seg.kda_segments(state, slot, pos, q, k, v, g,
                                               beta, d ** -0.5)
        vec = ((P, H, d), f32)
        return fn, [((s["slots"] + 1, H, d, d), f32), ((P,), i32),
                    ((P,), i32), vec, vec, vec, vec, ((P, H), f32)]
    R, ps, maxp = s["rows"], s["page"], s["max_pages"]
    tail = [((R + 1, maxp), i32), ((R,), i32), ((R,), i32)]
    if kernel == "paged_attn":
        row = pallas_paged.kv_row_shape(s["kv_heads"], s["head_dim"])
        pool = ((s["pages"], ps) + row, bf16)

        def fn(q, kp, vp, table, lengths, row_slot):
            return pallas_paged.paged_attention(q, kp, vp, table, lengths,
                                                row_slot=row_slot,
                                                kv_heads=s["kv_heads"])
        return fn, [((R, s["heads"], s["head_dim"]), bf16), pool, pool] + tail

    def fn(q, pool, table, lengths, row_slot):
        return pallas_paged.latent_paged_attention(
            q, pool, table, lengths, 0.1, row_slot=row_slot,
            v_width=s["v_width"])
    return fn, [((R, s["heads"], s["width"]), bf16),
                ((s["pages"], ps, s["width"]), bf16)] + tail


def lowering_counts(kernel: str, shape: dict, dump_dir: str) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, [
        os.environ.get("LIBTPU_INIT_ARGS"),
        f"--xla_mosaic_dump_to={dump_dir}"]))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops import pallas_kda, pallas_paged
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot describe"
        return {"skipped": f"no v5e:2x2 topology can be described here: "
                           f"{str(e)[:200]}"}
    # compiled for the chip whatever backend this process has
    pallas_paged._interpret = pallas_kda._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, shapes = _build(kernel, shape)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    jax.jit(fn).lower(*args).compile()
    dumps = glob.glob(os.path.join(
        dump_dir, f"*{kernel}-post-apply-vector-layout.txt"))
    if len(dumps) != 1:
        return {"error": f"{len(dumps)} post-apply-vector-layout dumps of "
                         f"{kernel} in {sorted(os.listdir(dump_dir))[:8]}"}
    text = open(dumps[0]).read()
    out = {"kernel": kernel, "shape": shape, "lines": text.count("\n")}
    for op, pattern in COUNTED.items():
        out[op] = len(re.findall(pattern, text))
    # what the loads move, in vregs of 8 sublanes: a load names its live
    # sublanes, and one with a single live sublane of eight is a relayout
    out["vregs_loaded"] = sum(
        mask.count("true") for mask in re.findall(
            r"tpu\.(?:\w+_)?load\b[^\n]*?sublanes \[([^\]]*)\]", text)) / 8
    if kernel == "kda_seg":
        return out
    # a head's rows of a block are read by sublane-STRIDED loads (one stored
    # head at a time: `split_heads`): how many, and how many of them fill
    # their vreg — a whole uint32 sublane row a sublane
    strided = re.findall(
        r"tpu\.load\b[^\n]*?sublanes \[([^\]]*)\] sublane_stride "
        r"(?!1 )\d+ : memref<[^>]*xi32", text)
    out["strided_loads"] = len(strided)
    out["strided_loads_whole"] = sum("false" not in m for m in strided)
    # the widest float32 value of the program before the layout pass — a
    # ROW's scores against a block as one dense operand, tokens x stored
    # heads wide — and the most rows a value that wide has: a query row's
    # heads, never a tile's (a run of rows scores a stored head at a time,
    # a block's tokens wide)
    original = glob.glob(os.path.join(dump_dir, f"*{kernel}-original.txt"))
    f32 = [(int(c), int(r)) for r, c in re.findall(
        r"vector<(\d+)x(\d+)xf32>", open(original[0]).read())]
    out["f32_cols"], out["f32_rows_at_cols"] = max(f32)
    # pages a block: what the loads are held against (4 vregs a page at most)
    row = pallas_paged.kv_row_shape(shape["kv_heads"], shape["head_dim"]) \
        if kernel == "paged_attn" else (1, shape["width"])
    block = pallas_paged.block_tokens(
        shape["page"], row[0], row[1], 2, shape["max_pages"])
    out["pages_per_block"] = block // shape["page"]
    # rows a grid step holds: the program has a walk one row at a time and
    # a walk of the whole tile, each with its own loads of a block
    out["tile_rows"] = pallas_paged.tile_rows(
        shape["rows"], *pallas_paged.query_tile(
            shape["heads"], shape.get("kv_heads", 1), row, block,
            "bfloat16"))
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] not in SHAPES or len(argv) > 2:
        print(f"usage: kernel_lowering.py {{{'|'.join(SHAPES)}}} [k=v,...]",
              file=sys.stderr)
        return 2
    shape = dict(SHAPES[argv[0]])
    for kv in (argv[1].split(",") if len(argv) == 2 else []):
        k, _, v = kv.partition("=")
        if k not in shape:
            print(f"unknown key {k!r}: {sorted(shape)}", file=sys.stderr)
            return 2
        shape[k] = int(v)
    with tempfile.TemporaryDirectory() as d:
        out = lowering_counts(argv[0], shape, d)
    print(json.dumps(out))
    return 3 if "skipped" in out else 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main())
