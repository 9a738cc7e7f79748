"""Prove the tensor-parallel decode step never all-gathers the KV pools.

Sibling of tools/hlo_sparse_check.py, for the serving engine's sharded
decode (docs/serving.md "Sharded decode"): with the mesh `model` axis
partitioning attention heads and the per-layer KV page pools, the ONLY
acceptable cross-device traffic in a decode step is the post-attention
all-reduce (the Megatron out-projection meeting its row-sharded partial
sums) — GSPMD deciding instead to all-gather a pool (reassembling every
head's pages on every chip) or an attention projection would silently
forfeit both the HBM win (a model bigger than one chip) and the FLOPs win
(decode faster than one chip) that sharding exists for.

This tool compiles the REAL engine's decode, mixed, speculative-verify
AND batched draft programs (the ModelDrafter's draft step must lower
with ZERO collectives — its params are replicated by contract, so any
cross-device op means the replication boundary broke) over an N-device
mesh,
inventories every collective in the optimized HLO, flags
any all-gather whose shape+gather-dim matches a KV pool (kv-head axis),
an attention projection, a Megatron-split FFN weight, or the row-sharded
LM head (each on its sharded axis) — the same shape-anchored detector
hlo_sparse_check uses — and prints a JSON verdict.  The expected
all-reduce count is derived from what the engine actually sharded: one
per attention layer (w_o row split) + one per FFN pair (down-projection
row split) + one for the LM head's partial logits.  Run under the
virtual CPU mesh (the SPMD partitioning decision is backend-agnostic):

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/hlo_shard_check.py [--model 2] [--save PATH.hlo]

Exit 0 = clean (no pool/param all-gather), 2 = violation.  Wired into
tier-1 via tests/test_tools.py.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.hlo_sparse_check import gather_spans_table  # noqa: E402


def _collectives(hlo: str):
    """Inventory collective ops (async -start/-done pairs count once);
    returns ({op: count}, [all-gather lines], [all-reduce lines])."""
    colls: dict[str, int] = {}
    gathers, reduces = [], []
    for ln in hlo.splitlines():
        m = re.search(r"(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)(-start|-done)?\(", ln)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        colls[op] = colls.get(op, 0) + 1
        if op == "all-gather":
            gathers.append(ln.strip())
        elif op == "all-reduce":
            reduces.append(ln.strip())
    return colls, gathers, reduces


def run_check(model: int = 2, config_args: str = "vocab=61,dim=32,"
              "layers=2,heads=4,batch_size=4", save: str = "") -> dict:
    """Compile the sharded decode + mixed steps and return the verdict
    dict (see module docstring).  Needs >= `model` local devices."""
    import numpy as np

    from paddle_tpu.config.parser import parse_config
    from paddle_tpu.parallel.mesh import model_mesh
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.trainer.trainer import Trainer

    cfg = parse_config("demo/model_zoo/transformer_lm.py", config_args)
    tr = Trainer(cfg, seed=1)
    eng = ServingEngine(tr.executor, tr.params, num_slots=2, page_size=8,
                        max_context=64, spec_k=2, mesh=model_mesh(model))

    # the shapes the tool is anchored to: every KV pool sharded on its
    # kv-head axis (2), every attention projection on its sharded axis,
    # the Megatron FFN pairs (up-projection column 1, down-projection
    # row 0), and the row-sharded LM head — reassembling ANY of them on
    # every chip would forfeit the sharding's HBM/FLOPs split
    tables = []
    pool_shapes = {}
    for name, pool in eng.kv.pools.items():
        pool_shapes[name] = list(pool["k"].shape)
        tables.append((tuple(pool["k"].shape), 2))
    params_sharded = {}

    def _anchor(pn: str, axis: int) -> None:
        tables.append((tuple(eng.params[pn].shape), axis))
        params_sharded[pn] = {"shape": list(eng.params[pn].shape),
                              "sharded_axis": axis}

    for l in tr.executor.model.layers:
        if l.type != "multi_head_attention":
            continue
        names = [l.inputs[i].input_parameter_name for i in range(4)]
        for pn, axis in zip(names, (1, 1, 1, 0)):       # wq wk wv | wo
            _anchor(pn, axis)
    for w1, w2 in eng._tp_ffn_pairs:                    # ffn up | down
        _anchor(w1, 1)
        _anchor(w2, 0)
    if eng._tp_lm_head:
        _anchor(eng._tp_lm_head, 0)                     # vocab projection

    # drive one real request so both compiled paths exist with live state,
    # then lower them exactly as the pump dispatches them
    rng = np.random.default_rng(0)
    eng.add_request(Request("probe", rng.integers(2, 61, 5)
                            .astype(np.int32), max_new=4))
    eng.step()
    eng._sync_run_mask([s for s in range(len(eng.slots))
                        if eng.slots[s] is not None])
    eng._sync_device_state()
    st = eng._build_state()
    hlo_decode = eng._decode_step.lower(
        eng.params, st, eng._d_run).compile().as_text()
    T = eng.max_step_tokens
    S = len(eng.slots)
    z = np.zeros(T, np.int32)
    hlo_mixed = eng._mixed_step.lower(
        eng.params, eng._build_state(), eng._stage(z),
        eng._stage(np.full(T, S, np.int32)), eng._stage(z),
        eng._stage(np.zeros(S, np.int32)),
        eng._stage(np.zeros(S, np.int32)),
        eng._stage(np.zeros(S, bool))).compile().as_text()
    # the speculative VERIFY step is a third sharded program — the one
    # nearly every dispatch runs when --spec-k is on, so its layout
    # discipline needs the same proof as decode/mixed (the chain gather
    # over replicated logits must not tempt GSPMD into anything new)
    hlo_spec = eng._spec_step.lower(
        eng.params, eng._build_state(), eng._stage(z),
        eng._stage(np.full(T, S, np.int32)), eng._stage(z),
        eng._stage(np.zeros(S, np.int32)),
        eng._stage(np.zeros(S, np.int32)),
        eng._stage(np.zeros((S, eng.spec_k), np.int32)),
        eng._stage(np.zeros(S, bool)), eng._stage(np.zeros(S, bool)),
        eng._stage(np.zeros(S, np.int32))).compile().as_text()
    # the batched DRAFT step (ModelDrafter): the drafter's replication
    # contract says it holds host/replicated params and compiles with
    # ZERO collectives under any mesh — drafting must never add
    # cross-device traffic to the verify step it feeds.  Self-spec
    # (from_target) is the strongest case: the TARGET's weights, which
    # the engine DID shard — proving its draft program still lowers
    # collective-free shows the replication boundary holds.
    from paddle_tpu.serving.drafter import ModelDrafter
    drafter = ModelDrafter.from_target(tr.executor, tr.params, window=16)
    draft_k = 2
    hlo_draft = drafter._step.lower(
        drafter.params,
        np.zeros((len(eng.slots), drafter.window + draft_k), np.int32),
        np.ones(len(eng.slots), np.int32), draft_k).compile().as_text()

    # the ONLY acceptable collectives: one post-attention all-reduce per
    # attention layer (Megatron w_o row split), one per sharded FFN pair
    # (down-projection row split), and one for the row-sharded LM head's
    # partial logits — derived from what the engine ACTUALLY sharded, so
    # a divisibility skip can never desynchronize tool and engine
    n_expected = (len(eng.kv.pools) + len(eng._tp_ffn_pairs)
                  + (1 if eng._tp_lm_head else 0))
    out = {"mesh": {"model": model}, "pool_shapes": pool_shapes,
           "sharded_params": params_sharded,
           "ffn_pairs_sharded": len(eng._tp_ffn_pairs),
           "lm_head_sharded": bool(eng._tp_lm_head),
           "draft": {"window": drafter.window, "k": draft_k,
                     "kind": drafter.kind}, "steps": {}}
    bad = []
    for step, hlo in (("decode", hlo_decode), ("mixed", hlo_mixed),
                      ("spec", hlo_spec), ("draft", hlo_draft)):
        colls, gathers, reduces = _collectives(hlo)
        table_gathers = [ln[:200] for ln in gathers
                        if gather_spans_table(ln, tables)]
        bad += table_gathers
        if step == "draft" and colls:
            # the draft program's bar is stricter than shape-anchoring:
            # ANY collective means the replicated-drafter contract broke
            bad += [f"draft-step collective: {op} x{n}"
                    for op, n in colls.items()]
        out["steps"][step] = {
            "collectives": colls,
            "n_all_gathers": len(gathers),
            "n_all_reduces": len(reduces),
            "expected_all_reduces": 0 if step == "draft" else n_expected,
            "table_all_gathers": table_gathers,
        }
        if save:
            path = save if step == "decode" else \
                re.sub(r"(\.[^.]*)?$", rf".{step}\1", save, count=1)
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path, "w") as f:
                    f.write(hlo)
                out["steps"][step]["hlo_saved"] = path
            except OSError:
                pass
    out["verdict"] = (
        "GSPMD all-gathers a sharded KV pool or attention projection — "
        "the tensor-parallel decode forfeits its HBM/FLOPs split" if bad
        else "clean: no KV-pool or attention-param all-gather; only the "
             "post-attention all-reduce crosses devices")
    out["ok"] = not bad
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", type=int, default=2,
                    help="mesh model-axis size (tensor-parallel shards)")
    ap.add_argument("--config-args",
                    default="vocab=61,dim=32,layers=2,heads=4,batch_size=4")
    ap.add_argument("--save", default=os.path.join(REPO, "output",
                                                   "serving_tp_step.hlo"))
    args = ap.parse_args()

    import jax

    if len(jax.devices()) < args.model:
        print(json.dumps({"error": f"need {args.model} devices, have "
                          f"{len(jax.devices())} — run with JAX_PLATFORMS="
                          f"cpu XLA_FLAGS=--xla_force_host_platform_"
                          f"device_count={args.model}"}))
        return 1
    out = run_check(args.model, args.config_args, args.save)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 2


if __name__ == "__main__":
    sys.exit(main())
