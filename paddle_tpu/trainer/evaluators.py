"""Evaluator zoo — streaming metrics over batches.

TPU re-design of the reference's Evaluator framework (ref:
paddle/gserver/evaluators/Evaluator.{h,cpp}:41-1235 — classification_error,
sum, column_sum, auc, precision_recall, pnpair; ChunkEvaluator.cpp;
CTCErrorEvaluator.cpp).  Each evaluator contributes per-batch partial sums
computed *inside the jitted step* (cheap jnp reductions fused into the graph);
the host accumulates partials across batches and finalizes — the analog of the
reference's eval start/finish + merge protocol, without leaving the device
during the hot loop.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.config.schema import EvaluatorConfig, ModelConfig
from paddle_tpu.parameter.argument import Argument

Array = jax.Array

# type -> (batch_fn(cfg, outputs, feed) -> dict partials,
#          finalize_fn(cfg, accumulated) -> dict of floats)
evaluator_registry: dict[str, tuple[Callable, Callable]] = {}


def register_evaluator(*names):
    def deco(pair):
        for n in names:
            evaluator_registry[n] = pair
        return pair
    return deco


def _get(outputs: dict[str, Argument], name: str) -> Argument:
    return outputs[name].flatten_image()


# -- classification error ---------------------------------------------------

def _cls_err_batch(cfg: EvaluatorConfig, outputs, feed):
    out = _get(outputs, cfg.input_layer_names[0])
    lbl = _get(outputs, cfg.input_layer_names[1])
    pred = out.value
    if pred is None:
        # a softmax head the executor fused with its cost publishes the
        # rows' argmax itself (graph/layers_cost.py:fused_softmax_cost)
        err = (out.ids != lbl.ids).astype(jnp.float32)
    elif pred.shape[-1] == 1:
        err = (pred[..., 0] > cfg.classification_threshold).astype(jnp.float32) \
            != lbl.ids.astype(jnp.float32)
        err = err.astype(jnp.float32)
    else:
        err = (jnp.argmax(pred, axis=-1) != lbl.ids).astype(jnp.float32)
    if out.is_sequence:
        mask = out.mask(jnp.float32)
        return {"err": jnp.sum(err * mask), "n": jnp.sum(mask)}
    return {"err": jnp.sum(err), "n": jnp.asarray(err.size, jnp.float32)}


def _cls_err_final(cfg, acc):
    return {"classification_error": acc["err"] / max(acc["n"], 1.0)}


register_evaluator("classification_error")((_cls_err_batch, _cls_err_final))


# -- sums -------------------------------------------------------------------

def _sum_batch(cfg, outputs, feed):
    out = _get(outputs, cfg.input_layer_names[0])
    v = out.data.astype(jnp.float32)
    if out.is_sequence:
        mask = out.mask(jnp.float32)
        v = v * (mask[..., None] if v.ndim == 3 else mask)
    return {"sum": jnp.sum(v), "n": jnp.asarray(v.shape[0], jnp.float32)}


def _sum_final(cfg, acc):
    return {"sum": acc["sum"], "mean": acc["sum"] / max(acc["n"], 1.0)}


register_evaluator("sum")((_sum_batch, _sum_final))


def _colsum_batch(cfg, outputs, feed):
    out = _get(outputs, cfg.input_layer_names[0])
    v = out.value
    if out.is_sequence:
        v = v * out.mask(jnp.float32)[..., None]
        v = jnp.sum(v, axis=1)
    return {"colsum": jnp.sum(v, axis=0), "n": jnp.asarray(v.shape[0], jnp.float32)}


def _colsum_final(cfg, acc):
    return {"column_sum_mean": acc["colsum"] / max(acc["n"], 1.0)}


register_evaluator("column_sum")((_colsum_batch, _colsum_final))


# -- AUC (histogram method, matching the reference's bucketed AUC) ----------

_AUC_BINS = 1024


def _auc_batch(cfg, outputs, feed):
    """(ref: Evaluator.cpp AucEvaluator — 2 x kBinNum histograms; created
    with colIdx=-1 for 'last-column-auc' (Evaluator.cpp:857-858), so the
    score is always the LAST output column; optional 3rd input = per-sample
    weight)."""
    out = _get(outputs, cfg.input_layer_names[0])
    lbl = _get(outputs, cfg.input_layer_names[1])
    p = out.value
    pos_prob = p[..., -1]
    y = lbl.ids.astype(jnp.float32).reshape(pos_prob.shape)
    w = jnp.ones_like(pos_prob)
    if len(cfg.input_layer_names) > 2:
        wt = _get(outputs, cfg.input_layer_names[2])
        w = wt.value.reshape(pos_prob.shape).astype(jnp.float32)
    idx = jnp.clip((pos_prob * _AUC_BINS).astype(jnp.int32), 0, _AUC_BINS - 1)
    pos_hist = jnp.zeros((_AUC_BINS,), jnp.float32).at[idx].add(y * w)
    neg_hist = jnp.zeros((_AUC_BINS,), jnp.float32).at[idx].add((1.0 - y) * w)
    return {"pos": pos_hist, "neg": neg_hist}


def _auc_final(cfg, acc):
    pos, neg = np.asarray(acc["pos"]), np.asarray(acc["neg"])
    # integrate from the high-score end (ref: AucEvaluator::calcAuc)
    tp = np.cumsum(pos[::-1])
    fp = np.cumsum(neg[::-1])
    tot_pos, tot_neg = tp[-1], fp[-1]
    if tot_pos == 0 or tot_neg == 0:
        return {"auc": 0.0}
    tpr = np.concatenate([[0.0], tp / tot_pos])
    fpr = np.concatenate([[0.0], fp / tot_neg])
    auc = float(np.trapezoid(tpr, fpr))
    return {"auc": auc}


# "auc" is a convenience alias: the reference registers ONLY
# "last-column-auc" (= AucEvaluator(-1), Evaluator.cpp:857; the DSL's
# auc_evaluator emits that type too), so last-column scoring IS the
# reference behavior — for the common 2-column softmax output it is
# column 1, the positive class
register_evaluator("auc", "last-column-auc")((_auc_batch, _auc_final))


# -- precision / recall -----------------------------------------------------

def _pr_batch(cfg, outputs, feed):
    """(ref: PrecisionRecallEvaluator) — binary or per-class counts."""
    out = _get(outputs, cfg.input_layer_names[0])
    lbl = _get(outputs, cfg.input_layer_names[1])
    p = out.value
    C = p.shape[-1]
    pred = jnp.argmax(p, axis=-1) if C > 1 else (
        p[..., 0] > cfg.classification_threshold).astype(jnp.int32)
    y = lbl.ids.reshape(pred.shape)
    nC = max(C, 2)
    onehot_p = jax.nn.one_hot(pred, nC)
    onehot_y = jax.nn.one_hot(y, nC)
    tp = jnp.sum(onehot_p * onehot_y, axis=tuple(range(onehot_p.ndim - 1)))
    fp = jnp.sum(onehot_p * (1 - onehot_y), axis=tuple(range(onehot_p.ndim - 1)))
    fn = jnp.sum((1 - onehot_p) * onehot_y, axis=tuple(range(onehot_p.ndim - 1)))
    return {"tp": tp, "fp": fp, "fn": fn}


def _pr_final(cfg, acc):
    tp, fp, fn = (np.asarray(acc[k]) for k in ("tp", "fp", "fn"))
    if cfg.positive_label >= 0:
        tp, fp, fn = tp[cfg.positive_label], fp[cfg.positive_label], fn[cfg.positive_label]
        prec = tp / max(tp + fp, 1.0)
        rec = tp / max(tp + fn, 1.0)
    else:
        prec = float(np.mean(tp / np.maximum(tp + fp, 1.0)))
        rec = float(np.mean(tp / np.maximum(tp + fn, 1.0)))
    f1 = 2 * prec * rec / max(prec + rec, 1e-8)
    return {"precision": float(prec), "recall": float(rec), "F1-score": float(f1)}


register_evaluator("precision_recall")((_pr_batch, _pr_final))


# ---------------------------------------------------------------------------
# Host evaluators — metrics with inherently sequential algorithms (segment
# matching, sorting, DP edit distance).  The reference runs these on CPU too
# (ref: ChunkEvaluator.cpp evalImp CHECK(!useGpu); Evaluator.cpp RankAuc
# "does not support GPU"); here they consume numpy copies of just the layers
# they need, fetched once per batch outside the jitted step.
#
# registry: type -> (new_state_fn() -> state,
#                    batch_fn(cfg, args: list[Argument(np)], state) -> None,
#                    finalize_fn(cfg, state) -> dict)
# ---------------------------------------------------------------------------

host_evaluator_registry: dict[str, tuple[Callable, Callable, Callable]] = {}


def register_host_evaluator(*names):
    def deco(triple):
        for n in names:
            host_evaluator_registry[n] = triple
        return triple
    return deco


def _np_arg(arg: Argument) -> Argument:
    """Device → host copy of one Argument."""
    return jax.tree.map(np.asarray, arg)


def _seq_rows(arg: Argument):
    """Yield (row ids/values, length) per sequence of a padded Argument.
    Non-sequence args are treated as length-1 sequences per sample."""
    lengths = np.asarray(arg.lengths) if arg.lengths is not None else None
    data = np.asarray(arg.data)
    B = data.shape[0]
    for b in range(B):
        if lengths is not None:
            L = int(lengths[b])
            yield data[b, :L], L
        elif data.ndim >= 2:
            yield data[b], data.shape[1]
        else:
            yield data[b:b + 1], 1


# -- chunk (NER F1) ---------------------------------------------------------

_CHUNK_SCHEMES = {
    # scheme -> (num_tag_types, begin, inside, end, single)
    "IOB": (2, 0, 1, -1, -1),
    "IOE": (2, -1, 0, 1, -1),
    "IOBES": (4, 0, 1, 2, 3),
    "plain": (1, -1, -1, -1, -1),
}


def _chunk_segments(labels: np.ndarray, scheme: str, num_chunk_types: int):
    """Extract (begin, end, type) segments
    (ref: ChunkEvaluator::getSegments/isChunkBegin/isChunkEnd)."""
    n_tag, t_begin, t_inside, t_end, t_single = _CHUNK_SCHEMES[scheme]
    other = num_chunk_types
    segments = []
    in_chunk = False
    chunk_start = 0
    tag, typ = -1, other

    def is_end(ptag, ptyp, tag, typ):
        if ptyp == other:
            return False
        if typ == other or typ != ptyp:
            return True
        if ptag in (t_begin, t_inside):
            return tag in (t_begin, t_single)
        return ptag in (t_end, t_single)

    def is_begin(ptag, ptyp, tag, typ):
        if ptyp == other:
            return typ != other
        if typ == other:
            return False
        if typ != ptyp:
            return True
        if tag == t_begin or tag == t_single:
            return True
        if tag in (t_inside, t_end):
            return ptag in (t_end, t_single)
        return False

    for i, lab in enumerate(labels):
        ptag, ptyp = tag, typ
        tag = int(lab) % n_tag
        typ = int(lab) // n_tag
        if in_chunk and is_end(ptag, ptyp, tag, typ):
            segments.append((chunk_start, i - 1, ptyp))
            in_chunk = False
        if is_begin(ptag, ptyp, tag, typ):
            chunk_start = i
            in_chunk = True
    if in_chunk:
        segments.append((chunk_start, len(labels) - 1, typ))
    return segments


def _chunk_state():
    return {"label_segs": 0, "out_segs": 0, "correct": 0}


def _chunk_batch(cfg, args, state):
    out, lbl = args[0], args[1]
    excluded = set(cfg.excluded_chunk_types or [])
    for (o, _), (l, _) in zip(_seq_rows(out), _seq_rows(lbl)):
        segs_o = _chunk_segments(o.reshape(-1), cfg.chunk_scheme, cfg.num_chunk_types)
        segs_l = _chunk_segments(l.reshape(-1), cfg.chunk_scheme, cfg.num_chunk_types)
        if excluded:
            segs_o = [s for s in segs_o if s[2] not in excluded]
            segs_l = [s for s in segs_l if s[2] not in excluded]
        state["correct"] += len(set(segs_o) & set(segs_l))
        state["out_segs"] += len(segs_o)
        state["label_segs"] += len(segs_l)


def _chunk_final(cfg, state):
    prec = state["correct"] / max(state["out_segs"], 1)
    rec = state["correct"] / max(state["label_segs"], 1)
    f1 = 0.0 if not state["correct"] else 2 * prec * rec / (prec + rec)
    return {"chunk_f1": f1, "true_chunks": state["label_segs"],
            "result_chunks": state["out_segs"], "correct_chunks": state["correct"]}


register_host_evaluator("chunk")((_chunk_state, _chunk_batch, _chunk_final))


# -- seq_classification_error ----------------------------------------------

def _seqcls_state():
    return {"err": 0, "n": 0}


def _seqcls_batch(cfg, args, state):
    """A sequence counts as one error if ANY frame is misclassified
    (ref: SequenceClassificationErrorEvaluator::evalImp)."""
    out, lbl = args[0], args[1]
    pred = np.asarray(out.value)
    if pred.shape[-1] == 1:
        frame_pred = (pred[..., 0] > cfg.classification_threshold).astype(np.int64)
    else:
        frame_pred = np.argmax(pred, axis=-1)
    labels = np.asarray(lbl.ids).reshape(frame_pred.shape)
    lengths = np.asarray(out.lengths) if out.lengths is not None else None
    for b in range(frame_pred.shape[0]):
        L = int(lengths[b]) if lengths is not None else frame_pred.shape[1]
        state["err"] += int(np.any(frame_pred[b, :L] != labels[b, :L]))
        state["n"] += 1


def _seqcls_final(cfg, state):
    return {"seq_classification_error": state["err"] / max(state["n"], 1)}


register_host_evaluator("seq_classification_error")(
    (_seqcls_state, _seqcls_batch, _seqcls_final))


# -- ctc_edit_distance ------------------------------------------------------

def _ctc_collapse(path, blank):
    """Collapse repeats then drop blanks (ref: CTCErrorEvaluator::path2String)."""
    out, prev = [], -1
    for lab in path:
        lab = int(lab)
        if lab != blank and (not out or lab != out[-1] or prev == blank):
            out.append(lab)
        prev = lab
    return out


def _edit_distance(a, b):
    n, m = len(a), len(b)
    if n == 0:
        return m
    if m == 0:
        return n
    prev = list(range(m + 1))
    for i in range(1, n + 1):
        cur = [i] + [0] * m
        for j in range(1, m + 1):
            cost = 0 if a[i - 1] == b[j - 1] else 1
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + cost)
        prev = cur
    return prev[m]


def _ctc_state():
    return {"dist": 0.0, "len": 0, "seq_err": 0, "n": 0}


def _ctc_batch(cfg, args, state):
    """Best-path decode + edit distance vs label
    (ref: CTCErrorEvaluator::bestLabelSeq/stringAlignment)."""
    out, lbl = args[0], args[1]
    acts = np.asarray(out.value)          # [B, T, C]; blank = C-1
    blank = acts.shape[-1] - 1
    out_lens = np.asarray(out.lengths) if out.lengths is not None else None
    for b, (lab_row, _) in enumerate(_seq_rows(lbl)):
        T = int(out_lens[b]) if out_lens is not None else acts.shape[1]
        path = np.argmax(acts[b, :T], axis=-1)
        rec = _ctc_collapse(path, blank)
        gt = [int(x) for x in np.asarray(lab_row).reshape(-1)]
        d = _edit_distance(gt, rec)
        state["dist"] += d
        state["len"] += len(gt)
        state["seq_err"] += int(d != 0)
        state["n"] += 1


def _ctc_final(cfg, state):
    return {"ctc_edit_distance": state["dist"] / max(state["n"], 1),
            "character_error_rate": state["dist"] / max(state["len"], 1),
            "sequence_error_rate": state["seq_err"] / max(state["n"], 1)}


register_host_evaluator("ctc_edit_distance")((_ctc_state, _ctc_batch, _ctc_final))


# -- pnpair -----------------------------------------------------------------

def _pnpair_state():
    return {"records": []}


def _pnpair_batch(cfg, args, state):
    """Collect (score, label, queryid, weight) records
    (ref: PnpairEvaluator::evalImp — score is the LAST output column)."""
    out, lbl, info = args[0], args[1], args[2]
    weight = args[3] if len(args) > 3 else None
    scores = np.asarray(out.value).reshape(out.value.shape[0], -1)[:, -1]
    labels = np.asarray(lbl.ids).reshape(-1)
    infos = np.asarray(info.ids).reshape(-1)
    ws = (np.asarray(weight.data).reshape(-1) if weight is not None
          else np.ones_like(scores))
    state["records"].extend(zip(scores.tolist(), labels.tolist(),
                                infos.tolist(), ws.tolist()))


def _pnpair_final(cfg, state):
    """Count concordant/discordant pairs within each query group
    (ref: PnpairEvaluator::calc/stat)."""
    recs = sorted(state["records"], key=lambda r: r[2])
    pos = neg = spe = 0.0
    i = 0
    while i < len(recs):
        j = i
        while j < len(recs) and recs[j][2] == recs[i][2]:
            j += 1
        group = recs[i:j]
        for a in range(len(group)):
            for b in range(a + 1, len(group)):
                sa, la, _, wa = group[a]
                sb, lb, _, wb = group[b]
                if la == lb:
                    continue
                w = (wa + wb) / 2.0
                if (sa > sb and la > lb) or (sa < sb and la < lb):
                    pos += w
                elif (sa > sb and la < lb) or (sa < sb and la > lb):
                    neg += w
                else:
                    spe += w
        i = j
    return {"pos_pairs": pos, "neg_pairs": neg, "special_pairs": spe,
            "pnpair": pos / max(neg, 1e-8)}


register_host_evaluator("pnpair")((_pnpair_state, _pnpair_batch, _pnpair_final))


# -- rankauc ----------------------------------------------------------------

def _rankauc_state():
    return {"auc_sum": 0.0, "n": 0}


def _rank_auc_one(scores, clicks, pvs):
    """(ref: RankAucEvaluator::calcRankAuc) — tie-aware trapezoid."""
    order = np.argsort(-scores, kind="stable")
    auc = click_sum = old_click_sum = 0.0
    no_click = no_click_sum = 0.0
    last = scores[order[0]] + 1.0
    for idx in order:
        if scores[idx] != last:
            auc += (click_sum + old_click_sum) * no_click / 2.0
            old_click_sum = click_sum
            no_click = 0.0
            last = scores[idx]
        no_click += pvs[idx] - clicks[idx]
        no_click_sum += no_click
        click_sum += clicks[idx]
    auc += (click_sum + old_click_sum) * no_click / 2.0
    denom = click_sum * no_click_sum
    return 0.0 if denom == 0.0 else auc / denom


def _rankauc_batch(cfg, args, state):
    out, click = args[0], args[1]
    pv = args[2] if len(args) > 2 else None
    scores = np.asarray(out.value)
    clicks = np.asarray(click.data, np.float64).reshape(scores.shape[0], -1)
    pvs = (np.asarray(pv.data, np.float64).reshape(scores.shape[0], -1)
           if pv is not None else np.ones_like(clicks))
    lengths = np.asarray(out.lengths) if out.lengths is not None else None
    for b in range(scores.shape[0]):
        L = int(lengths[b]) if lengths is not None else scores.shape[1] if scores.ndim > 2 else clicks.shape[1]
        s = scores[b].reshape(-1)[:L]
        state["auc_sum"] += _rank_auc_one(s, clicks[b].reshape(-1)[:L],
                                          pvs[b].reshape(-1)[:L])
        state["n"] += 1


def _rankauc_final(cfg, state):
    return {"rankauc": state["auc_sum"] / max(state["n"], 1)}


register_host_evaluator("rankauc")((_rankauc_state, _rankauc_batch, _rankauc_final))


# -- printers ---------------------------------------------------------------
# (ref: Evaluator.cpp value_printer/max_id_printer/seq_text_printer/
#  classification_error_printer — side-effect evaluators that log samples)

def _printer_state():
    return {"printed": 0}


def _make_printer(fmt_fn, limit=5):
    def batch(cfg, args, state):
        if state["printed"] >= limit:
            return
        from paddle_tpu.utils import get_logger
        log = get_logger("evaluator")
        log.info("[%s] %s", cfg.name, fmt_fn(cfg, args))
        state["printed"] += 1

    def final(cfg, state):
        return {}
    return (_printer_state, batch, final)


register_host_evaluator("value_printer")(_make_printer(
    lambda cfg, args: " ".join(np.array2string(np.asarray(a.data), threshold=20)
                               for a in args)))
register_host_evaluator("max_id_printer")(_make_printer(
    lambda cfg, args: np.array2string(
        np.argmax(np.asarray(args[0].value), axis=-1), threshold=50)))
# seq_text_printer: decodes id sequences (via dict_file when given) and either
# appends them to result_file or logs them
# (ref: Evaluator.cpp SequenceTextPrinter — result_file/dict_file/delimited).

def _seqtext_state():
    return {"printed": 0, "dict": None, "file_reset": False}


def _seqtext_batch(cfg, args, state):
    rows = []
    if state["dict"] is None and cfg.dict_file:
        with open(cfg.dict_file) as f:
            state["dict"] = [ln.rstrip("\n") for ln in f]
    vocab = state["dict"]
    sep = " " if cfg.delimited else ""
    for row, _ in _seq_rows(args[0]):
        toks = [int(x) for x in np.asarray(row).reshape(-1)]
        rows.append(sep.join(vocab[t] if vocab and 0 <= t < len(vocab)
                             else str(t) for t in toks))
    if cfg.result_file:
        mode = "a" if state["file_reset"] else "w"
        state["file_reset"] = True
        with open(cfg.result_file, mode) as f:
            f.write("\n".join(rows) + "\n")
    elif state["printed"] < 5:
        from paddle_tpu.utils import get_logger
        get_logger("evaluator").info("[%s] %s", cfg.name, " | ".join(rows[:8]))
        state["printed"] += 1


register_host_evaluator("seq_text_printer")(
    (_seqtext_state, _seqtext_batch, lambda cfg, state: {}))
def _cls_err_print(cfg, args):
    pred = np.argmax(np.asarray(args[0].value), axis=-1)
    labels = np.asarray(args[1].ids).reshape(pred.shape)
    return np.array2string((pred != labels).astype(np.int32), threshold=50)


register_host_evaluator("classification_error_printer")(
    _make_printer(_cls_err_print))


def _max_frame_print(cfg, args):
    """Per sequence, print the value-maximizing frame and its index
    (ref: Evaluator.cpp MaxFramePrinter — selects each sequence's frame
    with the maximal output value)."""
    a = args[0]
    if a.value is None:
        raise ValueError(
            f"max_frame_printer on {cfg.input_layer_names!r}: probed layer has "
            f"no dense value (ids-only output) — point it at a layer that "
            f"emits values")
    v = np.asarray(a.value)
    if v.ndim == 2:
        v = v[:, None, :]               # [B, 1, D]: non-sequence = 1 frame
    lengths = np.asarray(a.lengths) if a.lengths is not None else None
    lines = []
    for b in range(v.shape[0]):
        L = int(lengths[b]) if lengths is not None else v.shape[1]
        frames = v[b, :max(L, 1)]
        t = int(np.argmax(frames.max(axis=-1)))
        lines.append(f"seq {b}: frame {t} "
                     f"{np.array2string(frames[t], threshold=10)}")
    return "; ".join(lines[:8])


register_host_evaluator("max_frame_printer")(_make_printer(_max_frame_print))

# gradient_printer: prints the probed layer's OUTPUT GRADIENT, delivered by
# the trainer as a __grad__<layer> Argument computed via an additive-zero
# probe (ref: Evaluator.cpp GradientPrinter reads Layer::getOutputGrad() —
# autodiff has no per-layer grad buffers, so the probe recreates them on
# demand for exactly the printed layers).
register_host_evaluator("gradient_printer")(_make_printer(
    lambda cfg, args: " ".join(np.array2string(np.asarray(a.data), threshold=20)
                               for a in args)))


# -- driver -----------------------------------------------------------------

class EvaluatorSet:
    """Accumulates all configured evaluators across batches
    (ref: Evaluator start/eval/finish + printStats protocol)."""

    # validation layer type -> evaluator it hosts (ref: ValidationLayer.cpp
    # AucValidation::init sets type 'last-column-auc', PnpairValidation::init
    # sets 'pnpair'; the layer is a pass-through registered in
    # graph/layers_cost.py)
    _VALIDATION_LAYERS = {"auc-validation": "last-column-auc",
                          "pnpair-validation": "pnpair"}

    def __init__(self, model: ModelConfig):
        evals = list(model.evaluators)
        for layer in model.layers:
            ev_type = self._VALIDATION_LAYERS.get(layer.type)
            if ev_type is not None:
                evals.append(EvaluatorConfig(
                    name=layer.name, type=ev_type,
                    input_layer_names=[i.input_layer_name
                                       for i in layer.inputs]))
        self.configs = [e for e in evals if e.type in evaluator_registry]
        self.host_configs = [e for e in evals
                             if e.type in host_evaluator_registry]
        # True = silently skip evaluators whose input layers are absent
        # from the step outputs (the Trainer sets this under pipeline
        # parallelism, where stage-internal activations never surface);
        # False (default) = a missing layer is a loud config error
        self.allow_missing = False

    @staticmethod
    def _host_keys(cfg: EvaluatorConfig) -> list[str]:
        """Output-dict keys one host evaluator consumes: layer names, or the
        trainer-provided __grad__<layer> probe results for gradient_printer."""
        if cfg.type == "gradient_printer":
            return ["__grad__" + n for n in cfg.input_layer_names]
        return list(cfg.input_layer_names)

    @property
    def host_layer_names(self) -> list[str]:
        """Keys host evaluators need fetched from the step outputs each batch."""
        names: list[str] = []
        for cfg in self.host_configs:
            for n in self._host_keys(cfg):
                if n not in names:
                    names.append(n)
        return names

    def new_host_state(self) -> dict:
        return {cfg.name: host_evaluator_registry[cfg.type][0]()
                for cfg in self.host_configs}

    def host_update(self, host_state: dict, outputs: dict[str, Argument]) -> None:
        """Feed one batch's (host-resident) outputs to every host evaluator."""
        cache = {n: _np_arg(outputs[n]) for n in self.host_layer_names
                 if n in outputs}
        for cfg in self.host_configs:
            keys = self._host_keys(cfg)
            missing = [n for n in keys if n not in cache]
            if missing:
                if self.allow_missing:
                    continue   # stage-internal under pipeline parallelism
                raise KeyError(
                    f"host evaluator {cfg.name!r} ({cfg.type}) references "
                    f"{missing} absent from the step outputs")
            args = [cache[n] for n in keys]
            host_evaluator_registry[cfg.type][1](cfg, args, host_state[cfg.name])

    def finalize_host(self, host_state: dict) -> dict[str, float]:
        out: dict[str, float] = {}
        many = len(self.host_configs) + len(self.configs) > 1
        for cfg in self.host_configs:
            res = host_evaluator_registry[cfg.type][2](cfg, host_state[cfg.name])
            for k, v in res.items():
                out[f"{cfg.name}.{k}" if many else k] = float(v)
        return out

    def batch_partials(self, outputs, feed) -> dict[str, dict]:
        """Called inside jit: returns {evaluator_name: partials}.

        When `allow_missing` is set (the Trainer sets it under pipeline
        parallelism, where intermediate activations never materialize
        outside their stage), an evaluator whose input layers are
        unavailable is skipped; on the plain path a missing layer is a
        config error and fails loudly."""
        res = {}
        for cfg in self.configs:
            missing = [n for n in cfg.input_layer_names
                       if n not in outputs and n not in feed]
            if missing:
                if self.allow_missing:
                    continue
                raise KeyError(
                    f"evaluator {cfg.name!r} ({cfg.type}) references "
                    f"layer(s) {missing} absent from the forward outputs")
            batch_fn, _ = evaluator_registry[cfg.type]
            res[cfg.name] = batch_fn(cfg, outputs, feed)
        return res

    def new_accumulator(self) -> dict:
        return {}

    def accumulate_stacked(self, acc: dict, stacked: dict, n: int) -> dict:
        """Fold a fused dispatch's per-step partials (each leaf stacked
        [n, ...] along a leading step axis by the k-step lax.scan) into the
        accumulator — ONE device fetch for the whole group, then the same
        per-step float64 additions, in the same order, as n separate
        `accumulate` calls: the fused path's evaluator results stay
        bit-identical to the per-batch loop's."""
        if not stacked:
            return acc
        host = jax.tree.map(np.asarray, jax.device_get(stacked))
        for i in range(n):
            acc = self.accumulate(
                acc, jax.tree.map(lambda a: a[i], host))
        return acc

    def accumulate(self, acc: dict, partials: dict) -> dict:
        for name, parts in partials.items():
            if name not in acc:
                acc[name] = {k: np.asarray(v, np.float64) for k, v in parts.items()}
            else:
                for k, v in parts.items():
                    acc[name][k] = acc[name][k] + np.asarray(v, np.float64)
        return acc

    def finalize(self, acc: dict) -> dict[str, float]:
        out: dict[str, float] = {}
        many = len(self.configs) + len(self.host_configs) > 1
        for cfg in self.configs:
            if cfg.name not in acc:
                continue
            _, fin = evaluator_registry[cfg.type]
            for k, v in fin(cfg, acc[cfg.name]).items():
                out[f"{cfg.name}.{k}" if many else k] = float(
                    np.asarray(v).reshape(-1)[0]) if np.ndim(v) == 0 or np.size(v) == 1 \
                    else v
        return out
