"""Autoregressive decoding for sequence-in/logits-out models (the
transformer LM family).

The reference's generation story is beam search over recurrent groups
(RecurrentGradientMachine; graph/generator.py here).  Full-sequence
attention models have no recurrent group to unroll, so this provides the
matching TPU-native decode loop: ONE compiled `lax.scan` over a
fixed-size token buffer — each step runs the full forward on the padded
prefix (masked by the running length), reads the next-token logits at the
last valid position, and samples greedy / temperature / top-k / top-p.

Two decode modes:
  * whole-prefix re-forward (default) — each step runs the full forward on
    the padded buffer; O(T^2) total but zero layer-level support needed,
    and at short contexts it is one fused program XLA pipelines well.
  * `use_cache=True` — per-layer KV caches (init_kv_caches) ride the
    executor's state channel (the same threading as BN moving stats); each
    step runs the stack on ONE new token per row against the caches
    (ops/attention.py:cached_attention_step) — O(T) per token, the
    long-context decode path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from paddle_tpu.graph.builder import GraphExecutor
from paddle_tpu.graph.context import TEST
from paddle_tpu.graph.registry import slot_state_types
from paddle_tpu.parameter.argument import Argument

Array = jax.Array


def nucleus_filter(scaled: Array, top_p: float) -> Array:
    """Top-p (nucleus) cut on [B, V] logits: keep the smallest
    probability-sorted prefix whose cumulative mass reaches top_p (the
    first token AT the threshold stays in — the standard formulation),
    -inf elsewhere.  Kept support is EXACT: indices are scattered back
    from the sorted order, so logit ties at the cutoff can never widen
    the set (same discipline as the top-k branch in lm_generate)."""
    if not 0.0 < top_p < 1.0:
        return scaled
    order = jnp.argsort(scaled, axis=-1)[:, ::-1]            # desc
    srt = jnp.take_along_axis(scaled, order, axis=-1)
    probs = jax.nn.softmax(srt, axis=-1)
    keep = jnp.cumsum(probs, axis=-1) - probs < top_p        # n_keep >= 1
    return jnp.full_like(scaled, -jnp.inf).at[
        jnp.arange(scaled.shape[0])[:, None], order].set(
        jnp.where(keep, srt, -jnp.inf))


def pick_next(last: Array, key: Optional[Array], temperature: float = 0.0,
              top_k: int = 0, top_p: float = 0.0,
              is_probs: bool = False) -> Array:
    """One sampling decision on [B, V] next-token scores -> [B] int32.

    Module-level (not a closure inside lm_generate) so the serving
    engine's per-slot sampler (serving/sampler.py:pick_next_per_slot) can
    hold itself to EXACTLY these semantics — any drift between the two
    shows up as a token divergence in the serving parity oracle.

    `is_probs`: the logits layer emits probabilities (softmax activation)
    — sample through log; raw-activation layers sample directly."""
    last = jnp.log(jnp.maximum(last.astype(jnp.float32), 1e-30)) \
        if is_probs else last.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    scaled = last / temperature
    if top_k > 0:
        # exact k-best support via top_k (ref pattern:
        # graph/generator.py beam candidate selection): scatter the
        # k values back to -inf elsewhere so ties at the kth value
        # can never widen the candidate set
        vals, idxs = jax.lax.top_k(scaled, top_k)
        scaled = jnp.full_like(scaled, -jnp.inf).at[
            jnp.arange(scaled.shape[0])[:, None], idxs].set(vals)
    scaled = nucleus_filter(scaled, top_p)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def _chunked_scan(step, carry, keys, chunk: int, done_of):
    """`lax.scan(step, carry, keys)` split into `chunk`-step scans with a
    HOST all-done check between chunks: a batch whose every row hit eos at
    step 5 of max_new=512 stops paying for the 507 dead steps.  Bit-exact
    with the single scan (scan composes sequentially; done rows are frozen
    by `advance`, so skipped trailing steps are no-ops on the outputs, and
    the pre-split keys mean skipped steps never consumed rng).  Compiled
    signatures stay bounded: one `chunk`-length scan program plus at most
    one remainder-length program."""
    if chunk <= 0 or chunk >= keys.shape[0]:
        carry, _ = jax.lax.scan(step, carry, keys)
        return carry
    i = 0
    while i < keys.shape[0]:
        n = min(chunk, keys.shape[0] - i)
        carry, _ = jax.lax.scan(step, carry, keys[i:i + n])
        i += n
        if i < keys.shape[0] and bool(jnp.all(done_of(carry))):
            break
    return carry


def _resolve_io_names(model, input_name, logits_name):
    """Default input = first data layer; default logits = last non-cost,
    non-validation layer (shared by lm_generate / lm_beam_generate)."""
    if input_name is None:
        input_name = model.input_layer_names[0]
    if logits_name is None:
        from paddle_tpu.graph.registry import (cost_layer_types,
                                               validation_layer_types)
        skip = cost_layer_types | validation_layer_types | {"data"}
        logits_name = [l.name for l in model.layers if l.type not in skip][-1]
    return input_name, logits_name


def _prefill(executor, params, input_name, logits_name, prompt_ids,
             prompt_lengths, total):
    """Fill fresh KV caches with one forward over the padded prompt; return
    (state, last-valid-position logits [B, V])."""
    state = init_kv_caches(executor, prompt_ids.shape[0], total)
    outputs, _, state = executor.forward(
        params, {input_name: Argument(ids=prompt_ids,
                                      lengths=prompt_lengths)},
        state, TEST, None)
    logits = outputs[logits_name].value
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0, :]
    return state, last


def lm_generate(
    executor: GraphExecutor,
    params: dict[str, Array],
    prompt_ids,                   # [B, P] int32 prompt tokens
    prompt_lengths=None,          # [B] valid prompt lengths (default: P)
    max_new: int = 32,
    *,
    input_name: Optional[str] = None,
    logits_name: Optional[str] = None,
    temperature: float = 0.0,     # 0 = greedy
    top_k: int = 0,               # 0 = full distribution
    top_p: float = 0.0,           # 0 = no nucleus cut; else keep the
                                  # smallest prefix with cum. prob >= top_p
    eos_id: int = -1,             # -1 = never stop early
    rng: Optional[Array] = None,
    use_cache: bool = False,      # O(T) per-token decode via KV caches
    early_exit_chunk: int = 0,    # >0: decode in chunked scans with a host
                                  # all-done check between chunks (eos
                                  # batches stop paying for dead steps)
):
    """Returns (tokens [B, P+max_new], lengths [B]) — the prompt plus up to
    max_new sampled tokens per row (rows stop growing at eos_id).

    The model is any config whose `input_name` data layer takes an id
    sequence and whose `logits_name` layer emits [B, T, vocab]
    (next-token distribution at each position) — the transformer LM
    shape.  Defaults: the first id-sequence input layer and the last
    non-cost layer.
    """
    model = executor.model
    input_name, logits_name = _resolve_io_names(model, input_name,
                                                logits_name)

    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    B, P = prompt_ids.shape
    total = P + max_new
    if prompt_lengths is None:
        prompt_lengths = jnp.full((B,), P, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    buf0 = jnp.zeros((B, total), jnp.int32).at[:, :P].set(prompt_ids)

    # only reject knob values that would actually change sampling (the
    # same effective ranges the sampler uses: top_p in (0,1), top_k > 0)
    if temperature <= 0.0 and (top_k > 0 or 0.0 < top_p < 1.0):
        raise ValueError(
            f"top_k={top_k}/top_p={top_p} need temperature > 0 — "
            f"temperature=0 means greedy argmax, which would silently "
            f"ignore them")

    import functools
    sample = functools.partial(
        pick_next, temperature=temperature, top_k=top_k, top_p=top_p,
        is_probs=_is_probs(model, logits_name))

    def advance(buf, lengths, done, nxt):
        # frozen rows keep their buffer and length
        write_pos = jnp.clip(lengths, 0, total - 1)
        new_buf = buf.at[jnp.arange(B), write_pos].set(
            jnp.where(done, buf[jnp.arange(B), write_pos], nxt))
        new_len = jnp.where(done, lengths, jnp.minimum(lengths + 1, total))
        new_done = jnp.logical_or(done, jnp.logical_or(
            nxt == eos_id, new_len >= total))
        return new_buf, new_len, new_done

    if max_new == 0:
        return buf0, prompt_lengths
    keys = jax.random.split(rng, max_new)

    # compile observability: the batch decode compiles per
    # (B, P, max_new, path, knob) tuple — the first call with a new tuple
    # records a compile event on the `compile` lane (obs/compile_watch.py),
    # so a caller churning shapes shows up as a recompile storm instead of
    # a silent slowdown.  id(executor) scopes the key per model instance.
    from paddle_tpu.obs.compile_watch import get_compile_watch
    _cw = get_compile_watch().watch(
        "lm_decode.generate",
        (id(executor), B, P, int(max_new), bool(use_cache),
         int(early_exit_chunk), float(temperature), int(top_k),
         float(top_p), int(eos_id)))

    if use_cache:
        # O(total) per token: prefill the per-layer KV caches on the padded
        # prompt once, then each step runs the stack on ONE new token per
        # row, threading the caches through the executor's state channel
        with _cw:
            state, last = _prefill(executor, params, input_name,
                                   logits_name, prompt_ids, prompt_lengths,
                                   total)
            nxt = sample(last, keys[0])
            buf, lengths, done = advance(buf0, prompt_lengths,
                                         jnp.zeros((B,), bool), nxt)

            def step_cached(carry, key):
                buf, lengths, done, state = carry
                tok = buf[jnp.arange(B),
                          jnp.clip(lengths - 1, 0, total - 1)]
                feed = {input_name: Argument(ids=tok[:, None],
                                             lengths=jnp.ones((B,),
                                                              jnp.int32))}
                outputs, _, state = executor.forward(params, feed, state,
                                                     TEST, None)
                nxt = sample(outputs[logits_name].value[:, 0, :], key)
                buf, lengths, done = advance(buf, lengths, done, nxt)
                return (buf, lengths, done, state), None

            buf, lengths, _, _ = _chunked_scan(
                step_cached, (buf, lengths, done, state), keys[1:],
                early_exit_chunk, done_of=lambda c: c[2])
        return buf, lengths

    def step(carry, key):
        buf, lengths, done = carry
        feed = {input_name: Argument(ids=buf, lengths=lengths)}
        outputs, _, _ = executor.forward(params, feed, None, TEST, None)
        logits = outputs[logits_name].value          # [B, total, V]
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0, :]
        nxt = sample(last, key)
        return advance(buf, lengths, done, nxt), None

    with _cw:
        buf, lengths, _ = _chunked_scan(
            step, (buf0, prompt_lengths, jnp.zeros((B,), bool)), keys,
            early_exit_chunk, done_of=lambda c: c[2])
    return buf, lengths


def init_kv_caches(executor: GraphExecutor, batch: int, total: int) -> dict:
    """Zeroed per-attention-layer KV caches sized for `total` positions.
    Passing this dict as `state` to executor.forward flips every causal
    multi_head_attention layer into its incremental cached path
    (graph/layers_attn.py:_cached_step)."""
    dtype = jnp.dtype(executor.compute_dtype) if executor.compute_dtype \
        else jnp.float32
    state: dict = {}
    for l in executor.model.layers:
        if l.type in slot_state_types:
            raise ValueError(
                f"layer {l.name!r}: a recurrent layer has no dense cache "
                f"here — decode a model with recurrent layers through the "
                f"serving engine (its slot state, serving/paged_kv.py) or "
                f"with use_cache=False")
        if l.type == "multi_head_attention":
            heads = int(l.attrs["num_heads"])
            h_kv = int(l.attrs.get("num_kv_heads", 0) or heads)
            dh = int(l.size) // heads
            state[l.name] = {
                "k": jnp.zeros((batch, total, h_kv, dh), dtype),
                "v": jnp.zeros((batch, total, h_kv, dh), dtype),
            }
        elif l.type == "mla_attention":
            # one latent row [c_kv, k_pe] a token, at the paged pool's
            # width so a prefill's rows pack into pages as they are
            from paddle_tpu.ops.mla import lane_width
            width = lane_width(int(l.attrs["kv_lora_rank"]) +
                               int(l.attrs["qk_rope_head_dim"]))
            state[l.name] = {"kv": jnp.zeros((batch, total, width), dtype)}
        else:
            continue
        state[l.name]["pos"] = jnp.zeros((batch,), jnp.int32)
    assert state, "model has no attention layers to cache"
    return state


def _is_probs(model, logits_name: str) -> bool:
    """Whether the logits layer emits probabilities (softmax activation) —
    sampled through log; raw-activation layers sample directly."""
    for l in model.layers:
        if l.name == logits_name:
            return l.active_type in ("softmax", "sequence_softmax")
    return False


def build_draft_roll(executor: GraphExecutor, *,
                     input_name: Optional[str] = None,
                     logits_name: Optional[str] = None):
    """Build the GREEDY k-chain rollout a batched serving drafter jits:
    `roll(params, buf, lens, k) -> [B, k]` proposals, where `buf` is a
    [B, W + k] windowed-context buffer (each row's last valid token at
    `lens[b] - 1`, k columns of slack on the right) and k is STATIC.

    One `lax.scan` of k whole-window forwards: each body re-forwards the
    padded buffer (the zero-support decode mode of `lm_generate` — no KV
    cache to thread, so the rollout stays a pure params/ids -> tokens
    function the serving engine can jit under ONE signature per (B, k)),
    reads the last valid position's logits, takes the shared greedy pick
    (serving/sampler.py:greedy_next — the drafter/sampler tie contract),
    and appends.  Causal masking makes the right-side slack inert, so
    garbage past `lens` can never leak into a proposal.

    Cost: k forwards over W + k positions of whatever model `executor`
    holds — a tiny draft transformer, or the TARGET itself over a
    truncated window (self-speculation; the window cap is what makes it
    cheaper than real decode at long contexts).  Proposals are guesses
    by construction: the verify step re-scores every chain exactly, so
    nothing here can change an emitted token."""
    model = executor.model
    input_name, logits_name = _resolve_io_names(model, input_name,
                                                logits_name)
    from paddle_tpu.serving.sampler import greedy_next

    def roll(params, buf, lens, k: int):
        B, W = buf.shape

        def body(carry, _):
            buf, lens = carry
            feed = {input_name: Argument(ids=buf, lengths=lens)}
            outputs, _, _ = executor.forward(params, feed, None, TEST,
                                             None)
            logits = outputs[logits_name].value        # [B, W, V]
            last = jnp.take_along_axis(
                logits, (jnp.clip(lens, 1, W) - 1)[:, None, None],
                axis=1)[:, 0, :]
            nxt = greedy_next(last)
            buf = buf.at[jnp.arange(B),
                         jnp.clip(lens, 0, W - 1)].set(nxt)
            lens = jnp.minimum(lens + 1, W)
            return (buf, lens), nxt

        _, toks = jax.lax.scan(body, (buf, lens), None, length=k)
        return toks.T                                  # [k, B] -> [B, k]

    return roll


def lm_beam_generate(
    executor: GraphExecutor,
    params: dict[str, Array],
    prompt_ids,                   # [B, P] int32 prompt tokens
    prompt_lengths=None,          # [B] valid prompt lengths (default: P)
    beam_size: int = 4,
    max_new: int = 32,
    *,
    input_name: Optional[str] = None,
    logits_name: Optional[str] = None,
    eos_id: int = -1,             # -1 = never finish early
):
    """Beam search for the LM family — the generation story the reference
    gives recurrent models (RecurrentGradientMachine::beamSearch,
    graph/generator.py here) extended to full-attention models, built on
    the KV-cache decode path: caches are prefilled once per source row,
    tiled to B*beam, and REORDERED by beam parent at every step (the cache
    gather is the TPU-native analog of the reference's per-Path state
    copying).

    Scoring is the plain sum of token log-probabilities (the reference's
    Path::logProb accumulation); a beam that emits `eos_id` is frozen —
    its only continuation is eos at logprob 0.  Returns
    (tokens [B, beam, P+max_new], lengths [B, beam], scores [B, beam]),
    beams sorted best-first per row.
    """
    model = executor.model
    input_name, logits_name = _resolve_io_names(model, input_name,
                                                logits_name)

    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    B, P = prompt_ids.shape
    K = beam_size
    total = P + max_new
    if prompt_lengths is None:
        prompt_lengths = jnp.full((B,), P, jnp.int32)
    else:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)

    def logprobs_of(raw):                              # [N, V] -> log p
        raw = raw.astype(jnp.float32)
        if _is_probs(model, logits_name):
            return jnp.log(jnp.maximum(raw, 1e-30))
        return jax.nn.log_softmax(raw, axis=-1)

    if max_new == 0:
        buf = jnp.zeros((B, K, total), jnp.int32).at[:, :, :P].set(
            prompt_ids[:, None, :])
        return (buf, jnp.repeat(prompt_lengths[:, None], K, 1),
                jnp.zeros((B, K), jnp.float32))

    # ---- prefill ONCE per source row, then tile caches to B*K ----
    state, last = _prefill(executor, params, input_name, logits_name,
                           prompt_ids, prompt_lengths, total)
    lp0 = logprobs_of(last)                            # [B, V]
    V = lp0.shape[-1]
    state = jax.tree.map(lambda x: jnp.repeat(x, K, axis=0), state)

    # first expansion: top-K tokens of the last prompt position seed the
    # beams (all beams share the prompt, so expanding every beam would
    # produce K duplicates of the same K tokens)
    scores, tok0 = jax.lax.top_k(lp0, K)               # [B, K] each
    buf = jnp.zeros((B, K, total), jnp.int32).at[:, :, :P].set(
        prompt_ids[:, None, :])
    lengths = jnp.repeat(prompt_lengths[:, None], K, axis=1)  # [B, K]
    bi, ki = jnp.arange(B)[:, None], jnp.arange(K)[None, :]
    buf = buf.at[bi, ki, lengths].set(tok0)
    lengths = lengths + 1
    done = (tok0 == eos_id)

    def step(carry, _):
        buf, lengths, scores, done, state = carry
        tok = buf.reshape(B * K, total)[
            jnp.arange(B * K),
            jnp.clip(lengths.reshape(B * K) - 1, 0, total - 1)]
        feed = {input_name: Argument(ids=tok[:, None],
                                     lengths=jnp.ones((B * K,), jnp.int32))}
        outputs, _, state = executor.forward(params, feed, state, TEST, None)
        lp = logprobs_of(outputs[logits_name].value[:, 0, :]) \
            .reshape(B, K, V)
        # frozen beams: eos continues at logprob 0, everything else -inf
        frozen = jnp.full((V,), -jnp.inf).at[jnp.maximum(eos_id, 0)].set(0.0)
        lp = jnp.where(done[:, :, None], frozen[None, None, :], lp)
        cand = scores[:, :, None] + lp                 # [B, K, V]
        scores, flat = jax.lax.top_k(cand.reshape(B, K * V), K)
        parent, tok_new = flat // V, (flat % V).astype(jnp.int32)  # [B, K]

        # reorder beams by parent: token buffers, lengths, done, KV caches
        buf = jnp.take_along_axis(buf, parent[:, :, None], axis=1)
        lengths = jnp.take_along_axis(lengths, parent, axis=1)
        done = jnp.take_along_axis(done, parent, axis=1)

        def reorder(x):                                # [B*K, ...] leaves
            xk = x.reshape(B, K, *x.shape[1:])
            idx = parent.reshape(B, K, *([1] * (x.ndim - 1)))
            return jnp.take_along_axis(xk, idx, axis=1) \
                .reshape(B * K, *x.shape[1:])

        state = jax.tree.map(reorder, state)

        write = jnp.where(done, buf[bi, ki, jnp.clip(lengths, 0, total - 1)],
                          tok_new)
        buf = buf.at[bi, ki, jnp.clip(lengths, 0, total - 1)].set(write)
        lengths = jnp.where(done, lengths, jnp.minimum(lengths + 1, total))
        done = jnp.logical_or(done, tok_new == eos_id)
        return (buf, lengths, scores, done, state), None

    (buf, lengths, scores, _, _), _ = jax.lax.scan(
        step, (buf, lengths, scores, done, state), None, length=max_new - 1)
    # top_k keeps each row's beams sorted best-first already
    return buf, lengths, scores
