"""GraphExecutor — compiles a ModelConfig into pure JAX functions.

TPU-native replacement for the reference's GradientMachine/NeuralNetwork
executor family (ref: paddle/gserver/gradientmachines/GradientMachine.cpp:31-60
factory; NeuralNetwork.cpp:230-288 forward/backward loops;
RecurrentGradientMachine.cpp per-timestep frame unrolling).

Re-design: instead of per-layer virtual forward()/backward() calls over
mutable Arguments, the whole graph becomes ONE pure function
`forward(params, feed) -> (outputs, costs, state)` traced and compiled by XLA;
`jax.grad` of the summed costs replaces every hand-written backward.  The
reference's RecurrentGradientMachine — which clones a frame network per
timestep and wires memories between frames — becomes a `lax.scan` whose body
executes the sub-model's layers, with memories as the scan carry.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.config.schema import LayerConfig, ModelConfig, SubModelConfig
from paddle_tpu.graph.context import ForwardContext, TRAIN
from paddle_tpu.graph.layers_cost import fused_softmax_cost
from paddle_tpu.graph.registry import get_layer_fn, register_layer
from paddle_tpu.parameter.argument import Argument
from paddle_tpu.parameter.init import init_parameter

Array = jax.Array


# Agent layer types are placeholders fed by the executor, like the reference's
# AgentLayer/ScatterAgentLayer/GatherAgentLayer plumbing
# (ref: paddle/gserver/layers/AgentLayer.cpp).
@register_layer("agent", "sequence_agent", "scatter_agent", "sequence_scatter_agent",
                "gather_agent", "sequence_gather_agent")
def _agent_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    raise AssertionError(f"agent layer {cfg.name!r} must be fed by the executor")


@register_layer("get_output")
def _get_output_layer(ctx: ForwardContext, cfg: LayerConfig) -> Argument:
    """Expose a sub-model out_link (ref: GetOutputLayer.cpp); by the time the
    root walk reaches it, the scan has published the linked output."""
    return ctx.get_input(cfg, 0)


class GraphExecutor:
    """Builds and runs the layer graph described by a ModelConfig."""

    def __init__(self, model: ModelConfig, mesh=None, compute_dtype: str = ""):
        self.model = model
        self.mesh = mesh  # enables parallel layer paths (ring attention)
        # '' = run in param dtype; 'bfloat16' casts float params + inputs for
        # MXU-speed matmuls while softmax/log/BN-stats/costs stay float32
        # (settings(compute_dtype=...) / --compute_dtype)
        self.compute_dtype = compute_dtype
        self.layer_map: dict[str, LayerConfig] = {l.name: l for l in model.layers}
        # layers belonging to a recurrent sub-model are executed by its scan
        # (layer_names holds only the INNERMOST group's layers, so _sub_of
        # maps each layer to the group whose step body runs it)
        self._sub_of: dict[str, SubModelConfig] = {}
        self._sub_by_name: dict[str, SubModelConfig] = {}
        for sm in model.sub_models:
            if sm.is_recurrent_layer_group:
                self._sub_by_name[sm.name] = sm
                for ln in sm.layer_names:
                    self._sub_of[ln] = sm
        # per-group execution plans (nested groups appear as ('scan', child)
        # items inside their parent's plan)
        self._sub_plan: dict[str, list[tuple[str, Any]]] = {}
        self._plan = self._build_plan()
        # per-group suffix-deferral splits (see _split_deferred), lazy
        self._defer_cache: dict[str, Optional[dict]] = {}

    # -- planning ---------------------------------------------------------
    def _build_plan(self) -> list[tuple[str, Any]]:
        """Execution plans: ('layer', cfg) and ('scan', sub_model) items in
        config order (the DSL emits layers topologically, like config_parser).
        The top-level plan holds root groups; each group's own plan
        (self._sub_plan) interleaves its layers with nested child scans."""
        plan: list[tuple[str, Any]] = []
        seen_subs: set[str] = set()
        for l in self.model.layers:
            sm = self._sub_of.get(l.name)
            if sm is None:
                if l.type != "data":
                    plan.append(("layer", l))
                continue
            self._sub_plan.setdefault(sm.name, []).append(("layer", l))
            # first appearance of a group (or of any of its descendants)
            # emits a ('scan', group) item into its parent's plan
            child = sm
            while child is not None and child.name not in seen_subs:
                seen_subs.add(child.name)
                if child.parent:
                    self._sub_plan.setdefault(child.parent, []).append(
                        ("scan", child))
                    child = self._sub_by_name[child.parent]
                else:
                    plan.append(("scan", child))
                    child = None
        return plan

    # -- parameters -------------------------------------------------------
    def init_params(self, rng: jax.Array, dtype=None) -> dict[str, Array]:
        """Every parameter from its config's initializer; with `dtype`,
        floating parameters are cast to it one by one as they are made
        (a server holding bf16 weights never holds the fp32 set)."""
        params: dict[str, Array] = {}
        for i, pc in enumerate(self.model.parameters):
            v = init_parameter(pc, jax.random.fold_in(rng, i))
            if dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(dtype)
            params[pc.name] = v
        return params

    def init_state(self) -> dict[str, Any]:
        """Mutable layer state (batch-norm moving stats) — built lazily on the
        first forward; an empty dict is a valid initial state."""
        return {}

    @property
    def static_param_names(self) -> set[str]:
        return {p.name for p in self.model.parameters if p.is_static}

    def cast_params(self, params: dict[str, Array]) -> dict[str, Array]:
        """The parameters as the layers compute with them: floating leaves
        in `compute_dtype`, everything else — and every leaf when no
        compute dtype is set — as given.  A leaf that already has the
        compute dtype IS the given leaf (no copy, and no op inside a jit),
        so a caller whose weights do not change between steps casts once
        outside the step (ServingEngine.params) and `prepare` then finds
        nothing to do."""
        if not self.compute_dtype:
            return params
        dt = jnp.dtype(self.compute_dtype)
        return {k: (v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating)
                    and v.dtype != dt else v) for k, v in params.items()}

    # -- forward ----------------------------------------------------------
    def prepare(self, params: dict[str, Array], feed: dict[str, Argument]):
        """Pre-forward transforms shared by every execution path (plain
        forward and the pipeline executor): stop_gradient on static
        parameters, and the mixed-precision cast of float params/inputs."""
        static = self.static_param_names
        if static:
            params = {k: (jax.lax.stop_gradient(v) if k in static else v)
                      for k, v in params.items()}
        params = self.cast_params(params)
        if self.compute_dtype:
            dt = jnp.dtype(self.compute_dtype)
            def _cast(arg):
                if (arg.value is not None
                        and jnp.issubdtype(arg.value.dtype, jnp.floating)):
                    arg = arg.replace(value=arg.value.astype(dt))
                if arg.sparse_vals is not None:
                    arg = arg.replace(sparse_vals=arg.sparse_vals.astype(dt))
                return arg
            feed = {name: _cast(arg) for name, arg in feed.items()}
        return params, feed

    def forward(
        self,
        params: dict[str, Array],
        feed: dict[str, Argument],
        state: Optional[dict[str, Any]] = None,
        mode: str = TRAIN,
        rng: Optional[jax.Array] = None,
        probes: Optional[dict[str, Array]] = None,
        rows: Optional[dict[str, Array]] = None,
        scopes: Optional[dict[str, str]] = None,
    ) -> tuple[dict[str, Argument], dict[str, Array], dict[str, Any]]:
        """Run the graph. Returns (layer outputs, per-sample costs, new state).

        `probes` maps layer names to zero arrays added to those layers'
        outputs: grad of the loss w.r.t. a probe IS that layer's output
        gradient — how the gradient_printer evaluator observes what the
        reference reads from Layer::getOutputGrad() (ref: Evaluator.cpp
        GradientPrinter; hand-written backward buffers replaced by autodiff).

        `rows` maps a top-level layer's name to a 1-D int index array: that
        layer runs on those positions of its inputs' token axis alone
        ([B, T, d] -> [B, R, d], lengths R) and its output has R rows —
        how a serving step keeps a vocabulary head off the rows it does
        not sample.  Refused (ValueError) for a layer inside a recurrent
        group, an input that is not a dense sequence, and a layer that
        would read the cut-down output in this same forward.

        `scopes` maps a top-level layer's name to the `jax.named_scope`
        its ops are traced under (a profiler trace then names them).

        Every layer's output is the caller's to read, so every layer runs
        as configured; `loss` is the entry point that may fuse a softmax
        head with its cost.
        """
        return self._run(params, feed, state, mode, rng, probes, {},
                         rows, scopes)

    def _run(self, params, feed, state, mode, rng, probes,
             heads: dict[str, LayerConfig], rows=None, scopes=None):
        """The plan, layer by layer.  `heads` maps an fc's name to the cost
        layer it may run with as one op (`_fusable_softmax_costs`): the fc
        is passed over at its own place and the pair runs at the cost's."""
        rows, scopes = rows or {}, scopes or {}
        for name in (*rows, *scopes):
            if name in self._sub_of or name not in self.layer_map \
                    or self.layer_map[name].type == "data":
                raise ValueError(
                    f"rows/scopes name {name!r}: not a top-level layer the "
                    f"plan runs (a recurrent group's layers run in its scan)")
        params, feed = self.prepare(params, feed)
        ctx = ForwardContext(
            model=self.model, params=params, mode=mode, rng=rng,
            state_in=state or {}, mesh=self.mesh,
        )
        for name, arg in feed.items():
            ctx.outputs[name] = arg
        held: dict[str, LayerConfig] = {}       # cost name -> its fc
        for kind, item in self._plan:
            if kind == "layer":
                cfg: LayerConfig = item
                cost = heads.get(cfg.name)
                if cost is not None and not (probes and cfg.name in probes) \
                        and self._fused_inputs_ready(ctx, cfg, cost):
                    held[cost.name] = cfg
                    continue
                if cfg.name in held:
                    fused_softmax_cost(ctx, held[cfg.name], cfg)
                    continue
                if any(inp.input_layer_name not in ctx.outputs for inp in cfg.inputs):
                    # depends on a generator group's output — only produced by
                    # generate(); skip in plain forward
                    continue
                if rows:
                    self._refuse_cut_input(
                        rows, f"layer {cfg.name!r}",
                        (inp.input_layer_name for inp in cfg.inputs))
                with jax.named_scope(scopes[cfg.name]) \
                        if cfg.name in scopes else contextlib.nullcontext():
                    if cfg.name in rows:
                        out = self._layer_on_rows(ctx, cfg, rows[cfg.name])
                    else:
                        out = get_layer_fn(cfg.type)(ctx, cfg)
                if probes and cfg.name in probes and out.value is not None:
                    out = out.replace(value=out.value + probes[cfg.name])
                ctx.outputs[cfg.name] = out
            else:
                sm: SubModelConfig = item
                if sm.generator is not None and not sm.in_links:
                    continue  # generation-only group: run via generate()
                if rows:
                    self._refuse_cut_input(
                        rows, f"recurrent group {sm.name!r}",
                        (*sm.in_links, *sm.static_links,
                         *(m.boot_layer_name for m in sm.memories)))
                self._run_scan(ctx, sm)
        return ctx.outputs, ctx.costs, ctx.state_out

    @staticmethod
    def _refuse_cut_input(rows: dict, reader: str, reads) -> None:
        """A layer that `rows` cut down to its chosen rows no longer lines
        up with the sequence: nothing that runs may read it."""
        for name in reads:
            if name in rows:
                raise ValueError(
                    f"{reader} reads {name!r}, which `rows` cut down to "
                    f"its chosen rows of the sequence")

    @staticmethod
    def _layer_on_rows(ctx: ForwardContext, cfg: LayerConfig,
                       idx: Array) -> Argument:
        """`cfg`'s layer on positions `idx` of its inputs' token axis: the
        inputs are swapped for their gathered rows while the layer's
        function runs, and put back for the layers that read them whole."""
        idx = jnp.asarray(idx)
        if idx.ndim != 1 or not jnp.issubdtype(idx.dtype, jnp.integer):
            raise ValueError(
                f"rows[{cfg.name!r}] must be a 1-D integer index array, "
                f"got {idx.dtype}{list(idx.shape)}")
        whole = {inp.input_layer_name: ctx.outputs[inp.input_layer_name]
                 for inp in cfg.inputs}
        cut = {}
        for n, arg in whole.items():
            if arg.value is None or arg.value.ndim != 3 or arg.sparse_dim \
                    or arg.lengths is None or arg.sub_lengths is not None:
                raise ValueError(
                    f"rows[{cfg.name!r}]: input {n!r} is not a dense "
                    f"[B, T, d] sequence of rows")
            cut[n] = Argument(
                value=arg.value[:, idx],
                lengths=jnp.full_like(arg.lengths, idx.shape[0]))
        ctx.outputs.update(cut)
        try:
            return get_layer_fn(cfg.type)(ctx, cfg)
        finally:
            ctx.outputs.update(whole)

    # -- softmax head + cost as one op -------------------------------------
    def _fusable_softmax_costs(self) -> dict[str, LayerConfig]:
        """fc name -> cost layer for every `multi-class-cross-entropy`
        whose input is a top-level `fc` with softmax activation that
        nothing else reads: then ops/softmax_ce.py gives the cost (and the
        rows' argmax for `classification_error`) from the fc's input and
        weight, and the probabilities are never built.

        Read from the graph alone.  Any other reader keeps the layer as
        configured: a second consuming layer, a recurrent group's link or
        boot, a declared output, an evaluator that is not a
        `classification_error` over (this layer, the cost's label) — host
        evaluators and `gradient_printer` probes among them.  So does a
        layer with several inputs, dropout, a `tp_out` stamp (stamped by
        the serving engine after construction, hence read at each trace),
        one class (read by `classification_error` as a binary score), or a
        place inside a recurrent group."""
        readers = collections.Counter(
            inp.input_layer_name for l in self.model.layers for inp in l.inputs)
        linked = set(self.model.output_layer_names)
        for sm in self.model.sub_models:
            linked.update(sm.in_links, sm.static_links,
                          (m.boot_layer_name for m in sm.memories))
        pairs = {}
        for cost in self.model.layers:
            if cost.type != "multi-class-cross-entropy" \
                    or cost.name in self._sub_of:
                continue
            fc = self.layer_map.get(cost.inputs[0].input_layer_name)
            if fc is None or fc.type != "fc" or fc.name in self._sub_of \
                    or fc.active_type != "softmax" or len(fc.inputs) != 1 \
                    or fc.size < 2 or fc.drop_rate > 0.0 \
                    or fc.attrs.get("tp_out") or readers[fc.name] != 1 \
                    or fc.name in linked:
                continue
            label = cost.inputs[1].input_layer_name
            if any(fc.name in ev.input_layer_names
                   and (ev.type != "classification_error"
                        or list(ev.input_layer_names[:2]) != [fc.name, label])
                   for ev in self.model.evaluators):
                continue
            pairs[fc.name] = cost
        return pairs

    @staticmethod
    def _fused_inputs_ready(ctx: ForwardContext, fc: LayerConfig,
                            cost: LayerConfig) -> bool:
        """What only the trace shows, asked at the fc's place in the plan:
        its input is there as dense rows or a flat sequence of them, and
        the cost's label ids (and weight) are there already."""
        names = [fc.inputs[0].input_layer_name] + [
            inp.input_layer_name for inp in cost.inputs[1:]]
        if any(n not in ctx.outputs for n in names):
            return False
        src, lbl = ctx.outputs[names[0]], ctx.outputs[names[1]]
        return (src.value is not None and not src.sparse_dim
                and src.sub_lengths is None and lbl.ids is not None)

    def loss(
        self,
        params: dict[str, Array],
        feed: dict[str, Argument],
        state: Optional[dict[str, Any]] = None,
        mode: str = TRAIN,
        rng: Optional[jax.Array] = None,
        probes: Optional[dict[str, Array]] = None,
    ) -> tuple[Array, tuple[dict[str, Argument], dict[str, Array], dict[str, Any]]]:
        """Mean summed cost over the batch (ref: Argument::sumCosts / the
        reference divides by batch size at the updater via batch_size scaling —
        here the loss is per-sample mean, and the optimizer LR semantics match).

        A softmax `fc` that only its `multi-class-cross-entropy` reads
        (`_fusable_softmax_costs`) runs with it as one op: in the returned
        outputs that layer's entry then holds the rows' argmax as `ids` and
        no `value` — what `classification_error` reads."""
        outputs, costs, new_state = self._run(
            params, feed, state, mode, rng, probes,
            self._fusable_softmax_costs())
        assert costs, "model has no cost layers"
        from paddle_tpu.utils.dtypes import promote_compute
        total = None
        for c in costs.values():
            s = jnp.mean(promote_compute(c))
            total = s if total is None else total + s
        return total, (outputs, costs, new_state)

    def run_group_layers(self, sm: SubModelConfig, sub: ForwardContext,
                         skip: Optional[set] = None) -> None:
        """Execute one timestep of a sub-model's layers; agent/alias layers
        must already be fed into sub.outputs.  Nested child groups run as
        inner scans at their position in the plan.  `skip` holds layer
        names deferred to post-scan batched execution."""
        for kind, item in self._sub_plan.get(sm.name, []):
            if kind == "scan":
                self._run_scan(sub, item)
                continue
            cfg: LayerConfig = item
            if cfg.name in sub.outputs:      # agents already fed
                continue
            if skip and cfg.name in skip:
                continue
            sub.outputs[cfg.name] = get_layer_fn(cfg.type)(sub, cfg)

    # -- suffix-layer deferral --------------------------------------------
    _DEFER_PROJS = {"fc", "full_matrix", "trans_full_matrix", "table",
                    "identity", "dot_mul", "scaling"}

    def _split_deferred(self, sm: SubModelConfig) -> Optional[dict]:
        """Layers of a recurrent group OUTSIDE the carry-dependency closure
        need not run inside the sequential scan: they can execute ONCE on
        the stacked [B, T, ...] sequence afterwards, turning T small
        per-step matmuls into one large MXU-shaped one.  The classic case
        is an attention decoder's vocabulary softmax projection — the
        dominant matmul of the step, feeding only the cost, never the
        recurrence.

        Returns {deferred, cfgs, emit} or None when nothing defers.  Only
        batch-agnostic layer types (last-dim ops) are eligible; a deferred
        layer may read scan-internal values (emitted per step) or in_link
        aliases (reconstructed as full sequences) but not static links
        (their [B, D] shape would not broadcast against [B, T, D])."""
        plan = self._sub_plan.get(sm.name, [])
        if sm.generator is not None or any(k == "scan" for k, _ in plan):
            return None
        layer_cfgs = {item.name: item for k, item in plan if k == "layer"}
        alias = set(sm.in_link_layers)
        statics = set(sm.static_link_layers)
        agents = {m.layer_name for m in sm.memories}

        # carry closure: memory-linked layers + their transitive inputs
        needed: set = set()
        stack = [m.link_name for m in sm.memories]
        while stack:
            n = stack.pop()
            if n in needed or n not in layer_cfgs:
                continue
            needed.add(n)
            for inp in layer_cfgs[n].inputs:
                stack.append(inp.input_layer_name)

        def safe(cfg: LayerConfig) -> bool:
            if any(i.input_layer_name in statics for i in cfg.inputs):
                return False
            if cfg.type in ("fc", "addto"):
                return True
            if cfg.type == "mixed":
                return (all(i.proj is None or i.proj.type in self._DEFER_PROJS
                            for i in cfg.inputs)
                        and all(op.type == "dot_mul" for op in cfg.operators))
            return False

        deferred = {item.name for k, item in plan if k == "layer"
                    and item.name not in needed and item.name not in alias
                    and item.name not in agents and safe(item)}
        # fixpoint: an inside layer consuming a deferred output pulls the
        # producer back inside
        changed = True
        while changed:
            changed = False
            for k, item in plan:
                if k != "layer" or item.name in deferred:
                    continue
                for inp in item.inputs:
                    if inp.input_layer_name in deferred:
                        deferred.discard(inp.input_layer_name)
                        changed = True
        if not deferred:
            return None
        cfgs = [item for k, item in plan
                if k == "layer" and item.name in deferred]
        emit: set = set()
        for cfg in cfgs:
            for inp in cfg.inputs:
                n = inp.input_layer_name
                if n in deferred or n in alias:
                    continue
                if n in layer_cfgs or n in agents:
                    emit.add(n)
        return {"deferred": deferred, "cfgs": cfgs, "emit": emit}

    # -- recurrent sub-model as lax.scan ---------------------------------
    def _run_scan(self, ctx: ForwardContext, sm: SubModelConfig) -> None:
        """Execute a recurrent layer group over the time axis
        (ref: RecurrentGradientMachine.cpp:372-560 forward: reorders sequences,
        clones a frame net per timestep, wires memory_t <- frame_{t-1}).

        Here: in_links are sliced per step, memories are the scan carry,
        out_links are stacked; variable lengths freeze the carry and mask
        outputs — no sorting, no cloning, one compiled scan.
        """
        in_link_alias = dict(zip(sm.in_links, sm.in_link_layers))
        static_alias = dict(zip(sm.static_links, sm.static_link_layers))

        # outside sequence inputs: [B, T, D] -> time-major [T, B, D].
        # A nested (level-2) in_link [B, S, T, ...] + sub_lengths instead
        # iterates over the SUBSEQUENCE axis: each step feeds one whole
        # [B, T, ...] sequence with that subsequence's lengths
        # (ref: RecurrentGradientMachine.cpp:626-699 hierarchical forward)
        xs = {}
        lengths = None
        sub_lens_src = None          # [B, S] of the nested in_link(s)
        sparse_links: dict[str, int] = {}   # in_link -> sparse_dim
        T = None
        nest_levels = {ctx.outputs[o].sub_lengths is not None
                       for o in sm.in_links}
        assert len(nest_levels) <= 1, (
            f"recurrent group {sm.name!r} mixes nested (SubsequenceInput) and "
            f"flat sequence in_links — all in_links must share one nesting "
            f"level (the step counts differ)")
        for outer in sm.in_links:
            arg = ctx.outputs[outer]
            assert arg.is_sequence, f"in_link {outer!r} must be a sequence"
            seq = arg.data
            if arg.sparse_dim:
                # keep the sparse-row structure through per-step slicing
                # (values reversed in lockstep with the ids below)
                sparse_links[outer] = arg.sparse_dim
                spvals = arg.sparse_vals
                if sm.reversed and arg.sub_lengths is None:
                    from paddle_tpu.ops.sequence import seq_reverse
                    spvals = seq_reverse(spvals, arg.lengths)
                xs["__spvals__" + outer] = jnp.moveaxis(spvals, 1, 0)
            if arg.sub_lengths is not None:
                assert not sm.reversed, \
                    "reverse=True on a nested recurrent group is not supported"
                xs[outer] = jnp.moveaxis(seq, 1, 0)              # [S, B, T, ..]
                xs["__sublen__" + outer] = jnp.moveaxis(arg.sub_lengths, 1, 0)
                sub_lens_src = arg.sub_lengths
                lengths = arg.lengths if lengths is None else jnp.maximum(lengths, arg.lengths)
                T = seq.shape[1] if T is None else max(T, seq.shape[1])
                continue
            if sm.reversed:
                from paddle_tpu.ops.sequence import seq_reverse
                seq = seq_reverse(seq, arg.lengths)
            xs[outer] = jnp.moveaxis(seq, 1, 0)
            lengths = arg.lengths if lengths is None else jnp.maximum(lengths, arg.lengths)
            T = seq.shape[1] if T is None else max(T, seq.shape[1])

        assert T is not None, f"recurrent group {sm.name!r} has no in_links"
        B = lengths.shape[0]

        # initial memories (scan carry): boot layer output, const id, or zeros
        carry0: dict[str, Array] = {}
        for mem in sm.memories:
            if mem.boot_layer_name:
                boot = ctx.outputs[mem.boot_layer_name].data
            elif mem.boot_with_const_id is not None:
                boot = jnp.full((B,), mem.boot_with_const_id, jnp.int32)
            else:
                boot = jnp.zeros((B, mem.size), jnp.float32)
            carry0[mem.link_name] = boot

        mode, rng = ctx.mode, ctx.rng
        params = ctx.params
        model = self.model

        # suffix layers outside the carry closure run post-scan, batched
        # over all timesteps (computed once per group, cached)
        if sm.name not in self._defer_cache:
            self._defer_cache[sm.name] = self._split_deferred(sm)
        spec = self._defer_cache[sm.name]
        defer_active = spec is not None and sub_lens_src is None
        skip = spec["deferred"] if defer_active else None
        emit_names = (sorted((set(sm.output_layer_names) - spec["deferred"])
                             | spec["emit"])
                      if defer_active else list(sm.output_layer_names))

        out_is_seq: dict[str, bool] = {}   # filled once during scan tracing

        def step(carry, inp):
            t = inp["__t__"]
            sub = ForwardContext(model=model, params=params, mode=mode,
                                 rng=(jax.random.fold_in(rng, t) if rng is not None else None))
            # feed sliced in_links through their in-group alias layers,
            # preserving ids-vs-value payload kind (an integer id sequence
            # must stay an ids Argument so table projections index correctly);
            # a nested link's slice is itself a sequence with this
            # subsequence's lengths
            for outer, inner in in_link_alias.items():
                sl = inp[outer]
                sub_len = inp.get("__sublen__" + outer)
                if outer in sparse_links:
                    sub.outputs[inner] = Argument(
                        ids=sl, sparse_vals=inp["__spvals__" + outer],
                        sparse_dim=sparse_links[outer], lengths=sub_len)
                elif jnp.issubdtype(sl.dtype, jnp.integer):
                    sub.outputs[inner] = Argument(ids=sl, lengths=sub_len)
                else:
                    sub.outputs[inner] = Argument(value=sl, lengths=sub_len)
            # feed static links: same value every step (ref: StaticInput)
            for outer, inner in static_alias.items():
                sub.outputs[inner] = ctx.outputs[outer]
            # feed memories: the agent layer reads last step's linked output
            for mem in sm.memories:
                prev = carry[mem.link_name]
                sub.outputs[mem.layer_name] = (
                    Argument(ids=prev) if prev.dtype in (jnp.int32, jnp.int64)
                    else Argument(value=prev))
            self.run_group_layers(sm, sub, skip=skip)
            valid = (t < lengths)
            new_carry = {}
            for mem in sm.memories:
                out = sub.outputs[mem.link_name].data
                v = valid.reshape((B,) + (1,) * (out.ndim - 1))
                prev = carry[mem.link_name]
                # keep the carry dtype fixed across steps (a stray fp32 op in
                # the step body must not flip a bf16 memory to fp32 mid-scan)
                new_carry[mem.link_name] = jnp.where(v, out, prev).astype(prev.dtype)
            emitted = {}
            for name in emit_names:
                o = sub.outputs[name]
                out_is_seq[name] = o.lengths is not None
                emitted[name] = o.data
            return new_carry, emitted

        inp_seq = {"__t__": jnp.arange(T)}
        inp_seq.update(xs)
        # Training scans remat the step body: backward then recomputes the
        # step's internals (attention scores, gate pre-activations, ...)
        # from the small carry instead of storing them per timestep — the
        # scan is HBM-bandwidth-bound, so saved residual traffic buys more
        # than the recompute costs (+8% on the seq2seq bench).  Forward-only
        # runs (test/generation) have no residuals to save; remat there only
        # inhibits XLA fusion across the checkpoint boundary.
        body = jax.checkpoint(step) if mode == TRAIN else step
        _, stacked = jax.lax.scan(body, carry0, inp_seq)

        # publish out_links as [B, T, D] sequences; a nested group whose step
        # emitted per-subsequence sequences publishes [B, S, T, D] with the
        # in_link's subsequence structure
        deferred_names = spec["deferred"] if defer_active else set()
        for name in sm.output_layer_names:
            if name in deferred_names:
                continue  # produced by the deferred batched execution below
            seq = jnp.moveaxis(stacked[name], 0, 1)
            if sm.reversed:
                from paddle_tpu.ops.sequence import seq_reverse
                seq = seq_reverse(seq, lengths)
            if sub_lens_src is not None and out_is_seq.get(name):
                ctx.outputs[name] = Argument(value=seq, lengths=lengths,
                                             sub_lengths=sub_lens_src)
            else:
                ctx.outputs[name] = Argument(value=seq, lengths=lengths)

        if defer_active:
            # run the suffix layers ONCE over the stacked sequences: one
            # [B*T, D] matmul instead of T [B, D] ones inside the scan.
            # rng folded with a large per-group constant so deferred dropout
            # masks are independent of the root context's key sequence and
            # of other groups' (the scan body folds small t values)
            drng = None
            if rng is not None:
                gid = [s.name for s in model.sub_models].index(sm.name)
                drng = jax.random.fold_in(rng, 2**31 - 1 - gid)
            dctx = ForwardContext(model=model, params=params, mode=mode,
                                  rng=drng)
            for outer, inner in in_link_alias.items():
                full = jnp.moveaxis(xs[outer], 0, 1)   # scan orientation
                if outer in sparse_links:
                    dctx.outputs[inner] = Argument(
                        ids=full,
                        sparse_vals=jnp.moveaxis(xs["__spvals__" + outer], 0, 1),
                        sparse_dim=sparse_links[outer], lengths=lengths)
                elif jnp.issubdtype(full.dtype, jnp.integer):
                    dctx.outputs[inner] = Argument(ids=full, lengths=lengths)
                else:
                    dctx.outputs[inner] = Argument(value=full, lengths=lengths)
            for name in spec["emit"]:
                v = jnp.moveaxis(stacked[name], 0, 1)
                if jnp.issubdtype(v.dtype, jnp.integer):
                    dctx.outputs[name] = Argument(ids=v, lengths=lengths)
                else:
                    dctx.outputs[name] = Argument(value=v, lengths=lengths)
            for cfg in spec["cfgs"]:
                dctx.outputs[cfg.name] = get_layer_fn(cfg.type)(dctx, cfg)
            for name in sm.output_layer_names:
                if name not in deferred_names:
                    continue
                seq = dctx.outputs[name].data
                if sm.reversed:
                    from paddle_tpu.ops.sequence import seq_reverse
                    seq = seq_reverse(seq, lengths)
                ctx.outputs[name] = Argument(value=seq, lengths=lengths)
