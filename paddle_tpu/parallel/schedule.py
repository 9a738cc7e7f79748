"""What a compiled step does about its collectives, read from its text.

A compiled XLA module is printed in SCHEDULE order (`is_scheduled=true`): an
instruction runs where it stands.  A collective stands there in one of two
forms, and only the form says whether anything can run beside it:

  sync    `all-reduce(...)` (or `all-gather`, `reduce-scatter`, ...): the
          core issues it and waits; the wire's time is the step's.
  async   `all-reduce-start(...)` ... `all-reduce-done(...)`, or the TPU
          compiler's `async-collective-start/-done` around a fusion that
          holds the collective: what the schedule places between the two
          runs while the bytes cross, and only the WAIT at `-done` is the
          step's.

`read_collectives(text)` lists them with their bytes and what stands
between start and done; `summarize` folds that to the counts the trainer
publishes (`trainer_step_collectives{form=}`,
`trainer_step_collective_bytes{form=}`).  The reader is stdlib only:
`tools/step_schedule.py` reads a compile for a DESCRIBED topology with it.
`StepCollectives` is the trainer's side, and keeps the reading OFF the step
path: a new signature leaves its abstract arguments there, and the lowering,
the text and the parse happen when the gauges are first collected.
"""

from __future__ import annotations

import re
import threading
import time

from paddle_tpu.utils.logger import get_logger

log = get_logger("schedule")

#: op names whose operands cross chips
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute", "collective-broadcast")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
                "s4": 1, "u4": 1}

_SHAPE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|"
                    r"called_computations)=\{?%?([\w.\-]+)")


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape string; a tuple's are its leaves' sum."""
    total = 0
    for dtype, dims in _SHAPE.findall(shape):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * size
    return total


def _computations(text: str) -> tuple[dict, str]:
    """{computation name: [(instruction, shape, op, line)]} and the entry's
    name, in the order the module prints them."""
    comps: dict[str, list] = {}
    entry, cur = "", None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m and "=" not in line.split("(")[0]:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            cur.append((m.group(1), m.group(2), m.group(3), line))
    return comps, entry


def _kind_of(op: str) -> str:
    for k in KINDS:
        if op == k or op == k + "-start":
            return k
    return ""


def _held_collective(comps: dict, line: str, depth: int = 0):
    """(kind, shape) of the collective a fusion or an async wrapper calls,
    or None: `async-collective-start` wraps a fusion that holds it."""
    if depth > 3:
        return None
    for callee in _CALLS.findall(line):
        for _name, shape, op, inner in comps.get(callee, ()):
            if _kind_of(op):
                return _kind_of(op), shape
            held = _held_collective(comps, inner, depth + 1)
            if held:
                return held
    return None


def _payload(shape: str) -> str:
    """An asynchronous start's shape is `(operands, results[, contexts])`;
    the payload is the results' half."""
    if not shape.startswith("("):
        return shape
    depth, parts, cur = 0, [], ""
    for ch in shape[1:-1]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append(cur.strip())
            cur = ""
        else:
            cur += ch
    parts.append(cur.strip())
    return parts[1] if len(parts) > 1 else parts[0]


def read_collectives(text: str) -> list[dict]:
    """Every collective of the module's entry computation (and of the loop
    bodies it calls), in schedule order: `{name, kind, form, bytes, shape}`
    and, for an asynchronous one, `between`: how many instructions the
    schedule places between start and done, and the first few by name that
    do work (`fusion`, `custom-call`, `convolution`, `while`)."""
    comps, entry = _computations(text)
    out: list[dict] = []
    seen: set = set()

    def walk(comp: str):
        if comp in seen or comp not in comps:
            return
        seen.add(comp)
        instrs = comps[comp]
        for i, (name, shape, op, line) in enumerate(instrs):
            kind, asynchronous = _kind_of(op), op.endswith("-start")
            if not kind and (name.startswith("async-collective-start")
                             or op == "async-start"):
                # the TPU compiler's form: a custom fusion NAMED
                # async-collective-start that holds the collective
                held = _held_collective(comps, line)
                if held:
                    kind, asynchronous = held[0], True
                    shape = held[1]
            elif asynchronous:
                shape = _payload(shape)
            if kind:
                rec = {"name": name, "kind": kind,
                       "form": "async" if asynchronous else "sync",
                       "bytes": shape_bytes(shape), "shape": shape[:120],
                       "put_back": "async_collective_name" in line
                       and not asynchronous}
                if asynchronous:
                    rec["between"] = _between(instrs, i, name)
                out.append(rec)
            elif op in ("while", "call", "conditional"):
                for callee in _CALLS.findall(line):
                    walk(callee)

    walk(entry)
    return out


_WORK = ("fusion", "custom-call", "convolution", "while", "dot")
_FIRST = 6      # how many of them `between` names


def _between(instrs: list, start: int, name: str) -> dict:
    """What the schedule runs while `name` is in flight."""
    n, work, names = 0, 0, []
    done = name.replace("-start", "-done", 1)
    for other, _shape, op, line in instrs[start + 1:]:
        if other == done or (op.endswith("-done") and re.search(
                r"\(%?" + re.escape(name) + r"[,)]", line.split("=", 1)[1])):
            return {"instructions": n, "work": work, "first": names}
        n += 1
        if op in _WORK:
            work += 1
            if len(names) < _FIRST:
                names.append(other)
    return {"instructions": n, "work": work, "first": names,
            "done": "not found"}


def summarize(collectives: list[dict]) -> dict:
    """{form: {"count", "bytes"}} over both forms (zeros included, so a
    gauge that read 3 reads 0 after the next compile, not 3 still)."""
    out = {form: {"count": 0, "bytes": 0} for form in ("async", "sync")}
    for c in collectives:
        out[c["form"]]["count"] += 1
        out[c["form"]]["bytes"] += c["bytes"]
    return out


def _abstract(args):
    """The call's arguments as shapes: what `.lower()` needs to find the
    signature's executable again after the call has donated the arrays
    themselves.  An uncommitted array (a fresh rng key) stays without a
    sharding, as the call saw it."""
    import jax

    def one(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    return jax.tree.map(one, args)


class StepCollectives:
    """What a trainer's compiled steps do about their collectives, as a
    `metrics.register_collector` callable.

    `note(site, step, args)` is all the step path pays: it keeps a NEW
    signature's jitted step and abstract arguments, taken before the call
    donates them.  Calling the object (a metrics render or snapshot) lowers
    each noted signature once -- it is the call's own, so jit hands the
    lowering and the executable back and what this costs is the module's
    text and its parse, about a second at the dp4 cell's size -- logs one
    line, and yields the newest signature's counts as
    `trainer_step_collectives{form=}` and
    `trainer_step_collective_bytes{form=}`.  A process nobody scrapes never
    pays; a reading that fails logs and yields nothing."""

    def __init__(self):
        self._lock = threading.Lock()
        self._noted: list = []       # (site, step, abstract args), unread
        self._forms: dict = {}       # summarize() of the newest one read

    def note(self, site: str, step, args) -> None:
        noted = (site, step, _abstract(args))
        with self._lock:
            self._noted.append(noted)

    def __call__(self) -> list[tuple]:
        with self._lock:             # held for the swap, not the reading
            noted, self._noted = self._noted, []
        for site, step, shapes in noted:
            self._read(site, step, shapes)
        out = []
        for form, n in self._forms.items():
            out.append(("trainer_step_collectives", "gauge",
                        {"form": form}, float(n["count"])))
            out.append(("trainer_step_collective_bytes", "gauge",
                        {"form": form}, float(n["bytes"])))
        return out

    def _read(self, site: str, step, shapes) -> None:
        t0 = time.perf_counter()
        try:
            found = read_collectives(step.lower(*shapes).compile().as_text())
        except Exception as e:             # noqa: BLE001 — observability
            log.warning("%s: the executable's collectives were not read "
                        "(%s)", site, e)
            return
        self._forms = forms = summarize(found)
        waited = sorted((c for c in found if c["form"] == "sync"),
                        key=lambda c: -c["bytes"])
        log.info(
            "%s collectives: %d asynchronous (%.4f GB), %d synchronous "
            "(%.2f MB; the largest: %s); read in %.2f s", site,
            forms["async"]["count"], forms["async"]["bytes"] / 1e9,
            forms["sync"]["count"], forms["sync"]["bytes"] / 1e6,
            ", ".join(f"{c['name']} {c['bytes'] / 1e6:.2f} MB"
                      for c in waited[:4]) or "none",
            time.perf_counter() - t0)
