"""Device time inside the benchmark's spans, charged to the program's
named scopes (``jax.named_scope``; the vocabulary is ``SCOPES`` in
``repro/models/common.py``, with its docstring).

A device operation in the reduced trace (``bench/trace.py``) is named by
its HLO instruction: ``%fusion.72 = bf16[32,151552]{...} fusion(...)`` on
the chip, the bare name on the CPU.  The compiled text of the program that
ran holds the same instruction with its ``op_name`` metadata, the path of
scopes it was traced under.  So:

- an operation's scope is the innermost name of ``SCOPES`` in that path.
  A fusion carries its root instruction's metadata, so it goes to its
  root's scope.  An instruction of the program with no such name is
  ``unscoped``; an operation that is not an instruction of the program
  (another program's, such as the greedy pick's eager ops) is ``other``;
- the layer scan's own operations (``layers``) and XLA's (``unscoped``)
  are told apart by what their result holds: one whose result has the
  shape of the decode cache, stacked or one layer's, is labelled
  ``<scope>:cache``; one with the shape of a stacked matrix weight, or one
  layer's slice of it, ``<scope>:weights``; the rest keep the scope;
- each operation is charged its self time: at each instant the device's
  time goes to the innermost operation running, the latest started of
  those open, so a ``while`` keeps only what its body's operations leave
  and every nanosecond counts once.  The charges of one span add up to
  ``Trace.busy_in`` of that span.

``of(rec)`` gives, for a traced serving record that carries its cache
slots (``max_seq``), device seconds by label inside its ``prefill`` and
inside its ``decode`` spans.  It compiles the engine's two programs
from shapes to read their text, and gives ``None`` for a record without
slots, where the program cannot lower itself that way or carries no
scope, and for a span where more than ``OTHER_MAX`` of the time is
``other`` (the compiled copy then is not the program that ran).  JAX's
persistent cache leaves metadata out of its key, so a program cached
before its scopes were added would come back without them: these compiles
put the metadata into the key.  The instruction names do not depend on
metadata, so they are those of the program that ran, wherever it came
from.
"""

from __future__ import annotations

import re
from collections import defaultdict

from bench.trace import clip, union

try:
    from repro.models.common import SCOPES
except ImportError:          # a program from before its scopes names none
    SCOPES = ()

UNSCOPED = "unscoped"
OTHER = "other"
SPANS = ("prefill", "decode")
# Scopes whose operations are labelled by what their result holds.
MOVERS = ("layers", UNSCOPED)
CACHE, WEIGHTS = "cache", "weights"
# Largest share of a span's device time that may be ``other``: the greedy
# pick's eager ops are ~0.1 % of a decode span on a v5e.
OTHER_MAX = 0.01

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def result_type(rest: str) -> str:
    """The result type at the head of an instruction's text after
    ``= ``, without layouts, comments or spaces: ``bf16[8,32]``, or a
    tuple ``(s32[],bf16[8,32])``."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        head = rest[:i + 1]
    else:
        head = rest.split(" ", 1)[0]
    return re.sub(r"\{[^{}]*\}|/\*.*?\*/|\s", "", head)


def shape_of(type_: str):
    """The dimensions of an array type such as ``bf16[1,32,8192]``; None
    for a tuple."""
    m = re.fullmatch(r"[a-z0-9]+\[([0-9,]*)\]", type_)
    return None if m is None else tuple(int(d) for d in m.group(1).split(",") if d)


def scope_of_path(path: str) -> str:
    inner = [p for p in path.split("/") if p in SCOPES]
    return inner[-1] if inner else UNSCOPED


def instruction_scopes(hlo_text: str) -> dict:
    """{instruction name: (result type, scope)} for every instruction of
    a compiled module's text."""
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        path = _OP_NAME.search(rest)
        table[name] = (result_type(rest),
                       scope_of_path(path.group(1)) if path else UNSCOPED)
    return table


def scope_of_op(op: str, table: dict) -> str:
    """The scope of a trace operation named ``op`` in a program whose
    instructions are ``table``; ``other`` when the program has no such
    instruction (the name and, where the trace gives it, the type)."""
    name, sep, rest = op.partition(" = ")
    found = table.get(name.lstrip("%"))
    if found is None or (sep and found[0] != result_type(rest)):
        return OTHER
    return found[1]


def moving(kind: str) -> tuple:
    """The labels of the ``layers`` and ``unscoped`` operations whose
    result holds ``kind`` (``CACHE`` or ``WEIGHTS``)."""
    return tuple(f"{scope}:{kind}" for scope in MOVERS)


def label_of_op(op: str, table: dict, kinds: dict) -> str:
    """The operation's scope; for ``layers`` and ``unscoped``, with
    ``:cache`` or ``:weights`` where its result's shape is one of those
    ``kinds`` maps (``moved_shapes``)."""
    scope = scope_of_op(op, table)
    if scope in MOVERS:
        kind = kinds.get(shape_of(table[op.partition(" = ")[0].lstrip("%")][0]))
        if kind:
            return f"{scope}:{kind}"
    return scope


def moved_shapes(params, cache) -> dict:
    """{shape: CACHE or WEIGHTS}: each leaf of the decode cache and each
    stacked matrix weight (a parameter of rank 3, its layers first), whole,
    one layer's slice with its leading 1 and without it.  The leaves may
    be arrays or anything with a ``shape``."""
    import jax

    kinds = {}
    for kind, leaves in ((WEIGHTS, [a for a in jax.tree.leaves(params)
                                    if len(a.shape) == 3]),
                         (CACHE, jax.tree.leaves(cache))):
        for a in leaves:
            shape = tuple(a.shape)
            for s in (shape, (1, *shape[1:]), shape[1:]):
                kinds[s] = kind
    return kinds


def self_time(ops):
    """[(name, start, end)] -> [(name, start, end)] pieces of each
    operation's self time: every instant goes to the latest-started
    operation open at it."""
    pieces, stack = [], []
    t = None

    def advance(to):
        nonlocal t
        while stack and t < to:
            name, end = stack[-1]
            upto = min(end, to)
            if upto > t:
                pieces.append((name, t, upto))
                t = upto
            if end <= t:
                stack.pop()
        t = to if t is None else max(t, to)

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        advance(s)
        stack.append((name, e))
    advance(float("inf") if stack else t)
    return pieces


def span_ops(trace, span: str) -> dict:
    """{operation: self seconds} inside the spans called ``span`` (and the
    traced window), averaged over devices."""
    spans = clip(union((s, e) for n, s, e in trace.spans if n == span),
                 *trace.window)
    by = defaultdict(int)
    for dev in trace.ops:
        pieces = self_time(trace.ops[dev])
        i = 0
        for name, s, e in pieces:           # pieces are in time order
            while i < len(spans) and spans[i][1] <= s:
                i += 1
            j = i
            while j < len(spans) and spans[j][0] < e:
                part = min(e, spans[j][1]) - max(s, spans[j][0])
                if part > 0:
                    by[name] += part
                j += 1
    return {k: v / len(trace.ops) / 1e9 for k, v in by.items()}


def span_scopes(trace, span: str, hlo_text: str, kinds=None) -> dict:
    """{label: device seconds} inside the spans called ``span``: each
    operation of ``span_ops`` labelled by ``label_of_op`` from
    ``hlo_text`` and ``kinds``."""
    table = instruction_scopes(hlo_text)
    by = defaultdict(float)
    for op, seconds in span_ops(trace, span).items():
        by[label_of_op(op, table, kinds or {})] += seconds
    return dict(by)


def programs(rec):
    """The compiled text of the serve engine's two programs at the
    record's shapes and cache slots (``rec.max_seq``), ``{"prefill": text,
    "decode": text}``, and the shapes the decode step moves
    (``moved_shapes``); or None for a record that names no slots.  The
    weights' shapes come from the family of the record's configuration
    (``bench/family.py``)."""
    import jax

    from bench import family, generator
    from repro.serve import ServeEngine

    max_seq = getattr(rec, "max_seq", None)
    if max_seq is None or not hasattr(ServeEngine, "lower"):
        return None
    ref, prog = family.load(rec.config)
    spec = ref.Spec.from_config(rec.config)
    params = jax.eval_shape(
        lambda k: prog.to_program(ref.init_weights(spec, k)),
        generator.weights_key(0))
    engine = ServeEngine(prog.program_config(rec.config), params, None,
                         max_seq=max_seq, batch_size=rec.batch)
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        lowered = engine.lower(rec.prompt)
        texts = {span: low.compile().as_text() for span, low in lowered.items()}
    finally:
        jax.config.update(key, before)
    params_info, cache_info = lowered["decode"].args_info[0][:2]
    return texts, moved_shapes(params_info, cache_info)


def of(rec):
    """{span: {label: device seconds} or None} for the ``prefill`` and
    ``decode`` spans of a traced serving record, or None; read once per
    record, which keeps ``programs`` too."""
    if not hasattr(rec, "scopes"):
        rec.scopes = None
        rec.programs = programs(rec) if rec.trace is not None else None
        if rec.programs is not None:
            texts, kinds = rec.programs
            if any(scope != UNSCOPED for text in texts.values()
                   for _, scope in instruction_scopes(text).values()):
                rec.scopes = {}
                for span in SPANS:
                    got = span_scopes(rec.trace, span, texts[span], kinds)
                    sound = got.get(OTHER, 0.0) <= OTHER_MAX * sum(got.values())
                    rec.scopes[span] = got if sound else None
    return rec.scopes


def seconds(rec, span: str, labels) -> float:
    """Device seconds charged to any of ``labels`` inside ``span``; 0 when
    the record has no scopes there."""
    found = of(rec)
    got = found and found[span]
    return sum(got.get(n, 0.0) for n in labels) if got else 0.0
