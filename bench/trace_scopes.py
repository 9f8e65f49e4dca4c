"""The program's own names in a profiler trace, beside what
``bench/trace_reduce.py`` reduces.

- scope paths: each device operation's ``op_name`` (the JAX name stack, as
  ``jit(f)/compress.bbo/while/body/bbo.surrogate/vmap(jit(cholesky))/...``).
  A TPU trace's ``XLA Ops`` events carry no such stat, so the path is read
  from the compiled programs themselves: the trace's ``/host:metadata``
  plane holds each program's ``HloProto``, which names every instruction's
  ``op_name``.  An operation's program is the ``XLA Modules`` event that
  holds it in time; its instruction is the name its HLO text starts with.
  Each distinct (program, text) is looked up once.
- ``scope_seconds``: device seconds under each named scope (a dotted, lower
  case component of the path, transform wrappers such as ``vmap(...)``
  unwrapped); an operation counts under every scope of its path, a fusion
  under its root's (the fusion's own ``op_name``).
- ``span_idle_s``: device idle seconds inside the window that overlap each
  program host span (``repro.*``), counted under the innermost span, by
  intersection.
- ``scoped_share``: the share of the window's device operation seconds
  under any ``compress.*`` or ``bbo.*`` scope.

``reduce_trace`` reads the trace once and returns a ``ScopedReduction``:
every field of ``trace_reduce.Reduction``, with the same values, and these.
"""

from __future__ import annotations

import collections
import dataclasses
import re

from bench import trace_reduce
from bench.device import log

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = (trace_reduce.SPAN_PREFIX, "repro.")
PROGRAM_SPAN = "repro."
JOB_SCOPES = ("compress.", "bbo.")
SCOPE = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+")
WRAPPED = re.compile(r"[A-Za-z_][\w.<>-]*\((.*)\)")


# -- op_name paths -----------------------------------------------------------

def _split(path: str) -> list:
    """``/``-separated components at parenthesis depth 0."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def scopes_of(path: str) -> frozenset:
    """The named scopes of an ``op_name`` path.  XLA joins the paths of
    merged instructions with ``;``; a wrapper ``vmap(a/b)`` holds a
    path of its own."""
    found = set()
    todo = [p for p in path.split(";") if p]
    while todo:
        for comp in _split(todo.pop()):
            m = WRAPPED.fullmatch(comp)
            if m:
                todo.append(m.group(1))
            elif SCOPE.fullmatch(comp):
                found.add(comp)
    return frozenset(found)


# -- the HLO of the trace's programs (protobuf wire format) ------------------

def _varint(buf, i: int):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of a protobuf message in ``buf[lo:hi]``: an int
    for varints and fixed-width fields, a (start, end) slice for
    length-delimited ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            v, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"protobuf wire type {wire} before byte {i}")
        yield key >> 3, v


def _field(buf, span, number):
    """The first value of field ``number`` in the message at ``span`` (a
    plane's name comes before its lines, so a plane's header is all that
    is read for it)."""
    for f, v in _fields(buf, *span):
        if f == number:
            return v
    return None


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode() if span else ""


def _packed(buf, value) -> list:
    """A repeated integer field's values: packed in one slice, or one."""
    if not isinstance(value, tuple):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _hlo_op_names(buf, span) -> dict:
    """{instruction name: op_name} of one ``HloProto``: hlo_module (1) ->
    computations (3: id (5), instructions (2: name (1), metadata (7) ->
    op_name (2), called_computation_ids (38))).  An instruction with no
    op_name of its own (a copy or loop slice that XLA added) takes the
    op_name of the instruction that calls its computation: it runs inside
    that one."""
    comps = {}
    module = _field(buf, span, 1)
    for f, comp in _fields(buf, *module) if module else ():
        if f != 3:
            continue
        cid, insts = None, []
        for g, v in _fields(buf, *comp):
            if g == 5:
                cid = v
            elif g == 2:
                name, op, called = "", "", []
                for h, w in _fields(buf, *v):
                    if h == 1:
                        name = _text(buf, w)
                    elif h == 7:
                        op = _text(buf, _field(buf, w, 2))
                    elif h == 38:
                        called += _packed(buf, w)
                insts.append((name, op, called))
        comps[cid] = insts
    caller = {}
    for cid, insts in comps.items():
        for _, op, called in insts:
            for c in called:
                caller.setdefault(c, (cid, op))
    context = {}

    def inherited(cid) -> str:
        if cid not in context:
            parent, op = caller.get(cid, (None, ""))
            context[cid] = op or (inherited(parent) if parent is not None
                                  else "")
        return context[cid]

    return {name: op or inherited(cid)
            for cid, insts in comps.items() for name, op, _ in insts
            if op or inherited(cid)}


def program_op_names(buf) -> dict:
    """{program name as the trace names it, e.g. ``jit_f(5)``:
    {instruction name: op_name}} from the ``HloProto`` stats of a
    serialized XSpace's ``/host:metadata`` plane: planes (1) -> name (2),
    event_metadata (4: {id: XEventMetadata name (2), stats (5)}),
    stat_metadata (5: {id: XStatMetadata name (2)}); an XStat holds its
    metadata_id (1) and bytes (6)."""
    for f, plane in _fields(buf):
        if f != 1 or _text(buf, _field(buf, plane, 2)) != METADATA_PLANE:
            continue
        fields = list(_fields(buf, *plane))
        stat_names = {}
        for g, entry in fields:
            meta = _field(buf, entry, 2) if g == 5 else None
            if meta is not None:
                stat_names[_field(buf, entry, 1)] = _text(buf, _field(buf, meta, 2))
        programs = {}
        for g, entry in fields:
            meta = _field(buf, entry, 2) if g == 4 else None
            if meta is None:
                continue
            name, protos = "", []
            for h, v in _fields(buf, *meta):
                if h == 2:
                    name = _text(buf, v)
                elif h == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                programs[name] = _hlo_op_names(buf, proto)
        return programs
    return {}


# -- reading a trace ---------------------------------------------------------

def op_path(programs: dict, program: str, text: str) -> str:
    """The ``op_name`` of the operation whose HLO text is ``text`` in
    ``program`` (``programs`` as ``program_op_names`` gives them); "" where
    the trace holds none."""
    return programs.get(program, {}).get(text.split(" = ", 1)[0].lstrip("%"), "")


def read_trace(path: str):
    """({device: [(op HLO text, start_ns, dur_ns)]},
    [(span, start_ns, dur_ns)] of ``bench.*`` and ``repro.*`` spans,
    {op HLO text: op_name path}) from one trace file.  A text that two
    programs share gets both paths, joined with ``;``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    programs = program_op_names(memoryview(raw))
    pd = ProfileData.from_serialized_xspace(raw)
    del raw
    device_ops, host_spans, paths, seen = {}, [], {}, set()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE not in lines:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            ops = device_ops[plane.name] = []
            k = 0
            for e in lines[trace_reduce.OPS_LINE].events:
                text, s = e.name, e.start_ns
                while k + 1 < len(mods) and mods[k + 1][0] <= s:
                    k += 1
                prog = mods[k][2] if mods and mods[k][0] <= s < mods[k][1] else ""
                if (prog, text) not in seen:
                    seen.add((prog, text))
                    p = op_path(programs, prog, text)
                    old = paths.get(text)
                    paths[text] = p if not old or old == p else f"{old};{p}"
                ops.append((text, s, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host_spans.append((e.name, e.start_ns, e.duration_ns))
    return device_ops, host_spans, paths


# -- reduction ---------------------------------------------------------------

@dataclasses.dataclass
class Scopes:
    scope_seconds: dict      # scope -> device seconds, summed over devices
    span_idle_s: dict        # repro.* span -> idle seconds, mean over devices
    scoped_share: float | None   # of op seconds under compress.* / bbo.*
    span_counts: dict        # repro.* span -> spans inside the window


def _idle_by_span(gaps, spans) -> collections.Counter:
    """Seconds of ``gaps`` [(start, end)] under the innermost of ``spans``
    [(name, start, end)] at each instant."""
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    segments = []
    for a, b in zip(edges, edges[1:]):
        cover = [(e - s, n) for n, s, e in spans if s <= a and b <= e]
        if cover:
            segments.append((a, b, min(cover)[1]))
    idle = collections.Counter()
    i = 0
    for gs, ge in sorted(gaps):
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < ge:
            a, b, name = segments[j]
            idle[name] += (min(b, ge) - max(a, gs)) * 1e-9
            j += 1
    return idle


def reduce_scopes(device_ops, host_spans, paths, t0_ns: float,
                  t1_ns: float) -> Scopes:
    """The program's scopes and spans over the window [t0, t1): arguments
    as ``read_trace`` returns them (a text missing from ``paths`` has no
    scope)."""
    text_seconds = collections.Counter()
    gaps = []
    for ops in device_ops.values():
        ivs = []
        for text, s, d in ops:
            cs, ce = max(s, t0_ns), min(s + d, t1_ns)
            if ce <= cs:
                continue
            ivs.append((cs, ce))
            text_seconds[text] += (ce - cs) * 1e-9
        edges = ([t0_ns] + [x for iv in trace_reduce._union(ivs) for x in iv]
                 + [t1_ns])
        gaps += [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                 if ge > gs]
    scope_seconds = collections.Counter()
    scoped = total = 0.0
    for text, sec in text_seconds.items():
        if trace_reduce._container(trace_reduce.op_name(text)):
            continue
        scopes = scopes_of(paths.get(text, ""))
        for scope in scopes:
            scope_seconds[scope] += sec
        total += sec
        if any(s.startswith(JOB_SCOPES) for s in scopes):
            scoped += sec
    spans = [(n, max(s, t0_ns), min(s + d, t1_ns)) for n, s, d in host_spans
             if n.startswith(PROGRAM_SPAN) and s < t1_ns and s + d > t0_ns]
    n_dev = max(len(device_ops), 1)
    return Scopes(
        scope_seconds=dict(scope_seconds),
        span_idle_s={n: s / n_dev
                     for n, s in _idle_by_span(gaps, spans).items()},
        scoped_share=scoped / total if total > 0 else None,
        span_counts=dict(collections.Counter(n for n, _, _ in spans)),
    )


@dataclasses.dataclass
class ScopedReduction(trace_reduce.Reduction):
    scope_seconds: dict = dataclasses.field(default_factory=dict)
    span_idle_s: dict = dataclasses.field(default_factory=dict)
    scoped_share: float | None = None
    span_counts: dict = dataclasses.field(default_factory=dict)


def reduce_trace(trace_dir: str, window_span: str = "bench.window",
                 max_gaps: int = 10) -> ScopedReduction:
    """``trace_reduce.reduce_trace`` with the program's scopes and spans,
    from one read of the newest trace under ``trace_dir``; logs them."""
    device_ops, host_spans, paths = read_trace(
        trace_reduce.latest_xplane(trace_dir))
    marks = [(s, s + d) for n, s, d in host_spans if n == window_span]
    if not marks:
        raise ValueError(f"no {window_span!r} span in the trace")
    t0, t1 = marks[0]
    base = trace_reduce.reduce_events(device_ops, host_spans, t0, t1,
                                      max_gaps)
    scopes = reduce_scopes(device_ops, host_spans, paths, t0, t1)
    log(f"[trace] scope_seconds {scopes.scope_seconds}; span_idle_s "
        f"{scopes.span_idle_s}; scoped share {scopes.scoped_share}")
    return ScopedReduction(**vars(base), **vars(scopes))


# -- what the per-layer metrics read -----------------------------------------

def tile_iterations(jobs) -> int:
    """Tile-iterations of BBO the jobs ran, by the program's own count:
    over every BBO pool of each job's manifest, the tiles of its chunks
    times its ``bbo_iters``."""
    return sum(sum(p["chunk_sizes"]) * p["bbo_iters"]
               for _, artifact in jobs for p in artifact.manifest["pools"]
               if p["method"] == "bbo")


def us_per_tile_iteration(ctx, scope: str):
    """Device microseconds under ``scope`` per tile-iteration of the
    window's jobs; None where the trace has no such scope."""
    seconds = getattr(ctx["trace"], "scope_seconds", {}).get(scope)
    iters = tile_iterations(ctx["window"]["jobs"])
    if not seconds or iters <= 0:
        return None
    return seconds / iters * 1e6
