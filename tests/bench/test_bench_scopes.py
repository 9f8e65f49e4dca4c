"""The program's scopes and spans in a trace (``bench/trace_scopes.py``):
op_name paths read from the programs' HLO in the trace's metadata plane,
device seconds by scope, device idle under the innermost program span,
and the per-layer metrics that read them."""

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import cells, trace_reduce, trace_scopes  # noqa: E402

MS = 1_000_000
SURROGATE = ("jit(job)/compress.bbo/while/body/closed_call/bbo.surrogate/"
             "vmap(jit(cholesky))/cholesky")
APPEND = "jit(job)/compress.bbo/while/body/closed_call/vmap(bbo.append)/add"
CHOL = ('%custom-call.79 = f32[280,137,137]{2,1,0} custom-call(f32[280,137,137]'
        ' %p), custom_call_target="Cholesky"')
FUSION = "%add_fusion.3 = f32[280,8]{1,0} fusion(f32[280,8]{1,0} %a), kind=kLoop"
OTHER = "%add_fusion.3 = u32[4]{0} fusion(u32[4]{0} %b), kind=kLoop"
WHILE = "%while.1 = (s32[], f32[280,8]) while((s32[], f32[280,8]) %t)"


# -- a serialized XSpace, field by field ---------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _msg(field: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _hlo(module: str, op_names: dict) -> bytes:
    """HloProto: hlo_module (1) -> name (1), computations (3) -> id (5),
    instructions (2) -> name (1), metadata (7) -> op_name (2),
    called_computation_ids (38).  ``op_names``: {inst: op_name} of one
    computation, or {computation id: [(inst, op_name, called ids)]}."""
    if all(isinstance(v, str) for v in op_names.values()):
        op_names = {1: [(i, op, ()) for i, op in op_names.items()]}
    comps = b""
    for cid, insts in op_names.items():
        body = _int(5, cid)
        for i, op, called in insts:
            inst = _msg(1, i) + (_msg(7, _msg(2, op)) if op else b"")
            if len(called) > 1:      # packed, as proto3 writes them
                inst += _msg(38, b"".join(_varint(c) for c in called))
            inst += b"".join(_int(38, c) for c in called if len(called) == 1)
            body += _msg(2, inst)
        comps += _msg(3, body)
    return _msg(1, _msg(1, module) + comps)


def _plane(name: str, lines=(), events=None, stats=None, pid=1) -> bytes:
    """XPlane: id (1), name (2), lines (3), event_metadata (4),
    stat_metadata (5).  ``events``: {id: (name, [(stat id, bytes)])};
    ``stats``: {id: name}."""
    body = _int(1, pid) + _msg(2, name) + b"".join(_msg(3, ln) for ln in lines)
    for i, (ev, st) in (events or {}).items():
        xstats = b"".join(_msg(5, _int(1, s) + _msg(6, v)) for s, v in st)
        body += _msg(4, _int(1, i) + _msg(2, _int(1, i) + _msg(2, ev) + xstats))
    for i, st in (stats or {}).items():
        body += _msg(5, _int(1, i) + _msg(2, _int(1, i) + _msg(2, st)))
    return body


def _line(name: str, events) -> bytes:
    """XLine: id (1), name (2), timestamp_ns (3), events (4:
    metadata_id (1), offset_ps (2), duration_ps (3))."""
    evs = b"".join(_msg(4, _int(1, m) + _int(2, s * 1000) + _int(3, d * 1000))
                   for m, s, d in events)
    return _int(1, 1) + _msg(2, name) + _int(3, 0) + evs


def _write_trace(tmp_path: pathlib.Path, ops, modules, spans) -> pathlib.Path:
    """A trace file shaped like a v5e's: ``ops`` [(text, start, dur)] on the
    ``XLA Ops`` line, ``modules`` [(program, start, dur, {inst: op_name})]
    on ``XLA Modules`` and in the metadata plane's HLO, ``spans``
    [(name, start, dur)] on a host thread; times in ns."""
    names = sorted({t for t, _, _ in ops} | {m[0] for m in modules})
    ids = {n: i + 1 for i, n in enumerate(names)}
    device = _plane(
        "/device:TPU:0",
        lines=[_line("XLA Modules", [(ids[p], s, d) for p, s, d, _ in modules]),
               _line("XLA Ops", [(ids[t], s, d) for t, s, d in ops])],
        events={i: (n, []) for n, i in ids.items()})
    span_ids = {n: i + 1 for i, n in enumerate(sorted({s[0] for s in spans}))}
    host = _plane("/host:CPU", lines=[_line("python3", [
        (span_ids[n], s, d) for n, s, d in spans])],
        events={i: (n, []) for n, i in span_ids.items()}, pid=2)
    meta = _plane(
        "/host:metadata", pid=3, stats={1: "Hlo Proto"},
        events={i + 1: (p, [(1, _hlo(p.split("(")[0], names))])
                for i, (p, _, _, names) in enumerate(modules)})
    path = tmp_path / "plugins" / "profile" / "1" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"".join(_msg(1, p) for p in (device, host, meta)))
    return path


def _job_trace(tmp_path):
    ops = [(WHILE, 10 * MS, 60 * MS), (CHOL, 12 * MS, 30 * MS),
           (FUSION, 45 * MS, 5 * MS), (FUSION, 60 * MS, 5 * MS),
           (OTHER, 85 * MS, 5 * MS)]
    modules = [
        ("jit_job(7)", 10 * MS, 60 * MS,
         {"while.1": "jit(job)/compress.bbo/while", "custom-call.79":
          SURROGATE, "add_fusion.3": APPEND}),
        ("jit_other(9)", 80 * MS, 15 * MS, {"add_fusion.3": "jit(other)/add"}),
    ]
    spans = [("bench.window", 0, 100 * MS), ("bench.job", 1 * MS, 98 * MS),
             ("repro.execute", 2 * MS, 96 * MS),
             ("repro.execute.pack", 50 * MS, 45 * MS)]
    return _write_trace(tmp_path, ops, modules, spans)


def test_paths_come_from_the_programs_hlo_by_program_and_instruction(
        tmp_path):
    device_ops, spans, paths = trace_scopes.read_trace(str(_job_trace(tmp_path)))
    assert [t for t, _, _ in device_ops["/device:TPU:0"]] == [
        WHILE, CHOL, FUSION, FUSION, OTHER]
    assert paths == {WHILE: "jit(job)/compress.bbo/while", CHOL: SURROGATE,
                     FUSION: APPEND, OTHER: "jit(other)/add"}
    assert [n for n, _, _ in spans] == [
        "bench.window", "bench.job", "repro.execute", "repro.execute.pack"]


def test_each_distinct_op_of_a_program_is_looked_up_once(tmp_path,
                                                        monkeypatch):
    ops = [(CHOL, i * MS, MS // 2) for i in range(50)]
    path = _write_trace(tmp_path, ops, [("jit_job(7)", 0, 60 * MS, {
        "custom-call.79": SURROGATE})], [("bench.window", 0, 60 * MS)])
    calls = []
    look_up = trace_scopes.op_path

    def counting(programs, program, text):
        calls.append((program, text))
        return look_up(programs, program, text)

    monkeypatch.setattr(trace_scopes, "op_path", counting)
    _, _, paths = trace_scopes.read_trace(str(path))
    assert calls == [("jit_job(7)", CHOL)] and paths == {CHOL: SURROGATE}


def test_an_op_xla_added_takes_the_op_name_of_its_caller():
    loop = "jit(job)/compress.bbo/while"
    proto = _hlo("jit_job", {
        1: [("while.1", loop, (2, 4)), ("p.0", "", ())],
        2: [("copy.5", "", ()), ("add.2", APPEND, ()),
            ("fusion.9", "", (3,))],
        3: [("x.1", "", ())],
        4: [("cond.1", "", ())],
    })
    buf = memoryview(proto)
    got = trace_scopes._hlo_op_names(buf, (0, len(buf)))
    assert got == {"while.1": loop, "copy.5": loop, "add.2": APPEND,
                   "fusion.9": loop, "x.1": loop, "cond.1": loop}


def test_an_op_is_looked_up_in_its_own_program():
    programs = {"jit_job(7)": {"add_fusion.3": APPEND},
                "jit_job(8)": {"add_fusion.3": SURROGATE}}
    assert trace_scopes.op_path(programs, "jit_job(7)", FUSION) == APPEND
    assert trace_scopes.op_path(programs, "jit_job(8)", FUSION) == SURROGATE
    assert trace_scopes.op_path(programs, "jit_job(7)", CHOL) == ""
    assert trace_scopes.op_path(programs, "", CHOL) == ""


def test_the_scoped_reduction_keeps_every_field_of_the_reduction(tmp_path):
    _job_trace(tmp_path)
    base = trace_reduce.reduce_trace(str(tmp_path))
    red = trace_scopes.reduce_trace(str(tmp_path))
    for field in vars(base):
        assert getattr(red, field) == getattr(base, field), field
    assert red.scope_seconds == {
        "compress.bbo": pytest.approx(0.040),
        "bbo.surrogate": pytest.approx(0.030),
        "bbo.append": pytest.approx(0.010)}
    assert red.scoped_share == pytest.approx(40 / 45)
    assert red.span_counts == {"repro.execute": 1, "repro.execute.pack": 1}


def test_scope_seconds_unwrap_transforms_count_fusions_not_containers():
    ops = {"/device:TPU:0": [
        ("%while.3 = () while()", 0, 90 * MS),
        ("%fusion.1 = f32[8] fusion()", 0, 20 * MS),
        ("%custom-call.2 = f32[8] custom-call()", 20 * MS, 30 * MS),
        ("%copy.4 = f32[8] copy()", 50 * MS, 10 * MS),
        ("%fusion.5 = f32[8] fusion()", 60 * MS, 20 * MS),
    ]}
    paths = {
        "%while.3 = () while()": "jit(f)/compress.bbo/while",
        "%fusion.1 = f32[8] fusion()":
            "jit(f)/compress.bbo/while/body/vmap(vmap(bbo.surrogate))/add",
        "%custom-call.2 = f32[8] custom-call()":
            "jit(f)/compress.bbo/while/body/bbo.surrogate/"
            "vmap(jit(cholesky))/cholesky",
        "%copy.4 = f32[8] copy()": "jit(g)/copy",
    }
    got = trace_scopes.reduce_scopes(ops, [], paths, 0, 100 * MS)
    assert got.scope_seconds == {"compress.bbo": pytest.approx(0.050),
                                 "bbo.surrogate": pytest.approx(0.050)}
    # 50 of the 80 ms of operations (the while holds no time of its own)
    assert got.scoped_share == pytest.approx(50 / 80)
    assert trace_scopes.reduce_scopes({}, [], {}, 0, MS).scoped_share is None


def test_idle_is_counted_under_the_innermost_span_by_intersection():
    ops = {"/device:TPU:0": [("a", 0, 30 * MS), ("b", 50 * MS, 10 * MS),
                             ("c", 95 * MS, 5 * MS)]}
    spans = [("bench.window", -10 * MS, 110 * MS),
             ("repro.execute", 0, 100 * MS),
             ("repro.execute.pack", 40 * MS, 50 * MS),
             ("repro.other", 200 * MS, 10 * MS)]
    got = trace_scopes.reduce_scopes(ops, spans, {}, -10 * MS, 100 * MS)
    # gaps -10..0 (no program span), 30..50 across the pack's start at 40,
    # 60..95 across its end at 90
    assert got.span_idle_s == {"repro.execute": pytest.approx(0.015),
                               "repro.execute.pack": pytest.approx(0.040)}
    assert got.span_counts == {"repro.execute": 1, "repro.execute.pack": 1}


def test_idle_is_averaged_over_devices():
    ops = {"/device:TPU:0": [("a", 0, 100 * MS)],
           "/device:TPU:1": [("a", 0, 60 * MS)]}
    got = trace_scopes.reduce_scopes(
        ops, [("repro.execute", 0, 100 * MS)], {}, 0, 100 * MS)
    assert got.span_idle_s == {"repro.execute": pytest.approx(0.020)}


POOLS = [{"method": "bbo", "chunk_sizes": [280] * 10 + [272],
          "bbo_iters": 64},
         {"method": "alternating", "chunk_sizes": [500], "bbo_iters": 0}]


def _ctx(red, jobs=1):
    artifact = types.SimpleNamespace(manifest={"pools": POOLS})
    return {"trace": red, "window": {"jobs": [({}, artifact)] * jobs}}


def _scoped(scopes=None, idle=None, counts=None):
    return trace_scopes.ScopedReduction(
        window_s=11.2, busy_s=11.0, op_seconds={}, op_calls={}, gaps=[],
        devices=1, scope_seconds=scopes or {}, span_idle_s=idle or {},
        scoped_share=None, span_counts=counts or {})


@pytest.mark.parametrize("metric,scope", [
    ("bbo_surrogate_us_per_tile_iter", "bbo.surrogate"),
    ("bbo_dataset_us_per_tile_iter", "bbo.append"),
])
def test_per_tile_iteration_metrics(metric, scope):
    read = cells.metric_reader(ROOT, metric)
    assert trace_scopes.tile_iterations(_ctx(None)["window"]["jobs"]) == \
        3072 * 64
    red = _scoped({scope: 8.75, "bbo.ising": 1.0})
    assert read(_ctx(red)) == pytest.approx(8.75 / 196_608 * 1e6)
    # two jobs in the window: twice the tile-iterations
    assert read(_ctx(red, jobs=2)) == pytest.approx(8.75 / 393_216 * 1e6)
    assert read(_ctx(_scoped({"bbo.ising": 1.0}))) is None
    plain = trace_reduce.reduce_events({}, [], 0, MS)
    assert read(_ctx(plain)) is None


def test_device_idle_under_execute_spans_per_job():
    read = cells.metric_reader(ROOT, "device_idle_ms.execute")
    red = _scoped(idle={"repro.execute": 0.015, "repro.execute.pack": 0.040,
                        "repro.executed": 1.0, "repro.other": 1.0},
                  counts={"repro.execute": 2})
    assert read(_ctx(red, jobs=2)) == pytest.approx(27.5)
    assert read(_ctx(_scoped(counts={"repro.execute": 1}))) == 0.0
    assert read(_ctx(_scoped(idle={"repro.execute.pack": 0.04}))) is None
    assert read(_ctx(trace_reduce.reduce_events({}, [], 0, MS))) is None
