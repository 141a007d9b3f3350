"""The reduction of the program's scopes and host spans
(``harness/scopes.py``, ``harness/xplane_meta.py``): on hand-built traces,
on ``data/small_trace.xplane.pb`` (recorded on a TPU v5e before the program
had scopes) and on ``data/small_fl_trace.xplane.pb`` (recorded on a TPU
v5e by ``bench/record_trace.py``: round 0 and two chunks of one round at
N = 10, D = 582,026, pallas, with a store, checkpoints and a sink)."""
import os
import re
from types import SimpleNamespace as NS

import pytest
from jax.profiler import ProfileData

from harness import registry, scopes, trace, xplane_meta

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD = os.path.join(DATA, "small_trace.xplane.pb")
RECORDED = os.path.join(DATA, "small_fl_trace.xplane.pb")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
OLD_PROGRAM = "2513865350324037201"
NEW_METRICS = ("local_phase_ms", "w_build_ms", "coalition_round_ms",
               "eval_ms", "boundary_idle_ms", "edge_idle_ms")
FL_SCOPES = ("fl.local_phase", "fl.w_build", "fl.coalition_round",
             "fl.eval")
FL_SPANS = ("fl.run", "fl.cohort_schedule", "fl.prologue", "fl.dispatch",
            "fl.publish", "fl.checkpoint", "fl.emit", "fl.history")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def fake(modules, ops, host):
    return NS(planes=[
        NS(name="/device:TPU:0",
           lines=[NS(name="XLA Modules", events=modules),
                  NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
    ])


# -- the wire reader ---------------------------------------------------------

def test_wire_reader_finds_each_ops_name_stack():
    meta = xplane_meta.op_scopes(OLD)
    assert list(meta) == [OLD_PROGRAM]
    assert meta[OLD_PROGRAM]["center_sq_dists.1"] == \
        "jit(<lambda>)/jit(center_sq_dists)/pallas_call:"


def test_wire_reader_agrees_with_the_protobuf_library():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    for path in (OLD, RECORDED):
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        want: dict = {}
        for plane in space.planes:
            if not (plane.name.startswith("/device:TPU:")
                    and "Core" not in plane.name):
                continue
            stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
            for m in plane.event_metadata.values():
                stats = {stat_names[s.metadata_id]: s for s in m.stats}
                if "tf_op" not in stats:
                    continue
                s = stats["tf_op"]
                tf_op = (s.str_value if s.WhichOneof("value") == "str_value"
                         else stat_names[s.ref_value])
                p = stats["program_id"]
                program = str(getattr(p, p.WhichOneof("value")))
                want.setdefault(program, {})[trace.op_name(m.name)] = tf_op
        assert xplane_meta.tf_ops(path) == want


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    return bytes(out + bytes([n]))


def msg(*fields) -> bytes:
    """A protobuf message of ``(field number, value)``: an int is a varint,
    bytes or str a length-delimited field."""
    out = bytearray()
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return bytes(out)


def instr(iid, name, operands=(), op_name=None):
    """An ``HloInstructionProto``, its operand ids packed."""
    fields = [(1, name), (35, iid)]
    if op_name is not None:
        fields.append((7, msg((1, "op"), (2, op_name))))
    if operands:
        fields.append((36, b"".join(_varint(o) for o in operands)))
    return msg(*fields)


def test_an_unnamed_op_takes_the_name_stack_its_consumers_share():
    wb = "jit(chunk)/while/body/fl.w_build"
    comp = msg(
        (1, "body"),
        (2, instr(1, "while.5", op_name="jit(chunk)/while/body/"
                  "fl.local_phase/while")),
        (2, instr(2, "copy.1", [1])),               # layout copy of a leaf
        (2, instr(3, "custom-call.2")),             # W's buffer
        (2, instr(4, "dynamic-update-slice.3", [3, 2])),
        (2, instr(5, "fusion.4", [4, 1])),
        (2, instr(6, "dynamic-update-slice.5", [5, 2],
                  op_name=wb + "/concatenate")),
        (2, instr(7, "bitcast.6", [2], op_name=wb + "/reshape")),
        (2, instr(8, "copy.7", [1])),               # read by two phases
        (2, instr(9, "add.8", [8], op_name="jit(chunk)/while/body/"
                  "fl.coalition_round/add")),
        (2, instr(10, "mul.9", [8], op_name="jit(chunk)/while/body/"
                  "fl.eval/mul")),
        (2, instr(11, "copy.10", [1])),             # read by one
        (2, instr(12, "add.11", [11], op_name="jit(chunk)/while/body/"
                  "fl.eval/add")),
        (2, instr(13, "tuple.12", [6, 7, 9, 10, 12])),
        (2, instr(14, "copy.13", [13])))            # reaches no named op
    names = xplane_meta.consumer_names(msg((1, msg((1, "m"), (3, comp)))))
    assert names == {"custom-call.2": wb + "/concatenate",
                     "dynamic-update-slice.3": wb + "/concatenate",
                     "fusion.4": wb + "/concatenate",
                     "copy.1": wb,
                     "copy.7": "jit(chunk)/while/body",
                     "copy.10": "jit(chunk)/while/body/fl.eval/add"}
    assert "fl.w_build" in scopes.components(names["fusion.4"])


def test_pruning_keeps_the_named_fields_and_their_values():
    inner = msg((1, "keep"), (2, "drop"), (3, 7))
    buf = msg((1, 300), (2, inner), (3, "drop"), (4, inner))
    kept = xplane_meta.pruned(buf, {1: None, 2: {1: None, 3: None},
                                    4: None})
    assert kept == msg((1, 300), (2, msg((1, "keep"), (3, 7))), (4, inner))


# -- scope seconds -----------------------------------------------------------

def test_scope_seconds_follow_the_name_stack_and_leave_out_containers():
    modules = [ev("jit_chunk(7)", 0, 1000), ev("jit_round0(9)", 1000, 2000)]
    ops = [ev("%while.1 = (f32[4]) while(...)", 0, 600),        # container
           ev("%fusion.1 = f32[4] fusion(...)", 0, 100),         # forward
           ev("%fusion.2 = f32[4] fusion(...)", 100, 300),       # backward
           ev("%fusion.3 = f32[4] fusion(...)", 300, 350),       # W build
           ev("%copy.4 = f32[4] copy(...)", 350, 400),           # unscoped
           ev("%fusion.1 = f32[4] fusion(...)", 1000, 1100)]     # eval
    op_scopes = {
        "7": {"while.1": "jit(chunk)/while/body/fl.local_phase/while",
              "fusion.1": "jit(chunk)/while/body/fl.local_phase/vmap(f)/add:",
              "fusion.2": "jit(chunk)/while/body/transpose(jvp("
                          "fl.local_phase))/mul:",
              "fusion.3": "jit(chunk)/while/body/fl.w_build/concatenate:"},
        "9": {"fusion.1": "jit(round0)/fl.eval/argmax:"}}
    red = scopes.reduce_scopes(
        fake(modules, ops, [ev("bench.window", 0, 2000),
                            ev("fl.run", 0, 2000)]), op_scopes)
    assert red.scope_seconds == {"fl.local_phase": pytest.approx(300e-9),
                                 "fl.w_build": pytest.approx(50e-9),
                                 "fl.eval": pytest.approx(100e-9)}
    assert red.op_s == pytest.approx(500e-9)          # the while left out
    assert red.scoped_s == pytest.approx(450e-9)
    assert red.scope("fl.local_phase") == pytest.approx(300e-9)
    with pytest.raises(trace.MissingEvents, match="fl.coalition_round"):
        red.scope("fl.coalition_round")


def test_a_program_without_scopes_or_spans_reads_nothing():
    red = scopes.reduce_scopes(
        fake([ev("jit_f(1)", 0, 100)], [ev("%fusion.1 = f32[]", 0, 100)],
             [ev("bench.window", 0, 200), ev("bench.federation_run", 0,
                                             200)]), {})
    assert not red.instrumented
    assert red.scope("fl.eval") is None and red.idle(("fl.publish",)) is None
    assert red.idle_by_span == {"bench.federation_run": pytest.approx(1e-7)}


def test_components_take_transform_wrappers_off():
    assert scopes.components("jit(f)/transpose(jvp(fl.x))/vmap(fl.y)/mul:") \
        == {"f", "fl.x", "fl.y", "mul:"}


# -- idle by span --------------------------------------------------------------

def test_idle_is_split_over_the_innermost_spans_by_overlap():
    ops = [ev("%fusion.1 = f32[]", 0, 100), ev("%fusion.2 = f32[]", 700,
                                               1000)]
    host = [ev("bench.window", 0, 1000), ev("fl.run", 0, 1000),
            ev("fl.publish", 50, 300), ev("fl.dispatch", 300, 350),
            ev("fl.dispatch", 650, 690)]
    red = scopes.reduce_scopes(fake([ev("jit_f(1)", 0, 1000)], ops, host),
                               {})
    # one gap, 100-700: 200 under the publish, 50 and 40 under the two
    # dispatches, the rest (350-650, 690-700) under fl.run alone, where a
    # gap named at its midpoint (400) would go whole
    assert red.idle_by_span == {"fl.publish": pytest.approx(200e-9),
                                "fl.dispatch": pytest.approx(90e-9),
                                "fl.run": pytest.approx(310e-9)}
    assert red.span_counts == {"fl.run": 1, "fl.publish": 1,
                               "fl.dispatch": 2}
    assert red.idle(("fl.dispatch", "fl.publish", "fl.emit")) == \
        pytest.approx(290e-9)


# -- the readers ----------------------------------------------------------------

def _ctx(path, rounds):
    red = trace.reduce_trace(ProfileData.from_file(path))
    return {"trace": red, "scopes": scopes.load(path), "rounds": rounds,
            "kind": "train", "window_s": red.window_s}


def test_the_new_readers_report_nothing_for_a_program_without_scopes():
    ctx = _ctx(OLD, 3)
    for name in NEW_METRICS:
        assert registry.metric_reader(name, BENCH)(ctx) is None, name


def test_recorded_trace_holds_every_scope_and_span():
    red = scopes.load(RECORDED)
    assert red.chips == 1 and red.instrumented
    for name in FL_SCOPES:
        assert red.scope(name) > 0, name
    for name in FL_SPANS:
        assert red.span_counts.get(name, 0) >= 1, name
    assert red.span_counts["fl.dispatch"] == 2
    # the scopes hold most of the round's device time
    assert red.scoped_s > 0.5 * red.op_s
    busy = trace.reduce_trace(ProfileData.from_file(RECORDED)).busy_s
    assert sum(red.idle_by_span.values()) == pytest.approx(red.window_s
                                                           - busy)


def test_recorded_trace_counts_the_writes_of_w_under_w_build():
    """XLA writes W's concatenate in place, in ops with no name stack; named
    by their consumers they take ``fl.w_build`` at least one write of W a
    round (10 x 582,026 f32 at the v5e's 819 GB/s), where the ops that
    carry the scope themselves take less."""
    pdata = ProfileData.from_file(RECORDED)
    own = scopes.reduce_scopes(pdata, xplane_meta.tf_ops(RECORDED))
    named = scopes.reduce_scopes(pdata, xplane_meta.op_scopes(RECORDED))
    one_write = 10 * 582_026 * 4 / 819e9
    per_round = named.scope("fl.w_build") / 3
    assert own.scope("fl.w_build") / 3 < one_write <= per_round
    assert named.scoped_s > own.scoped_s
    assert named.op_s == own.op_s


def test_the_names_the_readers_read_are_the_programs():
    """A program change that renames a scope or a span fails here, where a
    traced run of a program with none of the names would report nothing."""
    with open(os.path.join(ROOT, "src", "repro", "core", "server.py")) as f:
        src = f.read()
    program = set(re.findall(r'named_scope\("(fl\.[^"]+)"\)', src))
    program |= set(re.findall(r'TraceAnnotation\("(fl\.[^"]+)"\)', src))
    read = {scopes.RUN_SPAN}
    for name in NEW_METRICS:
        g = registry.metric_reader(name, BENCH).__globals__
        read |= {g["SCOPE"]} if "SCOPE" in g else set(g["SPANS"])
    assert read == program == set(FL_SCOPES) | set(FL_SPANS)


#: the metrics the benchmark had before the scopes, on the recorded trace,
#: as its unchanged reduction reads them
OLD_READINGS = {"device_idle_share.train": 97.44872961836313,
                "coalition_kernel_ms": 0.24643133333333334,
                "coalition_kernel_roofline": 27.684315511350494}


def test_recorded_trace_new_readers_read_and_old_ones_are_unmoved():
    ctx = _ctx(RECORDED, 3)
    ctx.update(config=registry.load_cell(ROOT, "paper_cnn_n10.train").config,
               peaks={"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    def read(names):
        return {m: registry.metric_reader(m, BENCH)(ctx) for m in names}

    assert read(OLD_READINGS) == OLD_READINGS
    new = read(NEW_METRICS)
    assert read(OLD_READINGS) == OLD_READINGS
    assert all(new[m] > 0 for m in NEW_METRICS), new
    # three rounds: round 0 and two chunks of one round
    assert new["local_phase_ms"] == pytest.approx(
        1e3 * ctx["scopes"].scope_seconds["fl.local_phase"] / 3)
