"""Read each TPU op's JAX name stack (``tf_op``) from an ``.xplane.pb``.

``jax.profiler.ProfileData`` gives an op event its name and times but not
its metadata's stats, and the stat ``tf_op`` is where the name stack of the
op's JAX code (``jax.named_scope`` included) reaches the trace.  This is a
reader of the protobuf wire format, with no dependency, of just the fields
it needs:

* ``XSpace``: ``planes`` 1;
* ``XPlane``: ``name`` 2, ``event_metadata`` 4 and ``stat_metadata`` 5
  (maps: key 1, value 2);
* ``XEventMetadata``: ``name`` 2, ``stats`` 5;
* ``XStat``: ``metadata_id`` 1, ``str_value`` 5, ``ref_value`` 7 (a ref
  names a ``stat_metadata`` entry, whose name is the value);
* ``XStatMetadata``: ``id`` 1, ``name`` 2.

Op names repeat across programs, so the result is keyed by program id (the
stat ``program_id``, also the parenthesised suffix of a module event's name
on the ``XLA Modules`` line) and then by op name.

Some ops have no name stack: XLA added them while it optimized the
program, as layout copies or as the in-place writes that a concatenate
becomes, and their ``tf_op`` names no JAX code.  The ``/host:metadata``
plane holds each program's optimized HLO (an ``XEventMetadata`` named
``jit_f(<program id>)`` with the stat ``Hlo Proto``), and
:func:`consumer_names` names such an op by what reads its result.  Its
fields: ``HloProto.hlo_module`` 1; ``HloModuleProto.computations`` 3;
``HloComputationProto.instructions`` 2; ``HloInstructionProto`` ``name``
1, ``metadata`` 7 (``OpMetadata.op_name`` 2), ``id`` 35, ``operand_ids``
36; ``XStat.bytes_value`` 6.

:func:`pruned` keeps only the fields of a plane that these readers need,
to keep a recorded trace small.
"""
from __future__ import annotations

from harness.trace import op_name

#: the plane of each program's optimized HLO, and the stat that holds it
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: what :func:`hlo_protos` and :func:`consumer_names` read of an ``XPlane``
METADATA_FIELDS = {2: None, 5: None,
                   4: {1: None, 2: {2: None, 5: {1: None, 6: {1: {3: {2: {
                       1: None, 7: {2: None}, 35: None, 36: None}}}}}}}}


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _encode_varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _entries(buf: memoryview):
    """``(field number, value, start, end)`` of each field of one message:
    the value is an int for varint and fixed-width fields, a slice of
    ``buf`` for length-delimited ones; ``buf[start:end]`` is the field's
    whole encoding."""
    i, n = 0, len(buf)
    while i < n:
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val, start, i


def _fields(buf: memoryview):
    """``(field number, value)`` of one message (see :func:`_entries`)."""
    for field, val, _, _ in _entries(buf):
        yield field, val


def _ints(val) -> list[int]:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        x, i = _varint(val, i)
        out.append(x)
    return out


def pruned(buf, keep: dict) -> bytes:
    """The message ``buf`` with only the fields in ``keep``, each kept
    whole (``None``) or pruned by its own ``keep``."""
    view, out = memoryview(buf), bytearray()
    for field, val, start, end in _entries(view):
        if field not in keep:
            continue
        if keep[field] is None or isinstance(val, int):
            out += view[start:end]
        else:
            inner = pruned(val, keep[field])
            out += _encode_varint(field << 3 | 2)
            out += _encode_varint(len(inner)) + inner
    return bytes(out)


def _map_values(entries: list[memoryview]):
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def _stat_names(plane: dict) -> dict[int, str]:
    out = {}
    for meta in _map_values(plane.get(5, [])):
        d = dict(_fields(meta))
        out[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
    return out


def _plane_ops(plane: dict) -> dict[str, dict[str, str]]:
    stat_names = _stat_names(plane)
    ids = {sid for sid, name in stat_names.items() if name == "program_id"}
    ops = {sid for sid, name in stat_names.items() if name == "tf_op"}
    out: dict[str, dict[str, str]] = {}
    for meta in _map_values(plane.get(4, [])):
        name, program, tf_op = "", None, None
        for f, v in _fields(meta):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                which = stat.get(1)
                if which in ops:
                    tf_op = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7), ""))
                elif which in ids:
                    program = str(stat.get(4, stat.get(3)))
        if tf_op is not None:
            out.setdefault(program, {})[op_name(name)] = tf_op
    return out


def program_of(module_name: str) -> str:
    """The program id in a module's name, ``jit_f(1234)`` -> ``1234``."""
    head, _, tail = module_name.rpartition("(")
    return tail.rstrip(")") if head else module_name


def hlo_protos(plane: dict) -> dict[str, memoryview]:
    """``{program id: HloProto}`` of the metadata plane ``plane``."""
    hlo = {sid for sid, name in _stat_names(plane).items()
           if name == HLO_STAT}
    out = {}
    for meta in _map_values(plane.get(4, [])):
        name, proto = "", None
        for f, v in _fields(meta):
            if f == 2:
                name = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat.get(1) in hlo:
                    proto = stat.get(6)
        if proto is not None:
            out[program_of(name)] = proto
    return out


def _shared(a: tuple, b: tuple) -> tuple:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def consumer_names(hlo_proto: memoryview) -> dict[str, str]:
    """``{instruction name: name stack}`` for each instruction of one
    optimized ``HloProto`` that has no name stack of its own, named by the
    ``/``-separated components that its consumers' name stacks share: the
    named instructions its result reaches, directly or through unnamed
    ones.  One whose consumers share nothing, or that reaches no named
    instruction, is left out."""
    module = next(v for f, v in _fields(hlo_proto) if f == 1)
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        order, names, stacks, users = [], {}, {}, {}
        for g, ins in _fields(comp):
            if g != 2:
                continue
            iid, name, stack, operands = None, "", "", []
            for h, v in _fields(ins):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    stack = next((bytes(x).decode() for k, x in _fields(v)
                                  if k == 2), "")
                elif h == 35:
                    iid = v
                elif h == 36:
                    operands += _ints(v)
            order.append(iid)
            names[iid] = name
            stacks[iid] = tuple(stack.split("/")) if stack else None
            for o in operands:
                users.setdefault(o, []).append(iid)
        shared: dict[int, tuple] = {}
        # a computation lists its instructions in post order, operands
        # first, so an instruction's users are settled before it
        for iid in reversed(order):
            if stacks[iid] is not None:
                continue
            common = None
            for u in users.get(iid, ()):
                theirs = stacks[u] if stacks[u] is not None else shared.get(u)
                if theirs is not None:
                    common = (theirs if common is None
                              else _shared(common, theirs))
            if common is not None:
                shared[iid] = common
                if common:
                    out[names[iid]] = "/".join(common)
    return out


def plane_name(plane_buf: memoryview) -> str:
    return next((bytes(v).decode() for f, v in _fields(plane_buf) if f == 2),
                "")


def _planes(path: str):
    """``(name, {field: [values]})`` of the trace's planes, for the fields
    these readers use (``event_metadata`` 4, ``stat_metadata`` 5)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())      # slices without copies
    for field, plane_buf in _fields(buf):
        if field != 1:
            continue
        plane: dict[int, list] = {}
        for f, v in _fields(plane_buf):
            if f in (4, 5):
                plane.setdefault(f, []).append(v)
        yield plane_name(plane_buf), plane


def _is_tpu(name: str) -> bool:
    return name.startswith("/device:TPU:") and "Core" not in name


def tf_ops(path: str) -> dict[str, dict[str, str]]:
    """``{program id: {op name: tf_op}}`` over the trace's TPU planes."""
    out: dict[str, dict[str, str]] = {}
    for name, plane in _planes(path):
        if _is_tpu(name):
            for program, ops in _plane_ops(plane).items():
                out.setdefault(program, {}).update(ops)
    return out


def op_scopes(path: str) -> dict[str, dict[str, str]]:
    """``{program id: {op name: name stack}}``: each op's ``tf_op``, and for
    the ops that XLA added with none of their own, their consumers' shared
    name stack (:func:`consumer_names`) where the trace holds the HLO."""
    out = tf_ops(path)
    for name, plane in _planes(path):
        if name == METADATA_PLANE:
            for program, proto in hlo_protos(plane).items():
                out.setdefault(program, {}).update(consumer_names(proto))
    return out


def with_plane_pruned(buf: bytes, name: str, keep: dict) -> bytes:
    """The ``XSpace`` ``buf`` with its planes called ``name`` pruned to
    ``keep`` (:func:`pruned`; ``planes`` is field 1)."""
    view, out = memoryview(buf), bytearray()
    for field, val, start, end in _entries(view):
        if field == 1 and plane_name(val) == name:
            inner = pruned(val, keep)
            out += _encode_varint(1 << 3 | 2)
            out += _encode_varint(len(inner)) + inner
        else:
            out += view[start:end]
    return bytes(out)
