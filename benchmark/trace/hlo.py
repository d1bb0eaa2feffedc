"""The compiled step as the TRACE holds it: what is inside each device
operation.

``scopes.py`` gives a device operation the Fluid name of its ROOT
instruction (``tf_op``): one name a fusion.  A v5e trace holds more (looked
at by hand on ``tests/benchmark_suite/data/probe_trace.xplane.pb``, PR 51):

* every operation's event-metadata NAME is its whole HLO instruction —
  ``%fusion.7 = bf16[..]{..S(1)} fusion(bf16[..]{..S(1)} %copy-done, bf16[..]
  %constant.3), kind=kOutput, calls=%fused_computation.8`` — operands with
  shapes, layouts and memory spaces (``S(1)``: the array came through a
  prefetch into the fast memory), and the computation it calls;
* the stat ``program_id`` names the compiled program it belongs to;
* the plane ``/host:metadata`` holds, per program, the stat ``Hlo Proto``:
  field 1 of its bytes is the serialized module, which the installed
  jaxlib prints scheduled, fused computations and every inner instruction's
  ``metadata={op_name=...}`` included.

From that text: fused computation -> the ``dot`` / ``convolution``
instructions inside it with THEIR ``op_name``s (a product fused under an
``adam`` root still says ``fluid[mul_grad]../dw/..``), and the Fluid types
of all its inner instructions.  A CPU's trace has no such plane: ``load``
then returns no programs and every reader above it answers None.

The first half reads the profiler's file (one parse, with
``scopes._xspace_class``); the second is text and plain tuples.
"""

import collections
import re

from benchmark.trace import reduce as trace_reduce
from benchmark.trace import scopes

PRODUCT_OPCODES = ("convolution", "dot")
# instructions that do no work of their own: XLA shares one constant among
# fusions of many Fluid ops, and it keeps the name of whoever made it first
PLUMBING_OPCODES = ("constant", "parameter", "broadcast", "iota", "bitcast",
                    "tuple", "get-tuple-element")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"^([\w\-]+)\(")
_CALLS = re.compile(r"\bcalls=%([^\s,}]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"([a-z][a-z0-9]*\[[^\]]*\](?:\{[^}]*\})?) %")

Instruction = collections.namedtuple(
    "Instruction", "name shape opcode calls op_name")


# ---------------------------------------------------------------------------
# the profiler's file
# ---------------------------------------------------------------------------

def _varint(data, i):
    shift = value = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def module_text(hlo_proto):
    """The module of an ``Hlo Proto`` stat's bytes as text (field 1 of an
    ``xla.HloProto`` is its ``HloModuleProto``), or None where this
    installation cannot print it."""
    if not hlo_proto or hlo_proto[0] != 0x0A:
        return None
    size, start = _varint(hlo_proto, 1)
    try:
        from jax._src.lib import xla_client

        return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            bytes(hlo_proto[start:start + size])).to_string()
    except Exception:       # noqa: BLE001 — another jaxlib: nothing to read
        return None


def load(path):
    """{"devices": {ordinal: {"ops": [(name, start_ns, dur_ns, tf_op,
    hlo_category, flops, bytes_accessed, instruction text, program id),
    ...]}}, "programs": {program id: module name}, "protos": {program id:
    the ``Hlo Proto`` bytes}, "peaks": {device_type_string,
    peak_teraflops_per_second, ...} as the first device plane states them}
    — ``scopes.load``'s operations, in its order and with its first seven
    fields, plus the instruction and the program each belongs to."""
    from google.protobuf.message import DecodeError

    space = scopes._xspace_class()()
    with open(path, "rb") as f:
        try:
            space.ParseFromString(f.read())
        except DecodeError as e:
            raise ValueError("%s is no XSpace: %s" % (path, e))
    out = {"devices": {}, "programs": {}, "protos": {}, "peaks": {}}
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][int(m.group(1))] = {
                "ops": _device_ops(plane, names)}
            if not out["peaks"]:
                out["peaks"] = {names.get(s.metadata_id):
                                scopes._stat_value(s, names)
                                for s in plane.stats}
        elif plane.name == "/host:metadata":
            for key, md in plane.event_metadata.items():
                # the map's key is the program id as a SIGNED 64-bit
                # number; the operations' ``program_id`` stat prints it
                # unsigned
                pid = str(key % 2 ** 64)
                for s in md.stats:
                    if names.get(s.metadata_id) == "Hlo Proto":
                        out["programs"][pid] = md.name
                        out["protos"][pid] = s.bytes_value
    return out


def _device_ops(plane, names):
    meta = {}

    def describe(metadata_id):
        if metadata_id not in meta:
            md = plane.event_metadata[metadata_id]
            stats = {names.get(s.metadata_id): scopes._stat_value(s, names)
                     for s in md.stats}
            meta[metadata_id] = (
                trace_reduce.op_name(md.name), str(stats.get("tf_op") or ""),
                str(stats.get("hlo_category") or ""),
                int(stats.get("flops") or 0),
                int(stats.get("bytes_accessed") or 0), md.name,
                str(stats.get("program_id") or ""))
        return meta[metadata_id]

    ops = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                d = describe(ev.metadata_id)
                ops.append((d[0], line.timestamp_ns + ev.offset_ps / 1e3,
                            ev.duration_ps / 1e3) + d[1:])
    return ops


# ---------------------------------------------------------------------------
# the module's text
# ---------------------------------------------------------------------------

def _closing(text):
    """Index of the bracket that closes the first ``(`` of ``text`` (its
    length where there is none)."""
    depth = 0
    for i, c in enumerate(text):
        depth += c == "("
        depth -= c == ")"
        if depth == 0 and c == ")":
            return i
    return len(text)


def _split_shape(rest):
    """(result shape, what follows it) of an instruction's right-hand
    side; a tuple shape is bracketed."""
    if rest.startswith("("):
        end = _closing(rest) + 1
        return rest[:end], rest[end:].lstrip()
    shape, _, tail = rest.partition(" ")
    return shape, tail


def parse_instruction(text):
    """An ``Instruction`` from one line ``%name = shape opcode(...), ...``
    (a module's, or a device operation's event name), or None."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    shape, tail = _split_shape(m.group(2))
    op = _OPCODE.match(tail)
    name = _OP_NAME.search(tail)
    return Instruction(m.group(1), shape, op.group(1) if op else "",
                       tuple(_CALLS.findall(tail)),
                       name.group(1) if name else "")


def parse_module(text):
    """{computation name: [Instruction, ...]} of a module's text."""
    comps, cur = {}, None
    for line in text.split("\n"):
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            ins = parse_instruction(line)
            if ins is not None:
                cur.append(ins)
    return comps


def inner_instructions(comps, name, _seen=None):
    """Every instruction of computation ``name`` and of the computations
    its fusions call, nested ones too."""
    seen = set() if _seen is None else _seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    out = []
    for ins in comps[name]:
        out.append(ins)
        if ins.opcode == "fusion":
            for callee in ins.calls:
                out.extend(inner_instructions(comps, callee, seen))
    return out


def contents(comps, operation):
    """What is inside one device operation (an ``Instruction`` parsed from
    its event name; ``tf_op`` stands for the ``op_name`` the event name
    does not carry): (the product instructions — ``dot`` / ``convolution``
    — inside it, the ``op_name``s of the instructions inside it that do
    work: constants, parameters, broadcasts and the like are left out).  A
    fusion is its called computations; anything else is itself."""
    if operation.opcode == "fusion":
        inner = [i for callee in operation.calls
                 for i in inner_instructions(comps, callee)]
    else:
        inner = [operation]
    return ([i for i in inner if i.opcode in PRODUCT_OPCODES],
            [i.op_name for i in inner
             if i.op_name and i.opcode not in PLUMBING_OPCODES])


def operands(text):
    """(result shape, [operand shape, ...]) of a device operation's event
    name, each with its layout: ``S(1)`` in one says the array lies in the
    fast memory (an operand: it came through a prefetch)."""
    _, _, rhs = text.partition(" = ")
    result, tail = _split_shape(rhs)
    return result, _OPERAND.findall(tail[:_closing(tail) + 1])


class Programs:
    """The trace's compiled programs, each parsed when first asked for."""

    def __init__(self, loaded):
        self._protos = loaded["protos"]
        self.names = loaded["programs"]
        self._parsed = {}
        self.text_bytes = 0

    def __bool__(self):
        return bool(self._protos)

    def computations(self, program_id):
        """``parse_module`` of the program, or None (no proto for it, or
        none this installation can print)."""
        if program_id not in self._parsed:
            text = module_text(self._protos.get(program_id))
            self.text_bytes += len(text or "")
            self._parsed[program_id] = parse_module(text) if text else None
        return self._parsed[program_id]
