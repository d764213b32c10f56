"""Device time by the program's named scopes, from a profiler trace.

The program names its parts with `jax.named_scope`; `program_scopes` reads
every name it gives there, as a string literal, from the program's source,
so a scope the program adds is charged without a change here. XLA keeps the
names in each instruction's `op_name` metadata, a path such as
`jit(_engine_step_impl)/while/body/layers/while/body/attention/dot_general`.
`load` reads a trace directory into bench/trace_reduce.py's intervals, each
op with its path; `reduce` charges every op of a program call that starts in
the window to the innermost scope in its path, or to `other`.

Where a path comes from: the profiler keeps each loaded program's compiled
HLO on the `/host:metadata` plane (stat `Hlo Proto`, keyed by the program id
that also names the program's runs, `jit__engine_step_impl(<id>)`), and an
op event is found there by its instruction name. A fusion takes its root's
`op_name`. Where the root has none, as the tuple of a multi-output fusion
or a convert that XLA put in front of a dot, the fusion takes the first
`op_name` found walking back from the root through its operands, breadth
first. On the TPU the profiler's own `tf_op` stat is empty for such
fusions, so a reduction by `tf_op` charges the paged decode's gather of
each row's page table and its f32 convert to `other`.

Every traced run reads it: the serving driver's `Tracer.reduce` adds
`reduce`'s totals to the trace reduction as `scopes`, bench/run.py puts
`decode_ms` in the result's `breakdown`, and readers such as
bench/metrics/decode_{scan_self,attention}_ms.*.py take one scope's time
through `ms_per_substep`.
"""
from __future__ import annotations

import ast
import bisect
import functools
import glob
import importlib.util
import os
from collections import defaultdict, deque
from dataclasses import dataclass
from pathlib import Path

from bench import trace_reduce
from bench.readers import DECODE


@dataclass
class Op(trace_reduce.Interval):
    path: str = ""           # the op's HLO op_name


def _scope_calls(src: str | None):
    """(file, call) of every `named_scope(...)` call in the source under
    `src` (default: the program's package, `repro`)."""
    roots = [src] if src else \
        importlib.util.find_spec("repro").submodule_search_locations
    for root in roots:
        for path in sorted(Path(root).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_bytes())):
                if isinstance(node, ast.Call) and node.args:
                    fn = node.func
                    name = fn.attr if isinstance(fn, ast.Attribute) \
                        else getattr(fn, "id", "")
                    if name == "named_scope":
                        yield path, node


def _literal(call):
    arg = call.args[0]
    return arg.value if isinstance(arg, ast.Constant) and \
        isinstance(arg.value, str) else None


@functools.cache
def program_scopes(src: str | None = None) -> frozenset:
    """Every name the program's source under `src` (default: the program's
    package, `repro`) gives `jax.named_scope` as a string literal."""
    return frozenset(name for _, call in _scope_calls(src)
                     if (name := _literal(call)) is not None)


def unread_scopes(src: str | None = None) -> list[str]:
    """`file:line` of each `named_scope` call under `src` whose name is not
    a string literal: `program_scopes` cannot read it, so its ops would be
    charged to the scope around it."""
    return [f"{path}:{call.lineno}" for path, call in _scope_calls(src)
            if _literal(call) is None]


def scope(path: str, names=None) -> str:
    """The innermost of the program's scope names (`names`, default
    `program_scopes()`) among the parts of a name path (a part may be
    wrapped by a transformation, as in `jvp(mlp)`), or "other"."""
    names = program_scopes() if names is None else names
    for part in reversed(path.split("/")):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in names:
            return part
    return "other"


# --------------------------------------------------------------------------
# protobuf fields read straight off the wire. XSpace.planes = 1; XPlane.name
# = 2, .event_metadata = 4 (map entries: key = 1, value = 2);
# XEventMetadata.id = 1, .stats = 5; XStat.bytes_value = 6. HloProto
# .hlo_module = 1; HloModuleProto.computations = 3; HloComputationProto
# .instructions = 2, .id = 5, .root_id = 6; HloInstructionProto.name = 1,
# .opcode = 2, .metadata = 7, .id = 35, .operand_ids = 36,
# .called_computation_ids = 38; OpMetadata.op_name = 2.
# --------------------------------------------------------------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: ints for
    varints, memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            size = 8 if kind == 1 else 4
            val, i = buf[i:i + size], i + size
        yield key >> 3, val


def _ints(val):
    """A repeated integer field's values: packed, or a single varint."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


def _text(val) -> str:
    return bytes(val).decode(errors="replace")


def module_paths(module) -> dict:
    """{instruction name: op_name} of one serialized HloModuleProto, every
    fusion resolved as the module docstring says."""
    comps = {}                        # id -> (root id, {id: instruction})
    for f, comp in _fields(memoryview(module)):
        if f != 3:
            continue
        cid = root = 0
        ins = {}
        for g, v in _fields(comp):
            if g == 2:
                d = {"ops": [], "calls": [], "name": "", "opcode": "",
                     "path": ""}
                for h, w in _fields(v):
                    if h == 1:
                        d["name"] = _text(w)
                    elif h == 2:
                        d["opcode"] = _text(w)
                    elif h == 7:
                        d["path"] = next((_text(x) for k, x in _fields(w)
                                          if k == 2), "")
                    elif h == 35:
                        d["id"] = w
                    elif h == 36:
                        d["ops"] += _ints(w)
                    elif h == 38:
                        d["calls"] += _ints(w)
                ins[d.get("id", 0)] = d
            elif g == 5:
                cid = v
            elif g == 6:
                root = v
        comps[cid] = (root, ins)

    def resolve(d):
        if d["path"] or d["opcode"] != "fusion" or not d["calls"]:
            return d["path"]
        root, ins = comps.get(d["calls"][0], (0, {}))
        todo, seen = deque([root]), set()
        while todo:
            k = todo.popleft()
            if k in seen or k not in ins:
                continue
            seen.add(k)
            got = resolve(ins[k])
            if got:
                return got
            todo.extend(ins[k]["ops"])
        return ""

    return {d["name"]: resolve(d) for _, ins in comps.values()
            for d in ins.values()}


def hlo_paths(raw: bytes) -> dict:
    """{program id: {instruction name: op_name}} from the HLO the profiler
    keeps on the `/host:metadata` plane of one serialized XSpace."""
    out = {}
    for k, plane_buf in _fields(memoryview(raw)):
        plane = list(_fields(plane_buf)) if k == 1 else []
        if not any(f == 2 and bytes(v) == b"/host:metadata"
                   for f, v in plane):
            continue
        for entry in (v for f, v in plane if f == 4):
            meta = dict(_fields(entry)).get(2, b"")
            fs = list(_fields(meta))
            pid = next((v for f, v in fs if f == 1), None)
            for f, st in fs:
                proto = dict(_fields(st)).get(6) if f == 5 else None
                module = dict(_fields(proto)).get(1) if proto else None
                if module is not None:
                    out.setdefault(pid, {}).update(module_paths(module))
    return out


def program_id(module_run: str):
    """The id in a program run's name, `jit__engine_step_impl(<id>)`."""
    head, _, tail = module_run.rpartition("(")
    return int(tail[:-1]) if head and tail[:-1].isdigit() else None


def load(logdir: str) -> trace_reduce.Trace:
    """bench/trace_reduce.py's Trace of the directory, each device op an
    `Op` with its path (empty where the trace keeps no HLO for it)."""
    tr = trace_reduce.load(logdir)
    hlo = {}
    for path in glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True):
        with open(path, "rb") as f:
            hlo.update(hlo_paths(f.read()))
    for d in tr.devices.values():
        ms = sorted(d["modules"], key=lambda m: m.start)
        starts = [m.start for m in ms]
        ops = []
        for o in d["ops"]:
            k = bisect.bisect_right(starts, o.start) - 1
            run = ms[k].name if k >= 0 and o.start < ms[k].end else ""
            name = o.name.partition(" = ")[0].lstrip("%")
            ops.append(Op(o.name, o.start, o.end, o.group,
                          hlo.get(program_id(run), {}).get(name, "")))
        d["ops"] = ops
    return tr


def reduce(tr: trace_reduce.Trace) -> dict:
    """{program name: {scope: device seconds}}: the ops of each program
    call that starts in the window (bench/trace_reduce.py `window`), each
    charged to `scope` of its path; clipped at the window's close and
    summed over chips, as trace_reduce.reduce counts `modules`. Loops and
    calls, which span the ops inside them, are left out."""
    win = trace_reduce.window(tr)
    out = defaultdict(lambda: defaultdict(float))
    if win is None:
        return {}
    lo, hi = win
    for d in tr.devices.values():
        ms = sorted(d["modules"], key=lambda m: m.start)
        starts = [m.start for m in ms]
        for o in d["ops"]:
            k = bisect.bisect_right(starts, o.start) - 1
            if k < 0 or o.start >= ms[k].end or not lo <= ms[k].start < hi \
                    or o.start >= hi or trace_reduce._container(o.name):
                continue
            prog = ms[k].name.split("(")[0]
            out[prog][scope(getattr(o, "path", ""))] += \
                min(o.end, hi) - o.start
    return {k: dict(v) for k, v in out.items()}


def ms_per_substep(ctx, name):
    """Device time under scope `name` in the decode-block program, per call
    and per decode sub-step (as decode_substep_ms.chat divides), from a run
    context whose trace reduction holds `scopes`; None where no op of that
    program carries the scope."""
    red = ctx.get("trace") or {}
    hit = trace_reduce.module_time(red, DECODE) if "modules" in red else None
    got = [v[name] for k, v in red.get("scopes", {}).items()
           if DECODE in k and name in v]
    if not hit or not hit[0] or not got:
        return None
    return sum(got) / hit[0] / ctx["decode_block"] * 1e3


def decode_ms(ctx):
    """[[scope, ms per decode sub-step], ...] of the decode-block program,
    longest first; None where the trace reduction holds no scope of it."""
    red = (ctx.get("trace") or {}).get("scopes", {})
    seen = {name for k, v in red.items() if DECODE in k for name in v}
    got = [[name, ms] for name in seen
           if (ms := ms_per_substep(ctx, name)) is not None]
    return sorted(got, key=lambda kv: -kv[1]) or None
