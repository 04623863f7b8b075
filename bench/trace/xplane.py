"""Reduce a JAX profiler trace (``.xplane.pb``) to device-time numbers.

What it gives, over the traced segment (the span of the harness's
``bench.segment`` host annotation):

* busy seconds: the union of the intervals in which an operation ran on
  the device (container ops such as ``while`` count: the loop runs on the
  device), averaged over the devices traced; the idle share is the rest;
* device time under a named scope (``jax.named_scope``): the summed
  durations of the leaf ops whose HLO ``op_name`` metadata holds that
  scope as a path component;
* kernel time: the summed durations of the ops whose HLO instruction name
  starts with the kernel's jitted entry name (``_rglru_scan``,
  ``vmap_jit__scale_noise_``);
* the top device ops by self time, labelled by their scope and primitive;
* the longest idle gaps, each labelled with the host span and the Python
  frame that were open in the middle of it.

The trace's event lines are read with ``jax.profiler.ProfileData``; the HLO
of each program, which carries the ``op_name`` metadata, is read from the
trace's ``/host:metadata`` plane with a minimal protobuf reader (field
numbers of ``XSpace``/``XPlane``/``XEventMetadata``/``XStat`` and
``HloProto``/``HloModuleProto``/``HloComputationProto``/
``HloInstructionProto``/``OpMetadata``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SEGMENT = "bench.segment"
CONTAINERS = {"while", "conditional", "call"}


# ---------------------------------------------------------------------------
# Protobuf wire format, just enough to walk the HLO in the trace
# ---------------------------------------------------------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """Yield (field number, wire type, value); a length-delimited value is
    the (start, end) of its bytes."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, val


def _str(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _instructions(buf: bytes, span) -> Dict[str, Tuple[str, str]]:
    """HloProto bytes -> {instruction name: (opcode, op_name)}."""
    out = {}
    for num, _, mod in _fields(buf, *span):
        if num != 1:                                    # hlo_module
            continue
        for n2, _, comp in _fields(buf, *mod):
            if n2 != 3:                                 # computations
                continue
            for n3, _, ins in _fields(buf, *comp):
                if n3 != 2:                             # instructions
                    continue
                name = opcode = op_name = ""
                for n4, _, v in _fields(buf, *ins):
                    if n4 == 1:
                        name = _str(buf, v)
                    elif n4 == 2:
                        opcode = _str(buf, v)
                    elif n4 == 7:                       # metadata
                        for n5, _, w in _fields(buf, *v):
                            if n5 == 2:
                                op_name = _str(buf, w)
                out[name] = (opcode, op_name)
    return out


def hlo_by_program(path: str) -> Dict[int, Dict[str, Tuple[str, str]]]:
    """{program id: {instruction: (opcode, op_name)}} from the trace's
    ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[int, Dict[str, Tuple[str, str]]] = {}
    for num, _, plane in _fields(buf):
        if num != 1:
            continue
        name, stat_names, metas = "", {}, []
        for n2, _, v in _fields(buf, *plane):
            if n2 == 2:
                name = _str(buf, v)
            elif n2 == 4:
                metas.append(v)
            elif n2 == 5:                               # stat_metadata entry
                sid, sname = None, ""
                for n3, _, w in _fields(buf, *v):
                    if n3 == 2:
                        for n4, _, x in _fields(buf, *w):
                            if n4 == 1:
                                sid = x
                            elif n4 == 2:
                                sname = _str(buf, x)
                stat_names[sid] = sname
        if name != "/host:metadata":
            continue
        for entry in metas:
            for n3, _, em in _fields(buf, *entry):
                if n3 != 2:
                    continue
                pid, protos = None, []
                for n4, _, w in _fields(buf, *em):
                    if n4 == 1:
                        pid = w
                    elif n4 == 5:                       # stats
                        sid, val = None, None
                        for n5, _, x in _fields(buf, *w):
                            if n5 == 1:
                                sid = x
                            elif n5 == 6:
                                val = x
                        if val is not None and stat_names.get(sid) == "Hlo Proto":
                            protos.append(val)
                for p in protos:
                    out.setdefault(pid, {}).update(_instructions(buf, p))
    return out


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------


@dataclass
class Op:
    name: str          # HLO instruction name
    start: float       # ns, profile-relative
    end: float
    opcode: str
    op_name: str       # HLO metadata op_name (scope path), "" if unknown
    device: int

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def scopes(self) -> List[str]:
        return self.op_name.split("/")


_INSTR = re.compile(r"^%?([^\s=]+)")
_PROGRAM = re.compile(r"\((\d+)\)\s*$")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def label(op: Op) -> str:
    """Scope-and-primitive label of a device op: the op_name path without
    the transformation wrappers (``jit(..)``, ``vmap(..)``, ``while`` bodies),
    last two components, else the opcode."""
    parts = [p for p in op.scopes
             if p and "(" not in p and p not in ("body", "cond", "while",
                                                 "branch", "closed_call")]
    if not parts:
        return op.opcode or op.name.split(".")[0]
    return "/".join(parts[-2:])


@dataclass
class Reduction:
    ops: List[Op]
    t0: float
    t1: float
    n_devices: int
    host: List[Tuple[float, float, str]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy_intervals(self, device: int):
        return _union([(max(o.start, self.t0), min(o.end, self.t1))
                       for o in self.ops
                       if o.device == device and o.end > self.t0
                       and o.start < self.t1])

    @property
    def busy_s(self) -> float:
        tot = sum(e - s for d in range(self.n_devices)
                  for s, e in self._busy_intervals(d))
        return tot / max(self.n_devices, 1) * 1e-9

    def leaf_ops(self) -> List[Op]:
        return [o for o in self.ops if o.opcode not in CONTAINERS]

    def scope_s(self, scope: str) -> Optional[float]:
        """Device seconds of leaf ops under the named scope, None if no op
        carries it."""
        hits = [o.dur for o in self.leaf_ops() if scope in o.scopes]
        return sum(hits) * 1e-9 if hits else None

    def kernel_s(self, prefix: str) -> Optional[float]:
        hits = [o.dur for o in self.ops if o.name.startswith(prefix)]
        return sum(hits) * 1e-9 if hits else None

    def kernel_calls(self, prefix: str) -> int:
        return sum(1 for o in self.ops if o.name.startswith(prefix))

    def top_ops(self, n: int = 10) -> List[list]:
        agg: Dict[str, float] = {}
        for o in self.leaf_ops():
            agg[label(o)] = agg.get(label(o), 0.0) + o.dur * 1e-9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def host_at(self, t: float) -> str:
        spans = [(s, e, nm) for s, e, nm in self.host if s <= t <= e]
        ann = [x for x in spans if not x[2].startswith("$")]
        frames = [x for x in spans if x[2].startswith("$")]
        inner = lambda xs: max(xs, key=lambda x: x[0])[2] if xs else ""
        a, f = inner(ann), inner(frames).lstrip("$")
        return f"{a}|{f}" if a or f else "no host span"

    def gaps(self, device: int = 0) -> List[Tuple[float, float]]:
        busy = self._busy_intervals(device)
        out, t = [], self.t0
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.t1:
            out.append((t, self.t1))
        return out

    def top_gaps(self, n: int = 10) -> List[list]:
        gs = sorted(self.gaps(0), key=lambda g: g[0] - g[1])[:n]
        return [[self.host_at((s + e) / 2), (e - s) * 1e-9] for s, e in gs]


def reduce_file(path: str, n_devices: int = 1) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    hlo = hlo_by_program(path)
    ops: List[Op] = []
    host: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = int(plane.name.rsplit(":", 1)[1])
            if dev >= n_devices:
                continue
            lines = {l.name: l for l in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in (lines["XLA Modules"].events
                          if "XLA Modules" in lines else []))
            events = sorted(((e.start_ns, e.duration_ns, e.name)
                             for e in (lines["XLA Ops"].events
                                       if "XLA Ops" in lines else [])))
            mi = 0
            for s, d, nm in events:
                while mi + 1 < len(modules) and modules[mi][1] < s:
                    mi += 1
                pid = None
                if modules and modules[mi][0] <= s <= modules[mi][1]:
                    m = _PROGRAM.search(modules[mi][2])
                    pid = int(m.group(1)) if m else None
                inst = _INSTR.match(nm).group(1)
                opcode, op_name = hlo.get(pid, {}).get(inst, ("", ""))
                ops.append(Op(inst, s, s + d, opcode, op_name, dev))
        elif plane.name == "/host:CPU":
            for l in plane.lines:
                for e in l.events:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
    seg = [h for h in host if h[2] == SEGMENT]
    if seg:
        t0, t1 = seg[0][0], seg[0][1]
    elif ops:
        t0, t1 = min(o.start for o in ops), max(o.end for o in ops)
    else:
        t0 = t1 = 0.0
    return Reduction(ops=ops, t0=t0, t1=t1, n_devices=n_devices, host=host)


def reduce_dir(logdir: str, n_devices: int = 1) -> Reduction:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce_file(files[-1], n_devices=n_devices)
