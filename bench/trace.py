"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the profiler's ``.xplane.pb`` into a plain record:

    {"devices": {plane: [[op, start_ns, dur_ns, path, opcode, module], ...]},
     "host": [[span, start_ns, dur_ns], ...]}

where ``module`` is the XLA program the op ran in (``jit_f``, from the
device's module line) and ``path`` the JAX op-name path of the HLO op
(``jit(f)/.../op``), taken from the event's own stats or, failing that,
from the compiled programs' HLO text: an op's instruction name and output
shape pick its line in the text of its module.  Everything below works on that record, so it is tested
on a small recorded chip trace and on hand-made event lists.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
PATH_STATS = ("tf_op", "long_name", "op_name")
CONTROL_FLOW = ("while", "conditional", "call")
NO_OP_NAME = "<no op_name>"
#: Op-name paths, or programs, of the crossbar fabric's jitted pieces:
#: ``Fabric._plan_impl``, ``_dispatch_impl``, ``_combine_impl`` and their
#: cached, transfer and address twins.
FABRIC = r"_(plan|dispatch|combine|transfer|addrs)(_cached)?_impl"
#: A scatter the TPU compiler left without an op name.  In a prefill step
#: with experts it is the MoE dispatch's packet scatter, the step's only
#: scatter; a driver says so by ``fabric_scatter`` in its output.
UNNAMED_SCATTER = r"^<no op_name>/scatter$"
FABRIC_PATHS = {"fabric": FABRIC, "unnamed_scatter": UNNAMED_SCATTER}
_OP_NAME = re.compile(r"%?([\w.\-]+)")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=")
_META = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def opcode(instruction: str):
    """The opcode of an HLO instruction's text (``%x = shape op(...)``),
    or None when the text is not an instruction."""
    if "=" not in instruction:
        return None
    rhs = instruction.split("=", 1)[1].lstrip()
    i = rhs.find(" ")
    if rhs.startswith("("):                  # tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    m = re.match(r"\s*([\w\-]+)\(", rhs[i:] if i >= 0 else "")
    return m.group(1) if m else None


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event, whose name may be the
    whole instruction text (``%fusion.12 = bf16[...] fusion(...)``)."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def _prefix(instruction: str, op) -> str:
    """``%name = shape opcode(`` of an instruction's text: what a device
    event's name starts with."""
    text = instruction.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    i = text.find(f" {op}(", text.find("="))
    return text[:i + len(op) + 2] if op and i >= 0 else text


def hlo_op_paths(hlo_text: str, prefixes: dict = None) -> Dict[str, str]:
    """HLO instruction name -> op-name path, from compiled HLO text.  An
    instruction without metadata (a fusion, say) takes the path of the
    computation it calls: that of its root, following nested fusions down.
    Where the compiler left the root without an op name, the path is
    ``NO_OP_NAME/<root opcode>``.  ``prefixes``, when given, is filled
    with each instruction's ``_prefix``."""
    meta, callee, root, ops = {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMP.match(line)
        if c and "=" not in line.split("{")[0]:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        ops[name] = opcode(line)
        if prefixes is not None:
            prefixes[name] = _prefix(line, ops[name])
        if m.group(1) and comp:
            root[comp] = name
        found, calls = _META.search(line), _CALLS.search(line)
        if found:
            meta[name] = found.group(1)
        if calls:
            callee[name] = calls.group(1)

    def resolve(name, depth=0):
        if name in meta:
            return meta[name]
        comp = callee.get(name)
        if comp in root and depth < 16:
            return resolve(root[comp], depth + 1)
        return f"{NO_OP_NAME}/{ops.get(name)}"

    return {n: resolve(n) for n in ops}


def hlo_index(hlo_texts: Iterable[str]) -> Dict[Tuple[str, str], list]:
    """(module, instruction name) -> [(prefix, path)] over several
    compiled programs; programs of one name (one function at several
    shapes) share a key and are told apart by the prefix."""
    index: Dict[Tuple[str, str], list] = {}
    for text in hlo_texts:
        m = _MODULE.match(text)
        module = m.group(1) if m else ""
        prefixes: Dict[str, str] = {}
        for name, path in hlo_op_paths(text, prefixes).items():
            index.setdefault((module, name), []).append(
                (prefixes[name], path))
    return index


def lookup(index: dict, module: str, event_name: str) -> str:
    """The op-name path of a device event of ``module``, or ""."""
    found = index.get((module, op_name(event_name)), [])
    for prefix, path in found:
        if event_name.startswith(prefix):
            return path
    return found[0][1] if found else ""


def module_of(event_name: str) -> str:
    """``jit_f`` of a module event named ``jit_f(<program id>)``."""
    return event_name.split("(", 1)[0]


def load(xplane_path: str, hlo_texts: Iterable[str] = (),
         host_prefix: str = HOST_PREFIX) -> dict:
    from jax.profiler import ProfileData

    index = hlo_index(hlo_texts)
    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                              module_of(ev.name))
                             for ev in lines.get(MODULES_LINE, []))
            ops = []
            for ev in lines.get(OPS_LINE, []):
                start = int(ev.start_ns)
                module = _containing(modules, start)
                stats = dict(ev.stats)
                path = next((str(stats[k]) for k in PATH_STATS
                             if stats.get(k)), "") \
                    or lookup(index, module, ev.name)
                ops.append([op_name(ev.name), start, int(ev.duration_ns),
                            path, opcode(ev.name) or "", module])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _containing(modules: Sequence, t: int) -> str:
    """The module whose [start, end) interval holds ``t``, or ""."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else ""


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def window(record: dict) -> Tuple[int, int]:
    """The traced window: from the first to the end of the last host span
    the benchmark recorded around its steps."""
    spans = record["host"]
    if not spans:
        raise ValueError("no benchmark spans in the trace")
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def busy_ns(ops: Sequence, lo: int, hi: int) -> int:
    return sum(e - s for s, e in
               union(clip([(o[1], o[1] + o[2]) for o in ops], lo, hi)))


def path_ns(ops: Sequence, pattern: str, lo: int, hi: int) -> int:
    """Device time of the ops whose op-name path, or module, matches
    ``pattern``."""
    rx = re.compile(pattern)
    return sum(e - s for s, e in
               union(clip([(o[1], o[1] + o[2]) for o in ops
                           if rx.search(o[3]) or rx.search(o[5])],
                          lo, hi)))


def _label(op) -> str:
    name, path, module = op[0], op[3], op[5]
    tail = "/".join(p for p in path.split("/")[-3:] if p)
    name = f"{module}:{name}" if module else name
    return f"{name} [{tail}]" if tail else name


def breakdown(record: dict, lo: int, hi: int, top: int = 10) -> dict:
    """The device ops that took most time, summed over the window and
    averaged over devices (control flow, whose time is its body's, left
    out), and the longest idle gaps on the first device, each named by the
    host span it fell in."""
    per_op: Dict[str, float] = {}
    n_dev = max(1, len(record["devices"]))
    for ops in record["devices"].values():
        for op in ops:
            if op[4] in CONTROL_FLOW:
                continue
            s, e = max(op[1], lo), min(op[1] + op[2], hi)
            if e > s:
                per_op[_label(op)] = per_op.get(_label(op), 0.0) \
                    + (e - s) / 1e9 / n_dev
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if record["devices"]:
        ops = next(iter(record["devices"].values()))
        busy = union(clip([(o[1], o[1] + o[2]) for o in ops], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((host_label(record["host"], (s + e) // 2),
                             (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def host_label(spans: Sequence, t: int) -> str:
    """The innermost benchmark span that covers ``t``, or "outside"."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside"


def summarize(record: dict, paths: Dict[str, str]) -> dict:
    """Busy, window and per-path device seconds, averaged over devices."""
    lo, hi = window(record)
    devs = list(record["devices"].values())
    n = max(1, len(devs))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns(ops, lo, hi) for ops in devs) / n / 1e9,
        "devices": len(devs),
        "path_s": {k: sum(path_ns(ops, p, lo, hi) for ops in devs) / n / 1e9
                   for k, p in paths.items()},
        "breakdown": breakdown(record, lo, hi),
    }


def fabric_s(ctx: dict) -> float:
    """Device seconds of the fabric's ops in a traced run's summary
    (``ctx["trace"]``, reduced with ``FABRIC_PATHS``)."""
    path_s = ctx["trace"]["path_s"]
    extra = path_s.get("unnamed_scatter", 0.0) if ctx.get("fabric_scatter") \
        else 0.0
    return path_s.get("fabric", 0.0) + extra
