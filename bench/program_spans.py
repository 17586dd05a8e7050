"""The program's own spans and device scopes, read from a traced run.

``bench.trace`` keeps the benchmark's ``bench:`` spans, the jitted calls
and the device operations by name.  The program under test also marks
its layers: host spans named ``repro:<layer>`` whose arguments are
counts (``repro.utils.spans``), and device regions named with
``jax.named_scope`` (``im2col``, ``decode_attention``), which reach the
trace as each operation's op_name.  :func:`of` reads those once per run
from the profile the run just wrote, and logs where the window's
device-idle time went by innermost program span.  The other functions
reduce a :class:`Profile` to what the per-layer metrics read.

Times, spans and launch ids come from the ``.xplane.pb`` through
``jax.profiler.ProfileData``.  An operation's op_name is a stat (``tf_op``)
of the event's metadata, which ``ProfileData`` does not show, so
:func:`scopes` reads it from the same file's protobuf fields directly, by
program and operation name.  A host span is paired with the device
executions it launched by the profiler's own links (:func:`launched`),
never by order.

A tree whose program has no ``repro:`` spans (an older commit) gives no
profile, so every metric that reads one is left out of the result rather
than read as zero.  All times are seconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from bench.trace import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, SPAN_PREFIX,
                         WINDOW_SPAN, measure, merge, short_name, subtract)

PROGRAM_PREFIX = "repro:"
PJIT_PREFIX = "PjitFunction"
LAUNCH_ID = "run_id"          # the profiler's host-to-device launch id
FLOW_OUT, FLOW_IN = "_p", "_c"  # the profiler's links between host events
OP_NAME_STAT = "tf_op"        # an operation's op_name, on its metadata
PROGRAM_STAT = "program_id"  # the program an operation belongs to
PROGRAM_ID = re.compile(r"\((\d+)\)$")  # "jit_f(<program id>)"
CONTAINERS = ("while", "conditional", "call")
PREFILL_PROGRAM = "prefill"   # the engine's jitted B=1 prefill
DECODE_PROGRAM = "_decode_fn"  # the engine's jitted decode step
CACHE_KEY = "program_profile"


@dataclass
class Span:
    start: float
    end: float
    name: str                      # without the ``repro:`` prefix
    args: Dict[str, float] = field(default_factory=dict)
    line: str = ""                 # the host thread that ran it


@dataclass
class Flow:
    """A host event that takes part in a launch: it starts a link to
    other events (``out``), is reached by one (``into``), or hands an
    execution to the device (``run``, the launch id)."""
    start: float
    end: float
    line: str
    out: Optional[int] = None
    into: Optional[int] = None
    run: Optional[int] = None


@dataclass
class Device:
    start: float
    end: float
    name: str
    scope: str = ""                # the operation's op_name
    launch: Optional[int] = None   # a program execution's launch id


@dataclass
class Profile:
    window: Tuple[float, float]
    spans: List[Span] = field(default_factory=list)     # repro:, by start
    host: List[Span] = field(default_factory=list)      # bench: and jit calls
    flows: Dict[str, List[Flow]] = field(default_factory=dict)  # by line
    ops: Dict[int, List[Device]] = field(default_factory=dict)
    modules: Dict[int, List[Device]] = field(default_factory=dict)
    _into: Optional[Dict[int, List[Flow]]] = field(default=None, repr=False)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def in_window(self, spans):
        """The spans that start inside the window."""
        a, b = self.window
        return [s for s in spans if a <= s.start < b]

    def clip(self, intervals) -> List[Tuple[float, float]]:
        a, b = self.window
        return [(max(o.start, a), min(o.end, b)) for o in intervals
                if o.end > a and o.start < b]


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------


def parse(path: str) -> Profile:
    """Read the program's spans, the benchmark's spans, launch ids, and
    every device operation and program execution from one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    prof = Profile(window=(0.0, 0.0))
    window = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                ops = line.name == OPS_LINE
                dest = (prof.ops if ops else prof.modules).setdefault(chip, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    d = Device(s, s + ev.duration_ns * 1e-9, ev.name)
                    if ops:
                        d.name = short_name(ev.name)
                    else:
                        d.launch = dict(ev.stats).get(LAUNCH_ID)
                    dest.append(d)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    sp = Span(s, s + ev.duration_ns * 1e-9, ev.name,
                              line=line.name)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        sp.name = ev.name[len(PROGRAM_PREFIX):]
                        sp.args = dict(ev.stats)
                        prof.spans.append(sp)
                        continue
                    if ev.name == WINDOW_SPAN:
                        window = (sp.start, sp.end)
                    elif ev.name.startswith((SPAN_PREFIX, PJIT_PREFIX)):
                        prof.host.append(sp)
                    st = dict(ev.stats)
                    fl = Flow(sp.start, sp.end, line.name, st.get(FLOW_OUT),
                              st.get(FLOW_IN), st.get(LAUNCH_ID))
                    if (fl.out, fl.into, fl.run) != (None, None, None):
                        prof.flows.setdefault(line.name, []).append(fl)
    if window is not None:
        prof.window = window
    for lst in (prof.spans, prof.host, *prof.flows.values()):
        lst.sort(key=lambda s: (s.start, -s.end))
    for dev in (prof.ops, prof.modules):
        for lst in dev.values():
            lst.sort(key=lambda o: o.start)
    return prof


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes of a length-delimited field, raw bytes otherwise."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str) -> Dict[Tuple[int, int, str], str]:
    """{(chip, program id, operation): op_name} from the event metadata
    of an ``.xplane.pb``'s device planes (XSpace.planes = 1; XPlane.name =
    2, event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, uint64 = 3, int64 = 4, str = 5,
    ref = 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_name = {}
        for k, v in fields:
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_name[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            op_name = program = None
            ev_name, stats = "", []
            for key, w in _fields(dict(_fields(v)).get(2, b"")):
                if key == 2:
                    ev_name = bytes(w).decode()
                elif key == 5:
                    stats.append(dict(_fields(w)))
            for st in stats:
                what = stat_name.get(st.get(1))
                if what == OP_NAME_STAT:
                    op_name = bytes(st[5]).decode() if 5 in st \
                        else stat_name.get(st.get(7))
                elif what == PROGRAM_STAT:
                    program = st.get(3, st.get(4))
            if op_name:
                out[(int(m.group(1)), program, short_name(ev_name))] = op_name
    return out


def scopes(prof: Profile, path: str) -> int:
    """Give each device operation its op_name from the ``.xplane.pb`` at
    ``path``: its program is the execution it ran in (one chip runs one
    program at a time).  Returns how many operations got one."""
    named = op_names(path)
    n = 0
    for chip, ops in prof.ops.items():
        runs = sorted(prof.modules.get(chip, []), key=lambda r: r.start)
        j = 0
        for o in ops:
            while j < len(runs) and runs[j].end <= o.start:
                j += 1
            program = None
            if j < len(runs) and runs[j].start <= o.start:
                m = PROGRAM_ID.search(runs[j].name)
                program = int(m.group(1)) if m else None
            o.scope = named.get((chip, program, o.name), "")
            n += bool(o.scope)
    return n


def newest_trace(root) -> Optional[str]:
    found = glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def of(run) -> Optional[Profile]:
    """The run's profile, parsed once and kept in ``run``: the newest
    ``.xplane.pb`` under ``bench_traces/``, accepted only if its window is
    the one ``run["trace"]`` read.  None where it holds no program span."""
    if CACHE_KEY in run:
        return run[CACHE_KEY]
    from bench.harness import ROOT, log
    prof = None
    path = newest_trace(ROOT / "bench_traces")
    want = run["trace"].window
    if path is not None and want is not None:
        prof = parse(path)
        if not (math.isclose(prof.window[0], want[0], abs_tol=1e-6)
                and math.isclose(prof.window[1], want[1], abs_tol=1e-6)):
            log(f"[program spans] {path}: its window {prof.window} is not "
                f"the run's {want}; not read")
            prof = None
        elif not prof.spans:
            prof = None
    if prof is not None:
        n = scopes(prof, path)
        log(f"[program spans] {len(prof.spans)} program spans; op_name of "
            f"{n} of {sum(map(len, prof.ops.values()))} device operations")
    run[CACHE_KEY] = prof
    if prof is not None:
        for line in report(prof):
            log(line)
    return prof


# ---------------------------------------------------------------------------
# interval helpers
# ---------------------------------------------------------------------------


def intersect(a_ivs, b_ivs) -> List[Tuple[float, float]]:
    """The union of ``a_ivs`` inside the union of ``b_ivs``."""
    a, b = merge(a_ivs), merge(b_ivs)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def idle_intervals(prof: Profile, chip: int) -> List[Tuple[float, float]]:
    """The window less the union of the chip's operations."""
    a, b = prof.window
    out, cur = [], a
    for s, e in merge(prof.clip(prof.ops.get(chip, []))):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < b:
        out.append((cur, b))
    return out


def by_innermost(intervals, spans) -> Dict[Optional[str], float]:
    """Seconds of ``intervals`` by the innermost span covering each
    moment (the latest opened of those open; spans of one thread nest),
    None where no span covers it."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    ivs = merge(intervals)
    pts = sorted({t for iv in ivs for t in iv}
                 | {t for s in spans for t in (s.start, s.end)})
    out: Dict[Optional[str], float] = {}
    active: List[Span] = []
    i = j = 0
    for p, q in zip(pts, pts[1:]):
        while j < len(spans) and spans[j].start <= p:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s.end > p]
        while i < len(ivs) and ivs[i][1] <= p:
            i += 1
        if i < len(ivs) and ivs[i][0] <= p:
            lab = active[-1].name if active else None
            out[lab] = out.get(lab, 0.0) + (q - p)
    return out


def _chips(prof: Profile) -> List[int]:
    return sorted(c for c, ops in prof.ops.items() if ops)


def in_scope(op: Device, scope: str) -> bool:
    """Whether the operation was traced under ``jax.named_scope(scope)``:
    a whole component of its op_name, also inside a transform's
    parentheses (``transpose(jvp(im2col))``)."""
    if op.name.split(".")[0] in CONTAINERS:
        return False
    return re.search(rf"(^|[/(]){re.escape(scope)}([)/]|$)",
                     op.scope) is not None


# ---------------------------------------------------------------------------
# reductions the metrics read
# ---------------------------------------------------------------------------


def idle_inside(prof: Profile, name: str) -> Optional[float]:
    """Device-idle seconds inside the union of the program's ``name``
    spans, in the window, averaged over the chips that ran anything."""
    spans = prof.clip(prof.named(name))
    chips = _chips(prof)
    if not spans or not chips:
        return None
    return sum(subtract(spans, prof.clip(prof.ops[c]))
               for c in chips) / len(chips)


def scope_share(prof: Profile, scope: str) -> Optional[float]:
    """Device seconds of operations under ``scope`` over device busy
    seconds, in the window, summed over chips."""
    num = den = 0.0
    for c in _chips(prof):
        num += measure(prof.clip(o for o in prof.ops[c] if in_scope(o, scope)))
        den += measure(prof.clip(prof.ops[c]))
    return num / den if num > 0 and den > 0 else None


def scope_share_in(prof: Profile, scope: str,
                   program: str) -> Optional[float]:
    """Device seconds of operations under ``scope`` inside the executions
    of programs named with ``program``, over those executions' device
    seconds, in the window, summed over chips."""
    num = den = 0.0
    for c in _chips(prof):
        runs = prof.clip(m for m in prof.modules.get(c, [])
                         if program in m.name)
        scoped = prof.clip(o for o in prof.ops[c] if in_scope(o, scope))
        num += measure(intersect(scoped, runs))
        den += measure(runs)
    return num / den if num > 0 and den > 0 else None


def launched(prof: Profile, span: Span) -> List[int]:
    """The launch ids of the device executions the host span launched.

    A jitted call made inside the span leaves a host event on the span's
    own thread that starts a link (``_p``); the profiler ends that link
    at the runtime's execute event (``_c``), which either holds the event
    that enqueues the program (it carries the launch id, ``run_id``) or
    starts further links to the worker thread that does.  Those links
    are followed to every launch id they reach."""
    if prof._into is None:
        prof._into = {}
        for fls in prof.flows.values():
            for f in fls:
                if f.into is not None:
                    prof._into.setdefault(f.into, []).append(f)
    out, seen = [], set()
    todo = [(span.line, span.start, span.end)]
    while todo:
        line, a, b = todo.pop()
        fls = prof.flows.get(line, [])
        k = bisect.bisect_left(fls, a, key=lambda f: f.start)
        for f in fls[k:]:
            if f.start >= b:
                break
            if f.end > b or id(f) in seen:
                continue
            seen.add(id(f))
            if f.run is not None:
                out.append(f.run)
            if f.out is not None:
                todo += [(g.line, g.start, g.end)
                         for g in prof._into.get(f.out, [])]
    return out


AFTER = "after"   # launched, but not run while the trace was recording


def link(prof: Profile, spans: List[Span],
         program: str) -> List[Union[Device, str, None]]:
    """For each host span, the execution on the first chip of the
    program named with ``program`` that it launched; :data:`AFTER` where
    it launched one that did not run while the trace was recording; None
    where the trace links no launch to it."""
    chips = sorted(prof.modules)
    by_id = {m.launch: m for m in prof.modules[chips[0]]
             if m.launch is not None} if chips else {}
    out: List[Union[Device, str, None]] = []
    for s in spans:
        runs = launched(prof, s)
        mine = [by_id[r] for r in runs
                if r in by_id and program in by_id[r].name]
        out.append(mine[0] if mine else AFTER if runs else None)
    return out


def first_token_ms(prof: Profile) -> Tuple[List[float], int, int]:
    """Per admission that opens in the window: the request's wait before
    admission plus the time from the admission's start to the end of its
    prefill on the device, in ms.  Also how many admissions were left out
    because their prefill ended after the window (or after the trace),
    and how many because the trace links no launch to their prefill."""
    admits = prof.in_window(prof.named("serve.admit"))
    prefills = prof.named("serve.prefill")
    execs = link(prof, prefills, PREFILL_PROGRAM)
    out, late, unlinked = [], 0, 0
    for a in admits:
        j = next((k for k, p in enumerate(prefills)
                  if a.start <= p.start < a.end), None)
        ex = None if j is None else execs[j]
        if ex is None:
            unlinked += 1
        elif ex is AFTER or ex.end > prof.window[1]:
            late += 1
        else:
            out.append(a.args.get("wait_us", 0.0) / 1e3
                       + (ex.end - a.start) * 1e3)
    return out, late, unlinked


def gate_hits(prof: Profile) -> Tuple[int, int]:
    """(hits, lookups) of the gate cache among the window's lookups."""
    g = prof.in_window(prof.named("serve.gate"))
    return sum(1 for s in g if s.args.get("hit")), len(g)


def report(prof: Profile) -> List[str]:
    """Lines for the run's log: the window's device-idle seconds on the
    first chip by innermost program span, how much of the idle time
    inside the benchmark's own spans the program's spans cover, the host
    copy rate of each span that counts its ``bytes``, and how many
    admissions the first-token reading kept."""
    chips = _chips(prof)
    if not chips:
        return ["[program spans] no device operations in the trace"]
    idle = idle_intervals(prof, chips[0])
    table = by_innermost(idle, prof.spans)
    lines = [f"[program spans] device-idle seconds by innermost program "
             f"span (chip {chips[0]}, window {prof.window_s:.6f}s, idle "
             f"{measure(idle):.6f}s):"]
    for lab, sec in sorted(table.items(), key=lambda kv: -kv[1]):
        lines.append(f"[program spans]   {lab or 'outside program spans'} "
                     f"{sec:.6f}")
    outer = [h for h in prof.host if h.name.startswith(SPAN_PREFIX)]
    inside = intersect(idle, prof.clip(outer))
    cov = by_innermost(inside, prof.spans)
    tot = sum(cov.values())
    if tot > 0:
        lines.append(f"[program spans] idle inside bench spans "
                     f"{tot:.6f}s, inside program spans "
                     f"{tot - cov.get(None, 0.0):.6f}s "
                     f"({100.0 * (1 - cov.get(None, 0.0) / tot):.2f}%)")
    for name in sorted({sp.name for sp in prof.spans if "bytes" in sp.args}):
        got = prof.in_window(prof.named(name))
        nbytes = sum(sp.args["bytes"] for sp in got)
        sec = sum(sp.end - sp.start for sp in got)
        if sec > 0:
            lines.append(f"[program spans] {name}: {nbytes / 1e9:.3f} GB "
                         f"in {sec:.6f} s of host time, "
                         f"{nbytes / 1e9 / sec:.3f} GB/s")
    ft, late, unlinked = first_token_ms(prof)
    if ft or late or unlinked:
        med = statistics.median(ft) if ft else math.nan
        lines.append(f"[program spans] first token: {len(ft)} admissions "
                     f"in the window, median {med:.3f} ms; left out: {late} "
                     f"whose prefill ended after the window, {unlinked} "
                     f"whose prefill the trace links to no launch")
    return lines
