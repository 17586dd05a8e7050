"""One-off checks on a kept profile of a traced run, printed, not metrics.

    python3 -m bench.trace_checks <run>.xplane.pb [--scope im2col]

- launch order: each ``repro:`` span opens before the jitted calls it
  holds, and before the host enqueues the executions it launched (one
  host clock); on the device clock, no execution starts before its span
  by more than the clocks' lag (:func:`lag`).
- decode backlog: the most decode steps dispatched and not yet started
  on the device, in the window.
- a device scope's share by fusion: the operations counted under the
  scope (an operation carries the op_name of its fusion's root), each
  with the share of its fusion's instructions that lie in the scope,
  read from the optimized HLO the profile keeps of every program.

Pairs of spans and executions come from ``program_spans.link``.
"""
from __future__ import annotations

import argparse
import bisect
import re
from typing import Dict, List, Optional, Tuple

from bench import program_spans as ps
from bench.trace import measure

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PLAIN = ("parameter", "constant", "bitcast", "tuple", "get-tuple-element")


def lag(prof: ps.Profile) -> float:
    """How far the device's timestamps run behind the host's: the most by
    which a program execution starts before the host event that enqueued
    it (the event carrying its launch id), or 0."""
    chips = sorted(prof.modules)
    if not chips:
        return 0.0
    start = {m.launch: m.start for m in prof.modules[chips[0]]
             if m.launch is not None}
    return max([0.0] + [f.start - start[f.run]
                        for fls in prof.flows.values() for f in fls
                        if f.run in start])


def launch_order(prof: ps.Profile) -> List[str]:
    """Lines: spans that open inside a jitted call they hold; and, per
    program the engine launches, the spans paired with an execution, of
    which the host enqueued it before the span opened, and of which it
    starts on the device before the span less the clocks' lag."""
    calls = [h for h in prof.host if h.name.startswith(ps.PJIT_PREFIX)]
    starts = [c.start for c in calls]
    n_call = bad_call = 0
    for s in prof.spans:
        lo = bisect.bisect_left(starts, s.start)
        hi = bisect.bisect_left(starts, s.end)
        # a call open when the span opens (calls nest, so look back a few)
        open_at = any(calls[k].end > s.start
                      for k in range(max(lo - 4, 0), lo))
        if hi > lo or open_at:
            n_call += 1
            bad_call += open_at
    skew = lag(prof)
    lines = [f"launch order: {bad_call} of {n_call} spans holding a jitted "
             f"call open after it; device clock behind the host's by up to "
             f"{skew * 1e3:.4f} ms"]
    enqueued = {f.run: f.start for fls in prof.flows.values() for f in fls
                if f.run is not None}
    for name, program in (("serve.prefill", ps.PREFILL_PROGRAM),
                          ("serve.decode", ps.DECODE_PROGRAM),
                          ("train.dispatch", "epoch")):
        spans = prof.named(name)
        if not spans:
            continue
        got = ps.link(prof, spans, program)
        pairs = [(s, ex) for s, ex in zip(spans, got)
                 if isinstance(ex, ps.Device)]
        early_host = sum(enqueued.get(ex.launch, s.start) < s.start
                         for s, ex in pairs)
        early_raw = [s.start - ex.start for s, ex in pairs
                     if ex.start < s.start]
        early = sum(ex.start + skew < s.start for s, ex in pairs)
        lines.append(
            f"  {name}: {len(spans)} spans, {len(pairs)} paired by launch "
            f"id, {got.count(ps.AFTER)} ran after the trace, "
            f"{got.count(None)} unlinked; enqueued before the span: "
            f"{early_host}; starting on the device before the span: "
            f"{len(early_raw)} as stamped (by up to "
            f"{max(early_raw, default=0.0) * 1e3:.4f} ms), {early} with "
            f"the lag allowed for")
    return lines


def decode_backlog(prof: ps.Profile) -> Optional[Tuple[int, int]]:
    """The most decode steps dispatched and not yet started on the
    device, over the window, counted at the end of each dispatch: with
    the device's start stamps as they are, and with the clocks' lag added
    to them.  Steps whose execution the trace does not hold count as not
    started."""
    steps = prof.named("serve.decode")
    if not steps:
        return None
    execs = ps.link(prof, steps, ps.DECODE_PROGRAM)
    skew = lag(prof)
    most = [0, 0]
    for i, s in enumerate(steps):
        if not prof.window[0] <= s.end < prof.window[1]:
            continue
        for k, add in enumerate((0.0, skew)):
            most[k] = max(most[k], sum(
                1 for e in execs[:i + 1]
                if not isinstance(e, ps.Device) or e.start + add > s.end))
    return most[0], most[1]


# ---------------------------------------------------------------------------
# a device scope by fusion
# ---------------------------------------------------------------------------


def hlo_texts(path: str) -> Dict[str, str]:
    """{program name: its optimized HLO text with op_name metadata} from
    the HLO protos in an ``.xplane.pb``'s metadata plane."""
    from jax._src.lib import xla_client
    with open(path, "rb") as f:
        space = memoryview(f.read())
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    out = {}
    for num, plane in ps._fields(space):
        if num != 1:
            continue
        fields = list(ps._fields(plane))
        if next((bytes(v).decode() for k, v in fields if k == 2),
                "") != METADATA_PLANE:
            continue
        stat_name = {}
        for k, v in fields:
            if k == 5:
                entry = dict(ps._fields(v))
                meta = dict(ps._fields(entry.get(2, b"")))
                stat_name[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            md = list(ps._fields(dict(ps._fields(v)).get(2, b"")))
            name = next((bytes(w).decode() for kk, w in md if kk == 2), "")
            for kk, w in md:
                st = dict(ps._fields(w)) if kk == 5 else {}
                if stat_name.get(st.get(1)) == HLO_STAT and 6 in st:
                    module = dict(ps._fields(bytes(st[6])))[1]
                    comp = xla_client._xla.XlaComputation(bytes(module))
                    out[name] = comp.get_hlo_module().to_string(opts)
    return out


INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
CALLS = re.compile(r"calls=%?([\w.\-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def fusion_shares(text: str, scope: str) -> Dict[str, float]:
    """{instruction: share of its fused computation's instructions traced
    under ``scope``} for every fusion in one program's HLO text, counting
    only instructions that carry an op_name (XLA makes some without one)
    and leaving out parameters, constants, bitcasts and tuples."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    calls: Dict[str, str] = {}
    cur = None
    for ln in text.splitlines():
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{"):
            cur = ln.split()[1 if ln.startswith("ENTRY") else 0].lstrip("%")
            comps[cur] = []
            continue
        m = INSTR.match(ln)
        if m is None or cur is None:
            continue
        name, opcode = m.groups()
        on = OP_NAME.search(ln)
        comps[cur].append((opcode, on.group(1) if on else ""))
        c = CALLS.search(ln)
        if opcode == "fusion" and c:
            calls[name] = c.group(1)
    out = {}
    for name, comp in calls.items():
        body = [on for op, on in comps.get(comp, [])
                if op not in PLAIN and on]
        if body:
            out[name] = sum(ps.in_scope(ps.Device(0, 0, "x", on), scope)
                            for on in body) / len(body)
    return out


def scope_by_fusion(prof: ps.Profile, path: str, scope: str,
                    top: int = 12) -> List[str]:
    """Lines: device seconds in the window of operations counted under
    ``scope`` (their root's op_name) and of those not counted whose fusion
    holds some of it, by the share of the fusion's body in the scope; and
    the largest counted operations."""
    texts = hlo_texts(path)
    shares = {name: fusion_shares(t, scope) for name, t in texts.items()}
    chip = min(c for c, ops in prof.ops.items() if ops)
    runs = sorted(prof.modules.get(chip, []), key=lambda r: r.start)
    starts = [r.start for r in runs]
    rows: Dict[Tuple[str, str], List] = {}
    for o in prof.ops[chip]:
        k = bisect.bisect_right(starts, o.start) - 1
        prog = runs[k].name if k >= 0 and o.start < runs[k].end else ""
        share = shares.get(prog, {}).get(o.name)
        counted = ps.in_scope(o, scope)
        if not counted and not share:
            continue
        row = rows.setdefault((prog, o.name), [[], o.scope, share, counted])
        row[0].append(o)
    a, b = prof.window
    busy = measure(prof.clip(prof.ops[chip]))
    bins = {}
    for (_, _), (ops, _, share, counted) in rows.items():
        sec = measure(prof.clip(ops))
        kind = ("not a fusion" if share is None else "body all in scope"
                if share == 1 else "body half or more in scope"
                if share >= 0.5 else "body under half in scope")
        key = ("counted" if counted else "not counted", kind)
        bins[key] = bins.get(key, 0.0) + sec
    lines = [f"scope {scope} (chip {chip}, busy {busy:.6f} s in the window "
             f"{b - a:.6f} s; {len(texts)} programs' HLO in the profile):"]
    for (c, kind), sec in sorted(bins.items()):
        lines.append(f"  {c}, {kind}: {sec:.6f} s ({100 * sec / busy:.2f}% "
                     f"of busy)")
    ranked = sorted(((measure(prof.clip(ops)), name, prog, root, share)
                     for (prog, name), (ops, root, share, counted)
                     in rows.items() if counted), reverse=True)
    for sec, name, prog, root, share in ranked[:top]:
        body = "-" if share is None else f"{100 * share:.0f}%"
        lines.append(f"  {sec:.6f} s  {name} in {prog}: body in scope "
                     f"{body}; root {root}")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--scope", action="append", default=[])
    a = ap.parse_args(argv)
    prof = ps.parse(a.xplane)
    ps.scopes(prof, a.xplane)
    print(f"window {prof.window_s:.6f} s, {len(prof.spans)} program spans")
    for ln in launch_order(prof):
        print(ln)
    backlog = decode_backlog(prof)
    if backlog is not None:
        print(f"decode steps dispatched and not yet started, at most: "
              f"{backlog[0]} as stamped, {backlog[1]} with the lag added")
    for scope in a.scope:
        for ln in scope_by_fusion(prof, a.xplane, scope):
            print(ln)


if __name__ == "__main__":
    main()
