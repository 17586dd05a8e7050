"""The readers of the program's own spans and device scopes, and the
one-off trace checks, on small profiles whose answers are known by hand,
and on real (CPU) traces for the parse and for a tree whose program
writes no spans."""
import glob

import jax
import pytest

from bench import program_spans as ps
from bench import trace
from bench import trace_checks as checks
from bench.program_spans import Device, Flow, Profile, Span
from bench.run import load_reader


def _run(prof, window_s=None):
    """What a reader gets, with the parse already cached."""
    return {ps.CACHE_KEY: prof, "window_s": window_s or prof.window_s}


def test_idle_inside_overlapping_and_window_cut_feed_spans():
    prof = Profile(window=(0.0, 10.0))
    prof.ops[0] = [Device(1.0, 3.0, "fusion.1"), Device(6.0, 7.0, "fusion.2")]
    prof.ops[1] = [Device(0.0, 10.0, "fusion.1")]
    prof.spans = [Span(-1.0, 2.0, "train.feed"), Span(1.5, 4.0, "train.feed"),
                  Span(5.0, 7.5, "train.dispatch"),
                  Span(8.0, 12.0, "train.feed")]
    # feed union in the window: [0,4] and [8,10]; chip 0 idle there
    # [0,1] + [3,4] + [8,10] = 4 s, chip 1 none: 2 s averaged
    assert ps.idle_inside(prof, "train.feed") == pytest.approx(2.0)
    assert load_reader("feed_idle_share.train")(_run(prof)) == \
        pytest.approx(20.0)
    assert ps.idle_inside(prof, "train.sync") is None


IM2COL = "jit(epoch_fn)/while/body/vmap(jvp(im2col))/concatenate"


def test_scope_shares():
    prof = Profile(window=(0.0, 10.0))
    prof.ops[0] = [
        Device(0.0, 8.0, "while.3", IM2COL.rsplit("/", 1)[0]),  # a loop
        Device(1.0, 2.0, "concatenate.11", IM2COL),
        Device(2.0, 3.0, "slice.4", "transpose(jvp(im2col))/slice"),
        Device(3.0, 4.0, "fusion.9", "jit(f)/im2colx/mul"),   # not it
        Device(9.5, 11.0, "pad.55", "jvp(im2col)/pad")]        # window-cut
    # busy [0,8] + [9.5,10] = 8.5; im2col 1 + 1 + 0.5 = 2.5
    assert ps.scope_share(prof, "im2col") == pytest.approx(2.5 / 8.5)
    assert load_reader("im2col_share.train")(_run(prof)) == \
        pytest.approx(100 * 2.5 / 8.5)
    assert ps.scope_share(prof, "decode_attention") is None

    att = "jit(_decode_fn)/while/body/decode_attention/dot_general"
    prof = Profile(window=(0.0, 10.0))
    prof.modules[0] = [Device(1.0, 4.0, "jit__decode_fn(1)"),
                       Device(5.0, 6.0, "jit_prefill(2)"),
                       Device(9.0, 12.0, "jit__decode_fn(1)")]
    prof.ops[0] = [Device(1.5, 2.5, "fusion.1", att),
                   Device(2.5, 3.0, "fusion.2", "jit(_decode_fn)/dot"),
                   Device(5.2, 5.5, "fusion.1", att),      # in the prefill
                   Device(9.5, 10.5, "fusion.1", att)]
    # decode runs [1,4] + [9,10] = 4 s; attention inside 1 + 0.5
    assert ps.scope_share_in(prof, "decode_attention", "_decode_fn") == \
        pytest.approx(1.5 / 4.0)
    assert load_reader("decode_attention_share.serve")(_run(prof)) == \
        pytest.approx(100 * 1.5 / 4.0)


def _launch(prof, span, run, tag, worker=False):
    """The profiler's links for one jitted call made inside ``span``: an
    event on the span's thread starts a link to the runtime's execute
    event, which enqueues the program itself or links on to a worker
    thread that does (the enqueue event carries the launch id)."""
    a = span.start + 0.2 * (span.end - span.start)
    d = 0.1 * (span.end - span.start)
    flows = prof.flows
    flows.setdefault(span.line, []).append(Flow(a, a + d, span.line, out=tag))
    flows.setdefault("main", []).append(
        Flow(a + d, a + 3 * d, "main", into=tag))
    if worker:
        flows["main"].append(Flow(a + d, a + 2 * d, "main", out=tag + 1))
        flows.setdefault("worker", []).append(
            Flow(a + 5 * d, a + 6 * d, "worker", into=tag + 1, run=run))
    else:
        flows["main"].append(Flow(a + d, a + 2 * d, "main", run=run))
    for fls in flows.values():
        fls.sort(key=lambda f: (f.start, -f.end))


def _admissions(worker=False):
    """Window [10, 20]; one prefill per admission, each launching a
    conversion and then the prefill; one execution left over from a
    dispatch made before the trace."""
    prof = Profile(window=(10.0, 20.0))
    prof.ops[0] = [Device(0.0, 30.0, "fusion.1")]
    admits = [(8.0, 9.0, 0), (12.0, 12.1, 5000), (15.0, 15.1, 0),
              (16.0, 16.1, 0), (19.5, 19.6, 0), (19.8, 19.9, 0)]
    execs = [(0.0, 1.0), (9.5, 10.5), (12.2, 12.3), (15.05, 15.1),
             (16.05, 16.1), (19.7, 20.5)]   # the last admission's never ran
    prof.modules[0] = [Device(0.0, 1.0, "jit_prefill(7)", launch=99)]
    for k, (a, b, wait) in enumerate(admits):
        adm = Span(a, b, "serve.admit", {"wait_us": wait}, "python")
        pre = Span(a + 0.01, a + 0.02, "serve.prefill", line="python")
        prof.spans += [adm, pre]
        if a == 16.0:          # a prefill the trace links to no launch
            continue
        _launch(prof, pre, 500 + k, 10 * k, worker)
        _launch(prof, Span(pre.start + 0.005, pre.end, "", line="python"),
                100 + k, 10 * k + 5, worker)
        prof.modules[0].append(Device(a + 0.011, a + 0.012,
                                      "jit_convert_element_type(9)",
                                      launch=500 + k))
        if a != 19.8:
            prof.modules[0].append(Device(*execs[k + 1], "jit_prefill(7)",
                                          launch=100 + k))
    prof.modules[0].append(Device(13.0, 14.0, "jit__decode_fn(3)"))
    prof.modules[0].sort(key=lambda m: m.start)
    prof.spans.sort(key=lambda s: (s.start, -s.end))
    return prof


@pytest.mark.parametrize("worker", [False, True],
                         ids=["enqueued_by_execute", "enqueued_by_worker"])
def test_first_token_linking_at_the_window_edges(worker):
    prof = _admissions(worker)
    ms, late, unlinked = ps.first_token_ms(prof)
    # the admission at 8 ran before the window opened and is not one of
    # the window's; 19.5 ends after the window, 19.8 never ran in it;
    # 16.0's prefill is linked to no launch, and not paired by order
    assert ms == pytest.approx([5.0 + 300.0, 100.0])
    assert (late, unlinked) == (2, 1)
    assert load_reader("first_token_p50_ms.serve")(_run(prof)) == \
        pytest.approx((305.0 + 100.0) / 2)


def test_link_by_id_ignores_an_execution_order_would_take():
    prof = _admissions()
    # an execution of an earlier dispatch, queued until after the first
    # traced prefill span opened: order alone would pair it with that span
    prof.modules[0].insert(1, Device(8.5, 8.9, "jit_prefill(7)", launch=98))
    got = ps.link(prof, prof.named("serve.prefill"), "prefill")
    assert [m.launch for m in got[:3]] == [100, 101, 102]
    assert got[3] is None and got[5] is ps.AFTER
    # without the profiler's links nothing is paired, whatever the order
    prof.flows = {}
    prof._into = None
    got = ps.link(prof, prof.named("serve.prefill"), "prefill")
    assert got == [None] * 6


def test_gate_ratio():
    prof = Profile(window=(0.0, 10.0))
    prof.spans = [Span(t, t + 0.1, "serve.gate", {"hit": h})
                  for t, h in ((-1.0, 0), (1.0, 1), (2.0, 0), (3.0, 1),
                               (4.0, 1), (10.5, 0))]
    assert ps.gate_hits(prof) == (3, 4)
    assert load_reader("gate_hit_ratio.serve")(_run(prof)) == \
        pytest.approx(75.0)


def test_idle_by_innermost_span_and_decode_backlog():
    spans = [Span(0.0, 10.0, "train"), Span(1.0, 4.0, "train.feed"),
             Span(3.0, 4.0, "train.put")]
    got = ps.by_innermost([(0.5, 3.5), (9.0, 11.0)], spans)
    assert got == pytest.approx({"train": 1.5, "train.feed": 2.0,
                                 "train.put": 0.5, None: 1.0})

    prof = Profile(window=(0.0, 10.0))
    prof.ops[0] = [Device(1.5, 6.0, "fusion.1")]
    prof.spans = [Span(t, t + 0.1, "serve.decode", line="python")
                  for t in (1.0, 2.0, 3.0)]
    prof.modules[0] = [Device(s, s + 1, "jit__decode_fn(1)", launch=r)
                       for s, r in ((1.5, 1), (4.0, 2), (5.0, 3))]
    for k, sp in enumerate(prof.spans):
        _launch(prof, sp, k + 1, 10 * k)
    # after the second dispatch one step waits, after the third two
    assert checks.decode_backlog(prof) == (2, 2)
    lines = checks.launch_order(prof)
    assert "3 paired by launch id" in lines[1]
    assert "enqueued before the span: 0" in lines[1]
    prof.host = [Span(0.9, 0.95, "PjitFunction(_decode_fn)"),
                 Span(1.01, 1.05, "PjitFunction(_decode_fn)")]
    assert checks.launch_order(prof)[0].startswith("launch order: 0 of 1 ")
    prof.host.append(Span(1.95, 2.05, "PjitFunction(_decode_fn)"))
    # opened inside its call
    assert checks.launch_order(prof)[0].startswith("launch order: 1 of 2 ")
    lines = ps.report(prof)
    assert lines[0].startswith("[program spans] device-idle seconds")


def test_lag_and_the_device_side_launch_order():
    prof = Profile(window=(0.0, 10.0))
    prof.spans = [Span(t, t + 0.1, "serve.decode", line="python")
                  for t in (1.0, 2.0, 3.0)]
    # the second execution is stamped 1 ms before its span opened
    prof.modules[0] = [Device(s, s + 0.4, "jit__decode_fn(1)", launch=r)
                       for s, r in ((1.05, 4), (1.999, 5), (3.5, 6))]
    for k, sp in enumerate(prof.spans):
        _launch(prof, sp, k + 4, 10 * k)
    # its enqueue event (at 2.03) is 31 ms after the device stamp
    assert checks.lag(prof) == pytest.approx(0.031)
    line = checks.launch_order(prof)[1]
    assert "1 as stamped (by up to 1.0000 ms), 0 with the lag" in line
    assert checks.decode_backlog(prof) == (1, 1)


def _record(tmp_path, program_spans):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for hit in program_spans:
            with jax.profiler.TraceAnnotation("repro:serve.gate", hit=hit):
                pass
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.pb"))
    return path


NEW_METRICS = ("feed_idle_share.train", "im2col_share.train",
               "decode_attention_share.serve", "first_token_p50_ms.serve",
               "gate_hit_ratio.serve")


def test_real_trace_parse_and_none_without_program_spans(tmp_path,
                                                         monkeypatch):
    path = _record(tmp_path / "with", [1, 0, 1, 1])
    monkeypatch.setattr(ps, "newest_trace", lambda root: path)
    run = {"trace": trace.load(path), "window_s": 1.0}
    prof = ps.of(run)
    assert [s.args for s in prof.spans] == [{"hit": h} for h in (1, 0, 1, 1)]
    assert prof.window == run["trace"].window
    assert load_reader("gate_hit_ratio.serve")(run) == pytest.approx(75.0)

    # an older tree's program: the same trace with no repro: span in it
    path = _record(tmp_path / "without", [])
    run = {"trace": trace.load(path), "window_s": 1.0}
    for name in NEW_METRICS:
        assert load_reader(name)(run) is None, name
    assert run[ps.CACHE_KEY] is None

    # a trace whose window is not the run's is not read
    run = {"trace": trace.Trace(window=(0.0, 1.0)), "window_s": 1.0}
    other = _record(tmp_path / "other", [1])
    monkeypatch.setattr(ps, "newest_trace", lambda root: other)
    assert ps.of(run) is None


def _pb(*fields):
    """A protobuf message from (field, value) pairs: ints as varints,
    strings and bytes length-delimited."""
    out = bytearray()

    def varint(x):
        while True:
            b, x = x & 0x7F, x >> 7
            out.append(b | (0x80 if x else 0))
            if not x:
                return
    for num, v in fields:
        if isinstance(v, int):
            varint(num << 3)
            varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            varint(num << 3 | 2)
            varint(len(v))
            out += v
    return bytes(out)


def test_op_names_from_the_xplane_metadata(tmp_path):
    """The same operation name in two programs keeps each its own
    op_name (one a string, one a reference to a stat name); one outside
    every program gets none."""
    stat = lambda i, name: (5, _pb((1, i), (2, _pb((1, i), (2, name)))))
    op = lambda i, name, *stats: (4, _pb((1, i), (2, _pb(
        (1, i), (2, name), *[(5, _pb(*st)) for st in stats]))))
    text = "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
    plane = _pb((2, "/device:TPU:0"), stat(1, "tf_op"), stat(2, "program_id"),
                stat(3, "jit(b)/mul:"),
                op(10, text, ((1, 1), (5, "jit(a)/vmap(jvp(im2col))/slice:")),
                   ((1, 2), (3, 111))),
                op(11, text, ((1, 1), (7, 3)), ((1, 2), (3, 222))),
                op(12, "%copy.2 = f32[2]{0} copy(f32[2]{0} %q)", ((1, 2), (3, 222))))
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(_pb((1, plane), (1, _pb((2, "/host:CPU")))))
    assert ps.op_names(str(path)) == {
        (0, 111, "fusion.1"): "jit(a)/vmap(jvp(im2col))/slice:",
        (0, 222, "fusion.1"): "jit(b)/mul:"}

    prof = Profile(window=(0.0, 1e-4))
    prof.modules[0] = [Device(0.0, 1e-5, "jit_a(111)"),
                       Device(2e-5, 3e-5, "jit_b(222)")]
    prof.ops[0] = [Device(1e-6, 3e-6, "fusion.1"),
                   Device(2.1e-5, 2.3e-5, "fusion.1"),
                   Device(2.4e-5, 2.5e-5, "copy.2"),
                   Device(5e-5, 6e-5, "fusion.1")]
    assert ps.scopes(prof, str(path)) == 2
    assert [o.scope for o in prof.ops[0]] == [
        "jit(a)/vmap(jvp(im2col))/slice:", "jit(b)/mul:", "", ""]
    assert ps.in_scope(prof.ops[0][0], "im2col")
    assert not ps.in_scope(Device(0, 1, "fusion.3", "jit(f)/my_im2col/pad"),
                           "im2col")
    assert ps.in_scope(Device(0, 1, "fusion.3",
                              "jit(dec)/decode_attention/reduce_max:"),
                       "decode_attention")


HLO = """HloModule jit_f, is_scheduled=true

fused_computation.1 {
  param_0 = f32[8]{0} parameter(0)
  pad.1 = f32[12]{0} pad(param_0, c), padding=2_2, metadata={op_name="jit(f)/jvp(im2col)/pad"}
  slice.2 = f32[8]{0} slice(pad.1), slice={[0:8]}, metadata={op_name="jit(f)/jvp(im2col)/slice"}
  dot.3 = f32[8]{0} multiply(slice.2, param_0), metadata={op_name="jit(f)/panel_gemm/dot_general"}
  mul.5 = f32[8]{0} multiply(dot.3, dot.3)
  ROOT add.4 = f32[8]{0} add(dot.3, param_0), metadata={op_name="jit(f)/jvp(im2col)/concatenate"}
}

fused_computation.2 {
  param_0.1 = f32[8]{0} parameter(0)
  neg.1 = f32[8]{0} negate(param_0.1), metadata={op_name="jit(f)/transpose(jvp(im2col))/slice"}
  mul.2 = f32[8]{0} multiply(neg.1, neg.1), metadata={op_name="jit(f)/adam/mul"}
  ROOT sub.3 = f32[8]{0} subtract(mul.2, neg.1), metadata={op_name="jit(f)/adam/sub"}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  p = f32[8]{0} parameter(0)
  fusion.1 = f32[8]{0} fusion(p), kind=kLoop, calls=fused_computation.1, metadata={op_name="jit(f)/jvp(im2col)/concatenate"}
  ROOT fusion.2 = f32[8]{0} fusion(fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/adam/sub"}
  concatenate.3 = f32[16]{0} concatenate(p, p), dimensions={0}, metadata={op_name="jit(f)/jvp(im2col)/concatenate"}
}
"""


def test_scope_by_fusion_body(monkeypatch):
    shares = checks.fusion_shares(HLO, "im2col")
    assert shares == pytest.approx({"fusion.1": 3 / 4, "fusion.2": 1 / 3})

    prof = Profile(window=(0.0, 10.0))
    prof.modules[0] = [Device(0.0, 10.0, "jit_f(1)")]
    prof.ops[0] = [
        Device(1.0, 3.0, "fusion.1", "jit(f)/jvp(im2col)/concatenate"),
        Device(3.0, 4.0, "fusion.2", "jit(f)/adam/sub"),
        Device(4.0, 4.5, "concatenate.3", "jit(f)/jvp(im2col)/concatenate"),
        Device(5.0, 9.0, "fusion.4", "jit(f)/panel_gemm/dot_general")]
    monkeypatch.setattr(checks, "hlo_texts", lambda path: {"jit_f(1)": HLO})
    lines = checks.scope_by_fusion(prof, "unused.xplane.pb", "im2col")
    assert "busy 7.500000 s" in lines[0]
    got = "\n".join(lines)
    assert "counted, body half or more in scope: 2.000000 s" in got
    assert "counted, not a fusion: 0.500000 s" in got
    assert "not counted, body under half in scope: 1.000000 s" in got
    assert "fusion.4" not in got


def test_fusion_shares_from_a_real_trace(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("im2col"):
            y = jax.numpy.concatenate([x, x * 2], axis=-1)
        return jax.numpy.sin(y).sum()

    f(jax.numpy.ones((8, 4))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jax.numpy.ones((8, 4))).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.pb"))
    texts = checks.hlo_texts(path)
    name, = [n for n in texts if n.startswith("jit_f")]
    shares = checks.fusion_shares(texts[name], "im2col")
    # XLA fuses the scoped concatenate into the sine and the sum
    assert any(0 < v < 1 for v in shares.values())
