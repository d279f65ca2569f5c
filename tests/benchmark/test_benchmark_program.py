"""The readers of the program's own names: phase and part of a scope
path, kernels by name, self time of a host span, the kernels' counts and
their roofline, on hand-made tuples; then the whole of
``benchmark/trace/program.py`` on a trace recorded on the chip."""

import os
import types

import pytest

from tinybench import ROOT

from benchmark import harness, kernel_counts
from benchmark.trace import program, reduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BERT_LARGE = {"hidden": 1024, "heads": 16, "causal": False}
GPT2_MEDIUM = {"hidden": 1024, "heads": 16, "causal": True}
NEW_METRICS = ("kernels.flash_fwd_ms", "kernels.flash_bwd_ms",
               "kernels.flash_roofline_pct", "model.fwd_ms",
               "model.remat_ms", "model.bwd_ms", "trainer.optimizer_ms",
               "exchange.pack_ms", "trainer.overhead_ms", "input.h2d_ms")

STEP = "jit(step)/shard_map/"
SCAN = "while/body/checkpoint/"


@pytest.mark.parametrize("path,phase,part", [
    (STEP + "bps.model/jvp(bps.embed)/gather", "forward", "bps.embed"),
    (STEP + "bps.model/jvp()/" + SCAN + "bps.attn/dot_general",
     "forward", "bps.attn"),
    (STEP + "bps.model/jvp()/" + SCAN + "bps.attn/bps_flash_fwd/pallas_call",
     "forward", "bps.attn"),
    (STEP + "bps.model/transpose(jvp())/" + SCAN
     + "rematted_computation/bps.mlp/dot_general", "remat", "bps.mlp"),
    (STEP + "bps.model/transpose(jvp())/" + SCAN
     + "bps.attn/bps_flash_bwd_fused/pallas_call", "backward", "bps.attn"),
    (STEP + "bps.model/transpose(jvp(bps.head))/jit(log_softmax)/div",
     "backward", "bps.head"),
    (STEP + "bps.model/transpose(bps.model)/jvp(bps.embed)/dot_general",
     "backward", "bps.embed"),
    (STEP + "bps.exchange/bps.exchange.pack/concatenate", "exchange", "-"),
    (STEP + "bps.exchange/bps.exchange.reduce/psum", "exchange", "-"),
    (STEP + "bps.optimizer/jit(_where)/select_n", "optimizer", "-"),
    (STEP + "pmean", "other", "-"),
    ("", "other", "-"),
], ids=["embed", "attn_fwd", "flash_fwd", "mlp_remat", "flash_bwd",
        "head_bwd", "embed_bwd", "pack", "reduce", "optimizer", "loss_mean",
        "no_path"])
def test_phase_and_part_of_a_scope_path(path, phase, part):
    assert program.phase(path) == phase
    assert program.part(path) == part


def test_the_recompute_is_asked_before_the_backward_pass():
    # its path holds both markers: it runs inside the transposed scan
    path = (STEP + "bps.model/transpose(jvp())/" + SCAN
            + "rematted_computation/bps.attn/bps_flash_fwd/pallas_call")
    assert "transpose(" in path and program.phase(path) == "remat"


@pytest.mark.parametrize("name,kernel", [
    ("%bps_flash_fwd.3 = (bf16[8,16,512,64]{3,2,1,0}, f32[8,16,512,1]"
     "{3,2,1,0}) custom-call(%a, %b, %c), custom_call_target="
     "\"tpu_custom_call\"", "bps_flash_fwd"),
    ("%bps_flash_bwd_dkv.1 = _ custom-call()", "bps_flash_bwd_dkv"),
    ("%bps_flash_bwd_fused = _ custom-call()", "bps_flash_bwd_fused"),
    ("%closed_call.8 = _ custom-call()", None),
    ("%fusion.325 = _ fusion()", None),
], ids=["whole_instruction", "brief", "no_number", "parents_name", "fusion"])
def test_kernel_of_an_event_name(name, kernel):
    assert program.kernel(name) == kernel


def _ops():
    """Two steps' worth of hand-made operations, 1 us each unless said."""
    model = STEP + "bps.model/"
    rows = [
        ("%fusion.1 = _ fusion()", model + "jvp()/" + SCAN + "bps.attn/dot", 2),
        ("%bps_flash_fwd.2 = _ custom-call()",
         model + "jvp()/" + SCAN + "bps.attn/bps_flash_fwd/pallas_call", 4),
        ("%fusion.2 = _ fusion()", model + "jvp()/" + SCAN + "bps.mlp/dot", 3),
        ("%bps_flash_fwd.3 = _ custom-call()", model + "transpose(jvp())/"
         + SCAN + "rematted_computation/bps.attn/bps_flash_fwd/pallas_call",
         4),
        ("%bps_flash_bwd_fused.1 = _ custom-call()", model
         + "transpose(jvp())/" + SCAN + "bps.attn/bps_flash_bwd_fused/"
         "pallas_call", 6),
        ("%fusion.3 = _ fusion()", model + "transpose(jvp())/" + SCAN
         + "bps.mlp/dot", 5),
        ("%fusion.4 = _ fusion()", STEP + "bps.exchange/bps.exchange.pack/c",
         1),
        ("%all-reduce.1 = _ all-reduce()",
         STEP + "bps.exchange/bps.exchange.reduce/psum", 2),
        ("%fusion.5 = _ fusion()", STEP + "bps.optimizer/mul", 2),
        ("%copy.1 = _ copy()", "", 1),
    ]
    ops, t = [], 0.0
    for _ in range(2):
        for name, path, us in rows:
            ops.append((name, path, t, t + us * 1e3))
            t += us * 1e3 + 100.0           # a gap after every operation
    return ops


def test_sums_by_phase_part_and_kernel():
    ops = _ops()
    assert program.named(ops)
    by_phase = program.ns_by_phase(ops)
    assert by_phase == {"forward": 18e3, "remat": 8e3, "backward": 22e3,
                        "optimizer": 4e3, "exchange": 6e3, "other": 2e3}
    # the phases are a partition: their sum is the busy time
    assert sum(by_phase.values()) == reduce.length(
        (s, e) for _, _, s, e in ops)
    table = program.ns_by_part_and_phase(ops)
    assert table[("bps.attn", "forward")] == 12e3
    assert table[("bps.attn", "remat")] == 8e3
    assert table[("bps.mlp", "backward")] == 10e3
    assert table[("-", "exchange")] == 6e3
    assert sum(table.values()) == sum(by_phase.values())
    assert program.ns_by_kernel(ops) == {
        "bps_flash_fwd": (16e3, 4), "bps_flash_bwd_fused": (12e3, 2)}


def test_a_program_without_names_reads_as_nothing():
    # the parent of PR 25: paths without a bps scope, kernels under the
    # names of the remat machinery
    ops = [("%closed_call.8 = _ custom-call()", "jit(step)/jvp()/x", 0.0, 5.0),
           ("%fusion.1 = _ fusion()", "", 5.0, 9.0)]
    trace = program.Program("/device:TPU:0", (0.0, 9.0), 1, ops, [], [], None)
    assert not program.named(ops)
    assert trace.phase_ms("forward") is None
    assert trace.kernels_ms(program.FORWARD_KERNELS) is None
    assert trace.step_spans() == []


def _spans():
    span = program.HostSpan
    return [
        span("bps.step", 0, 100.0, 200.0, {"step_num": 7}),
        span("bps.shard_batch", 0, 105.0, 115.0, {}),
        span("bps.dispatch", 0, 120.0, 180.0, {}),
        span("bps.stats", 0, 185.0, 195.0, {}),
        span("bps.feed.h2d", 1, 110.0, 150.0, {"bytes": 4096}),  # a thread apart
        span("bps.step", 0, 300.0, 420.0, {"step_num": 8}),
        span("bps.dispatch", 0, 310.0, 400.0, {}),
    ]


def test_self_time_of_a_host_span():
    spans = _spans()
    first, second = [s for s in spans if s.name == "bps.step"]
    assert [s.name for s in program.children(first, spans)] == [
        "bps.shard_batch", "bps.dispatch", "bps.stats"]
    assert program.self_ns(first, spans) == 100.0 - 10.0 - 60.0 - 10.0
    assert program.self_ns(first, spans, ("bps.dispatch",)) == 40.0
    assert program.self_ns(second, spans, ("bps.dispatch",)) == 30.0
    assert program.durations_ms(spans, "bps.feed.h2d") == [40.0 / 1e6]


@pytest.mark.parametrize("sizes,mix,kernel,flops,nbytes", [
    # BERT-large, 64 x 512: u = 2*64*16*512*512*64 = 34.36e9 a product;
    # a tensor 64*16*512*64*2 B = 67.1 MB, a row statistic 2.1 MB
    (BERT_LARGE, {"batch_per_chip": 64, "seq": 512}, "bps_flash_fwd",
     68719476736.0, 4 * 67108864 + 2097152),
    (BERT_LARGE, {"batch_per_chip": 64, "seq": 512}, "bps_flash_bwd_fused",
     2.5 * 68719476736.0, 7 * 67108864 + 2097152),
    # BERT-large, 256 x 128: a quarter of the operations, the same bytes
    (BERT_LARGE, {"batch_per_chip": 256, "seq": 128}, "bps_flash_fwd",
     68719476736.0 / 4, 4 * 67108864 + 2097152),
    # GPT-2-medium, 8 x 1024, causal: 512.5 keys a query;
    # u = 2*8*16*1024*512.5*64 = 8.598e9; a tensor 16.8 MB
    (GPT2_MEDIUM, {"batch_per_chip": 8, "seq": 1024}, "bps_flash_fwd",
     2 * 8598323200.0, 4 * 16777216 + 524288),
    (GPT2_MEDIUM, {"batch_per_chip": 8, "seq": 1024}, "bps_flash_bwd_dq",
     3 * 8598323200.0, 5 * 16777216 + 2 * 524288),
    (GPT2_MEDIUM, {"batch_per_chip": 8, "seq": 1024}, "bps_flash_bwd_dkv",
     4 * 8598323200.0, 6 * 16777216 + 2 * 524288),
], ids=["bert_s512_fwd", "bert_s512_bwd", "bert_s128_fwd", "gpt2_fwd",
        "gpt2_dq", "gpt2_dkv"])
def test_kernel_counts_by_hand(sizes, mix, kernel, flops, nbytes):
    count = kernel_counts.of_cell(sizes, mix)[kernel]
    assert count["flops"] == flops
    assert count["bytes"] == nbytes


def test_which_bound_sets_a_kernels_roofline():
    s512 = kernel_counts.of_cell(BERT_LARGE, {"batch_per_chip": 64,
                                              "seq": 512})
    s128 = kernel_counts.of_cell(BERT_LARGE, {"batch_per_chip": 256,
                                              "seq": 128})
    least, bound = kernel_counts.least_seconds(s512["bps_flash_fwd"], PEAKS)
    assert bound == "flops" and least == pytest.approx(348.8e-6, rel=1e-3)
    least, bound = kernel_counts.least_seconds(s128["bps_flash_fwd"], PEAKS)
    assert bound == "hbm" and least == pytest.approx(330.3e-6, rel=1e-3)
    # the step's flash calls at 64 x 512: forward, recompute and backward
    # in each of 24 layers, 7.4 TFLOP (ISSUE 25's figure by hand)
    layer = 2 * s512["bps_flash_fwd"]["flops"] \
        + s512["bps_flash_bwd_fused"]["flops"]
    assert 24 * layer == pytest.approx(7.42e12, rel=1e-3)


def test_roofline_share_of_hand_made_calls():
    counts = {"bps_flash_fwd": {"flops": 197e12 * 1e-3, "bytes": 1.0},
              "bps_flash_bwd_fused": {"flops": 1.0, "bytes": 819e9 * 2e-3}}
    # forward: 2 calls that could take 1 ms each took 8 ms: 25 %, by
    # operations; backward: 1 call of 2 ms took 4 ms: 50 %, by bytes
    by_kernel = {"bps_flash_fwd": (8e6, 2), "bps_flash_bwd_fused": (4e6, 1),
                 "bps_flash_other": (1e6, 1)}
    shares = program.roofline(by_kernel, counts, PEAKS)
    assert shares["bps_flash_fwd"] == {"pct": pytest.approx(25.0),
                                       "bound": "flops"}
    assert shares["bps_flash_bwd_fused"] == {"pct": pytest.approx(50.0),
                                             "bound": "hbm"}
    assert shares["all"]["pct"] == pytest.approx(100.0 * 4 / 12)
    assert "bps_flash_other" not in shares


def test_root_of_a_cells_directories(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny_mlm_cell")
    assert program.root_of(cell.dirs) == os.path.abspath(tiny_root)
    assert program.root_of([os.path.join(ROOT, "benchmark", "metrics")]) \
        == ROOT


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_reader_reads_nothing_without_a_device_trace(metric, tiny_root):
    """A CPU run (``run.chips`` empty) and a chip run of a program that
    names nothing both leave the metric out; neither raises."""
    reader = harness.load_metric(metric, [os.path.join(ROOT, "benchmark")])
    cell = harness.load_cell(tiny_root, "tiny_mlm_cell")
    run = types.SimpleNamespace(cell=cell, chips=[], peaks=None, spans={})
    assert reader.read(run) is None
    run.chips, run.peaks = [object()], PEAKS    # a chip, and no trace file
    assert reader.read(run) is None


# ------------------------------------------------- the wire-format reader

def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, a float as a double, bytes
    or str as length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        import struct
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xspace_by_hand() -> bytes:
    stat_names = {1: "tf_op", 2: "step_num", 3: "flops", 4: "share",
                  5: "kind", 6: "custom-call"}
    stat_meta = b"".join(
        _field(5, _field(1, k) + _field(2, _field(1, k) + _field(2, v)))
        for k, v in stat_names.items())
    event_meta = _field(4, _field(1, 7) + _field(2, (
        _field(1, 7) + _field(2, "%bps_flash_fwd.3 = f32[] custom-call()")
        + _field(5, _field(1, 1) + _field(5, "jit(step)/bps.model/x:"))
        + _field(5, _field(1, 3) + _field(4, 68719476736))
        + _field(5, _field(1, 4) + _field(2, 0.25))
        + _field(5, _field(1, 5) + _field(7, 6)))))
    event_meta += _field(4, _field(1, -2) + _field(2, (      # a negative id
        _field(1, -2) + _field(2, "bps.step"))))
    event = (_field(1, 7) + _field(2, 1500999) + _field(3, 2000999)
             + _field(4, _field(1, 2) + _field(3, 41)))
    line = (_field(2, "XLA Ops") + _field(3, 1000) + _field(4, event)
            + _field(4, _field(1, -2) + _field(2, 0) + _field(3, 999)))
    skipped = _field(2, "Async XLA Ops") + _field(3, 5) + _field(4, event)
    plane = (_field(1, 3) + _field(2, "/device:TPU:0") + _field(3, line)
             + _field(3, skipped) + event_meta + stat_meta)
    return _field(1, plane) + _field(1, _field(2, "/host:metadata"))


def test_wire_reader_on_a_message_made_by_hand(tmp_path):
    from benchmark.trace import xspace
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace_by_hand())
    raw = xspace.planes(str(path))
    assert list(raw) == ["/device:TPU:0", "/host:metadata"]
    chip = xspace.plane(raw["/device:TPU:0"], lambda n: n == "XLA Ops")
    assert [ln.name for ln in chip.lines] == ["XLA Ops"]
    first, second = chip.lines[0].events
    # whole ns, as ProfileData gives them: 1000 + 1500999 ps, 2000999 ps
    assert (first.name, first.start_ns, first.dur_ns) == (
        "%bps_flash_fwd.3 = f32[] custom-call()", 2500.0, 2000.0)
    assert first.stats == {"step_num": 41}
    assert chip.metadata_stats[first.metadata_id] == {
        "tf_op": "jit(step)/bps.model/x:", "flops": 68719476736,
        "share": 0.25, "kind": "custom-call"}
    assert (second.name, second.start_ns, second.dur_ns) == (
        "bps.step", 1000.0, 0.0)
    with pytest.raises(ValueError):
        list(xspace.fields(memoryview(b"\x0b\x00")))    # a group: not ours


def test_wire_reader_agrees_with_profile_data(tmp_path):
    """On a trace this process records: the same events with the same
    whole-ns times as ``jax.profiler.ProfileData``, and the annotations'
    arguments."""
    import glob
    import jax
    from jax.profiler import ProfileData
    from benchmark.trace import xspace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("bps.step", step_num=i):
                with jax.profiler.TraceAnnotation("bps.feed.h2d", bytes=64):
                    jax.block_until_ready(jax.numpy.ones((8,)) + i)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path) + "/plugins/profile/*/*.xplane.pb")
    ours = xspace.plane(xspace.planes(path)[reduce.HOST_PLANE])
    theirs = next(p for p in ProfileData.from_file(path).planes
                  if p.name == reduce.HOST_PLANE)
    got = [(ln.name, [(e.name, e.start_ns, e.dur_ns) for e in ln.events])
           for ln in ours.lines]
    want = [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                       for e in ln.events]) for ln in theirs.lines]
    assert got == want and sum(len(evs) for _, evs in got) >= 6
    spans = [e for ln in ours.lines for e in ln.events
             if e.name.startswith("bps.")]
    assert [e.stats["step_num"] for e in spans
            if e.name == "bps.step"] == [0, 1, 2]
    assert {e.stats["bytes"] for e in spans
            if e.name == "bps.feed.h2d"} == {64}


# --------------------------------------- a trace recorded on the chip

# tests/benchmark/fixtures/tiny_trace_<n>chip_scopes.json.gz: the tiny
# benchmark's MLM cell (hidden 128, 2 heads, 8 x 128 a chip, bfloat16) on
# the TPU v5e with PR 25's names, written by program.dump_fixture
RECORDED = types.SimpleNamespace(
    name="tiny_mlm_cell", dirs=[os.path.join(ROOT, "benchmark")],
    config={"sizes": {"hidden": 128, "heads": 2, "causal": False}},
    mix={"batch_per_chip": 8, "seq": 128})


@pytest.fixture(scope="module", params=[1, 4], ids=["1chip", "4chip"])
def chips(request):
    return request.param


@pytest.fixture(scope="module")
def recorded(chips):
    return program.load_fixture(os.path.join(
        FIXTURES, f"tiny_trace_{chips}chip_scopes.json.gz"))


def test_recorded_trace_names_every_phase_and_kernel(recorded, chips):
    assert recorded.path_stat == "tf_op" and recorded.steps >= 10
    by_phase = program.ns_by_phase(recorded.ops)
    for name in ("forward", "remat", "backward", "optimizer"):
        assert by_phase[name] > 0
    # the exchange exists only across chips (a size-1 axis is bypassed)
    assert (by_phase["exchange"] > 0) == (chips == 4)
    # the phases are a partition of the busy time, and little is unnamed
    assert sum(by_phase.values()) == pytest.approx(recorded.busy_ns,
                                                   rel=5e-3)
    assert by_phase["other"] < 0.1 * recorded.busy_ns
    by_kernel = program.ns_by_kernel(recorded.ops)
    assert set(by_kernel) == {"bps_flash_fwd", "bps_flash_bwd_fused"}
    # forward and recompute: two calls of the forward kernel for each of
    # the backward's, and all of them the custom calls that take time
    assert by_kernel["bps_flash_fwd"][1] == 2 * \
        by_kernel["bps_flash_bwd_fused"][1]
    pallas = sum(e - s for n, _, s, e in recorded.ops
                 if reduce.category(n) == "pallas")
    assert sum(ns for ns, _ in by_kernel.values()) == pytest.approx(
        pallas, rel=5e-3)
    # a kernel call lies under its part's scope in its pass
    paths = {program.kernel(n): p for n, p, _, _ in recorded.ops
             if program.kernel(n) and "rematted" not in p}
    assert program.part(paths["bps_flash_fwd"]) == "bps.attn"
    assert program.phase(paths["bps_flash_bwd_fused"]) == "backward"
    # the two scans of the step are its containers
    assert len({reduce.short_name(n) for n, _, _, _ in
                recorded.containers}) >= 2


def test_recorded_trace_holds_the_host_spans(recorded, chips):
    steps = recorded.step_spans()
    numbers = [s.args["step_num"] for s in steps]
    assert len(steps) >= 10
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))
    for span in steps:
        inside = [s.name for s in program.children(span, recorded.host)]
        assert inside.count("bps.dispatch") == 1
        assert 0 <= program.self_ns(span, recorded.host) \
            <= program.self_ns(span, recorded.host, ("bps.dispatch",))
    h2d = [s for s in recorded.host if s.name == "bps.feed.h2d"]
    assert h2d and {s.args["bytes"] for s in h2d} == {
        2 * 8 * chips * 128 * 4}
    assert {s.thread for s in h2d}.isdisjoint({s.thread for s in steps})
    assert any(s.name == "bps.feed.wait" for s in recorded.host)


def test_report_of_the_recorded_trace(recorded):
    text = program.report(recorded, RECORDED, PEAKS)
    for needle in ("forward", "remat", "bps.attn", "bps_flash_bwd_fused",
                   "while", "bps.step self time", "% of their roofline"):
        assert needle in text


# what the chip runs that recorded the fixtures printed (my chip runs,
# PR 25): the readers on a fixture give its run's own numbers
PRINTED = {
    1: {"kernels.flash_fwd_ms": 0.025238074074074076,
        "kernels.flash_bwd_ms": 0.013622037037037037,
        "kernels.flash_roofline_pct": 24.864465638638528,
        "trainer.overhead_ms": 0.29033, "input.h2d_ms": 0.8557999999999999},
    4: {"kernels.flash_fwd_ms": 0.0252375,
        "kernels.flash_bwd_ms": 0.013632636363636364,
        "kernels.flash_roofline_pct": 24.858052680767713,
        "model.fwd_ms": 0.04028095454545454,
        "model.remat_ms": 0.020227363636363637,
        "model.bwd_ms": 0.05481345454545455,
        "trainer.optimizer_ms": 0.007470181818181818,
        "exchange.pack_ms": 0.0021809545454545454,
        "trainer.overhead_ms": 0.28872, "input.h2d_ms": 1.89169},
}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_reader_on_the_recorded_trace(monkeypatch, recorded, chips,
                                          metric):
    monkeypatch.setattr(program, "of_run", lambda run: recorded)
    reader = harness.load_metric(metric, [os.path.join(ROOT, "benchmark")])
    run = types.SimpleNamespace(cell=RECORDED, chips=[object()], peaks=PEAKS)
    got = reader.read(run)
    if metric in PRINTED[chips]:
        assert got == pytest.approx(PRINTED[chips][metric], rel=1e-9)
    else:       # the one-chip run was made before the paths could be read
        assert 0 <= got < 1e3 * recorded.busy_ns / 1e9 / recorded.steps
        assert (got > 0) == (metric != "exchange.pack_ms")
