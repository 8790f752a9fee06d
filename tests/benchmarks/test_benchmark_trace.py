"""The reduction from a profiler trace to device metrics: on a hand-built
trace with known answers, and on a small trace recorded on the v5e."""

import gzip
import os
import shutil
import struct

import pytest

from benchmarks import device, readers, xplane

TESTDATA = os.path.join(
    os.path.dirname(os.path.abspath(xplane.__file__)), "testdata"
)
RECORDED = os.path.join(TESTDATA, "anakin_small_v5e.xplane.pb.gz")

# ------------------------------------------------ a tiny XSpace encoder


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines: dict, stat_names=("tf_op",)) -> bytes:
    """``lines``: {line name: [(event name, tf_op, start_ps, duration_ps)]}"""
    out = _field(2, name)
    stat_id = {n: i + 1 for i, n in enumerate(stat_names)}
    for n, i in stat_id.items():
        out += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    meta_id: dict[tuple, int] = {}
    for line_name, events in lines.items():
        line = _field(2, line_name) + _field(3, 0)
        for ev_name, tf_op, start, duration in events:
            key = (ev_name, tf_op)
            if key not in meta_id:
                meta_id[key] = len(meta_id) + 1
                meta = _field(1, meta_id[key]) + _field(2, ev_name)
                if tf_op:
                    meta += _field(
                        5, _field(1, stat_id["tf_op"]) + _field(5, tf_op)
                    )
                out += _field(4, _field(1, meta_id[key]) + _field(2, meta))
            line += _field(
                4, _field(1, meta_id[key]) + _field(2, start) + _field(3, duration)
            )
        out += _field(3, line)
    return out


def _write(path, planes) -> str:
    with open(path, "wb") as f:
        for p in planes:
            f.write(_field(1, p))
    return str(path)


US = 1_000_000  # picoseconds


@pytest.fixture
def hand_built(tmp_path):
    """Two updates in a 100 us window. Chip 0: a while (10..90) spanning
    rollout 10..30 (its last 4 us another kernel's Mosaic call, which names
    the V-trace kernel's result among its operands), the V-trace kernel's
    Mosaic call 30..32, loss_and_grad 32..70, an all-reduce 70..80 of which
    74..78 overlaps a fusion, then idle from 90. Chip 1: busy 0..50."""
    w = "jit(step)/while/body/"
    ops0 = [
        ("%while.1 = (...) while(...)", w[:-1], 10 * US, 80 * US),
        ("%fusion.1 = f32[8] fusion(...)", w + "rollout/conv", 10 * US, 16 * US),
        ("%gqa_step.7 = f32[8] custom-call(f32[8] %jvp_jit_fused_vtrace_pallas__.2), "
         'custom_call_target="tpu_custom_call"',
         w + "rollout/gqa/gqa_step/pallas_call", 26 * US, 4 * US),
        ("%jvp_jit_fused_vtrace_pallas__.2 = f32[8] custom-call(...), "
         'custom_call_target="tpu_custom_call"',
         w + "loss_and_grad/jvp(jit(fused_vtrace_pallas))/pallas_call", 30 * US, 2 * US),
        ("%fusion.3 = f32[8] fusion(...)", w + "loss_and_grad/jvp(M)/conv", 32 * US, 38 * US),
        ("%all-reduce.4 = f32[8] all-reduce(...)", w + "psum", 70 * US, 10 * US),
        ("%fusion.5 = f32[8] fusion(...)", w + "optimizer/add", 80 * US, 10 * US),
    ]
    async0 = [
        ("%all-reduce-start.9 = f32[8] all-reduce-start(...)", w + "psum", 60 * US, 8 * US),
    ]
    host = {"python": [
        ("bench.window", "", 0, 100 * US),
        ("bench.update_call", "", 0, 8 * US),
        ("bench.sync", "", 8 * US, 92 * US),
        ("$other.py:1 f", "", 0, 100 * US),
    ]}
    return _write(tmp_path / "hand.xplane.pb", [
        _plane("/device:TPU:0", {"XLA Ops": ops0, "Async XLA Ops": async0,
                                 "Steps": [("0", "", 0, 90 * US)]}),
        _plane("/device:TPU:1", {"XLA Ops": [
            ("%fusion.1 = f32[8] fusion(...)", w + "rollout/conv", 0, 50 * US)]}),
        _plane("/host:CPU", host),
    ])


def test_hand_built_trace_has_the_known_answers(hand_built):
    trace = xplane.load_trace(hand_built)
    assert [d.index for d in trace.devices] == [0, 1]
    assert trace.window_s == pytest.approx(100e-6)
    d0, d1 = trace.devices
    # busy union: the while covers 10..90 on chip 0; 0..50 on chip 1
    assert d0.busy_ps() == 80 * US and d1.busy_ps() == 50 * US
    assert trace.busy_s == pytest.approx(65e-6)
    assert d0.idle_gaps() == [(0, 10 * US), (90 * US, 100 * US)]
    # per-scope time is self time: the while itself has none left over
    assert d0.scope_ps("rollout") == 20 * US
    assert d0.scope_ps("loss_and_grad") == 40 * US
    assert d0.scope_ps("optimizer") == 10 * US
    assert d0.scope_ps("roll") == 0  # a scope is a whole path component
    # the kernels: every Mosaic call, and one kernel's by the name its op
    # carries (an operand that names another kernel's result is not it)
    assert [e.duration_ps for e in d0.mosaic_calls()] == [4 * US, 2 * US]
    assert [e.duration_ps for e in d0.mosaic_calls("fused_vtrace_pallas")] == [2 * US]
    assert [e.duration_ps for e in d0.mosaic_calls("gqa_step")] == [4 * US]
    assert d0.mosaic_calls("kda_step") == []
    # collectives: 60..68 (async) and 70..80 (sync) = 18 us in all; the
    # async one runs under the loss_and_grad fusion (32..70), the sync one
    # alone: 10 us exposed
    assert d0.collectives() == (18 * US, 10 * US)
    # idle gaps by the innermost of the benchmark's own annotations
    assert dict(trace.idle_by_annotation()) == {
        "bench.update_call": pytest.approx(10e-6),
        "bench.sync": pytest.approx(10e-6),
    }
    top = dict(trace.devices[0].top_ops())
    assert top["fusion.3 [loss_and_grad/jvp(M)/conv]"] == pytest.approx(38e-6)
    assert top["while.1 [jit(step)/while/body]"] == 0


def test_readers_on_the_hand_built_trace(hand_built):
    ev = {
        "trace": xplane.load_trace(hand_built), "traced_updates": 2, "chips": 2,
        "geometry": {"num_envs": 512, "unroll_len": 32, "rollout_on_device": True},
        "model": {"torso": "mlp", "obs_shape": [6], "hidden_sizes": [256, 256],
                  "num_actions": 6},
        "peaks": {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e12},
    }
    # mean over the two chips, per update: (20 + 50) / 2 / 2 us
    assert readers.scope_device_ms(ev, scope="rollout") == pytest.approx(17.5e-3)
    assert readers.scope_device_ms(ev, scope="nothing") is None
    # the V-trace kernel's call alone: the 4 us of the other kernel's would
    # make the mean 3 us, as the reader read until PR 35
    vtrace = {"kernel": "fused_vtrace_pallas"}
    assert readers.mosaic_device_us(ev, **vtrace) == pytest.approx(2.0)
    assert readers.mosaic_device_us(ev, kernel="gqa_step") == pytest.approx(4.0)
    assert readers.mosaic_device_us(ev, kernel="kda_step") is None
    # 4 * (8 * 32 * 256 + 256) bytes per chip at 1e12 B/s = 0.263168 us of 2 us
    assert readers.fused_vtrace_roofline(ev, **vtrace) == pytest.approx(13.1584, rel=1e-4)
    assert readers.fused_vtrace_roofline(ev, kernel="kda_step") is None
    assert readers.collective_ms(ev) == pytest.approx(9e-3)
    assert readers.collective_ms(ev, exposed=True) == pytest.approx(5e-3)
    assert readers.device_idle_share(ev) == pytest.approx(35.0)
    # FLOPs of the two updates on one chip's 256 envs x T=32, over the
    # 65 us an op ran there (mean over the chips) and the peak: it is read
    # from the trace, so the 35% the chips idle do not lower it
    per_frame = 4 * 2 * (6 * 256 + 256 * 256 + 256 * 7)
    assert readers.model_flops_util(ev) == pytest.approx(
        100 * per_frame * 256 * 32 * 2 / 65e-6 / 1e12
    )
    assert readers.model_flops_util({**ev, "traced_updates": 0}) is None
    assert readers.model_flops_util({**ev, "trace": None}) is None


@pytest.mark.parametrize("name", ["fused_vtrace_device_us", "fused_vtrace_roofline"])
def test_the_vtrace_metrics_name_the_kernel_the_program_builds(name):
    import inspect

    from asyncrl_tpu.ops import pallas_scan
    from benchmarks import run

    spec = run.Spec(os.path.join(run.ROOT, "BENCHMARK.json"), [run.BENCH_DIR])
    read, params = spec.reader(name)
    assert read is getattr(readers, {"fused_vtrace_device_us": "mosaic_device_us"}.get(name, name))
    assert params == {"kernel": "fused_vtrace_pallas"}
    assert f'name="{params["kernel"]}"' in inspect.getsource(pallas_scan.fused_vtrace_pallas)
    entry = next(m for m in spec.doc["per_layer"] if m["name"] == name)
    assert "workloads" not in entry and entry["layer"] == "Kernels"


def test_a_trace_without_a_chip_reads_as_nothing(tmp_path):
    path = _write(tmp_path / "cpu.xplane.pb", [
        _plane("/host:CPU", {"python": [("bench.window", "", 0, US)]})
    ])
    assert xplane.load_trace(path) is None
    assert readers.device_idle_share({"trace": None}) is None


# ------------------------------------------ the trace recorded on the chip


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_decoder_agrees_with_profile_data(recorded):
    """The wire-format decoder against jax's own reader, event for event."""
    import jax

    ours = {p.name: p for p in xplane.read_xspace(recorded)}
    theirs = jax.profiler.ProfileData.from_file(recorded)
    checked = 0
    for plane in theirs.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            want = list(line.events)
            got = ours[plane.name].line(line.name).events
            assert [e.name for e in got] == [e.name for e in want], line.name
            for g, w in zip(got, want):
                assert g.duration_ps / 1000 == pytest.approx(w.duration_ns, abs=1)
                assert g.start_ps / 1000 == pytest.approx(w.start_ns, abs=1)
            checked += len(want)
    assert checked > 1000


def test_recorded_trace_reduces_to_what_was_run(recorded):
    """``testdata/README.md`` says what was recorded: K=2 updates a call of
    the IMPALA-CNN step, 16 envs x T=8, on one TPU v5 lite."""
    trace = xplane.load_trace(recorded)
    assert len(trace.devices) == 1
    d = trace.devices[0]
    calls = sum(1 for a in trace.annotations if a.name == "bench.update_call")
    updates = 2 * calls
    assert calls >= 1
    # one Mosaic V-trace call per update, each a fraction of a microsecond
    mosaic = d.mosaic_calls()
    assert len(mosaic) == updates
    assert d.mosaic_calls("fused_vtrace_pallas") == mosaic
    assert d.mosaic_calls("gqa_step") == []
    assert all(0 < e.duration_ps < 5 * US for e in mosaic)
    # the scopes of learn/learner.py are found, and they are most of the
    # busy time, which is most of the window
    rollout, learn = d.scope_ps("rollout"), d.scope_ps("loss_and_grad")
    assert rollout > 0 and learn > 0
    assert 0.7 * d.busy_ps() <= rollout + learn <= d.busy_ps()
    assert 0 < trace.busy_s <= trace.window_s
    assert d.collectives() == (0, 0)  # one chip
    # idle gaps and busy intervals tile the window
    idle = sum(e - s for s, e in d.idle_gaps())
    assert idle + d.busy_ps() == d.window_ps
    assert {n for n, _ in trace.idle_by_annotation()} <= {
        "bench.window", "bench.update_call", "bench.sync", "(none)"
    }
    # utilisation comes from the trace's busy time: at this toy size a
    # fraction of a percent of the v5e, and above what the same FLOPs over
    # the whole window (idle gaps included) would give
    ev = {
        "trace": trace, "traced_updates": updates, "chips": 1,
        "geometry": {"num_envs": 16, "unroll_len": 8, "rollout_on_device": True},
        "model": {"torso": "impala_cnn", "channels": [16, 32, 32],
                  "obs_shape": [84, 84, 4], "num_actions": 6},
        "peaks": device.peaks("TPU v5 lite"),
    }
    util = readers.model_flops_util(ev)
    over_window = util * trace.busy_s / trace.window_s
    assert 0 < over_window < util < 100
