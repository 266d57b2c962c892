"""chipbench/trace.py: the reduction from a profiler trace to busy time,
idle share, per-program busy time, exposed collectives and named idle gaps,
on a small trace written here in the profiler's own format (times in µs
below; the trace holds ns)."""
import pytest

from chipbench import trace as tr

US = 1_000_000  # ps per µs


def _plane(pid, name, lines, names):
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in enumerate(names, start=1))
    body = ""
    for lid, (lname, events) in enumerate(lines, start=1):
        evs = "".join(f"    events {{ metadata_id: {names.index(n) + 1} "
                      f"offset_ps: {int(s * US)} duration_ps: {int(d * US)} }}\n"
                      for n, s, d in events)
        body += f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{evs}  }}\n'
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def _device(pid, ops, modules):
    names = sorted({n for n, _, _ in ops + modules})
    return _plane(pid, f"/device:TPU:{pid - 1}",
                  [("XLA Ops", ops), ("XLA Modules", modules)], names)


# window 0..100 µs. Device 0 runs one train step (10..60) holding a matmul
# (10..30), an all-reduce (25..45, 5 µs of it beside the matmul) and a
# fusion (50..60); device 1 the same, its all-reduce fully hidden (25..30).
# The host makes a batch (60..90) and reads the loss (0..10).
HOST = _plane(9, "/host:CPU", [("python", [
    ("chipbench.window", 0, 100), ("loss_read", 0, 10), ("input", 60, 30),
    ("step", 9, 1)])], ["chipbench.window", "loss_read", "input", "step"])
DEV0 = _device(1, [("dot.1", 10, 20), ("all-reduce.2", 25, 20), ("fusion.3", 50, 10)],
               [("jit_train_step(7)", 10, 50)])
DEV1 = _device(2, [("dot.1", 10, 20), ("all-reduce.2", 25, 5), ("fusion.3", 50, 10)],
               [("jit_train_step(7)", 10, 50)])


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """The trace as the profiler writes it (a ``.xplane.pb`` under
    ``plugins/profile/<run>/``), read back by ``trace.load``."""
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace")
    run = d / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    blob = ProfileData.text_proto_to_serialized_xspace(HOST + DEV0 + DEV1)
    (run / "host.xplane.pb").write_bytes(blob)
    return tr.load(str(d))


def test_interval_arithmetic():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == [(0, 4), (5, 10)]
    assert tr.total([(0, 4), (5, 10)]) == 9
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (8, 22), (25, 26)]) == \
        [(0, 2), (3, 8), (22, 25), (26, 30)]
    assert tr.gaps([(10, 20), (15, 30)], 0, 40) == [(0, 10), (30, 40)]


def test_busy_union_and_idle_share(events):
    red = tr.reduce(events, ("loss_read", "input", "step"))
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(100e-6)
    # device 0: 10..45 and 50..60 = 45 µs; device 1: 10..30 and 50..60 = 30
    assert red["busy_s"] == pytest.approx((45 + 30) / 2 * 1e-6)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.625)


def test_per_step_busy(events):
    red = tr.reduce(events, ())
    runs, busy, wall = tr.module_stats(red, "train_step")
    assert runs == 1
    assert busy == pytest.approx(37.5e-6) and wall == pytest.approx(50e-6)
    assert tr.module_stats(red, "decode_step") == (0.0, 0.0, 0.0)


def test_runs_told_apart_by_dispatch_order():
    host = [tr.Event("/host:CPU", "python", n, a, b) for n, a, b in (
        (tr.WINDOW, 0, 100), ("prefill", 1, 2), ("decode", 30, 31), ("decode", 60, 61))]
    dev = [tr.Event("/device:TPU:0", line, n, a, b) for line, n, a, b in (
        ("XLA Modules", "jit__unknown(1)", 5, 25), ("XLA Ops", "fusion.1", 5, 20),
        ("XLA Modules", "jit__unknown(1)", 32, 40), ("XLA Ops", "fusion.2", 32, 40),
        ("XLA Modules", "jit_argmax(2)", 41, 42), ("XLA Ops", "reduce.1", 41, 42),
        ("XLA Modules", "jit__unknown(1)", 62, 70), ("XLA Ops", "fusion.2", 62, 66))]
    red = tr.reduce(host + dev, ("prefill", "decode"))
    labels = ("prefill", "decode")
    assert tr.runs_by_dispatch(red, "prefill", labels) == [pytest.approx((15e-9, 20e-9))]
    assert tr.runs_by_dispatch(red, "decode", labels) == [
        pytest.approx((8e-9, 8e-9)), pytest.approx((4e-9, 8e-9))]
    red["dispatches"].append("decode")  # a dispatch whose run is missing
    assert tr.runs_by_dispatch(red, "decode", labels) == []


def test_exposed_collectives(events):
    red = tr.reduce(events, ())
    # device 0: 25..45 in collectives, 25..30 beside the matmul -> 15 exposed;
    # device 1: 25..30, all beside the matmul -> 0
    assert red["collective_s"] == pytest.approx((20 + 5) / 2 * 1e-6)
    assert red["collective_exposed_s"] == pytest.approx(7.5e-6)


def test_gaps_named_by_host_span(events):
    red = tr.reduce(events, ("loss_read", "input", "step"))
    gaps = {(name, round(s * 1e6)) for name, s in red["idle_gaps"]}
    # device 0 idles 0..10 (loss read), 45..50 (no span) and 60..100 (the
    # batch being made covers 30 of its 40 µs)
    assert gaps == {("loss_read", 10), ("none", 5), ("input", 40)}
    assert red["idle_gaps"][0][0] == "input"
    top = dict(red["top_ops"])
    assert top["dot.1"] == pytest.approx(20e-6)
    assert top["all-reduce.2"] == pytest.approx(12.5e-6)


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device"):
        tr.reduce([tr.Event("/host:CPU", "python", tr.WINDOW, 0.0, 10.0)])
    with pytest.raises(ValueError, match="window"):
        tr.reduce([tr.Event("/device:TPU:0", "XLA Ops", "dot", 0.0, 1.0)])


def test_collective_exposed_reader(events):
    """``collective_exposed_ms.train`` is the exposed collective time per
    run of the train step: 7.5 µs over the chips' one run; a trace with no
    collective gives nothing to read."""
    from chipbench import run

    red = tr.reduce(events, ())
    assert run._read_metric("collective_exposed_ms.train", {"trace": red}) == \
        pytest.approx(7.5e-3)
    quiet = [ev for ev in events if not ev.name.startswith("all-reduce")]
    red = tr.reduce(quiet, ())
    assert red["collective_s"] == 0
    assert run._read_metric("collective_exposed_ms.train", {"trace": red}) is None
