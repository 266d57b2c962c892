"""chipbench/scopes.py: device time by the program's layer scopes, its host
spans, idle gaps named by them, the idle time under each span and the gaps
between a program's runs, on hand-built traces (times in ns); and
chipbench/breakdown.py end to end at the program's reduced sizes on the CPU."""
import os

import pytest

from chipbench import breakdown, run
from chipbench import scopes as sc
from chipbench import trace as tr

SEED = 2**31 + 12345


def _ev(plane, line, name, a, b):
    return tr.Event(plane, line, name, float(a), float(b))


def _op(name, a, b, dev=0):
    return _ev(f"/device:TPU:{dev}", tr.OPS_LINE, name, a, b)


def _module(name, a, b, dev=0):
    return _ev(f"/device:TPU:{dev}", tr.MODULES_LINE, name, a, b)


def _host(name, a, b):
    return _ev("/host:CPU", "python", name, a, b)


PATHS = {
    "while.1": "jit(train_step)/jvp()/while",
    "fusion.1": "jit(train_step)/jvp()/while/body/closed_call/attn/dot_general",
    "fusion.2": "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/ffn/mul",
    "fusion.3": "jit(train_step)/transpose(jvp(head))/dot_general",
    "fusion.4": "jit(train_step)/optimizer/sqrt",
    "fusion.5": "jit(train_step)/jvp(embed)/jit(silu)/logistic",
    "copy.1": "jit(train_step)/attn/closed_call/while/body/ssd",
}


def test_scope_of_takes_the_innermost_known_name():
    assert [sc.scope_of(PATHS[k]) for k in sorted(PATHS)] == [
        "ssd", "attn", "ffn", "head", "optimizer", "embed", "other"]
    assert sc.scope_of("") == "other"
    assert sc.scope_of("jit(f)/transpose(jvp(attention))/dot") == "other"


def test_nested_ops_are_counted_once():
    """A ``while`` holds two body ops; only the loop's own time is its own.
    Device 1 runs the same with its body ops 10 ns shorter."""
    events = [_host(tr.WINDOW, 0, 1000)]
    for dev, cut in ((0, 0), (1, 10)):
        events += [_op("while.1", 100, 400, dev), _op("fusion.1", 120, 200 - cut, dev),
                   _op("fusion.2", 250, 380 - cut, dev), _op("fusion.3", 500, 600, dev),
                   _op("fusion.4", 600, 650, dev), _op("mystery.9", 700, 750, dev)]
    got = sc.scopes(events, PATHS, 0, 1000)
    assert got["attn"] == pytest.approx((80 + 70) / 2 * 1e-9)
    assert got["ffn"] == pytest.approx((130 + 120) / 2 * 1e-9)
    assert got["head"] == pytest.approx(100e-9) and got["optimizer"] == pytest.approx(50e-9)
    # the loop's own 90 (100) ns and the op of no known name
    assert got["other"] == pytest.approx((90 + 110) / 2 * 1e-9 + 50e-9)
    assert got["ssd"] == got["moe"] == got["embed"] == 0.0
    # nothing counted twice: the scopes add up to the devices' busy time
    assert sum(got.values()) == pytest.approx(tr.reduce(events)["busy_s"])


def test_scopes_clip_to_the_window():
    events = [_host(tr.WINDOW, 150, 1000), _op("while.1", 100, 400),
              _op("fusion.1", 120, 200), _op("fusion.2", 250, 380)]
    got = sc.scopes(events, PATHS, 150, 1000)
    assert got["attn"] == pytest.approx(50e-9)
    assert got["ffn"] == pytest.approx(130e-9)
    assert got["other"] == pytest.approx(70e-9)


def test_op_paths_from_hlo_text():
    text = """
  %fusion.12 = bf16[4,8]{1,0} fusion(%p.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/jvp(head)/dot_general" stack_frame_id=4}
  ROOT %while.2 = (s32[]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp()/while"}
  %copy.1 = f32[2]{0} copy(%x)
"""
    assert sc.op_paths(text) == {"fusion.12": "jit(train_step)/jvp(head)/dot_general",
                                 "while.2": "jit(train_step)/jvp()/while"}


# one decode batch on device 0: the prefill (10..100) and two decode runs
# (130..150, 160..185); the host samples, reads and dispatches between them
SERVE = [
    _host(tr.WINDOW, 0, 300),
    _host("serve.init_caches", 2, 8), _host("serve.prefill", 8, 12),
    _host("serve.sample", 12, 14), _host("serve.host_read", 14, 104),
    _host("serve.decode", 104, 128), _host("serve.sample", 128, 130),
    _host("serve.host_read", 130, 152), _host("serve.decode", 152, 158),
    _host("data.next_batch", 200, 260), _host("search", 262, 298),
    _host("search.eta_model", 265, 290), _host("decode", 105, 127),
    _module("jit__unknown(1)", 10, 100), _op("fusion.7", 10, 100),
    _module("jit__unknown(1)", 130, 150), _op("fusion.8", 130, 140), _op("fusion.9", 142, 150),
    _module("jit_argmax(2)", 151, 152), _op("reduce.1", 151, 152),
    _module("jit__unknown(1)", 160, 185), _op("fusion.8", 160, 185),
]


def test_program_spans_by_name():
    got = sc.program_spans(SERVE, 0, 300)
    assert set(got) == {"serve.init_caches", "serve.prefill", "serve.sample",
                        "serve.host_read", "serve.decode", "data.next_batch",
                        "search", "search.eta_model"}
    assert got["serve.host_read"] == pytest.approx([90e-9, 22e-9])
    assert got["search.eta_model"] == pytest.approx([25e-9])
    assert sc.program_spans(SERVE, 150, 300)["serve.decode"] == pytest.approx([6e-9])


def test_idle_gaps_named_by_the_innermost_program_span():
    got = {(name, round(s * 1e9)) for name, s in sc.idle_gaps_program(SERVE, 0, 300)}
    # 0..10: init_caches 6, prefill 2 -> init_caches; 100..130: decode 24 ns;
    # 140..142 and 150..151 under a host read; 152..160 decode; 185..300: search holds
    # 36 ns of it, data.next_batch 60, so the batch; the eta model nests in
    # the search but covers less
    assert got == {("serve.init_caches", 10), ("serve.decode", 30), ("serve.host_read", 2),
                   ("serve.host_read", 1), ("serve.decode", 8), ("data.next_batch", 115)}
    assert sc.idle_gaps_program(SERVE, 0, 300, n=1) == [["data.next_batch", pytest.approx(115e-9)]]
    # a gap that two nested spans cover alike goes to the inner one
    nested = [_host(tr.WINDOW, 0, 100), _host("search", 0, 100),
              _host("search.eta_model", 10, 90), _op("fusion.1", 0, 20), _op("fusion.2", 80, 100)]
    assert sc.idle_gaps_program(nested, 0, 100) == [["search.eta_model", pytest.approx(60e-9)]]


def test_idle_time_under_each_span():
    got = sc.idle_under(SERVE, 0, 300)
    # the device idles 0..10, 100..130, 140..142, 150..151, 152..160, 185..300
    assert got == {"serve.init_caches": pytest.approx(6e-9),
                   "serve.prefill": pytest.approx(2e-9), "serve.sample": pytest.approx(2e-9),
                   "serve.host_read": pytest.approx(7e-9), "serve.decode": pytest.approx(30e-9),
                   "data.next_batch": pytest.approx(60e-9), "search": pytest.approx(36e-9),
                   "search.eta_model": pytest.approx(25e-9)}


def test_gaps_between_runs_of_one_program():
    # 100..130 idle 30; 150..160 holds the argmax's 1 ns
    assert sc.run_gaps(SERVE, r"^jit__unknown", 0, 300) == [
        pytest.approx(30e-9), pytest.approx(9e-9)]
    assert sc.run_gaps(SERVE, r"^jit_serve_decode", 0, 300) == []


@pytest.mark.parametrize("workload", [w["name"] for w in run._load_json(
    os.path.join(run.ROOT, "BENCHMARK.json"))["workloads"]])
def test_breakdown_rehearsal(run_dirs, workload):
    """The tool end to end at reduced sizes on the CPU: its line's keys, and
    a train step's time split by scope with nothing lost (the CPU trace has
    no program runs, so the scopes' sum stands in for the step's busy time)."""
    out = breakdown.breakdown(workload, SEED, rehearse=True)
    assert out["device"] == "cpu" and out["busy_ms"] > 0
    assert out["idle_gaps_program"] and out["program_spans"]
    if "train" in workload:
        assert set(out["program_spans"]) == {"data.next_batch"}
        assert out["scopes_sum_ms_per_step"] == pytest.approx(out["busy_ms"] / out["steps"])
        assert set(out["scopes_ms_per_step"]) == set(sc.SCOPES) | {"other"}
    else:
        assert set(out["program_spans"]) == {
            "serve.init_caches", "serve.prefill", "serve.sample", "serve.host_read",
            "serve.decode"}
        assert out["token_gap_median_ms"] > 0
