"""The reduction from trace to metrics, on a hand-made trace with known
answers and on a short piece of a trace recorded on a TPU v5e."""
import os

import pytest

from bench import readers, trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_small.json.gz")


def _hand_made():
    # ns. Two steps: a prefill program 100-300 with ops 100-180, 150-250
    # (overlapping) and a decode program 500-600 with op 500-560.
    ops = [["%fusion.1 = f32[] fusion()", 100, 80],
           ["%fusion.2 = f32[] fusion()", 150, 100],
           ["%while.7 = f32[] while()", 500, 60]]
    modules = [["jit_chunk_step(123)", 100, 200], ["jit_decode_step(9)", 500, 100]]
    spans = [["bench.step#0", 50, 300], ["bench.idle", 350, 100],
             ["bench.step#1", 450, 200], ["bench.submit", 400, 20]]
    return T.Events(ops, modules, sorted(spans, key=lambda s: s[1]))


def test_busy_is_the_union_of_device_operations():
    red = T.reduce(_hand_made())
    assert red["window_s"] == pytest.approx(600e-9)          # 50 .. 650
    assert red["busy_s"] == pytest.approx((150 + 60) * 1e-9)  # 100-250, 500-560


def test_busy_is_the_mean_over_the_cells_devices():
    ev = _hand_made()
    ev.more_ops = [[[100, 300]], [[0, 1000]]]     # 100-400; the whole window
    red = T.reduce(ev)
    assert red["busy_by_device"] == pytest.approx([210e-9, 300e-9, 600e-9])
    assert red["busy_s"] == pytest.approx(370e-9)
    assert red["steps"] == T.reduce(_hand_made())["steps"]    # first device's


def test_prefill_mfu_is_a_share_of_the_whole_meshs_peak():
    class Family:
        @staticmethod
        def prefill_flops(dims, n):
            return 1e3 * n

    run = {"steps": [{"kind": "prefill", "span_s": 1.0, "busy_s": 1.0,
                      "prefill_device_s": 1e-6, "prompt_lens": [10]}],
           "family": Family, "dims": {}, "peaks": {"bf16_flops_per_s": 1e12},
           "cell": {"chips": 1}}
    assert readers.prefill_mfu(run) == pytest.approx(1.0)
    run["cell"]["chips"] = 4
    assert readers.prefill_mfu(run) == pytest.approx(0.25)


def test_device_time_is_grouped_by_program_and_operation():
    red = T.reduce(_hand_made())
    assert red["modules"] == {"jit_chunk_step": pytest.approx(200e-9),
                              "jit_decode_step": pytest.approx(100e-9)}
    ops = dict(red["top_ops"])
    assert ops["jit_chunk_step:fusion"] == pytest.approx(180e-9)
    assert ops["jit_decode_step:while"] == pytest.approx(60e-9)


def test_idle_gaps_are_named_by_the_covering_span():
    red = T.reduce(_hand_made())
    idle = dict(red["idle_by_span"])
    # step#0 covers 50-100 and 250-350 idle; idle span 350-400 and 420-450;
    # submit (innermost, started later) 400-420; step#1 450-500, 560-650
    assert idle["bench.step"] == pytest.approx((50 + 100 + 50 + 90) * 1e-9)
    assert idle["bench.idle"] == pytest.approx((50 + 30) * 1e-9)
    assert idle["bench.submit"] == pytest.approx(20e-9)
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def test_steps_carry_their_busy_time_and_programs():
    red = T.reduce(_hand_made())
    s0, s1 = red["steps"]
    assert s0["index"] == 0 and s0["span_s"] == pytest.approx(300e-9)
    assert s0["busy_s"] == pytest.approx(150e-9)
    assert s0["programs"] == {"jit_chunk_step": pytest.approx(200e-9)}
    assert s1["busy_s"] == pytest.approx(60e-9)

    class H:
        def __init__(self, kind, lens):
            self.kind, self.prompt_lens = kind, lens

    steps = T.join_steps(red, [H("prefill", [10]), H("decode", [])])
    run = {"steps": steps, "trace": red}
    assert readers.mean_span_ms(run, "decode") == pytest.approx(200e-6)
    assert readers.mean_host_ms(run, "decode") == pytest.approx(140e-6)
    assert readers.idle_share(run) == pytest.approx(100 * (1 - 210 / 600))
    assert readers.mean_span_ms(run, "none") is None


def test_recorded_trace_reduces_consistently():
    ev = T.load_json(DATA)
    red = T.reduce(ev)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] <= sum(red["modules"].values()) + 1e-9
    idle = sum(v for _, v in red["idle_by_span"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert {"jit_chunk_step", "jit_decode_step"} & set(red["modules"])
    for st in red["steps"]:
        assert 0 <= st["busy_s"] <= st["span_s"] + 1e-9
    assert os.path.getsize(DATA) < 1 << 20
