"""The trace reduction on small recorded traces (XSpaces written as text):
one TPU plane with an ``XLA Ops`` line of five operations and one host span;
and one with four launches of a step on its ``XLA Modules`` line."""
import os

from benchmark.lib import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))


GROUPS = [["conv_dw", "blocks_.*conv_dw"], ["conv_pw", "blocks_.*conv_pw"]]


def _load(name="trace.textproto"):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "tiny", name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = os.path.join(HERE, "tiny", ".trace.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    try:
        return TR.read_device_events(path)
    finally:
        os.remove(path)


def test_reduction_of_the_recorded_trace():
    ev = _load()
    assert len(ev["chips"]) == 1 and len(ev["chips"][0]["ops"]) == 5
    red = TR.reduce_events(ev, groups=GROUPS)
    assert red["steps"] is None           # no launches: the whole extent
    # ops (ms): [0,10) [5,20) overlap -> 20 busy; [30,40); [40,45); [90,100)
    assert abs(red["busy_s"] - 0.045) < 1e-9
    assert abs(red["window_s"] - 0.100) < 1e-9
    assert red["has_paths"]
    assert abs(red["by_group"]["conv_dw"] - 0.025) < 1e-9
    assert abs(red["by_group"]["conv_pw"] - 0.010) < 1e-9
    assert abs(red["by_group"]["other"] - 0.015) < 1e-9
    assert red["device_ops"][0][0] == "fusion.1"
    gaps = red["idle_gaps"]
    assert abs(gaps[0][1] - 0.045) < 1e-9 and gaps[0][0].endswith("next_batch")
    assert abs(gaps[1][1] - 0.010) < 1e-9


def test_steady_span_runs_from_the_second_launch_of_the_step_to_its_last():
    red = TR.reduce_events(_load("trace_steps.textproto"), groups=GROUPS)
    # jit_step launched at 2, 22, 42, 62 ms; the first may be cut short by
    # the trace's start, so the span is 22..62: two whole periods of 20 ms,
    # each with 5 + 3 ms of the step and 1 ms of the prologue's copy
    assert red["step_module"] == "jit_step" and red["steps"] == 2
    assert abs(red["window_s"] - 0.040) < 1e-9
    assert abs(red["busy_s"] - 0.018) < 1e-9
    assert abs(red["by_group"]["conv_dw"] - 0.010) < 1e-9
    assert red["modules"]["jit_step"] == {"count": 2, "mean_s": 0.008}
    assert abs(max(g[1] for g in red["idle_gaps"]) - 0.009) < 1e-9
    from benchmark.metrics import device_idle_share
    assert abs(device_idle_share.read({"trace": red}) - 55.0) < 1e-6


def test_groups_come_from_the_configuration_most_specific_first():
    groups = [["conv_pwl", "blocks_.*conv_pwl"], ["conv_pw", "blocks_.*conv_pw"]]
    assert TR.group_of("a/blocks_1_0/conv_pwl/conv", groups) == "conv_pwl"
    assert TR.group_of("a/blocks_1_0/conv_pw/conv", groups) == "conv_pw"
    assert TR.group_of("a/attn/qkv", groups) == "other"
    assert TR.group_of("a/blocks_1_0/conv_pw/conv") == "other"


def test_union_counts_nested_intervals_once():
    total, gaps = TR._union([(0, 10), (2, 3), (9, 12), (20, 21)])
    assert total == 13 and gaps == [(12, 20)]


def test_no_device_plane_gives_nothing():
    assert TR.reduce_events({"chips": [], "host": []}) == {}


def test_hlo_paths_reads_op_names_from_a_compiled_modules_text():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("blocks_1_0/conv_dw"):
            return jnp.tanh(x @ x)
    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    paths = TR.hlo_paths(text)
    assert paths and any("conv_dw" in p for p in paths.values())
    assert all(k.startswith("%") for k in paths)
    assert TR._short("%fusion.28 = (f32[2]) fusion(f32[2] %p), kind=kLoop") \
        == "%fusion.28"
