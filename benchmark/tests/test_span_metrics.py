"""The four readers of the metrics that read the program's spans and its
per-phase and compile counters, on hand-made evidence: what each reads, and
that each reads nothing (never 0) where the program has no such counter,
as the commit before the spans has not."""
import pytest

from benchmark.lib import manifest as M
from benchmark.metrics import (counter_at_start, counter_delta,
                               counter_per_batch, idle_gap_share)

C0 = {"input_train_batches_total": 32.0, "step_seconds_total": 11.0,
      "data_wait_seconds_total": 5.0,
      "input_train_load_seconds_total": 1.0,
      "input_train_mixup_seconds_total": 6.0,
      "compiles_total": 40.0, "jax_trace_seconds_total": 30.0,
      "jax_lower_seconds_total": 12.0, "backend_compile_seconds_total": 9.0}
C1 = dict(C0, **{"input_train_batches_total": 72.0,
                 "step_seconds_total": 24.0,
                 "input_train_load_seconds_total": 1.4,
                 "input_train_mixup_seconds_total": 14.0})
OLD = {"input_train_batches_total": 72.0, "step_seconds_total": 9.0,
       "data_wait_seconds_total": 5.0}
GAPS = [["python:dfd.input.host_wait", 0.16], ["python:dfd.input.stage", 0.02],
        ["futex-default-SDomainT/3396:tpu::System::Execute__Done", 0.02]]


def ev(c0=C0, c1=C1, gaps=GAPS):
    return {"counters0": c0, "counters1": c1,
            "trace": None if gaps is None else {"idle_gaps": gaps}}


def test_counter_per_batch():
    args = {"counter": "input_train_load_seconds_total",
            "batches": "input_train_batches_total", "scale": 1000.0}
    assert counter_per_batch.read(ev(), **args) == pytest.approx(10.0)
    assert counter_per_batch.read(
        ev(), **dict(args, counter="input_train_mixup_seconds_total")) \
        == pytest.approx(200.0)
    assert counter_per_batch.read(ev(c0=OLD, c1=OLD), **args) is None
    assert counter_per_batch.read(ev(c1=C0), **args) is None   # no batch
    assert counter_per_batch.read({}, **args) is None


def test_counter_delta():
    assert counter_delta.read(ev(), counter="compiles_total") == 0.0
    assert counter_delta.read(
        ev(c1=dict(C1, compiles_total=42.0)), counter="compiles_total") == 2.0
    assert counter_delta.read(ev(c0=OLD, c1=OLD),
                              counter="compiles_total") is None
    assert counter_delta.read({}, counter="compiles_total") is None


def test_counter_at_start():
    both = ["jax_trace_seconds_total", "jax_lower_seconds_total"]
    assert counter_at_start.read(ev(), counters=both) == pytest.approx(42.0)
    assert counter_at_start.read(
        ev(), counters=["backend_compile_seconds_total"]) == 9.0
    assert counter_at_start.read(ev(c0=OLD), counters=both) is None
    assert counter_at_start.read({}, counters=both) is None


def test_idle_gap_share():
    assert idle_gap_share.read(ev(), contains="dfd.") == pytest.approx(90.0)
    assert idle_gap_share.read(ev(), contains="dfd.input.host_wait") \
        == pytest.approx(80.0)
    unnamed = [["no host span (the program has no TraceAnnotation)", 0.17]]
    assert idle_gap_share.read(ev(gaps=unnamed), contains="dfd.") == 0.0
    assert idle_gap_share.read(ev(gaps=[]), contains="dfd.") is None
    assert idle_gap_share.read(ev(gaps=None), contains="dfd.") is None


def test_the_manifest_finds_each_new_metric_and_leaves_out_what_is_missing():
    man = M.manifest()
    flagship, b4 = (M.Cell(n, man) for n in ("train_flagship_600",
                                             "train_b4_380"))
    new = {"input_load_ms.train", "input_collate_ms.train",
           "input_mixup_ms.train", "input_stage_ms.train",
           "idle_attributed_share.train", "idle_input_starved_share.train",
           "window_compiles.train", "setup_trace_lower_s.train",
           "setup_backend_compile_s.train"}
    names = lambda cell: {m["name"] for m in cell.per_layer()}  # noqa: E731
    assert new <= names(flagship)
    assert new - names(b4) == {"input_mixup_ms.train"}
    # evidence of a program without the counters and with no trace: none of
    # the new metrics is in the line, and none raised
    got = M.read_per_layer(flagship, {"counters0": OLD, "counters1": OLD,
                                      "trace": {}})
    assert not new & set(got)
    got = M.read_per_layer(flagship, ev())
    assert got["input_mixup_ms.train"]["value"] == pytest.approx(200.0)
    assert got["window_compiles.train"] == {"value": 0.0, "unit": "count"}
