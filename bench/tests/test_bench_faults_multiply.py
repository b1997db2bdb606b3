"""The multiply cell on the CPU at a tiny size: a sound run is correct;
the control and each fault the cell can have are not."""
import dataclasses

import jax.numpy as jnp
import pytest

WORKLOAD = "dense_multiply_1chip"


def test_sound_run_is_correct(bench_run):
    line = bench_run.run(WORKLOAD)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"multiply_s", "setup_s"}


def test_traced_run_reports_per_layer_metrics(bench_run):
    line = bench_run.run(WORKLOAD, trace=1)
    assert line["correct"], line["checks"]
    # the CPU trace has no TPU plane: the device metrics find nothing
    assert set(line["metrics"]) == {"compiles_in_window.multiply",
                                    "host_call_s.multiply"}
    assert line["metrics"]["compiles_in_window.multiply"]["value"] == 0
    assert "busy_s" in line["device"] and "breakdown" in line


def test_control_is_not_correct(bench_run):
    line = bench_run.run(WORKLOAD, control=True)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(bench_run, monkeypatch, fault):
    from repro.core import engine

    multiply = engine.multiply

    def broken(a, b, *args, **kw):
        c = multiply(a, b, *args, **kw)
        if fault == "unchanged":
            return a
        if fault == "half":
            h = c.blocks.shape[0] // 2
            return dataclasses.replace(c, blocks=c.blocks.at[h:].set(0.0))
        # one block of C off by 1%
        return dataclasses.replace(
            c, blocks=c.blocks.at[0, 0].multiply(jnp.float32(1.01)))

    monkeypatch.setattr(engine, "multiply", broken)
    line = bench_run.run(WORKLOAD)
    assert not line["correct"], line["checks"]
