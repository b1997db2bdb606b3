"""The purification cell on the CPU at a tiny size: a sound run is
correct; the control and each fault the cell can have are not."""
import dataclasses

import jax.numpy as jnp
import pytest

WORKLOAD = "h2o_purify_1chip"


@pytest.fixture
def fresh_programs():
    from repro.core import plan

    plan.clear_cache()
    yield
    plan.clear_cache()


def test_sound_run_is_correct(bench_run, fresh_programs):
    line = bench_run.run(WORKLOAD)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"purify_s", "hbm_peak_gb", "setup_s"} - {
        "hbm_peak_gb"}  # the CPU reports no device memory
    assert list(line)[-1] == "checks"


def test_control_is_not_correct(bench_run, fresh_programs):
    line = bench_run.run(WORKLOAD, control=True)
    assert not line["correct"], line["checks"]


def _broken_sweep(monkeypatch, fault):
    from repro.core import signiter

    make = signiter._make_sweep

    def broken(*args, **kw):
        sweep = make(*args, **kw)

        def run(xb, xm, xn, ib, im):
            cb, cm, cn, res, occ = sweep(xb, xm, xn, ib, im)
            if fault == "unchanged":
                return xb, xm, xn, res, occ
            h = xb.shape[0] // 2  # "half": the second half of the rows stays
            return (cb.at[h:].set(xb[h:]), cm.at[h:].set(xm[h:]),
                    cn.at[h:].set(xn[h:]), res, occ)
        return run

    monkeypatch.setattr(signiter, "_make_sweep", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_broken_sweep_is_not_correct(bench_run, fresh_programs, monkeypatch,
                                     fault):
    _broken_sweep(monkeypatch, fault)
    line = bench_run.run(WORKLOAD)
    assert not line["correct"], line["checks"]


def test_altered_answer_is_not_correct(bench_run, fresh_programs,
                                       monkeypatch):
    from repro.core import signiter

    density_matrix = signiter.density_matrix

    def altered(*args, **kw):
        p, stats = density_matrix(*args, **kw)
        # one diagonal block of P off by 1%
        return dataclasses.replace(
            p, blocks=p.blocks.at[0, 0].multiply(jnp.float32(1.01))), stats

    monkeypatch.setattr(signiter, "density_matrix", altered)
    line = bench_run.run(WORKLOAD)
    assert not line["correct"], line["checks"]
