"""Autotuning runtime: features, candidate model, pruning soundness, the
tuning DB, the corpus, and the all-caches clear_cache contract.

The candidate model and the Eq. (6) memory prune are *analytic* — they
are property-tested here on abstract meshes (no devices needed), across
rectangular grids and uneven depths.  End-to-end ``engine="auto"``
resolution runs on a real 1x1 mesh (single CPU device); the multi-device
behavior is covered by tests/_dist.py::check_tuner_auto.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro import tuner
from repro.core import bsm as B
from repro.core import plan as plan_mod
from repro.core.commvolume import device_memory_bytes
from repro.core.engine import multiply, multiply_reference
from repro.tuner import (
    Candidate,
    TuningDB,
    autotune,
    feature_bucket,
    featurize,
    rank_candidates,
)
from repro.tuner.corpus import corpus, make_mask
from repro.tuner.db import make_key
from repro.launch.mesh import make_mesh
from repro.tuner.model import (
    enumerate_candidates,
    estimate_candidate,
    valid_square_depths,
)


class FakeMesh:
    """Mesh stand-in for analytic-only tuning: axis names + sizes, no
    devices.  Hash/eq by shape so ``plan_multiply``'s LRU treats equal
    shapes as one topology."""

    def __init__(self, **shape: int):
        self._shape = dict(shape)

    @property
    def axis_names(self):
        return tuple(self._shape)

    @property
    def shape(self):
        return dict(self._shape)

    def __hash__(self):
        return hash(tuple(self._shape.items()))

    def __eq__(self, other):
        return isinstance(other, FakeMesh) and other._shape == self._shape


def _pair(nb=8, bs=4, occupancy=0.2, seed=0, pattern="decay"):
    a = B.random_bsm(jax.random.key(seed), nb=nb, bs=bs,
                     occupancy=occupancy, pattern=pattern)
    b = B.random_bsm(jax.random.key(seed + 1), nb=nb, bs=bs,
                     occupancy=occupancy, pattern=pattern)
    return a, b


def _ok_cube(a, b):
    am, bm = np.asarray(a.mask, bool), np.asarray(b.mask, bool)
    return am[:, :, None] & bm[None, :, :]


# ---- features --------------------------------------------------------------


def test_featurize_counts_match_cube():
    a, b = _pair(nb=10, bs=4, occupancy=0.3)
    f = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    # the boolean mask product is EXACT at threshold 0
    assert f.n_products == int(ok.sum())
    assert f.product_fill == pytest.approx(ok.mean())
    assert f.out_fill == pytest.approx(ok.any(axis=1).mean())
    assert f.occ_a == pytest.approx(np.asarray(a.mask).mean())


def test_featurize_bandwidth_banded():
    a = B.random_bsm(jax.random.key(0), nb=12, bs=4, occupancy=0.1,
                     pattern="banded", bandwidth=2)
    f = featurize(a, a, 0.0)
    assert f.bandwidth_a == pytest.approx(2 / 12)
    assert f.nb_r == f.nb_k == 12 and f.bs_r == 4


def test_feature_bucket_stable_and_discriminating():
    a, b = _pair(nb=8, occupancy=0.2, seed=0)
    f1 = featurize(a, b, 0.0)
    assert feature_bucket(f1) == feature_bucket(featurize(a, b, 0.0))
    big_a, big_b = _pair(nb=16, occupancy=0.2, seed=0)
    assert feature_bucket(f1) != feature_bucket(featurize(big_a, big_b, 0.0))


# ---- corpus ----------------------------------------------------------------


def test_corpus_masks():
    for kind in ("dft_chain", "exp_decay", "zipf"):
        m = make_mask(kind, 16, jax.random.key(3), occupancy=0.2, bandwidth=2)
        assert m.shape == (16, 16) and m.dtype == bool
        assert m[np.arange(16), np.arange(16)].all()  # dominant diagonal
        m2 = make_mask(kind, 16, jax.random.key(3), occupancy=0.2, bandwidth=2)
        np.testing.assert_array_equal(m, m2)  # deterministic per key


def test_corpus_zipf_is_heavy_tailed():
    m = make_mask("zipf", 32, jax.random.key(0), occupancy=0.15,
                  zipf_alpha=1.4)
    rows = m.sum(axis=1)
    assert rows.max() >= 4 * np.median(rows)  # hub rows dominate


def test_corpus_entries_build():
    for entry in corpus(smoke=True):
        a, b = entry.build()
        if entry.kind == "three_center":  # matricized: (nb^2, nb) grid
            assert (a.nb_r, a.nb_c) == (entry.nb**2, entry.nb)
            assert (a.bs_r, a.bs_c) == (entry.bs**2, entry.bs)
        else:
            assert a.nb_r == entry.nb and a.bs_r == entry.bs
        a2, b2 = entry.build()
        np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(a2.mask))
        if entry.symmetric:  # DFT families: symmetric H, B is H
            np.testing.assert_array_equal(
                np.asarray(a.mask), np.asarray(a.mask).T)


# ---- candidate enumeration -------------------------------------------------


def test_valid_square_depths():
    assert valid_square_depths(2) == [4]
    assert valid_square_depths(4) == [4, 16]
    assert valid_square_depths(6) == [4, 9, 36]
    assert valid_square_depths(3) == [9]


def test_enumerate_square_vs_rectangular():
    a, b = _pair(nb=8)
    f = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    sq = enumerate_candidates(FakeMesh(r=2, c=2), f, ok=ok)
    engines = {(c.engine, c.l) for c in sq}
    assert ("cannon", None) in engines and ("twofive", 4) in engines
    rect = enumerate_candidates(FakeMesh(r=2, c=4), f, ok=ok)
    engines = {(c.engine, c.l) for c in rect}
    assert ("cannon", None) not in engines  # square grids only
    assert ("twofive", 2) in engines  # forced L = mx/mn
    # mx > mn^2: the paper's rule forbids a 2.5D factorization
    wide = enumerate_candidates(FakeMesh(r=2, c=8), f, ok=ok)
    assert all(c.engine != "twofive" for c in wide)
    stacked = enumerate_candidates(FakeMesh(l=2, r=2, c=2), f, ok=ok)
    assert {c.engine for c in stacked} == {"twofive"}


def test_enumerate_respects_constraints():
    a, b = _pair(nb=8)
    f = featurize(a, b, 0.0)
    only = enumerate_candidates(FakeMesh(r=2, c=2), f,
                                engines=("gather",), backends=("jnp",))
    assert {(c.engine, c.backend) for c in only} == {("gather", "jnp")}
    # without a concrete cube there is no sound capacity: compacted
    # backends must be skipped, never guessed
    nocube = enumerate_candidates(FakeMesh(r=2, c=2), f,
                                  backends=("jnp", "stacks"))
    assert {c.backend for c in nocube} == {"jnp"}


def test_enumerate_transport_dimension():
    """With a concrete cube the space doubles over transport modes (the
    capacities themselves come from the masks at execution); without one
    compressed transport is skipped like the compacted backends."""
    a, b = _pair(nb=8)
    f = featurize(a, b, 0.0)
    with_cube = enumerate_candidates(FakeMesh(r=2, c=2), f,
                                     ok=_ok_cube(a, b),
                                     engines=("gather",),
                                     backends=("jnp",))
    assert {c.transport for c in with_cube} == {"dense", "compressed"}
    nocube = enumerate_candidates(FakeMesh(r=2, c=2), f,
                                  engines=("gather",), backends=("jnp",))
    assert {c.transport for c in nocube} == {"dense"}
    pinned = enumerate_candidates(FakeMesh(r=2, c=2), f,
                                  ok=_ok_cube(a, b), engines=("gather",),
                                  backends=("jnp",),
                                  transports=("compressed",))
    assert {c.transport for c in pinned} == {"compressed"}
    # compressed candidates are labeled distinctly (the oracle tables in
    # bench_tuner key on labels)
    labels = {c.label for c in with_cube}
    assert labels == {"gather/jnp", "gather/jnp+ct"}


def test_compressed_transport_cheaper_at_low_fill():
    """The sparsity-aware volume model must rank compressed transport
    under dense for a low-occupancy pattern (Eq. (7) scaled by panel
    occupancy) and roughly tie at full occupancy."""
    a, b = _pair(nb=8, occupancy=0.08)
    f = featurize(a, b, 0.0)
    mesh = FakeMesh(r=2, c=2)
    dense = estimate_candidate(Candidate("gather"), mesh, f)
    comp = estimate_candidate(Candidate("gather", transport="compressed"),
                              mesh, f)
    assert comp.comm_s < dense.comm_s
    full_a, full_b = _pair(nb=8, occupancy=1.0)
    ff = featurize(full_a, full_b, 0.0)
    dense_f = estimate_candidate(Candidate("gather"), mesh, ff)
    comp_f = estimate_candidate(Candidate("gather", transport="compressed"),
                                mesh, ff)
    assert comp_f.comm_s >= 0.9 * dense_f.comm_s


def test_chain_safety_excludes_compressed_transport():
    from repro.tuner.model import chain_safe

    assert chain_safe(Candidate("gather"))
    assert not chain_safe(Candidate("gather", backend="stacks",
                                    stack_capacity=8))
    assert not chain_safe(Candidate("gather", transport="compressed"))
    # an envelope lifts the restriction: capacities derived from the
    # forecast union cube cover every sweep, so EVERY candidate is safe
    assert chain_safe(Candidate("gather", backend="stacks",
                                stack_capacity=8), envelope=True)
    assert chain_safe(Candidate("gather", transport="compressed"),
                      envelope=True)


def test_db_record_persists_transport(tmp_path):
    """The measured winner's transport mode rides the DB record, and a
    rehydrated record (even a pre-transport one) yields a valid
    candidate."""
    from repro.tuner import _db_candidate

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=4, occupancy=0.4)
    plan_mod.clear_cache()
    db = TuningDB(str(tmp_path / "db.json"))
    dec = autotune(a, b, mesh, db=db, top_k=2)
    assert len(db.records) == 1
    rec = next(iter(db.records.values()))
    assert rec["transport"] in ("dense", "compressed")
    assert rec["transport"] == dec.transport
    # a record written before the transport field reads as dense
    f = featurize(a, b, 0.0)
    legacy = {"engine": "gather", "l": None, "backend": "jnp"}
    cand = _db_candidate(legacy, _ok_cube(a, b), mesh, f)
    assert cand is not None and cand.transport == "dense"
    # schema drift: an unknown mode is a miss, not a crash
    assert _db_candidate({**legacy, "transport": "zstd"},
                         _ok_cube(a, b), mesh, f) is None


def test_pre_transport_db_records_still_warm_hit(tmp_path):
    """A tuning DB persisted BEFORE the transport layer (4-element
    constraint keys, records without a transport field) must still
    resolve measurement-free: the unpinned constraint shape is
    unchanged, and the record reads as dense transport."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=4, occupancy=0.4)
    f = featurize(a, b, 0.0)
    db = TuningDB(str(tmp_path / "db.json"))
    # the exact key shape PR 4 wrote: ("mult", "*", "*", 0), no transport
    old_key = make_key(feature_bucket(f),
                       tuner.mesh_signature(mesh)
                       if hasattr(tuner, "mesh_signature")
                       else tuple((n, int(mesh.shape[n]))
                                  for n in mesh.axis_names),
                       ("mult", "*", "*", 0), f.dtype)
    db.record(old_key, {"engine": "gather", "l": None, "backend": "jnp",
                        "measured_s": 1e-4})
    plan_mod.clear_cache()
    dec = autotune(a, b, mesh, db=db)
    assert dec.source == "db" and dec.engine == "gather"
    assert dec.transport == "dense"
    assert plan_mod.cache_stats()["tuner_trials"] == 0


# ---- Eq. (6) memory pruning: the property the tuner must never break -------

_MESHES = [
    {"r": 2, "c": 2},
    {"r": 2, "c": 4},
    {"r": 4, "c": 2},  # rectangular, forced virtual L = 2
    {"r": 6, "c": 2},  # rectangular with mx > mn^2: no 2.5D factorization
    {"r": 6, "c": 6},  # square with uneven L=9 (9 does not divide V=6)
    {"r": 2, "c": 8},  # no valid 2.5D factorization at all
    {"l": 2, "r": 2, "c": 2},
]


@settings(deadline=None, max_examples=40)
@given(
    mesh_shape=st.sampled_from(_MESHES),
    budget=st.sampled_from([3e5, 1e6, 5e6, 1e8]),
    occupancy=st.floats(min_value=0.05, max_value=0.6),
)
def test_prune_never_selects_over_budget(mesh_shape, budget, occupancy):
    """The tuner NEVER selects a candidate whose Eq. (6) footprint
    (incl. the device_stack_bound-sized stack arrays) exceeds the
    per-device budget — across rectangular meshes and uneven L; when
    nothing fits, it refuses rather than over-committing."""
    mesh = FakeMesh(**mesh_shape)
    a, b = _pair(nb=24, bs=4, occupancy=occupancy, seed=7)
    f = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    try:
        report = rank_candidates(mesh, f, ok=ok, budget_bytes=budget)
    except ValueError:
        # refusal is the sound outcome when every candidate is too big:
        # verify at least the cheapest engine really exceeds the budget
        est = estimate_candidate(Candidate("gather"), mesh, f,
                                 budget_bytes=budget)
        assert est.mem_bytes > budget
        return
    assert report.ranked, "feasible report must be non-empty"
    for est in report.ranked:
        assert est.feasible
        assert est.mem_bytes <= budget, est
        # independent recomputation from the plan tables
        plan = plan_mod.plan_multiply(mesh, est.candidate.engine,
                                      est.candidate.l)
        mem = device_memory_bytes(
            plan, f.nb_r, f.bs_r, itemsize=4.0,
            stack_capacity=est.candidate.stack_capacity or 0,
        )
        assert mem == pytest.approx(est.mem_bytes)
        assert mem <= budget
    # compacted candidates carry the exact bucketed device bound
    for est in report.ranked:
        c = est.candidate
        if c.backend != "jnp":
            assert c.stack_capacity == plan_mod.get_device_capacity(
                ok, mesh, c.engine)


def test_analytic_decision_is_feasible():
    """autotune(measure=False) on an abstract mesh returns a decision
    whose footprint fits the budget."""
    plan_mod.clear_cache()
    mesh = FakeMesh(r=4, c=2)
    a, b = _pair(nb=16, bs=4, occupancy=0.2)
    dec = autotune(a, b, mesh, budget_bytes=1e8, measure=False)
    est = estimate_candidate(
        Candidate(dec.engine, dec.l, dec.backend, dec.stack_capacity),
        mesh, featurize(a, b, 0.0), budget_bytes=1e8)
    assert dec.source == "analytic" and est.feasible
    s = plan_mod.cache_stats()
    assert s["tuner_misses"] == 1 and s["tuner_trials"] == 0


# ---- tuning DB -------------------------------------------------------------


def test_db_roundtrip(tmp_path):
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    key = make_key(("fb1", 3), (("r", 2), ("c", 2)), ("mult", "*", "*", 0),
                   "float32")
    db.record(key, {"engine": "gather", "l": None, "backend": "jnp",
                    "measured_s": 1e-3})
    db2 = TuningDB.load(path)
    assert db2.lookup(key)["engine"] == "gather"
    assert TuningDB.load_or_create(path).lookup(key) is not None
    assert len(TuningDB.load_or_create(str(tmp_path / "missing.json"))) == 0


def test_db_hit_revalidated_for_this_topology():
    """A DB record must be re-run through the enumeration validity gates
    on every hit: a corrupt / hand-copied / schema-drifted record (an L
    the paper's rule forbids, an engine the grid shape excludes, a
    compacted backend on an empty pattern) must fall through to a fresh
    decision instead of crashing later in plan compilation."""
    from repro.tuner import _db_candidate

    mesh = FakeMesh(r=2, c=4)
    a, b = _pair(nb=8, bs=4, occupancy=0.3)
    feats = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    # cannon is square-grid-only: invalid on 2x4 no matter what the
    # record says
    assert _db_candidate({"engine": "cannon", "l": None, "backend": "jnp"},
                         ok, mesh, feats) is None
    # L=3 violates the paper rule on this grid (forced L is 2)
    assert _db_candidate({"engine": "twofive", "l": 3, "backend": "jnp"},
                         ok, mesh, feats) is None
    # compacted backend over an empty pattern: no sound program to run
    assert _db_candidate({"engine": "gather", "l": None,
                          "backend": "stacks"},
                         np.zeros_like(ok), mesh, feats) is None
    good = _db_candidate({"engine": "gather", "l": None, "backend": "jnp"},
                         ok, mesh, feats)
    assert good is not None and good.engine == "gather"
    # end-to-end: a poisoned record in the right bucket falls through to
    # a fresh valid decision, not a crash in plan.validate_blocks
    plan_mod.clear_cache()
    db = TuningDB()
    db.record(make_key(feature_bucket(feats),
                       tuner.mesh_signature(mesh),
                       ("mult", "*", "*", 0), feats.dtype),
              {"engine": "cannon", "l": None, "backend": "jnp"})
    dec = autotune(a, b, mesh, db=db, measure=False)
    assert dec.engine != "cannon"
    s = plan_mod.cache_stats()
    assert s["tuner_misses"] == 1 and s["tuner_hits"] == 0, s


def test_decision_cache_keys_on_budget():
    """A decision made under one memory budget must never answer for
    another — the Eq. (6) guarantee would silently break otherwise."""
    plan_mod.clear_cache()
    mesh = FakeMesh(r=2, c=2)
    a, b = _pair(nb=16, bs=4, occupancy=0.2)
    autotune(a, b, mesh, budget_bytes=1e9, measure=False)
    autotune(a, b, mesh, budget_bytes=5e5, measure=False)
    s = plan_mod.cache_stats()
    assert s["tuner_misses"] == 2 and s["tuner_hits"] == 0, s
    # same budget twice IS a cache hit
    autotune(a, b, mesh, budget_bytes=5e5, measure=False)
    assert plan_mod.cache_stats()["tuner_hits"] == 1


def test_db_rejects_unknown_schema(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"schema": "something-else", "records": {}}')
    with pytest.raises(ValueError):
        TuningDB.load(str(path))


# ---- end-to-end engine="auto" on a real (1x1) mesh -------------------------


def test_auto_multiply_matches_reference():
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=8, bs=8, occupancy=0.25)
    plan_mod.clear_cache()
    c = multiply(a, b, mesh, engine="auto", threshold=1e-6)
    ref = multiply_reference(a, b, threshold=1e-6)
    np.testing.assert_allclose(np.asarray(c.to_dense()),
                               np.asarray(ref.to_dense()),
                               rtol=1e-5, atol=1e-5)
    s1 = plan_mod.cache_stats()
    assert s1["tuner_misses"] == 1 and s1["tuner_trials"] >= 1
    # repeated pattern: decision-cache hit, zero new trials
    multiply(a, b, mesh, engine="auto", threshold=1e-6)
    s2 = plan_mod.cache_stats()
    assert s2["tuner_hits"] == s1["tuner_hits"] + 1
    assert s2["tuner_trials"] == s1["tuner_trials"]


def test_auto_warm_db_runs_zero_trials(tmp_path):
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=8, bs=8, occupancy=0.25, seed=3)
    path = str(tmp_path / "db.json")
    plan_mod.clear_cache()
    tuner.set_default_db(path)
    multiply(a, b, mesh, engine="auto", threshold=1e-6)
    assert plan_mod.cache_stats()["tuner_trials"] >= 1
    assert len(tuner.get_default_db()) == 1
    # a fresh process is simulated by clear_cache (drops decisions AND
    # the DB binding) + re-binding the persisted file
    plan_mod.clear_cache()
    tuner.set_default_db(path)
    multiply(a, b, mesh, engine="auto", threshold=1e-6)
    s = plan_mod.cache_stats()
    assert s["tuner_trials"] == 0 and s["tuner_misses"] == 0, s
    assert s["tuner_hits"] == 1, s


# ---- clear_cache drops EVERY cache level (regression) ----------------------


def test_clear_cache_drops_all_caches(tmp_path):
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=8, bs=8, occupancy=0.2, seed=5)
    plan_mod.clear_cache()
    tuner.set_default_db(str(tmp_path / "db.json"))
    # populate every level: program + pattern + chain + tuner caches
    multiply(a, b, mesh, engine="auto", threshold=1e-6)
    multiply(a, b, mesh, engine="gather", threshold=1e-6, backend="stacks")
    from repro.core.signiter import sign_iteration

    sign_iteration(a, mesh=mesh, engine="onesided", max_iter=2, tol=0.0)
    stats = plan_mod.cache_stats()
    assert stats["builds"] > 0 and stats["chain_misses"] == 1
    assert stats["pattern_misses"] > 0 and stats["tuner_misses"] == 1
    assert plan_mod.plan_multiply.cache_info().currsize > 0

    plan_mod.clear_cache()
    assert all(v == 0 for v in plan_mod.cache_stats().values()), (
        plan_mod.cache_stats())
    assert len(plan_mod._program_cache) == 0
    assert len(plan_mod._pattern_cache) == 0
    assert len(plan_mod._bound_cache) == 0
    assert plan_mod.plan_multiply.cache_info().currsize == 0
    assert len(tuner._decision_cache) == 0
    assert tuner.get_default_db() is None  # DB binding reset too
    # and the next resolution really is a cold miss
    multiply(a, b, mesh, engine="auto", threshold=1e-6)
    s = plan_mod.cache_stats()
    assert s["tuner_misses"] == 1 and s["misses"] >= 1


def test_clear_cache_drops_envelope_and_drift_levels(tmp_path):
    """The envelope layer's cache levels obey the same contract: the
    plan-layer forecast cache, the tuner's bucket/stream caches and the
    envelope/drift counters are all dropped by ONE clear_cache (mirror
    of test_clear_cache_drops_all_caches for the levels PR 8 added)."""
    from repro.core import envelope as E

    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=8, bs=8, occupancy=0.3, seed=5)
    plan_mod.clear_cache()
    tuner.set_default_db(str(tmp_path / "db.json"))
    # populate: forecast cache (miss + hit), drift counter (non-covering
    # envelope -> exact fallback), tuner bucket/stream caches
    m = np.asarray(a.mask, bool)
    n = np.asarray(a.norms, np.float32)
    env = plan_mod.get_envelope(m, n, sweeps=2, threshold=1e-6,
                                filter_eps=1e-6, bs=a.bs_r)
    assert plan_mod.get_envelope(m, n, sweeps=2, threshold=1e-6,
                                 filter_eps=1e-6, bs=a.bs_r) is env
    tiny = E.union_envelope([np.eye(8, dtype=bool)])
    multiply(a, b, mesh, engine="gather", threshold=1e-6,
             backend="stacks", envelope=tiny)
    autotune(a, b, mesh)
    stats = plan_mod.cache_stats()
    assert stats["envelope_misses"] == 1 and stats["envelope_hits"] == 1
    assert stats["drift_retunes"] == 1, stats
    assert len(plan_mod._envelope_cache) == 1
    assert len(tuner._bucket_cache) == 1
    assert len(tuner._stream_last_bucket) == 1

    plan_mod.clear_cache()
    assert all(v == 0 for v in plan_mod.cache_stats().values()), (
        plan_mod.cache_stats())
    assert len(plan_mod._envelope_cache) == 0
    assert len(tuner._bucket_cache) == 0
    assert len(tuner._stream_last_bucket) == 0
    # and the next forecast really is a cold miss
    plan_mod.get_envelope(m, n, sweeps=2, threshold=1e-6,
                          filter_eps=1e-6, bs=a.bs_r)
    s = plan_mod.cache_stats()
    assert s["envelope_misses"] == 1 and s["envelope_hits"] == 0, s


# ---- tile-shape search axis (MXU-tiled pallas kernel) ----------------------


def test_enumerate_tile_axis_on_pallas():
    """Large atomic blocks open the tile axis: every pallas candidate is
    replicated per feasible MXU tile shape (default None first), labels
    carry the shape, and non-pallas backends never grow the axis."""
    from repro.kernels.block_spgemm import tile_candidates
    from repro.kernels.ops import _default_interpret

    a, b = _pair(nb=4, bs=128, occupancy=0.4)
    f = featurize(a, b, 0.0)
    cands = enumerate_candidates(FakeMesh(r=2, c=2), f, ok=_ok_cube(a, b),
                                 engines=("gather",), backends=("pallas",),
                                 transports=("dense",))
    tiles = [c.tile for c in cands]
    expect = tile_candidates(128, 128, 128, np.dtype(f.dtype),
                             interpret=_default_interpret())
    assert tiles == expect and tiles[0] is None and len(tiles) > 1
    labels = {c.label for c in cands}
    assert "gather/pallas" in labels
    tm, tk, tn = next(t for t in tiles if t is not None)
    assert f"gather/pallas/t{tm}x{tk}x{tn}" in labels
    # jnp never grows a tile axis — tiling is a pallas staging concern
    jn = enumerate_candidates(FakeMesh(r=2, c=2), f, ok=_ok_cube(a, b),
                              engines=("gather",), backends=("jnp",),
                              transports=("dense",))
    assert all(c.tile is None for c in jn)


def test_estimate_tile_vmem_feasibility():
    """The analytic model folds the kernel's VMEM working set into
    feasibility: a whole-block candidate at bs=1024 f32 cannot stage and
    is marked infeasible, while a split tile of the same block is fine."""
    a, b = _pair(nb=4, bs=8, occupancy=0.4)
    f = featurize(a, b, 0.0)
    f = type(f)(**{**f.__dict__, "bs_r": 1024, "bs_k": 1024, "bs_c": 1024})
    mesh = FakeMesh(r=2, c=2)
    whole = estimate_candidate(
        Candidate("gather", backend="pallas", stack_capacity=4), mesh, f)
    assert not whole.feasible and "VMEM" in whole.reason
    split = estimate_candidate(
        Candidate("gather", backend="pallas", stack_capacity=4,
                  tile=(256, 256, 256)), mesh, f)
    assert split.feasible


def test_db_record_persists_tile(tmp_path):
    """The winner's tile rides the DB record; pre-tile records read as
    tile=None; a persisted tile invalid for this pattern's block shape
    drops to the default WITHOUT missing the whole record."""
    from repro.tuner import _db_candidate

    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=4, occupancy=0.4)
    plan_mod.clear_cache()
    db = TuningDB(str(tmp_path / "db.json"))
    dec = autotune(a, b, mesh, db=db, top_k=2)
    rec = next(iter(db.records.values()))
    assert "tile" in rec  # schema always writes the field
    assert (tuple(rec["tile"]) if rec["tile"] is not None else None) == dec.tile
    f = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    base = {"engine": "gather", "l": None, "backend": "jnp"}
    # pre-tile record: reads as default staging
    cand = _db_candidate(base, ok, mesh, f)
    assert cand is not None and cand.tile is None
    # valid persisted tile survives rehydration (bs=4: only (4,4,4) or
    # finer divides; interpret mode relaxes lane alignment on CPU)
    cand = _db_candidate({**base, "tile": [4, 4, 4]}, ok, mesh, f)
    assert cand is not None and cand.tile in ((4, 4, 4), None)
    # a tile that does not divide this pattern's blocks drops to None,
    # keeping the engine/backend choice alive
    cand = _db_candidate({**base, "tile": [3, 5, 7]}, ok, mesh, f)
    assert cand is not None and cand.tile is None
    # garbage shapes are a default, not a crash
    cand = _db_candidate({**base, "tile": "64x64"}, ok, mesh, f)
    assert cand is not None and cand.tile is None


def test_pre_tile_db_records_still_warm_hit(tmp_path):
    """A DB persisted before the tile axis (records without a ``tile``
    field) still resolves measurement-free."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=4, occupancy=0.4)
    f = featurize(a, b, 0.0)
    db = TuningDB(str(tmp_path / "db.json"))
    old_key = make_key(feature_bucket(f),
                       tuple((n, int(mesh.shape[n])) for n in mesh.axis_names),
                       ("mult", "*", "*", 0), f.dtype)
    db.record(old_key, {"engine": "gather", "l": None, "backend": "jnp",
                        "transport": "dense", "measured_s": 1e-4})
    plan_mod.clear_cache()
    dec = autotune(a, b, mesh, db=db)
    assert dec.source == "db" and dec.engine == "gather"
    assert dec.tile is None
    assert plan_mod.cache_stats()["tuner_trials"] == 0


# ---- block->device assignment axis (core.distribute) -----------------------


def test_corpus_imbalance_statistic():
    """Satellite of the distribution layer: the zipf hub family is the
    workload the layer exists for — its identity-layout per-device
    product-load imbalance is MATERIAL (>2x on a 4x4 grid), while the
    uniform family (the randomized-permutation limit) sits near 1x."""
    from repro.tuner.corpus import CorpusEntry

    z = CorpusEntry("zipf_hub", "zipf", 32, 8, occupancy=0.15,
                    zipf_alpha=1.4, seed=15)
    assert z.imbalance(4, 4) > 2.0
    u = CorpusEntry("uniform_flat", "uniform", 64, 8, occupancy=0.15,
                    seed=15)
    assert u.imbalance(4, 4) < 1.3
    # masks() is exactly what build() fills — the statistic describes the
    # operands the tuner will actually measure
    ma, mb = z.masks()
    a, b = z.build()
    np.testing.assert_array_equal(ma, np.asarray(a.mask))
    np.testing.assert_array_equal(mb, np.asarray(b.mask))


def test_corpus_three_center_tall_skinny():
    """Satellite of the tensor layer: the three_center family is the
    rectangular workload — its matricized mask is (nb^2, nb) tall-skinny,
    carries the on-site diagonal, honors the requested mean occupancy,
    and is EXACTLY the mask the tensor layer's matricization produces."""
    from repro.tuner.corpus import CorpusEntry

    e = CorpusEntry("tc", "three_center", 8, 4, occupancy=0.10, seed=17)
    ma, mb = e.masks()
    assert ma.shape == (64, 8) and mb.shape == (8, 8)  # nb_r = nb * nb_c
    i = np.arange(8)
    assert ma[i * 8 + i, i].all()  # on-site (i==j==k) blocks always kept
    assert 0.03 < ma.mean() < 0.30  # screened, but not empty
    ma2, _ = e.masks()
    np.testing.assert_array_equal(ma, ma2)  # deterministic per key
    # masks() is exactly what build() fills, post-matricization
    a, b = e.build()
    np.testing.assert_array_equal(ma, np.asarray(a.mask))
    np.testing.assert_array_equal(mb, np.asarray(b.mask))
    # ... and the tensor mask flattens to the same pattern the entry
    # advertises (build_tensor -> matricize == build)
    t, _ = e.build_tensor()
    np.testing.assert_array_equal(np.asarray(t.mask).reshape(64, 8), ma)
    # the imbalance statistic computes on the rectangular product grid
    assert e.imbalance(2, 2) >= 1.0
    with pytest.raises(ValueError, match="three_center"):
        CorpusEntry("x", "uniform", 8, 4).build_tensor()


def test_candidate_assign_labels():
    assert Candidate("gather").label == "gather/jnp"
    assert Candidate("gather", assign="nnz_greedy").label == "gather/jnp@nnz"
    assert Candidate("gather", assign="randomized").label == "gather/jnp@rand"


def test_enumerate_assignment_axis():
    """With hub-skewed counts the space grows an assignment axis; without
    counts (or with near-flat loads) it stays identity-only."""
    from repro.core.distribute import product_counts

    a, b = _pair(nb=8, bs=4, occupancy=0.2, seed=2)
    mask = np.asarray(a.mask).copy()
    mask[:2] = True  # hub rows
    counts = product_counts(mask, np.asarray(b.mask))
    f = featurize(a, b, 0.0)
    cands = enumerate_candidates(FakeMesh(r=2, c=2), f, ok=_ok_cube(a, b),
                                 engines=("gather",), backends=("jnp",),
                                 counts=counts)
    assigns = {c.assign for c in cands}
    assert "identity" in assigns
    assert "nnz_greedy" in assigns or "randomized" in assigns
    nocounts = enumerate_candidates(FakeMesh(r=2, c=2), f, ok=_ok_cube(a, b),
                                    engines=("gather",), backends=("jnp",))
    assert {c.assign for c in nocounts} == {"identity"}


def test_db_record_persists_assign(tmp_path):
    """The winner's assignment mode rides the DB record (mode only — the
    permutation is re-derived from the concrete mask product on every
    use) and survives a JSON round-trip."""
    if len(jax.devices()) != 1:
        pytest.skip("single-device check")
    mesh = make_mesh((1, 1), ("r", "c"))
    a, b = _pair(nb=4, occupancy=0.4)
    plan_mod.clear_cache()
    db = TuningDB(str(tmp_path / "db.json"))
    dec = autotune(a, b, mesh, db=db, top_k=2)
    rec = next(iter(db.records.values()))
    assert "assign" in rec and rec["assign"] == dec.assign
    db2 = TuningDB.load(str(tmp_path / "db.json"))
    rec2 = next(iter(db2.records.values()))
    assert rec2["assign"] == rec["assign"]


def test_db_assign_revalidated_per_topology():
    """Persisted assignment modes are revalidated on every hit like tile
    and transport: a mode underivable on THIS (pattern, mesh) — mesh
    shape whose lcm does not divide the block grid, unknown mode, missing
    counts — silently drops to identity, keeping the engine/backend
    choice alive instead of missing the record."""
    from repro.core.distribute import product_counts
    from repro.tuner import _db_candidate

    a, b = _pair(nb=8, bs=4, occupancy=0.3)
    feats = featurize(a, b, 0.0)
    ok = _ok_cube(a, b)
    counts = product_counts(np.asarray(a.mask), np.asarray(b.mask))
    mesh = FakeMesh(r=2, c=2)
    base = {"engine": "gather", "l": None, "backend": "jnp"}
    # a record written before the distribution layer reads as identity
    cand = _db_candidate(base, ok, mesh, feats, counts)
    assert cand is not None and cand.assign == "identity"
    # the persisted mode survives where the permutation is derivable
    cand = _db_candidate({**base, "assign": "nnz_greedy"}, ok, mesh, feats,
                         counts)
    assert cand is not None and cand.assign == "nnz_greedy"
    # a topology the record's plan cannot even validate on is a MISS
    # (nb = 8 does not divide a 2x3 grid), independent of assignment
    assert _db_candidate({**base, "assign": "nnz_greedy"}, ok,
                         FakeMesh(r=2, c=3), feats, counts) is None
    # a (pattern, mesh) where the symmetric permutation itself is
    # underivable (non-square block grid) drops the MODE, keeps the record
    counts_rect = np.ones((8, 6), np.int64)
    cand = _db_candidate({**base, "assign": "nnz_greedy"}, ok, mesh, feats,
                         counts_rect)
    assert cand is not None and cand.assign == "identity"
    # schema drift and missing counts drop to identity, not to a miss
    cand = _db_candidate({**base, "assign": "zigzag"}, ok, mesh, feats,
                         counts)
    assert cand is not None and cand.assign == "identity"
    cand = _db_candidate({**base, "assign": "nnz_greedy"}, ok, mesh, feats,
                         None)
    assert cand is not None and cand.assign == "identity"
    # compacted backend: the capacity must come from the PERMUTED cube
    from repro.core.distribute import assignment_for, permute_cube

    cand = _db_candidate({**base, "backend": "stacks",
                          "assign": "nnz_greedy"}, ok, mesh, feats, counts)
    assert cand is not None and cand.assign == "nnz_greedy"
    asg = assignment_for("nnz_greedy", counts, (2, 2))
    assert cand.stack_capacity == plan_mod.get_device_capacity(
        permute_cube(ok, asg.perm), mesh, "gather")


def test_model_scales_compute_by_imbalance():
    """The cost model prices load imbalance: on hub-skewed counts the
    identity candidate's local-compute estimate exceeds a balanced
    assignment's for the same engine, so the ranking can prefer the
    permuted layout without measuring."""
    from repro.core.distribute import product_counts
    from repro.tuner.model import assignment_imbalances

    a, b = _pair(nb=16, bs=8, occupancy=0.2, seed=4)
    mask = np.asarray(a.mask).copy()
    mask[:3] = True  # hub rows
    counts = product_counts(mask, np.asarray(b.mask))
    mesh = FakeMesh(r=2, c=2)
    f = featurize(a, b, 0.0)
    imbs = assignment_imbalances(counts, mesh)
    assert imbs["identity"] > imbs.get("nnz_greedy", imbs["identity"]) - 1e-9
    # the compacted backends are product-proportional, so the slowest
    # device gates them: compute scales by the candidate's own imbalance
    est_id = estimate_candidate(
        Candidate("gather", backend="stacks", stack_capacity=8), mesh, f,
        imbalance=imbs["identity"])
    est_gr = estimate_candidate(
        Candidate("gather", backend="stacks", stack_capacity=8,
                  assign="nnz_greedy"), mesh, f,
        imbalance=imbs["nnz_greedy"])
    assert est_gr.compute_s < est_id.compute_s
    # the dense jnp einsum contracts the full cube regardless of layout
    dj = estimate_candidate(Candidate("gather"), mesh, f,
                            imbalance=imbs["identity"])
    assert dj.compute_s == estimate_candidate(
        Candidate("gather"), mesh, f,
        imbalance=imbs["nnz_greedy"]).compute_s


def test_failing_trial_raises_on_tpu_only(monkeypatch):
    """Off the TPU a candidate that fails is a lost race, recorded in its
    Trial; on a TPU the same failure raises."""
    from repro.tuner.measure import measure_candidates

    a, b = _pair(nb=4, bs=4, occupancy=0.5)
    mesh = make_mesh((1, 1), ("r", "c"))
    bad = Candidate("no-such-engine")
    trials = measure_candidates(a, b, mesh, [bad], reps=1)
    assert not trials[0].ok and "no-such-engine" in trials[0].error
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="no-such-engine"):
        measure_candidates(a, b, mesh, [bad], reps=1)
