"""Autotuner subsystem: enumeration, cache contract, tuned-channel parity.

The contract under test (ISSUE 3 + ISSUE 4 + ISSUE 5 acceptance):
  * candidate enumeration is deterministic and honors
    ``mapping.effective_channels`` divisibility;
  * the joint space's compute-tile lattice respects shape-divisibility,
    MXU-alignment, and VMEM-footprint pruning — for the GEMM kinds AND the
    attention/MoE consumers;
  * the measured ranker's timing path is trustworthy: compile time is
    AOT-split out of every score, ``time_fn`` reports (median, iqr) and
    refuses cold calls, and the successive-halving sweep prunes the joint
    space while agreeing with the exhaustive sweep's winner;
  * cache entries survive a save/load round-trip (memo AND disk); v1/v2 and
    malformed/corrupt records re-tune under the v3 schema instead of
    crashing;
  * a mesh-fingerprint mismatch invalidates (re-tunes) instead of silently
    reusing another mesh's winner;
  * a fingerprint hit never re-measures;
  * a channel the tuner resolves per call shape (``resolve_channel`` inside
    shard_map, or ``ParallelContext(tune=True)``), lowered explicitly through
    ``compile_overlap``, is parity-equal to the default-tile path on both
    backends on the 4-rank emulated mesh, for the GEMM kinds and the tiled
    attention/MoE consumers alike;
  * ``compile_overlap`` lowers explicit channels only: the removed tuning
    forms raise an error that names their replacement.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import backend, tune
from repro.compat import make_mesh, shard_map
from repro.core import BlockChannel, CompSpec, compile_overlap, effective_channels
from repro.core.comp_tiles import DEFAULT_TILE, blocked_dot
from repro.core.moe_overlap import moe_router
from repro.tune import cache as tune_cache
from repro.tune import measure as tune_measure
from repro.tune import sweep as tune_sweep

R = 4
KEY = jax.random.PRNGKey(0)

SIGS = {
    "ag_matmul": (1, 16, 16, 12),
    "matmul_rs": (1, R * 8, 8, 16),
    "ag_attention": (1, 2, 1, 16, 8),
    "ag_moe": (16, 8, 2, 2, 8),
}

TINY_SPACE = tune.Space(orders=("ring",), channel_counts=(1,), accum_dtypes=("float32",))

MEASURE_KW = dict(ranker="measure", space=TINY_SPACE, repeats=1, warmup=1)


class FakeCaseTimer:
    """Drop-in for measure.CaseTimer: deterministic scores, no wall clock."""

    calls = []

    def __init__(self, kind, mesh, axis, sig):
        self.kind = kind

    def time(self, channel, *, repeats=3, warmup=1):
        type(self).calls.append((self.kind, repeats))
        return 1.0, 0.0


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh((R,), ("model",))


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    """Every test gets a fresh cache dir + empty process memo."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune-cache"))
    tune_cache.clear_memo()
    yield
    tune_cache.clear_memo()


# ---- candidate enumeration --------------------------------------------------


def test_enumeration_deterministic():
    a = tune.enumerate_candidates("ag_matmul", extent=32)
    b = tune.enumerate_candidates("ag_matmul", extent=32)
    assert a == b
    assert len(a) == 18  # 3 orders x {1,2,4} x 2 dtypes, all feasible


def test_enumeration_honors_effective_channels():
    # extent 6: requested {1,2,4} -> effective {1,2,3} via the same
    # largest-divisor rule the runtime plan layer applies
    cands = tune.enumerate_candidates("ag_matmul", extent=6)
    for c in cands:
        assert 6 % c.num_channels == 0
        assert c.num_channels in {effective_channels(6, req) for req in (1, 2, 4)}
    # extent 5 (prime, < 2): every count clamps to 1 and duplicates collapse
    clamped = tune.enumerate_candidates("ag_matmul", extent=5)
    assert {c.num_channels for c in clamped} == {1}
    assert len(clamped) == 6  # 3 orders x 2 dtypes, one channel point each


def test_signature_canonicalization():
    assert tune.signature("ag_matmul", [(2, 3, 16, 8), (8, 5)]) == (6, 16, 8, 5)
    att = tune.signature("ag_attention", [(1, 4, 16, 8), (1, 2, 16, 8)])
    assert att == (1, 4, 2, 16, 8)
    sig = tune.signature("ag_moe", [(16, 8), (16, 2), (16, 2), (4, 8, 32), (4, 16, 8)])
    assert sig == (16, 8, 2, 4, 16)


# ---- joint space: compute-tile lattice (ISSUE 4) ----------------------------


def test_joint_enumeration_divisibility():
    sig = (1, 256, 512, 384)  # (lead, m_loc, k, n_loc); n=384 defeats tn=256
    cands = tune.enumerate_candidates(
        "ag_matmul", extent=256, space=tune.JOINT_SPACE, sig=sig, world=4
    )
    assert cands == tune.enumerate_candidates(
        "ag_matmul", extent=256, space=tune.JOINT_SPACE, sig=sig, world=4
    )  # deterministic
    for c in cands:
        tm, tn, tk = c.comp_tile
        if c.comp_tile == DEFAULT_TILE:
            continue  # sentinel: backend-chosen blocking, never clamped
        m_sub = 256 // c.num_channels
        assert m_sub % tm == 0 and 384 % tn == 0 and 512 % tk == 0
        # MXU alignment: clamped dims are full-extent or packing multiples
        assert tn == 384 or tn % 128 == 0
    # the 256-request on n=384 clamps to 192, which is neither the full
    # extent nor lane-aligned — the pruner must have dropped it
    assert all(c.comp_tile[1] != 192 for c in cands)
    # a genuinely non-default tile survives for this shape
    assert any(c.comp_tile != DEFAULT_TILE for c in cands)


def test_joint_enumeration_vmem_pruning(monkeypatch):
    sig = (1, 256, 512, 256)
    full = tune.comp_tile_candidates("ag_matmul", sig, world=4, space=tune.JOINT_SPACE)
    assert len(full) > 1
    monkeypatch.setenv("REPRO_VMEM_BYTES", "1000")  # nothing fits
    pruned = tune.comp_tile_candidates("ag_matmul", sig, world=4, space=tune.JOINT_SPACE)
    assert pruned == (DEFAULT_TILE,)  # only the unprunable sentinel survives


def test_joint_space_extends_to_attention_and_moe():
    # ISSUE 5: the attention/MoE consumers have a compute-tile axis too —
    # tiles clamp to their own dims (attention: queries x head dim x
    # per-channel KV rows; MoE: per-expert rows x 2f x d_model)
    att_sig = (1, 2, 1, 64, 32)
    att = tune.enumerate_candidates(
        "ag_attention", extent=64, space=tune.JOINT_SPACE, sig=att_sig, world=R
    )
    assert len(att) > 18  # the joint space genuinely grew past comm-only
    assert any(c.comp_tile != DEFAULT_TILE for c in att)
    for c in att:
        if c.comp_tile == DEFAULT_TILE:
            continue
        tm, tn, tk = c.comp_tile
        s_sub = 64 // c.num_channels
        assert 64 % tm == 0 and 32 % tn == 0 and s_sub % tk == 0

    moe_sig = (32, 16, 2, 2, 16)
    moe = tune.enumerate_candidates(
        "ag_moe", extent=32, space=tune.JOINT_SPACE, sig=moe_sig, world=R
    )
    assert len(moe) > 18
    assert any(c.comp_tile != DEFAULT_TILE for c in moe)
    for c in moe:
        if c.comp_tile == DEFAULT_TILE:
            continue
        tm, tn, tk = c.comp_tile
        m_sub = 32 // c.num_channels
        assert m_sub % tm == 0 and 32 % tn == 0 and 16 % tk == 0

    # an unknown signature still collapses to the sentinel
    assert tune.comp_tile_candidates("ag_attention", None, world=R) == (DEFAULT_TILE,)


def test_joint_winner_differs_from_default_tile(mesh4):
    # the acceptance shape: big enough that explicit MXU blocking beats the
    # 128^3 default under the per-tile cost terms
    res = tune.autotune(
        "ag_matmul", signature=(1, 256, 512, 256), mesh=mesh4, space=tune.JOINT_SPACE
    )
    assert res.candidate.comp_tile != DEFAULT_TILE
    assert res.channel.comp.tile == res.candidate.comp_tile
    assert "tile=" in res.candidate.label()


def test_blocked_dot_matches_plain_dot():
    a = np.asarray(jax.random.normal(KEY, (2, 24, 32)), np.float32)
    b = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (32, 16)), np.float32)
    got = np.asarray(blocked_dot(jax.numpy.asarray(a), jax.numpy.asarray(b), (8, 8, 8)))
    np.testing.assert_allclose(got, a @ b, atol=1e-5, rtol=1e-5)


# ---- cache contract ---------------------------------------------------------


def test_cache_round_trip(mesh4):
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not first.cache_hit and first.considered == 18

    memo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert memo.cache_hit and memo.candidate == first.candidate

    tune_cache.clear_memo()  # force the JSON read
    disk = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert disk.cache_hit and disk.candidate == first.candidate
    assert disk.ranker == first.ranker

    files = os.listdir(tune_cache.cache_dir())
    assert len(files) == 1 and files[0].endswith(".json")
    with open(os.path.join(tune_cache.cache_dir(), files[0])) as fh:
        payload = json.load(fh)
    assert payload["fingerprint"] == first.fingerprint
    assert len(payload["entries"]) == 1


def test_fingerprint_mismatch_invalidates(mesh4):
    first = tune.autotune("matmul_rs", signature=SIGS["matmul_rs"], mesh=mesh4)
    assert not first.cache_hit

    # same file name, tampered fingerprint payload: the stored identity no
    # longer matches the live mesh -> whole file must be ignored (re-tune),
    # never silently reused
    digest = tune_cache.fingerprint_digest(first.fingerprint)
    path = os.path.join(tune_cache.cache_dir(), digest + ".json")
    with open(path) as fh:
        payload = json.load(fh)
    payload["fingerprint"]["jax_version"] = "0.0.0-other-mesh"
    with open(path, "w") as fh:
        json.dump(payload, fh)

    tune_cache.clear_memo()
    redo = tune.autotune("matmul_rs", signature=SIGS["matmul_rs"], mesh=mesh4)
    assert not redo.cache_hit  # invalidated -> re-tuned
    assert redo.candidate == first.candidate  # same space, same winner

    # and the re-tune heals the file back to the live fingerprint
    with open(path) as fh:
        assert json.load(fh)["fingerprint"] == first.fingerprint


def test_cache_hit_never_remeasures(mesh4, monkeypatch):
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, **MEASURE_KW)
    assert not first.cache_hit and first.ranker == "measure"

    class Boom:
        def __init__(self, *a, **k):
            raise AssertionError("cache hit must not re-measure")

    monkeypatch.setattr(tune_measure, "CaseTimer", Boom)
    tune_cache.clear_memo()  # disk hit, not memo hit
    hit = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, **MEASURE_KW)
    assert hit.cache_hit and hit.candidate == first.candidate


def test_explicit_measure_upgrades_model_entry(mesh4, monkeypatch):
    # pre-warm flow: a model-ranked record must not satisfy an explicit
    # measured request — it is re-ranked by measurement and overwritten
    model = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, space=TINY_SPACE)
    assert not model.cache_hit and model.ranker == "model"

    calls = FakeCaseTimer.calls
    calls.clear()
    monkeypatch.setattr(tune_measure, "CaseTimer", FakeCaseTimer)
    up = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, **MEASURE_KW)
    assert not up.cache_hit and up.ranker == "measure" and calls

    # the measured record now satisfies BOTH rankers without re-measuring
    calls.clear()
    hit_m = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, **MEASURE_KW)
    hit_a = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, space=TINY_SPACE)
    assert hit_m.cache_hit and hit_a.cache_hit and not calls
    assert hit_a.ranker == "measure"  # measured result is never clobbered


def test_cache_dirs_are_isolated_in_process(mesh4, tmp_path):
    # the process memo must not leak entries across cache_dir arguments
    a = tune.autotune(
        "ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, cache_dir=str(tmp_path / "a")
    )
    b = tune.autotune(
        "ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, cache_dir=str(tmp_path / "b")
    )
    assert not a.cache_hit and not b.cache_hit  # distinct stores, no cross-hit
    assert os.path.isdir(tmp_path / "a") and os.path.isdir(tmp_path / "b")


def test_axis_and_world_are_part_of_entry_key():
    # one multi-axis mesh fingerprint: a winner tuned along the 4-rank axis
    # must not be reused for the 2-rank axis (different ring length)
    mesh = make_mesh((2, 4), ("data", "model"))
    a = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh, axis="model")
    b = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh, axis="data")
    assert not a.cache_hit and not b.cache_hit  # no cross-axis reuse
    assert a.fingerprint == b.fingerprint  # same file, distinct entries


def test_store_merges_external_writes(mesh4):
    # a concurrent process's entry written between our read and our write
    # must survive our store (per-entry last-writer-wins, not per-file)
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    digest = tune_cache.fingerprint_digest(first.fingerprint)
    path = os.path.join(tune_cache.cache_dir(), digest + ".json")
    with open(path) as fh:
        payload = json.load(fh)
    payload["entries"]["external|entry"] = {"ranker": "measure", "score": 1.0}
    with open(path, "w") as fh:
        json.dump(payload, fh)

    # our memo still holds the pre-external snapshot; a new store must merge
    tune.autotune("matmul_rs", signature=SIGS["matmul_rs"], mesh=mesh4)
    with open(path) as fh:
        entries = json.load(fh)["entries"]
    assert "external|entry" in entries  # not clobbered by the stale memo
    assert len(entries) == 3


def test_cache_v1_schema_migration_retunes(mesh4):
    # a PR-3 cache file (comm-only records: no "schema", no "comp_tile")
    # must re-tune under the v3 schema, never crash or half-apply
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    digest = tune_cache.fingerprint_digest(first.fingerprint)
    path = os.path.join(tune_cache.cache_dir(), digest + ".json")
    with open(path) as fh:
        payload = json.load(fh)
    for rec in payload["entries"].values():  # downgrade every record to v1
        rec.pop("schema", None)
        rec.pop("comp_tile", None)
    with open(path, "w") as fh:
        json.dump(payload, fh)

    tune_cache.clear_memo()
    redo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not redo.cache_hit  # v1 record rejected -> re-tuned
    assert redo.candidate == first.candidate

    # the re-tune healed the record to the current schema
    tune_cache.clear_memo()
    healed = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert healed.cache_hit
    with open(path) as fh:
        entries = json.load(fh)["entries"]
    assert all(rec.get("schema") == tune.CACHE_SCHEMA for rec in entries.values())
    assert all("comp_tile" in rec for rec in entries.values())


def test_cache_v2_schema_migration_retunes(mesh4):
    # a PR-4 record (schema 2: joint winner, but chosen from the smaller
    # pre-sweep space with no attention/MoE tile axes) re-tunes under v3
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    digest = tune_cache.fingerprint_digest(first.fingerprint)
    path = os.path.join(tune_cache.cache_dir(), digest + ".json")
    with open(path) as fh:
        payload = json.load(fh)
    for rec in payload["entries"].values():  # downgrade every record to v2
        rec["schema"] = 2
        rec.pop("sweep", None)
        rec.pop("score_iqr_us", None)
    with open(path, "w") as fh:
        json.dump(payload, fh)

    tune_cache.clear_memo()
    redo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not redo.cache_hit  # v2 record rejected -> re-tuned
    assert redo.candidate == first.candidate

    tune_cache.clear_memo()
    healed = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert healed.cache_hit
    with open(path) as fh:
        entries = json.load(fh)["entries"]
    assert all(rec.get("schema") == tune.CACHE_SCHEMA for rec in entries.values())


def test_cache_corrupt_file_and_records_retune(mesh4):
    # a junk cache file (truncated JSON) and a malformed record (hand-edited
    # entry) both degrade to a re-tune — load must never raise
    first = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    digest = tune_cache.fingerprint_digest(first.fingerprint)
    path = os.path.join(tune_cache.cache_dir(), digest + ".json")

    with open(path, "w") as fh:  # truncated/binary junk: not JSON at all
        fh.write('{"fingerprint": {"mesh_sh\x00\x01garbage')
    tune_cache.clear_memo()
    redo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not redo.cache_hit and redo.candidate == first.candidate

    # valid JSON, garbage records: wrong types, missing fields, junk values
    with open(path) as fh:
        payload = json.load(fh)
    (key,) = payload["entries"].keys()
    for bad in ("not-a-record", {"schema": tune.CACHE_SCHEMA}, {"schema": "x"}, 7, None):
        payload["entries"][key] = bad
        with open(path, "w") as fh:
            json.dump(payload, fh)
        tune_cache.clear_memo()
        redo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
        assert not redo.cache_hit and redo.candidate == first.candidate
        with open(path) as fh:  # the re-tune healed the record
            payload = json.load(fh)

    # a record whose winner fails spec validation (junk order) also re-tunes
    payload["entries"][key] = dict(
        schema=tune.CACHE_SCHEMA,
        order="zigzag",
        num_channels=1,
        accum_dtype="float32",
        comp_tile=[128, 128, 128],
        ranker="model",
        score=1.0,
    )
    with open(path, "w") as fh:
        json.dump(payload, fh)
    tune_cache.clear_memo()
    redo = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not redo.cache_hit and redo.candidate == first.candidate


# ---- measured ranker: timing contract + early-exit sweep (ISSUE 5) ----------


def test_time_fn_stats_and_warmup_guard():
    calls = []

    def fn(x):
        calls.append(1)
        return x

    med, iqr = tune_measure.time_fn(fn, 1.0, repeats=5, warmup=2)
    assert len(calls) == 7  # warmup + repeats, one shared callable
    assert med >= 0.0 and iqr >= 0.0
    with pytest.raises(ValueError, match="warmup >= 1"):
        tune_measure.time_fn(fn, 1.0, warmup=0)
    with pytest.raises(ValueError, match="repeats >= 1"):
        tune_measure.time_fn(fn, 1.0, repeats=0)


def test_time_fn_aot_splits_compile_from_measurement():
    import jax.numpy as jnp

    traces = []

    @jax.jit
    def f(x):
        traces.append(1)
        return x + 1.0

    med, iqr = tune_measure.time_fn(f, jnp.ones((8,)), repeats=3, warmup=1)
    # lower().compile() traced exactly once; the compiled executable served
    # every warmup and timed call — compile time can never enter a score
    assert len(traces) == 1
    assert med > 0.0 and iqr >= 0.0


def _oracle(kind, sig, world):
    """Deterministic fake timer: analytic cost in us + stable per-point skew."""
    import hashlib

    def timer(cand, *, repeats=3, warmup=1):
        from repro.tune import cost as tune_cost

        j = int(hashlib.sha256(cand.label().encode()).hexdigest()[:4], 16) % 97
        return tune_cost.predict_cost(kind, sig, world, cand) * 1e6 * (1.0 + j / 9700.0), 0.0

    return timer


def test_measured_sweep_prunes_and_matches_exhaustive():
    sig = (1, 256, 512, 256)
    cands = tune.enumerate_candidates(
        "ag_matmul", extent=256, space=tune.JOINT_SPACE, sig=sig, world=R
    )
    timer = _oracle("ag_matmul", sig, R)
    cfg = tune_sweep.SweepConfig()
    sw = tune_sweep.measured_sweep("ag_matmul", sig, R, cands, timer, config=cfg)
    ex = tune_sweep.measured_sweep(
        "ag_matmul", sig, R, cands, timer, config=tune_sweep.SweepConfig(enabled=False)
    )
    assert sw.winner == ex.winner  # pruning never changes the winner here
    assert sw.stats["total"] == len(cands) == ex.stats["total"]
    assert sw.stats["screened"] <= len(cands) // 2  # timed <= 50% of the space
    assert sw.stats["pruned"] >= len(cands) - len(cands) // 2
    assert sw.stats["timed"] < sw.stats["screened"]  # full repeats: a handful
    assert ex.stats == {
        "total": len(cands),
        "screened": len(cands),
        "timed": len(cands),
        "pruned": 0,
        "early_exit": False,
    }


def test_measured_sweep_early_exit_on_incumbent_bound():
    sig = (1, 256, 512, 256)
    cands = tune.enumerate_candidates(
        "ag_matmul", extent=256, space=tune.JOINT_SPACE, sig=sig, world=R
    )
    timer = _oracle("ag_matmul", sig, R)
    sw = tune_sweep.measured_sweep("ag_matmul", sig, R, cands, timer)
    # deterministic oracle: iqr == 0, so after the first full timing the
    # incumbent's lower bound equals its median and beats every later screen
    assert sw.stats["early_exit"] and sw.stats["timed"] == 1


def test_measured_sweep_noise_widens_the_search():
    # the early exit must use the incumbent's UPPER bound (median + iqr): a
    # candidate whose screen sits inside the incumbent's noise band is still
    # plausibly faster and must be fully timed — exiting on the optimistic
    # lower bound (median - iqr) would prune the true winner exactly when
    # measurements are noisy
    sig = (1, 256, 512, 256)
    cands = tune.enumerate_candidates(
        "ag_matmul", extent=256, space=tune.JOINT_SPACE, sig=sig, world=R
    )
    from repro.tune import cost as tune_cost

    order = sorted(cands, key=lambda c: tune_cost.predict_cost("ag_matmul", sig, R, c))
    c0, c1 = order[0], order[1]

    def timer(cand, *, repeats=3, warmup=1):
        if repeats == 1:  # the 1-repeat screen: c0 looks best, c1 second
            return (50.0, 0.0) if cand == c0 else (70.0, 0.0) if cand == c1 else (500.0, 0.0)
        return (100.0, 40.0) if cand == c0 else (70.0, 1.0)  # full repeats

    sw = tune_sweep.measured_sweep("ag_matmul", sig, R, cands, timer)
    assert sw.stats["timed"] >= 2  # c1's 70us screen < 100 + 40: must be timed
    assert sw.winner == c1 and sw.median_us == 70.0


def test_sweep_env_knobs(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_SWEEP", "0")
    assert not tune_sweep.sweep_config_from_env().enabled
    monkeypatch.setenv("REPRO_TUNE_SWEEP", "1")
    monkeypatch.setenv("REPRO_TUNE_SWEEP_SCREEN", "0.25")
    monkeypatch.setenv("REPRO_TUNE_SWEEP_KEEP", "0.5")
    cfg = tune_sweep.sweep_config_from_env()
    assert cfg.enabled and cfg.screen_fraction == 0.25 and cfg.keep_fraction == 0.5
    with pytest.raises(ValueError, match="fractions"):
        tune_sweep.SweepConfig(screen_fraction=0.0)


def test_measured_record_carries_sweep_stats(mesh4, monkeypatch):
    monkeypatch.setattr(tune_measure, "CaseTimer", FakeCaseTimer)
    res = tune.autotune(
        "ag_matmul",
        signature=(1, 64, 64, 64),
        mesh=mesh4,
        ranker="measure",
        space=tune.JOINT_SPACE,
    )
    assert res.ranker == "measure" and res.sweep is not None
    assert res.sweep["total"] == res.considered
    assert res.sweep["pruned"] >= 1  # the joint space is big enough to prune

    # the pruning ledger is part of the v3 record and survives the round-trip
    tune_cache.clear_memo()
    hit = tune.autotune(
        "ag_matmul",
        signature=(1, 64, 64, 64),
        mesh=mesh4,
        ranker="measure",
        space=tune.JOINT_SPACE,
    )
    assert hit.cache_hit and hit.sweep == res.sweep and hit.score_iqr == res.score_iqr


# ---- tiled attention/MoE consumers: parity on both backends (ISSUE 5) --------


def _with_tile(ch, tile):
    """``ch`` with its compute tile replaced (accum dtype kept); None = as is."""
    return ch if tile is None else ch.with_(comp=dataclasses.replace(ch.comp, tile=tile))


def _attention_case(mesh4):
    b, h, hkv, s_loc, d = 1, 2, 1, 16, 8
    q = jax.random.normal(KEY, (b, h, R * s_loc, d))
    kv = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, R * s_loc, d))
    spec = P(None, None, "model", None)

    def build(ch, tile=None):
        fn = compile_overlap("ag_attention", _with_tile(ch, tile), causal=True)
        return jax.jit(shard_map(fn, mesh4, in_specs=(spec,) * 3, out_specs=spec))

    return build, (q, kv, kv)


def test_tiled_attention_parity_xla(mesh4):
    build, args = _attention_case(mesh4)
    base = BlockChannel(axis="model", num_channels=2)
    ref = np.asarray(build(base)(*args), np.float32)
    # an explicit (tm, ., tk) blocks (block_q, block_kv); tk=6 clamps to 4
    tiled = np.asarray(build(base, tile=(8, 128, 6))(*args), np.float32)
    np.testing.assert_allclose(tiled, ref, atol=2e-5, rtol=2e-5)

    # tuner-resolved joint winner: ag_attention is an AG flow, so the f32
    # tie-break must hold (the cost model's compute term is accum-dtype-free)
    res = tune.autotune(
        "ag_attention", signature=(1, 2, 1, 16, 8), mesh=mesh4, space=tune.JOINT_SPACE
    )
    assert res.candidate.accum_dtype == "float32"
    got = np.asarray(build(res.channel)(*args), np.float32)
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-3)


def test_tiled_moe_parity_xla(mesh4):
    m_loc, dm, top_k, e_loc, f = 16, 8, 2, 2, 8
    e = e_loc * R
    x = jax.random.normal(KEY, (R * m_loc, dm)) * 0.5
    wgu = jax.random.normal(jax.random.PRNGKey(5), (e, dm, 2 * f)) * 0.1
    wdn = jax.random.normal(jax.random.PRNGKey(6), (e, f, dm)) * 0.1
    wr = jax.random.normal(jax.random.PRNGKey(4), (dm, e))
    specs = dict(
        in_specs=(P("model", None), P("model", None, None), P("model", None, None)),
        out_specs=P("model", None),
    )

    def build(ch, tile=None):
        g = compile_overlap("ag_moe", _with_tile(ch, tile), capacity_factor=8.0)

        def f_(xs, wgu_, wdn_):
            ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=top_k)
            return g(xs, ids, wts, wgu_, wdn_)

        return jax.jit(shard_map(f_, mesh4, **specs))

    base = BlockChannel(axis="model")
    ref = np.asarray(build(base)(x, wgu, wdn), np.float32)
    tiled = np.asarray(build(base, tile=(8, 8, 4))(x, wgu, wdn), np.float32)
    np.testing.assert_allclose(tiled, ref, atol=2e-5, rtol=2e-5)

    # tuner-resolved joint winner (ag_rs flow: the tuner may pick a bf16
    # flow dtype — the bf16 tolerance rule applies then)
    res = tune.autotune(
        "ag_moe", signature=(16, 8, 2, 2, 8), mesh=mesh4, space=tune.JOINT_SPACE
    )
    got = np.asarray(build(res.channel)(x, wgu, wdn), np.float32)
    if res.candidate.accum_dtype == "float32":
        tol = dict(atol=2e-4, rtol=2e-3)
    else:
        tol = dict(atol=8e-2, rtol=3e-2)
    np.testing.assert_allclose(got, ref, **tol)


@pytest.mark.parametrize("n_kv", [1, 2, 4, 8])
def test_apply_seq_ring_matches_apply_seq(mesh4, n_kv):
    # n_kv sweeps the GQALayout regimes on tp=4: MQA (kv_pad=1, the original
    # shared-head ring), kv < tp (kv_pad=2, rep=2: ranks share a group),
    # kv == tp (one distinct group per rank) and kv > tp (kv_loc=2 groups
    # per rank) — the per-KV-group ring must match apply_seq on all of them
    from repro.configs.base import ArchConfig
    from repro.nn import attention as nn_attention
    from repro.parallel.context import ParallelContext

    cfg = ArchConfig(
        name="tiny",
        family="dense",
        n_layers=1,
        d_model=32,
        n_heads=8,
        n_kv_heads=n_kv,
        d_ff=64,
        vocab_size=64,
    )
    pc = ParallelContext(mesh=mesh4, axis="model", dp_axes=())
    params = nn_attention.init(KEY, cfg, pc.tp, dtype=jax.numpy.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, R * 16, 32)) * 0.5
    full = nn_attention.specs(cfg, pc.tp, pc.dp_spec())
    sp = {k: pc.manual(v) for k, v in full.items()}

    def run(fn):
        sm = pc.smap(
            lambda p, xs: fn(p, xs, pc, cfg), (sp, P(None, "model", None)), P(None, "model", None)
        )
        return np.asarray(jax.jit(sm)(params, x), np.float32)

    ring = run(nn_attention.apply_seq_ring)
    seq = run(nn_attention.apply_seq)
    np.testing.assert_allclose(ring, seq, atol=2e-4, rtol=2e-3)


def test_auto_keeps_unsupported_backend_loud():
    # unsupported (kind, backend) raises at BUILD time — a tuner-resolved
    # channel is refused exactly like a hand-built one
    ch = tune.resolve_channel(
        "ag_attention", sig=SIGS["ag_attention"], world=R, space=tune.JOINT_SPACE
    )
    with pytest.raises(NotImplementedError, match="copy engine"):
        compile_overlap("ag_attention", ch, backend="pallas")
    with pytest.raises(NotImplementedError, match="copy engine"):
        compile_overlap("ag_attention", BlockChannel(axis="model"), backend="pallas")


def test_space_is_part_of_entry_key(mesh4):
    narrow = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, space=TINY_SPACE)
    full = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4)
    assert not full.cache_hit  # narrowed sweep must not shadow the full one
    assert narrow.considered == 1 and full.considered == 18


def test_base_channel_fields_inherited(mesh4):
    pull = dataclasses.replace(BlockChannel(axis="model").comm, mode="pull")
    base = BlockChannel(axis="model", comm=pull)
    res = tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], mesh=mesh4, base=base)
    assert res.channel.comm.mode == "pull"  # non-tuned field survives
    assert res.channel.comm.order == res.candidate.order


# ---- channels resolved per call shape, lowered explicitly -------------------


def _resolving(kind, *, backend_="xla", overlapped=True, mesh=None, space=tune.DEFAULT_SPACE,
               **kw):
    """Per-shard callable that resolves its channel from its own operand
    shapes at trace time (``tune.resolve_channel``; world from the manual
    axis when no mesh is given) and lowers it through ``compile_overlap``."""

    def fn(*args, **call_kw):
        ch = tune.resolve_channel(
            kind,
            shapes=[jnp.shape(a) for a in args],
            mesh=mesh,
            world=None if mesh is not None else int(backend.axis_size("model")),
            space=space,
        )
        g = compile_overlap(kind, ch, backend=backend_, overlapped=overlapped, **kw)
        return g(*args, **call_kw)

    return fn


def _tuned_and_explicit(kind, mesh4):
    """(tuned_fn, explicit_fn, baseline_fn, args, resolved): same specs,
    three lowerings; ``tuned_fn`` resolves inside shard_map."""
    key = KEY
    resolved = tune.resolve_channel(kind, sig=SIGS[kind], mesh=mesh4)

    def sm(fn, in_specs, out_specs):
        return jax.jit(shard_map(fn, mesh4, in_specs=in_specs, out_specs=out_specs))

    def lower(ch, ov, **kw):
        if ch is None:
            return _resolving(kind, overlapped=ov, mesh=mesh4, **kw)
        return compile_overlap(kind, ch, overlapped=ov, **kw)

    if kind == "ag_matmul":
        _, m_loc, k, n = SIGS[kind]
        args = (
            jax.random.normal(key, (R * m_loc, k)),
            jax.random.normal(jax.random.PRNGKey(1), (k, n)),
        )
        specs = ((P("model", None), P(None, None)), P(None, None))

        def build(ch, ov=True):
            return sm(lower(ch, ov), *specs)
    elif kind == "matmul_rs":
        _, m, k_loc, n = SIGS[kind]
        args = (
            jax.random.normal(key, (m, R * k_loc)),
            jax.random.normal(jax.random.PRNGKey(2), (R * k_loc, n)),
        )
        specs = ((P(None, "model"), P("model", None)), P("model", None))

        def build(ch, ov=True):
            return sm(lower(ch, ov), *specs)
    elif kind == "ag_attention":
        b, h, hkv, s_loc, d = SIGS[kind]
        q = jax.random.normal(key, (b, h, R * s_loc, d))
        kv = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, R * s_loc, d))
        args = (q, kv, kv)
        specs = ((P(None, None, "model"),) * 3, P(None, None, "model"))

        def build(ch, ov=True):
            return sm(lower(ch, ov, causal=True), *specs)
    else:  # ag_moe
        m_loc, dm, top_k, e_loc, f = SIGS[kind]
        e = e_loc * R
        args = (
            jax.random.normal(key, (R * m_loc, dm)) * 0.5,
            jax.random.normal(jax.random.PRNGKey(5), (e, dm, 2 * f)) * 0.1,
            jax.random.normal(jax.random.PRNGKey(6), (e, f, dm)) * 0.1,
        )
        wr = jax.random.normal(jax.random.PRNGKey(4), (dm, e))
        specs = (
            (P("model", None), P("model", None, None), P("model", None, None)),
            P("model", None),
        )

        def build(ch, ov=True):
            g = lower(ch, ov, capacity_factor=8.0)

            def f_(xs, wgu, wdn):
                ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=top_k)
                return g(xs, ids, wts, wgu, wdn)

            return sm(f_, *specs)

    return build(None), build(resolved), build(resolved, False), args, resolved


@pytest.mark.parametrize("kind", tune.TUNABLE_KINDS)
def test_channel_auto_parity(kind, mesh4):
    tuned_fn, explicit_fn, baseline_fn, args, resolved = _tuned_and_explicit(kind, mesh4)
    got = np.asarray(tuned_fn(*args), np.float32)
    want = np.asarray(explicit_fn(*args), np.float32)
    # per-call resolution lands on exactly the explicit channel: bit-identical
    np.testing.assert_array_equal(got, want)
    # ... and correct vs the non-overlapping baseline, at the tolerance of
    # the flow dtype the tuner picked (bf16 partials are genuinely lossy)
    base = np.asarray(baseline_fn(*args), np.float32)
    if resolved.comp.accum_dtype == "float32":
        tol = dict(atol=2e-4, rtol=2e-3)
    else:
        tol = dict(atol=8e-2, rtol=3e-2)
    np.testing.assert_allclose(got, base, **tol)


def test_comp_auto_parity_xla(mesh4):
    # a joint-space resolution must match the default-tile lowering; the
    # shape is big enough that the winner's tile genuinely differs
    m_loc, k, n = 256, 512, 256
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    specs = dict(in_specs=(P("model", None), P(None, None)), out_specs=P(None, None))

    joint = jax.jit(shard_map(_resolving("ag_matmul", space=tune.JOINT_SPACE), mesh4, **specs))
    got = np.asarray(joint(x, w), np.float32)  # resolves the joint winner

    res = tune.autotune("ag_matmul", signature=(1, m_loc, k, n), world=R, space=tune.JOINT_SPACE)
    assert res.cache_hit and res.candidate.comp_tile != DEFAULT_TILE  # joint hit

    default = _with_tile(res.channel, DEFAULT_TILE)
    ref_fn = jax.jit(shard_map(compile_overlap("ag_matmul", default), mesh4, **specs))
    want = np.asarray(ref_fn(x, w), np.float32)
    if res.candidate.accum_dtype == "float32":
        tol = dict(atol=2e-4, rtol=2e-3)
    else:
        tol = dict(atol=8e-2, rtol=3e-2)
    np.testing.assert_allclose(got, want, **tol)


def test_comp_explicit_tile_parity_pallas(mesh4):
    # the fused Pallas kernels must honor a non-default (tm, tn, tk); parity
    # vs the default-tile kernel on both fused kinds
    m_loc, k, n = 16, 32, 32
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n))
    specs = dict(in_specs=(P("model", None), P(None, None)), out_specs=P(None, None))

    def pallas_fn(tile):
        ch = _with_tile(BlockChannel(axis="model"), tile)
        fn = compile_overlap("ag_matmul", ch, backend="pallas", world_size=R)
        return jax.jit(shard_map(fn, mesh4, **specs))

    tiled = np.asarray(pallas_fn((8, 16, 16))(x, w), np.float32)
    ref = np.asarray(pallas_fn(None)(x, w), np.float32)
    np.testing.assert_allclose(tiled, ref, atol=2e-4, rtol=2e-3)

    xr = jax.random.normal(KEY, (R * 16, R * 8))
    wr = jax.random.normal(jax.random.PRNGKey(3), (R * 8, 32))
    rs_specs = dict(in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None))

    def rs_fn(tile):
        ch = _with_tile(BlockChannel(axis="model"), tile)
        fn = compile_overlap("matmul_rs", ch, backend="pallas", world_size=R)
        return jax.jit(shard_map(fn, mesh4, **rs_specs))

    tiled_rs = np.asarray(rs_fn((8, 16, 4))(xr, wr), np.float32)
    ref_rs = np.asarray(rs_fn(None)(xr, wr), np.float32)
    np.testing.assert_allclose(tiled_rs, ref_rs, atol=2e-4, rtol=2e-3)


def test_comp_auto_parity_pallas(mesh4):
    # joint resolution through the fused backend: the tuned winner (whatever
    # tile it picks) must stay parity-equal to the plain local matmul
    m_loc, k, n = 16, 32, 32
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(2), (k, n))
    fn = _resolving(
        "ag_matmul", backend_="pallas", mesh=mesh4, space=tune.JOINT_SPACE, world_size=R
    )
    specs = dict(in_specs=(P("model", None), P(None, None)), out_specs=P(None, None))
    sm = jax.jit(shard_map(fn, mesh4, **specs))
    np.testing.assert_allclose(np.asarray(sm(x, w)), np.asarray(x @ w), atol=2e-4, rtol=2e-3)


def test_comp_rejects_bad_values():
    ch = BlockChannel(axis="model")
    # the compute half travels on the channel only: comp= names its replacement
    with pytest.raises(TypeError, match=r"channel\.with_\(comp="):
        compile_overlap("ag_matmul", ch, comp=(64, 64, 64))
    with pytest.raises(ValueError, match="comp tile must be"):
        CompSpec(tile=(128, 128))
    # an explicit CompSpec replaces the whole compute half (tile AND flow dtype)
    fn = compile_overlap("ag_matmul", ch.with_(comp=CompSpec(tile=(64, 64, 64))))
    assert fn.keywords["channel"].comp.tile == (64, 64, 64)
    assert fn.keywords["channel"].comp.accum_dtype == "float32"
    # replacing the tile alone keeps the channel's flow dtype
    bf16 = BlockChannel(axis="model", comp=CompSpec(accum_dtype="bfloat16"))
    fn2 = compile_overlap("matmul_rs", _with_tile(bf16, (64, 64, 64)))
    assert fn2.keywords["channel"].comp.tile == (64, 64, 64)
    assert fn2.keywords["channel"].comp.accum_dtype == "bfloat16"


def test_auto_channel_with_pinned_comp_honors_tile(mesh4):
    # a space with one pinned tile: the comm half is searched, the tile is
    # pinned — the resolved lowering must actually carry the (clamped)
    # explicit tile, not the backend-chosen sentinel
    m_loc, k, n = 16, 32, 32
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    specs = dict(in_specs=(P("model", None), P(None, None)), out_specs=P(None, None))
    pinned = tune.Space(comp_tiles=((8, 16, 16),))  # tile pinned, rest swept
    fn = jax.jit(shard_map(_resolving("ag_matmul", space=pinned), mesh4, **specs))
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w), atol=2e-4, rtol=2e-3)
    res = tune.autotune("ag_matmul", signature=(1, m_loc, k, n), world=R, space=pinned)
    assert res.cache_hit  # the traced call resolved exactly this pinned space
    assert res.candidate.comp_tile == (8, 16, 16)
    assert res.channel.comp.tile == (8, 16, 16)


def test_auto_resolves_without_mesh_inside_shard_map(mesh4):
    # no mesh: world comes from axis_size inside the manual region and the
    # fingerprint narrows to the collective axis
    _, m_loc, k, n = SIGS["ag_matmul"]
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    sm = shard_map(
        _resolving("ag_matmul"),
        mesh4,
        in_specs=(P("model", None), P(None, None)),
        out_specs=P(None, None),
    )
    fn = jax.jit(sm)
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w), atol=2e-4, rtol=2e-3)
    assert tune.autotune("ag_matmul", signature=SIGS["ag_matmul"], world=R).cache_hit


def test_parallel_context_tune_resolves(mesh4):
    from repro.parallel.context import ParallelContext

    pc = dataclasses.replace(ParallelContext(mesh=mesh4, axis="model", dp_axes=()), tune=True)
    _, m_loc, k, n = SIGS["ag_matmul"]
    x = jax.random.normal(KEY, (R * m_loc, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    sm = pc.smap(lambda a, b: pc.ag_matmul(a, b), (P("model", None), P(None, None)), P(None, None))
    fn = jax.jit(sm)
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w), atol=2e-4, rtol=2e-3)
    # the resolution landed in the persistent cache
    assert os.path.isdir(tune_cache.cache_dir())
    assert len(os.listdir(tune_cache.cache_dir())) == 1


def _pc_case(op, mesh4):
    """(pc_call(pc, *args), explicit(*args), args, in_specs, out_specs) for
    one ParallelContext op: ``explicit`` lowers the channel(s) the matching
    ``tune.resolve_*`` returns for the call's per-shard shapes."""
    space = tune.JOINT_SPACE
    r = jax.random.PRNGKey
    if op in ("ag_matmul", "matmul_rs"):
        _, a, b, c = SIGS[op]
        if op == "ag_matmul":
            args = (jax.random.normal(KEY, (R * a, b)), jax.random.normal(r(1), (b, c)))
            specs = (P("model", None), P(None, None)), P(None, None)
            shard = [(a, b), (b, c)]
        else:
            args = (jax.random.normal(KEY, (a, R * b)), jax.random.normal(r(2), (R * b, c)))
            specs = (P(None, "model"), P("model", None)), P("model", None)
            shard = [(a, b), (b, c)]
        ch = tune.resolve_channel(op, shapes=shard, mesh=mesh4, space=space)
        return (lambda pc, *xs: getattr(pc, op)(*xs),
                compile_overlap(op, ch), args) + specs
    if op == "ag_attention":
        b, h, hkv, s_loc, d = SIGS[op]
        q = jax.random.normal(KEY, (b, h, R * s_loc, d))
        kv = jax.random.normal(r(3), (b, hkv, R * s_loc, d))
        shard = [(b, h, s_loc, d), (b, hkv, s_loc, d), (b, hkv, s_loc, d)]
        ch = tune.resolve_channel(op, shapes=shard, mesh=mesh4, space=space)
        spec = P(None, None, "model")
        return (lambda pc, *xs: pc.ring_attention(*xs, causal=True),
                compile_overlap(op, ch, causal=True), (q, kv, kv), (spec,) * 3, spec)
    if op in ("ag_moe", "a2a"):
        m_loc, dm, top_k, e_loc, f = SIGS["ag_moe"]
        e = e_loc * R
        args = (jax.random.normal(KEY, (R * m_loc, dm)) * 0.5,
                jax.random.normal(r(5), (e, dm, 2 * f)) * 0.1,
                jax.random.normal(r(6), (e, f, dm)) * 0.1)
        wr = jax.random.normal(r(4), (dm, e))
        shard = [(m_loc, dm), (m_loc, top_k), (m_loc, top_k), (e_loc, dm, 2 * f),
                 (e_loc, f, dm)]
        if op == "ag_moe":
            ch = tune.resolve_channel(op, shapes=shard, mesh=mesh4, space=space)
            g = compile_overlap(op, ch, capacity_factor=8.0)

            def call(pc, xs, ids, wts, wgu, wdn):
                return pc.ag_moe(xs, ids, wts, wgu, wdn, capacity_factor=8.0)
        else:
            fused, ch_d, ch_c = tune.resolve_a2a(shapes=shard, mesh=mesh4, space=space,
                                                 capacity_factor=8.0)
            g = compile_overlap([("a2a_dispatch", ch_d), ("combine_rs", ch_c)],
                                overlapped=fused, capacity_factor=8.0)

            def call(pc, xs, ids, wts, wgu, wdn):
                return pc.a2a_moe(xs, ids, wts, wgu, wdn, capacity_factor=8.0)

        def routed(fn):
            def f_(xs, wgu, wdn):
                ids, wts, _ = moe_router(xs, wr, num_experts=e, top_k=top_k)
                return fn(xs, ids, wts, wgu, wdn)
            return f_

        specs = (P("model", None), P("model", None, None), P("model", None, None))
        return (lambda pc, *xs: routed(lambda *a: call(pc, *a))(*xs), routed(g), args,
                specs, P("model", None))
    # the RS -> AG seam
    lead, m_glob, k_loc, n_mid, n2_loc = SEAM_SIG
    args = (jax.random.normal(KEY, (m_glob, R * k_loc)),
            jax.random.normal(r(1), (R * k_loc, n_mid)),
            jax.random.normal(r(2), (n_mid, R * n2_loc)))
    shard = [(m_glob, k_loc), (k_loc, n_mid), (n_mid, n2_loc)]
    fused, ch_rs, ch_ag = tune.resolve_seq(shapes=shard, mesh=mesh4, space=space)
    assert fused
    g = compile_overlap([("matmul_rs", ch_rs), ("ag_matmul", ch_ag)])
    specs = (P(None, "model"), P("model", None), P(None, "model"))
    return (lambda pc, *xs: pc.matmul_rs_ag(*xs), g, args, specs,
            (P("model", None), P(None, "model")))


@pytest.mark.parametrize("op", ["ag_matmul", "matmul_rs", "ag_attention", "ag_moe",
                                "seam", "a2a"])
def test_parallel_context_tuned_matches_explicit_channel(op, mesh4):
    """``ParallelContext(tune=True)`` lowers exactly the channel the
    matching ``tune.resolve_*`` returns for the call's shapes."""
    from repro.parallel.context import ParallelContext

    call, explicit, args, in_specs, out_specs = _pc_case(op, mesh4)
    pc = ParallelContext(mesh=mesh4, axis="model", dp_axes=(), tune=True,
                         ep_axis="model" if op == "a2a" else None)
    tuned = jax.jit(pc.smap(lambda *xs: call(pc, *xs), in_specs, out_specs))
    want = jax.jit(shard_map(explicit, mesh4, in_specs=in_specs, out_specs=out_specs))
    for got, ref in zip(jax.tree_util.tree_leaves(tuned(*args)),
                        jax.tree_util.tree_leaves(want(*args))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("removed", [
    {"channel": "auto"},
    {"comp": "auto"},
    {"quant": "auto"},
    {"axis": "model"},
    {"mesh": None},
    {"tune_ranker": "model"},
    {"tune_base": None},
    {"tune_space": None},
])
def test_removed_compile_overlap_forms_name_replacement(removed):
    """``compile_overlap`` lowers explicit channels only; each removed
    tuning form fails at build time and says what replaces it."""
    kw = dict(removed)
    channel = kw.pop("channel", BlockChannel(axis="model"))
    want = {"channel": "ParallelContext", "comp": r"channel\.with_\(comp=",
            "quant": r"channel\.with_\(quant=", "axis": r"BlockChannel\(axis="}
    match = want.get(next(iter(removed)), r"ParallelContext\(tune=True\).*resolve_seq")
    for kind in ("ag_matmul", ["matmul_rs", "ag_matmul"], ["a2a_dispatch", "combine_rs"]):
        with pytest.raises((TypeError, ValueError), match=match):
            compile_overlap(kind, channel, **kw)


# ---- the autotuner's contract per kind, on each kind's own shapes -----------

# one small per-shard signature per kind (see repro.tune.signature)
SMOKE_SIGS = {
    "ag_matmul": (1, 32, 32, 32),  # (lead, m_loc, k, n_loc)
    "matmul_rs": (1, 64, 16, 32),  # (lead, m_glob, k_loc, n)
    "ag_attention": (1, 2, 1, 32, 16),  # (b, h, hkv, s_loc, d)
    "ag_moe": (32, 16, 2, 2, 16),  # (m_loc, d_model, top_k, e_loc, f)
}
# joint-space shapes: large enough that each kind's tile lattice survives
# divisor/alignment pruning
JOINT_SIGS = {
    "ag_matmul": (1, 256, 512, 256),
    "matmul_rs": (1, 1024, 128, 512),
    "ag_attention": (1, 2, 1, 64, 32),
    "ag_moe": (32, 16, 2, 2, 16),
}


def _assert_winner_parity(kind, res, mesh4):
    """The tuned channel, lowered explicitly, matches the default channel."""
    build, args = tune_measure.build_case(kind, mesh4, "model", res.signature)
    got = np.asarray(build(res.channel)(*args), np.float32)
    ref = np.asarray(build(BlockChannel(axis="model"))(*args), np.float32)
    tol = 1e-3 if res.candidate.accum_dtype == "float32" else 1e-1
    assert np.max(np.abs(got - ref)) < tol * max(1.0, float(np.max(np.abs(ref))))


@pytest.mark.parametrize("kind", tune.TUNABLE_KINDS)
def test_tuned_channel_cache_round_trip_and_parity(kind, mesh4):
    kw = dict(signature=SMOKE_SIGS[kind], mesh=mesh4, ranker="model")
    first = tune.autotune(kind, **kw)
    memo = tune.autotune(kind, **kw)
    tune_cache.clear_memo()  # force the disk read
    disk = tune.autotune(kind, **kw)
    assert not first.cache_hit
    assert memo.cache_hit and disk.cache_hit
    assert memo.candidate == first.candidate == disk.candidate
    _assert_winner_parity(kind, first, mesh4)


@pytest.mark.parametrize("kind", tune.TUNABLE_KINDS)
def test_joint_winner_parity_every_kind(kind, mesh4):
    res = tune.autotune(kind, signature=JOINT_SIGS[kind], mesh=mesh4, ranker="model",
                        space=tune.JOINT_SPACE)
    assert res.considered > 18  # every kind has a compute-tile axis
    _assert_winner_parity(kind, res, mesh4)


@pytest.mark.parametrize("kind", tune.TUNABLE_KINDS)
def test_measured_sweep_prunes_every_kind(kind):
    sig = JOINT_SIGS[kind]
    cands = tune.enumerate_candidates(
        kind, extent=tune.chunk_extent(kind, sig), space=tune.JOINT_SPACE, sig=sig, world=R
    )
    timer = _oracle(kind, sig, R)
    sw = tune_sweep.measured_sweep(kind, sig, R, cands, timer)
    ex = tune_sweep.measured_sweep(
        kind, sig, R, cands, timer, config=tune_sweep.SweepConfig(enabled=False)
    )
    assert sw.winner == ex.winner
    assert 2 * sw.stats["screened"] <= len(cands)  # timed <= 50% of the space
    assert 2 * sw.stats["pruned"] >= len(cands)  # pruned >= 50% unmeasured


def test_bad_inputs():
    with pytest.raises(ValueError, match="not tunable"):
        tune.enumerate_candidates("nope")
    with pytest.raises(ValueError, match="mesh or an explicit world"):
        tune.autotune("ag_matmul", signature=(1, 8, 8, 8))
    with pytest.raises(ValueError, match="explicit BlockChannel"):
        compile_overlap("ag_matmul", "fastest")
    with pytest.raises(ValueError, match="unknown ranker"):
        tune.autotune("ag_matmul", signature=(1, 8, 8, 8), world=4, ranker="vibes")


# ---------------------------------------------------------------------------
# seam-aware resolution (fused RS -> AG, PR 7)

SEAM_SIG = (1, R * 16, 16, 32, 8)  # (lead, m_glob, k_loc, n_mid, n2_loc)


def test_seq_candidates_share_one_effective_channel_count():
    cands = tune.enumerate_seq_candidates(sig=SEAM_SIG, world=R)
    assert cands
    m_loc = SEAM_SIG[1] // R
    for c in cands:
        # the seam handoff is per-channel: both halves' chunked extents must
        # clamp to the candidate's count, or the pair degrades to unfused
        assert effective_channels(SEAM_SIG[3], c.num_channels) == c.num_channels
        assert effective_channels(m_loc, c.num_channels) == c.num_channels


def test_predict_seq_cost_credits_strictly_positive_saving():
    from repro.tune import cost as tune_cost

    for cand in tune.enumerate_seq_candidates(sig=SEAM_SIG, world=R):
        saving = tune_cost.seam_saving(SEAM_SIG, R, cand)
        assert saving > 0.0
        fused = tune_cost.predict_seq_cost(SEAM_SIG, R, cand, fused=True)
        unfused = tune_cost.predict_seq_cost(SEAM_SIG, R, cand, fused=False)
        assert fused == pytest.approx(unfused - saving)


def test_resolve_seq_verdicts_fused_with_shared_channels():
    fused, ch_rs, ch_ag = tune.resolve_seq(sig=SEAM_SIG, world=R)
    assert fused
    assert ch_rs.num_channels == ch_ag.num_channels
    assert ch_rs.comm.order == ch_ag.comm.order


# dense FFN seam signatures (lead, m_glob, k_loc, n_mid, n2_loc) at world 4:
# one block's down-proj GEMM+RS feeding the next block's AG+GEMM
DENSE_SEAMS = {
    "small": (1, 64, 32, 64, 32),
    "mlp-1k": (1, 1024, 256, 1024, 512),
    "mlp-4k": (1, 4096, 1024, 4096, 2048),
}


@pytest.mark.parametrize("name", sorted(DENSE_SEAMS))
def test_fused_seam_beats_unfused_pair_modeled(name):
    """On every dense FFN seam shape the best fused candidate beats the best
    unfused pair on the modeled scale, and resolve_seq verdicts fused with
    one shared channel."""
    from repro.tune import cost as tune_cost

    sig = DENSE_SEAMS[name]
    cands = tune.enumerate_seq_candidates(sig=sig, world=R)
    assert cands

    def best(fused):
        return min(tune_cost.predict_seq_cost(sig, R, c, fused=fused) for c in cands)

    assert best(True) < best(False)
    fused, ch_rs, ch_ag = tune.resolve_seq(sig=sig, world=R)
    assert fused and ch_rs == ch_ag

