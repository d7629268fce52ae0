"""Static-verifier tests: acceptance, seeded-mutation detection, lint, wiring.

The acceptance property mirrors the dynamic parity sweeps (tests/test_plan.py
runs every order x world x channels against jnp references on a live mesh):
the verifier must accept exactly that space — and flag every seeded schedule
bug the mutation suite plants in the baked tables / instruction streams.
"""
import dataclasses

import pytest

from repro import analysis
from repro.analysis import lint as repro_lint
from repro.analysis import verify as verify_cli
from repro.analysis.errors import PlanVerificationError
from repro.analysis.ir import PlanTables
from repro.analysis.protocol import DmaStart, Wait, build_streams, check_streams
from repro.analysis.schedule import check_channel_partition, check_schedule
from repro.core.channels import BlockChannel, CommSpec, ORDERS
from repro.core.plan import FLOW_OF_KIND, ChannelSchedule, build_plan
from repro.tune.candidates import enumerate_candidates

try:
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs requirements-dev.txt
    HAS_HYPOTHESIS = False


def _tables(kind="ag_matmul", order="ring", world=4, nch=2) -> PlanTables:
    ch = BlockChannel(axis="model", comm=CommSpec(order=order), num_channels=nch)
    return PlanTables.from_plan(build_plan(kind, ch, world, nch))


# ---- acceptance: the verifier accepts what the parity sweep accepts ---------


@pytest.mark.parametrize("kind", sorted(FLOW_OF_KIND))
@pytest.mark.parametrize("order", ORDERS)
def test_shipped_space_accepted(kind, order):
    for world in (2, 3, 4, 8):
        for nch in (1, 2, 3):
            ch = BlockChannel(axis="model", comm=CommSpec(order=order), num_channels=nch)
            report = analysis.verify_plan(build_plan(kind, ch, world, nch), protocol=True)
            assert report.passes == ("schedule", "protocol")
            assert report.effective_channels == nch
            assert report.checks > 0 and report.events > 0


def test_verify_cli_all_passes(capsys):
    assert verify_cli.main(["--all", "--quiet"]) == 0
    assert "0 failure(s)" in capsys.readouterr().out


def test_channel_partition():
    assert check_channel_partition(8, 2) > 0
    with pytest.raises(PlanVerificationError) as e:
        check_channel_partition(6, 4)
    assert e.value.check == "channel_partition"


# ---- seeded mutation suite: every planted bug must be flagged ---------------


def _expect(check_names, fn, *args):
    with pytest.raises(PlanVerificationError) as e:
        fn(*args)
    assert e.value.check in check_names, e.value
    return e.value


def test_mutation_off_by_one_step():
    t = _tables()
    rotated = tuple(ch[1:] + ch[:1] for ch in t.src)  # every step shifted by one
    _expect({"seed_identity", "per_step_permutation"}, check_schedule,
            dataclasses.replace(t, src=rotated))


def test_mutation_swapped_perm_pair():
    t = _tables()
    row = list(t.flow_dst[0][1])
    row[0], row[1] = row[1], row[0]
    bad = [[list(r) for r in ch] for ch in t.flow_dst]
    bad[0][1] = row
    t2 = dataclasses.replace(t, flow_dst=tuple(tuple(tuple(r) for r in ch) for ch in bad))
    _expect({"flow_composition"}, check_schedule, t2)


def test_mutation_nonpermutation_src_row():
    t = _tables()
    dup = t.src[0][1][1]  # duplicate a neighbor's entry within one step row
    _expect({"per_step_permutation"}, check_schedule, t.poke("src", 0, 1, 0, dup))


def test_mutation_rs_segment_poked():
    t = _tables(kind="matmul_rs")
    wrong = (t.rs_seg[0][1][0] + 1) % t.world
    _expect({"rs_time_reversal", "rs_home"}, check_schedule, t.poke("rs_seg", 0, 1, 0, wrong))


def test_mutation_align_poked():
    t = _tables(kind="ag_moe")
    wrong = (t.align[0][0] + 1) % t.world
    _expect({"align_home"}, check_schedule, t.poke_align(0, 0, wrong))


def test_mutation_dropped_signal_deadlocks():
    t = _tables()
    streams = build_streams(t)
    streams[0] = [op for op in streams[0] if not isinstance(op, DmaStart)][:]
    # rank 0 never pushes: its consumers starve (counts catch it first)
    _expect({"sem_count", "deadlock"}, check_streams, streams, t)


def test_mutation_wait_after_read_races():
    t = _tables()
    streams = build_streams(t)
    ops = streams[0]
    idx = next(i for i, op in enumerate(ops) if isinstance(op, Wait) and op.sem[0] == "recv")
    # acquire moved past the gathered-tile loads it guards
    streams[0] = ops[:idx] + ops[idx + 1 :] + [ops[idx]]
    _expect({"read_before_signal"}, check_streams, streams, t)


def test_mutation_reused_recv_slot():
    t = _tables()
    streams = build_streams(t)
    ops = streams[0]
    idx = next(i for i, op in enumerate(ops) if isinstance(op, DmaStart))
    other = (ops[idx].dst[1] + 1) % (t.world * t.num_channels)
    streams[0] = (
        ops[:idx]
        + [dataclasses.replace(ops[idx], dst=("gather", other))]
        + ops[idx + 1 :]
    )
    _expect(
        {"double_write", "read_before_signal", "overwritten_before_wait"},
        check_streams, streams, t,
    )


def test_mutation_held_pushes_deadlock():
    t = _tables(order="ring", world=4, nch=1)
    streams = build_streams(t)
    for r, ops in streams.items():
        di = next(i for i, op in enumerate(ops) if isinstance(op, DmaStart))
        wi = next(
            i for i, op in enumerate(ops) if isinstance(op, Wait) and op.sem[0] == "recv"
        )
        dma = ops[di]
        # every rank holds its step-0 push until after its step-0 acquire:
        # a signal/wait cycle around the ring — counts still match
        streams[r] = ops[:di] + ops[di + 1 : wi + 1] + [dma] + ops[wi + 1 :]
    err = _expect({"deadlock"}, check_streams, streams, t)
    assert err.rank is not None


# ---- the documented latent bug: shared send semaphore across channels -------


def test_shared_rs_send_sem_war_race():
    """Pre-fix gemm_rs shared one send semaphore across channels: the
    wait_send credits are interchangeable, so channel c's stage-s push may
    still be reading its accumulator columns when stage s+1 overwrites them.
    Safe at C == 1; a WAR race at C >= 2 (why kernels/gemm_rs.py now uses
    per-channel send semaphores)."""
    for order in ORDERS:
        safe = _tables(kind="matmul_rs", order=order, world=4, nch=1)
        check_streams(build_streams(safe, shared_rs_send_sem=True), safe)  # C=1 ok
        t = _tables(kind="matmul_rs", order=order, world=4, nch=2)
        check_streams(build_streams(t), t)  # per-channel sems: race-free
        err = _expect(
            {"overwritten_before_wait"},
            check_streams, build_streams(t, shared_rs_send_sem=True), t,
        )
        assert err.check == "overwritten_before_wait"


# ---- structured errors + executor/tuner wiring ------------------------------


def test_error_carries_coordinates():
    t = _tables(order="bidir_ring", world=4, nch=2)
    err = _expect({"per_step_permutation"}, check_schedule,
                  t.poke("src", 1, 2, 3, t.src[1][2][0]))
    assert isinstance(err, ValueError)
    assert (err.kind, err.order, err.world) == ("ag_matmul", "bidir_ring", 4)
    assert err.channel == 1 and err.step == 2 and err.rank is not None
    assert "per_step_permutation" in str(err)


def test_flow_perm_raises_structured_error():
    class Broken(ChannelSchedule):
        def source(self, rank, step):
            return 0 if step else rank  # constant after step 0: not a perm

    with pytest.raises(PlanVerificationError) as e:
        Broken(order="ring", world=4).flow_perm(0)
    assert e.value.check == "per_step_permutation"
    assert e.value.world == 4 and e.value.step == 1


def test_build_plan_verifies_unless_opted_out(monkeypatch):
    calls = []

    def boom(plan, **kw):
        calls.append(plan)
        raise PlanVerificationError("planted", check="planted")

    monkeypatch.setattr(analysis, "verify_plan", boom)
    build_plan.cache_clear()
    try:
        ch = BlockChannel(axis="model")
        monkeypatch.setenv("REPRO_VERIFY", "0")
        assert build_plan("ag_matmul", ch, 4, 1).world == 4  # escape hatch
        assert not calls
        build_plan.cache_clear()
        monkeypatch.setenv("REPRO_VERIFY", "1")
        with pytest.raises(PlanVerificationError):
            build_plan("ag_matmul", ch, 4, 1)
        assert calls
    finally:
        build_plan.cache_clear()


def test_build_plan_cache_is_bounded():
    assert build_plan.cache_info().maxsize is not None


def test_candidate_filter_keeps_legal_space():
    with_world = enumerate_candidates("ag_matmul", extent=8, world=4)
    without = enumerate_candidates("ag_matmul", extent=8)
    assert with_world == without  # the shipped space is fully legal
    assert analysis.check_candidate("ag_matmul", "ring", 4, 2) is None


def test_report_records_effective_channels():
    ch = BlockChannel(axis="model", num_channels=4)
    plan = build_plan("ag_matmul", ch, 4, 3)  # extent 6 clamps 4 -> 3
    report = analysis.verify_plan(plan, requested_channels=4)
    assert report.effective_channels == plan.num_channels == 3
    assert report.requested_channels == 4 and report.clamped
    assert "requested 4" in report.summary()


# ---- lint pass --------------------------------------------------------------


def test_lint_repo_is_clean():
    assert repro_lint.lint_tree() == []


def test_lint_flags_ppermute_outside_overlap():
    bad = repro_lint.lint_source("y = lax.ppermute(x, 'i', perm)\n", "nn/layers.py")
    assert [v.rule for v in bad] == ["ppermute-site"]
    ok = repro_lint.lint_source("y = lax.ppermute(x, 'i', perm)\n", "core/overlap.py")
    assert ok == []


def test_lint_flags_semaphores_outside_kernels():
    bad = repro_lint.lint_source("backend.semaphore_wait(s, 1)\n", "core/overlap.py")
    assert [v.rule for v in bad] == ["semaphore-site"]
    assert repro_lint.lint_source("backend.dma_semaphore()\n", "kernels/new.py") == []
    assert repro_lint.lint_source("pltpu.semaphore_signal(s)\n", "backend/lowering.py") == []


@pytest.mark.parametrize("relpath,allowed", [
    ("core/compiler.py", False),
    ("kernels/ag_gemm.py", False),
    ("nn/ffn.py", False),
    ("models/lm.py", False),
    ("serving/engine.py", False),
    ("parallel/context.py", True),
    ("tune/cost.py", True),
])
def test_lint_tune_import_only_above_core(relpath, allowed):
    """repro.tune is imported only by parallel/context.py and tune/ — in any
    import form, at module level or inside a function."""
    forms = (
        "from repro import tune\n",
        "import repro.tune\n",
        "from repro.tune import resolve_channel\n",
        "from repro.tune.cost import predict_cost\n",
        "def f():\n    from repro.tune import resolve_seq\n",
    )
    for src in forms:
        rules = [v.rule for v in repro_lint.lint_source(src, relpath)]
        assert rules == ([] if allowed else ["tune-import"]), (src, relpath)
    # near misses are not the tuner
    assert repro_lint.lint_source("from repro import tuned\nimport repro.tuner\n",
                                  relpath) == []


def test_lint_flags_raw_pallas_call():
    bad = repro_lint.lint_source("pl.pallas_call(k, grid=(1,))\n", "kernels/new.py")
    assert [v.rule for v in bad] == ["raw-pallas-call"]
    assert repro_lint.lint_source("backend.pallas_call(k)\n", "kernels/new.py") == []
    assert repro_lint.lint_source("pl.pallas_call(k)\n", "backend/target.py") == []


# ---- hypothesis properties (CI; local runs skip without the package) --------

if HAS_HYPOTHESIS:
    SET = settings(max_examples=60, deadline=None)

    plan_points = st.tuples(
        st.sampled_from(sorted(FLOW_OF_KIND)),
        st.sampled_from(ORDERS),
        st.integers(2, 9),
        st.integers(1, 4),
    )

    @SET
    @given(point=plan_points)
    def test_property_space_accepted(point):
        kind, order, world, nch = point
        ch = BlockChannel(axis="model", comm=CommSpec(order=order), num_channels=nch)
        report = analysis.verify_plan(build_plan(kind, ch, world, nch), protocol=True)
        assert report.checks > 0

    @SET
    @given(
        point=plan_points,
        coord=st.tuples(st.integers(0, 99), st.integers(0, 99), st.integers(0, 99)),
        delta=st.integers(1, 8),
    )
    def test_property_single_entry_mutations_rejected(point, coord, delta):
        kind, order, world, nch = point
        ch = BlockChannel(axis="model", comm=CommSpec(order=order), num_channels=nch)
        t = PlanTables.from_plan(build_plan(kind, ch, world, nch))
        c, s, r = coord[0] % nch, coord[1] % world, coord[2] % world
        old = t.src[c][s][r]
        mutated = t.poke("src", c, s, r, (old + delta % (world - 1) + 1) % world)
        with pytest.raises(PlanVerificationError):
            check_schedule(mutated)
